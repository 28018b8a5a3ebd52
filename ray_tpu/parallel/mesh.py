"""Device-mesh construction with named parallelism axes.

The reference expresses parallelism as process groups created per strategy
(DDP ``train/torch/config.py:63``; NCCL groups
``util/collective/collective.py:120``). TPU-native design: one global
`jax.sharding.Mesh` whose named axes carry every strategy at once —

  ``dp``   data parallel (gradient psum)
  ``fsdp`` sharded data parallel (ZeRO: params/optimizer sharded, gathered
           per-layer; maps to the reference's FSDP/DeepSpeed passthrough,
           ``train/lightning/_lightning_utils.py:84,127``)
  ``tp``   tensor parallel (megatron-style column/row sharding)
  ``sp``   sequence/context parallel (ring attention — absent from the
           reference, first-class here per SURVEY §5)
  ``pp``   pipeline parallel (stage dimension)
  ``ep``   expert parallel (MoE)

Mesh axis *order* matters on TPU: the innermost (last) axes should map to
ICI-adjacent devices. We order axes (pp, dp, fsdp, ep, sp, tp) so that
tp/sp — the chatty collectives — land on contiguous device neighbourhoods.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

AXIS_PP = "pp"
AXIS_DP = "dp"
AXIS_FSDP = "fsdp"
AXIS_EP = "ep"
AXIS_SP = "sp"
AXIS_TP = "tp"

# Innermost-last ordering: tp gets the fastest ICI links.
AXIS_ORDER: Tuple[str, ...] = (AXIS_PP, AXIS_DP, AXIS_FSDP, AXIS_EP,
                               AXIS_SP, AXIS_TP)


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Declarative mesh shape. ``-1`` on at most one axis means "absorb the
    remaining devices" (like a reshape wildcard)."""

    dp: int = -1
    fsdp: int = 1
    tp: int = 1
    sp: int = 1
    pp: int = 1
    ep: int = 1

    def resolve(self, n_devices: int) -> "MeshSpec":
        sizes = {f.name: getattr(self, f.name)
                 for f in dataclasses.fields(self)}
        wild = [k for k, v in sizes.items() if v == -1]
        if len(wild) > 1:
            raise ValueError(f"at most one -1 axis allowed, got {wild}")
        fixed = math.prod(v for v in sizes.values() if v != -1)
        if wild:
            if n_devices % fixed:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes "
                    f"product {fixed}")
            sizes[wild[0]] = n_devices // fixed
        elif fixed != n_devices:
            raise ValueError(
                f"mesh spec {sizes} covers {fixed} devices, have "
                f"{n_devices}")
        return MeshSpec(**sizes)

    def axis_sizes(self) -> Tuple[int, ...]:
        return tuple(getattr(self, name) for name in AXIS_ORDER)

    @property
    def total(self) -> int:
        if any(s == -1 for s in self.axis_sizes()):
            raise ValueError(
                "MeshSpec has an unresolved -1 axis; call resolve(n) first")
        return math.prod(self.axis_sizes())


def mesh_shape_for(n_devices: int,
                   tp: int = 1,
                   sp: int = 1,
                   pp: int = 1,
                   ep: int = 1,
                   fsdp: int = 1) -> MeshSpec:
    """Convenience: everything not given goes to dp."""
    return MeshSpec(dp=-1, fsdp=fsdp, tp=tp, sp=sp, pp=pp,
                    ep=ep).resolve(n_devices)


def build_mesh(spec: Optional[MeshSpec] = None,
               devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """Build a `jax.sharding.Mesh` with the canonical axis names.

    TPU devices go through `jax.experimental.mesh_utils.create_device_mesh`
    so that physical ICI adjacency is respected, and an error from it
    surfaces; CPU / virtual devices have no topology and are reshaped.
    """
    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    spec = (spec or MeshSpec()).resolve(len(devices))
    shape = spec.axis_sizes()
    if devices[0].platform == "tpu":
        from jax.experimental import mesh_utils
        dev_array = mesh_utils.create_device_mesh(
            shape, devices=devices, allow_split_physical_axes=True)
    else:
        dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, AXIS_ORDER)
