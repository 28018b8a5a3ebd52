"""Checkpoint — directory-backed, with first-class JAX pytree support.

Reference: AIR ``Checkpoint`` (``air/checkpoint.py:67``) morphs between
dict/directory/URI. Here a checkpoint IS a directory (what the storage
layer and orbax want); dict convenience wraps it. JAX pytrees go through
**orbax** (async-capable, sharding-aware — the TPU-native answer to the
reference's torch.save path in ``train/torch/``).
"""

from __future__ import annotations

import json
import os
import pickle
import shutil
import tempfile
from typing import Any, Dict, Optional

from .._private import telemetry
from ..util import tracing

M_SAVE_SECONDS = telemetry.define(
    "histogram", "rtpu_checkpoint_save_seconds",
    "Seconds inside Checkpoint.from_pytree (device-to-host copy and the "
    "orbax write)",
    buckets=telemetry.LONG_BUCKETS)
M_SAVE_BYTES = telemetry.define(
    "counter", "rtpu_checkpoint_save_bytes_total",
    "Bytes of pytree leaves handed to Checkpoint.from_pytree (the sum "
    "of the leaves' nbytes)")

_METRICS_FILE = ".rtpu_metrics.json"
_DICT_FILE = "data.pkl"
_PYTREE_DIR = "pytree"


class Checkpoint:
    def __init__(self, path: str):
        self.path = os.path.abspath(path)

    # ------------------------------------------------------------- creation
    @classmethod
    def from_directory(cls, path: str) -> "Checkpoint":
        return cls(path)

    @classmethod
    def from_dict(cls, data: Dict[str, Any],
                  dir: Optional[str] = None) -> "Checkpoint":
        path = dir or tempfile.mkdtemp(prefix="rtpu_ckpt_")
        os.makedirs(path, exist_ok=True)
        with open(os.path.join(path, _DICT_FILE), "wb") as f:
            pickle.dump(data, f)
        return cls(path)

    @classmethod
    def from_pytree(cls, tree: Any, dir: Optional[str] = None,
                    extra: Optional[Dict[str, Any]] = None) -> "Checkpoint":
        """Save a JAX pytree (params / TrainState) via orbax."""
        import jax
        with tracing.timed_span("checkpoint::save", M_SAVE_SECONDS):
            path = dir or tempfile.mkdtemp(prefix="rtpu_ckpt_")
            os.makedirs(path, exist_ok=True)
            import orbax.checkpoint as ocp
            ckptr = ocp.PyTreeCheckpointer()
            target = os.path.join(path, _PYTREE_DIR)
            with tracing.start_span("checkpoint::clear_target"):
                if os.path.exists(target):
                    shutil.rmtree(target)
            with tracing.start_span("checkpoint::orbax_save"):
                ckptr.save(target, tree)
            if extra:
                with open(os.path.join(path, _DICT_FILE), "wb") as f:
                    pickle.dump(extra, f)
        telemetry.counter_inc(M_SAVE_BYTES, float(sum(
            getattr(leaf, "nbytes", 0)
            for leaf in jax.tree_util.tree_leaves(tree))))
        return cls(path)

    # ------------------------------------------------------------- reading
    def to_directory(self, path: Optional[str] = None) -> str:
        if path is None or os.path.abspath(path) == self.path:
            return self.path
        if os.path.exists(path):
            shutil.rmtree(path)
        shutil.copytree(self.path, path)
        return path

    def to_dict(self) -> Dict[str, Any]:
        with open(os.path.join(self.path, _DICT_FILE), "rb") as f:
            return pickle.load(f)

    def to_pytree(self, template: Any = None) -> Any:
        """Restore a pytree; pass abstract arrays / shardings as
        ``template`` to restore sharded on-device (orbax restore_args)."""
        import orbax.checkpoint as ocp
        with tracing.start_span("checkpoint::restore"):
            ckptr = ocp.PyTreeCheckpointer()
            target = os.path.join(self.path, _PYTREE_DIR)
            if template is None:
                return ckptr.restore(target)
            return ckptr.restore(target, item=template)

    # ------------------------------------------------------------ metadata
    def set_metrics(self, metrics: Dict[str, Any]) -> None:
        with open(os.path.join(self.path, _METRICS_FILE), "w") as f:
            json.dump(metrics, f, default=str)

    def get_metrics(self) -> Dict[str, Any]:
        p = os.path.join(self.path, _METRICS_FILE)
        if not os.path.exists(p):
            return {}
        with open(p) as f:
            return json.load(f)

    def __repr__(self):
        return f"Checkpoint({self.path})"
