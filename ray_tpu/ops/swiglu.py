"""The SwiGLU product of rows whose up and gate halves lie in one array,
times a weight a row, and its backward pass:

    out[r, :] = weights[r] * silu(up_gate[r, F:]) * up_gate[r, :F]

in float32, rounded once to the rows' dtype — or, for a model whose experts
gate by ReLU (`act="relu"`), relu in silu's place, in every form below: the
forward product, the `jnp` backward pass and the kernel, whose derivative of
the gate is then a step (0 at a gate of 0, as `jax.nn.relu`'s). One matmul
over weights joined
along their output width gives `up_gate` ([R, 2F]: up | gate), and its
transposes then want the product's cotangent as one [R, 2F] array too. The
forward product (`weighted_swiglu`) is `jnp`: the compiler fuses the two
half-width slices into the one pass that writes `out`. The backward pass
(`weighted_swiglu_bwd`) is where a kernel is needed: autodiff pads each
half's cotangent to the whole width and adds them, and a `concatenate` of the
two fares no better — on a TPU the compiler writes both halves out and joins
them in a pass of its own, 1.34 GB written where 0.67 would do at
[163,840, 2048] bf16 (read in the program compiled for a v5e: PERF.md, PR
56). The pass also writes the product again: whoever multiplies `out` by a
matrix needs it once more for that matrix's gradient, and a pass that holds
up, gate and the weights makes it for the price of writing it (0.55 ms at
those shapes, where keeping it costs 0.34 GB and making it alone 1.47 ms).
The two are not tied by a `custom_vjp` here: the caller's own rule spans
the product and the matmul it feeds (`models/moe.py::_weighted_down`).

Two forms of the backward pass, chosen by `impl` (`ops/_impl.py`; the kernel
at an F of whole 128-lane tiles):

* `moe_swiglu_bwd`, a Pallas kernel: a grid step takes `_ROWS` rows of
  `up_gate`, of the cotangent and of the weights, walks them `_LANES` lanes
  at a time so that a slice's float32 terms stay in vector registers, and
  writes d up | d gate into the two halves of one [rows, 2F] block, the
  product into a [rows, F] block, and the weights' gradient — the sum over F
  of cotangent x silu(gate) x up, which reads what the pass already holds —
  as a [rows, 1] block. Every row is read once and written once; the rows
  of a grid step made no difference from 128 to 2,048 on a v5e (the pass
  runs at 0.7 of the HBM peak).
* `jnp`, the halves concatenated: the reference the kernel is held to, and
  what other backends, other widths and a mesh run (a Mosaic call is not
  partitioned automatically).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._impl import resolve_impl

# A grid step's rows, and the lanes of a half that the kernel holds in
# float32 at a time. [256, 2F] and [256, F] bf16 blocks in and the same out,
# each double-buffered, are 6 MB of VMEM at F = 1,024 (512 rows do not fit
# the default scoped limit, and buy nothing where it is raised).
_ROWS = 256
_LANES = 256


_ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def gate_activation(act: str):
    """The function on an expert's gate projection, by the model's name for
    it (`GPTConfig.moe_activation`); any other name is refused."""
    if act not in _ACTIVATIONS:
        raise ValueError(f"moe_activation {act!r}: one of "
                         f"{sorted(_ACTIVATIONS)}")
    return _ACTIVATIONS[act]


def _halves(up_gate):
    f = up_gate.shape[-1] // 2
    return (up_gate[:, :f].astype(jnp.float32),
            up_gate[:, f:].astype(jnp.float32))


def _pulled(up, gate, g, w, act="silu"):
    """d up, d gate, the terms whose sum over F is d weight, and the
    product itself, from float32 slices of up, gate and the cotangent g and
    the weights w ([rows, 1]). One body for both activations: act(gate) is
    gate x s with s the sigmoid (silu) or the step (relu), whose derivative
    is s (1 + gate (1 - s)) or the step itself."""
    relu = gate_activation(act) is jax.nn.relu
    s = (gate > 0).astype(gate.dtype) if relu else jax.nn.sigmoid(gate)
    product = gate * s * up
    gw = g * w
    return (gw * gate * s,
            gw * up * (s if relu else s * (1.0 + gate * (1.0 - s))),
            g * product, product * w)


def weighted_swiglu_bwd_reference(up_gate, weights, g, act="silu"):
    """The backward pass in `jnp`: (d up_gate [R, 2F], d weights [R], the
    product itself [R, F], made again)."""
    d_up, d_gate, d_w, out = _pulled(
        *_halves(up_gate), g.astype(jnp.float32),
        weights.astype(jnp.float32)[:, None], act)
    return (jnp.concatenate([d_up, d_gate], -1).astype(up_gate.dtype),
            d_w.sum(-1).astype(weights.dtype), out.astype(up_gate.dtype))


def _bwd_kernel(ug_ref, g_ref, w_ref, d_ref, dw_ref, out_ref, *, lanes,
                act="silu"):
    """ug_ref, d_ref: [rows, 2F]; g_ref, out_ref: [rows, F]; w_ref, dw_ref:
    [rows, 1] float32."""
    f = g_ref.shape[1]
    w = w_ref[...]
    d_w = jnp.zeros((g_ref.shape[0], lanes), jnp.float32)
    for at in range(0, f, lanes):
        here, there = pl.ds(at, lanes), pl.ds(f + at, lanes)
        d_up, d_gate, terms, out = _pulled(
            ug_ref[:, here].astype(jnp.float32),
            ug_ref[:, there].astype(jnp.float32),
            g_ref[:, here].astype(jnp.float32), w, act)
        d_ref[:, here] = d_up.astype(d_ref.dtype)
        d_ref[:, there] = d_gate.astype(d_ref.dtype)
        out_ref[:, here] = out.astype(out_ref.dtype)
        d_w = d_w + terms
    dw_ref[...] = d_w.sum(-1, keepdims=True)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _bwd_pallas(up_gate, weights, g, interpret, act="silu"):
    r, f = g.shape
    rows = min(_ROWS, -(-r // 8) * 8)
    lanes = _LANES if f % _LANES == 0 else 128
    column = pl.BlockSpec((rows, 1), lambda i: (i, 0))
    whole = pl.BlockSpec((rows, 2 * f), lambda i: (i, 0))
    half = pl.BlockSpec((rows, f), lambda i: (i, 0))
    d, d_w, out = pl.pallas_call(
        functools.partial(_bwd_kernel, lanes=lanes, act=act),
        grid=(-(-r // rows),),
        in_specs=[whole, half, column],
        out_specs=[whole, column, half],
        out_shape=[jax.ShapeDtypeStruct(up_gate.shape, up_gate.dtype),
                   jax.ShapeDtypeStruct((r, 1), jnp.float32),
                   jax.ShapeDtypeStruct(g.shape, up_gate.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret, name="moe_swiglu_bwd",
    )(up_gate, g, weights.astype(jnp.float32)[:, None])
    return d, d_w[:, 0].astype(weights.dtype), out


def weighted_swiglu(up_gate: jax.Array, weights: jax.Array,
                    act: str = "silu") -> jax.Array:
    """up_gate: [R, 2F], up | gate; weights: [R]. Returns [R, F] in
    `up_gate`'s dtype: weights x act(gate) x up, computed in float32
    (module docstring). Differentiated by `weighted_swiglu_bwd`, which its
    caller's own rule calls."""
    up, gate = _halves(up_gate)
    return (gate_activation(act)(gate) * up
            * weights.astype(jnp.float32)[:, None]).astype(up_gate.dtype)


def weighted_swiglu_bwd(up_gate: jax.Array, weights: jax.Array,
                        g: jax.Array, *, impl: str = "auto",
                        act: str = "silu"):
    """`weighted_swiglu`'s backward pass for the cotangent g [R, F]:
    d up_gate [R, 2F], d weights [R], and the product itself, made again —
    so that a caller who needs it in its own backward pass (the gradient of
    the matmul it feeds) need not keep it from the forward one.

    impl: as `ops._impl.resolve_impl` takes it; the kernel takes an F of
    whole 128-lane tiles."""
    impl = resolve_impl(impl, "weighted SwiGLU", g.shape[-1])
    if impl == "reference":
        return weighted_swiglu_bwd_reference(up_gate, weights, g, act)
    return _bwd_pallas(up_gate, weights, g, impl == "pallas_interpret", act)
