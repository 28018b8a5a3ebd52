"""The Gated DeltaNet layer's two passes around the rule (`ops/delta_rule.py`).

Before the rule, over the projection's q~ k~ v~ columns, [B, S, 2 Hk Dk +
Hv Dv], q | k | v side by side:

    pre_t = sum_i conv_w[i] x_{t - taps + 1 + i}      zeros before the row
    u = SiLU(pre)
    q, k = u * rsqrt(sum_head u^2 + eps) * (Dk^-1/2, 1),   v = u

After it, over the rule's o and the projection's z, [B, S, Hv Dv]:

    y = o * rsqrt(mean_head o^2 + eps) * lin_norm * SiLU(z)

Both are memory-bound: the work is a few dozen vector operations an element
and each array should cross HBM once. Two forms of each, chosen by `impl`
(`ops/_impl.py`; the kernels at head widths of whole 128-lane tiles):

* Two Pallas kernel pairs, `gdn_conv_fwd` / `gdn_conv_bwd` and
  `gdn_norm_fwd` / `gdn_norm_bwd`, each a `jax.custom_vjp`. Every array is
  read and written as the projections and the rule's kernels hold it,
  [B, S, H D] with a head a block of lanes: nothing is padded, transposed
  or reshaped between the projection, the rule and the out-projection. A
  grid step is a batch row, `_ROWS` positions and `_LANES` lanes of whole
  heads; it converts its blocks to float32, computes in float32 and rounds
  once on the way out. The convolution runs once for each of q, k and v,
  reading its columns of the projection in place (a block map that starts
  at the part's first block of lanes) and writing the array the rule takes.
  Its history across a block's edge is the `_HALO` positions before the
  block, read again as a block of their own (zeros at the row's start), and
  shifted windows of a float32 scratch give the taps. The backward kernels
  keep nothing but the inputs (which "full" rematerialisation makes again
  anyway): the convolution's makes the pre-activation and the normalisation
  again for its block and the positions after it that its inputs reach,
  sums the anti-causal taps into dx and accumulates d conv_w in float32
  over the grid's rows and sequence blocks; the three calls write their
  columns of one d qkv array (the second and third take the first's output
  as an aliased operand), so the projection's backward pass gets it whole
  and nothing is concatenated. The gated norm's backward writes do and dz
  and accumulates d lin_norm the same way.
* `gdn_conv_reference`, `gdn_gated_norm_reference`: the `jnp` forms, the
  reference the kernels are held to and what other backends and other
  widths run. They compute in the inputs' dtype where the kernels compute
  in float32 (bf16 inputs: products and sums rounded at every tap, the
  norm's output rounded before the gate).

On a v5e (PERF.md, PR 35): the `jnp` forms ran at a tenth to a fifth of the
HBM peak as pads, shifted slices off the sublane tiling and relayouts to a
128-wide minor axis; the convolution's kernels run at 74% (forward) and 55%
(backward) of it, bound by their vector work (the windows' sublane rotates
and the lane sums), the gated norm's at 82%, bound by HBM.
"""

from __future__ import annotations

import functools
import math
import types

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._impl import resolve_impl

# A grid step: positions of the row and lanes of whole heads (a block's size
# is what amortises the step's fixed cost and its DMAs), and within them the
# rows one chain of vector work covers, written out for the scheduler to
# overlap. By the compiler's schedules for a v5e (PERF.md, PR 35): the parts
# that sum over a head's lanes (q, k, the gated norm) schedule as densely in
# chunks of 256 rows as of 32 and are an eighth of the operations to trace,
# which a job's start pays for (`setup_s`); v, which stores what it loads
# with little work between, spills less in chunks of 32.
_ROWS = 512
_LANES = 1024
_HEAD_CHUNK_ROWS = 256
_PLAIN_CHUNK_ROWS = 32
# The positions re-read across a block's edge: one tile of rows of a bf16
# array (a float32 array's is 8), which holds any convolution this short.
_HALO = 16


def _sigmoid(x):
    # by tanh: one transcendental and two vector operations, where
    # 1 / (1 + exp(-x)) is two transcendentals and a guarded division
    return 0.5 + 0.5 * jnp.tanh(0.5 * x)


def _silu_and_slope(x):
    """SiLU(x) and its derivative, sigma (1 + x (1 - sigma))."""
    sig = _sigmoid(x)
    return x * sig, sig * (1.0 + x * (1.0 - sig))


def _head_sums(x, d):
    """[rows, h d] -> each head's sum over its d lanes, on every lane of
    the head."""
    return jnp.concatenate(
        [jnp.broadcast_to(jnp.sum(x[:, j:j + d], axis=1, keepdims=True),
                          (x.shape[0], d))
         for j in range(0, x.shape[1], d)], axis=1)


# ---------------------------------------------------------------------------
# The `jnp` forms
# ---------------------------------------------------------------------------

def gdn_conv_reference(qkv, conv_w, *, key_heads, key_dim, eps):
    """`gdn_conv` in `jnp`, in qkv's dtype: one shifted product a tap."""
    dt, f32 = qkv.dtype, jnp.float32
    taps = conv_w.astype(dt)
    s = qkv.shape[1]
    padded = jnp.pad(qkv, ((0, 0), (taps.shape[0] - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(padded[:, i:i + s] * taps[i]
                          for i in range(taps.shape[0])))
    keys = key_heads * key_dim
    q, k, v = jnp.split(qkv, (keys, 2 * keys), axis=-1)

    def unit(y, scale):
        yf = y.astype(f32).reshape(*y.shape[:2], key_heads, key_dim)
        yf = yf * (lax.rsqrt(jnp.sum(yf * yf, -1, keepdims=True) + eps)
                   * scale)
        return yf.astype(dt).reshape(y.shape)

    return unit(q, key_dim ** -0.5), unit(k, 1.0), v


def gdn_gated_norm_reference(o, z, scale, *, eps):
    """`gdn_gated_norm` in `jnp`: the norm in float32, rounded to o's dtype
    before the gate."""
    f32 = jnp.float32
    of = o.astype(f32).reshape(*o.shape[:2], -1, scale.shape[0])
    of = of * lax.rsqrt(jnp.mean(of * of, -1, keepdims=True) + eps)
    normed = (of * scale.astype(f32)).astype(o.dtype).reshape(o.shape)
    return normed * jax.nn.silu(z)


# ---------------------------------------------------------------------------
# The Pallas kernels
# ---------------------------------------------------------------------------

def _chunks(rows, heads=True):
    """(start, size) of a block's chunks of rows; `heads`: the work sums
    over heads' lanes."""
    size = _HEAD_CHUNK_ROWS if heads else _PLAIN_CHUNK_ROWS
    return [(r, min(size, rows - r)) for r in range(0, rows, size)]


def _scoped(*scopes):
    """The scopes the trace files a pass under (`GPT._linear_mixer`'s), kept
    inside the `custom_vjp`'s rules as well."""
    def wrap(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with jax.named_scope(scopes[0]), jax.named_scope(scopes[1]):
                return fn(*args, **kwargs)
        return scoped
    return wrap


def _tiling(s, channels, d, first=0):
    """Rows and lanes a grid step, the rows' padding, and the first block
    of lanes of a part that starts at lane `first` of a wider array (None if
    that lane is no whole number of blocks: the part is then sliced out)."""
    rows = min(_ROWS, -(-s // _HALO) * _HALO)
    heads = channels // d
    lanes = math.gcd(max(1, _LANES // d), heads) * d
    return types.SimpleNamespace(
        rows=rows, pad=-s % rows, blocks=-(-s // rows), lanes=lanes,
        groups=channels // lanes,
        first=first // lanes if first % lanes == 0 else None)


def _pad_rows(x, pad):
    return jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x


def _row_specs(t, first=0, order=lambda b, i, g: (b, i, g)):
    """Block specs over [B, S, lanes ...] for the grid's (row, block, group)
    as `order` gives them from the grid's indices: the block, and the
    `_HALO` positions before and after it (the row's own first or last where
    there are none, which the kernels blank). `first`: the part's first
    block of lanes in the array."""
    per = t.rows // _HALO

    def spec(rows, at):
        def index(*ids):
            b, i, g = order(*ids)
            return b, at(i), first + g
        return pl.BlockSpec((None, rows, t.lanes), index)

    return types.SimpleNamespace(
        block=spec(t.rows, lambda i: i),
        before=spec(_HALO, lambda i: jnp.maximum(i * per - 1, 0)),
        after=spec(_HALO, lambda i: jnp.minimum((i + 1) * per,
                                                t.blocks * per - 1)))


def _accumulate(ref, value, start):
    """ref = value at the grid's first step of the sum, ref += value after."""
    @pl.when(start)
    def _first():
        ref[...] = value

    @pl.when(jnp.logical_not(start))
    def _rest():
        ref[...] += value


def _unit(u, d, scale, eps):
    """u * rsqrt(sum_head u^2 + eps) * scale, and the rsqrt."""
    r = lax.rsqrt(_head_sums(u * u, d) + eps)
    return u * (r * scale), r


def _taps_sum(ref, w, at, n):
    """sum_i w[i] * ref[at + i : at + i + n] for the taps' rows w, the
    window that starts on a tile of rows first: the sum takes its first
    term's place in the tiles and the other windows are shifted to it."""
    first = sorted(range(len(w)), key=lambda i: (at + i) % 8 != 0)
    return functools.reduce(
        lambda a, b: a + b, (ref[pl.ds(at + i, n), :] * w[i] for i in first))


def _tap_rows(w_ref):
    """conv_w's block as its taps' [1, lanes] float32 rows."""
    return [w_ref[i:i + 1, :].astype(jnp.float32)
            for i in range(w_ref.shape[0])]


def _conv_fwd_kernel(x_ref, before_ref, w_ref, out_ref, ext_ref, *, d, scale,
                     eps):
    """One block of rows and lanes of one of q, k, v. x: [rows, lanes], the
    `_HALO` rows before it, conv_w [taps, lanes]; scratch [_HALO + rows,
    lanes] float32. `scale` None: no normalisation (v)."""
    f32 = jnp.float32
    ext_ref[0:_HALO] = jnp.where(pl.program_id(1) == 0, 0.0,
                                 before_ref[...].astype(f32))
    ext_ref[_HALO:] = x_ref[...].astype(f32)
    w = _tap_rows(w_ref)
    for r, n in _chunks(x_ref.shape[0], scale is not None):
        pre = _taps_sum(ext_ref, w, _HALO - len(w) + 1 + r, n)
        u = pre * _sigmoid(pre)
        if scale is not None:
            u, _ = _unit(u, d, scale, eps)
        out_ref[pl.ds(r, n), :] = u.astype(out_ref.dtype)


def _conv_bwd_kernel(x_ref, before_ref, after_ref, w_ref, dout_ref,
                     dafter_ref, *rest, d, scale, eps):
    """The same block, the grid as (group, row, block). Beside the forward's
    operands the `_HALO` rows after x, dout and the `_HALO` rows after it
    (and, unread, the array the earlier parts wrote their dx into); dx
    [rows, lanes]; d conv_w [taps, lanes] float32, one block for all of the
    grid's rows and sequence blocks; scratch: the inputs [_HALO + rows +
    _HALO, lanes] and d pre [rows + _HALO, lanes], float32."""
    dx_ref, dw_ref, ext_ref, dpre_ref = rest[-4:]
    f32 = jnp.float32
    rows = x_ref.shape[0]
    first = pl.program_id(2) == 0
    last = pl.program_id(2) == pl.num_programs(2) - 1
    ext_ref[0:_HALO] = jnp.where(first, 0.0, before_ref[...].astype(f32))
    ext_ref[_HALO:_HALO + rows] = x_ref[...].astype(f32)
    ext_ref[_HALO + rows:] = jnp.where(last, 0.0, after_ref[...].astype(f32))
    w = _tap_rows(w_ref)
    taps = len(w)

    def d_pre(r, n, dout):
        """d pre of rows r .. r + n from the block's start."""
        pre = _taps_sum(ext_ref, w, _HALO - taps + 1 + r, n)
        u, slope = _silu_and_slope(pre)
        du = dout.astype(f32)
        if scale is not None:
            # out = scale u r with r = rsqrt(sum u^2 + eps):
            # du = scale r (dout - u r^2 sum(dout u))
            _, rs = _unit(u, d, scale, eps)
            du = (scale * rs) * (du - u * (rs * rs) * _head_sums(du * u, d))
        dpre_ref[pl.ds(r, n), :] = du * slope

    for r, n in _chunks(rows, scale is not None):
        d_pre(r, n, dout_ref[pl.ds(r, n), :])
    # the positions after the block that its inputs reach; nothing comes
    # back from beyond the row's end
    d_pre(rows, _HALO, jnp.where(last, 0.0, dafter_ref[...].astype(f32)))
    dw = [jnp.zeros((1, x_ref.shape[1]), f32)] * taps
    for r, n in _chunks(rows, scale is not None):
        # x_t enters pre_{t + j} through tap taps - 1 - j: d pre's windows
        # from t on, each rotated into place once, give both dx and
        # d conv_w (summed over the block's own x_t, which counts every
        # product once)
        ahead = dpre_ref[pl.ds(r, n + 8), :]
        x = ext_ref[pl.ds(_HALO + r, n), :]
        dx = None
        for j in range(taps):
            i = taps - 1 - j
            d = ahead[:n] if j == 0 else pltpu.roll(ahead, n + 8 - j, 0)[:n]
            dx = d * w[i] if dx is None else dx + d * w[i]
            dw[i] = dw[i] + jnp.sum(d * x, axis=0, keepdims=True)
        dx_ref[pl.ds(r, n), :] = dx.astype(dx_ref.dtype)
    _accumulate(dw_ref, jnp.concatenate(dw, axis=0),
                jnp.logical_and(pl.program_id(1) == 0, first))


_ANY_ORDER = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel"))
_SUMMING = pltpu.CompilerParams(
    dimension_semantics=("arbitrary", "arbitrary", "arbitrary"))


def _conv_parts(x, key_heads, key_dim, value_dim):
    """(first lane, lanes, head width, scale) of q, k and v in x."""
    keys = key_heads * key_dim
    values = x.shape[-1] - 2 * keys
    assert values > 0 and values % value_dim == 0, (x.shape, keys, value_dim)
    return ((0, keys, key_dim, key_dim ** -0.5), (keys, keys, key_dim, 1.0),
            (2 * keys, values, value_dim, None))


# Under `jax.jit`, as the rule's wrappers are: a model's layers, its loss
# and its evaluation trace the kernels' bodies once between them.
@functools.partial(jax.jit, static_argnames=(
    "key_heads", "key_dim", "value_dim", "eps", "interpret"))
def _conv_fwd_pallas(x, w, *, key_heads, key_dim, value_dim, eps, interpret):
    b, s, _ = x.shape
    outs = []
    for first, channels, d, scale in _conv_parts(x, key_heads, key_dim,
                                                 value_dim):
        t = _tiling(s, channels, d, first)
        part, w_part, at = x, w, t.first
        if at is None:      # not at a whole block of its lanes: sliced out
            part, w_part = (a[..., first:first + channels] for a in (x, w))
            at = 0
        part = _pad_rows(part, t.pad)
        rows = _row_specs(t, at)
        outs.append(pl.pallas_call(
            functools.partial(_conv_fwd_kernel, d=d, scale=scale, eps=eps),
            grid=(b, t.blocks, t.groups),
            in_specs=[rows.block, rows.before,
                      pl.BlockSpec((w.shape[0], t.lanes),
                                   lambda b, i, g: (0, at + g))],
            out_specs=_row_specs(t).block,
            out_shape=jax.ShapeDtypeStruct((b, s + t.pad, channels),
                                           x.dtype),
            scratch_shapes=[pltpu.VMEM((_HALO + t.rows, t.lanes),
                                       jnp.float32)],
            compiler_params=_ANY_ORDER, interpret=interpret,
            name="gdn_conv_fwd",
        )(part, part, w_part)[:, :s])
    return tuple(outs)


@functools.partial(jax.jit, static_argnames=(
    "key_heads", "key_dim", "value_dim", "eps", "interpret"))
def _conv_bwd_pallas(x, w, douts, *, key_heads, key_dim, value_dim, eps,
                     interpret):
    """d x (x's dtype) and d conv_w (float32) from dq, dk, dv."""
    b, s, _ = x.shape
    parts = _conv_parts(x, key_heads, key_dim, value_dim)
    tilings = [_tiling(s, channels, d, first)
               for first, channels, d, _ in parts]
    # every part's columns of one array, if each starts at a whole block of
    # its lanes and the rows are whole blocks; else part by part, joined
    whole = all(t.first is not None and not t.pad for t in tilings)
    dx, dxs, dws = None, [], []
    order = lambda g, b, i: (b, i, g)      # noqa: E731
    for (first, channels, d, scale), t, dout in zip(parts, tilings, douts):
        part, w_part = x, w
        if not whole:
            part, w_part = (a[..., first:first + channels] for a in (x, w))
        part, dout = _pad_rows(part, t.pad), _pad_rows(dout, t.pad)
        at = t.first if whole else 0
        rows, own = _row_specs(t, at, order), _row_specs(t, 0, order)
        operands = [part, part, part, w_part, dout, dout]
        in_specs = [rows.block, rows.before, rows.after,
                    pl.BlockSpec((w.shape[0], t.lanes),
                                 lambda g, b, i: (0, at + g)),
                    own.block, own.after]
        aliases = {}
        if dx is not None:
            operands.append(dx)
            in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
            aliases = {len(operands) - 1: 0}
        dx_part, dw = pl.pallas_call(
            functools.partial(_conv_bwd_kernel, d=d, scale=scale, eps=eps),
            grid=(t.groups, b, t.blocks),
            in_specs=in_specs,
            out_specs=[rows.block,
                       pl.BlockSpec((w.shape[0], t.lanes),
                                    lambda g, b, i: (0, g))],
            out_shape=[jax.ShapeDtypeStruct(part.shape, x.dtype),
                       jax.ShapeDtypeStruct((w.shape[0], channels),
                                            jnp.float32)],
            scratch_shapes=[
                pltpu.VMEM((_HALO + t.rows + _HALO, t.lanes), jnp.float32),
                pltpu.VMEM((t.rows + _HALO, t.lanes), jnp.float32)],
            input_output_aliases=aliases,
            compiler_params=_SUMMING, interpret=interpret,
            name="gdn_conv_bwd",
        )(*operands)
        dws.append(dw)
        if whole:
            dx = dx_part
        else:
            dxs.append(dx_part[:, :s])
    if not whole:
        dx = jnp.concatenate(dxs, axis=-1)
    return dx, jnp.concatenate(dws, axis=-1).astype(w.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _conv(x, w, static):
    return _conv_fwd_pallas(x, w, **dict(static))


@_scoped("attn_qkv", "gdn_conv")
def _conv_fwd(x, w, static):
    return _conv_fwd_pallas(x, w, **dict(static)), (x, w)


@_scoped("attn_qkv", "gdn_conv")
def _conv_bwd(static, residuals, douts):
    return _conv_bwd_pallas(*residuals, douts, **dict(static))


_conv.defvjp(_conv_fwd, _conv_bwd)


def _norm_fwd_kernel(o_ref, z_ref, w_ref, y_ref, *, eps):
    """One block of rows and whole heads. o, z, y: [rows, lanes]; lin_norm
    [1, d]."""
    f32 = jnp.float32
    d = w_ref.shape[1]
    w = jnp.tile(w_ref[...].astype(f32), (1, o_ref.shape[1] // d))
    for r, n in _chunks(o_ref.shape[0]):
        o, z = (ref[pl.ds(r, n), :].astype(f32) for ref in (o_ref, z_ref))
        rs = lax.rsqrt(_head_sums(o * o, d) * (1.0 / d) + eps)
        y_ref[pl.ds(r, n), :] = ((o * rs * w) * (z * _sigmoid(z))).astype(
            y_ref.dtype)


def _norm_bwd_kernel(o_ref, z_ref, w_ref, dy_ref, do_ref, dz_ref, dw_ref, *,
                     eps):
    """The same block. do, dz: [rows, lanes]; d lin_norm [1, d] float32, one
    block for the whole grid."""
    f32 = jnp.float32
    lanes, d = o_ref.shape[1], w_ref.shape[1]
    w = jnp.tile(w_ref[...].astype(f32), (1, lanes // d))
    dw = jnp.zeros((1, lanes), f32)
    for r, n in _chunks(o_ref.shape[0]):
        o, z, dy = (ref[pl.ds(r, n), :].astype(f32)
                    for ref in (o_ref, z_ref, dy_ref))
        rs = lax.rsqrt(_head_sums(o * o, d) * (1.0 / d) + eps)
        unit = o * rs
        gate, slope = _silu_and_slope(z)
        dz_ref[pl.ds(r, n), :] = (dy * (unit * w) * slope).astype(
            dz_ref.dtype)
        d_unit = dy * gate
        dw = dw + jnp.sum(d_unit * unit, axis=0, keepdims=True)
        d_unit = d_unit * w
        # unit = o r with r = rsqrt(mean o^2 + eps):
        # do = r (d_unit - unit mean(d_unit unit))
        do_ref[pl.ds(r, n), :] = (rs * (d_unit - unit * (
            _head_sums(d_unit * unit, d) * (1.0 / d)))).astype(do_ref.dtype)
    _accumulate(dw_ref, sum(dw[:, j:j + d] for j in range(0, lanes, d)),
                sum(pl.program_id(axis) for axis in range(3)) == 0)


def _norm_call(arrays, w, eps, interpret):
    """The gated norm's forward kernel over blocks of o and z, or with dy as
    well its backward kernel: y, or do, dz and d lin_norm [1, d]."""
    backward = len(arrays) == 3
    b, s, channels = arrays[0].shape
    t = _tiling(s, channels, w.shape[0])
    rows = _row_specs(t).block
    scale = pl.BlockSpec((1, w.shape[0]), lambda b, i, g: (0, 0))
    shape = jax.ShapeDtypeStruct((b, s + t.pad, channels), arrays[0].dtype)
    out_specs, out_shape = [rows], [shape]
    if backward:
        out_specs, out_shape = [rows, rows, scale], [
            shape, shape, jax.ShapeDtypeStruct((1, w.shape[0]), jnp.float32)]
    o, z, *dy = (_pad_rows(a, t.pad) for a in arrays)
    out = pl.pallas_call(
        functools.partial(_norm_bwd_kernel if backward else _norm_fwd_kernel,
                          eps=eps),
        grid=(b, t.blocks, t.groups),
        in_specs=[rows, rows, scale] + [rows] * len(dy),
        out_specs=out_specs, out_shape=out_shape,
        compiler_params=_SUMMING if backward else _ANY_ORDER,
        interpret=interpret,
        name="gdn_norm_bwd" if backward else "gdn_norm_fwd",
    )(o, z, w.reshape(1, -1), *dy)
    return [a[:, :s] if a.ndim == 3 else a for a in out]


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def _norm_fwd_pallas(o, z, w, *, eps, interpret):
    return _norm_call([o, z], w, eps, interpret)[0]


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def _norm_bwd_pallas(o, z, w, dy, *, eps, interpret):
    do, dz, dw = _norm_call([o, z, dy], w, eps, interpret)
    return do, dz, dw.reshape(w.shape).astype(w.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _norm(o, z, w, static):
    return _norm_fwd_pallas(o, z, w, **dict(static))


@_scoped("attn_out", "gdn_out")
def _norm_fwd(o, z, w, static):
    return _norm_fwd_pallas(o, z, w, **dict(static)), (o, z, w)


@_scoped("attn_out", "gdn_out")
def _norm_bwd(static, residuals, dy):
    return _norm_bwd_pallas(*residuals, dy, **dict(static))


_norm.defvjp(_norm_fwd, _norm_bwd)


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------

def gdn_conv(qkv: jax.Array, conv_w: jax.Array, *, key_heads: int,
             key_dim: int, value_dim: int, eps: float, impl: str = "auto"):
    """qkv: [B, S, 2 Hk Dk + Hv Dv], the projection's q~ | k~ | v~ columns;
    conv_w: [taps, the same channels]. Returns q, k [B, S, Hk Dk] (unit
    length a head, q scaled by Dk^-1/2 besides) and v [B, S, Hv Dv], in
    qkv's dtype, as the delta rule takes them."""
    impl = resolve_impl(impl, "Gated DeltaNet passes", key_dim, value_dim)
    if impl == "reference":
        return gdn_conv_reference(qkv, conv_w, key_heads=key_heads,
                                  key_dim=key_dim, eps=eps)
    return _conv(qkv, conv_w, (
        ("key_heads", key_heads), ("key_dim", key_dim),
        ("value_dim", value_dim), ("eps", eps),
        ("interpret", impl == "pallas_interpret")))


def gdn_gated_norm(o: jax.Array, z: jax.Array, scale: jax.Array, *,
                   eps: float, impl: str = "auto") -> jax.Array:
    """o, z: [B, S, Hv Dv]; scale (lin_norm): [Dv]. Returns the RMS-normed o
    times scale times SiLU(z), in o's dtype: the out-projection's
    operand."""
    impl = resolve_impl(impl, "Gated DeltaNet passes", scale.shape[0])
    if impl == "reference":
        return gdn_gated_norm_reference(o, z, scale, eps=eps)
    return _norm(o, z, scale, (("eps", eps),
                               ("interpret", impl == "pallas_interpret")))
