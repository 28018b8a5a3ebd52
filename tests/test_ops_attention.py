"""Parity tests: Pallas flash attention (interpret mode) and ring
attention vs the jnp reference. Runs on the virtual 8-device CPU mesh
(conftest). Mirrors the reference's mocked-backend test style (SURVEY §4:
kernels testable without real hardware)."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.shard_map import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ray_tpu.ops import attention
from ray_tpu.ops.attention import (_bwd_pallas, _fwd_pallas, _k_chunk_bounds,
                                   _q_chunk_bounds, attention_reference,
                                   chunk_classes, dot_product_attention,
                                   flash_attention)
from ray_tpu.ops.ring_attention import ring_attention


def _qkv(b=2, h=4, hk=2, s=256, sk=None, d=64, dtype=jnp.float32):
    sk = s if sk is None else sk
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(keys[0], (b, h, s, d), dtype)
    k = jax.random.normal(keys[1], (b, hk, sk, d), dtype)
    v = jax.random.normal(keys[2], (b, hk, sk, d), dtype)
    return q, k, v


FLASH = functools.partial(flash_attention, block_q=128, block_k=128,
                          interpret=True)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_fwd_matches_reference(causal):
    q, k, v = _qkv()
    ref = attention_reference(q, k, v, causal=causal)
    out = FLASH(q, k, v, causal)
    np.testing.assert_allclose(out, ref, atol=2e-5)


def test_flash_grads_match_reference():
    q, k, v = _qkv(s=256)

    def loss(fn, q, k, v):
        return (fn(q, k, v) ** 2).sum()

    g_ref = jax.grad(functools.partial(loss, attention_reference),
                     argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(functools.partial(loss, FLASH),
                    argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_fl):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=5e-4)


@pytest.mark.parametrize("head_dim", [64, 128])
def test_flash_output_residual_is_lane_dense(head_dim):
    """What the backward pass keeps of the output has a minor dimension of
    whole 128-lane tiles: at head width 64 the kernel's transposed output,
    [B, H, D, S], at 128 the output itself."""
    from ray_tpu.ops.attention import _flash_fwd

    q, k, v = _qkv(d=head_dim, dtype=jnp.bfloat16)
    out, (_, _, _, saved, lse) = jax.eval_shape(
        lambda q, k, v: _flash_fwd(q, k, v, True, None, 128, 128, True,
                                   False),
        q, k, v)
    assert out.shape == q.shape
    assert saved.shape == ((2, 4, 64, 256) if head_dim == 64 else q.shape)
    assert saved.shape[-1] % 128 == 0 and saved.dtype == q.dtype
    assert lse.shape == (2, 4, 256)


def test_flash_non_divisible_length():
    # 300 % 128 != 0: padded tiles must be masked, not NaN.
    q, k, v = _qkv(s=300)
    ref = attention_reference(q, k, v, causal=True)
    out = FLASH(q, k, v, True)
    np.testing.assert_allclose(out, ref, atol=2e-5)
    g = jax.grad(lambda q: (FLASH(q, k, v, True) ** 2).sum())(q)
    assert np.isfinite(np.asarray(g)).all()


def test_flash_cross_length_causal_alignment():
    # Decode-style q_len < k_len: causal mask is end-aligned like the
    # reference.
    q, k, v = _qkv(s=128, sk=256)
    ref = attention_reference(q, k, v, causal=True)
    out = FLASH(q, k, v, True)
    np.testing.assert_allclose(out, ref, atol=2e-5)


def _fwd_and_grads(fn, q, k, v):
    def loss(q, k, v):
        out = fn(q, k, v)
        return (out.astype(jnp.float32) ** 2).sum(), out
    (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                         has_aux=True)(q, k, v)
    return (out, *grads)


# name: (_qkv arguments, causal, blocks or None for the shipped default).
# float32 cases keep the tolerances of the tests above (2e-5 forward, 5e-4
# gradients); bf16 inputs are held to the float32 reference at 2e-2 of the
# largest reference value (bf16 keeps 8 bits: 4e-3 relative a rounding,
# several roundings a path).
_PARITY_CASES = {
    # the benchmark cells' shape: one 1024 tile, head width 64, odd heads
    "causal_1024_d64_odd_heads": (dict(b=1, h=3, hk=3, s=1024), True, None),
    "bf16_1024_d64": (dict(b=1, h=2, hk=2, s=1024, dtype=jnp.bfloat16),
                      True, None),
    "non_causal": (dict(s=256), False, None),
    "q_shorter_than_k": (dict(s=128, sk=256), True, None),
    "ragged_300": (dict(s=300), True, (128, 128)),
    "gqa_4_2_d128": (dict(h=4, hk=2, s=256, d=128), True, None),
    # the saved output is [B, H, D, S] at this width: as square as q
    "seq_equals_head_dim_64": (dict(b=1, s=64), True, (64, 64)),
    # several tiles: aligned (diagonal and interior tiles unroll), then
    # unaligned and ragged (loops over bounds from the program ids)
    "tiles_aligned": (dict(b=1, s=512), True, (256, 256)),
    "tiles_aligned_cross_length": (dict(b=1, s=256, sk=512), True,
                                   (128, 128)),
    "tiles_unaligned": (dict(b=1, s=512), True, (128, 256)),
    "tiles_ragged_non_causal": (dict(b=1, s=300), False, (128, 128)),
}


@pytest.mark.parametrize("case", sorted(_PARITY_CASES))
def test_flash_fwd_and_grads_match_reference(case):
    kw, causal, blocks = _PARITY_CASES[case]
    q, k, v = _qkv(**kw)
    block_kw = dict(zip(("block_q", "block_k"), blocks)) if blocks else {}
    got = _fwd_and_grads(
        lambda q, k, v: flash_attention(q, k, v, causal, interpret=True,
                                        **block_kw), q, k, v)
    f32 = lambda x: x.astype(jnp.float32)
    want = _fwd_and_grads(
        lambda q, k, v: attention_reference(q, k, v, causal=causal),
        f32(q), f32(k), f32(v))
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        assert a.dtype == q.dtype
        if q.dtype == jnp.bfloat16:
            atol = 2e-2 * float(jnp.max(jnp.abs(b)))
        else:
            atol = 2e-5 if name == "out" else 5e-4
        np.testing.assert_allclose(np.asarray(f32(a)), np.asarray(b),
                                   atol=atol, err_msg=f"{case}: {name}")


# The projections' own layout, [B, S, H, Dh], which `GPT._attention` hands
# over: name: (_qkv arguments, blocks or None). A block is 128 lanes of
# H * Dh: two heads of 64 (one in the last block of an odd count), eight of
# 16, every head of a model narrower than the lanes; width 128 and GQA at a
# narrow head go through the head-major kernels.
_SEQ_MAJOR_CASES = {
    "h16_d64_bf16": (dict(b=1, h=16, hk=16, s=256, dtype=jnp.bfloat16),
                     None),
    "h5_d64_half_block": (dict(b=2, h=5, hk=5, s=256), None),
    "h2_d128": (dict(b=2, h=2, hk=2, s=256, d=128), None),
    "gqa_4_2_d128": (dict(h=4, hk=2, s=256, d=128), None),
    "gqa_4_2_d64": (dict(h=4, hk=2, s=256), None),
    "ragged_300_h3_d64": (dict(b=1, h=3, hk=3, s=300), (128, 128)),
    "tiles_aligned_h4_d64": (dict(b=1, h=4, hk=4, s=512), (256, 256)),
    "h10_d16_eight_a_block": (dict(b=1, h=10, hk=10, s=128, d=16), None),
    "h2_d16_under_the_lanes": (dict(b=1, h=2, hk=2, s=128, d=16), None),
}


@pytest.mark.parametrize("case", sorted(_SEQ_MAJOR_CASES))
def test_flash_seq_major_fwd_and_grads_match_reference(case):
    kw, blocks = _SEQ_MAJOR_CASES[case]
    q, k, v = (jnp.swapaxes(x, 1, 2) for x in _qkv(**kw))
    block_kw = dict(zip(("block_q", "block_k"), blocks)) if blocks else {}
    got = _fwd_and_grads(
        lambda q, k, v: flash_attention(q, k, v, True, interpret=True,
                                        seq_major=True, **block_kw), q, k, v)
    f32 = lambda x: x.astype(jnp.float32)
    want = _fwd_and_grads(
        lambda q, k, v: dot_product_attention(q, k, v, impl="reference",
                                              seq_major=True),
        f32(q), f32(k), f32(v))
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        assert a.dtype == q.dtype and a.shape == b.shape
        if q.dtype == jnp.bfloat16:
            atol = 2e-2 * float(jnp.max(jnp.abs(b)))
        else:
            atol = 2e-5 if name == "out" else 5e-4
        np.testing.assert_allclose(np.asarray(f32(a)), np.asarray(b),
                                   atol=atol, err_msg=f"{case}: {name}")


def test_flash_layouts_give_the_same_numbers():
    """`flash_attention`'s [B, H, S, Dh] entry (ring attention's layout)
    and the sequence-major one run the same bodies on the same heads:
    outputs and gradients are equal bit for bit, bf16 included (dq, dk and
    dv are rounded once, from the float32 accumulators, in both)."""
    q, k, v = _qkv(b=1, h=4, hk=4, s=256, dtype=jnp.bfloat16)
    head_major = _fwd_and_grads(
        lambda q, k, v: flash_attention(q, k, v, True, interpret=True),
        q, k, v)
    seq_major = _fwd_and_grads(
        lambda q, k, v: flash_attention(q, k, v, True, interpret=True,
                                        seq_major=True),
        *(jnp.swapaxes(x, 1, 2) for x in (q, k, v)))
    for a, b in zip(head_major, seq_major):
        np.testing.assert_array_equal(
            np.asarray(a.astype(jnp.float32)),
            np.asarray(jnp.swapaxes(b, 1, 2).astype(jnp.float32)))


# The forward kernel alone, as `_flash_fwd`, the served path and ring
# attention call it, at each class of shape the benchmark's cells run, cut
# to the interpreter's reach: name: (_qkv arguments, causal, blocks or None
# for the shipped tile, seq_major, each row's document length or None). A
# document shorter than its row is right-padded, as the served path's
# buckets are: its real positions are held to the document alone.
_BF16 = dict(dtype=jnp.bfloat16)
_FWD_CASES = {
    # GPT-2: two heads a 128-lane block, one 1024 tile
    "two_heads_a_block_1024": (dict(b=1, h=2, hk=2, s=1024, **_BF16), True,
                               None, True, None),
    # gpt2_xl: an odd count, the last block half full
    "odd_heads_half_block": (dict(b=1, h=3, hk=3, s=512, **_BF16), True,
                             None, True, None),
    # OLMoE: width 128, a row of four k tiles
    "d128_four_k_tiles": (dict(b=1, h=2, hk=2, s=512, d=128, **_BF16), True,
                          (128, 128), False, None),
    # Qwen3-Next: a GQA group at width 256, several k tiles
    "gqa_group_d256": (dict(b=1, h=4, hk=2, s=256, d=256, **_BF16), True,
                       (128, 128), False, None),
    # the served buckets: bf16, forward only, right-padded documents
    "bucket_128": (dict(b=2, h=2, hk=2, s=128, **_BF16), True, None, True,
                   (128, 37)),
    "bucket_256": (dict(b=2, h=2, hk=2, s=256, **_BF16), True, None, True,
                   (130, 255)),
    "bucket_512": (dict(b=2, h=2, hk=2, s=512, **_BF16), True, None, True,
                   (400, 257)),
    "bucket_1024": (dict(b=1, h=2, hk=2, s=1024, **_BF16), True, None, True,
                    (777,)),
    "ragged_300": (dict(b=1, h=2, hk=2, s=300), True, (128, 128), False,
                   None),
    "cross_length": (dict(b=1, h=2, hk=2, s=128, sk=256), True, (128, 128),
                     False, None),
    "q_longer_than_k": (dict(b=1, h=2, hk=2, s=256, sk=128), True, None,
                        False, None),
    # ring attention's partial: no mask, float32 kept
    "non_causal_keep_f32": (dict(b=1, h=2, hk=2, s=256), False, (128, 128),
                            False, None),
}

# What the parent's forward kernel (PR 44's tree, 512 x 512 rectangles, p.v
# in place) gave under the interpreter at eight places of out and four of
# lse of each case (`_samples`): the kernel may add in another order, not
# in another precision.
_FWD_PARENT = {
    "bucket_1024": (
        [-0.6679688, 0.05541992, 0.0390625, -0.04589844, 0.1162109, -0.1196289,
         0.06494141, -0.07470703],
        [-0.5967165, 7.013887, 6.382701, 7.389666]),
    "bucket_128": (
        [-0.6679688, -0.08789062, -0.0100708, 0.1157227, -0.2519531,
         0.0005683899, 0.03759766, -0.07226562],
        [-0.5967165, 4.079811, 4.879762, 5.122957]),
    "bucket_256": (
        [-0.6679688, 0.3300781, -0.2519531, 0.05419922, -0.0625, -0.0177002,
         0.08007812, -0.03271484],
        [-0.5967165, 5.217459, 5.559694, 5.924831]),
    "bucket_512": (
        [-0.6679688, 0.05541992, -0.0625, -0.1044922, 0.1162109, -0.1196289,
         0.1279297, -0.1865234],
        [-0.5967165, 5.559694, 6.382701, 6.668979]),
    "cross_length": (
        [-0.04504188, -0.3304721, 0.2263153, 0.1282314, 0.1247439, 0.081916,
         0.01881928, 0.07707193],
        [5.334502, 5.908154, 5.766953, 5.962478]),
    "d128_four_k_tiles": (
        [-0.6679688, -0.078125, 0.1611328, 0.05053711, -0.05639648, -0.1630859,
         -0.01953125, -0.08398438],
        [-0.6982549, 6.241089, 5.574415, 6.748142]),
    "gqa_group_d256": (
        [-0.6679688, 0.1757812, -0.02612305, 0.04467773, 0.2451172, 0.08984375,
         -0.2392578, -0.1591797],
        [-0.6964874, 5.096807, 5.563406, 5.975451]),
    "non_causal_keep_f32": (
        [-0.1725049, -0.08803102, 0.09398368, -0.01346552, -0.04215935,
         0.02492446, -0.1030271, 0.08762099],
        [6.078001, 6.387012, 5.925965, 6.102642]),
    "odd_heads_half_block": (
        [-0.6679688, 0.06591797, 0.02575684, -0.1689453, -0.1044922, 0.1650391,
         0.02368164, -0.04125977],
        [-0.5967165, 6.59877, 6.616065, 6.620105]),
    "q_longer_than_k": (
        [0, 0, 0.001217768, -0.05473015, 0, 0, -0.1813978, -0.2656818],
        [-1e+30, 4.813403, -1e+30, 5.255448]),
    "ragged_300": (
        [1.295636, 0.3069276, -0.07012283, 0.1180166, 0.08767437, 0.1687294,
         0.1202324, 0.01905929],
        [-0.6854866, 6.050971, 4.971652, 6.158551]),
    "two_heads_a_block_1024": (
        [-0.6679688, 0.05541992, 0.0390625, -0.04589844, 0.1162109, -0.1196289,
         0.06494141, -0.07470703],
        [-0.5967165, 7.013887, 6.382701, 7.389666]),
}


def _samples(x, n):
    flat = np.asarray(x, np.float32).reshape(-1)
    return flat[np.linspace(0, flat.size - 1, n).astype(int)]


def _forward(case):
    """(out [B, H, S, D] float32, lse [B, H, S], and the q, k, v they came
    from, head-major) of a forward case, through `_flash_fwd` as the models
    call it."""
    kw, causal, blocks, seq_major, _ = _FWD_CASES[case]
    q, k, v = _qkv(**kw)
    blocks = blocks or (attention.DEFAULT_BLOCK_Q, attention.DEFAULT_BLOCK_K)
    layout = (lambda x: jnp.swapaxes(x, 1, 2)) if seq_major else (lambda x: x)
    out, (*_, lse) = attention._flash_fwd(
        layout(q), layout(k), layout(v), causal, None, *blocks, True,
        seq_major)
    assert out.dtype == q.dtype and lse.dtype == jnp.float32
    return np.asarray(layout(out), np.float32), np.asarray(lse), q, k, v


def _reference(q, k, v, causal):
    """(out, lse) in float32 of [B, H, S, D] inputs."""
    f32 = lambda x: x.astype(jnp.float32)
    group = q.shape[1] // k.shape[1]
    s = jnp.einsum("bhqd,bhkd->bhqk", f32(q),
                   jnp.repeat(f32(k), group, axis=1)) / np.sqrt(q.shape[-1])
    if causal:
        qi = jnp.arange(q.shape[2])[:, None] + k.shape[2] - q.shape[2]
        s = jnp.where(jnp.arange(k.shape[2])[None, :] <= qi, s, -jnp.inf)
    lse = jax.nn.logsumexp(s, axis=-1)
    out = attention_reference(f32(q), f32(k), f32(v), causal=causal)
    return np.asarray(out), np.asarray(lse)


@pytest.mark.parametrize("case", sorted(_FWD_CASES))
def test_flash_fwd_out_and_lse(case):
    """out and lse of the forward kernel against the float32 reference —
    a padded row's real positions against its document alone — and against
    the parent's kernel to one bf16 step."""
    kw, causal, _, _, docs = _FWD_CASES[case]
    out, lse, q, k, v = _forward(case)
    bf16 = q.dtype == jnp.bfloat16
    for b in range(q.shape[0]):
        n = docs[b] if docs else q.shape[2]
        nk = docs[b] if docs else k.shape[2]
        want, want_lse = _reference(q[b:b + 1, :, :n], k[b:b + 1, :, :nk],
                                    v[b:b + 1, :, :nk], causal)
        live = np.isfinite(want_lse[0])      # a query before the first key
        # one bf16 step at the largest value: p is rounded to bf16 for p.v
        atol = 2.0 ** -8 * np.abs(want).max() if bf16 else 2e-5
        np.testing.assert_allclose(out[b, :, :n][live], want[0][live],
                                   atol=atol, err_msg=case)
        # bf16: q is scaled in bf16, and 128 ** -0.5 is no power of two
        np.testing.assert_allclose(lse[b, :, :n][live], want_lse[0][live],
                                   atol=1e-2 if bf16 else 1e-5, rtol=1e-5,
                                   err_msg=case)
    parent_out, parent_lse = _FWD_PARENT[case]
    step = 2.0 ** -7 if bf16 else 2.0 ** -20    # of a value in [1, 2)
    np.testing.assert_allclose(_samples(out, 8), parent_out, rtol=step,
                               atol=1e-6, err_msg=case)
    np.testing.assert_allclose(_samples(lse, 4), parent_lse, rtol=1e-6,
                               atol=1e-5, err_msg=case)


# The backward pass alone, as `_flash_bwd` and ring attention call it: name:
# (_qkv arguments, causal, blocks, seq_major, keep_f32, fused). `fused` says
# which form `_bwd_pallas` picks at the shape: one kernel, `flash_bwd`,
# wherever the row's dq accumulator fits in VMEM beside the blocks, the pair
# `flash_bwd_dq` + `flash_bwd_dkv` otherwise; "forced_pair" describes a core
# with little VMEM to put a small row on the rule's far side.
_BWD_CASES = {
    "seq_major_two_heads_a_block": (dict(b=1, h=4, hk=4, s=512), True,
                                    None, True, False, True),
    "seq_major_odd_heads": (dict(b=2, h=3, hk=3, s=256), True, None, True,
                            False, True),
    "seq_major_bf16": (dict(b=1, h=2, hk=2, s=512, dtype=jnp.bfloat16),
                       True, None, True, False, True),
    "head_major_d128": (dict(b=1, h=2, hk=2, s=256, d=128), True, None,
                        False, False, True),
    "gqa_group_d128": (dict(b=1, h=4, hk=2, s=256, d=128), True, None,
                       False, False, True),
    "ragged_300": (dict(b=1, h=2, hk=2, s=300), True, (128, 128), False,
                   False, True),
    "cross_length": (dict(b=1, h=2, hk=2, s=128, sk=256), True, (128, 128),
                     False, False, True),
    "non_causal_keep_f32": (dict(b=1, h=2, hk=2, s=256), False, (128, 128),
                            False, True, True),
    "row_of_four_tiles": (dict(b=1, h=2, hk=2, s=512), True, (128, 128),
                          False, False, True),
    "row_of_four_tiles_unaligned": (dict(b=1, h=2, hk=2, s=512), True,
                                    (128, 256), False, False, True),
    "row_of_four_tiles_forced_pair": (dict(b=1, h=2, hk=2, s=512), True,
                                      (128, 128), False, False, False),
}


def _bwd_forms(case, monkeypatch):
    """(dq, dk, dv) of the form the rule picks, of the pair on the same
    inputs, and of the float32 reference, with the jaxpr of the first."""
    kw, causal, blocks, seq_major, keep_f32, fused = _BWD_CASES[case]
    q, k, v = _qkv(**kw)
    do = jax.random.normal(jax.random.PRNGKey(1), q.shape, q.dtype)
    f32 = lambda x: x.astype(jnp.float32)
    _, vjp = jax.vjp(
        lambda q, k, v: attention_reference(q, k, v, causal=causal),
        f32(q), f32(k), f32(v))
    want = vjp(f32(do))
    block_q, block_k = blocks or (1024, 1024)
    scale = q.shape[-1] ** -0.5
    out, lse = _fwd_pallas(q, k, v, scale=scale, causal=causal,
                           block_q=block_q, block_k=block_k, interpret=True)
    delta = jnp.sum(f32(do) * f32(out), axis=-1)
    if seq_major:
        q, k, v, do = (jnp.swapaxes(x, 1, 2) for x in (q, k, v, do))

    def bwd(q, k, v, do):
        got = _bwd_pallas(q, k, v, lse, do, delta, scale=scale,
                          causal=causal, block_q=block_q, block_k=block_k,
                          interpret=True, keep_f32=keep_f32,
                          seq_major=seq_major)
        return tuple(jnp.swapaxes(x, 1, 2) if seq_major else x for x in got)

    if not fused:
        monkeypatch.setattr(attention, "_vmem_capacity", lambda: 2 ** 17)
    text = str(jax.make_jaxpr(bwd)(q, k, v, do))
    picked = bwd(q, k, v, do)
    monkeypatch.setattr(attention, "_vmem_capacity", lambda: 0)
    pair = bwd(q, k, v, do)
    return picked, pair, want, text


@pytest.mark.parametrize("case", sorted(_BWD_CASES))
def test_flash_bwd_matches_the_reference_and_the_pair(case, monkeypatch):
    """The fused backward kernel against the float32 reference and against
    the kernel pair on the same inputs: the same p and ds in the same
    dtypes, so they differ by the order of float32 sums (and, bf16, by the
    rounding that order moves), and the fused form is held to the
    reference as closely as the pair is."""
    kw, _, _, _, keep_f32, fused = _BWD_CASES[case]
    picked, pair, want, text = _bwd_forms(case, monkeypatch)
    names = set(re.findall(r"name=(flash_\w+)", text))
    assert names == ({"flash_bwd"} if fused
                     else {"flash_bwd_dq", "flash_bwd_dkv"})
    bf16 = kw.get("dtype") == jnp.bfloat16
    for name, a, b, ref in zip(("dq", "dk", "dv"), picked, pair, want):
        assert a.dtype == (jnp.float32 if keep_f32 or not bf16
                           else jnp.bfloat16)
        assert a.shape == b.shape == ref.shape
        a, b, ref = (np.asarray(x.astype(jnp.float32)) for x in (a, b, ref))
        top = float(np.max(np.abs(ref)))
        atol = 2e-2 * top if bf16 else 5e-4
        np.testing.assert_allclose(a, ref, atol=atol, err_msg=f"{name}")
        # against the pair: float32 sums in another order, one bf16
        # rounding of the result where the inputs are bf16
        np.testing.assert_allclose(a, b, atol=2 ** -7 * top if bf16
                                   else 2e-5 * max(top, 1.0),
                                   err_msg=f"{name} against the pair")
        # no worse against the reference than the pair is
        assert np.max(np.abs(a - ref)) <= 1.5 * np.max(np.abs(b - ref)) \
            + 1e-6 * top, name


_V5E, _V4 = ("TPU v5 lite", 1), ("TPU v4", 2)


@pytest.mark.parametrize("cell,chip,q_shape,k_shape,seq_major,fused", [
    ("gpt2m-steady", _V5E, (12, 1024, 16, 64), (12, 1024, 16, 64), True,
     True),
    ("gpt2xl-fsdp4", _V5E, (16, 1024, 25, 64), (16, 1024, 25, 64), True,
     True),
    ("olmoe-steady", _V5E, (5, 16, 4096, 128), (5, 16, 4096, 128), False,
     True),
    ("qwen3next-steady", _V5E, (4, 16, 8192, 256), (4, 2, 8192, 256),
     False, True),
    # a row that no v5e kernel could hold: 64k keys of width 128
    ("longer-than-vmem", _V5E, (1, 1, 65536, 128), (1, 1, 65536, 128),
     False, False),
    # the rule asks the device: a v4 core has 16 MiB where the v5e's has
    # 128, so the [4096, 128] row (9.4 MiB by the rule's count) takes the
    # pair there and the one-tile row (5.9 MiB) still fuses
    ("olmoe-steady-on-a-v4", _V4, (5, 16, 4096, 128), (5, 16, 4096, 128),
     False, False),
    ("gpt2m-steady-on-a-v4", _V4, (12, 1024, 16, 64), (12, 1024, 16, 64),
     True, True),
])
def test_bwd_form_at_the_benchmark_cells_shapes(cell, chip, q_shape, k_shape,
                                                seq_major, fused):
    """Which form `_bwd_pallas` picks is a function of static shapes and of
    the VMEM of the device the call is traced for (described here, as a
    mesh describes it): the fused kernel where the row's accumulator and
    the blocks fit in half of it (as each cell's attention layers call it:
    bf16, the shipped tile), the pair beyond."""
    from jax.sharding import AbstractDevice, AbstractMesh

    heads = attention._Heads(q_shape, k_shape, seq_major)
    narrow = heads.dim % 128 != 0
    x = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype)
    stats = x((heads.batch, heads.num, heads.q_len), jnp.float32)
    do = x((heads.batch, heads.num, heads.dim, heads.q_len) if narrow
           else q_shape)
    with jax.sharding.use_abstract_mesh(AbstractMesh(
            (), (), abstract_device=AbstractDevice(*chip))):
        text = str(jax.make_jaxpr(
            lambda q, k, v, lse, do, delta: _bwd_pallas(
                q, k, v, lse, do, delta, scale=1.0, causal=True,
                block_q=attention.DEFAULT_BLOCK_Q,
                block_k=attention.DEFAULT_BLOCK_K, interpret=False,
                seq_major=seq_major, do_t=narrow))(
            x(q_shape), x(k_shape), x(k_shape), stats, do, stats))
    names = set(re.findall(r"name=(flash_\w+)", text))
    assert names == ({"flash_bwd"} if fused
                     else {"flash_bwd_dq", "flash_bwd_dkv"}), cell


@pytest.mark.parametrize("tile,sub,chunk,computed,interior,edge", [
    (512, 512, 512, 0.75, 1, 2),      # before PR 26: whole 512 tiles
    (1024, 128, 128, 0.5625, 28, 8),  # dkv's rectangles (transposed)
    (1024, 256, 256, 0.625, 6, 4),
    (1024, 512, 512, 0.75, 1, 2),     # dq as shipped, the forward till PR 45
])
def test_chunk_classes_of_the_benchmark_cells(tile, sub, chunk, computed,
                                              interior, edge):
    got = chunk_classes(1024, 1024, True, tile=tile, sub=sub, chunk=chunk)
    assert got["computed_share"] == computed
    assert (got["interior"], got["edge"]) == (interior, edge)
    assert got["dead"] + interior + edge == (1024 // sub) * (1024 // chunk)
    full = chunk_classes(1024, 1024, False, tile=tile, sub=sub, chunk=chunk)
    assert full["computed_share"] == 1.0 and full["edge"] == 0


@pytest.mark.parametrize("seq,width,dead,interior,edge,computed", [
    (128, 64, 0, 0, 1, 1.0),           # the served buckets: one rectangle,
    (256, 64, 1, 1, 2, 0.75),          # then the diagonal's share falls
    (512, 64, 6, 6, 4, 0.625),
    (1024, 64, 28, 28, 8, 0.5625),     # GPT-2 (0.75 at 512 x 512 till PR 45)
    (4096, 128, 496, 496, 32, 0.515625),       # OLMoE, four k tiles a row
    (8192, 256, 2016, 2016, 64, 0.5078125),    # Qwen3-Next, eight
])
def test_forward_rectangles_at_the_cells_shapes(seq, width, dead, interior,
                                                edge, computed,
                                                monkeypatch):
    """The rectangles the forward kernel walks at each (row length, head
    width) the benchmark's cells run: 128 queries x 128 keys everywhere —
    `_rect` cuts nothing at these widths, a [256, 128] float32 accumulator
    is the 32 registers it may have — counted by `chunk_classes` at its
    defaults, which are the forward's. The sizes follow static shapes alone:
    the same for any batch, head count and layout."""
    tile = min(seq, attention.DEFAULT_BLOCK_Q)
    assert attention._rect(tile, tile, width, attention._FWD_RECT) == (
        128, 128)
    got = chunk_classes(seq, seq, True)
    assert got == chunk_classes(seq, seq, True, sub=128, chunk=128)
    assert got == {"dead": dead, "interior": interior, "edge": edge,
                   "computed_share": computed}

    seen = set()
    kernel = attention._fwd_kernel

    def recording(*refs, sub, chunk, **kw):
        seen.add((sub, chunk))
        return kernel(*refs, sub=sub, chunk=chunk, **kw)

    monkeypatch.setattr(attention, "_fwd_kernel", recording)
    for batch, heads, seq_major in ((1, 2, False), (3, 2, False),
                                    (2, 4, True)):
        if seq_major and width >= 128:
            continue        # the models hand such a width over head-major
        shape = ((batch, seq, heads, width) if seq_major
                 else (batch, heads, seq, width))
        x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
        jax.eval_shape(functools.partial(
            _fwd_pallas, scale=1.0, causal=True,
            block_q=attention.DEFAULT_BLOCK_Q,
            block_k=attention.DEFAULT_BLOCK_K, interpret=True,
            seq_major=seq_major), x, x, x)
    assert seen == {(128, 128)}


@pytest.mark.parametrize("q_len,k_len,rel", [
    (1024, 1024, 0), (512, 1024, 512), (1024, 1024, None), (300, 300, 0)])
def test_chunk_bounds_agree_between_kernels(q_len, k_len, rel):
    """The forward / dq walk (queries fixed, key chunks) and the dkv walk
    (keys fixed, query chunks) give every square rectangle one class."""
    size = 128
    nq, nk = -(-q_len // size), -(-k_len // size)

    def cls(bounds, c):
        lo, mid, hi, end = bounds
        return ("dead" if c < lo or c >= end else
                "interior" if mid <= c < hi else "edge")

    for qi in range(nq):
        interior_end, live_end = _k_chunk_bounds(qi * size, size, rel,
                                                 k_len, chunk=size)
        for ki in range(nk):
            by_q = ("interior" if ki < interior_end else
                    "edge" if ki < live_end else "dead")
            if (qi + 1) * size > q_len and by_q == "interior":
                by_q = "edge"      # dkv must mask padded queries
            bounds = _q_chunk_bounds(ki * size, size, rel, q_len, k_len,
                                     chunk=size)
            assert cls(bounds, qi) == by_q, (qi, ki)


def test_dispatch_validates_impl():
    q, k, v = _qkv(s=128)
    with pytest.raises(ValueError):
        dot_product_attention(q, k, v, impl="nope")


class TestRingAttention:
    def _ring(self, sp, impl="reference", causal=True, **kw):
        mesh = Mesh(np.asarray(jax.devices()[:sp]), ("sp",))
        spec = P(None, None, "sp", None)
        return shard_map(
            functools.partial(ring_attention, axis_name="sp",
                              causal=causal, impl=impl, **kw),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_rep=False)

    @pytest.mark.parametrize("sp", [2, 4])
    @pytest.mark.parametrize("causal", [True, False])
    def test_fwd(self, sp, causal):
        q, k, v = _qkv(s=256)
        ref = attention_reference(q, k, v, causal=causal)
        out = jax.jit(self._ring(sp, causal=causal))(q, k, v)
        np.testing.assert_allclose(out, ref, atol=2e-5)

    def test_grads(self):
        q, k, v = _qkv(s=256)
        ring = self._ring(4)

        def loss(fn, q, k, v):
            return (fn(q, k, v) ** 2).sum()

        g_ref = jax.grad(
            lambda q, k, v: (attention_reference(q, k, v, True) ** 2).sum(),
            argnums=(0, 1, 2))(q, k, v)
        g_ring = jax.jit(jax.grad(lambda q, k, v: (ring(q, k, v) ** 2).sum(),
                                  argnums=(0, 1, 2)))(q, k, v)
        for a, b in zip(g_ref, g_ring):
            np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                       atol=5e-4)

    def test_pallas_partials(self):
        q, k, v = _qkv(b=1, h=2, hk=2, s=256)
        ring = self._ring(2, impl="pallas_interpret", block_q=128,
                          block_k=128)
        ref = attention_reference(q, k, v, causal=True)
        out = jax.jit(ring)(q, k, v)
        np.testing.assert_allclose(out, ref, atol=2e-5)

    def test_bad_impl_raises(self):
        q, k, v = _qkv(s=128)
        with pytest.raises(ValueError):
            jax.jit(self._ring(2, impl="refernce"))(q, k, v)
