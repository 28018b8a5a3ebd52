"""The Gated DeltaNet layer's operators against their plain forms, in
float32 and bf16 on the CPU (`test_qwen3_next_reference.py` has the whole
model): the chunked delta rule (`ops/delta_rule.py`) against the recurrence
one position at a time, its solve against the inverse, and the two passes
around it (`ops/gated_deltanet.py`), kernels under the interpreter against
the `jnp` forms.

The chip runs another branch of the rule than float32 inputs take: bf16
operands with float32 accumulation, the solve at `Precision.HIGH`. The
bfloat16 cases give that branch bf16 inputs and hold the result and every
gradient to the float32 reference within bf16's rounding: the largest
difference read, as a share of the reference's largest entry, was 0.0097
(8 seeds at each of three decays); 0.02 is allowed. A backward rule with a
transpose missing is off by 0.5 or more.

Last, the configuration's step on the chip: `qwen3next-steady`'s train step
compiled for one chip of a described TPU v5e, without one (`_chip.py`).
It is tier-1's longest test (two compiles of two minutes), and it is here,
in a file of many tests, because `--dist loadfile` hands out the files of
many tests first: in a file of its own it started last.
"""

import collections
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import SingleDeviceSharding

from _chip import (HBM_BYTES, _kernel_calls, _kernel_names,  # noqa: F401
                   _moved, _on, _qwen3_next_config, _qwen3_next_step, v5e)
from ray_tpu.ops import gated_deltanet
from ray_tpu.ops.delta_rule import gated_delta_rule


def _recurrence(q, k, v, g, beta):
    """The delta rule one position at a time, as the reference writes it."""
    hv = v.shape[2]
    q, k = (jnp.repeat(x, hv // x.shape[2], 2) for x in (q, k))

    def position(state, at):
        q_t, k_t, v_t, g_t, b_t = at
        state = jnp.exp(g_t)[..., None, None] * state
        delta = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", state,
                                                   k_t))
        state = state + k_t[..., :, None] * delta[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    state = jnp.zeros((q.shape[0], hv, q.shape[-1], v.shape[-1]))
    _, out = lax.scan(position, state, tuple(
        jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(out, 0, 1)


def _delta_rule_inputs(seed, dtype, rate, b, s, hk, hv, d):
    """q and k normalised as `GPT._linear_mixer` hands them over, g and beta
    in float32, and a weight for the result's sum."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    q, k = (jax.random.normal(key, (b, s, hk, d)) for key in keys[:2])
    q = (q / jnp.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(d)
         ).astype(dtype)
    k = (k / jnp.linalg.norm(k, axis=-1, keepdims=True)).astype(dtype)
    v = jax.random.normal(keys[2], (b, s, hv, d)).astype(dtype)
    g = -rate * jax.nn.softplus(jax.random.normal(keys[3], (b, s, hv)))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (b, s, hv)))
    weight = jax.random.normal(keys[5], (b, s, hv, d))
    return (q, k, v, g, beta), weight


def _rule_and_grads(impl, args, weight):
    def weighted(*a):
        return (gated_delta_rule(*a, impl=impl).astype(jnp.float32)
                * weight).sum()

    return (gated_delta_rule(*args, impl=impl),
            *jax.grad(weighted, argnums=range(5))(*args))


@pytest.mark.parametrize("impl", ["reference", "pallas_interpret"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("rate", [1e-3, 1.0, 40.0],
                         ids=["decay_near_1", "decay_mid", "decay_near_0"])
def test_chunked_delta_rule_matches_the_recurrence(rate, dtype, impl):
    """Forward and every input's gradient, on a row of 150 (two chunks and
    a ragged third), two value heads a key head, against the recurrence in
    float32: the `jnp` form and the kernel pair under the interpreter. In
    float32 both sides differ by the order of their sums; in bfloat16 (the
    branch the chip runs: q, k, v in bf16, g and beta in float32 as
    `GPT._linear_mixer` hands them over) by bf16's rounding of the same
    inputs."""
    f32 = jnp.float32
    wide = dtype == "float32"
    args, weight = _delta_rule_inputs(0 if wide else 5, dtype, rate, 2, 150,
                                      2, 4, 16 if wide else 32)
    exact = tuple(a.astype(f32) for a in args)
    with jax.default_matmul_precision("highest" if wide else "default"):
        out, *grads = _rule_and_grads(impl, args, weight)
    with jax.default_matmul_precision("highest"):
        want = _recurrence(*exact)
        wants = jax.grad(lambda *a: (_recurrence(*a) * weight).sum(),
                         argnums=range(5))(*exact)
    assert out.dtype == dtype
    if wide:
        assert float(jnp.max(jnp.abs(out - want))) < 2e-6
    for name, got, ref in zip("o q k v g beta".split(), (out, *grads),
                              (want, *wants)):
        assert bool(jnp.all(jnp.isfinite(got))), name
        largest = float(jnp.max(jnp.abs(ref)))
        allowed = 2e-5 * max(1.0, largest) if wide else 0.02 * largest
        assert float(jnp.max(jnp.abs(got.astype(f32) - ref))) <= allowed, name


def test_delta_rule_kernels_carry_the_state_across_grid_steps():
    """At the head width the chip runs (128 keys, 128 values, bf16) on a
    row of 600: ten chunks, so three grid steps of the chunk axis, the last
    ragged. The kernels under the interpreter against the `jnp` form: the
    state the forward carries from step to step, dS the backward carries
    the other way, and dq, dk summed over a key head's two value heads."""
    f32 = jnp.float32
    args, weight = _delta_rule_inputs(7, "bfloat16", 1.0, 1, 600, 1, 2, 128)
    got = _rule_and_grads("pallas_interpret", args, weight)
    want = _rule_and_grads("reference", args, weight)
    for name, a, b in zip("o q k v g beta".split(), got, want):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        largest = float(jnp.max(jnp.abs(b.astype(f32))))
        assert float(jnp.max(jnp.abs(a.astype(f32) - b.astype(f32)))
                     ) <= 0.02 * largest, name


def _solve_inputs(c, rate, width, seed):
    """a^T as `_Chunk.heads` makes it of a chunk of c positions: strictly
    upper, -beta_t k_t.k_j e^{G_t - G_j} at [j, t]."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    k = jax.random.normal(keys[0], (c, width))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    total = jnp.cumsum(-rate * jax.nn.softplus(jax.random.normal(keys[1],
                                                                 (c,))))
    beta = jax.nn.sigmoid(jax.random.normal(keys[2], (c,)))
    upper = jnp.arange(c)[:, None] < jnp.arange(c)[None, :]
    return jnp.where(upper, -beta[None, :] * (k @ k.T) * jnp.exp(
        jnp.where(upper, total[None, :] - total[:, None], 0.0)), 0.0)


def _three_dot_inverses_t(a_ts):
    """The solve as the kernels made it until PR 46, the yardstick of the
    bf16 branch: (I + a)(I + a^2)(I + a^4)..., each product three separate
    bf16 passes summed in float32."""
    def split(a):
        high = a.astype(jnp.bfloat16)
        return high, (a - high.astype(jnp.float32)).astype(jnp.bfloat16)

    def mm(a, b):
        (a0, a1), (b0, b1) = split(a), split(b)

        def dot(x, y):
            return jnp.matmul(x, y, preferred_element_type=jnp.float32)

        return dot(a0, b0) + (dot(a0, b1) + dot(a1, b0))

    inverses = []
    for a_t in a_ts:
        c = a_t.shape[0]
        inverse, power = jnp.eye(c) + a_t, a_t
        for _ in range(c.bit_length() - 2):
            power = mm(power, power)
            inverse = inverse + mm(power, inverse)
        inverses.append(inverse)
    return inverses


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("c", [32, 64])
def test_delta_rule_solve_matches_the_inverse(c, dtype):
    """`_inverses_t`, the kernels' (I - a)^-1 transposed, of three
    chunks side by side (one of decays near 1 and narrow keys, so that T is
    far from I) against numpy's inverse in float64, as a share of its
    largest entry, at the kernels' chunk and at half of it. Beside float32
    inputs it is float32's; beside bf16 inputs (three bf16 passes a
    product, two of them summed along the matrix unit's depth) no further
    off than the three separate passes that it replaced, or than float32's
    limit where both are inside it: the two differ in the order of three
    sums, by 1.3% at most here, and a tenth is allowed. (A chunk of 128,
    the backward kernel's until PR 46, reads 1.8e-4 on the third input by
    the yardstick, and 8.4e-6 in float32: the squares up to a^64 lose what
    a chunk of 64 keeps.)"""
    from ray_tpu.ops.delta_rule import _inverses_t

    a_ts = [_solve_inputs(c, rate, width, seed)
            for seed, (rate, width) in enumerate([(1.0, 128), (0.1, 32),
                                                  (1e-3, 12)])]
    wants = [np.linalg.inv(np.eye(c) - np.asarray(a_t, np.float64))
             for a_t in a_ts]

    def errors(gots):
        return [float(np.max(np.abs(np.asarray(got, np.float64) - want))
                      / np.max(np.abs(want)))
                for got, want in zip(gots, wants)]

    with jax.default_matmul_precision(
            "highest" if dtype == "float32" else "default"):
        got = errors(_inverses_t(a_ts, dt=jnp.dtype(dtype)))
        was = errors(_three_dot_inverses_t(a_ts))
    assert np.max(np.abs(wants[2] - np.eye(c))) > 0.5      # far from I
    for new, old in zip(got, was):
        assert new <= (2e-6 if dtype == "float32"
                       else max(1.1 * old, 2e-6)), (got, was)


# The Gated DeltaNet layer's passes around the rule (`ops/gated_deltanet.py`),
# with `_ROWS` set to 64: (row length, key heads, key width, value heads,
# value width).
PASSES = {
    # two blocks and 22 positions of a third; v's columns start at no whole
    # block of its lanes, so the parts are sliced out and dx joined
    "two_blocks_and_a_ragged_third": (150, 2, 8, 4, 12),
    "shorter_than_a_block": (40, 2, 16, 4, 16),
    # whole blocks at the chip's head width: q, k, v read in place and the
    # three backward calls write one dx through its aliases
    "whole_blocks_read_in_place": (128, 1, 128, 2, 128),
}


def _held_to(name, got, want, dtype):
    """float32: the order of the sums; bfloat16: one rounding of the output
    (an 8-bit mantissa rounds by at most 2^-9 of the value; sums the
    kernels keep in float32 are held as float32)."""
    f32 = jnp.float32
    assert got.shape == want.shape, name
    largest = float(jnp.max(jnp.abs(want)))
    error = jnp.abs(got.astype(f32) - want)
    if dtype == "float32" or got.dtype == f32:
        assert got.dtype == f32, name
        assert float(jnp.max(error)) <= 2e-6 * max(1.0, largest), name
    else:
        assert got.dtype == dtype, name
        assert bool(jnp.all(error <= 2.0 ** -8 * jnp.abs(want)
                            + 1e-6 * largest)), name


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(PASSES))
def test_convolution_pass_kernels_match_the_jnp_form(case, dtype,
                                                     monkeypatch):
    """`gdn_conv_fwd` / `gdn_conv_bwd` under the interpreter against the
    `jnp` form in float32 on the same inputs: q, k, v, d qkv and d conv_w.
    The first taps - 1 positions against a history of zeros and the
    positions around a block's edge against the positions before it,
    written out."""
    monkeypatch.setattr(gated_deltanet, "_ROWS", 64)
    f32 = jnp.float32
    s, kh, kd, vh, vd = PASSES[case]
    keys = jax.random.split(jax.random.PRNGKey(11), 5)
    x = jax.random.normal(keys[0], (2, s, 2 * kh * kd + vh * vd)).astype(
        dtype)
    w = 0.5 * jax.random.normal(keys[1], (4, x.shape[-1]))
    # cotangents the outputs' dtype holds, so that both sides are given
    # the same ones
    weights = [jax.random.normal(key, (2, s, n)).astype(dtype).astype(f32)
               for key, n in zip(keys[2:], (kh * kd, kh * kd, vh * vd))]

    def run(impl, x):
        def weighted(x, w):
            outs = gated_deltanet.gdn_conv(
                x, w, key_heads=kh, key_dim=kd, value_dim=vd, eps=1e-6,
                impl=impl)
            return sum((o.astype(f32) * t).sum()
                       for o, t in zip(outs, weights)), outs

        (_, outs), grads = jax.value_and_grad(
            weighted, argnums=(0, 1), has_aux=True)(x, w)
        return (*outs, *grads)

    got = run("pallas_interpret", x)
    want = run("reference", x.astype(f32))
    for name, a, b in zip("q k v dqkv dconv_w".split(), got, want):
        _held_to(name, a, b, dtype)
    # v is SiLU of the taps' sum alone
    xv, wv = x.astype(f32)[..., 2 * kh * kd:], w[:, 2 * kh * kd:]
    for t in (0, 1, 2, 63, 64, 65, 66):
        if t >= s:
            continue
        pre = sum(wv[i] * xv[:, t - 3 + i] for i in range(4)
                  if t - 3 + i >= 0)
        _held_to(f"v at {t}", got[2][:, t], jax.nn.silu(pre), dtype)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(PASSES))
def test_gated_norm_pass_kernels_match_the_jnp_form(case, dtype,
                                                    monkeypatch):
    """`gdn_norm_fwd` / `gdn_norm_bwd` under the interpreter against the
    `jnp` form in float32 on the same inputs: the result, do, dz and
    d lin_norm, at a scale that is not its start."""
    monkeypatch.setattr(gated_deltanet, "_ROWS", 64)
    f32 = jnp.float32
    s, _, _, vh, vd = PASSES[case]
    keys = jax.random.split(jax.random.PRNGKey(12), 4)
    o, z = (jax.random.normal(key, (2, s, vh * vd)).astype(dtype)
            for key in keys[:2])
    scale = 1.0 + 0.3 * jax.random.normal(keys[2], (vd,))
    weight = jax.random.normal(keys[3], o.shape).astype(dtype).astype(f32)

    def run(impl, o, z):
        def weighted(o, z, scale):
            y = gated_deltanet.gdn_gated_norm(o, z, scale, eps=1e-6,
                                              impl=impl)
            return (y.astype(f32) * weight).sum(), y

        (_, y), grads = jax.value_and_grad(
            weighted, argnums=(0, 1, 2), has_aux=True)(o, z, scale)
        return (y, *grads)

    got = run("pallas_interpret", o, z)
    want = run("reference", o.astype(f32), z.astype(f32))
    for name, a, b in zip("y do dz dlin_norm".split(), got, want):
        _held_to(name, a, b, dtype)


def test_the_passes_kernels_refuse_a_width_they_do_not_take():
    """As the rule's: named, the kernels refuse a head width that is no
    whole number of 128-lane tiles, and "auto" takes the `jnp` form by the
    shape (here by the backend too)."""
    x = jnp.ones((1, 16, 2 * 2 * 16 + 4 * 16), jnp.bfloat16)
    w = jnp.ones((4, x.shape[-1]))
    kw = dict(key_heads=2, key_dim=16, value_dim=16, eps=1e-6)
    with pytest.raises(ValueError, match="whole 128-lane tiles"):
        gated_deltanet.gdn_conv(x, w, impl="pallas", **kw)
    with pytest.raises(ValueError, match="unknown impl"):
        gated_deltanet.gdn_conv(x, w, impl="mosaic", **kw)
    for a, b in zip(gated_deltanet.gdn_conv(x, w, impl="auto", **kw),
                    gated_deltanet.gdn_conv(x, w, impl="reference", **kw)):
        assert jnp.array_equal(a, b)
    o = jnp.ones((1, 16, 64), jnp.bfloat16)
    with pytest.raises(ValueError, match="whole 128-lane tiles"):
        gated_deltanet.gdn_gated_norm(o, o, jnp.ones((16,)), eps=1e-6,
                                      impl="pallas")


def test_delta_rule_kernels_refuse_a_width_they_do_not_take():
    """Asked for by name, the kernels refuse a head width that is no
    multiple of the 128 lanes; only "auto" falls to the `jnp` form by the
    shape (here by the backend too), so a measurement that named the
    kernels never reads the reference instead."""
    args, _ = _delta_rule_inputs(3, "bfloat16", 1.0, 1, 64, 1, 2, 16)
    with pytest.raises(ValueError, match="whole 128-lane tiles"):
        gated_delta_rule(*args, impl="pallas")
    with pytest.raises(ValueError, match="unknown impl"):
        gated_delta_rule(*args, impl="mosaic")
    auto = gated_delta_rule(*args, impl="auto")
    want = gated_delta_rule(*args, impl="reference")
    assert jnp.array_equal(auto, want)


def test_qwen3_next_period_train_step_fills_one_chip(v5e):
    """`qwen3next-steady`'s step: one period of Qwen3-Next at published
    widths (three Gated DeltaNet layers, one gated full-attention layer,
    each with a shared expert and 32 of 512 routed experts), 18,992 rows of
    embedding and untied head, float32 AdamW state, at the configuration's
    `batch_per_chip` rows of 8,192 tokens under "full" remat. Since PR 35
    (the Gated DeltaNet layer's two passes as kernels) it fits with nothing
    recomputed by the compiler on its own (PR 33: three [4, 8192, 12288]
    projections, a [4, 8192, 8192] pass and three [4, 8192, 2048] ones) and
    holds no copy of an activation around the passes; one row more, refused
    by 65 MB with the `jnp` delta rule (PR 32) and by 686 MB with its
    kernel pair (PR 33), compiles too — with no `.remat` from PR 35 to
    PR 51, with six since PR 52 (below): `batch_per_chip` is the
    benchmark's to change (PERF.md, section 7)."""
    one_chip = SingleDeviceSharding(v5e.devices[0])
    config = _qwen3_next_config()
    rows = config["batch_per_chip"]
    step, state, tokens = _qwen3_next_step(config, rows)
    assert tokens.shape == (rows, 8192)
    lowered = step.lower(_on(one_chip, state),
                         {"tokens": _on(one_chip, tokens)})
    # the held experts' walk takes trips of two sizes (PR 52: the chunk of
    # 40,960 rows and 30,720, midway to the balance), forward and backward,
    # in each of the period's four layers; the trips are jitted functions of
    # their arrays, so the step lowers each once a size, not once a layer
    private = collections.Counter(
        re.sub(r"_\d+$", "", name) for name in re.findall(
            r"func\.func private @(\w+)\(", lowered.as_text()))
    assert private["_held_trip"] == 2 and private["_held_trip_bwd"] == 2
    compiled = lowered.compile()
    # the flash kernels of the one full-attention layer: forward, the
    # forward recomputed under "full" remat, and the one backward kernel
    # (PR 38); the held experts' grouped matmuls are kernels too, inside
    # loops whose trip count follows the pairs routed here
    assert _kernel_names(compiled, "flash_") == [
        "flash_bwd", "flash_fwd", "flash_fwd"]
    # the delta rule's, three for each of the period's three Gated DeltaNet
    # layers (the scan is over periods; a period's layers are written out):
    # the primal forward, which writes no states, the `fwd` rule's forward
    # under "full" remat, which writes them, and the backward
    assert _kernel_names(compiled, "gdn_rule_") == (
        ["gdn_rule_bwd"] * 3 + ["gdn_rule_fwd"] * 6)
    # the passes around it, the same three to a layer: the convolution a
    # call each for q, k and v, the gated norm one
    assert _kernel_names(compiled, "gdn_conv_") == (
        ["gdn_conv_bwd"] * 9 + ["gdn_conv_fwd"] * 18)
    assert _kernel_names(compiled, "gdn_norm_") == (
        ["gdn_norm_bwd"] * 3 + ["gdn_norm_fwd"] * 6)
    text = compiled.as_text()
    assert "while(" in text
    # the held experts' rows return to token order by `ops.segment_sum`'s
    # kernel, a call a size of trip a walk (PR 36; two sizes, each a loop
    # of its own, since PR 52): the forward walk and the backward walk of
    # each of the period's four layers — the forward walk that "full" remat
    # would make again is dead code, the backward rule makes a trip's
    # products itself. PR 36's parent had a row scatter-add into
    # f32[32768,2048] in each of those eight places
    assert _kernel_names(compiled, "moe_segsum") == ["moe_segsum"] * 16
    assert not re.search(r"= f32\[32768,2048\]\S* scatter\(", text)
    # the router's top-10 of 512 is `ops.router_topk`'s kernel (PR 41), a
    # call a layer in the forward pass and one in its recomputation, under
    # the router's scope; its backward rule is compares and selects, no
    # kernel. The parent's program sorted f32[32768,512] rows there and
    # scattered [32768, 10] values into 16.7 M elements on the way back
    topk = _kernel_calls(compiled, "moe_topk_")
    assert _kernel_names(compiled, "moe_topk_") == ["moe_topk_rounds"] * 8
    assert all("/moe_router/" in line for line in topk)
    assert sum("rematted_computation" in line for line in topk) == 4
    assert not any("transpose(jvp" in line for line in topk
                   if "rematted_computation" not in line)
    router = [line for line in text.splitlines() if "/moe_router/" in line]
    assert router
    assert not [line for line in router
                if re.search(r" (sort|scatter)\(", line)
                and "[32768,512]" in line]
    # the compiler makes no room on its own any more (PERF.md, PR 29's
    # lesson), and between a layer's projection and its out-projection no
    # activation is copied, padded, sliced out, joined or transposed
    assert ".remat" not in text
    assert not _moved(text, rows)
    mem = compiled.memory_analysis()
    # the donated state is aliased to the new one: 12 bytes a parameter
    assert mem.alias_size_in_bytes > 7.4e9
    # the step's temporaries, 7.10 GiB (7.08 before PR 52's conditional,
    # 7.22 with its smaller trip at the balance's 20,480 rows, 8.87 in
    # PR 33, 9.62 in PR 32): one more [rows, 8192, 4096] bf16 array kept
    # across a layer is 0.25 GiB
    assert mem.temp_size_in_bytes < 7.4 * 2 ** 30
    step, state, tokens = _qwen3_next_step(config, rows + 1)
    compiled = step.lower(_on(one_chip, state),
                          {"tokens": _on(one_chip, tokens)}).compile()
    # 7.12 GiB beside 6.99 of donated state, of 15.75 — since PR 52 with
    # six arrays recomputed by the compiler to get there (it was 8.44 GiB
    # and none): three [5, 8192, 2048] products of the shared experts and
    # three [5, 8192, 8192] projections of the Gated DeltaNet layers. The
    # choice of a trip's size is a conditional, whose results (a trip's
    # three weight gradients, 67 MB each in bf16) stay until it ends, and
    # 5 rows had 0.3 GiB to spare
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < HBM_BYTES
    assert mem.temp_size_in_bytes < 7.7 * 2 ** 30
    recomputed = dict(re.findall(r"%(\S+\.remat\d*) = (\w+\[[\d,]+\])",
                                 compiled.as_text()))
    assert set(recomputed.values()) <= {"bf16[5,8192,2048]",
                                        "bf16[5,8192,8192]"}
    assert len(recomputed) <= 6
