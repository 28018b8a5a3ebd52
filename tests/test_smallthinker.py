"""A router that reads the mixer's normed input, ahead of attention, over
ReLU-gated experts, in periods of one full layer without positions and three
window layers (`layer_pattern=("full", "window", "window", "window")`,
`moe_router_input="mixer"`, `moe_activation="relu"`: SmallThinker's layer) on
the CPU at small sizes with seeded weights: the program against the plain
reference (`benchmarks/reference/smallthinker.py`) in float32; the published
form of the routing weights against the program's; padding and neighbours;
each planted fault of `benchmarks/tests/smallthinker_faults.py` over the
limits; four shares of a layer's experts adding up to the layer; what the
router's logits depend on; what trains and what is refused by name; a GQA
group of 7 under a 4,096-key window at 8,192 tokens against the masked dense
form; that the new fields at their defaults add no weight to a configuration
the benchmark has; and, closing the file, the served cell's largest and
smallest buckets compiled for a described v5e (`_chip.py` says why here)."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from _chip import (_grouped_matmul_weights, _kernel_names,  # noqa: E402,F401
                   _writes_of, benchmark_config, served_bucket, v5e)
from benchmarks.reference import (smallthinker,            # noqa: E402
                                  smallthinker_glue)
from benchmarks.tests import smallthinker_faults           # noqa: E402
from ray_tpu.models import moe                             # noqa: E402
from ray_tpu.models.gpt import (GPT, GPTConfig,            # noqa: E402
                                _layer_weights)
from ray_tpu.ops.attention import dot_product_attention    # noqa: E402

WINDOW = 64
# the published keys the reference reads, at the toy widths: two periods
PUBLISHED = dict(
    num_attention_heads=14, num_key_value_heads=2, rms_norm_eps=1e-6,
    rope_theta=1500000, sliding_window_size=WINDOW,
    moe_num_active_primary_experts=6, moe_primary_router_apply_softmax=True,
    norm_topk_prob=True, rope_scaling=None, num_hidden_layers=8,
    sliding_window_layout=[0, 1, 1, 1] * 2, rope_layout=[0, 1, 1, 1] * 2)


def _config(impl="reference", **kw):
    base = dict(
        vocab_size=512, n_layers=8, d_model=128, n_heads=14, n_kv_heads=2,
        d_head=32, d_ff=32, max_seq_len=1024,
        layer_pattern=("full", "window", "window", "window"),
        attn_window=WINDOW, activation="swiglu", norm="rmsnorm",
        norm_eps=1e-6, positions="rope", rope_theta=1.5e6,
        rope_layers=("window",), tie_embeddings=False, n_experts=64,
        moe_top_k=6, moe_score="softmax", moe_norm_topk_prob=True,
        moe_router_input="mixer", moe_activation="relu",
        z_loss=0.0, moe_aux_coeff=0.0, dtype=jnp.float32,
        param_dtype=jnp.float32, remat=False, attention_impl=impl)
    base.update(kw)
    return GPTConfig(**base)


def _tokens(rows, length, seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), (rows, length), 0,
                              512)


def _scores_of(model, params, tokens):
    logits = jax.jit(model.apply)(params, tokens)[:, :-1]
    return (jnp.take_along_axis(logits, tokens[:, 1:, None], -1)[..., 0]
            - jax.nn.logsumexp(logits, -1))


def _reference_scores(params, tokens, published=PUBLISHED, **kw):
    top, layers = smallthinker_glue.reference_weights(params, None,
                                                      jax.devices())
    return smallthinker.token_logprobs(tokens, top, layers, published, **kw)


def _rms(gap):
    return float(jnp.sqrt((gap ** 2).mean()))


# ------------------------------------------ the program and the reference

@pytest.mark.parametrize("impl", ["reference", "pallas_interpret"])
def test_the_program_scores_as_the_plain_reference_does(impl):
    """Float32 on both sides, the same seeded weights through the glue, two
    periods: the log-probabilities differ by the order of the sums (1e-6
    read; 5e-5 is the limit, a twentieth of the weakest planted fault's
    root mean square below)."""
    model = GPT(_config(impl))
    params = model.init(jax.random.PRNGKey(0))
    tokens = _tokens(2, 300)
    got = _scores_of(model, params, tokens)
    want = _reference_scores(params, tokens)
    assert got.shape == want.shape == (2, 299)
    assert float(jnp.abs(got - want).max()) < 5e-5


@pytest.mark.parametrize("fault", smallthinker_faults.FAULTS)
def test_a_planted_fault_fails_the_limits(fault):
    """Each mechanism left out or done wrong moves a token's log-probability
    by more than 0.002 at its widest and 0.0005 in the root mean square: in
    float32 the sound program reads 1e-6, and the weakest fault, RoPE on
    the two full layers of eight, 0.0045 and 0.0012 (the others 0.013 to
    0.58 and 0.004 to 0.12). The seeded start's branches write little into
    the stream at this width; the chip's readings at the published widths
    are in `configs/smallthinker_21b_a3b.serve.json`."""
    model = GPT(_config())
    tokens = _tokens(2, 300)
    params = model.init(jax.random.PRNGKey(0))
    want = _reference_scores(params, tokens)
    getattr(smallthinker_faults, fault)()
    try:
        got = _scores_of(GPT(_config()), params, tokens)
    finally:
        smallthinker_faults.restore()
    gap = jnp.abs(got - want)
    assert float(gap.max()) > 0.002 and _rms(gap) > 0.0005, (
        fault, float(gap.max()), _rms(gap))
    sound = jnp.abs(_scores_of(model, params, tokens) - want)
    assert float(sound.max()) < 5e-5


def test_softmax_over_the_chosen_is_the_full_softmaxs_six_rescaled():
    """The published form (the six largest logits, a softmax over them:
    `reference/smallthinker.py::_routing`) and the program's (a softmax over
    all 64, its six largest divided by their sum: `moe._route` under
    `moe_score="softmax"`, `moe_norm_topk_prob=True`) give the same experts
    and, to float32's rounding, the same weights."""
    logits = 3.0 * jax.random.normal(jax.random.PRNGKey(3), (200, 64))
    _, vals, idx = moe._route(logits, 6, True, "softmax", None, 1.0,
                              "reference")
    weights, chose = smallthinker._routing(
        logits, {"block_sparse_moe.primary_router": jnp.eye(64)},
        dict(PUBLISHED))
    dense = jnp.zeros((200, 64)).at[jnp.arange(200)[:, None], idx].set(vals)
    assert bool((chose == (dense > 0)).all())
    assert float(jnp.abs(dense - weights).max()) < 1e-6
    assert float(jnp.abs(vals.sum(-1) - 1.0).max()) < 1e-6
    # and the full softmax's six as they are sum to less
    _, raw, _ = moe._route(logits, 6, False, "softmax", None, 1.0,
                           "reference")
    assert float(raw.sum(-1).max()) < 0.999


@pytest.mark.parametrize("impl", ["reference", "pallas_interpret"])
def test_padding_and_a_neighbour_row_change_no_real_score(impl):
    """A document scored alone, right-padded to a bucket and beside another
    row: the same scores at its real positions (causal masks and a router
    that looks at one token at a time; 5e-5 for the sums' order, which the
    longer row changes)."""
    model = GPT(_config(impl, n_layers=4))
    params = model.init(jax.random.PRNGKey(0))
    doc = _tokens(1, 100)
    alone = _scores_of(model, params, doc)
    bucket = jnp.concatenate([jnp.pad(doc, ((0, 0), (0, 28))),
                              _tokens(1, 128, seed=9)])
    padded = _scores_of(model, params, bucket)[:1, :99]
    assert float(jnp.abs(padded - alone).max()) < 5e-5


def test_four_shares_of_the_experts_add_up_to_the_layer():
    """16 of 64 experts held, four times over (`moe_first_expert` 0, 16, 32,
    48): every share routes over all 64 from the mixer's normed input and
    computes its own experts' part, so the four FFN outputs add up to the
    whole layer's — what every share computes alike (the stream after
    attention) counted once."""
    whole = GPT(_config(n_layers=4, layer_pattern=("window",)))
    params = whole.init(jax.random.PRNGKey(0))
    tokens = _tokens(2, 128)
    x = params["tok_embed"][tokens]
    positions = jnp.broadcast_to(jnp.arange(128), tokens.shape)
    w = jax.tree_util.tree_map(lambda a: a[1], params["blocks"])

    def block(model, w):
        return jax.jit(lambda x: model._block(x, positions, w,
                                              kind="window"))(x)

    full, aux = block(whole, w)
    mixed = whole._full_mixer(x, positions, w, kind="window")[0]
    parts, given = [], []
    for first in range(0, 64, 16):
        share = GPT(_config(n_layers=4, layer_pattern=("window",),
                            moe_first_expert=first, moe_experts_held=16))
        held = {**w, **{name: w[name][first:first + 16]
                        for name in ("w_up", "w_gate", "w_down")}}
        out, facts = block(share, held)
        parts.append(out - mixed)
        given.append(int(facts["moe_routed_here"]))
        assert bool((facts["moe_expert_choice"]
                     == aux["moe_expert_choice"]).all())
    assert sum(given) == 2 * 128 * 6
    assert float(jnp.abs(sum(parts) - (full - mixed)).max()) < 2e-5 * float(
        jnp.abs(full - mixed).max())


# ----------------------------------------- where the router's input is from

def test_the_routers_logits_come_from_norm1_and_nothing_after_it():
    """`moe_router_z` is the mean squared logsumexp of a layer's router
    logits: a function of the logits alone. Under the early router its
    derivative by `norm2`'s scale and by every weight of the attention is
    exactly zero — the router's product takes the mixer's normed input and
    nothing attention made — and by `norm1`'s scale it is not; under the
    default it is the other way round."""
    tokens = _tokens(1, 64)

    def z_grads(**kw):
        model = GPT(_config(n_layers=1, layer_pattern=("full",),
                            rope_layers=(), **kw))
        params = model.init(jax.random.PRNGKey(0))
        return jax.grad(lambda p: model.forward_with_aux(p, tokens)[1][
            "moe_router_z"])(params)["blocks"]

    early, late = z_grads(), z_grads(moe_router_input="ffn")
    for name in ("norm2", "wq", "wk", "wv", "wo"):
        assert not np.asarray(early[name]).any(), name
        assert np.asarray(late[name]).any(), name
    assert np.asarray(early["norm1"]).any()
    assert np.asarray(early["router"]).any()
    assert np.asarray(late["norm1"]).any()      # through the stream

    # and in the traced block of the early layer the router's product (the
    # one `HIGHEST` matmul) takes a float32 cast of the very variable the q
    # projection multiplies: norm1's output
    model = GPT(_config(n_layers=4))
    params = model.init(jax.random.PRNGKey(0))
    w = jax.tree_util.tree_map(lambda a: a[0, 0], params["blocks"]["full"])
    eqns = jax.make_jaxpr(lambda x, w: model._block(
        x, jnp.arange(64)[None], w, kind="full"))(
            params["tok_embed"][tokens], w).jaxpr.eqns
    made_by = {out: eqn for eqn in eqns for out in eqn.outvars}
    dots = [e for e in eqns if e.primitive.name == "dot_general"]
    router = next(e for e in dots
                  if "HIGHEST" in str(e.params["precision"]))
    q_proj = next(e for e in dots if e is not router)

    def source(var):
        while var in made_by and made_by[var].primitive.name in (
                "convert_element_type", "reshape"):
            var = made_by[var].invars[0]
        return var

    assert source(router.invars[0]) is source(q_proj.invars[0])


# ----------------------------------------------- what trains, what does not

def test_the_reference_form_differentiates_as_the_plain_reference_does():
    """`GPT.loss` under `attention_impl="reference"` against `jax.grad` of
    the plain reference's mean log-probability: float32, relative to each
    leaf's largest entry (1e-4: sums in another order). The router's weight
    and `norm1`'s scale are among the leaves: the router's gradient reaches
    `norm1` through the early tap alone."""
    # sixteen experts, four a token, one period of (full, window): the
    # reference's pass over the experts, differentiated, is most of this
    # test's time
    model = GPT(_config(n_experts=16, moe_top_k=4, n_layers=2,
                        layer_pattern=("full", "window")))
    params = model.init(jax.random.PRNGKey(0))
    tokens = _tokens(1, 96)
    loss, metrics = model.loss(params, {"tokens": tokens})
    assert float(loss) == pytest.approx(float(metrics["ce_loss"]))
    published = dict(PUBLISHED, moe_num_active_primary_experts=4,
                     num_hidden_layers=2, sliding_window_layout=[0, 1],
                     rope_layout=[0, 1])

    def plain(p):
        return -_reference_scores(p, tokens, published, capacity=96).mean()

    got = jax.jit(jax.grad(
        lambda p: model.loss(p, {"tokens": tokens})[0]))(params)
    want = jax.jit(jax.grad(plain))(params)
    assert float(loss) == pytest.approx(float(plain(params)), rel=1e-5)
    seen = set()
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want)):
        name = jax.tree_util.keystr(path)
        seen.add(name.split("'")[-2])
        assert float(jnp.abs(b).max()) > 0, name
        assert float(jnp.abs(a - b).max()) <= 1e-4 * float(
            jnp.abs(b).max()) + 1e-9, name
    assert {"router", "norm1", "norm2", "w_gate"} <= seen


@pytest.mark.parametrize("impl", ["pallas", "pallas_interpret"])
def test_the_kernels_refuse_to_train_a_window_by_name(impl):
    model = GPT(_config(impl, n_layers=4))
    with pytest.raises(NotImplementedError, match="no backward kernel"):
        model.loss(model.init(jax.random.PRNGKey(0)),
                   {"tokens": _tokens(1, 64)})


def test_what_the_configuration_refuses():
    with pytest.raises(ValueError, match="moe_router_input"):
        _config(moe_router_input="attention")
    with pytest.raises(ValueError, match="needs experts"):
        _config(n_experts=0)
    with pytest.raises(ValueError, match='not "linear"'):
        _config(layer_pattern=("linear", "full"), linear_key_heads=2,
                linear_value_heads=2, linear_key_dim=16, linear_value_dim=16)
    with pytest.raises(ValueError, match="moe_activation 'gelu'"):
        _config(moe_activation="gelu")


def test_relu_is_what_every_path_of_the_experts_computes():
    """The three branches of `_swiglu_groups` (bare, weighted over weights
    in the rows' dtype, weighted over weights cast on the way in), the held
    walk and the shared expert under `act="relu"`, each against the formula
    written out with `jnp.maximum` — and against SiLU's, which they are
    not."""
    keys = jax.random.split(jax.random.PRNGKey(5), 6)
    e, d, f, t = 4, 32, 128, 64
    x = jax.random.normal(keys[0], (1, t, d))
    router = jax.random.normal(keys[1], (d, e))
    w_up, w_gate = (jax.random.normal(k, (e, d, f)) * 0.2 for k in keys[2:4])
    w_down = jax.random.normal(keys[4], (e, f, d)) * 0.2

    def plain(opened):
        probs = jax.nn.softmax(x[0] @ router, -1)
        vals, idx = jax.lax.top_k(probs, 2)
        vals = vals / vals.sum(-1, keepdims=True)
        every = jnp.einsum(
            "tef,efd->ted", opened(jnp.einsum("td,edf->tef", x[0], w_gate))
            * jnp.einsum("td,edf->tef", x[0], w_up), w_down)
        return (jnp.take_along_axis(every, idx[..., None], 1)
                * vals[..., None]).sum(1)

    relu, silu = plain(lambda g: jnp.maximum(g, 0.0)), plain(jax.nn.silu)
    assert float(jnp.abs(relu - silu).max()) > 0.1

    def ffn(**kw):
        return moe.moe_ffn(x, router, kw.pop("w_up", w_up),
                           kw.pop("w_gate", w_gate),
                           kw.pop("w_down", w_down), top_k=2,
                           impl="reference", act="relu", **kw)[0][0]

    with jax.default_matmul_precision("highest"):
        # weights in the rows' dtype: three matmuls; cast on the way in
        # (float32 masters under bf16 rows): the joined one
        assert float(jnp.abs(ffn(dtype=jnp.float32) - relu).max()) < 1e-4
        joined = ffn(dtype=jnp.bfloat16).astype(jnp.float32)
        assert float(jnp.abs(joined - relu).max()) < 0.05 * float(
            jnp.abs(relu).max())
        assert float(jnp.abs(joined - relu).max()) < 0.5 * float(
            jnp.abs(joined - silu).max())
        halves = [ffn(dtype=jnp.float32, first_expert=first,
                      w_up=w_up[first:first + 2],
                      w_gate=w_gate[first:first + 2],
                      w_down=w_down[first:first + 2]) for first in (0, 2)]
        assert float(jnp.abs(sum(halves) - relu).max()) < 1e-4
        rows = x[0]
        bare = moe._swiglu_groups(rows, w_up[:1], w_gate[:1], w_down[:1],
                                  jnp.array([t]), act="relu")
        want = (jnp.maximum(rows @ w_gate[0], 0) * (rows @ w_up[0])
                ) @ w_down[0]
        assert float(jnp.abs(bare - want).max()) < 1e-4
        shared = moe.shared_expert_ffn(x, w_up[0], w_gate[0], w_down[0],
                                       dtype=jnp.float32, act="relu")[0]
        assert float(jnp.abs(shared - want).max()) < 1e-4
    # the joined branch differentiates by the step
    grads = jax.grad(lambda g: moe.moe_ffn(
        x.astype(jnp.bfloat16), router, w_up, g, w_down, top_k=2,
        impl="reference", act="relu", dtype=jnp.bfloat16)[0].astype(
            jnp.float32).sum())(w_gate)
    want = jax.grad(lambda g: moe.moe_ffn(
        x, router, w_up, g, w_down, top_k=2, impl="reference", act="relu",
        dtype=jnp.float32)[0].sum())(w_gate)
    assert float(jnp.abs(grads - want).max()) < 0.05 * float(
        jnp.abs(want).max())
    with pytest.raises(ValueError, match="moe_activation 'tanh'"):
        moe.moe_ffn(x, router, w_up, w_gate, w_down, act="tanh")


# --------------------------- a group of 7 under the 4,096-key window

def _masked_dense(q, k, v, window, block=1024):
    """softmax(q k^T / sqrt(d)) v over t - window < s <= t, a block of
    queries at a time; q [1, H, S, D], k and v [1, Hk, S, D]."""
    group = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(a[0], group, axis=0) for a in (k, v))
    s = q.shape[2]
    key = jnp.arange(s)[None]
    out = []
    for first in range(0, s, block):
        t = first + jnp.arange(block)[:, None]
        keep = (key <= t) & (key > t - window)
        scores = jnp.einsum("hqd,hkd->hqk", q[0, :, first:first + block],
                            k) / np.sqrt(q.shape[-1])
        p = jax.nn.softmax(jnp.where(keep[None], scores, -jnp.inf), -1)
        out.append(jnp.einsum("hqk,hkd->hqd", p, v))
    return jnp.concatenate(out, 1)[None]


def test_a_group_of_seven_under_the_models_window_at_8192():
    """7 query heads on one key-value head, a window of 4,096 keys, 8,192
    tokens (two of the served buckets' three lengths hold such a band; at
    4,096 the band is the whole triangle): `flash_fwd_window` under the
    interpreter at the kernel's own tiles against the masked dense form,
    float32 (2e-5: the order of the sums)."""
    keys = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(keys[0], (1, 7, 8192, 64), jnp.float32)
    k, v = (jax.random.normal(key, (1, 1, 8192, 64), jnp.float32)
            for key in keys[1:])
    got = dot_product_attention(q, k, v, impl="pallas_interpret",
                                window=4096)
    want = _masked_dense(q, k, v, 4096)
    assert float(jnp.abs(got - want).max()) < 2e-5


# ------------------------- what the benchmark's configurations were, stays

@pytest.mark.parametrize("name", ["gpt2_medium", "gpt2_xl", "olmoe_1b_7b",
                                  "qwen3_next_80b_a3b", "keye_vl_2_30b_a3b",
                                  "trinity_mini"])
def test_the_new_fields_at_their_defaults_add_no_weight(name):
    """A configuration the benchmark had names neither new field, routes
    from the FFN's own input and gates by SiLU, and declares the weights it
    declared: the early router and ReLU add none in any case (they move a
    tap and change a function). Bit-equal logits and gradients against the
    parent commit were read once with the parent beside the change
    (CHANGES.md, PR 57); the jaxprs `test_moe_stack.py`, `test_models.py`,
    `test_keye_vl2.py` and `test_trinity_mini.py` pin are the parent's."""
    kw = dict(benchmark_config(name)["model"])
    assert "moe_router_input" not in kw and "moe_activation" not in kw
    kw["dtype"] = getattr(jnp, kw["dtype"])
    kw["param_dtype"] = getattr(jnp, kw["param_dtype"])
    config = GPTConfig(**kw)
    assert (config.moe_router_input, config.moe_activation) == ("ffn", "silu")
    if config.n_experts and "linear" not in config.kinds:
        import dataclasses
        early = dataclasses.replace(config, moe_router_input="mixer",
                                    moe_activation="relu")
        for kind in set(config.layer_pattern):
            assert _layer_weights(early, kind) == _layer_weights(config, kind)


# ------------------------------------- the served buckets, for the chip

def test_the_largest_served_bucket_compiles_and_fits_a_v5e(v5e):
    """`benchmarks/configs/smallthinker_21b_a3b.json` as
    `loops/serve.py::Scorer` builds it (bfloat16 weights, the bucket
    program's own text) at the largest bucket of the cell's traffic file,
    2 x 16,384: a period written out inside the scan over the two periods —
    the full kernel once, the windowed one three times — and by the
    compiler's account the program beside its 6.57 GB of weights."""
    with open(os.path.join(ROOT, "benchmarks/traffic/"
                           "serve-score-16k-steady-over-v19k.json")) as f:
        batching = json.load(f)["batching"]
    rows, length = max(batching["rows"]), max(batching["lengths"])
    assert (rows, length) == (2, 16384)
    params, compiled = served_bucket(v5e, "smallthinker_21b_a3b", rows,
                                     length)
    weights = sum(x.size * 2 for x in jax.tree_util.tree_leaves(params))
    assert 6.56e9 < weights < 6.59e9        # 3.29 B parameters in bfloat16
    assert _kernel_names(compiled, "flash_") == [
        "flash_fwd"] + ["flash_fwd_window"] * 3
    # a router of 64 outputs is no whole lane tile: `lax.top_k`, no kernel
    assert _kernel_names(compiled, "moe_") == []
    mem = compiled.memory_analysis()
    total = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert 9.0e9 < total < 13.0e9, total


def test_the_smallest_served_bucket_copies_no_expert_weight(v5e):
    """The 1 x 4,096 bucket's program: the period's experts are stacks
    [2, 1, 64, ...] and [2, 3, 64, ...] read where they lie (`moe.moe_ffn`'s
    `layer`) — each of the twelve grouped matmuls of a period written out
    takes the program's own parameter, and nothing writes an array of a
    layer's experts. At 4,096 tokens the window's band is the whole
    triangle, and a window layer still runs under `flash_fwd_window`'s
    name."""
    _, compiled = served_bucket(v5e, "smallthinker_21b_a3b", 1, 4096)
    assert _grouped_matmul_weights(compiled) == ["parameter"] * 12
    assert _writes_of(compiled, "bf16[64,2560,768]",
                      "bf16[64,768,2560]") == []
    assert _kernel_names(compiled, "flash_") == [
        "flash_fwd"] + ["flash_fwd_window"] * 3
