"""Window and full layers mixed (`layer_pattern=("window", "window",
"window", "full")` after a leading dense "window" layer: Trinity-Mini's
layer) on the CPU at small sizes with seeded weights: the forward kernel's
walk of a window's band against the masked `jnp` form, and its count of
rectangles against a brute one; the program against the plain reference
(`benchmarks/reference/trinity_mini.py`) in float32 with a non-zero selection
bias; padding and neighbours; each planted fault of
`benchmarks/tests/trinity_faults.py` over the limits; what trains and what is
refused by name; that the new fields at their defaults add no weight to a
configuration the benchmark has; and, closing the file, the served cell's
largest bucket compiled for a described v5e (`_chip.py` says why here)."""

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
from benchmarks.reference import (trinity_mini,            # noqa: E402
                                  trinity_mini_glue)
from benchmarks.tests import trinity_faults                # noqa: E402
from ray_tpu.models.gpt import (GPT, GPTConfig,            # noqa: E402
                                _layer_weights)
from ray_tpu.ops.attention import (attention_reference,    # noqa: E402
                                   chunk_classes, dot_product_attention)

WINDOW = 64
# the published keys the reference reads, at the toy widths; `layers_held`:
# the published model's layers 0 and 4..7
PUBLISHED = dict(
    num_attention_heads=8, num_key_value_heads=2, rms_norm_eps=1e-5,
    rope_theta=10000, sliding_window=WINDOW, num_experts_per_tok=8,
    route_norm=True, route_scale=2.826, score_func="sigmoid",
    num_shared_experts=1, n_group=1, topk_group=1, hidden_size=128,
    mup_enabled=True, num_hidden_layers=5, num_dense_layers=1,
    layer_types=["sliding_attention"] * 3 + ["full_attention"]
    + ["sliding_attention"] * 3 + ["full_attention"],
    layers_held=[0, 4, 5, 6, 7])


def _config(impl="reference", **kw):
    base = dict(
        vocab_size=512, n_layers=5, d_model=128, n_heads=8, n_kv_heads=2,
        d_head=32, d_ff=64, max_seq_len=1024,
        layer_pattern=("window", "window", "window", "full"),
        lead_layers=("window",), lead_d_ff=192, attn_window=WINDOW,
        activation="swiglu", norm="rmsnorm", norm_eps=1e-5,
        positions="rope", rope_theta=10000.0, rope_layers=("window",),
        tie_embeddings=False, qk_norm="head", attn_gate=True, post_norm=True,
        embed_scale=128 ** 0.5, n_experts=128, moe_top_k=8,
        moe_score="sigmoid", moe_select_bias=True, moe_route_scale=2.826,
        moe_shared_ff=64, moe_shared_gate=False, z_loss=0.0,
        dtype=jnp.float32, param_dtype=jnp.float32, remat=False,
        attention_impl=impl)
    base.update(kw)
    return GPTConfig(**base)


def _params(model, seed=0):
    """Seeded weights with a selection bias that is not zero."""
    trinity_faults.seeded_bias()
    try:
        return model.init(jax.random.PRNGKey(seed))
    finally:
        trinity_faults.restore()


def _tokens(rows, length, seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), (rows, length), 0,
                              512)


def _scores_of(model, params, tokens):
    logits = jax.jit(model.apply)(params, tokens)[:, :-1]
    return (jnp.take_along_axis(logits, tokens[:, 1:, None], -1)[..., 0]
            - jax.nn.logsumexp(logits, -1))


def _reference_scores(params, tokens, published=PUBLISHED, **kw):
    top, layers = trinity_mini_glue.reference_weights(params, None,
                                                      jax.devices())
    return trinity_mini.token_logprobs(tokens, top, layers, published, **kw)


# ------------------------------------------------- the walk of the band

@pytest.mark.parametrize("length,window,tile", [
    (1024, 512, 256),       # aligned: a diagonal, a whole and a crossed tile
    (1024, 300, 256),       # a window that is no multiple of tile or chunk
    (1024, 640, 256),       # two tiles and a half: two crossed tiles
    (1024, 100, 256),       # a window inside one rectangle
    (900, 300, 256),        # ragged: the looped walk on traced bounds
    (512, 2048, 256),       # a row shorter than the window
    (384, 200, 1024),       # one tile spans the row: a static place
], ids=lambda v: str(v))
def test_the_windowed_walk_is_the_masked_dense_form(length, window, tile):
    """`flash_fwd_window` against `attention_reference(window=)`: float32,
    so the two differ by the order of their sums alone (2e-5 holds every
    case at 1e-6 read)."""
    keys = jax.random.split(jax.random.PRNGKey(length + window), 3)
    q = jax.random.normal(keys[0], (1, 4, length, 64), jnp.float32)
    k, v = (jax.random.normal(key, (1, 2, length, 64), jnp.float32)
            for key in keys[1:])
    got = dot_product_attention(q, k, v, impl="pallas_interpret",
                                block_q=tile, block_k=tile, window=window)
    want = attention_reference(q, k, v, window=window)
    assert float(jnp.abs(got - want).max()) < 2e-5
    # and a window is not the triangle, where it is shorter than the row
    if window < length:
        assert float(jnp.abs(attention_reference(q, k, v) - want).max()) > .1


def test_a_windowed_walk_in_the_projections_own_layout():
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    q, k, v = (jax.random.normal(key, (2, 512, 2, 64), jnp.float32)
               for key in keys)
    got = dot_product_attention(q, k, v, impl="pallas_interpret", block_q=256,
                                block_k=256, seq_major=True, window=200)
    want = dot_product_attention(q, k, v, impl="reference", seq_major=True,
                                 window=200)
    assert float(jnp.abs(got - want).max()) < 2e-5


def _brute_classes(length, window, size=128):
    q, k = np.arange(length)[:, None], np.arange(length)[None]
    keep = (k <= q) & (k > q - window)
    counts = {"dead": 0, "interior": 0, "edge": 0}
    for r in range(0, length, size):
        for c in range(0, length, size):
            rect = keep[r:r + size, c:c + size]
            counts["dead" if not rect.any() else
                   "interior" if rect.all() else "edge"] += 1
    return counts


@pytest.mark.parametrize("length,window,tile", [
    (2048, 512, 512), (2048, 300, 512), (1024, 100, 256),
    (4096, 2048, 1024)], ids=lambda v: str(v))
def test_chunk_classes_counts_the_band(length, window, tile):
    got = chunk_classes(length, length, True, tile=tile, window=window)
    assert {k: got[k] for k in ("dead", "interior", "edge")} == \
        _brute_classes(length, window)
    whole = chunk_classes(length, length, True, tile=tile)
    assert got["dead"] > whole["dead"]      # the rectangles older than it


def test_the_served_rows_band_is_a_quarter_of_its_triangle():
    band = chunk_classes(16384, 16384, True, window=2048)
    whole = chunk_classes(16384, 16384, True)
    assert band["interior"] + band["edge"] == 2040
    assert whole["interior"] + whole["edge"] == 8256


def test_a_window_wants_a_causal_query_and_takes_no_choice():
    q = jnp.zeros((1, 2, 256, 64))
    with pytest.raises(ValueError, match="causal"):
        dot_product_attention(q, q, q, causal=False,
                              impl="pallas_interpret", window=64)
    with pytest.raises(NotImplementedError, match="no backward kernel"):
        jax.grad(lambda q: dot_product_attention(
            q, q, q, impl="pallas_interpret", window=64).sum())(q)


# ------------------------------------------ the program and the reference

@pytest.mark.parametrize("impl", ["reference", "pallas_interpret"])
def test_the_program_scores_as_the_plain_reference_does(impl):
    """Float32 on both sides, the same seeded weights through the glue, a
    selection bias that is not zero: the log-probabilities differ by the
    order of the sums (1e-6 read; 2e-5 is the limit, a thousandth of the
    weakest planted fault's reading below)."""
    model = GPT(_config(impl))
    params = _params(model)
    tokens = _tokens(2, 300)
    got = _scores_of(model, params, tokens)
    want = _reference_scores(params, tokens)
    assert got.shape == want.shape == (2, 299)
    assert float(jnp.abs(got - want).max()) < 2e-5


@pytest.mark.parametrize("fault", trinity_faults.FAULTS)
def test_a_planted_fault_fails_the_limits(fault):
    """Each mechanism left out or done wrong moves a token's log-probability
    by more than the limits the rehearsal's serve file states (widest gap
    0.02, root mean square 0.002: a thousand times the sound program's
    reading, a fifth of the weakest fault's, `shared_expert_gated` at 0.012
    rms)."""
    model = GPT(_config())
    tokens = _tokens(2, 300)
    seeded = fault == "seeded_bias_in_the_weights"
    params = _params(model) if seeded else model.init(jax.random.PRNGKey(0))
    want = _reference_scores(params, tokens)
    getattr(trinity_faults, fault)()
    try:
        got = _scores_of(GPT(_config()), params, tokens)
    finally:
        trinity_faults.restore()
    gap = jnp.abs(got - want)
    assert float(gap.max()) > 0.02 and float(
        jnp.sqrt((gap ** 2).mean())) > 0.002, (
            fault, float(gap.max()), float(jnp.sqrt((gap ** 2).mean())))
    sound = jnp.abs(_scores_of(model, params, tokens) - want)
    assert float(sound.max()) < 2e-5


@pytest.mark.parametrize("impl", ["reference", "pallas_interpret"])
def test_padding_and_a_neighbour_row_change_no_real_score(impl):
    """A document scored alone, right-padded to a bucket and beside another
    row: the same scores at its real positions (causal masks and a router
    that looks at one token at a time; 2e-5 for the sums' order, which the
    longer row changes)."""
    model = GPT(_config(impl))
    params = _params(model)
    doc = _tokens(1, 100)
    alone = _scores_of(model, params, doc)
    bucket = jnp.concatenate([jnp.pad(doc, ((0, 0), (0, 28))),
                              _tokens(1, 128, seed=9)])
    padded = _scores_of(model, params, bucket)[:1, :99]
    assert float(jnp.abs(padded - alone).max()) < 2e-5


def test_a_layer_reports_its_bands_rectangles_and_its_experts_tokens():
    model = GPT(_config("pallas_interpret"))
    tokens = _tokens(2, 256)
    _, aux = jax.jit(model.forward_with_aux)(_params(model), tokens)
    band = chunk_classes(256, 256, True, window=WINDOW)
    rects = 2 * 8 * (band["interior"] + band["edge"])
    # the leading layer, then the period: window, window, window, full
    assert aux["attn_window_rects"].tolist() == [rects] * 4 + [0]
    # the router's facts are the routed layers': a sigmoid has no loss
    assert aux["moe_expert_tokens"].shape == (4, 128)
    assert aux["moe_expert_tokens"].sum(-1).tolist() == [2 * 256 * 8] * 4
    assert "moe_aux_loss" not in aux and "moe_router_z" not in aux


# ----------------------------------------------- what trains, what does not

def test_the_reference_form_differentiates_as_the_plain_reference_does():
    """`GPT.loss` under `attention_impl="reference"` against `jax.grad` of
    the plain reference's mean log-probability: float32, relative to each
    leaf's largest entry (1e-4: sums in another order, read 2e-6)."""
    # sixteen experts, four a token: the reference's pass over the experts,
    # differentiated, is most of this test's time
    # and one period of (window, full): the published layers 0, 6 and 7
    model = GPT(_config(n_experts=16, moe_top_k=4, n_layers=3,
                        layer_pattern=("window", "full")))
    params = _params(model)
    tokens = _tokens(1, 96)
    loss, metrics = model.loss(params, {"tokens": tokens})
    assert "moe_aux_loss" not in metrics and float(loss) == pytest.approx(
        float(metrics["ce_loss"]))
    published = dict(PUBLISHED, num_experts_per_tok=4, num_hidden_layers=3,
                     layers_held=[0, 6, 7])

    def plain(p):
        return -_reference_scores(p, tokens, published, capacity=96).mean()

    got = jax.jit(jax.grad(
        lambda p: model.loss(p, {"tokens": tokens})[0]))(params)
    want = jax.jit(jax.grad(plain))(params)
    assert float(loss) == pytest.approx(float(plain(params)), rel=1e-5)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want)):
        name = jax.tree_util.keystr(path)
        if "router_bias" in name:       # no gradient's weight
            assert not np.asarray(a).any(), name
            continue
        assert float(jnp.abs(a - b).max()) <= 1e-4 * float(
            jnp.abs(b).max()) + 1e-9, name


@pytest.mark.parametrize("impl", ["pallas", "pallas_interpret"])
def test_the_kernels_refuse_to_train_a_window_by_name(impl):
    model = GPT(_config(impl))
    with pytest.raises(NotImplementedError, match="no backward kernel"):
        model.loss(model.init(jax.random.PRNGKey(0)),
                   {"tokens": _tokens(1, 64)})


def test_what_the_configuration_refuses():
    with pytest.raises(ValueError, match="attn_window"):
        _config(attn_window=0)
    with pytest.raises(ValueError, match="leading"):
        _config(n_layers=6)
    with pytest.raises(ValueError, match="softmax"):
        jax.eval_shape(GPT(_config(moe_score="tanh")).apply,
                       GPT(_config()).init(jax.random.PRNGKey(0)),
                       _tokens(1, 64))


# ------------------------- what the benchmark's configurations were, stays

_ADDED = {"norm1_post", "norm2_post", "bias1_post", "bias2_post",
          "router_bias"}


@pytest.mark.parametrize("name", ["gpt2_medium", "gpt2_xl", "olmoe_1b_7b",
                                  "qwen3_next_80b_a3b", "keye_vl_2_30b_a3b"])
def test_the_new_fields_at_their_defaults_add_no_weight(name):
    """A configuration the benchmark had declares the weights it declared:
    none of the new ones, no leading layer, a gated shared expert where it
    has one, and a softmax router's two losses. (Bit-equal parameters,
    logits and losses against the parent commit, both forms, were read once
    with the parent beside the change: CHANGES.md, PR 53; the jaxpr of a
    ("full",) model is pinned by `test_keye_vl2.py`.)"""
    kw = dict(benchmark_config(name)["model"])
    kw["dtype"] = getattr(jnp, kw["dtype"])
    kw["param_dtype"] = getattr(jnp, kw["param_dtype"])
    config = GPTConfig(**kw)
    assert not config.lead_layers and config.periods * len(
        config.layer_pattern) == config.n_layers
    for kind in set(config.layer_pattern):
        weights = _layer_weights(config, kind)
        assert not _ADDED & set(weights)
        assert ("ws_open" in weights) == bool(config.moe_shared_ff)
    shapes = jax.eval_shape(lambda: GPT(config).init(jax.random.PRNGKey(0)))
    assert "lead" not in shapes


def test_a_leading_layer_is_declared_with_its_own_ffn():
    config = _config()
    lead, period = (_layer_weights(config, "window", lead=lead)
                    for lead in (True, False))
    assert lead["w_up"].shape == (128, 192) and "router" not in lead
    assert period["w_up"].shape == (128, 128, 64)
    assert period["router_bias"].start == "zeros" and "ws_open" not in period
    model = GPT(config)
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    axes = model.param_logical_axes()
    assert jax.tree_util.tree_structure(shapes) == \
        jax.tree_util.tree_structure(
            axes, is_leaf=lambda x: isinstance(x, tuple))
    drawn = sum(int(np.prod(x.shape)) for path, x in
                jax.tree_util.tree_leaves_with_path(shapes) if x.ndim > 1
                and not any(part in jax.tree_util.keystr(path)
                            for part in ("norm", "router_bias")))
    assert config.n_params == drawn


# ------------------------------------- the served bucket, for the chip

def test_the_largest_served_bucket_compiles_and_fits_a_v5e(v5e):
    """`benchmarks/configs/trinity_mini.json` as `loops/serve.py::Scorer`
    builds it (bfloat16 weights, the bucket program's own text) at the
    largest bucket of the cell's traffic file, 2 x 16,384: the windowed
    kernel four times (the leading layer and the period's three), the full
    one once, the router's once, and by the compiler's account 12.2 GB with
    the 7.05 GB of weights, 76% of the chip."""
    with open(os.path.join(ROOT, "benchmarks/traffic/"
                           "serve-score-16k-steady-over-swa.json")) as f:
        batching = json.load(f)["batching"]
    rows, length = max(batching["rows"]), max(batching["lengths"])
    assert (rows, length) == (2, 16384)
    params, compiled = served_bucket(v5e, "trinity_mini", rows, length)
    weights = sum(x.size * 2 for x in jax.tree_util.tree_leaves(params))
    assert 7.04e9 < weights < 7.06e9        # 3.52 B parameters in bfloat16
    # the leading layer's call and the scanned period's three and one
    assert _kernel_names(compiled, "flash_") == [
        "flash_fwd"] + ["flash_fwd_window"] * 4
    assert _kernel_names(compiled, "moe_") == ["moe_topk_rounds"] * 4
    mem = compiled.memory_analysis()
    total = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert 11.5e9 < total < 13.0e9, total


def test_the_smallest_served_bucket_copies_no_expert_weight(v5e):
    """The 1 x 4,096 bucket's program, whose 86 ms a call had 12 in copies
    (PERF.md, PR 54): the three window layers' experts are a [1, 3, 128, ...]
    stack, a grouped matmul is a custom call into which no slice is fused,
    and nine of the twelve took a fusion that wrote a layer's 0.54 GB out
    first. Read in place (`moe.moe_ffn`'s `layer`) each takes the
    program's own parameter, nothing writes an array of a layer's experts,
    and the temporaries are a quarter of the 2.48 GB they were."""
    _, compiled = served_bucket(v5e, "trinity_mini", 1, 4096)
    assert _grouped_matmul_weights(compiled) == ["parameter"] * 12
    assert _writes_of(compiled, "bf16[128,2048,1024]",
                      "bf16[128,1024,2048]") == []
    assert compiled.memory_analysis().temp_size_in_bytes < 0.8e9
