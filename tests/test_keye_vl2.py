"""Learned sparse attention (`layer_pattern=("sparse",)`, Keye-VL-2.0's
language model) on the CPU at small sizes with seeded weights: the indexer's
choice of keys against `lax.top_k`, ties included, in both forms; attention
over a choice against the masked dense form, tiles without a chosen key
skipped; the program against the plain reference
(`benchmarks/reference/keye_vl2.py`) in float32; the eight shares of a
layer's experts against the uncut layer; padding and neighbours; the facts a
layer reports; what is refused; that a model of ("full",) is the program it
was; and, closing the file, the served cell's largest bucket compiled for a
described v5e (`_chip.py` says why here)."""

import hashlib
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from _chip import (_grouped_matmul_weights, _kernel_calls,  # noqa: E402,F401
                   _kernel_names, _writes_of, benchmark_config,
                   served_bucket, v5e)
from benchmarks.reference import keye_vl2, keye_vl2_glue   # noqa: E402
from ray_tpu.models.gpt import GPT, GPTConfig, llama_tiny  # noqa: E402
from ray_tpu.ops.attention import (_Tiling, _live_tiles,   # noqa: E402
                                   dot_product_attention)
from ray_tpu.ops.sparse_index import (_summary, count_tile,  # noqa: E402
                                      index_scores, sparse_index)

TOPK = 64
PUBLISHED = dict(
    num_attention_heads=8, num_key_value_heads=2, rms_norm_eps=1e-6,
    rope_theta=1e7, num_experts_per_tok=8, norm_topk_prob=True,
    first_expert_held=0,
    sa_config=dict(indexer_head_dim=16, indexer_num_heads=4,
                   indexer_num_kv_heads=1, topk=TOPK, q_chunk_size=512,
                   kv_chunk_size=512))


def _config(impl="reference", **kw):
    base = dict(
        vocab_size=512, n_layers=2, d_model=128, n_heads=8, n_kv_heads=2,
        d_head=32, d_ff=64, max_seq_len=1024, layer_pattern=("sparse",),
        activation="swiglu", norm="rmsnorm", norm_eps=1e-6,
        positions="rope", rope_theta=1e7, tie_embeddings=False,
        qk_norm="head", sparse_topk=TOPK, index_heads=4, index_head_dim=16,
        n_experts=128, moe_top_k=8, moe_norm_topk_prob=True,
        moe_first_expert=0, moe_experts_held=16, dtype=jnp.float32,
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


def _chosen_pairs(length, topk):
    return sum(min(t + 1, topk) for t in range(length))


# ------------------------------------------------------------- the indexer

def _index_inputs(batch, length, heads=4, dim=16, ties=False, seed=0,
                  dtype=jnp.float32):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(keys[0], (batch, length, heads, dim), dtype)
    k = jax.random.normal(keys[1], (batch, length, dim), dtype)
    w = jax.random.normal(keys[2], (batch, length, heads), dtype)
    if ties:    # whole numbers: many scores are equal, many are nought
        q, k, w = jnp.round(q), jnp.round(k), jnp.round(w)
    return q, k, w


def _top_k_choice(q, k, w, topk):
    """The choice written out from `lax.top_k` on the causal scores:
    [B, keys, queries] int8."""
    b, s = q.shape[:2]
    scores = index_scores(q, k, w)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    values, keys = lax.top_k(jnp.where(causal, scores, -jnp.inf),
                             min(topk, s))
    chosen = np.zeros((b, s, s), np.int8)
    values, keys = np.asarray(values), np.asarray(keys)
    for i in range(b):
        for t in range(s):
            chosen[i, keys[i, t][values[i, t] > -np.inf], t] = 1
    return chosen


@pytest.mark.parametrize("ties", [False, True], ids=["spread", "ties"])
@pytest.mark.parametrize("impl", ["reference", "pallas_interpret"])
@pytest.mark.parametrize("length,topk", [(256, 48), (1024, 100),
                                         (384, 2048)])
def test_the_chosen_keys_are_lax_top_ks(impl, ties, length, topk):
    """Both forms choose, for every query, exactly the keys `lax.top_k`
    names on the causal index scores — among equal scores the lowest
    indices — and count them a tile; a query with fewer causal keys than
    `topk` takes them all."""
    q, k, w = _index_inputs(2, length, ties=ties)
    got = sparse_index(q, k, w, topk, impl=impl)
    want = _top_k_choice(q, k, w, topk)
    assert got.mask.dtype == jnp.int8
    assert np.array_equal(np.asarray(got.mask), want)
    tile = count_tile(length)
    assert got.counts.shape == (2, length // tile, length)
    assert np.array_equal(
        np.asarray(got.counts),
        want.reshape(2, length // tile, tile, length).sum(2))
    assert np.array_equal(np.asarray(got.counts.sum(1))[0],
                          np.minimum(np.arange(length) + 1, topk))
    if ties and topk < length:  # as the name says: equals at the cut
        scores = np.asarray(index_scores(q, k, w))[0, length - 1]
        cut = np.sort(scores)[::-1][min(topk, length) - 1]
        assert (scores == cut).sum() > 1


def test_the_kernel_and_the_jnp_form_agree_on_bfloat16_operands():
    q, k, w = _index_inputs(1, 512, dtype=jnp.bfloat16, seed=3)
    a = sparse_index(q, k, w, 96, impl="reference")
    b = sparse_index(q, k, w, 96, impl="pallas_interpret")
    assert np.array_equal(np.asarray(a.mask), np.asarray(b.mask))
    assert np.array_equal(np.asarray(a.counts), np.asarray(b.counts))


def test_the_indexer_resolves_impl_as_the_other_operators_do():
    """Off a TPU "auto" is the `jnp` form at any length; the kernel by name
    takes a sequence of whole 128s and refuses another; an unknown name is
    refused (`ops/_impl.py`)."""
    q, k, w = _index_inputs(1, 100)
    auto = sparse_index(q, k, w, 16)
    assert np.array_equal(np.asarray(auto.mask), _top_k_choice(q, k, w, 16))
    assert auto.counts.shape == (1, 1, 100)
    with pytest.raises(ValueError, match="whole 128-lane tiles"):
        sparse_index(q, k, w, 16, impl="pallas")
    with pytest.raises(ValueError, match="unknown impl"):
        sparse_index(q, k, w, 16, impl="mosaic")


# ------------------------------------------------ attention over a choice

def _attention_inputs(batch, length, heads, kv_heads, dim, seed=1):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(keys[0], (batch, length, heads, dim)),
            jax.random.normal(keys[1], (batch, length, kv_heads, dim)),
            jax.random.normal(keys[2], (batch, length, kv_heads, dim)))


@pytest.mark.parametrize("heads,kv_heads,dim,block", [
    (4, 4, 64, 1024),       # no group, a head narrower than the lanes
    (4, 2, 128, 1024),      # a GQA group at full lanes
    (4, 2, 128, 128),       # two q tiles x two k tiles
    (2, 2, 128, 128),       # groups of 1, 4 and 8 over those four tiles
    (8, 2, 128, 128),
    (8, 1, 128, 128),
    (8, 1, 64, 1024)],
    ids=["seq_major", "gqa_128", "gqa_128_tiles", "group_1_tiles",
         "group_4_tiles", "group_8_tiles", "group_8_narrow"])
def test_attention_over_a_choice_is_the_masked_dense_form(heads, kv_heads,
                                                          dim, block):
    """Every query head of a grid step's group, at every tile of the grid,
    reads the one block of the choice: held to the masked dense form head
    by head."""
    q, k, v = _attention_inputs(2, 256, heads, kv_heads, dim)
    choice = sparse_index(*_index_inputs(2, 256), 32, impl="reference")
    want = dot_product_attention(q, k, v, impl="reference", seq_major=True,
                                 selection=choice)
    got = dot_product_attention(q, k, v, impl="pallas_interpret",
                                seq_major=True, selection=choice,
                                block_q=block, block_k=block)
    dense = dot_product_attention(q, k, v, impl="reference", seq_major=True)
    assert float(jnp.abs(got - want).max(axis=(0, 1, 3)).max()) < 1e-5
    assert float(jnp.abs(dense - want).max()) > 0.1     # the choice matters


def test_a_query_without_a_chosen_key_in_a_rectangle_a_tile_or_at_all():
    """The walk selects on nothing after the exp: a query's running maximum
    starts far above what a dropped score becomes, so a rectangle, or a whole
    k tile, in which a query has no chosen key adds exactly nothing to it,
    before its first kept key and after it. 512 positions under tiles of 256
    (rectangles of 128): queries 130..139 have no key among the first 128,
    queries 300..339 none in the first k tile, queries 260..263 none in the
    second (their last), and queries 5..8 and 400..404 none at all — those
    come out 0, every other as the masked dense form has it."""
    chosen = np.asarray(sparse_index(*_index_inputs(1, 512), 48,
                                     impl="reference").mask[0]) != 0
    chosen[:128, 130:140] = False       # [keys, queries]
    chosen[:256, 300:340] = False
    chosen[256:, 260:264] = False
    keyless = np.r_[5:9, 400:405]
    chosen[:, keyless] = False
    assert chosen[:, 130:140].any(0).all() and chosen[:, 300:340].any(0).all()
    assert chosen[:, 260:264].any(0).all()
    choice = _summary(jnp.asarray(chosen.T[None]))
    q, k, v = _attention_inputs(1, 512, 8, 2, 128)
    want = dot_product_attention(q, k, v, impl="reference", seq_major=True,
                                 selection=choice)
    got = dot_product_attention(q, k, v, impl="pallas_interpret",
                                seq_major=True, selection=choice,
                                block_q=256, block_k=256)
    assert bool(jnp.isfinite(got).all())
    keyed = np.setdiff1d(np.arange(512), keyless)
    assert float(jnp.abs(got - want)[:, keyed].max()) < 1e-5
    assert float(jnp.abs(got[:, keyless]).max()) == 0.0
    assert float(jnp.abs(got[:, keyed]).min(axis=(0, 2, 3)).max()) > 0


def _pallas_calls(jaxpr, name):
    """The `pallas_call` equations called `name`, nested ones included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call" and \
                eqn.params["name"] == name:
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _pallas_calls(sub, name)
    return found


def test_the_choice_is_fetched_once_a_gqa_group():
    """The grid of `dsa_attend_fwd` runs over the KV heads, not the query
    heads: a step holds the eight query heads of Keye's group against their
    one k / v block and the one block of the choice, whose index ignores the
    head — so the choice's bytes a call are batch x KV heads x live tiles x
    a block, an eighth of a head a step."""
    q, k, v = _attention_inputs(2, 512, 32, 4, 128)
    choice = sparse_index(*_index_inputs(2, 512), 64, impl="reference")
    jaxpr = jax.make_jaxpr(lambda q, k, v, s: dot_product_attention(
        q, k, v, impl="pallas_interpret", seq_major=True, selection=s,
        block_q=256, block_k=256))(q, k, v, choice)
    (call,) = _pallas_calls(jaxpr.jaxpr, "dsa_attend_fwd")
    mapping = call.params["grid_mapping"]
    assert tuple(mapping.grid) == (2, 4, 2, 2)  # batch, KV heads, q, k tiles
    blocks = [tuple(getattr(d, "block_size", None) for d in b.block_shape)
              for b in mapping.block_mappings]
    assert blocks == [
        (None, 8, 256, 128),        # q: the group's eight heads
        (None, None, 256, 128),     # k and v: their one KV head
        (None, None, 256, 128),
        (None, 256, 256),           # the choice: no head axis at all
        (None, 8, 256, 128)]        # the output
    assert not _pallas_calls(jaxpr.jaxpr, "flash_fwd")


def test_tiles_without_a_chosen_key_are_not_walked():
    """Keys of the first 128 positions score far above the rest, so every
    later query's choice lies there: the second tile of the diagonal holds
    no chosen key, the per-tile summary says so, and the kernel that skips
    it gives the masked dense form's result."""
    q_idx, k_idx, w_idx = (jnp.abs(x) for x in _index_inputs(1, 1024))
    k_idx = k_idx.at[:, :128].multiply(100.0)
    choice = sparse_index(q_idx, k_idx, w_idx, 64, impl="pallas_interpret")
    live = np.asarray(_live_tiles(choice, _Tiling(1024, 1024, 512, 512,
                                                  True))).reshape(2, 2)
    assert (live[:, 0] > 0).all() and (live[:, 1] == 0).all()
    assert live.sum() == _chosen_pairs(1024, 64)
    q, k, v = _attention_inputs(1, 1024, 2, 1, 128)
    want = dot_product_attention(q, k, v, impl="reference", seq_major=True,
                                 selection=choice)
    got = dot_product_attention(q, k, v, impl="pallas_interpret",
                                seq_major=True, selection=choice,
                                block_q=512, block_k=512)
    assert float(jnp.abs(got - want).max()) < 1e-5
    # tiles that the summary's own do not divide: nothing is skipped
    assert _live_tiles(choice, _Tiling(1024, 1024, 256, 256, True)).all()


def test_a_choice_over_ragged_tiles_is_refused_by_name():
    q, k, v = _attention_inputs(1, 192, 2, 2, 64)
    choice = sparse_index(*_index_inputs(1, 192), 32, impl="reference")
    with pytest.raises(NotImplementedError, match="whole tiles"):
        dot_product_attention(q, k, v, impl="pallas_interpret",
                              seq_major=True, selection=choice, block_q=128,
                              block_k=128)


# --------------------------------------------- the program, the reference

@pytest.mark.parametrize("impl", ["reference", "pallas_interpret"])
def test_the_program_scores_as_the_plain_reference_does(impl):
    """float32, seeded weights, two rows of 640 tokens (the reference
    follows a row with zeros to 1,024 and attends in blocks of 512)."""
    model = GPT(_config(impl))
    params = model.init(jax.random.PRNGKey(0))
    tokens = _tokens(2, 640)
    top, layers = keye_vl2_glue.reference_weights(params, None,
                                                  jax.devices())
    want = keye_vl2.token_logprobs(tokens, top, list(layers), PUBLISHED)
    got = _scores_of(model, params, tokens)
    assert want.shape == got.shape == (2, 639)
    assert float(jnp.abs(got - want).max()) < 2e-5


def test_the_eight_shares_of_a_layers_experts_add_up_to_the_uncut_layer():
    """One layer, all 128 experts' weights drawn once. Share i of eight
    (`moe_first_expert` 16 i, 16 held) runs in the program; the shares'
    parts of the layer's result, summed, are the uncut reference's: the
    layer with all 128 experts given."""
    whole = GPT(_config(n_layers=1, moe_experts_held=None))
    params = whole.init(jax.random.PRNGKey(2))
    tokens = _tokens(1, 256, seed=5)
    top, layers = keye_vl2_glue.reference_weights(params, None,
                                                  jax.devices())
    (w,) = list(layers)
    x = top["embed_tokens"][tokens[0]]
    hparams = keye_vl2._hparams(PUBLISHED)
    with jax.default_matmul_precision("highest"):
        mixed = keye_vl2._sparse_attention(x, w, hparams)
        uncut = keye_vl2._expert_block(mixed, w, hparams)
    parts = jnp.zeros_like(uncut)
    for share in range(8):
        first = 16 * share
        model = GPT(_config(n_layers=1, moe_first_expert=first))
        held = dict(params["blocks"])
        for name in ("w_up", "w_gate", "w_down"):
            held[name] = params["blocks"][name][:, first:first + 16]
        out, _ = model._block(x[None], jnp.arange(256)[None],
                              {k: v[0] for k, v in held.items()}, "sparse")
        parts = parts + (out[0] - mixed)
    assert float(jnp.abs(parts).max()) > 1e-3
    assert float(jnp.abs(mixed + parts - uncut).max()) < 1e-5


@pytest.mark.parametrize("impl", ["reference", "pallas_interpret"])
def test_padding_and_a_neighbour_row_change_no_real_score(impl):
    """`Scorer` pads a document on the right into a bucket beside other
    documents: the choice of keys is causal and a row's own."""
    model = GPT(_config(impl))
    params = model.init(jax.random.PRNGKey(0))
    doc = _tokens(1, 200, seed=7)
    other = _tokens(1, 384, seed=8)
    alone = _scores_of(model, params, jnp.pad(doc, ((0, 0), (0, 56))))
    bucket = jnp.concatenate([other, jnp.pad(doc, ((0, 0), (0, 184)))])
    beside = _scores_of(model, params, bucket)
    assert float(jnp.abs(alone[0, :199] - beside[1, :199]).max()) < 1e-5


@pytest.mark.parametrize("impl", ["reference", "pallas_interpret"])
def test_a_layer_reports_the_pairs_it_attended(impl):
    model = GPT(_config(impl))
    params = model.init(jax.random.PRNGKey(0))
    _, aux = jax.jit(model.forward_with_aux)(params, _tokens(2, 256))
    assert aux["dsa_selected_pairs"].tolist() == [
        2 * _chosen_pairs(256, TOPK)] * 2
    assert aux["moe_routed_here"].shape == (2,)


def test_a_period_of_sparse_and_full_layers_stacks_its_facts():
    model = GPT(_config(layer_pattern=("sparse", "full")))
    params = model.init(jax.random.PRNGKey(0))
    _, aux = jax.jit(model.forward_with_aux)(params, _tokens(1, 128))
    assert aux["dsa_selected_pairs"].tolist() == [
        _chosen_pairs(128, TOPK), 0]


def test_a_sparse_model_is_not_trained_and_says_why():
    model = GPT(_config())
    params = model.init(jax.random.PRNGKey(0))
    with pytest.raises(NotImplementedError, match="indexer"):
        model.loss(params, {"tokens": _tokens(1, 128)})
    with pytest.raises(ValueError, match="sparse_topk"):
        _config(sparse_topk=0)


def test_the_indexers_weights_are_declared_with_the_layers():
    model = GPT(_config())
    params = model.init(jax.random.PRNGKey(0))
    axes = model.param_logical_axes()
    blocks = params["blocks"]
    assert blocks["wq_idx"].shape == (2, 128, 4, 16)
    assert blocks["wk_idx"].shape == (2, 128, 16)
    assert blocks["w_idx"].shape == (2, 128, 4)
    assert blocks["k_idx_norm"].shape == blocks["k_idx_bias"].shape == (2, 16)
    assert jax.tree_util.tree_structure(params) == \
        jax.tree_util.tree_structure(
            axes, is_leaf=lambda x: isinstance(x, tuple))
    # drawn apart: no two matrices of a layer share a key
    assert float(jnp.abs(blocks["wq_idx"][0, :, 0] - blocks["wk_idx"][0]
                         ).max()) > 0
    dense = GPT(_config(layer_pattern=("full",), sparse_topk=0))
    assert model.config.n_params - dense.config.n_params == 2 * 128 * (
        4 * 16 + 16 + 4)


def test_the_embeddings_start_is_the_configurations_and_no_other_draw_moves():
    """`embed_std` scales the token embedding's own draw and nothing else:
    a configuration that leaves it alone is the model it was, and the served
    configuration's unit start (under which a token's routing follows the
    token and not the context's mean: PERF.md, PR 49) differs from it by
    that one matrix."""
    key = jax.random.PRNGKey(3)
    usual = GPT(_config()).init(key)
    unit = GPT(_config(embed_std=1.0)).init(key)
    assert GPTConfig().embed_std == 0.02
    assert abs(float(unit["tok_embed"].std()) - 1.0) < 0.01
    np.testing.assert_allclose(unit["tok_embed"] * 0.02, usual["tok_embed"],
                               rtol=1e-6)
    unit["tok_embed"] = usual["tok_embed"]
    assert all(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        lambda a, b: bool((a == b).all()), unit, usual)))
    assert benchmark_config("keye_vl_2_30b_a3b")["model"]["embed_std"] == 1.0


@pytest.mark.parametrize("sign, trips", [(1.0, 4), (-1.0, 0)],
                         ids=["all_routed_here", "none_routed_here"])
def test_the_held_walk_follows_a_collapsed_routing_and_drops_no_pair(
        sign, trips):
    """What seeded weights do to this model's router (PERF.md, PR 49): every
    token sends all eight of its choices to held experts, eight times the
    balance, and the walk takes four trips of its chunk of twice the balance;
    or none, and it takes no trip. Either way the sixteen held experts' part
    of the result is the plain sum over the pairs routed to them."""
    from ray_tpu.models import moe
    keys = jax.random.split(jax.random.PRNGKey(4), 5)
    x = jnp.abs(jax.random.normal(keys[0], (1, 512, 64)))
    router = jnp.abs(jax.random.normal(keys[1], (64, 128)))
    router = sign * router.at[:, 16:].multiply(-1.0)  # held logits on top
    w_up, w_gate = (jax.random.normal(k, (16, 64, 32)) * 0.1
                    for k in keys[2:4])
    w_down = jax.random.normal(keys[4], (16, 32, 64)) * 0.1
    out, aux = moe.moe_ffn(x, router, w_up, w_gate, w_down, top_k=8,
                           dtype=jnp.float32, impl="reference")
    rows = moe._held_chunk_rows(512 * 8, 16 / 128)
    assert rows == 1024
    # midway to the chunk in whole 1,024s is the chunk here: one size
    assert moe._held_trip_sizes(512 * 8, 16 / 128) == (1024,)
    assert int(aux["moe_routed_here"]) == trips * rows
    assert int(aux["moe_expert_tokens"][:16].sum()) == trips * rows
    assert int(aux["moe_rows_walked"]) == trips * rows

    probs = jax.nn.softmax(x[0] @ router, -1)
    gates, chosen = lax.top_k(probs, 8)
    gates = gates / gates.sum(-1, keepdims=True)
    plain = jnp.zeros_like(x[0])
    for e in range(16):
        weight = jnp.where(chosen == e, gates, 0.0).sum(-1, keepdims=True)
        plain += weight * ((jax.nn.silu(x[0] @ w_gate[e]) * (x[0] @ w_up[e]))
                           @ w_down[e])
    assert (float(jnp.abs(plain).max()) > 1e-3) == (trips > 0)
    assert float(jnp.abs(out[0] - plain).max()) < 1e-5


def _rows_walked(pairs, sizes):
    """The walk's policy said plainly: a trip takes the smallest of `sizes`
    that holds what is left, the largest where none does."""
    rows = 0
    while rows < pairs:
        rows += min((s for s in sizes if s >= pairs - rows),
                    default=max(sizes))
    return rows


def _routed_exactly(here, tokens, top_k, e, held, seed):
    """Logits [tokens, e] whose top-k send exactly `here` (token, expert)
    pairs to experts 0 .. held: all of a token's choices for the first
    here // top_k tokens, the remainder for the next, none after."""
    rng = np.random.default_rng(seed)
    logits = rng.uniform(0.0, 0.5, (tokens, e)).astype(np.float32)
    to_held = np.clip(here - top_k * np.arange(tokens), 0, top_k)
    for t in range(tokens):
        chosen = np.concatenate([
            rng.choice(held, to_held[t], replace=False),
            held + rng.choice(e - held, top_k - to_held[t], replace=False)])
        logits[t, chosen] += 3.0 + 0.25 * rng.permutation(top_k)
    return jnp.asarray(logits)


@pytest.mark.parametrize("impl", ["reference", "pallas_interpret"])
@pytest.mark.parametrize("here", [
    0, 1, 2048, 3071, 3072, 3073, 4095, 4096, 4097, 7168, 7169, 9000],
    ids=["no_pair", "one_pair", "the_balance", "the_smaller_trip_less_one",
         "the_smaller_trip", "the_smaller_trip_and_one",
         "the_chunk_less_one", "the_chunk", "the_chunk_and_one",
         "the_chunk_and_the_smaller_trip",
         "the_chunk_the_smaller_trip_and_one", "two_chunks_and_a_part"])
def test_the_held_walk_is_the_held_experts_part_whatever_the_run_s_length(
        here, impl, monkeypatch):
    """2,048 tokens x top-8 over 128 experts of which 16 are held: 2,048
    pairs at balance, so whole trips of 4,096 rows and a last trip of 3,072,
    midway, where that holds what is left. A router made to send exactly `here`
    pairs to the held experts (a token's logits are its input: the router is
    the identity): the result, what each held expert was given and the
    gradients of the input, the router and the three expert matrices are
    those of `moe_ffn` over all 128 experts where the 112 others' weights
    are zero — the all-experts path, which walks nothing — in no trip, one
    or several; the rows the walk took are whole chunks and then the
    smallest size that holds the rest, never fewer than the pairs; and with
    the smaller size taken off the ladder (the parent's walk: the chunk alone)
    the result, the counts and the gradients of the input and the router
    come out bit for bit the same (the expert matrices' to a rounding: the
    CPU's grouped matmul sums a gradient over the trip's static rows, the
    zeros of the rows that are not real among them)."""
    from ray_tpu.models import moe
    tokens, top_k, e, held, f = 2048, 8, 128, 16, 32
    sizes = moe._held_trip_sizes(tokens * top_k, held / e)
    assert sizes == (4096, 3072)
    keys = jax.random.split(jax.random.PRNGKey(here), 4)
    x = _routed_exactly(here, tokens, top_k, e, held, here)[None]
    router = jnp.eye(e)
    w_up, w_gate = (jax.random.normal(k, (held, e, f)) * 0.1
                    for k in keys[:2])
    w_down = jax.random.normal(keys[2], (held, f, e)) * 0.1
    mix = jax.random.normal(keys[3], (tokens, e))

    def part(x, router, w_up, w_gate, w_down):
        out, aux = moe.moe_ffn(x, router, w_up, w_gate, w_down, top_k=top_k,
                               dtype=jnp.float32, impl=impl)
        return (out[0] * mix).sum(), (out[0], aux)

    def run(*weights):
        with jax.default_matmul_precision("highest"):
            (_, (out, aux)), grads = jax.jit(jax.value_and_grad(
                part, argnums=(0, 1, 2, 3, 4), has_aux=True))(
                    x, router, *weights)
        return out, aux, grads

    out, aux, grads = run(w_up, w_gate, w_down)
    want, facts, wants = run(*(
        jnp.concatenate([w, jnp.zeros((e - held, *w.shape[1:]))])
        for w in (w_up, w_gate, w_down)))
    assert int(aux["moe_routed_here"]) == here
    assert np.array_equal(aux["moe_expert_tokens"], facts["moe_expert_tokens"])
    assert int(aux["moe_expert_tokens"][:held].sum()) == here
    assert int(aux["moe_rows_walked"]) == _rows_walked(here, sizes) >= here
    assert (float(jnp.abs(want).max()) > 1e-3) == (here > 0)
    assert float(jnp.abs(out - want).max()) < 1e-5
    for got, wanted in zip(grads, wants):
        wanted = wanted[:held] if wanted.ndim == 3 else wanted
        assert float(jnp.abs(got - wanted).max()) <= 2e-5 * max(
            1.0, float(jnp.abs(wanted).max()))

    monkeypatch.setattr(moe, "_held_trip_sizes",
                        lambda pairs, share: sizes[:1])
    alone, aux_alone, grads_alone = run(w_up, w_gate, w_down)
    assert int(aux_alone["moe_rows_walked"]) == -(-here // 4096) * 4096
    assert np.array_equal(out, alone)
    assert np.array_equal(aux["moe_expert_tokens"],
                          aux_alone["moe_expert_tokens"])
    for got, wanted in zip(grads[:2], grads_alone[:2]):
        assert np.array_equal(got, wanted)
    for got, wanted in zip(grads[2:], grads_alone[2:]):
        assert float(jnp.abs(got - wanted).max()) <= 1e-6 * max(
            1.0, float(jnp.abs(wanted).max()))


# --------------------------------------------- what ("full",) was, it stays

def _program_digest(impl):
    cfg = llama_tiny(attention_impl=impl, dtype=jnp.float32, qk_norm="head")
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tokens = _tokens(2, 128)
    text = str(jax.make_jaxpr(lambda p: (
        model.apply(p, tokens),
        jax.grad(lambda p: model.loss(p, {"tokens": tokens})[0])(p)))(params))
    return hashlib.sha256(re.sub(r" at 0x[0-9a-f]+", "", text).encode()
                          ).hexdigest()[:20]


@pytest.mark.parametrize("impl,digest", [
    ("reference", "08006333c4c0fcf9ec86"),
    ("pallas_interpret", "5991b8739c9d048720a3")])
def test_a_full_model_is_the_program_it_was_before_sparse_layers(impl,
                                                                 digest):
    """The logits and the gradients of a model of ("full",) are bit-equal
    to the parent commit's because they are the same program: the jaxpr of
    both (the flash kernels' bodies included under the interpreter), read
    off the parent (PR 47) with the same jax, is what this pins."""
    if jax.__version__ != "0.9.0":
        pytest.skip("the digests were read off jax 0.9.0's printer")
    assert _program_digest(impl) == digest


# ------------------------------------- the served bucket, for the chip

def test_the_largest_served_bucket_compiles_and_fits_a_v5e(v5e):
    """`benchmarks/configs/keye_vl_2_30b_a3b.json` as `loops/serve.py::
    Scorer` builds it (bfloat16 weights, the bucket program's own text) at
    the largest bucket of the cell's traffic file, 2 x 16,384: the two
    kernels of the mechanism and the held experts' two are in it, and by
    the compiler's account it takes 5.71 GB, 36% of the chip (what the
    cell's runs report as `memory_peak_bytes`)."""
    with open(os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "benchmarks/traffic/serve-score-16k-steady-over.json")) as f:
        batching = json.load(f)["batching"]
    rows, length = max(batching["rows"]), max(batching["lengths"])
    assert (rows, length) == (2, 16384)
    params, compiled = served_bucket(v5e, "keye_vl_2_30b_a3b", rows, length)
    weights = sum(x.size * 2 for x in jax.tree_util.tree_leaves(params))
    assert 1.70e9 < weights < 1.72e9        # 853 M parameters in bfloat16
    assert _kernel_names(compiled, "dsa_") == ["dsa_attend_fwd", "dsa_index"]
    # (the held walk's two sizes of trip, a body each in the one scanned
    # layer: PR 52)
    assert _kernel_names(compiled, "moe_") == ["moe_segsum", "moe_segsum",
                                               "moe_topk_rounds"]
    assert not _kernel_names(compiled, "flash_")
    # the group's blocks, accumulators and the decoded choice: past the
    # compiler's 16 MiB, so the call says what it needs, a fifth of the core's
    (call,) = _kernel_calls(compiled, "dsa_attend_fwd")
    stated = int(re.search(
        r'"scoped_memory_configs":\[\{[^}]*"size":"(\d+)"', call).group(1))
    assert 16 * 2 ** 20 < stated < 28 * 2 ** 20, stated
    mem = compiled.memory_analysis()
    total = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert 5.2e9 < total < 6.2e9, total


def test_the_smallest_served_bucket_copies_no_expert_weight(v5e):
    """The scan's form of what `test_trinity_mini.py` pins (PERF.md, PR 54):
    the eight layers' held experts are [8, 16, ...] stacks; scanned, a layer's
    slice was written out before its walk (three `dynamic-slice` fusions a
    layer, 24 copies of 50 MB a call); closed over and read at the layer's
    place, the walk's grouped matmuls take the loops' parameters and nothing
    writes an array of a layer's experts."""
    _, compiled = served_bucket(v5e, "keye_vl_2_30b_a3b", 1, 4096)
    # the walk's two sizes of trip, three matmuls each
    assert _grouped_matmul_weights(compiled) == ["parameter"] * 6
    assert _writes_of(compiled, "bf16[16,2048,768]",
                      "bf16[16,768,2048]") == []
