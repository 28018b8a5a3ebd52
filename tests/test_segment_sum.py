"""`ray_tpu.ops.segment_sum.sorted_segment_sum`, the sum that returns the
held experts' rows to token order (`models/moe.py::_sum_into_tokens`), alone
and inside `moe_ffn`.

Alone: the kernel under the interpreter and the `jnp` form against a plain
`jax.ops.segment_sum` that is told nothing about its ids, on the shapes of
ids the walk produces — tokens with no row, tokens with `top_k` rows, a token
whose rows straddle a block of segments and a chunk of rows, a tail of rows
that are not real (and hold NaNs, which must not leak) — for bf16 and float32
rows, with and without weights, onto nothing, onto zeros that are not read
and onto an array that is. Both sides sum in float32; the kernel splits its
float32 operands into bf16 terms whose products are exact, so what differs
is the order of a segment's additions: 1e-6 of the largest entry is allowed
(read: 2e-7), where a dropped row, a row counted for two blocks or a weight
rounded to bf16 is off by 1e-3 or more.

Inside `moe_ffn`: with every choice routed to the held share, the share's
result and the gradient of its input (whose rows come back through the same
sum) are the all-experts path's, in one trip and in several.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import moe
from ray_tpu.models.moe import moe_ffn
from ray_tpu.ops import segment_sum
from ray_tpu.ops.segment_sum import sorted_segment_sum

SEGMENTS, TOP_K = 600, 10


def _ids(case, rng):
    """Sorted ids over 600 segments (blocks of 256 in the kernel, chunks of
    128 rows), `SEGMENTS` for a row that is not real."""
    if case == "tokens_without_rows":
        # two in three segments empty, whole blocks' worth among them
        ids = np.sort(rng.choice(np.arange(0, SEGMENTS, 3), 300))
        ids = ids[(ids < 250) | (ids > 520)]
    elif case == "top_k_rows_a_token":
        ids = np.repeat(np.sort(rng.choice(SEGMENTS, 90, replace=False)),
                        TOP_K)
    elif case == "a_token_across_a_block_edge":
        # segment 255 is the last of the first block and segment 256 the
        # first of the second; their twenty rows lie across the edge of the
        # rows' first chunk (rows 118..137)
        ids = np.concatenate([np.sort(rng.integers(0, 255, 118)),
                              [255] * TOP_K, [256] * TOP_K,
                              np.sort(rng.integers(257, SEGMENTS, 200))])
    elif case == "a_tail_that_is_not_real":
        ids = np.concatenate([np.sort(rng.integers(0, SEGMENTS, 333)),
                              [SEGMENTS] * 190])
    elif case == "no_real_row":
        ids = np.full(130, SEGMENTS)
    return ids.astype(np.int32)


CASES = ["tokens_without_rows", "top_k_rows_a_token",
         "a_token_across_a_block_edge", "a_tail_that_is_not_real",
         "no_real_row"]


# the forward walk's call (weights, onto the loop's carry), the backward
# walk's (no weights), and each without a carry
FORMS = {"weighted_onto_nothing": (True, "nothing"),
         "plain_onto_nothing": (False, "nothing"),
         "weighted_onto_what_is_not_read": (True, "not_read"),
         "plain_onto_an_array": (False, "an_array")}


@pytest.mark.parametrize("form", list(FORMS))
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("impl", ["reference", "pallas_interpret"])
@pytest.mark.parametrize("case", CASES)
def test_sorted_segment_sum_matches_a_plain_segment_sum(case, impl, dtype,
                                                        form):
    weighted, onto = FORMS[form]
    rng = np.random.default_rng(CASES.index(case))
    ids = _ids(case, rng)
    real = ids < SEGMENTS
    rows = jnp.asarray(np.where(real[:, None], rng.standard_normal(
        (len(ids), 128)), np.nan), dtype)
    weights = jnp.asarray(rng.random(len(ids)), jnp.float32)
    start = jnp.asarray(rng.standard_normal((SEGMENTS, 128)), jnp.float32)

    want = jax.ops.segment_sum(
        jnp.where(real[:, None], rows.astype(jnp.float32) * (
            weights[:, None] if weighted else 1.0), 0.0),
        jnp.asarray(ids), num_segments=SEGMENTS)
    if onto == "an_array":
        want = want + start
    got = jax.jit(lambda rows, ids, weights, start: sorted_segment_sum(
        rows, ids, SEGMENTS, weights if weighted else None,
        onto={"nothing": None, "not_read": (start, False),
              "an_array": (start, True)}[onto],
        impl=impl))(rows, jnp.asarray(ids), weights, start)
    assert got.shape == (SEGMENTS, 128) and got.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(got - want))) <= 1e-6 * max(
        1.0, float(jnp.max(jnp.abs(want))))
    if case != "no_real_row":
        assert float(jnp.max(jnp.abs(want))) > 1.0     # and not trivially


def test_the_kernel_visits_each_block_and_only_chunks_with_real_rows():
    """The list of (block, chunk) pairs the kernel's grid walks: every block
    once at least, a chunk for every block it shares a row with, and none
    of the chunks that hold only rows that are not real."""
    ids = jnp.asarray(np.concatenate([
        np.zeros(130), np.full(10, 255), np.full(10, 256), np.full(106, 700),
        np.full(256, 1024)]).astype(np.int32))
    block, chunk, count, real = segment_sum._schedule(ids, 4, 4, 256, 128)
    pairs = list(zip(np.asarray(block).tolist(), np.asarray(chunk).tolist()))
    # block 0: rows 0..139 (chunks 0, 1); block 1: 140..149 (chunk 1);
    # block 2: 150..255 (chunk 1); block 3: none, visited once
    assert int(count[0]) == 5 and int(real[0]) == 256
    assert pairs[:5] == [(0, 0), (0, 1), (1, 1), (2, 1), (3, 1)]
    assert set(pairs[5:]) == {pairs[4]}         # the rest stay where it ended


def test_the_kernel_refuses_a_width_it_does_not_take():
    rows, ids = jnp.zeros((8, 48)), jnp.zeros((8,), jnp.int32)
    with pytest.raises(ValueError, match="whole 128-lane tiles"):
        sorted_segment_sum(rows, ids, 4, impl="pallas")
    with pytest.raises(ValueError, match="unknown impl"):
        sorted_segment_sum(rows, ids, 4, impl="scatter")
    assert sorted_segment_sum(rows, ids, 4, impl="auto").shape == (4, 48)


@pytest.mark.parametrize("trip_sizes, walked", [
    (None, 600), ((64,), 640), ((64, 32), 608), ((256, 128, 64), 640),
    ((600, 300), 600), ((599, 300), 899), ((601, 300), 601)],
    ids=["one_trip", "several_trips", "a_smaller_last_trip",
         "three_sizes", "exactly_a_chunk", "a_chunk_and_one",
         "a_chunk_less_one"])
@pytest.mark.parametrize("impl", ["reference", "pallas_interpret"])
def test_a_share_that_is_routed_everything_gives_what_all_experts_give(
        impl, trip_sizes, walked, monkeypatch):
    """`moe_ffn` with 4 of 16 experts held and a router that sends every
    choice to them: the held share's walk (rows sorted into token order and
    summed by segments, forward and for dx) against the all-experts path
    (gathers and a sum over k) on the same weights, result and the gradients of the input and of
    the experts' weights (the absent experts' are zero on both sides)."""
    if trip_sizes:
        monkeypatch.setattr(moe, "_held_trip_sizes",
                            lambda pairs, share: trip_sizes)
    keys = jax.random.split(jax.random.PRNGKey(3), 6)
    d, f, e, top_k = 128, 32, 16, 3
    router = jax.random.normal(keys[0], (d, e)).at[:, :3].add(50.0)
    w_up, w_gate = (jax.random.normal(k, (e, d, f)) * 0.3 for k in keys[1:3])
    w_down = jax.random.normal(keys[3], (e, f, d)) * 0.3
    h = 0.5 + jnp.abs(jax.random.normal(keys[4], (2, 100, d)))
    mix = jax.random.normal(keys[5], h.shape)

    def part(h, weights, held):
        out, aux = moe_ffn(h, router, *(w[held] for w in weights),
                           top_k=top_k, norm_topk_prob=True,
                           first_expert=held.start, dtype=jnp.float32,
                           impl=impl)
        return (out * mix).sum(), (out, aux)

    weights = (w_up, w_gate, w_down)
    with jax.default_matmul_precision("highest"):
        (_, (out, aux)), grads = jax.value_and_grad(
            part, argnums=(0, 1), has_aux=True)(h, weights, slice(0, 4))
        (_, (whole, _)), wants = jax.value_and_grad(
            part, argnums=(0, 1), has_aux=True)(h, weights, slice(0, e))
    assert int(aux["moe_routed_here"]) == 2 * 100 * top_k
    assert int(aux["moe_expert_tokens"][:4].sum()) == 2 * 100 * top_k
    # whole trips of the largest size, then the smallest that holds the rest
    assert int(aux["moe_rows_walked"]) == walked
    assert float(jnp.max(jnp.abs(whole))) > 1e-1
    assert float(jnp.max(jnp.abs(out - whole))) < 1e-5 * float(
        jnp.max(jnp.abs(whole)))
    for got, want in zip(jax.tree_util.tree_leaves(grads),
                         jax.tree_util.tree_leaves(wants)):
        assert float(jnp.max(jnp.abs(got - want))) < 1e-4 * float(
            jnp.max(jnp.abs(want)))
