"""The OLMoE-shaped model (`ray_tpu.models.GPT` with QK-norm, RMSNorm eps from
the configuration, dropless top-k experts, the two router losses) against the
plain reference `benchmarks/reference/olmoe.py`, in float32 on the CPU, on
the same seeded weights and rows: logits, the three loss terms, every
parameter's gradient, every (token, expert) choice.

Tolerances: both sides compute in float32 and differ only in the order of
their sums (the program sorts rows by expert and multiplies group by group;
the reference multiplies every token by every expert and masks), so what is
allowed is float32 rounding through two layers: 1e-4 on logits of size ~1,
1e-5 on the loss terms, and on gradients 2e-4 of each leaf's largest entry.
A path that dropped a token, normalised the top-k weights, counted only the
first choice in the balance loss, or took the wrong epsilon is off by 1e-2
or more.

Last, the configuration's step on the chip: `olmoe-steady`'s train step
compiled for a described TPU v5e, without one (`_chip.py`).
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from _chip import (HBM_BYTES, _asks_no_vmem,  # noqa: E402,F401
                   _bytes_written_to_rows, _kernel_names, _on, _operations,
                   benchmark_config, v5e)
from benchmarks.reference import olmoe, olmoe_glue          # noqa: E402
from ray_tpu.models import GPT, moe                          # noqa: E402
from ray_tpu.models.gpt import GPTConfig                     # noqa: E402

AUX, ROUTER_Z = 0.01, 0.001
SEQ = 128


def _config(n_experts, top_k, **kw):
    base = dict(vocab_size=256, n_layers=2, d_model=64, n_heads=4, d_ff=32,
                max_seq_len=SEQ, activation="swiglu", norm="rmsnorm",
                positions="rope", tie_embeddings=False, norm_eps=1e-5,
                qk_norm=True, n_experts=n_experts, moe_top_k=top_k,
                moe_norm_topk_prob=False, moe_aux_coeff=AUX,
                moe_router_z_coeff=ROUTER_Z, z_loss=0.0, dtype=jnp.float32,
                remat=False, attention_impl="reference")
    base.update(kw)
    return GPTConfig(**base)


def _hparams(config):
    return {"num_attention_heads": config.n_heads,
            "num_key_value_heads": config.kv_heads,
            "num_experts_per_tok": config.moe_top_k,
            "norm_topk_prob": config.moe_norm_topk_prob,
            "rms_norm_eps": config.norm_eps, "rope_theta": config.rope_theta}


def _params(model, seed):
    params = model.init(jax.random.PRNGKey(seed))
    # scales that are not one, or a dropped norm scale would pass; a router
    # wide enough that the choices are not near-ties
    blocks = params["blocks"]
    keys = jax.random.split(jax.random.PRNGKey(seed + 100), 6)
    for k, name in zip(keys, ("norm1", "norm2", "q_norm", "k_norm")):
        blocks[name] = blocks[name] + 0.2 * jax.random.normal(
            k, blocks[name].shape)
    params["norm_f"] = params["norm_f"] + 0.2 * jax.random.normal(
        keys[4], params["norm_f"].shape)
    blocks["router"] = blocks["router"] * 20.0
    for name in ("w_up", "w_gate", "w_down"):
        blocks[name] = blocks[name] * 10.0
    return params


def _reference(params, tokens, config):
    top, layers = olmoe_glue.reference_weights(params, None, jax.devices())
    return olmoe.loss_terms(tokens, top, layers, _hparams(config))


def _reference_total(params, tokens, config):
    terms = _reference(params, tokens, config)
    return (terms["ce"] + AUX * terms["load_balance"]
            + ROUTER_Z * terms["router_z"])


def _compare(config, params, tokens):
    model = GPT(config)
    with jax.default_matmul_precision("highest"):
        logits, aux = model.forward_with_aux(params, tokens)
        (total, metrics), grads = jax.value_and_grad(
            model.loss, has_aux=True)(params, {"tokens": tokens})
        ref = _reference(params, tokens, config)
        ref_total, ref_grads = jax.value_and_grad(_reference_total)(
            params, tokens, config)
    n_tokens = tokens.size
    # routing: the same choices, the same counts, nothing dropped
    assert np.array_equal(np.sort(np.asarray(aux["moe_expert_choice"]), -1),
                          np.sort(np.asarray(ref["chosen"]), -1))
    assert np.array_equal(np.asarray(aux["moe_expert_tokens"]),
                          np.asarray(ref["counts"]))
    assert int(metrics["moe_expert_tokens"].sum()) == (
        n_tokens * config.moe_top_k * config.n_layers)
    counts = np.asarray(ref["counts"], np.float64)
    assert float(metrics["moe_load_max_over_mean"]) == pytest.approx(
        (counts.max(-1) / counts.mean(-1)).mean(), rel=1e-5)
    # values
    assert float(jnp.max(jnp.abs(logits - ref["logits"]))) < 1e-4
    assert abs(float(metrics["ce_loss"]) - float(ref["ce"])) < 1e-5
    assert abs(float(metrics["moe_aux_loss"])
               - float(ref["load_balance"])) < 1e-5
    assert float(metrics["moe_router_z"]) == pytest.approx(
        float(ref["router_z"]), rel=1e-5)
    assert abs(float(total) - float(ref_total)) < 1e-5
    assert float(metrics["loss"]) == float(total)
    # every parameter's gradient
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    ref_flat = dict(jax.tree_util.tree_flatten_with_path(ref_grads)[0])
    for path, g in flat:
        want = ref_flat[path]
        scale = float(jnp.max(jnp.abs(want)))
        assert scale > 0, jax.tree_util.keystr(path)
        assert float(jnp.max(jnp.abs(g - want))) <= 2e-4 * scale, (
            jax.tree_util.keystr(path))
    return ref, metrics


@pytest.mark.parametrize("n_experts,top_k", [(8, 2), (64, 8), (4, 1)],
                         ids=["8x2", "olmoe_ratio_64x8", "switch_4x1"])
def test_system_agrees_with_the_plain_reference(n_experts, top_k):
    config = _config(n_experts, top_k)
    params = _params(GPT(config), seed=3)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, SEQ), 0, 256)
    ref, metrics = _compare(config, params, tokens)
    # the balance loss counts all k choices: k at uniform routing
    assert 0.8 * top_k < float(ref["load_balance"]) < 2.5 * top_k


def test_normalised_top_k_weights_follow_the_switch():
    """`norm_topk_prob: true` (Mixtral's convention) is the same algorithm
    with the weights rescaled; the reference has the same switch."""
    config = _config(8, 2, moe_norm_topk_prob=True)
    params = _params(GPT(config), seed=5)
    tokens = jax.random.randint(jax.random.PRNGKey(6), (2, SEQ), 0, 256)
    _compare(config, params, tokens)


def test_one_expert_takes_most_tokens_and_one_takes_none():
    """A router pushed so that expert 0 is chosen by nearly every token and
    expert 1 by none: a capacity of 1.25 x the mean would have dropped more
    than half of expert 0's tokens. The dropless path agrees with the
    reference as closely as under balanced routing, and an empty group is an
    ordinary input."""
    config = _config(8, 2)
    params = _params(GPT(config), seed=7)
    # every token's hidden state gets a common component c; the router reads
    # it with a large positive weight for expert 0, a negative one for 1
    c = jax.random.normal(jax.random.PRNGKey(8), (config.d_model,))
    params["tok_embed"] = params["tok_embed"] + 0.5 * c
    router = params["blocks"]["router"]
    router = router.at[:, :, 0].add(0.2 * c).at[:, :, 1].add(-0.2 * c)
    params["blocks"]["router"] = router
    tokens = jax.random.randint(jax.random.PRNGKey(9), (2, SEQ), 0, 256)
    ref, metrics = _compare(config, params, tokens)
    counts = np.asarray(ref["counts"])
    assert (counts[:, 0] > tokens.size // 2).all(), counts
    assert (counts[:, 1] == 0).all(), counts
    assert float(metrics["moe_load_max_over_mean"]) > 3.0


# ---------------------------------- the block that holds all its experts

BLOCK = dict(tokens=96, d=64, f=128, experts=8, top_k=2)
ROUTINGS = {
    "softmax": dict(norm_topk_prob=False),
    "sigmoid_with_select_bias": dict(norm_topk_prob=False, score="sigmoid"),
    "norm_topk_prob": dict(norm_topk_prob=True),
    "route_scale": dict(norm_topk_prob=True, route_scale=2.5),
    "an_expert_without_a_token": dict(norm_topk_prob=False),
}
# float32 weights under a float32 layer are multiplied where they lie, three
# grouped matmuls; bfloat16 ones are cast on the way in, which joins up and
# gate (exactly: every bfloat16 is a float32), the SwiGLU product's backward
# rule in `jnp` or as its kernel under the interpreter
FORMS = {"three_matmuls": (jnp.float32, "reference"),
         "joined": (jnp.bfloat16, "reference"),
         "joined_kernel": (jnp.bfloat16, "pallas_interpret")}


def _plain_block(x, router_w, w_up, w_gate, w_down, *, top_k, select_bias,
                 norm_topk_prob, score="softmax", route_scale=1.0):
    """The block as it was before PR 56, in float32: rows gathered by two
    argsorts, the counts binned, three grouped matmuls, the rounded rows
    weighed in an einsum after the gather back; autodiff throughout."""
    f32 = jnp.float32
    t, d = x.shape[1:]
    xf = x.reshape(t, d)
    logits = jnp.dot(xf, router_w, precision=jax.lax.Precision.HIGHEST)
    _, gate_vals, expert_idx = moe._route(
        logits, top_k, norm_topk_prob, score, select_bias, route_scale,
        "reference")
    flat = expert_idx.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    inverse = jnp.argsort(order)
    sizes = jnp.bincount(flat, length=router_w.shape[-1]).astype(jnp.int32)
    rows = xf[order // top_k]
    up = jax.lax.ragged_dot(rows, w_up.astype(f32), sizes)
    gate = jax.lax.ragged_dot(rows, w_gate.astype(f32), sizes)
    y = jax.lax.ragged_dot(jax.nn.silu(gate) * up, w_down.astype(f32), sizes)
    return jnp.einsum("tkd,tk->td", y[inverse].reshape(t, top_k, d),
                      gate_vals).reshape(x.shape), sizes


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("routing", ROUTINGS)
def test_the_block_and_every_gradient_agree_with_the_plain_form(routing,
                                                                form):
    """`moe_ffn` with all its experts — one sort, the routing weight inside
    the SwiGLU product, the combine as the dispatch's transpose, up and gate
    joined where they are cast — against the plain form: the output, the
    counts, and the gradients of x, the router (through the routing weights'
    way back, `_permuted` and the product's backward rule) and the three
    expert weights. Float32 sums in another order: 1e-5 of the largest entry
    of the output and 2e-4 of a gradient's; a bfloat16 weight's gradient is
    the same float32 number rounded, a step of 2 ** -7."""
    b = BLOCK
    weight_dtype, impl = FORMS[form]
    keys = jax.random.split(jax.random.PRNGKey(11), 7)
    x = jax.random.normal(keys[0], (1, b["tokens"], b["d"]))
    router_w = jax.random.normal(keys[1], (b["d"], b["experts"]))
    w_up, w_gate = ((0.2 * jax.random.normal(k, (
        b["experts"], b["d"], b["f"]))).astype(weight_dtype)
        for k in keys[2:4])
    w_down = (0.2 * jax.random.normal(keys[4], (
        b["experts"], b["f"], b["d"]))).astype(weight_dtype)
    mix = jax.random.normal(keys[5], x.shape)
    kw = dict(ROUTINGS[routing], top_k=b["top_k"], select_bias=None)
    if routing == "sigmoid_with_select_bias":
        kw["select_bias"] = 0.2 * jax.random.normal(keys[6], (b["experts"],))
    if routing == "an_expert_without_a_token":
        router_w = router_w.at[:, 1].set(0.0)
        x = x.at[..., 0].set(1.0)
        router_w = router_w.at[0, 1].set(-1e4)

    def ours(*args):
        out, aux = moe.moe_ffn(*args, dtype=jnp.float32, impl=impl, **kw)
        return (out * mix).sum(), (out, aux["moe_expert_tokens"])

    def plain(*args):
        out, sizes = _plain_block(*args, **kw)
        return (out * mix).sum(), (out, sizes)

    args = (x, router_w, w_up, w_gate, w_down)
    with jax.default_matmul_precision("highest"):
        (_, (out, counts)), grads = jax.jit(jax.value_and_grad(
            ours, argnums=range(5), has_aux=True))(*args)
        (_, (want, sizes)), wants = jax.jit(jax.value_and_grad(
            plain, argnums=range(5), has_aux=True))(*args)
    assert np.array_equal(counts, sizes) and int(sizes.sum()) == (
        b["tokens"] * b["top_k"])
    assert (int(sizes[1]) == 0) == (routing == "an_expert_without_a_token")
    # up and gate are one matmul exactly where they are cast
    text = str(jax.make_jaxpr(lambda *a: ours(*a)[0])(*args))
    assert (f"[{b['experts']},{b['d']},{2 * b['f']}]" in text) == (
        weight_dtype == jnp.bfloat16)
    scale = float(jnp.abs(want).max())
    assert scale > 0.1
    assert float(jnp.abs(out - want).max()) <= 1e-5 * scale
    for name, got, want in zip(("x", "router", "w_up", "w_gate", "w_down"),
                               grads, wants):
        assert got.dtype == want.dtype, name
        got, want = got.astype(jnp.float32), want.astype(jnp.float32)
        scale = float(jnp.abs(want).max())
        assert scale > 0, name
        step = 2.0 ** -7 if name.startswith("w_") and (
            weight_dtype == jnp.bfloat16) else 2e-4
        assert float(jnp.abs(got - want).max()) <= step * scale, name


def _olmoe_config():
    return benchmark_config("olmoe_1b_7b")


def _olmoe_step(config, batch):
    """The one-layer OLMoE step of the benchmark's `olmoe_1b_7b`
    configuration, as its cell builds it, at `batch` rows of 4,096."""
    from ray_tpu.models import (GPT, init_train_state, make_optimizer,
                                make_train_step)
    from ray_tpu.models.gpt import GPTConfig

    kw = dict(config["model"], attention_impl="pallas")
    kw["dtype"] = getattr(jnp, kw["dtype"])
    kw["param_dtype"] = getattr(jnp, kw["param_dtype"])
    model = GPT(GPTConfig(**kw))
    opt = make_optimizer(**config["optimizer"])
    state = jax.eval_shape(
        lambda: init_train_state(model, opt, jax.random.PRNGKey(0)))
    tokens = jax.ShapeDtypeStruct((batch, kw["max_seq_len"]), jnp.int32)
    return make_train_step(model, opt), state, tokens


def test_olmoe_one_layer_train_step_fills_one_chip(v5e):
    """`olmoe-steady`'s step: one OLMoE layer at published widths with all
    64 experts (dropless, by sort and grouped matmul), embedding and untied
    head, float32 AdamW state, at the configuration's `batch_per_chip` rows
    of 4,096 tokens. It fits; and since PR 56 so does one row more (15.26
    GiB), the first that does not being two more: until then the row after
    `batch_per_chip` was refused (16.02 GiB), which is what fixed it.

    And the expert block's shape in it (PR 56): up and gate are one grouped
    matmul, forward and in both transposes, so six where there were nine;
    the SwiGLU product's backward pass is the repo's kernel, which writes
    both halves' gradients as one array; nothing adds two [T x K, D] arrays
    in a pass of its own, nothing under `moe_dispatch` scatters, and the
    T x K rows are written 7.1 GB a step where they were 9.08 — the
    compiler's recomputation of the dispatch gather (`.remat`) counted, as
    it was; the SwiGLU product, which it also made twice, the kernel now
    writes the second time."""
    one_chip = SingleDeviceSharding(v5e.devices[0])
    config = _olmoe_config()
    rows = config["batch_per_chip"]
    step, state, tokens = _olmoe_step(config, rows)
    assert tokens.shape == (rows, 4096)
    compiled = step.lower(_on(one_chip, state),
                          {"tokens": _on(one_chip, tokens)}).compile()
    # the flash kernels, once each (no remat: nothing is run twice; at
    # [5, 16, 4096, 128] the row's dq accumulator fits and the backward is
    # the one kernel), and the grouped matmuls are kernels too
    assert _kernel_names(compiled, "flash_") == ["flash_bwd", "flash_fwd"]
    assert _asks_no_vmem(compiled, "flash_fwd")
    assert _kernel_names(compiled, "moe_") == ["moe_swiglu_bwd"]
    mem = compiled.memory_analysis()
    # the donated state is aliased to the new one: 12 bytes a parameter
    assert mem.alias_size_in_bytes > 7.4e9
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < HBM_BYTES
    pairs = rows * 4096 * config["model"]["moe_top_k"]
    made = _operations(compiled, entry=True)
    assert sum(op == "custom-call" and name.startswith("ragged-dot-none")
               for name, (_, op, _) in made.items()) == 6
    assert not [name for name, (result, op, _) in made.items()
                if op == "add" and f"[{pairs}," in result]
    assert not [name for name, (_, _, rest) in made.items()
                if "/moe_dispatch/" in rest and "scatter" in rest]
    written = _bytes_written_to_rows(compiled, pairs)
    assert 6.0e9 < sum(written.values()) <= 7.2e9, written
    step, state, tokens = _olmoe_step(config, rows + 1)
    mem = step.lower(_on(one_chip, state),
                     {"tokens": _on(one_chip, tokens)}
                     ).compile().memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < HBM_BYTES
    step, state, tokens = _olmoe_step(config, rows + 2)
    with pytest.raises(Exception, match="(?i)ran out of memory|exhausted"):
        step.lower(_on(one_chip, state),
                   {"tokens": _on(one_chip, tokens)}).compile()
