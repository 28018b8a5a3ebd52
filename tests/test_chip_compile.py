"""Compile the chip path's kernels, the GPT-2 steps and the steps on the
host's four chips for a described TPU v5e, without one (`_chip.py` says
how, and what a pass here is not). The one-chip steps of the sparse-expert
and the hybrid configurations, minutes each, close the files of those
configurations' other tests, `test_olmoe_reference.py` and
`test_qwen3_next_ops.py`: `--dist loadfile` hands a file whole to one
worker, files of many tests first, so a long compile in a file of few
tests would start last and a file that held them all was tier-1's wall.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from _chip import (_asks_no_vmem, _kernel_calls, _kernel_names,  # noqa: F401
                   _moved, _on, _qwen3_next_config, _qwen3_next_step, v5e)


@pytest.mark.parametrize("seq_major", [True, False],
                         ids=["seq_major", "head_major"])
@pytest.mark.parametrize("shape", [(12, 16, 1024, 64), (16, 25, 1024, 64)],
                         ids=["gpt2_medium", "gpt2_xl"])
def test_flash_attention_fwd_bwd_compiles_at_bench_width(v5e, shape,
                                                         seq_major):
    """The flash kernels (the forward and, since PR 38, one backward kernel
    in place of the pair) at the widths the benchmark's steps run, bf16, at
    the shipped tile: [batch 12, 16 heads, seq 1024, head_dim 64]
    (gpt2_medium) and [16, 25, 1024, 64] (gpt2_xl, a chip's share), in the
    layout the models use ([B, S, H, 64]: 128-lane blocks of two heads,
    the thirteenth block of gpt2_xl's 1,600 lanes half there) and in
    `flash_attention`'s own (ring attention's)."""
    from ray_tpu.ops.attention import flash_attention

    def loss(q, k, v):
        return flash_attention(q, k, v, seq_major=seq_major).astype(
            jnp.float32).sum()

    if seq_major:
        shape = (shape[0], shape[2], shape[1], shape[3])
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                             sharding=SingleDeviceSharding(v5e.devices[0]))
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x).compile()
    assert _kernel_names(compiled, "flash_") == ["flash_bwd", "flash_fwd"]
    assert _asks_no_vmem(compiled, "flash_fwd")


@pytest.mark.parametrize("length", [128, 256, 512, 1024])
def test_flash_forward_compiles_at_the_served_buckets(v5e, length):
    """The forward kernel alone, as the served cells' bucket programs hold
    it: bf16, no gradient, [32 rows, a bucket's length, 16 heads of 64] as
    the projections wrote them (under 3 s a bucket). Its blocks (twice),
    accumulators, the v^T scratch a head and the rectangles of scores made
    ahead fit in what the compiler gives a kernel unasked."""
    from ray_tpu.ops.attention import dot_product_attention

    x = jax.ShapeDtypeStruct((32, length, 16, 64), jnp.bfloat16,
                             sharding=SingleDeviceSharding(v5e.devices[0]))
    compiled = jax.jit(lambda q, k, v: dot_product_attention(
        q, k, v, causal=True, impl="pallas", seq_major=True)).lower(
            x, x, x).compile()
    assert _kernel_names(compiled, "flash_") == ["flash_fwd"]
    assert _asks_no_vmem(compiled, "flash_fwd")


def _gpt2_medium_step(mesh, batch, remat_policy="dots"):
    from ray_tpu.models import (GPT, gpt2_medium, init_train_state,
                                make_optimizer, make_train_step)

    # "auto" asks jax.default_backend(), which is the CPU here: name the
    # kernel, as the step on the chip resolves it
    cfg = gpt2_medium(max_seq_len=1024, remat_policy=remat_policy,
                      attention_impl="pallas")
    model = GPT(cfg, mesh=mesh) if mesh is not None else GPT(cfg)
    opt = make_optimizer()
    state = jax.eval_shape(
        lambda: init_train_state(model, opt, jax.random.PRNGKey(0)))
    tokens = jax.ShapeDtypeStruct((batch, 1024), jnp.int32)
    return model, opt, make_train_step(model, opt, mesh=mesh), state, tokens


@pytest.mark.parametrize("remat_policy,kernel_calls",
                         [("dots", 2), ("full", 3)])
def test_gpt2_medium_train_step_fits_one_chip(v5e, remat_policy,
                                              kernel_calls):
    """The step of `gpt2m-steady` and `gpt2m-ckpt`: batch 12, "dots" remat.
    The compiler refuses a program that exceeds HBM (batch 16 does). "dots"
    saves the flash kernel's output, so a block runs the forward kernel
    once, then the backward kernel (one, `flash_bwd`, since PR 38: at one
    1024 tile a row the pair is never picked); "full" runs the forward
    kernel again in the backward pass. Nor may the compiler make room by
    recomputing on its own: with the kernel's and the out-projection's
    outputs both saved it ran the logits matmul twice (7 ms a step on the
    chip, PERF.md PR 29). Nor may a kernel's layout cost copies beside it:
    the step held 12 `copy` instructions under "dots" and 11 under "full"
    with the pair, 10 and 10 with the one backward kernel, and as many with
    PR 45's forward, which states no VMEM limit either."""
    one_chip = SingleDeviceSharding(v5e.devices[0])
    _, _, step, state, tokens = _gpt2_medium_step(None, 12, remat_policy)
    compiled = step.lower(_on(one_chip, state),
                          {"tokens": _on(one_chip, tokens)}).compile()
    assert len(_kernel_calls(compiled)) == kernel_calls
    assert len(_kernel_calls(compiled, "flash_fwd")) == kernel_calls - 1
    assert _kernel_names(compiled, "flash_bwd") == ["flash_bwd"]
    text = compiled.as_text()
    assert ".remat" not in text
    assert len(re.findall(r"= \S+ copy\(", text)) <= 10
    assert _asks_no_vmem(compiled, "flash_fwd")
    # the residuals stacked over the 24 layers: none has a 64-wide minor
    # dimension in memory (stored at 128 lanes, twice its size: q, k and v
    # were `bf16[24,12,1024,16,64]{4,2,3,1,0}` before PR 31), and q, k, v
    # are [24, 12, 1024, 1024] as the projections write and the kernels read
    stacks = set(re.findall(r"(?:bf16|f32)\[24,12,[0-9,]+\]\{[0-9,]+", text))
    assert stacks
    for stack in stacks:
        dims, layout = stack.split("]{")
        dims = [int(d) for d in dims.split("[")[1].split(",")]
        assert dims[int(layout.split(",")[0])] % 128 == 0, stack
    if remat_policy == "dots":
        assert "bf16[24,12,1024,1024]{3,2,1,0" in stacks
    # compiling at all means it fits; the donated state is aliased to the
    # new one, so what must fit beside it is the temporaries: 12.68 GiB
    # under "dots" (14.37 before PR 31, with q, k and v padded)
    assert compiled.memory_analysis().temp_size_in_bytes < 13.0 * 2 ** 30


def test_flash_attention_compiles_at_qwen3_next_width(v5e):
    """The flash kernels as `qwen3next-steady`'s full-attention layer calls
    them: [4 rows, 8192, 16 query heads on 2 KV heads, width 256], bf16,
    handed over as the projections wrote them (a width that fills the lanes
    takes the head-major kernels, a group of 8 their GQA block maps). The
    backward is the one kernel: the row's [256, 8192] float32 dq
    accumulator and its whole-row output block take more VMEM than a
    kernel is given unasked, so the call states what it needs, computed
    from its shapes, and compiles within it; so does a row twice as long,
    and one four times as long takes the pair."""
    from ray_tpu.ops.attention import dot_product_attention

    model = _qwen3_next_config()["model"]
    rows = _qwen3_next_config()["batch_per_chip"]

    def loss(q, k, v):
        return dot_product_attention(q, k, v, causal=True, impl="pallas",
                                     seq_major=True).astype(jnp.float32).sum()

    one_chip = SingleDeviceSharding(v5e.devices[0])
    q, k = (jax.ShapeDtypeStruct(
        (rows, model["max_seq_len"], heads, model["d_head"]), jnp.bfloat16,
        sharding=one_chip) for heads in (model["n_heads"],
                                         model["n_kv_heads"]))
    assert q.shape == (4, 8192, 16, 256) and k.shape == (4, 8192, 2, 256)
    from ray_tpu.ops import attention

    def vmem_stated(compiled):
        call, = _kernel_calls(compiled, "flash_bwd")
        return int(re.search(
            r'"scoped_memory_configs":\[\{[^}]*"size":"(\d+)"', call).group(1))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, k, k).compile()
    assert _kernel_names(compiled, "flash_") == ["flash_bwd", "flash_fwd"]
    assert _asks_no_vmem(compiled, "flash_fwd")
    half = attention._vmem_capacity() // 2      # no TPU here: the v5e's
    assert half == 64 * 2 ** 20
    assert attention._VMEM_UNASKED < vmem_stated(compiled) <= half
    # twice the row, one head: still one kernel, within what it states;
    # four times the row: the pair
    q2 = jax.ShapeDtypeStruct((1, 16384, 1, 256), jnp.bfloat16,
                              sharding=one_chip)
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q2, q2, q2).compile()
    assert _kernel_names(compiled, "flash_bwd") == ["flash_bwd"]
    assert vmem_stated(compiled) <= half
    q4 = jax.ShapeDtypeStruct((1, 32768, 1, 256), jnp.bfloat16,
                              sharding=one_chip)
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q4, q4, q4).compile()
    assert _kernel_names(compiled, "flash_bwd") == [
        "flash_bwd_dkv", "flash_bwd_dq"]


def test_delta_rule_kernels_compile_at_qwen3_next_width(v5e):
    """The gated delta rule's kernel pair as `qwen3next-steady`'s three
    Gated DeltaNet layers call it: [4 rows, 8192, 16 key heads serving 32
    value heads, width 128], bf16 with float32 g and beta, read and written
    as the projections hold them. Mosaic takes both, and their lines of the
    compiled text carry the scope the trace's readers look for: filed
    elsewhere, `gdn_rule_roofline` would divide the rule's least time by
    the leftover plumbing."""
    from ray_tpu.ops.delta_rule import gated_delta_rule

    model = _qwen3_next_config()["model"]
    rows = _qwen3_next_config()["batch_per_chip"]

    def loss(q, k, v, g, beta):
        with jax.named_scope("attn_kernel"), jax.named_scope("gdn_rule"):
            return gated_delta_rule(q, k, v, g, beta, impl="pallas").astype(
                jnp.float32).sum()

    one_chip = SingleDeviceSharding(v5e.devices[0])
    q, v, g = (jax.ShapeDtypeStruct(
        (rows, model["max_seq_len"], *minor), dtype, sharding=one_chip)
        for minor, dtype in (
            ((model["linear_key_heads"], model["linear_key_dim"]),
             jnp.bfloat16),
            ((model["linear_value_heads"], model["linear_value_dim"]),
             jnp.bfloat16),
            ((model["linear_value_heads"],), jnp.float32)))
    assert q.shape == (4, 8192, 16, 128) and v.shape == (4, 8192, 32, 128)
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        q, q, v, g, g).compile()
    assert _kernel_names(compiled, "gdn_rule_") == ["gdn_rule_bwd",
                                                    "gdn_rule_fwd"]
    for line in _kernel_calls(compiled, "gdn_rule_"):
        path = re.search(r'op_name="([^"]*)"', line).group(1)
        assert "/gdn_rule/" in path.split("gdn_rule_")[0], path


def test_gated_deltanet_pass_kernels_compile_at_qwen3_next_width(v5e):
    """The two passes around the rule as `qwen3next-steady`'s Gated DeltaNet
    layers call them: the convolution over the projection's [4 rows, 8192,
    8192] q~ k~ v~ columns (16 key and 32 value heads of 128) and the gated
    norm over [4, 8192, 4096], bf16 with float32 conv_w and lin_norm. Mosaic
    takes all four (windows of a float32 scratch that start off the tiles of
    rows, blocks of 512 rows by 1024 lanes within its memory), q, k and v
    are read in place and written as the rule takes them, the backward
    calls write one d qkv, and the lines carry the scopes the trace's
    readers look for."""
    from ray_tpu.ops.gated_deltanet import gdn_conv, gdn_gated_norm

    model = _qwen3_next_config()["model"]
    rows = _qwen3_next_config()["batch_per_chip"]
    kh, kd = model["linear_key_heads"], model["linear_key_dim"]
    vh, vd = model["linear_value_heads"], model["linear_value_dim"]

    def loss(qkv, conv_w, z, lin_norm):
        with jax.named_scope("attn_qkv"), jax.named_scope("gdn_conv"):
            q, k, v = gdn_conv(qkv, conv_w, key_heads=kh, key_dim=kd,
                               value_dim=vd, eps=1e-6, impl="pallas")
        with jax.named_scope("attn_out"), jax.named_scope("gdn_out"):
            y = gdn_gated_norm(v, z, lin_norm, eps=1e-6, impl="pallas")
        return sum(a.astype(jnp.float32).sum() for a in (q, k, y))

    one_chip = SingleDeviceSharding(v5e.devices[0])
    qkv, conv_w, z, lin_norm = (
        jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        for shape, dtype in (
            ((rows, model["max_seq_len"], 2 * kh * kd + vh * vd),
             jnp.bfloat16),
            ((model["linear_conv"], 2 * kh * kd + vh * vd), jnp.float32),
            ((rows, model["max_seq_len"], vh * vd), jnp.bfloat16),
            ((vd,), jnp.float32)))
    assert qkv.shape == (4, 8192, 8192) and z.shape == (4, 8192, 4096)
    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3))).lower(
        qkv, conv_w, z, lin_norm).compile()
    # q, k and v a call each way
    assert _kernel_names(compiled, "gdn_conv_") == (
        ["gdn_conv_bwd"] * 3 + ["gdn_conv_fwd"] * 3)
    assert _kernel_names(compiled, "gdn_norm_") == ["gdn_norm_bwd",
                                                    "gdn_norm_fwd"]
    for scope in ("gdn_conv", "gdn_norm"):
        for line in _kernel_calls(compiled, f"{scope}_"):
            path = re.search(r'op_name="([^"]*)"', line).group(1)
            inside = "/gdn_out/" if scope == "gdn_norm" else "/gdn_conv/"
            assert inside in path.split(f"{scope}_")[0], path
    # nothing of an activation's size is copied, padded, sliced out or
    # joined around them
    assert not _moved(compiled.as_text(), rows)


@pytest.mark.parametrize("axes", [dict(fsdp=4), dict(fsdp=2, tp=2)],
                         ids=["fsdp4", "fsdp2_tp2"])
def test_qwen3_next_period_train_step_compiles_for_the_host(v5e, axes):
    """The same step on the host's four chips, 4 rows of 8,192 between
    them: the delta rule's kernels run under `GPT._delta_rule`'s
    shard_map (a Mosaic call is not partitioned automatically, and left
    bare the step does not lower), a chip's rows under fsdp, and under tp
    also its half of the 16 key heads with their value heads; the two
    passes around the rule under `GPT._over_rows`'s."""
    from ray_tpu.models.training import batch_shardings
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh

    config = _qwen3_next_config()
    mesh = build_mesh(MeshSpec(**axes), devices=v5e.devices)
    step, state, tokens = _qwen3_next_step(
        config, config["batch_per_chip"], mesh)
    compiled = step.lower(
        state, {"tokens": _on(batch_shardings(mesh), tokens)}).compile()
    assert _kernel_names(compiled, "gdn_rule_") == (
        ["gdn_rule_bwd"] * 3 + ["gdn_rule_fwd"] * 6)
    # the passes around it under shard_maps of their own, rows over fsdp
    # and every head on each side of tp
    assert _kernel_names(compiled, "gdn_conv_") == (
        ["gdn_conv_bwd"] * 9 + ["gdn_conv_fwd"] * 18)
    assert _kernel_names(compiled, "gdn_norm_") == (
        ["gdn_norm_bwd"] * 3 + ["gdn_norm_fwd"] * 6)
    assert _kernel_names(compiled, "flash_") == [
        "flash_bwd", "flash_fwd", "flash_fwd"]
    # the held experts' rows are summed in `jnp` on a mesh: no Mosaic call
    # that the partitioner would have to split (PR 36)
    assert not _kernel_calls(compiled, "moe_segsum")
    # and the router keeps `lax.top_k` there (PR 41)
    assert not _kernel_calls(compiled, "moe_topk_")
    # a chip's share of the rule: [rows / fsdp, 8192, 16 / tp key heads]
    assert (f"bf16[{4 // axes['fsdp']},8192,{2048 // axes.get('tp', 1)}]"
            in compiled.as_text())
    assert "all-gather" in compiled.as_text()


@pytest.mark.slow   # 12 s here, and tier-1 runs close to its time limit
def test_gpt2_medium_fsdp4_train_step_compiles_for_the_host(v5e):
    """One worker granted a whole four-chip host: the same model on an
    fsdp=4 mesh built by build_mesh from the four described chips, global
    batch 48; each chip holds about a quarter of the state."""
    from ray_tpu.models.training import batch_shardings
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec(fsdp=4), devices=v5e.devices)
    _, _, step, state, tokens = _gpt2_medium_step(mesh, batch=48)
    # the step's own in_shardings place the state
    compiled = step.lower(
        state, {"tokens": _on(batch_shardings(mesh), tokens)}).compile()
    # "dots" through the shard_map branch of GPT._attention: the forward
    # kernel and the one backward kernel
    assert _kernel_names(compiled, "flash_") == ["flash_bwd", "flash_fwd"]
    assert len(_kernel_calls(compiled)) == 2
    assert "all-gather" in compiled.as_text()
    total = sum(np.prod(x.shape) * x.dtype.itemsize
                for x in jax.tree_util.tree_leaves(state))
    per_chip = compiled.memory_analysis().argument_size_in_bytes
    assert 0.2 * total < per_chip < 0.4 * total


def test_gpt2_xl_fsdp4_train_step_holds_the_one_backward_kernel(v5e):
    """`gpt2xl-fsdp4`'s step as `benchmarks/configs/gpt2_xl.json` describes
    it: 48 layers of 25 heads of 64 on an fsdp=4 mesh of the host's four
    chips, 16 x 1024 rows a chip under "full" remat. A block runs the
    forward kernel, runs it again in the backward pass and then the one
    backward kernel, whose thirteenth block of two heads is half full; the
    pair is not in the step, and the compiler recomputes nothing on its
    own."""
    import json
    from ray_tpu.models import (GPT, GPTConfig, init_train_state,
                                make_optimizer, make_train_step)
    from ray_tpu.models.training import batch_shardings
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks/configs/gpt2_xl.json")
    with open(path) as f:
        config = json.load(f)
    mesh = build_mesh(MeshSpec(**config["mesh"]), devices=v5e.devices)
    cfg = GPTConfig(**{**config["model"], "dtype": jnp.bfloat16,
                       "param_dtype": jnp.float32,
                       "attention_impl": "pallas"})
    model, opt = GPT(cfg, mesh=mesh), make_optimizer()
    state = jax.eval_shape(
        lambda: init_train_state(model, opt, jax.random.PRNGKey(0)))
    tokens = jax.ShapeDtypeStruct(
        (config["batch_per_chip"] * config["chips"], cfg.max_seq_len),
        jnp.int32)
    compiled = make_train_step(model, opt, mesh=mesh).lower(
        state, {"tokens": _on(batch_shardings(mesh), tokens)}).compile()
    assert _kernel_names(compiled, "flash_") == [
        "flash_bwd", "flash_fwd", "flash_fwd"]
    assert len(_kernel_calls(compiled)) == 3
    assert _asks_no_vmem(compiled, "flash_fwd")
    assert ".remat" not in compiled.as_text()


def test_ring_attention_fwd_bwd_compiles_over_four_chips(v5e):
    """Ring attention under shard_map, sequence split four ways:
    [2, 16, 4096, 64] bf16, blocks 512. It has never run on a chip; the
    long-context work (ROADMAP R4) builds on it."""
    from ray_tpu.ops.ring_attention import ring_attention

    mesh = Mesh(np.array(v5e.devices), ("sp",))
    spec = P(None, None, "sp", None)

    def local(q, k, v):
        return ring_attention(q, k, v, "sp", True, None, "pallas", 512, 512)

    def loss(q, k, v):
        out = jax.shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                            out_specs=spec, check_vma=False)(q, k, v)
        return out.astype(jnp.float32).sum()

    x = jax.ShapeDtypeStruct((2, 16, 4096, 64), jnp.bfloat16,
                             sharding=NamedSharding(mesh, spec))
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "collective-permute" in text
