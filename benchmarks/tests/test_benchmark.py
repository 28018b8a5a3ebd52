"""The benchmark's own checks. CPU only, not part of tier-1:

    python -m pytest benchmarks/tests -q

They cover the yardstick (FLOP arithmetic, traffic generation, the plain
reference, the trace reduction), the contract of `BENCHMARK.json`, and the
rule that a configuration, a traffic mix, a cell and a per-layer metric are
added as files and entries: the rehearsal at the bottom runs cells that
exist only in `tests/fixtures/`, through the unedited harness, end to end on
the CPU, down to the contract's last line.
"""

import json
import math
import os
import re
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmarks import cells, flops, trace_reduce, traffic_gen  # noqa: E402

FIXTURES = os.path.join(HERE, "fixtures")
TINY = os.path.join(FIXTURES, "BENCHMARK.tiny.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _benchmark():
    return cells.load_json(os.path.join(ROOT, "BENCHMARK.json"))


# ------------------------------------------------------------------- flops

def test_flops_per_token_by_hand():
    """gpt2_medium: 24 layers x 12 d^2 matmul parameters, d = 1024;
    gpt2_xl: 48 layers, d = 1600; rows of 1024; vocab 50304."""
    medium = cells.load_json(os.path.join(
        ROOT, "benchmarks/configs/gpt2_medium.json"))["model"]
    xl = cells.load_json(os.path.join(
        ROOT, "benchmarks/configs/gpt2_xl.json"))["model"]
    by_hand_m = (6 * 24 * 12 * 1024 ** 2      # blocks   1.8119 G
                 + 6 * 1024 * 50304           # head     0.3091 G
                 + 6 * 24 * 1024 * 1024)      # causal attention 0.1510 G
    by_hand_x = 6 * 48 * 12 * 1600 ** 2 + 6 * 1600 * 50304 \
        + 6 * 48 * 1600 * 1024
    assert flops.model_flops_per_token(medium, 1024) == by_hand_m
    assert flops.model_flops_per_token(xl, 1024) == by_hand_x
    assert round(by_hand_m / 1e9, 3) == 2.272
    assert round(by_hand_x / 1e9, 3) == 9.802
    # the run's row length counts, not the configuration's maximum
    assert flops.model_flops_per_token(medium, 512) == \
        by_hand_m - 6 * 24 * 1024 * 512


def test_flash_work_and_roofline():
    model = {"d_model": 1024, "n_layers": 24, "n_heads": 16}
    work = flops.flash_attention_work(model, 1024, 12)
    tokens = 12 * 1024
    assert work["flops"] == 6 * 24 * 1024 * 1024 * tokens
    assert work["bytes"] == 12 * 24 * 1024 * tokens * 2
    # the configuration's own geometry: the layers of the pattern that
    # attend, the head width it states, fewer key and value heads. One
    # period of Qwen3-Next at 4 x 8192: one layer of 16 x 256, 33.5 ms at
    # the peak (PERF.md section 3), not n_layers x d_model = 4 x 2048
    hybrid = cells.load_json(os.path.join(
        ROOT, "benchmarks/configs/qwen3_next_80b_a3b.json"))["model"]
    work = flops.flash_attention_work(hybrid, 8192, 4)
    assert work["flops"] == 6 * 1 * 16 * 256 * 8192 * 4 * 8192
    assert work["bytes"] == 6 * (16 + 2) * 256 * 4 * 8192 * 2
    assert round(work["flops"] / 197e12 * 1e3, 1) == 33.5
    peak = cells.load_json(os.path.join(ROOT, "benchmarks/peaks.json"))[
        "TPU v5 lite"]
    line = flops.roofline_seconds(work, peak)
    assert line["bound"] == "compute"
    assert math.isclose(line["seconds"], work["flops"] / 197e12)


# ----------------------------------------------------------------- traffic

def test_traffic_is_a_pure_function_of_the_seed():
    traffic = cells.load_json(os.path.join(
        ROOT, "benchmarks/traffic/train-packed-1k.json"))
    a = traffic_gen.packed_rows(traffic, 64, seed=7)
    b = traffic_gen.packed_rows(traffic, 64, seed=7)
    c = traffic_gen.packed_rows(traffic, 64, seed=8)
    assert a["tokens"].dtype == np.int32 and a["tokens"].shape == (64, 1024)
    assert np.array_equal(a["tokens"], b["tokens"])
    assert not np.array_equal(a["tokens"], c["tokens"])
    # every document ends in the end-of-text id, and nothing else is it
    assert int((a["tokens"] == traffic["eot_id"]).sum()) in (
        len(a["doc_lengths"]), len(a["doc_lengths"]) - 1)
    assert a["tokens"].max() <= traffic["eot_id"] < 50304
    # heavy-tailed lengths, clipped; Zipf: the most frequent id is id 0
    assert a["doc_lengths"].min() >= 8 and a["doc_lengths"].max() <= 8192
    assert np.bincount(a["tokens"].ravel()).argmax() == 0
    assert 6.0 < traffic_gen.unigram_entropy(traffic["tokens"]) < 7.0


# --------------------------------------------------------------- reference

def test_reference_agrees_with_the_program_at_a_tiny_width():
    """`reference/gpt2.py` against `ray_tpu.models.GPT` in float32, on the
    CPU: the same seeded weights, the same rows, logits and loss."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import jax.numpy as jnp
    from benchmarks.reference import gpt2, gpt2_glue
    from ray_tpu.models import GPT
    from ray_tpu.models.gpt import GPTConfig

    config = GPTConfig(vocab_size=256, n_layers=3, d_model=64, n_heads=4,
                       max_seq_len=32, dtype=jnp.float32, remat=False,
                       attention_impl="reference")
    model = GPT(config)
    params = model.init(jax.random.PRNGKey(3))
    # a bias that is not zero, or a dropped LayerNorm bias would pass
    params["blocks"]["bias1"] = params["blocks"]["bias1"] + 0.1
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 32), 0, 256)
    with jax.default_matmul_precision("highest"):
        logits = model.apply(params, tokens)
        _, metrics = model.loss(params, {"tokens": tokens})
    top, layers = gpt2_glue.reference_weights(params, None, jax.devices())
    terms = gpt2.loss_terms(tokens, top, layers, {"model": {"n_heads": 4}})
    assert float(jnp.max(jnp.abs(terms["logits"] - logits))) < 1e-4
    assert abs(float(terms["ce"]) - float(metrics["ppl_log"])) < 1e-5


# ---------------------------------------------------- the first-loss check

def test_the_first_loss_is_centred_on_the_head_at_init():
    from benchmarks.loops.train import expected_first_loss
    olmoe = cells.load_json(os.path.join(
        ROOT, "benchmarks/configs/olmoe_1b_7b.json"))["model"]
    assert round(expected_first_loss(olmoe), 3) == 11.235
    # GPT-2 XL's: + 0.32 over ln V, GPT-2 medium's + 0.20
    assert math.isclose(expected_first_loss(
        {"vocab_size": 50304, "d_model": 1600}), math.log(50304) + 0.32)
    assert math.isclose(expected_first_loss(
        {"vocab_size": 50304, "d_model": 1024}), math.log(50304) + 0.2048)


def _cut_at_the_cells_width(config_name, vocab_scale=1.0):
    """The configuration's model cut between embedding and head: the width
    and the vocabulary, which the first loss depends on, stay. The hybrid's
    layers go down to toy sizes (its final norm hands the untied head rows of
    unit RMS whatever they do); GPT-2 keeps two whole layers at its own
    widths, because its head is its embedding and what the blocks add to the
    residual stream decides how much of a token's own embedding the head
    still sees."""
    import jax.numpy as jnp
    config = cells.load_json(os.path.join(
        ROOT, "benchmarks/configs", config_name + ".json"))
    model = dict(config["model"], max_seq_len=512, dtype=jnp.float32,
                 param_dtype=jnp.float32, remat=False,
                 attention_impl="reference")
    model["vocab_size"] = int(model["vocab_size"] * vocab_scale)
    if "layer_pattern" in model:
        model.update(n_heads=2, n_kv_heads=1, d_head=16, d_ff=16,
                     linear_key_heads=2, linear_value_heads=2,
                     linear_key_dim=16, linear_value_dim=16, n_experts=8,
                     moe_top_k=2, moe_experts_held=8, moe_shared_ff=16)
    else:
        model.update(n_layers=2)
    return config, model


# (configuration, cell, fault, the shift it causes from .. to, caught)
PLANTED = [
    ("qwen3_next_80b_a3b", "qwen3next-steady", None, 0.0, 0.0, False),
    ("qwen3_next_80b_a3b", "qwen3next-steady", "head x 1.5", 0.4, 0.6, True),
    ("qwen3_next_80b_a3b", "qwen3next-steady", "head x 0.5", -0.4, -0.25,
     True),
    ("gpt2_xl", "gpt2xl-fsdp4", None, 0.0, 0.0, False),
    ("gpt2_xl", "gpt2xl-fsdp4", "head x 1.5", 0.3, 0.45, False),
    ("gpt2_xl", "gpt2xl-fsdp4", "head x 2", 0.8, 1.1, True),
    ("gpt2_xl", "gpt2xl-fsdp4", "rows x 1.65", 0.45, 0.55, False),
    ("gpt2_xl", "gpt2xl-fsdp4", "rows x 2.7", 0.95, 1.05, True)]


@pytest.mark.parametrize("config_name,workload,fault,low,high,caught",
                         PLANTED, ids=[f"{c}-{f}" for c, _, f, *_ in PLANTED])
def test_a_planted_fault_against_the_first_loss_limit(config_name, workload,
                                                      fault, low, high,
                                                      caught):
    """The faults `first_loss_halfwidth` is there for, planted at the cell's
    width and vocabulary on the cell's own Zipf rows, forward only on the
    CPU: a head (for GPT-2 the tied embedding) whose entries have standard
    deviation 0.03, 0.04 or 0.01 where the seeded one has 0.02, which moves
    the first cross-entropy by about (std^2 - 0.02^2) d / 2; and a loss over
    1.65 or 2.7 times the vocabulary's rows, which moves it by the
    logarithm. Each case holds the shift the fault causes, against the sound
    model of the same seed, and whether the configuration's half-width
    catches it at this seed, whose sound reading lies within 0.05 of the
    centre. Qwen3-Next's 0.25 catches a head at 0.03. GPT-2 XL's 0.5 does
    not, nor 1.65 times the rows (kind `train`'s old limit, 0.5 around ln V,
    did, and refused sound seeds: PERF.md section 6, PR 34): its first
    cross-entropy strays from the centre with a standard deviation of 0.11
    from seed to seed, and the half-width is four of those; it catches a
    head at 0.04 and 2.7 times the rows."""
    import jax
    import jax.numpy as jnp
    from benchmarks.loops.train import (expected_first_loss,
                                        first_loss_problems)
    from ray_tpu.models import GPT
    from ray_tpu.models.gpt import GPTConfig

    cell = cells.resolve(workload)
    rows = traffic_gen.packed_rows(cell.traffic, 4, 12)["tokens"]
    batch = {"tokens": jnp.asarray(rows[:, :512], jnp.int32)}
    what, _, times = (fault or "nothing x 1").partition(" x ")

    def first_ce(vocab_scale=1.0, head_scale=1.0):
        config, model = _cut_at_the_cells_width(config_name, vocab_scale)
        gpt = GPT(GPTConfig(**model))
        params = jax.jit(gpt.init)(jax.random.PRNGKey(11))
        head = "lm_head" if "lm_head" in params else "tok_embed"
        params[head] = params[head] * head_scale
        _, metrics = jax.jit(gpt.loss)(params, batch)
        return config, float(metrics.get("ce_loss", metrics["ppl_log"]))

    config, sound = first_ce()
    assert abs(sound - expected_first_loss(config["model"])) < 0.05, sound
    _, planted = first_ce(**{"rows": {"vocab_scale": float(times)},
                             "head": {"head_scale": float(times)},
                             "nothing": {}}[what])
    assert low <= planted - sound <= high, planted - sound
    problems = first_loss_problems(
        [planted], config["model"],
        config["reference"]["first_loss_halfwidth"])
    assert bool(problems) is caught, (sound, planted)
    if problems:
        assert "first cross-entropy" in problems[0]


# ------------------------------------------------------------ trace reduce

def _synthetic_trace():
    """Two devices' worth of a trace, by hand: three step executions, a
    `while` with nested ops, an exposed all-gather, a gap under a span."""
    us = 1e3
    ops = [
        ("while.1", 0 * us, 60 * us, ""),
        ("fusion.1", 0 * us, 20 * us, "fusion"),
        ("all-gather-done.1", 20 * us, 10 * us, "all-gather"),
        ("custom-call.7", 30 * us, 30 * us, "custom-call"),
        ("fusion.2", 60 * us, 20 * us, "fusion"),
        # 80..100: idle (the host is in bench:batch_wait)
        ("while.1", 100 * us, 60 * us, ""),
        ("fusion.1", 100 * us, 20 * us, "fusion"),
        ("all-gather-done.1", 120 * us, 10 * us, "all-gather"),
        ("custom-call.7", 130 * us, 30 * us, "custom-call"),
        ("fusion.2", 160 * us, 20 * us, "fusion"),
        # 180..260: idle (bench:ckpt_write)
        ("fusion.1", 260 * us, 20 * us, "fusion"),
    ]
    modules = [("jit_train_step(123)", 0 * us, 80 * us, ""),
               ("jit_train_step(123)", 100 * us, 80 * us, ""),
               ("jit_checksum(9)", 185 * us, 1 * us, ""),
               ("jit_train_step(123)", 260 * us, 80 * us, "")]
    host = [("bench:batch_wait", 78 * us, 25 * us, ""),
            ("bench:ckpt_write", 182 * us, 70 * us, ""),
            ("bench:step_enqueue", 255 * us, 2 * us, "")]
    device = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": ops},
        {"name": "XLA Modules", "events": modules}]}
    return {"planes": [device,
                       {"name": "/host:CPU",
                        "lines": [{"name": "python", "events": host}]}]}


def test_trace_reduction_on_a_trace_made_by_hand():
    reduced = trace_reduce.reduce_trace(_synthetic_trace(), "train_step")
    assert reduced["n_steps"] == 2 and reduced["devices"] == 1
    assert math.isclose(reduced["window_s"], 260e-6)
    # busy: 0..80 and 100..180 (the nested ops lie inside the while)
    assert math.isclose(reduced["busy_s"], 160e-6)
    assert reduced["step_device_ms"] == [0.08, 0.08]
    table = {name: (secs, n) for name, secs, n, _ in reduced["ops"]}
    assert math.isclose(table["while.1"][0], 0.0, abs_tol=1e-12)
    assert math.isclose(table["custom-call.7"][0], 60e-6)
    assert table["fusion.1"][1] == 2         # the third starts the next window
    assert math.isclose(reduced["collective_exposed_s"], 20e-6)
    assert [g[0] for g in reduced["idle_gaps"]] == ["ckpt_write",
                                                    "batch_wait"]
    assert math.isclose(reduced["idle_gaps"][0][1], 80e-6)
    assert math.isclose(reduced["idle_by_span"]["ckpt_write"], 80e-6)
    out = trace_reduce.breakdown(reduced)
    assert len(out["device_ops"]) <= 10
    assert out["device_ops"][0] == ["custom-call.7 [custom-call]",
                                    table["custom-call.7"][0]]
    assert trace_reduce.short_name(
        "%fusion.12 = (f32[8,128]{1,0:T(8,128)S(1)}, s32[]) fusion(f32[8]{0} "
        "%p.1, s32[] %p.2), kind=kLoop, calls=%fused") == ("fusion.12",
                                                           "fusion")
    assert math.isclose(
        trace_reduce.op_seconds_per_step(reduced, r"^custom-call$"), 30e-6)
    assert trace_reduce.reduce_trace({"planes": []}, "train_step") is None


RECORDED = os.path.join(FIXTURES, "v5e_gpt2_tiny_3steps.xplane.pb")


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded trace in the fixtures")
def test_trace_reduction_on_a_recorded_v5e_trace():
    """A trace recorded on a v5e chip (PR 23): three steps and a sentinel of
    the fixtures' tiny model. Holds the reader to the real format: the
    plane and line names, nesting, the kernels' names, the spans' clock."""
    reduced = trace_reduce.reduce_file(RECORDED, "train_step")
    expected = cells.load_json(os.path.join(
        FIXTURES, "v5e_gpt2_tiny_3steps.expected.json"))
    assert reduced["devices"] == 1
    assert reduced["n_steps"] == expected["n_steps"]
    assert 0 < reduced["busy_s"] <= reduced["window_s"]
    assert math.isclose(reduced["busy_s"], expected["busy_s"], rel_tol=1e-9)
    assert math.isclose(reduced["window_s"], expected["window_s"],
                        rel_tol=1e-9)
    # 2 layers x (forward, recomputed forward, dq, dkv), every step
    kernels = [row for row in reduced["ops"]
               if row[3] == expected["kernel_opcode"] and row[1] > 1e-6]
    assert sum(row[2] for row in kernels) == \
        expected["kernel_calls_per_step"] * reduced["n_steps"]
    assert math.isclose(
        trace_reduce.op_seconds_per_step(reduced, r"^custom-call$"),
        sum(row[1] for row in kernels) / reduced["n_steps"], rel_tol=1e-3)
    # the save between the last traced step and the sentinel is the longest
    # gap, and the host span on the profiler's clock names it
    assert reduced["idle_gaps"][0] == expected["longest_gap"]
    assert {g[0] for g in reduced["idle_gaps"]} <= set(
        expected["span_labels"]) | {"none"}
    assert math.isclose(sum(reduced["idle_by_span"].values()),
                        reduced["window_s"] - reduced["busy_s"],
                        rel_tol=1e-9)


# ------------------------------------------------------- the steps' rate

def test_the_rate_is_a_median_that_late_wakeups_do_not_move():
    """`tokens_per_s_per_chip` comes from the median time between step
    completions: a host that reads a loss late, or a device queue that
    drains behind a stalled host, moves a few readings and not the rate;
    saves and the refill after them are in no reading."""
    from benchmarks.loops.train import median_step_seconds
    step = 0.363
    clean = [(1, i, 10.0 + i * step) for i in range(1, 121)]
    assert median_step_seconds(clean) == pytest.approx(step, rel=1e-9)
    # the seen kind: one loss read 0.81 s late, the queue drained for 0.1 s
    late = [(seg, i, t + (0.81 if i == 40 else 0.1 if i > 40 else 0.0))
            for seg, i, t in clean]
    assert median_step_seconds(late) == pytest.approx(step, rel=1e-9)
    # the refused kind: ten stalls of a second each, 3 s of a 45 s window
    # lost (a mean would read 7% low)
    lost, rows = 0.0, []
    for seg, i, t in clean:
        if i % 12 == 0:
            lost += 0.3
            rows.append((seg, i, t + lost + 0.7))   # read late as well
        else:
            rows.append((seg, i, t + lost))
    assert (rows[-1][2] - rows[0][2]) / 119 > 1.06 * step
    assert median_step_seconds(rows) == pytest.approx(step, rel=1e-9)
    # a save between two steps: a new segment, and no reading across it
    saved = ([(1, i, i * step) for i in range(1, 21)]
             + [(2, i, 25.0 + i * step) for i in range(21, 41)])
    assert median_step_seconds(saved) == pytest.approx(step, rel=1e-9)
    assert median_step_seconds([(1, 1, 0.0), (2, 2, 30.0)]) is None
    assert median_step_seconds([]) is None


# ---------------------------------------------------------------- contract

def test_benchmark_json_meets_the_contract_and_names_files_that_load():
    bench = _benchmark()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names)) and "setup_s" in names
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    layers = set()
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in names
        layers.add(m["layer"])
    for thing in names + [w["name"] for w in bench["workloads"]] + [
            c["name"] for c in bench["configs"]]:
        assert NAME.match(thing), thing
    for w in bench["workloads"]:
        assert len(w["why"]) <= 200, (w["name"], len(w["why"]))
        cell = cells.resolve(w["name"])
        assert cell.config["chips"] == w["chips"]
        assert os.path.isfile(os.path.join(
            ROOT, "benchmarks/loops", cell.traffic["kind"] + ".py"))
        # one loop a kind; what differs between models the configuration
        # names
        assert cell.traffic["kind"] in ("train", "serve")
        if cell.traffic["kind"] == "serve":
            _check_served_cell(cell)
        else:
            for named in (cell.config["reference"]["module"],
                          cell.config["reference"]["glue"],
                          cell.config["work"]["module"]):
                assert os.path.isfile(os.path.join(ROOT, "benchmarks", named))
            group = cell.config["reference"]
            assert 0 < group["first_loss_halfwidth"] < 0.8
            # what the first steps are held to, and what the reference follows
            for limit in ("step_loss_atol", "grad_norm_rtol", "grad_leaf_rtol",
                          "change_leaf_rtol"):
                assert 0 < group[limit] < 1, (w["name"], limit)
            assert 1 <= group["steps"] <= 3 and "z_loss" in group["objective"]
            assert set(group["adamw"]) == {
                "learning_rate", "warmup_steps", "total_steps", "end_fraction",
                "b1", "b2", "eps", "weight_decay", "clip"}
            assert group["adamw"]["learning_rate"] == cell.config["optimizer"][
                "learning_rate"]
            assert cell.traffic["warmup_steps"] >= group["steps"]
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.per_layer:
            # `<reader>.<suffix>` is one reader's number under two entries,
            # one for each end-to-end metric it moves (`loops/serve.py`)
            assert callable(cells.layer_reader(cell,
                                               m["name"].split(".")[0]))
            # an entry reads in a cell only if the cell reports what it moves
            assert any(e["name"] == m["moves"] for e in cell.end_to_end), (
                w["name"], m["name"])
    for c in bench["configs"]:
        assert any(w["config"] == c["name"] for w in bench["workloads"])
        config = cells.load_json(os.path.join(ROOT, c["file"]))
        assert config["source"] == c["source"]
        assert config["reduced"] == c["reduced"]
    # PERF.md lists every layer under the name BENCHMARK.json gives it
    perf = open(os.path.join(ROOT, "PERF.md")).read()
    for layer in layers:
        assert f"| {layer} |" in perf, layer


def _check_served_cell(cell):
    """What `loops/serve.py` needs named: the served group beside the
    configuration's file, the traffic's arrivals, buckets and sample."""
    from benchmarks.loops import serve
    served = serve.served_group(cell.root, cell.paths, cell.config_name)
    assert served["dtype"] in ("bfloat16", "float32")
    for named in (served["reference"]["module"], served["reference"]["glue"],
                  served["work"]["module"]):
        assert os.path.isfile(os.path.join(ROOT, "benchmarks", named))
    reference = cells.module(cell.root, cell.paths,
                             served["reference"]["module"])
    work = cells.module(cell.root, cell.paths, served["work"]["module"])
    assert callable(reference.token_logprobs)
    assert callable(work.forward_flops) and callable(work.flash_forward_work)
    for limit in ("score_gap_max", "score_gap_rms"):
        assert 0 < served["reference"][limit] < 1, limit
    assert len(served["reference"]["why"]) > 200
    traffic = cell.traffic
    assert traffic["arrivals"]["rate_per_s"] > 0
    assert traffic["deadline_ms"] > 0 and len(traffic["why"]) > 200
    batching = traffic["batching"]
    assert max(batching["lengths"]) == cell.config["model"]["max_seq_len"]
    assert max(batching["lengths"]) >= traffic["documents"]["length"]["max"]
    assert max(batching["rows"]) <= batching["max_batch_size"]
    assert traffic["check"]["sample"] >= 64
    assert traffic["tokens"]["support"] <= cell.config["model"]["vocab_size"]


# --------------------------------------------------------------- rehearsal

def _rehearse(workload, trace, devices, seconds="3", errors=None):
    """One cell of the fixtures' own BENCHMARK file through `run.main`, in a
    process of its own (it starts and stops a runtime). `errors`, a list,
    gets what the run wrote to standard error."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=os.path.join(
                   ROOT, ".bench_runs", "test_cache"))
    if devices > 1:
        env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count="
                            f"{devices}")
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "from benchmarks import run\n"
        "sys.exit(run.main(['--workload', %r, '--seed', '5', '--seconds', "
        "%r, '--trace', %r], benchmark_file=%r, "
        "rehearsal={'num_tpus': %d}))\n"
        % (ROOT, workload, seconds, str(trace), TINY, devices))
    done = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    if errors is not None:
        errors.append(done.stderr)
    return json.loads(done.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("workload,trace,devices", [
    ("tiny-steady", 0, 1), ("tiny-ckpt", 0, 1), ("tiny-ckpt", 1, 1),
    ("tiny-fsdp4", 0, 4)])
def test_a_cell_made_only_of_new_files_runs_end_to_end(workload, trace,
                                                       devices):
    """The fixtures add two configurations, two traffic mixes, three cells
    and a per-layer metric (`steps_in_window`) as files of their own plus
    entries in their own BENCHMARK file; nothing under `benchmarks/` outside
    `tests/` knows them."""
    line = _rehearse(workload, trace, devices)
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device", "compared"}
    # a CPU run is never a result: it says so itself
    assert line["correct"] is False and line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == devices
    assert line["attempted"] > 0 and line["failed"] == 0
    cell = cells.resolve(workload, TINY)
    if trace:
        # host-span readers answer; device readers find no device plane
        # and return nothing, so their metrics are left out of the line
        assert {"steps_in_window", "report_ms", "batch_wait_ms",
                "ckpt_write_s", "ckpt_persist_s", "ckpt_stall_s",
                "ckpt_durable_s", "ckpt_goodput", "window_idle_share"
                } <= set(line["metrics"])
        assert "device_idle_share" not in line["metrics"]
    else:
        assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}
    for name, m in line["metrics"].items():
        # a share of idle time may be 0 or, by the median's error, under it
        assert name == "window_idle_share" or m["value"] > 0, name
        assert math.isfinite(m["value"]) and m["unit"], name


def test_no_whole_save_before_the_window_and_nothing_compiles_in_it():
    """A checkpointing cell makes no whole save before its window (PR 44:
    two stood there, the state four times through the machine's disk, and
    `setup_s` swung with the disk). The save path's one-off work, the
    checksum's program and orbax's import, is still set-up's: the window's
    first save compiles nothing."""
    assert "warmup_saves" not in cells.resolve("gpt2m-ckpt").traffic
    errors = []
    line = _rehearse("tiny-ckpt", 0, 1, errors=errors)
    assert line["attempted"] > 0 and line["failed"] == 0
    assert "ckpt_leaf_error" in line["compared"]
    assert "not correct: ran on 'cpu'" in errors[0]     # the lines are there
    assert "inside the window" not in errors[0]


def test_no_program_no_result(tmp_path):
    """In a directory that holds only BENCHMARK.json and `benchmarks/`, the
    command exits non-zero and prints no result."""
    import shutil
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    done = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "gpt2m-steady",
         "--seed", "0", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"})
    assert done.returncode != 0 and done.stdout.strip() == ""
