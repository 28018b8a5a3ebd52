"""The checks of what PR 32 added to the benchmark: the `qwen3_next_80b_a3b`
configuration, the hybrid's work file, its routing check and the five
readers (`gdn_share`, `gdn_rule_ms`, `gdn_rule_roofline`, `moe_held_share`,
`moe_held_pairs_per_token`). CPU only, not part of tier-1:

    python -m pytest benchmarks/tests/test_qwen3_next.py -q

(The system against `reference/qwen3_next.py` is tier-1's
`tests/test_qwen3_next_reference.py`.)
"""

import json
import math
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmarks import (cells, flops, hybrid_work, moe_work,  # noqa: E402
                        program_trace)

FIXTURES = os.path.join(HERE, "fixtures")
TINY = os.path.join(FIXTURES, "BENCHMARK.hybrid_tiny.json")
NEW_METRICS = ("gdn_share", "gdn_rule_ms", "gdn_rule_roofline",
               "moe_held_share", "moe_held_pairs_per_token")
# OLMoE's readers that find the same scopes and step metrics in this cell
SHARED_METRICS = ("moe_experts_share", "moe_dispatch_share",
                  "moe_load_max_over_mean")


def _config():
    return cells.load_json(os.path.join(
        ROOT, "benchmarks/configs/qwen3_next_80b_a3b.json"))


def test_the_configuration_keeps_every_published_width():
    config = _config()
    published = config["published"]
    differs = {k for k, v in published.items() if config[k] != v}
    assert differs == set(config["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    # the floors of a cut: a whole period and four layers, at least 8
    # experts a layer, at least an eighth of the vocabulary
    assert config["num_hidden_layers"] == published["full_attention_interval"]
    assert config["num_experts"] >= 8
    assert config["vocab_size"] * 8 >= published["vocab_size"]
    model = config["model"]
    assert (model["d_model"], model["n_heads"], model["n_kv_heads"],
            model["d_head"], model["d_ff"], model["moe_shared_ff"],
            model["n_experts"], model["moe_top_k"],
            model["moe_experts_held"], model["vocab_size"],
            model["n_layers"]) == (
        published["hidden_size"], published["num_attention_heads"],
        published["num_key_value_heads"], published["head_dim"],
        published["moe_intermediate_size"],
        published["shared_expert_intermediate_size"],
        published["num_experts"], published["num_experts_per_tok"],
        config["num_experts"], config["vocab_size"],
        config["num_hidden_layers"])
    assert (model["linear_key_heads"], model["linear_value_heads"],
            model["linear_key_dim"], model["linear_value_dim"],
            model["linear_conv"]) == (
        published["linear_num_key_heads"],
        published["linear_num_value_heads"],
        published["linear_key_head_dim"], published["linear_value_head_dim"],
        published["linear_conv_kernel_dim"])
    assert model["layer_pattern"] == ["linear"] * 3 + ["full"]
    assert model["rope_fraction"] == published["partial_rotary_factor"]
    assert model["rope_theta"] == published["rope_theta"]
    assert model["norm_eps"] == published["rms_norm_eps"]
    assert model["moe_norm_topk_prob"] is published["norm_topk_prob"]
    assert model["tie_embeddings"] is published["tie_word_embeddings"]
    assert config["router_outputs"] == published["num_experts"]
    assert model["moe_first_expert"] == config["first_expert_held"] == 0
    cell = cells.resolve("qwen3next-steady")
    assert cell.traffic["seq_len"] == model["max_seq_len"] == 8192
    assert cell.traffic["kind"] == "train"
    assert config["work"] == {"module": "hybrid_work.py",
                              "routing_check": "held_share_routed"}
    assert cell.traffic["eot_id"] == cell.traffic["tokens"]["support"] == (
        config["vocab_size"] - 1)
    assert {m["name"] for m in cell.per_layer} >= set(
        NEW_METRICS + SHARED_METRICS)
    # since PR 34 from the pairs the steps reported, so it reads here too
    assert "moe_gmm_roofline" in {m["name"] for m in cell.per_layer}
    work = moe_work.expert_matmul_work(model, 0.46 * 4 * 8192 * 4)
    assert work["flops"] == 18 * 0.46 * 4 * 8192 * 4 * 2048 * 512
    # 32 held experts' three matrices a layer, read twice and written once
    assert work["bytes"] > 2 * 3 * (3 * 4 * 32 * 2048 * 512)
    peak = cells.load_json(os.path.join(ROOT, "benchmarks/peaks.json"))[
        "TPU v5 lite"]
    assert flops.roofline_seconds(work, peak)["bound"] == "compute"
    assert 5.5e-3 < flops.roofline_seconds(work, peak)["seconds"] < 6.0e-3


def test_model_flops_by_hand():
    """One period at 8k rows, 0.625 routed pairs a token a layer: three
    Gated DeltaNet layers 3 x 211.7 MFLOP a token, the full-attention layer
    163.6 of projections + 201.3 of attention, four expert blocks 4 x 37.0,
    the head 233.4: 1.381 GFLOP a token."""
    model = _config()["model"]
    d = 2048
    assert hybrid_work.uniform_pairs_per_token(model) == 0.625
    parts = hybrid_work.flops_by_part(model, 8192)
    linear = d * 12288 + d * 64 + 4 * 8192 + 4096 * d
    assert parts["linear_projections"] == 6 * 3 * linear
    assert parts["delta_rule"] == 3 * 18 * 128 * 128 * 32
    assert round((parts["linear_projections"] + parts["delta_rule"])
                 / 3e6, 1) == 211.7
    assert parts["full_projections"] == 6 * (
        d * 16 * 512 + 2 * d * 512 + 4096 * d)
    assert parts["attention"] == 6 * 8192 * 16 * 256
    block = d * 512 + 3 * d * 512 + d + 0.625 * 3 * d * 512
    assert parts["expert_blocks"] == 6 * 4 * block
    assert parts["head"] == 6 * d * 18992
    total = hybrid_work.model_flops_per_token(model, 8192)
    assert total == sum(parts.values())
    assert round(total / 1e9, 3) == 1.381
    # more pairs routed here, more work; a dense GPT-2 is the period ("full",)
    assert hybrid_work.model_flops_per_token(model, 8192, 1.25) == (
        total + 6 * 4 * 0.625 * 3 * d * 512)
    gpt2 = {"n_layers": 24, "d_model": 1024, "n_heads": 16}
    assert hybrid_work.mixer_params(gpt2, "full") == 4 * 1024 * 1024


def test_the_delta_rules_least_work_is_bound_by_its_bytes():
    model = _config()["model"]
    tokens = 4 * 8192
    work = hybrid_work.delta_rule_work(model, tokens)
    assert work["flops"] == 3 * tokens * 18 * 128 * 128 * 32
    # forward: q, k (2,048 each), v, o (4,096 each) in bf16, g and beta in
    # float32; backward: q, k, v, dO read, dq, dk, dv written, and g, beta,
    # dg, dbeta
    per_token = 2 * (2 * 2048 + 2 * 4096) + 4 * 2 * 32 + 2 * (
        2 * (2 * 2048 + 4096) + 4096) + 4 * 4 * 32
    assert work["bytes"] == 3 * tokens * per_token
    peak = cells.load_json(os.path.join(ROOT, "benchmarks/peaks.json"))[
        "TPU v5 lite"]
    line = flops.roofline_seconds(work, peak)
    assert line["bound"] == "memory"
    assert math.isclose(line["seconds"], work["bytes"] / 819e9)
    assert 0.007 < line["seconds"] < 0.009


def test_scopes_are_read_one_level_inside_the_known_ones():
    path = ("jit(train_step)/transpose(jvp(jit(loss)))/while/body/"
            "checkpoint/attn_kernel/gdn_rule/while/body/dot_general:")
    assert program_trace.scope_of(path) == "attn_kernel"
    assert hybrid_work.scope_of(path) == "gdn_rule"
    assert hybrid_work.scope_of(
        "jit(train_step)/jvp()/mlp/moe_shared/dot_general:") == "moe_shared"
    assert hybrid_work.scope_of("jit(train_step)/jvp()/mlp/dot:") is None
    assert hybrid_work.scope_of("") is None


def _rehearse(trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=os.path.join(
                   ROOT, ".bench_runs", "test_cache"))
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "from benchmarks import run\n"
        "sys.exit(run.main(['--workload', 'tiny-hybrid', '--seed', "
        "'3200000021', '--seconds', '3', '--trace', %r], benchmark_file=%r, "
        "rehearsal={'num_tpus': 1}))\n" % (ROOT, str(trace), TINY))
    done = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    lines = [json.loads(x) for x in done.stdout.splitlines()
             if x.startswith("{")]
    return lines[-1], lines[:-1]


@pytest.mark.parametrize("trace", [0, 1])
def test_a_hybrid_cell_runs_through_the_one_loop(trace):
    """`qwen3next-steady` at a toy width (one period, experts 4..7 of 16
    held, rows of 160: two chunks and a ragged third), through the unedited
    harness and kind `train`, on the CPU: the reference and its glue
    from the configuration, the share's no-drop check, the step's metrics in
    the reports, model FLOPs from the pairs routed here."""
    line, progress = _rehearse(trace)
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device", "compared"}
    assert line["correct"] is False and line["device"]["platform"] == "cpu"
    assert line["attempted"] > 0 and line["failed"] == 0
    # nothing is wrong but the device: no loss, reference or share problem
    verdict = next(p for p in progress if p.get("kind") == "verdict")
    assert all("cpu" in p or "device trace" in p
               for p in verdict["problems"]), verdict
    # the timed first step's counts and the evaluation's choices on its
    # batch against the reference's, after the window
    followed = next(p for p in progress if p.get("kind") == "reference")
    checked = followed["routing"]
    assert len(followed["steps"]) in (2, 3) and checked["choice_agreement"] > 0.97
    assert all(abs(v["value"]) <= v["limit"]
               for k, v in line["compared"].items() if k.endswith("_gap"))
    assert checked["counts_differ"] <= 2 * round(
        (1 - checked["choice_agreement"]) * checked["choices"])
    losses = next(p for p in progress if p.get("kind") == "losses")
    first = losses["first"][0]
    given, routed = first["moe_expert_tokens"], first["moe_routed_here"]
    assert len(given) == len(routed) == 4 and len(given[0]) == 4
    assert [sum(g) for g in given] == routed
    assert {"ce_loss", "moe_aux_loss", "moe_load_max_over_mean"} <= set(first)
    work = next(p for p in progress if p.get("kind") == "model_flops")
    cell = cells.resolve("tiny-hybrid", TINY)
    assert hybrid_work.uniform_pairs_per_token(cell.config["model"]) == 0.5
    assert 0.05 < work["pairs_per_token"] < 2.0
    assert work["per_token"] == pytest.approx(sum(hybrid_work.flops_by_part(
        cell.config["model"], cell.traffic["seq_len"],
        work["pairs_per_token"]).values()))
    if trace:
        # the counter's reader answers; the device readers find no device
        # plane on the CPU and are left out of the line
        assert line["metrics"]["moe_held_pairs_per_token"]["value"] == (
            work["pairs_per_token"])
        assert not set(NEW_METRICS[:4]) & set(line["metrics"])
        # OLMoE's counter reads this cell's steps: over all 16 outputs
        assert line["metrics"]["moe_load_max_over_mean"]["value"] == (
            losses["window_medians"]["moe_load_max_over_mean"])
        assert not set(SHARED_METRICS[:2]) & set(line["metrics"])
    else:
        assert set(line["metrics"]) == {"tokens_per_s_per_chip", "setup_s"}


def test_a_dropped_pair_is_a_problem():
    def problems_of(steps, checked, counts_differ_max=None):
        return hybrid_work.held_share_routed(
            model, steps, checked, {"counts_differ_max": counts_differ_max},
            100)

    model = {"n_layers": 2, "n_experts": 8, "moe_experts_held": 2}
    step = {"moe_expert_tokens": [[3, 4], [0, 9]], "moe_routed_here": [7, 9]}
    steps = [step, dict(step)]
    checked = {"choice_agreement": 0.99, "choices": 1000, "counts_differ": 20}
    assert problems_of(steps, checked) == []
    short = dict(step, moe_expert_tokens=[[3, 4], [0, 8]])
    problems = problems_of([step, short], checked)
    assert len(problems) == 1 and "1 of 2" in problems[0]
    # counts that differ by more than the disagreeing choices explain
    assert problems_of(steps, dict(checked, counts_differ=21))
    # and by more than the configuration allows: the real file's limit lies
    # between the sound runs' 556 and the float8 reference's 4,209 (the timed
    # first step's counts of the 32 held experts, PR 43)
    assert problems_of(steps, checked, 20) == []
    over = problems_of(steps, checked, 19)
    assert len(over) == 1 and "over the configuration's 19" in over[0]
    assert 3 * 556 > _config()["reference"]["counts_differ_max"] > 556 * 2
    assert _config()["reference"]["counts_differ_max"] * 2 < 4209
    # a program that reports the counts flat (all experts held) or not at all
    assert problems_of([{"moe_expert_tokens": [3, 4]}], checked)
    assert problems_of([{"moe_expert_tokens": [3, 4],
                         "moe_routed_here": [3, 4]}], checked)
    assert problems_of([], checked)


def test_a_program_without_the_scopes_reads_as_nothing():
    """The parent of PR 32, or any cell without linear layers or a held
    share: no `gdn_*` scope, no `moe_routed_here`. Every new reader gives
    None, none raises."""
    recorded = os.path.join(FIXTURES, "v5e_olmoe_tiny_pr27.xplane.pb")
    with open(recorded, "rb") as f:
        planes = program_trace.read_xspace(f.read())
    # OLMoE's trace has the moe_* scopes and no gdn_* one
    got = hybrid_work.analyse(planes, "train_step")
    assert got and not any(got["device_s_per_step"][s]
                           for s in hybrid_work.GDN_SCOPES)
    assert got["expert_matmul_s_per_step"] > 0
    dense = os.path.join(FIXTURES, "v5e_gpt2_tiny_pr24.xplane.pb")
    with open(dense, "rb") as f:
        assert hybrid_work.analyse(program_trace.read_xspace(f.read()),
                                   "train_step") is None
    assert hybrid_work.analyse([], "train_step") is None

    cell = cells.resolve("tiny-hybrid", TINY)
    peak = cells.load_json(os.path.join(ROOT, "benchmarks/peaks.json"))[
        "TPU v5 lite"]
    run = {"cell": {"name": "no-scopes", "chips": 1, "config": cell.config,
                    "traffic": cell.traffic},
           "peaks": peak, "trace": {"step_module": "train_step"},
           "window": {"step_records": [{"loss": 1.0}],
                      "first_window_record": 0, "tokens_per_step": 320}}
    for cached in (None, dict(got, step_device_s=1.0)):
        hybrid_work._cache["no-scopes"] = cached
        try:
            for m in NEW_METRICS:
                value = cells.layer_reader(cell, m)(run)
                if cached and m == "moe_held_share":
                    assert value > 0    # OLMoE's scopes are the same names
                else:
                    assert value is None, m
        finally:
            del hybrid_work._cache["no-scopes"]
    assert hybrid_work.of_run({"cell": {"name": "x"}, "trace": None}) is None
    # kind `train`'s window carries no step records at all
    run["window"] = {}
    assert cells.layer_reader(cell, "moe_held_pairs_per_token")(run) is None
    # and one that has them reads the window's median, warm-up left out
    run["window"] = {"tokens_per_step": 100, "first_window_record": 1,
                     "step_records": [{"moe_routed_here": [900, 900]},
                                      {"moe_routed_here": [50, 70]},
                                      {"moe_routed_here": [60, 100]},
                                      {"moe_routed_here": [10, 30]}]}
    assert cells.layer_reader(cell, "moe_held_pairs_per_token")(run) == 0.6
