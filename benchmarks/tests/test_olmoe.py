"""The checks of what PR 27 added to the benchmark: the `olmoe_1b_7b`
configuration, the expert matmuls' work, its routing check and the four
`moe_*` readers. CPU only, not part of tier-1:

    python -m pytest benchmarks/tests/test_olmoe.py -q

(The system against `reference/olmoe.py` is tier-1's
`tests/test_olmoe_reference.py`.)
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

from benchmarks import cells, flops, moe_work, program_trace  # noqa: E402

FIXTURES = os.path.join(HERE, "fixtures")
TINY = os.path.join(FIXTURES, "BENCHMARK.olmoe_tiny.json")
RECORDED = os.path.join(FIXTURES, "v5e_olmoe_tiny_pr27.xplane.pb")
MOE_METRICS = ("moe_experts_share", "moe_dispatch_share", "moe_gmm_roofline",
               "moe_load_max_over_mean")


def _config():
    return cells.load_json(os.path.join(
        ROOT, "benchmarks/configs/olmoe_1b_7b.json"))


def test_the_configuration_keeps_every_published_width():
    config = _config()
    published = config["published"]
    differs = {k for k, v in published.items() if config[k] != v}
    assert differs == set(config["reduced"]) == {"num_hidden_layers"}
    model = config["model"]
    assert (model["d_model"], model["d_ff"], model["n_heads"],
            model["n_experts"], model["moe_top_k"], model["vocab_size"],
            model["max_seq_len"], model["n_layers"]) == (
        published["hidden_size"], published["intermediate_size"],
        published["num_attention_heads"], published["num_experts"],
        published["num_experts_per_tok"], published["vocab_size"],
        published["max_position_embeddings"], config["num_hidden_layers"])
    assert model["norm_eps"] == published["rms_norm_eps"]
    assert model["moe_norm_topk_prob"] is published["norm_topk_prob"]
    assert model["tie_embeddings"] is published["tie_word_embeddings"]
    cell = cells.resolve("olmoe-steady")
    assert cell.traffic["seq_len"] == model["max_seq_len"]
    assert cell.traffic["kind"] == "train"
    assert config["work"] == {"module": "moe_work.py",
                              "routing_check": "every_pair_routed"}


def test_model_and_expert_flops_by_hand():
    """One OLMoE layer at 4k rows: 1,071.9 MFLOP a token — head 618.1,
    routed experts 301.9, attention projections 100.7, attention 50.3, the
    router 0.8 — and the expert matmuls' 18 x tokens x k x d x f."""
    model = _config()["model"]
    d, f, k, e, v = 2048, 1024, 8, 64, 50304
    by_hand = (6 * (4 * d * d + k * 3 * d * f + d * e)
               + 6 * d * v + 6 * d * 4096)
    assert flops.model_flops_per_token(model, 4096) == by_hand
    assert round(by_hand / 1e6, 1) == 1071.9
    tokens = 5 * 4096
    # every expert is held: tokens x top-k x layers (token, expert) pairs
    work = moe_work.expert_matmul_work(model, tokens * k * 1)
    assert moe_work.model_flops_per_token is flops.model_flops_per_token
    assert work["flops"] == 18 * tokens * k * d * f
    assert work["flops"] == 6 * tokens * (k * 3 * d * f)
    rows_d, rows_f, weights = tokens * k * d, tokens * k * f, 3 * e * d * f
    # every matrix read forward and backward, its gradient written: bf16
    assert work["bytes"] == 2 * (5 * rows_d + 7 * rows_f + 3 * weights)
    peak = cells.load_json(os.path.join(ROOT, "benchmarks/peaks.json"))[
        "TPU v5 lite"]
    line = flops.roofline_seconds(work, peak)
    assert line["bound"] == "compute"
    assert math.isclose(line["seconds"], work["flops"] / 197e12)


def test_a_dropped_token_is_a_problem():
    model = {"n_layers": 2, "moe_top_k": 2, "n_experts": 4}
    step = {"moe_expert_tokens": [10, 10, 10, 10]}      # 10 x 2 x 2
    assert moe_work.every_pair_routed(model, [step, step], {}, {}, 10) == []
    short = moe_work.every_pair_routed(
        model, [step, {"moe_expert_tokens": [10, 10, 10, 9]}], {}, {}, 10)
    assert len(short) == 1 and "1 of 2" in short[0] and "39" in short[0]
    assert moe_work.every_pair_routed(model, [{"loss": 1.0}], {}, {}, 10)
    assert moe_work.every_pair_routed(model, [], {}, {}, 10)
    # the pairs a reader takes its work from are what the steps reported
    window = {"first_window_record": 1, "tokens_per_step": 10,
              "step_records": [{"moe_expert_tokens": [99]}, step, step]}
    assert moe_work.pairs_per_step(window) == 40
    assert moe_work.pairs_per_token(window) is None
    window["step_records"] = [{"moe_expert_tokens": [[1, 2], [3, 4]],
                               "moe_routed_here": [3, 7]}]
    window["first_window_record"] = 0
    assert moe_work.pairs_per_step(window) == 10
    assert moe_work.pairs_per_token(window) == 0.5
    assert moe_work.pairs_per_step({"step_records": [{"loss": 1.0}]}) is None
    assert moe_work.pairs_per_step({}) is None


def test_moe_scopes_are_read_one_level_inside_mlp():
    path = ("jit(train_step)/transpose(jvp(jit(loss)))/while/body/"
            "checkpoint/mlp/moe_experts/ragged_dot_general:")
    assert program_trace.scope_of(path) == "mlp"
    assert moe_work.scope_of(path) == "moe_experts"
    assert moe_work.scope_of("jit(train_step)/jvp()/mlp/dot_general:") is None
    assert moe_work.scope_of("") is None


def _rehearse(trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=os.path.join(
                   ROOT, ".bench_runs", "test_cache"))
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "from benchmarks import run\n"
        "sys.exit(run.main(['--workload', 'tiny-olmoe', '--seed', "
        "'2600000021', '--seconds', '3', '--trace', %r], benchmark_file=%r, "
        "rehearsal={'num_tpus': 1}))\n" % (ROOT, str(trace), TINY))
    done = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    lines = [json.loads(x) for x in done.stdout.splitlines()
             if x.startswith("{")]
    return lines[-1], lines[:-1]


@pytest.mark.parametrize("trace", [0, 1])
def test_an_olmoe_shaped_cell_runs_through_the_one_loop(trace):
    """`olmoe-steady` at a toy width, through the unedited harness and kind
    `train`, on the CPU: the reference from the configuration, the
    centred first-loss check, the routing check, the step's metrics in the
    reports."""
    line, progress = _rehearse(trace)
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device", "compared"}
    assert line["correct"] is False and line["device"]["platform"] == "cpu"
    assert line["attempted"] > 0 and line["failed"] == 0
    # nothing is wrong but the device: no loss, reference or routing problem
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
    losses = next(p for p in progress if p.get("kind") == "losses")
    first = losses["first"][0]
    assert sum(first["moe_expert_tokens"]) == 2 * 128 * 2 * 2
    assert {"ce_loss", "moe_aux_loss", "moe_router_z",
            "moe_load_max_over_mean"} <= set(first)
    if trace:
        # the counter's reader answers; the device readers find no device
        # plane on the CPU and are left out of the line
        assert line["metrics"]["moe_load_max_over_mean"]["value"] >= 1.0
        assert not set(MOE_METRICS[:3]) & set(line["metrics"])
    else:
        assert set(line["metrics"]) == {"tokens_per_s_per_chip", "setup_s"}


# ------------------------------------------------ the four readers, recorded

def _recorded():
    with open(RECORDED, "rb") as f:
        planes = program_trace.read_xspace(f.read())
    return planes, cells.load_json(RECORDED.replace(".xplane.pb",
                                                    ".expected.json"))


def _run(name):
    """What a loop hands a reader, for the fixtures' toy cell."""
    cell = cells.resolve("tiny-olmoe", TINY)
    peak = cells.load_json(os.path.join(ROOT, "benchmarks/peaks.json"))[
        "TPU v5 lite"]
    return {"cell": {"name": name, "chips": 1, "config": cell.config,
                     "traffic": cell.traffic},
            "peaks": peak, "trace": {"step_module": "train_step"},
            "window": {"first_window_record": 2, "step_records": [
                {"moe_load_max_over_mean": 9.0}, {"loss": 1.0},
                {"moe_load_max_over_mean": 1.5,
                 "moe_expert_tokens": [512, 256, 256]},
                {"moe_load_max_over_mean": 2.5,
                 "moe_expert_tokens": [500, 500, 24]},
                {"moe_load_max_over_mean": 3.5,
                 "moe_expert_tokens": [1024]}]}}


def test_the_moe_readers_on_a_recorded_v5e_trace():
    """A trace recorded on a v5e chip (PR 27, chip call 1): the fixtures'
    `tiny-olmoe` cell, three steps and a sentinel. Holds the readers to the
    real format: the four scopes inside `mlp` in the `tf_op` paths, and the
    grouped matmuls as `ragged-dot*` custom calls that carry no scope."""
    planes, expected = _recorded()
    got = moe_work.analyse(planes, "train_step")
    whole = program_trace.analyse(planes, "train_step")
    assert got["n_steps"] == whole["n_steps"] == expected["n_steps"]
    table = got["device_s_per_step"]
    assert set(table) == set(moe_work.SCOPES)
    for scope in moe_work.SCOPES:
        assert table[scope]["forward"] > 0 and table[scope]["backward"] > 0
        # the fixture's model recomputes its blocks ("dots")
        assert table[scope]["recompute"] > 0
    # 2 layers x 3 matmuls x (forward, recomputed, two backward products)
    # share 12 instruction names here, and a metadata call heads each group
    ops = got["expert_matmul_ops_s_per_step"]
    assert sorted(ops) == sorted(expected["grouped_matmul_ops"])
    assert math.isclose(got["expert_matmul_s_per_step"],
                        expected["expert_matmul_s_per_step"], rel_tol=1e-9)
    assert math.isclose(got["expert_matmul_s_per_step"], sum(ops.values()),
                        rel_tol=1e-9)
    # where `program_trace` files the same operations: the four scopes
    # inside `mlp` by their paths, the grouped matmuls there by their names
    in_scopes = sum(sum(row.values()) for row in table.values())
    assert in_scopes + got["expert_matmul_s_per_step"] <= sum(
        whole["device_s_per_step"]["mlp"].values()) * (1 + 1e-9)
    assert not any(k.startswith("ragged-dot")
                   for k in whole["unscoped_top_s_per_step"])

    run = _run("recorded-olmoe")
    moe_work._cache["recorded-olmoe"] = dict(
        got, step_device_s=whole["step_device_s"])
    try:
        cell = cells.resolve("tiny-olmoe", TINY)
        read = {m: cells.layer_reader(cell, m)(run) for m in MOE_METRICS}
    finally:
        del moe_work._cache["recorded-olmoe"]
    step = whole["step_device_s"]
    assert read["moe_experts_share"] == pytest.approx(100 * (
        sum(table["moe_experts"].values())
        + got["expert_matmul_s_per_step"]) / step)
    assert read["moe_dispatch_share"] == pytest.approx(100 * (
        in_scopes - sum(table["moe_experts"].values())) / step)
    # the toy's matmuls are far too small to be near a roofline; the share
    # is model FLOPs at the peak over the time they took, under 100
    # at the pairs the window's steps reported: 2 x 128 tokens x top-2 x 2
    # layers, every expert held
    model = run["cell"]["config"]["model"]
    assert moe_work.pairs_per_step(run["window"]) == 1024 == 2 * 128 * 2 * 2
    work = moe_work.expert_matmul_work(model, 1024)
    assert work["flops"] == 18 * 2 * 256 * 2 * 128 * 64
    assert read["moe_gmm_roofline"] == pytest.approx(
        100 * flops.roofline_seconds(work, run["peaks"])["seconds"]
        / got["expert_matmul_s_per_step"])
    assert 0 < read["moe_gmm_roofline"] < 100
    # the counter: the median over the window's steps, warm-up left out
    assert read["moe_load_max_over_mean"] == 2.5


def test_a_program_without_the_scopes_reads_as_nothing():
    """The parent of PR 27, or any dense cell: no `moe_*` scope in any path.
    Every reader gives None, none raises."""
    recorded = os.path.join(FIXTURES, "v5e_gpt2_tiny_pr24.xplane.pb")
    with open(recorded, "rb") as f:
        planes = program_trace.read_xspace(f.read())
    assert program_trace.analyse(planes, "train_step")["scoped_ops"] > 0
    assert moe_work.analyse(planes, "train_step") is None
    assert moe_work.analyse([], "train_step") is None
    run = _run("dense")
    run["window"] = {"step_records": [{"loss": 1.0}],
                     "first_window_record": 0}
    moe_work._cache["dense"] = None
    try:
        cell = cells.resolve("tiny-olmoe", TINY)
        for m in MOE_METRICS:
            assert cells.layer_reader(cell, m)(run) is None, m
    finally:
        del moe_work._cache["dense"]
    assert moe_work.of_run({"cell": {"name": "x"}, "trace": None}) is None
    # kind `train`'s window carries no step records at all
    run["window"] = {}
    assert cells.layer_reader(cell, "moe_load_max_over_mean")(run) is None
