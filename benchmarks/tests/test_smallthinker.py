"""The served configuration of an early-routed, ReLU-gated model of full and
window layers and what it stands on, on the CPU at a toy width:

- a cell made of this PR's files alone (`smallthinker-score-16k-over`: the
  configuration, its served group, the traffic mix, the reference, the glue,
  the two readers and their module) resolves through `cells.py`, and its
  files state what the contract asks of them;
- `swa_work.py`, written for another pattern, counts this one's work (no
  leading layer, no shared expert, the full layer first, a 4,096-key window)
  as a brute loop over a document's positions does;
- the reference scores a document the same whether or not zeros follow it;
- a CPU rehearsal of the fixture cell (`BENCHMARK.smallthinker_tiny.json`)
  prints a well-formed last line with every entry of the cell, the path
  itself sound;
- the planted faults (`smallthinker_faults.py`) that a toy width shows
  through bfloat16 on both numbers each come out not correct (the fixture's
  serve file has all six readings; all six in float32:
  `tests/test_smallthinker.py`; all six at the cell's size on the chip:
  `configs/smallthinker_21b_a3b.serve.json`);
- the two new readers on a recorded trace that has the scopes, on one that
  has none and on no trace at all: a number, None, None, never a raise.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import (arrivals, cells, moe_scopes,     # noqa: E402
                        program_trace, swa_work)
from benchmarks.loops import serve as loop               # noqa: E402

FIXTURES = os.path.join(ROOT, "benchmarks", "tests", "fixtures")
TINY = os.path.join(FIXTURES, "BENCHMARK.smallthinker_tiny.json")
CELL = "smallthinker-score-16k-over"
SHARED_READERS = ("serve_swa_fwd_ms", "serve_swa_roofline",
                  "serve_attn_full_roofline", "serve_moe_experts_share",
                  "serve_moe_gmm_roofline")
NEW_READERS = ("serve_moe_router_ms", "serve_moe_plumbing_share")


def test_the_cell_of_new_files_resolves_and_states_its_cut():
    cell = cells.resolve(CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        "smallthinker_21b_a3b", "serve-score-16k-steady-over-v19k", 1)
    assert cells.loop_module(cell) is loop
    served = loop.served_group(cell.root, cell.paths, cell.config_name)
    assert cells.module(cell.root, cell.paths,
                        served["work"]["module"]) is swa_work
    for key in ("module", "glue"):
        assert cells.module(cell.root, cell.paths, served["reference"][key])
    assert [m["name"] for m in cell.end_to_end] == [
        "setup_s", "serve_tokens_per_s_per_chip"]
    names = {m["name"] for m in cell.per_layer}
    assert {n + ".rate" for n in SHARED_READERS + NEW_READERS} <= names
    assert {"serve_mfu.rate", "serve_latency_p99_ms",
            "serve_programs_s"} <= names
    assert not any(n.startswith(("serve_flash", "serve_dsa")) for n in names)
    for m in cell.per_layer:
        assert callable(cells.layer_reader(cell, m["name"].split(".")[0]))
        assert m["moves"] in ("setup_s", "serve_tokens_per_s_per_chip")
    # the two new entries list this cell and no other
    bench = cells.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    for m in bench["per_layer"]:
        if m["name"].split(".")[0] in NEW_READERS:
            assert m["workloads"] == [CELL]
    # the file: every published number under its key, the two cuts named
    config = cell.config
    assert config["reduced"] == ["num_hidden_layers", "vocab_size"]
    for key, value in config["published"].items():
        if key in config["reduced"]:
            assert config[key] != value and key in config["changed"]
        else:
            assert config[key] == value, key
    model = config["model"]
    assert (model["d_model"], model["n_heads"], model["n_kv_heads"],
            model["d_head"], model["d_ff"], model["moe_top_k"],
            model["n_experts"], model["attn_window"], model["norm_eps"],
            model["rope_theta"], model["max_seq_len"]) == (
        config["hidden_size"], config["num_attention_heads"],
        config["num_key_value_heads"], config["head_dim"],
        config["moe_ffn_hidden_size"],
        config["moe_num_active_primary_experts"],
        config["moe_num_primary_experts"], config["sliding_window_size"],
        config["rms_norm_eps"], config["rope_theta"],
        config["max_position_embeddings"])
    assert (model["moe_router_input"], model["moe_activation"],
            model["moe_score"], model["moe_norm_topk_prob"],
            model["tie_embeddings"]) == ("mixer", "relu", "softmax", True,
                                         config["tie_word_embeddings"])
    assert "moe_experts_held" not in model      # every expert is here
    assert not set(model) & {"lead_layers", "moe_shared_ff", "attn_gate",
                             "qk_norm", "post_norm", "embed_scale"}
    assert (model["n_layers"], model["vocab_size"]) == (
        config["num_hidden_layers"], config["vocab_size"]) == (8, 18992)
    # the layers held are the published model's, by their published index:
    # whole periods, the full layer first, RoPE on the window layers alone
    held = config["layers_held"]
    assert held == list(range(8))
    kinds = ["window" if config["sliding_window_layout"][l] else "full"
             for l in held]
    assert kinds == model["layer_pattern"] * 2
    assert [config["rope_layout"][l] == 1 for l in held] == [
        k in model["rope_layers"] for k in kinds]
    assert model["vocab_size"] * 8 >= config["published"]["vocab_size"]
    assert {"router_input", "secondary_experts", "expert_activation",
            "routing_weights", "window_convention", "positions",
            "initialisation"} <= set(config["assumed"])
    # the traffic: ISSUE 57's table
    traffic = cell.traffic
    assert traffic["batching"]["max_batch_size"] == 2
    assert traffic["batching"]["rows"] == [1, 2]
    assert traffic["batching"]["lengths"] == [4096, 8192, 16384]
    assert traffic["tokens"]["support"] == model["vocab_size"] - 1
    assert "burst" not in traffic["arrivals"]
    assert (traffic["arrivals"]["shuffle_block"],
            traffic["arrivals"]["pool_seed"]) == (16, 44)
    assert 12 <= traffic["check"]["sample"] <= 16
    assert (traffic["deadline_ms"], traffic["warmup"]["requests"]) == (
        20000, 96)
    # the same documents and buckets as the other two 16k cells, on purpose
    for other in ("keye2-score-16k-over", "trinity-score-16k-over"):
        other = cells.resolve(other).traffic
        assert other["documents"] == traffic["documents"]
        assert other["batching"] == traffic["batching"]


def _brute(model, n):
    """A document of n tokens, position by position."""
    d, heads, width = model["d_model"], model["n_heads"], model["d_head"]
    flops = 0.0
    pairs = {"window": 0, "full": 0}
    for t in range(n):
        attention = 2 * d * (2 * heads + 2 * model["n_kv_heads"]) * width
        flops += 8 * attention                      # eight layers' projections
        flops += 8 * 2 * (d * model["n_experts"]    # router, 6 routed
                          + 3 * d * model["d_ff"] * model["moe_top_k"])
        flops += 2 * d * model["vocab_size"]        # the sliced head
        pairs["window"] += min(t + 1, model["attn_window"])
        pairs["full"] += t + 1
    per_pair = 4 * heads * width
    return (flops + per_pair * (6 * pairs["window"] + 2 * pairs["full"]),
            pairs)


def test_the_work_counts_against_a_brute_loop():
    """`swa_work.py` as it stands is this configuration's work module: no
    leading layer, no shared expert, no output gate, the full layer first in
    a period (the counts know no order), a window of 4,096."""
    model = cells.resolve(CELL).config["model"]
    for n in (100, 4096, 6000):
        flops, pairs = _brute(model, n)
        assert swa_work.forward_flops(model, [n]) == pytest.approx(flops)
        assert swa_work.window_work(model, [n])["flops"] == (
            6 * 4.0 * 28 * 128 * pairs["window"])
        assert swa_work.full_work(model, [n])["flops"] == (
            2 * 4.0 * 28 * 128 * pairs["full"])
        both = swa_work.flash_forward_work(model, [n])
        assert both["bytes"] == 8 * 2.0 * n * 2 * 32 * 128
    # in the 4,096 bucket the band is the whole triangle; at 16,384 it is
    # under a half of it
    assert swa_work.band_pairs(4096, 4096) == swa_work.causal_pairs(4096)
    assert swa_work.band_pairs(16384, 4096) == (4096 * 4097 / 2
                                                + 12288 * 4096)
    assert 0.43 < (swa_work.band_pairs(16384, 4096)
                   / swa_work.causal_pairs(16384)) < 0.44
    experts = swa_work.expert_matmul_work(model, [1000, 24], calls=2)
    assert experts["flops"] == 6.0 * 8 * 6 * 1024 * 2560 * 768
    assert experts["bytes"] == 2 * (8 * 6 * 1024 * (2 * 2560 + 3 * 768)
                                    + 2 * 3.0 * 8 * 64 * 2560 * 768)
    # the issue's arithmetic: about 1.39 GFLOP a token at the mean document
    mean = 8500
    per_token = swa_work.forward_flops(model, [mean]) / mean
    assert 1.3e9 < per_token < 1.5e9
    # the kernels' names are told apart as the program names them
    import re
    assert re.match(swa_work.WINDOW_KERNEL, "flash_fwd_window")
    assert re.match(swa_work.FULL_KERNEL, "flash_fwd") and not re.match(
        swa_work.FULL_KERNEL, "flash_fwd_window")


def test_the_new_readers_on_a_recorded_trace_and_on_none():
    """OLMoE's recorded trace holds the three scopes; GPT-2's holds none;
    a run without a trace has nothing to read."""
    with open(os.path.join(FIXTURES, "v5e_olmoe_tiny_pr27.xplane.pb"),
              "rb") as f:
        planes = program_trace.read_xspace(f.read(), ("tf_op",))
    got = moe_scopes.analyse(planes, "train_step")
    assert set(got) == set(moe_scopes.SCOPES) and all(
        v > 0 for v in got.values())
    with open(os.path.join(FIXTURES, "v5e_gpt2_tiny_pr24.xplane.pb"),
              "rb") as f:
        assert moe_scopes.analyse(program_trace.read_xspace(
            f.read(), ("tf_op",)), "train_step") is None
    assert moe_scopes.analyse([], "train_step") is None

    cell = cells.resolve(CELL)
    run = {"cell": {"name": "recorded", "config": cell.config},
           "trace": {"step_module": "train_step", "n_steps": 3,
                     "busy_s": 0.01}, "traced": None, "peaks": None}
    moe_scopes._cache["recorded"] = got
    try:
        router = cells.layer_reader(cell, "serve_moe_router_ms")(run)
        share = cells.layer_reader(cell, "serve_moe_plumbing_share")(run)
    finally:
        del moe_scopes._cache["recorded"]
    assert router == pytest.approx(1e3 * got["moe_router"] / 3)
    assert share == pytest.approx(100 * sum(got.values()) / 0.01)
    assert 0 < share < 100
    # nothing to read: no trace; a trace whose file is gone; no scopes
    empty = {"cell": {"name": "none", "config": cell.config}, "trace": None,
             "traced": None, "peaks": None}
    gone = dict(run, cell={"name": "no-such-run", "config": cell.config})
    for name in NEW_READERS + SHARED_READERS:
        assert cells.layer_reader(cell, name)(empty) is None
    for name in NEW_READERS:
        assert cells.layer_reader(cell, name)(gone) is None
    assert moe_scopes.of_run(empty) is None


def test_the_schedule_is_the_other_16k_cells():
    traffic = cells.resolve(CELL).traffic
    a = arrivals.schedule(traffic, 45.0, 7)
    b = arrivals.schedule(traffic, 45.0, 2 ** 31 + 12345)
    assert sorted(a["lengths"][:16]) == sorted(b["lengths"][:16])
    assert 6500 < a["lengths"].mean() < 7600
    docs = arrivals.documents(traffic, a["lengths"][:4], 2 ** 31 + 12345)
    assert max(int(d.max()) for d in docs) < 18992


# ---------------------------------------------- the reference, the control

@pytest.fixture(scope="module")
def scorer():
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    jax.config.update("jax_platforms", "cpu")
    cell = cells.resolve("tiny-early-over", TINY)
    served = loop.served_group(cell.root, cell.paths, cell.config_name)
    cfg = {"config": cell.config, "served": served, "traffic": cell.traffic,
           "chips": 1, "platform": "cpu", "seed": 11, "root": cell.root,
           "paths": cell.paths, "patch": None, "run_called_wall": 0.0}
    plan = arrivals.schedule(cell.traffic, 8.0, 11)
    docs = arrivals.documents(cell.traffic, plan["lengths"][:12], 11)
    return loop.Scorer(cfg), cfg, served, docs


def test_the_reference_scores_a_document_alone_whatever_follows_it(scorer):
    deployment, cfg, _, docs = scorer
    import jax
    import jax.numpy as jnp
    from benchmarks.reference import smallthinker, smallthinker_glue
    top, layers = smallthinker_glue.reference_weights(
        deployment.init_params(jax.random.PRNGKey(cfg["seed"])), None,
        jax.devices())
    layers = list(layers)
    assert len(layers) == 8 and all(
        "block_sparse_moe.primary_router" in w for w in layers)
    doc = max(docs, key=len)
    assert len(doc) > 64            # longer than the fixture's window
    alone = np.asarray(smallthinker.token_logprobs(
        jnp.asarray(doc[None]), top, layers, cfg["config"]))[0]
    padded = loop._reference_scores(cfg, deployment.init_params, [doc])[0]
    assert alone.shape == padded.shape == (len(doc) - 1,)
    assert np.abs(alone - padded).max() < 2e-5
    # the reference imports nothing of the program
    with open(smallthinker.__file__) as f:
        assert "ray_tpu" not in f.read().split('"""', 2)[2]


# --------------------------------------------------- rehearsals and faults

def _rehearse(trace, patch=None, seconds="4"):
    rehearsal = {"num_tpus": 1}
    if patch:
        rehearsal["patch"] = "benchmarks.tests.smallthinker_faults:" + patch
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=os.path.join(
                   ROOT, ".bench_runs", "test_cache"))
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "from benchmarks import run\n"
        "sys.exit(run.main(['--workload', 'tiny-early-over', '--seed', '5', "
        "'--seconds', %r, '--trace', %r], benchmark_file=%r, "
        "rehearsal=%r))\n" % (ROOT, seconds, str(trace), TINY, rehearsal))
    done = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1]), done.stderr


@pytest.mark.parametrize("trace,metric", [
    (0, "serve_tokens_per_s_per_chip"), (1, "serve_pad_share.rate")])
def test_the_cell_rehearses_on_the_cpu_and_never_reads_correct(trace,
                                                               metric):
    line, stderr = _rehearse(trace)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"] and list(line)[-1] == "compared"
    assert line["correct"] is False and "ran on 'cpu'" in stderr
    assert line["attempted"] > 20 and line["failed"] == 0
    assert line["metrics"][metric]["value"] > 0
    compared = line["compared"]
    # the path itself is sound: only the machine is wrong
    for name in ("score_gap_max", "score_gap_rms",
                 "answers_of_wrong_length"):
        assert compared[name]["value"] <= compared[name]["limit"], compared
    if trace:       # no device trace on the CPU: those readers read nothing
        assert not any(name.split(".")[0] in SHARED_READERS + NEW_READERS
                       for name in line["metrics"])
        for name in ("serve_queue_ms.rate", "jax_trace_s", "serve_programs_s"):
            assert name in line["metrics"], sorted(line["metrics"])


@pytest.mark.parametrize("patch", [
    "window_layers_full", "window_halved", "weights_not_rescaled",
    "router_reads_after_attention"])
def test_a_planted_fault_comes_out_not_correct(patch):
    line, stderr = _rehearse(0, patch)
    assert line["correct"] is False
    for number in ("score_gap_max", "score_gap_rms"):
        seen = line["compared"][number]
        assert seen["value"] > seen["limit"], (patch, line["compared"])
    assert "not correct: " in stderr
