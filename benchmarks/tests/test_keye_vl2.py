"""The served sparse-attention configuration and what it stands on, on the
CPU at a toy width:

- a cell made of this PR's files alone (`keye2-score-16k-over`: the
  configuration, its served group, the traffic mix, the work module, the
  reference, the glue, the five readers) resolves through `cells.py`, and
  its files state what the contract asks of them;
- the work module's counts by hand;
- the reference scores a document the same whether or not zeros follow it;
- a CPU rehearsal of the fixture cell prints a well-formed last line, the
  path itself sound;
- the planted faults (`keye_faults.py`): the choice of keys ignored, `topk`
  halved, the ReLU left out of the index score, a query that attends the
  key after itself — each comes out not correct, on both numbers.
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

from benchmarks import arrivals, cells, dsa_work     # noqa: E402
from benchmarks.loops import serve as loop            # noqa: E402

FIXTURES = os.path.join(ROOT, "benchmarks", "tests", "fixtures")
TINY = os.path.join(FIXTURES, "BENCHMARK.keye_tiny.json")
CELL = "keye2-score-16k-over"
NEW_READERS = ("serve_dsa_index_ms", "serve_dsa_index_roofline",
               "serve_dsa_attend_ms", "serve_dsa_attend_roofline",
               "serve_dsa_share")


def test_the_cell_of_new_files_resolves_and_states_its_cut():
    cell = cells.resolve(CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        "keye_vl_2_30b_a3b", "serve-score-16k-steady-over", 1)
    assert cells.loop_module(cell) is loop
    served = loop.served_group(cell.root, cell.paths, cell.config_name)
    assert cells.module(cell.root, cell.paths,
                        served["work"]["module"]) is dsa_work
    for key in ("module", "glue"):
        assert cells.module(cell.root, cell.paths, served["reference"][key])
    assert [m["name"] for m in cell.end_to_end] == [
        "setup_s", "serve_tokens_per_s_per_chip"]
    names = {m["name"] for m in cell.per_layer}
    assert {n + ".rate" for n in NEW_READERS} <= names
    assert not any("flash" in n for n in names)
    for m in cell.per_layer:
        assert callable(cells.layer_reader(cell, m["name"].split(".")[0]))
        assert m["moves"] in ("setup_s", "serve_tokens_per_s_per_chip")
    # the file: every published number under its key, the three cuts named
    config = cell.config
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    for key, value in config["published"].items():
        if key in config["reduced"]:
            assert config[key] != value and key in config["changed"]
        else:
            assert config[key] == value, key
    model, sa = config["model"], config["sa_config"]
    assert (model["d_model"], model["n_heads"], model["n_kv_heads"],
            model["d_head"], model["d_ff"], model["moe_top_k"]) == (
        config["hidden_size"], config["num_attention_heads"],
        config["num_key_value_heads"], config["head_dim"],
        config["moe_intermediate_size"], config["num_experts_per_tok"])
    assert (model["sparse_topk"], model["index_heads"],
            model["index_head_dim"]) == (
        sa["topk"], sa["indexer_num_heads"], sa["indexer_head_dim"])
    assert (model["n_layers"], model["moe_experts_held"], model["n_experts"],
            model["vocab_size"]) == (
        config["num_hidden_layers"], config["num_experts"],
        config["router_outputs"], config["vocab_size"])
    assert model["n_layers"] >= 4 and model["moe_experts_held"] >= 8
    assert model["vocab_size"] * 8 >= config["published"]["vocab_size"]
    # the traffic: ISSUE 49's table
    traffic = cell.traffic
    assert traffic["batching"]["max_batch_size"] == 2
    assert traffic["batching"]["rows"] == [1, 2]
    assert traffic["batching"]["lengths"] == [4096, 8192, 16384]
    assert traffic["documents"]["length"] == {
        "distribution": "lognormal", "median": 6000, "sigma": 0.7,
        "min": 2048, "max": 16384}
    assert traffic["tokens"]["support"] == model["vocab_size"] - 1
    assert "burst" not in traffic["arrivals"]
    assert traffic["arrivals"]["shuffle_block"] == 64
    assert (traffic["check"]["sample"], traffic["deadline_ms"]) == (16, 20000)
    # and no field of the model group sizes the held share for the cell
    assert not [k for k in model if k.startswith("moe_held")]
    assert traffic["documents"]["length"]["max"] == model["max_seq_len"]


def test_the_schedule_of_long_documents_carries_the_same_work_every_seed():
    traffic = cells.resolve(CELL).traffic
    a = arrivals.schedule(traffic, 45.0, 7)
    b = arrivals.schedule(traffic, 45.0, 2 ** 31 + 12345)
    assert sorted(a["lengths"][:256]) == sorted(b["lengths"][:256])
    assert 6500 < a["lengths"].mean() < 7600
    assert 0.1 < (a["lengths"] > 10000).mean() < 0.3


# one seed's traced bucket programs on the v5e, milliseconds (my chip run,
# PR 49, seed 2149000511; 2 x 16,384 by the untraced runs' device waits)
BUCKET_MS = {(1, 4096): 49.4, (2, 4096): 94.8, (1, 8192): 120.5,
             (2, 8192): 236.3, (1, 16384): 345.5, (2, 16384): 690.0}


@pytest.mark.parametrize("seed, chip_read", [
    (4900000603, 38092.222), (4900000605, 39521.667),
    (4900000607, 39507.622), (4900000608, 38594.756)])
def test_a_seeds_rate_is_what_its_schedule_and_the_buckets_times_give(
        seed, chip_read):
    """The cell's rate under a seed is the traffic file's doing, not the
    weights': a queue simulated with one seed's bucket times answers the
    same documents by the close as the chip did under another's (seven of
    ten seeds to the token; PERF.md, PR 49). Pins the schedule, the
    grouping and the simulation the spread was read from."""
    from benchmarks.tests import serve_queue_sim
    rate = serve_queue_sim.window_rate(cells.resolve(CELL).traffic,
                                       BUCKET_MS, seed)
    assert abs(rate - chip_read) < 0.01


def test_the_spread_of_a_set_leaves_out_its_farthest_run():
    from benchmarks.tests import serve_queue_sim
    rates = [38780.8, 39235.4, 38092.2, 38560.8, 39521.7, 39190.9]
    assert round(100 * serve_queue_sim.spread(rates, False), 2) == 2.21
    assert round(100 * serve_queue_sim.spread(rates), 2) == 1.81


def test_the_work_counts_by_hand():
    model = cells.resolve(CELL).config["model"]
    assert dsa_work.chosen_pairs(100, 2048) == 5050
    assert dsa_work.chosen_pairs(4096, 2048) == (2048 * 2049 / 2
                                                 + 2048 * 2048)
    n = 16384
    chosen = dsa_work.chosen_pairs(n, 2048)
    assert dsa_work.attend_work(model, [n])["flops"] == (
        8 * 4.0 * 32 * 128 * chosen)
    assert dsa_work.index_work(model, [n])["flops"] == (
        8 * 2.0 * 16 * 64 * n * (n + 1) / 2)
    per_token = 2.0 * (8 * (
        2 * 2048 * 32 * 128 + 2 * 2048 * 4 * 128            # q, out; k, v
        + 2048 * (16 * 64 + 64 + 16)                        # the indexer's
        + 2048 * 128 + 1.0 * 3 * 2048 * 768)                # router, a pair
        + 2048 * 18992)
    assert dsa_work.forward_flops(model, [n, 100]) == pytest.approx(
        per_token * (n + 100)
        + dsa_work.index_work(model, [n, 100])["flops"]
        + dsa_work.attend_work(model, [n, 100])["flops"])
    # a reader that finds nothing to read returns nothing and does not raise
    cell = cells.resolve(CELL)
    empty = {"cell": {"name": "none", "config": cell.config}, "trace": None,
             "traced": None, "peaks": None}
    for name in NEW_READERS:
        assert cells.layer_reader(cell, name)(empty) is None


# ---------------------------------------------- the reference, the control

@pytest.fixture(scope="module")
def scorer():
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    jax.config.update("jax_platforms", "cpu")
    cell = cells.resolve("tiny-sparse-over", TINY)
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
    from benchmarks.reference import keye_vl2, keye_vl2_glue
    top, layers = keye_vl2_glue.reference_weights(
        deployment.init_params(jax.random.PRNGKey(cfg["seed"])), None,
        jax.devices())
    layers = list(layers)
    doc = max(docs, key=len)
    assert len(doc) > 32            # longer than the fixture's topk
    alone = np.asarray(keye_vl2.token_logprobs(
        jnp.asarray(doc[None]), top, layers, cfg["config"]))[0]
    padded = loop._reference_scores(cfg, deployment.init_params, [doc])[0]
    assert alone.shape == padded.shape == (len(doc) - 1,)
    assert np.abs(alone - padded).max() < 2e-5


# --------------------------------------------------- rehearsals and faults

def _rehearse(trace, patch=None, seconds="4"):
    rehearsal = {"num_tpus": 1}
    if patch:
        rehearsal["patch"] = "benchmarks.tests.keye_faults:" + patch
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=os.path.join(
                   ROOT, ".bench_runs", "test_cache"))
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "from benchmarks import run\n"
        "sys.exit(run.main(['--workload', 'tiny-sparse-over', '--seed', '5', "
        "'--seconds', %r, '--trace', %r], benchmark_file=%r, "
        "rehearsal=%r))\n" % (ROOT, seconds, str(trace), TINY, rehearsal))
    done = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1]), done.stderr


@pytest.mark.parametrize("trace,metric", [
    (0, "serve_tokens_per_s_per_chip"), (1, "serve_pad_share.rate")])
def test_the_sparse_cell_rehearses_on_the_cpu_and_never_reads_correct(
        trace, metric):
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
    if trace:       # no device trace on the CPU: the new readers read nothing
        assert not any(name.startswith("serve_dsa_")
                       for name in line["metrics"])
        for name in ("serve_queue_ms.rate", "jax_trace_s", "serve_programs_s"):
            assert name in line["metrics"], sorted(line["metrics"])


@pytest.mark.parametrize("patch", ["selection_ignored", "topk_halved",
                                   "relu_left_out", "key_after_the_query"])
def test_a_planted_fault_in_the_choice_of_keys_comes_out_not_correct(patch):
    line, stderr = _rehearse(0, patch)
    assert line["correct"] is False
    for number in ("score_gap_max", "score_gap_rms"):
        seen = line["compared"][number]
        assert seen["value"] > seen["limit"], (patch, line["compared"])
    assert "not correct: " in stderr
