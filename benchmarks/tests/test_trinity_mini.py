"""The served configuration of window and full layers and what it stands on,
on the CPU at a toy width:

- a cell made of this PR's files alone (`trinity-score-16k-over`: the
  configuration, its served group, the traffic mix, the work module, the
  reference, the glue, the five readers) resolves through `cells.py`, and
  its files state what the contract asks of them;
- the work module's counts against a brute loop over a document's positions;
- the reference scores a document the same whether or not zeros follow it;
- a CPU rehearsal of the fixture cell (`BENCHMARK.trinity_tiny.json`) prints
  a well-formed last line with every entry of the cell, the path itself
  sound;
- the planted faults (`trinity_faults.py`) that a toy width shows through
  bfloat16 on both numbers — window layers run full, the gate left out, a
  branch's post-norm left out — each come out not correct (the other three
  pass one of the toy limits or both under bfloat16's own noise there, the
  fixture's serve file has the readings; all six in float32:
  `tests/test_trinity_mini.py`; all six at the cell's size on the chip:
  `configs/trinity_mini.serve.json`).
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

from benchmarks import arrivals, cells, swa_work     # noqa: E402
from benchmarks.loops import serve as loop            # noqa: E402

FIXTURES = os.path.join(ROOT, "benchmarks", "tests", "fixtures")
TINY = os.path.join(FIXTURES, "BENCHMARK.trinity_tiny.json")
CELL = "trinity-score-16k-over"
NEW_READERS = ("serve_swa_fwd_ms", "serve_swa_roofline",
               "serve_attn_full_roofline", "serve_moe_experts_share",
               "serve_moe_gmm_roofline")


def test_the_cell_of_new_files_resolves_and_states_its_cut():
    cell = cells.resolve(CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        "trinity_mini", "serve-score-16k-steady-over-swa", 1)
    assert cells.loop_module(cell) is loop
    served = loop.served_group(cell.root, cell.paths, cell.config_name)
    assert cells.module(cell.root, cell.paths,
                        served["work"]["module"]) is swa_work
    for key in ("module", "glue"):
        assert cells.module(cell.root, cell.paths, served["reference"][key])
    assert [m["name"] for m in cell.end_to_end] == [
        "setup_s", "serve_tokens_per_s_per_chip"]
    names = {m["name"] for m in cell.per_layer}
    assert {n + ".rate" for n in NEW_READERS} <= names
    assert not any(n.startswith(("serve_flash", "serve_dsa")) for n in names)
    for m in cell.per_layer:
        assert callable(cells.layer_reader(cell, m["name"].split(".")[0]))
        assert m["moves"] in ("setup_s", "serve_tokens_per_s_per_chip")
    # the file: every published number under its key, the three cuts named
    config = cell.config
    assert config["reduced"] == ["num_hidden_layers", "num_dense_layers",
                                 "vocab_size"]
    for key, value in config["published"].items():
        if key in config["reduced"]:
            assert config[key] != value and key in config["changed"]
        else:
            assert config[key] == value, key
    model = config["model"]
    assert (model["d_model"], model["n_heads"], model["n_kv_heads"],
            model["d_head"], model["d_ff"], model["lead_d_ff"],
            model["moe_top_k"], model["n_experts"], model["attn_window"],
            model["moe_route_scale"], model["norm_eps"]) == (
        config["hidden_size"], config["num_attention_heads"],
        config["num_key_value_heads"], config["head_dim"],
        config["moe_intermediate_size"], config["intermediate_size"],
        config["num_experts_per_tok"], config["num_experts"],
        config["sliding_window"], config["route_scale"],
        config["rms_norm_eps"])
    assert model["moe_shared_ff"] == (config["moe_intermediate_size"]
                                      * config["num_shared_experts"])
    assert "moe_experts_held" not in model      # every expert is here
    assert model["embed_scale"] == config["hidden_size"] ** 0.5
    assert (model["n_layers"], len(model["lead_layers"]),
            model["vocab_size"]) == (
        config["num_hidden_layers"], config["num_dense_layers"],
        config["vocab_size"])
    # the layers held are the published model's, by their published index
    kinds = {"sliding_attention": "window", "full_attention": "full"}
    held = [kinds[config["layer_types"][l]] for l in config["layers_held"]]
    assert held == model["lead_layers"] + model["layer_pattern"]
    assert [l < config["published"]["num_dense_layers"]
            for l in config["layers_held"]] == [True] + [False] * 4
    assert model["vocab_size"] * 8 >= config["published"]["vocab_size"]
    # the traffic: ISSUE 53's table
    traffic = cell.traffic
    assert traffic["batching"]["max_batch_size"] == 2
    assert traffic["batching"]["rows"] == [1, 2]
    assert traffic["batching"]["lengths"] == [4096, 8192, 16384]
    assert traffic["documents"]["length"] == {
        "distribution": "lognormal", "median": 6000, "sigma": 0.7,
        "min": 2048, "max": 16384}
    assert traffic["tokens"]["support"] == model["vocab_size"] - 1
    assert "burst" not in traffic["arrivals"]
    assert traffic["arrivals"]["shuffle_block"] == 16
    assert (traffic["check"]["sample"], traffic["deadline_ms"],
            traffic["warmup"]["requests"], traffic["trace_seconds"]) == (
        16, 20000, 96, 8)
    assert traffic["documents"]["length"]["max"] == model["max_seq_len"]
    # the same documents as the sparse-attention cell's, on purpose
    other = cells.resolve("keye2-score-16k-over").traffic
    assert other["documents"] == traffic["documents"]
    assert other["batching"] == traffic["batching"]


def _brute(model, n):
    """A document of n tokens, position by position."""
    d, heads, width = model["d_model"], model["n_heads"], model["d_head"]
    flops = 0.0
    pairs = {"window": 0, "full": 0}
    for t in range(n):
        attention = 2 * d * (3 * heads + 2 * model["n_kv_heads"]) * width
        flops += 5 * attention                      # five layers' projections
        flops += 2 * 3 * d * model["lead_d_ff"]     # the leading dense layer
        flops += 4 * 2 * (d * model["n_experts"] + 3 * d * model["d_ff"] * (
            model["moe_top_k"] + 1))                # router, 8 routed, shared
        flops += 2 * d * model["vocab_size"]        # the sliced head
        pairs["window"] += min(t + 1, model["attn_window"])
        pairs["full"] += t + 1
    per_pair = 4 * heads * width
    return (flops + per_pair * (4 * pairs["window"] + pairs["full"]), pairs)


def test_the_work_counts_against_a_brute_loop():
    model = cells.resolve(CELL).config["model"]
    for n in (100, 2048, 5000):
        flops, pairs = _brute(model, n)
        assert swa_work.forward_flops(model, [n]) == pytest.approx(flops)
        assert swa_work.window_work(model, [n])["flops"] == (
            4 * 4.0 * 32 * 128 * pairs["window"])
        assert swa_work.full_work(model, [n])["flops"] == (
            1 * 4.0 * 32 * 128 * pairs["full"])
        both = swa_work.flash_forward_work(model, [n])
        assert both["flops"] == 4.0 * 32 * 128 * (4 * pairs["window"]
                                                  + pairs["full"])
        assert both["bytes"] == 5 * 2.0 * n * 2 * 36 * 128
    assert swa_work.band_pairs(16384, 2048) == (2048 * 2049 / 2
                                                + 14336 * 2048)
    experts = swa_work.expert_matmul_work(model, [1000, 24], calls=2)
    assert experts["flops"] == 6.0 * 4 * 8 * 1024 * 2048 * 1024
    assert experts["bytes"] == 2 * (4 * 8 * 1024 * (2 * 2048 + 3 * 1024)
                                    + 2 * 3.0 * 4 * 128 * 2048 * 1024)
    # the band of a 16,384-token row is under a quarter of its triangle
    assert 0.23 < (swa_work.band_pairs(16384, 2048)
                   / swa_work.causal_pairs(16384)) < 0.24
    # a reader that finds nothing to read returns nothing and does not raise
    cell = cells.resolve(CELL)
    empty = {"cell": {"name": "none", "config": cell.config}, "trace": None,
             "traced": None, "peaks": None}
    for name in NEW_READERS:
        assert cells.layer_reader(cell, name)(empty) is None


def test_the_schedule_is_the_sparse_cells_in_smaller_blocks():
    traffic = cells.resolve(CELL).traffic
    a = arrivals.schedule(traffic, 45.0, 7)
    b = arrivals.schedule(traffic, 45.0, 2 ** 31 + 12345)
    assert sorted(a["lengths"][:192]) == sorted(b["lengths"][:192])
    assert sorted(a["lengths"][:16]) == sorted(b["lengths"][:16])
    assert 6500 < a["lengths"].mean() < 7600


# ---------------------------------------------- the reference, the control

@pytest.fixture(scope="module")
def scorer():
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    jax.config.update("jax_platforms", "cpu")
    cell = cells.resolve("tiny-swa-over", TINY)
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
    from benchmarks.reference import trinity_mini, trinity_mini_glue
    top, layers = trinity_mini_glue.reference_weights(
        deployment.init_params(jax.random.PRNGKey(cfg["seed"])), None,
        jax.devices())
    layers = list(layers)
    assert len(layers) == 5 and "mlp.gate_proj" in layers[0] and all(
        "mlp.router.gate" in w for w in layers[1:])
    doc = max(docs, key=len)
    assert len(doc) > 64            # longer than the fixture's window
    alone = np.asarray(trinity_mini.token_logprobs(
        jnp.asarray(doc[None]), top, layers, cfg["config"]))[0]
    padded = loop._reference_scores(cfg, deployment.init_params, [doc])[0]
    assert alone.shape == padded.shape == (len(doc) - 1,)
    assert np.abs(alone - padded).max() < 2e-5


# --------------------------------------------------- rehearsals and faults

def _rehearse(trace, patch=None, seconds="4"):
    rehearsal = {"num_tpus": 1}
    if patch:
        rehearsal["patch"] = "benchmarks.tests.trinity_faults:" + patch
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=os.path.join(
                   ROOT, ".bench_runs", "test_cache"))
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "from benchmarks import run\n"
        "sys.exit(run.main(['--workload', 'tiny-swa-over', '--seed', '5', "
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
    if trace:       # no device trace on the CPU: the new readers read nothing
        assert not any(name.split(".")[0] in NEW_READERS
                       for name in line["metrics"])
        for name in ("serve_queue_ms.rate", "jax_trace_s", "serve_programs_s"):
            assert name in line["metrics"], sorted(line["metrics"])


@pytest.mark.parametrize("patch", [
    "window_layers_full", "gate_left_out", "post_norm_left_out"])
def test_a_planted_fault_comes_out_not_correct(patch):
    line, stderr = _rehearse(0, patch)
    assert line["correct"] is False
    for number in ("score_gap_max", "score_gap_rms"):
        seen = line["compared"][number]
        assert seen["value"] > seen["limit"], (patch, line["compared"])
    assert "not correct: " in stderr
