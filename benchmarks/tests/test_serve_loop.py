"""`loops/serve.py` and what it stands on, on the CPU at a toy width:

- the arrival schedule (`arrivals.py`): seeded, the mean rate, the bursts'
  period and factor, the same work for every seed in another order;
- the tails and the deadline accounting (`request_rows`, `window_numbers`);
- how a collected batch is cut into buckets (`plan_groups`);
- the forward-only FLOP count against the training count (`flops.py`);
- the reference scores a document the same whether or not zeros follow it;
- the float8 control fails `serve_check.compare` at the fixture's limits and
  the deployment class's own answers pass it;
- the planted faults: a whole CPU rehearsal of a fixture cell with the
  program broken underneath (`serve_faults.py`) comes out not correct on
  the number that should catch it;
- a CPU rehearsal of both cells prints a well-formed last line, `correct`
  never true;
- every per-layer entry of `BENCHMARK.json` that applies to a served cell
  has a reader that returns a number on a run recorded on the chip.
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

from benchmarks import arrivals, cells, flops, serve_check  # noqa: E402
from benchmarks.loops import serve as loop                   # noqa: E402

FIXTURES = os.path.join(ROOT, "benchmarks", "tests", "fixtures")
TINY = os.path.join(FIXTURES, "BENCHMARK.serve_tiny.json")
RECORDED = os.path.join(FIXTURES, "v5e_serve_score_pr44.run.json")


def _traffic(name):
    return cells.load_json(os.path.join(ROOT, "benchmarks", "traffic",
                                        name + ".json"))


# ------------------------------------------------------------- the schedule

@pytest.mark.parametrize("name", ["serve-score-1k-burst",
                                  "serve-score-1k-steady-over"])
def test_the_schedule_is_seeded_and_every_seed_carries_the_same_work(name):
    traffic = _traffic(name)
    a = arrivals.schedule(traffic, 45.0, 7)
    b = arrivals.schedule(traffic, 45.0, 7)
    c = arrivals.schedule(traffic, 45.0, 2 ** 31 + 12345)
    assert np.array_equal(a["send_s"], b["send_s"])
    assert np.array_equal(a["lengths"], b["lengths"])
    assert not np.array_equal(a["lengths"], c["lengths"])
    assert (np.diff(a["send_s"]) >= 0).all() and a["send_s"][-1] < 45.0
    # the same multiset of lengths block by block, so any stretch of the run
    # carries the same work for every seed
    block = int(traffic["arrivals"]["shuffle_block"])
    whole = len(a["lengths"]) // block * block
    assert whole == len(c["lengths"]) // block * block
    for lo in range(0, whole, block):
        assert sorted(a["lengths"][lo:lo + block]) == sorted(
            c["lengths"][lo:lo + block])
        # and the block's arrivals end at the same moment
        assert abs(a["send_s"][lo + block - 1]
                   - c["send_s"][lo + block - 1]) < 2e-3
    spec = traffic["documents"]["length"]
    assert a["lengths"].min() >= spec["min"]
    assert a["lengths"].max() <= spec["max"]
    # the mean rate, within three standard deviations of a Poisson count
    want = arrivals.mean_rate(traffic["arrivals"], 45.0) * 45.0
    assert abs(len(a["send_s"]) - want) < 3 * want ** 0.5 + 1
    docs = arrivals.documents(traffic, a["lengths"], 7)
    assert [len(d) for d in docs] == list(a["lengths"])
    assert max(d.max() for d in docs) < traffic["tokens"]["support"]
    again = arrivals.documents(traffic, a["lengths"], 7)
    assert all(np.array_equal(x, y) for x, y in zip(docs, again))


def test_the_bursts_come_on_their_period_at_their_factor():
    traffic = _traffic("serve-score-1k-burst")
    spec = traffic["arrivals"]
    burst = spec["burst"]
    send = arrivals.schedule(traffic, 45.0, 3)["send_s"]
    phase = send % burst["every_s"]
    inside = phase >= burst["every_s"] - burst["for_s"]
    periods = 45.0 / burst["every_s"]
    burst_s = sum(min(burst["for_s"], max(0.0, 45.0 - (
        k * burst["every_s"] + burst["every_s"] - burst["for_s"])))
        for k in range(int(np.ceil(periods))))
    rate_in = inside.sum() / burst_s
    rate_out = (~inside).sum() / (45.0 - burst_s)
    assert abs(rate_out / spec["rate_per_s"] - 1) < 0.1
    assert abs(rate_in / (spec["rate_per_s"] * burst["factor"]) - 1) < 0.1
    # a run starts quiet, and the mean lies under the peak
    assert not inside[0]
    mean = arrivals.mean_rate(spec, 45.0)
    assert spec["rate_per_s"] < mean < spec["rate_per_s"] * burst["factor"]
    # the schedule knows nothing of service times: it is made before any
    # request is sent, from the traffic file and the seed alone
    assert np.array_equal(send, arrivals.schedule(traffic, 45.0, 3)["send_s"])


# ------------------------------------------------- tails and the deadline

def test_percentile_is_an_observed_value_by_nearest_rank():
    values = list(range(1, 101))
    assert arrivals.percentile(values, 99) == 99
    assert arrivals.percentile(values, 50) == 50
    assert arrivals.percentile([5.0], 99) == 5.0
    assert arrivals.percentile([1, 2, 3, 1000], 99) == 1000


def test_a_late_failed_or_missing_request_counts_at_the_deadline():
    lengths = np.array([10, 20, 30, 40, 50])
    answer = {"bucket": (4, 64), "received": 100.011, "fired": 100.02,
              "done": 100.05, "logprobs": np.zeros(9, np.float32)}
    records = {
        0: {"due": 100.0, "sent": 100.001, "returned": 100.06,
            "answer": answer},
        1: {"due": 100.0, "sent": 100.001, "returned": 103.5,     # late
            "answer": dict(answer)},
        2: {"due": 100.0, "sent": 100.0, "returned": 100.2,       # raised
            "error": "boom"},
        # 3 never returned
        4: {"due": 100.5, "sent": 100.5, "returned": 101.4,       # after close
            "answer": dict(answer)},
    }
    rows = loop.request_rows(records, lengths, 0, 5, deadline_s=2.0)
    assert [r["ok"] for r in rows] == [True, False, False, False, True]
    assert [r["latency_s"] for r in rows][1:4] == [2.0, 2.0, 2.0]
    assert abs(rows[0]["latency_s"] - 0.06) < 1e-9
    assert abs(rows[0]["send_lag_s"] - 0.001) < 1e-9
    assert abs(rows[0]["ingress_s"] - 0.010) < 1e-6
    assert abs(rows[0]["queue_s"] - 0.009) < 1e-6
    assert abs(rows[0]["reply_s"] - 0.010) < 1e-6
    batches = [{"fired": 100.02, "done": 100.05, "exit": 100.051,
                "requests": 2, "calls": [(4, 64, [10, 20])]}]
    window = loop.window_numbers(rows, batches, 100.0, 1.0, 1, flops,
                                 {"d_model": 8, "n_layers": 1, "n_heads": 1,
                                  "vocab_size": 16})
    assert window["requests"] == 5 and window["failed"] == 3
    # the request answered after the close is in the tail, not in the rate
    assert window["answered_in_window"] == 1
    assert window["tokens_answered"] == 10
    assert window["serve_tokens_per_s_per_chip"] == 10.0
    assert window["latency_p99_ms"] == 2000.0
    assert window["real_tokens_fired"] == 30
    assert window["padded_tokens_fired"] == 256


def test_a_collected_batch_is_cut_by_length_and_padded_to_buckets():
    calls = loop.plan_groups([100, 10, 300, 128, 129, 1024] + [60] * 33,
                             rows=[4, 8, 16, 32],
                             widths=[128, 256, 512, 1024])
    by = {(r, w): m for r, w, m in calls}
    assert sorted((r, w, len(m)) for r, w, m in calls) == [
        (4, 128, 4), (4, 256, 1), (4, 512, 1), (4, 1024, 1), (32, 128, 32)]
    assert sorted(i for _, _, m in calls for i in m) == list(range(39))
    assert by[4, 256] == [4] and by[4, 1024] == [5]
    with pytest.raises(ValueError):
        loop.bucket_for(1025, [128, 1024])


# ------------------------------------------------------------- the work

def test_forward_flops_are_a_third_of_the_training_count():
    model = cells.load_json(os.path.join(
        ROOT, "benchmarks/configs/gpt2_medium.json"))["model"]
    for seq in (64, 400, 1024):
        assert flops.forward_flops(model, [seq]) == pytest.approx(
            seq * flops.model_flops_per_token(model, seq) / 3.0, rel=1e-12)
        fwd = flops.flash_forward_work(model, [seq] * 5)
        whole = flops.flash_attention_work(model, seq, 5)
        assert fwd["flops"] == pytest.approx(whole["flops"] / 3.0)
        assert fwd["bytes"] == pytest.approx(whole["bytes"] / 3.0)
    # documents are scored alone: two of 512 need less attention than one
    # row of 1024 that held both
    assert flops.forward_flops(model, [512, 512]) < flops.forward_flops(
        model, [1024])
    by_hand = 2.0 * (24 * 12 * 1024 * 1024 + 1024 * 50304) * 100 \
        + 2.0 * 24 * 1024 * 100 * 100
    assert flops.forward_flops(model, [100]) == by_hand


# ---------------------------------------------- the reference, the control

@pytest.fixture(scope="module")
def scorer():
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    jax.config.update("jax_platforms", "cpu")
    cell = cells.resolve("tiny-serve", TINY)
    served = loop.served_group(cell.root, cell.paths, cell.config_name)
    cfg = {"config": cell.config, "served": served, "traffic": cell.traffic,
           "chips": 1, "platform": "cpu", "seed": 11, "root": cell.root,
           "paths": cell.paths, "patch": None, "run_called_wall": 0.0}
    plan = arrivals.schedule(cell.traffic, 8.0, 11)
    docs = arrivals.documents(cell.traffic, plan["lengths"][:24], 11)
    return loop.Scorer(cfg), cfg, served, docs


def test_the_deployments_answers_pass_and_the_float8_control_fails(scorer):
    deployment, cfg, served, docs = scorer
    answers = []
    for lo in range(0, len(docs), 8):
        answers += [a["logprobs"] for a in
                    deployment._score_batch(docs[lo:lo + 8])]
    assert [len(a) for a in answers] == [len(d) - 1 for d in docs]
    reference = loop._reference_scores(cfg, deployment.init_params, docs)
    rows, problems = serve_check.compare(docs, answers, reference,
                                         served["reference"])
    assert not problems, problems
    assert {r[0] for r in rows} == {"score_gap_max", "score_gap_rms"}
    control = loop._reference_scores(cfg, deployment.init_params, docs,
                                     "float8_e4m3fn")
    rows, problems = serve_check.compare(docs, control, reference,
                                         served["reference"])
    assert len(problems) == 2, problems     # both numbers, three times over
    for name, _, value, limit in rows:
        assert value > 3 * limit, (name, value, limit)
    # an answer with a value missing, or a value that is no number
    short = [answers[0][:-1]] + answers[1:]
    assert serve_check.compare(docs, short, reference,
                               served["reference"])[1]
    holed = [np.where(np.arange(len(answers[0])) == 2, np.nan, answers[0])
             ] + answers[1:]
    assert serve_check.compare(docs, holed, reference,
                               served["reference"])[1]


def test_the_reference_scores_a_document_alone_whatever_follows_it(scorer):
    deployment, cfg, _, docs = scorer
    import jax
    import jax.numpy as jnp
    from benchmarks.reference import gpt2, gpt2_glue
    top, layers = gpt2_glue.reference_weights(
        deployment.init_params(jax.random.PRNGKey(cfg["seed"])), None,
        jax.devices())
    layers = list(layers)
    doc = docs[0]
    alone = np.asarray(gpt2.token_logprobs(
        jnp.asarray(doc[None]), top, layers, cfg["config"]))[0]
    padded = loop._reference_scores(cfg, deployment.init_params, [doc])[0]
    assert alone.shape == padded.shape == (len(doc) - 1,)
    assert np.abs(alone - padded).max() < 2e-5


# --------------------------------------------------- rehearsals and faults

def _rehearse(workload, trace, patch=None, seconds="4"):
    rehearsal = {"num_tpus": 1}
    if patch:
        rehearsal["patch"] = "benchmarks.tests.serve_faults:" + patch
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=os.path.join(
                   ROOT, ".bench_runs", "test_cache"))
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "from benchmarks import run\n"
        "sys.exit(run.main(['--workload', %r, '--seed', '5', '--seconds', "
        "%r, '--trace', %r], benchmark_file=%r, rehearsal=%r))\n"
        % (ROOT, workload, seconds, str(trace), TINY, rehearsal))
    done = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1]), done.stderr


@pytest.mark.parametrize("workload,trace,metric", [
    ("tiny-serve", 0, "latency_p99_ms"),
    ("tiny-serve-over", 0, "serve_tokens_per_s_per_chip"),
    ("tiny-serve-over", 1, "serve_pad_share.rate")])
def test_a_served_cell_rehearses_on_the_cpu_and_never_reads_correct(
        workload, trace, metric):
    line, stderr = _rehearse(workload, trace)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"] and list(line)[-1] == "compared"
    assert line["correct"] is False and "ran on 'cpu'" in stderr
    assert line["attempted"] > 20 and line["failed"] == 0
    assert line["metrics"][metric]["value"] > 0
    assert ("setup_s" in line["metrics"]) == (not trace)
    assert line["device"]["platform"] == "cpu"
    compared = line["compared"]
    # the path itself is sound: only the machine is wrong
    for name in ("score_gap_max", "score_gap_rms",
                 "answers_of_wrong_length"):
        assert compared[name]["value"] <= compared[name]["limit"], compared
        assert f"compared: {name} = " in stderr
    if trace:       # the proxy's probes and the program's own counters read
        for name in ("serve_proxy_ms.rate", "serve_queue_ms.rate",
                     "jax_trace_s", "serve_programs_s",
                     "serve_replica_class_load_s"):
            assert name in line["metrics"], sorted(line["metrics"])


@pytest.mark.parametrize("patch,number", [
    ("answers_swapped", "answers_of_wrong_length"),
    ("neighbour_leak", "score_gap_rms"),
    ("positions_shifted", "score_gap_rms"),
    ("last_rows_truncated", "score_gap_max")])
def test_a_planted_fault_comes_out_not_correct(patch, number):
    line, stderr = _rehearse("tiny-serve-over", 0, patch)
    assert line["correct"] is False
    seen = line["compared"][number]
    assert seen["value"] > seen["limit"], line["compared"]
    assert "not correct: " in stderr


# ------------------------------------------------- BENCHMARK.json's entries

def test_every_entry_of_a_served_cell_reads_a_number_on_a_recorded_run():
    """The run recorded on the v5e (PR 44: `gpt2m-serve-score-over`,
    `--trace 1`, what `loops/serve.py` hands its readers, the raw trace
    replaced by `program_trace`'s analysis of it) must give every per-layer
    entry that applies to a served cell a number through its reader."""
    from benchmarks import program_trace
    bench = cells.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    recorded = cells.load_json(RECORDED)
    run = recorded["run"]
    run["work"] = flops
    served = [w["name"] for w in bench["workloads"]
              if cells.resolve(w["name"]).traffic["kind"] == "serve"]
    assert len(served) == 2
    program_trace._cache[run["cell"]["name"]] = recorded["program_trace"]
    for name in served:
        cell = cells.resolve(name)
        assert cell.per_layer
        for m in cell.per_layer:
            if (m["source"] == "program_counter"
                    or m["name"] == "serve_replica_class_load_s"):
                continue    # the runtime's own table: read in the rehearsal
            value = cells.layer_reader(cell, m["name"].split(".")[0])(run)
            assert isinstance(value, float) and value == value, m["name"]
            if "mfu" in m["name"] or m["name"].split(".")[0].endswith(
                    "_roofline"):
                assert 0 < value < 100, (m["name"], value)
        # and no entry without a list is one the served cells cannot read
        for m in bench["per_layer"]:
            if "workloads" not in m:
                assert m["source"] == "program_counter", m["name"]
