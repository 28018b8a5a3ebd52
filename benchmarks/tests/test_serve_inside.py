"""The Serve layer's readers from inside (`serve_counters.py`,
`serve_trace.py` and the `layer_metrics/serve_*` on top of them), on a table
and a trace made by hand, and in a CPU rehearsal of a served cell. CPU only,
not part of tier-1:

    python -m pytest benchmarks/tests/test_serve_inside.py -q
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmarks import cells, program_counters  # noqa: E402
from benchmarks import serve_counters, serve_trace  # noqa: E402

TINY = os.path.join(HERE, "fixtures", "BENCHMARK.serve_tiny.json")
US = 1e3        # nanoseconds
TAGS = (("deployment", "scorer"),)
COUNTER_READERS = {     # reader -> what it must read off the table below
    "serve_collect_wait_ms": 2.0, "serve_collect_fill_ms": 10.0,
    "serve_batch_resolve_ms": 0.5, "serve_batch_queue_ms": 30.0,
    "serve_wake_ms": 4.0, "serve_exec_wait_ms": 600.0,
    "serve_slot_wait_ms": 590.0, "serve_route_max_ms": 25.0,
    "serve_refresh_ms": 1.5}
TRACE_READERS = ("serve_idle_starved_share", "serve_idle_collect_share")


def _new_entries():
    """The entries of `BENCHMARK.json` whose readers this file checks."""
    bench = cells.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    return [m for m in bench["per_layer"]
            if m["name"].split(".")[0] in (*COUNTER_READERS, *TRACE_READERS)]


# --------------------------------------------------- a table made by hand

def _digest(values):
    return {"centroids": [[v, 1.0] for v in sorted(values)],
            "count": len(values), "sum": sum(values), "min": min(values),
            "max": max(values)}


def _hist(buckets, observations):
    counts = [0] * (len(buckets) + 1)
    for v in observations:
        counts[next((i for i, b in enumerate(buckets) if v <= b),
                    len(buckets))] += 1
    return {"buckets": tuple(buckets), "counts": counts,
            "sum": sum(observations), "count": len(observations),
            "exemplar": None}


def _made_up_table():
    """Four batches of five requests: digest and histogram rows as the
    control plane keeps them, shaped as the readers get them."""
    edges = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05)
    phases = {"wait": [0.002] * 4, "fill": [0.010] * 4,
              "call": [0.050] * 4, "resolve": [0.0005] * 4}
    hists = {("rtpu_serve_batch_seconds", TAGS + (("phase", p),)):
             _hist(edges, values) for p, values in phases.items()}
    hists["rtpu_serve_handle_route_seconds", TAGS] = _hist(
        edges, [0.0002] * 18 + [0.004, 0.02])
    hists["rtpu_serve_handle_refresh_seconds", TAGS] = _hist(
        edges, [0.001, 0.002])
    digests = {
        ("rtpu_serve_batch_queue_seconds", TAGS):
            _digest([0.010] * 5 + [0.030] * 10 + [0.050] * 5),
        ("rtpu_serve_batch_wake_seconds", TAGS): _digest([0.004] * 20),
        ("rtpu_serve_queue_wait_digest_seconds", TAGS):
            _digest([0.6] * 20),
        ("rtpu_serve_replica_slot_wait_seconds", TAGS):
            _digest([0.59] * 20),
        # another deployment's, with fewer records: not the cell's
        ("rtpu_serve_batch_wake_seconds", (("deployment", "other"),)):
            _digest([0.5] * 3)}
    meta = {name: {"kind": kind, "description": ""}
            for kind, table in (("histogram", hists), ("digest", digests))
            for name, _ in table}
    return program_counters.shape({"hists": hists, "digests": digests,
                                   "meta": meta})


@pytest.fixture
def table(monkeypatch):
    monkeypatch.setattr(program_counters, "_rows", _made_up_table())
    monkeypatch.setattr(serve_counters, "_said", False)


@pytest.mark.parametrize("reader", sorted(COUNTER_READERS))
def test_a_counter_reader_reads_its_series_off_a_table_made_by_hand(
        table, reader, capsys):
    cell = cells.resolve("gpt2m-serve-score")
    run = {"window": {"batch_requests_mean": 5.0}}
    value = cells.layer_reader(cell, reader)(run)
    assert value == pytest.approx(COUNTER_READERS[reader]), reader
    said = [json.loads(line) for line in capsys.readouterr().out.splitlines()
            if line.startswith('{"kind": "serve_counters"')]
    assert len(said) == 1
    assert said[0]["batches"] == 4 and said[0]["requests_a_batch"] == 5.0
    assert said[0]["window_batch_rows_mean"] == 5.0
    names = {row["name"] for row in said[0]["series"]}
    assert len(names) == 7 and all(n.startswith("rtpu_serve_") for n in names)
    queue = next(row for row in said[0]["series"]
                 if row["name"] == "rtpu_serve_batch_queue_seconds")
    assert queue["count"] == 20 and queue["p50"] == pytest.approx(0.03)
    # a second reader of the process says nothing again
    cells.layer_reader(cell, "serve_wake_ms")(run)
    assert "serve_counters" not in capsys.readouterr().out


@pytest.mark.parametrize("reader", sorted(COUNTER_READERS))
def test_a_program_without_the_series_reads_nothing(monkeypatch, reader):
    """The parent of the PR that added them: no row, no number, no raise."""
    monkeypatch.setattr(program_counters, "_rows", [])
    monkeypatch.setattr(serve_counters, "_said", False)
    cell = cells.resolve("gpt2m-serve-score-over")
    assert cells.layer_reader(cell, reader)({"window": {}}) is None


# --------------------------------------------------- a trace made by hand

def _made_up_planes(offset_us, spans=True):
    """Three executions of bucket programs, busy 0..100, 200..300 and
    400..450 us: two gaps of 100 us in the stretch 0..400. The collector's
    thread waits over the first and fills over the second; three pool
    threads sit in `rtpu:actor_call::` over both. Host times run
    `offset_us` ahead of the device's."""
    ops = [("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop",
            s * US, d * US, {"program_id": 7})
           for s, d in ((0, 100), (200, 100), (400, 50))]
    modules = [(f"jit_score_bucket({p})", s * US, d * US, {"run_id": i})
               for i, (p, s, d) in enumerate(
                   ((7, 0, 100), (8, 200, 100), (7, 400, 50)))]
    off = offset_us * US

    def span(name, start, dur):
        return (name, start * US + off, dur * US, {})

    collector = [
        span("rtpu:serve::batch_call", -10, 111),
        span("bench:device_wait", 5, 90),
        span("rtpu:serve::batch_resolve", 101, 2),
        span("rtpu:serve::batch_wait", 103, 87),        # over 103..190
        span("rtpu:serve::batch_fill", 190, 5),
        span("rtpu:serve::batch_call", 195, 107),
        span("rtpu:serve::batch_resolve", 302, 2),
        span("rtpu:serve::batch_wait", 304, 1),
        span("rtpu:serve::batch_fill", 305, 85),        # over 305..390
        span("rtpu:serve::batch_call", 390, 70)]
    pool = [span("rtpu:actor_call::Replica.handle_request", -50, 600)]
    callbacks = [("CompleteCallbacks", (end + 0.5) * US + off, 1 * US,
                  {"run_id": i}) for i, end in enumerate((100, 300, 450))]
    lines = [{"name": "python3", "id": 20 + i, "events": pool}
             for i in range(3)]
    if spans:
        lines.append({"name": "python3", "id": 11, "events": collector})
    lines.append({"name": "tpu-runtime/5", "id": 5, "events": callbacks})
    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "id": 1, "events": ops},
            {"name": "XLA Modules", "id": 2, "events": modules}]},
        {"name": "/host:CPU", "lines": lines}]


@pytest.mark.parametrize("offset_us", [0.0, 1500.0])
def test_serve_trace_on_a_trace_made_by_hand(offset_us):
    got = serve_trace.analyse(_made_up_planes(offset_us))
    assert got["stretch_s"] == pytest.approx(400e-6)
    assert got["idle_s"] == pytest.approx(200e-6)
    assert got["host_clock_offset_s"] == pytest.approx(
        (offset_us + 0.5) * 1e-6)
    assert got["collector_threads"] == ["python3/11"]
    assert got["collector_spans"] == 9
    # host times read 0.5 us early after the alignment (the quickest
    # callback's lag). Gap 100..200: call to 100.5, resolve to 102.5, wait
    # to 189.5, fill to 194.5, call. Gap 300..400: call to 301.5, resolve
    # to 303.5, wait to 304.5, fill to 389.5, call.
    by = got["idle_s_by_phase"]
    assert by["batch_wait"] == pytest.approx(88e-6)
    assert by["batch_fill"] == pytest.approx(90e-6)
    assert by["batch_resolve"] == pytest.approx(4e-6)
    assert by["batch_call"] == pytest.approx(18e-6)
    assert got["idle_s_under_none"] == pytest.approx(0.0, abs=1e-12)
    # the four spans tile the collector's line over the whole stretch
    assert got["collector_cover_share"] == pytest.approx(100.0)
    assert not got["idle_s_under_other_spans"]


def test_the_idle_shares_read_half_and_half_and_ignore_the_pool(monkeypatch):
    """Two gaps of 100 us: the collector waits over the whole of one and
    fills over the whole of the other, host lines 1.5 ms ahead of the
    device's; the pool's three lines sit in `rtpu:actor_call::` over both."""
    run = {"cell": {"name": "made-up"}, "trace": {"step_module": "x"}}
    planes = _made_up_planes(1500.0, spans=False)
    off = 1500.0 * US
    planes[1]["lines"].append({"name": "python3", "id": 11, "events": [
        ("rtpu:serve::batch_wait", 95 * US + off, 110 * US, {}),
        ("rtpu:serve::batch_fill", 295 * US + off, 110 * US, {})]})
    monkeypatch.setitem(serve_trace._cache, "made-up",
                        serve_trace.analyse(planes))
    cell = cells.resolve("gpt2m-serve-score")
    starved = cells.layer_reader(cell, "serve_idle_starved_share")(run)
    collect = cells.layer_reader(cell, "serve_idle_collect_share")(run)
    assert starved == pytest.approx(50.0) and collect == pytest.approx(50.0)
    trace = serve_trace._cache["made-up"]
    assert trace["idle_s_under_none"] == pytest.approx(0.0, abs=1e-12)
    assert trace["collector_threads"] == ["python3/11"]


@pytest.mark.parametrize("reader", TRACE_READERS)
def test_a_trace_without_the_collectors_spans_reads_nothing(monkeypatch,
                                                            reader):
    run = {"cell": {"name": "parent"}, "trace": {"step_module": "x"}}
    got = serve_trace.analyse(_made_up_planes(0.0, spans=False))
    assert got["collector_spans"] == 0 and got["idle_s"] > 0
    monkeypatch.setitem(serve_trace._cache, "parent", got)
    cell = cells.resolve("keye2-score-16k-over")
    assert cells.layer_reader(cell, reader)(run) is None
    # nor without a trace at all (`--trace 0`, a run on the CPU)
    assert cells.layer_reader(cell, reader)(
        {"cell": {"name": "none"}, "trace": None}) is None


# ------------------------------------------------------ BENCHMARK.json's own

def test_every_reader_has_its_two_entries_and_its_file():
    entries = _new_entries()
    assert len(entries) == 2 * (len(COUNTER_READERS) + len(TRACE_READERS))
    over = ["gpt2m-serve-score-over", "keye2-score-16k-over",
            "trinity-score-16k-over"]
    for m in entries:
        reader, suffix = m["name"].split(".")
        assert m["layer"] == "Serve" and m["better"] == "lower"
        assert m["source"] == ("device_trace" if reader in TRACE_READERS
                               else "program_counter")
        assert (m["moves"], m["workloads"]) == {
            "tail": ("latency_p99_ms", ["gpt2m-serve-score"]),
            "rate": ("serve_tokens_per_s_per_chip", over)}[suffix]
        assert os.path.isfile(os.path.join(
            ROOT, "benchmarks", "layer_metrics", reader + ".py"))


# ------------------------------------------------------------ the rehearsal

def test_a_traced_rehearsal_holds_every_counter_entry(tmp_path):
    """`tiny-serve-over` on the CPU with `--trace 1`, its fixture's entries
    and this PR's: every `program_counter` entry reads a number from the
    replica's and the driver's own series; the two `device_trace` entries
    read nothing, a CPU trace holding no device plane."""
    bench = cells.load_json(TINY)
    added = [dict(m, workloads=["tiny-serve-over"]) for m in _new_entries()
             if m["name"].endswith(".rate")]
    bench["per_layer"] += added
    benchmark_file = tmp_path / "BENCHMARK.json"
    benchmark_file.write_text(json.dumps(bench))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=os.path.join(
                   ROOT, ".bench_runs", "test_cache"))
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "from benchmarks import run\n"
        "sys.exit(run.main(['--workload', 'tiny-serve-over', '--seed', '5',"
        " '--seconds', '4', '--trace', '1'], benchmark_file=%r, "
        "rehearsal={'num_tpus': 1}))\n" % (ROOT, str(benchmark_file)))
    done = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    said = [json.loads(line) for line in done.stdout.splitlines()
            if line.startswith("{")]
    line = said[-1]
    assert line["failed"] == 0
    for m in added:
        if m["source"] == "program_counter":
            assert line["metrics"][m["name"]]["value"] >= 0, m["name"]
        else:
            assert m["name"] not in line["metrics"]
    counters = next(s for s in said if s.get("kind") == "serve_counters")
    batches = counters["batches"]
    assert batches > 0 and counters["requests_a_batch"] >= 1.0
    # one observation a batch of every phase (the last wait is still open)
    phases = {row["tags"]["phase"]: row["count"]
              for row in counters["series"]
              if row["name"] == "rtpu_serve_batch_seconds"}
    assert phases == dict.fromkeys(("wait", "fill", "call", "resolve"),
                                   batches)
    # every request the client sent went through the handle's router
    routed = sum(row["count"] for row in counters["series"]
                 if row["name"] == "rtpu_serve_handle_route_seconds")
    assert routed >= line["attempted"]
