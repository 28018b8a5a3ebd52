"""Checks of `program_compile.py` and the six per-layer readers on top of it
(ISSUE 37), against a telemetry table made by hand. CPU only, not part of
tier-1:

    python -m pytest benchmarks/tests -q
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmarks import cells, program_compile, program_counters  # noqa: E402

READERS = ("jax_trace_s", "jax_lower_s", "jax_backend_compile_s",
           "jax_cache_misses", "gang_worker_register_s",
           "gang_worker_class_load_s")


def _made_up_snapshot(misses=1):
    """A warm start of one train worker beside two pool workers: the step
    and the state's init from the cache, `misses` small programs compiled;
    the pool's processes waited 40 s for work."""
    edges = (0.01, 1.0, 80.0)

    def h(total, count):
        return {"buckets": edges, "counts": [0, count, 0, 0], "sum": total,
                "count": count, "exemplar": None}

    def compile_(stage, fun, total, count=1, cache=None):
        tags = (("stage", stage), ("fun", fun))
        if cache:
            tags += (("cache", cache),)
        return (program_compile.COMPILE, tags), h(total, count)

    def start(phase, chips, total, count):
        return ((program_compile.WORKER_START,
                 (("phase", phase), ("chips", chips))), h(total, count))

    hists = dict([
        compile_("trace", "train_step", 6.0),
        compile_("trace", "add", 0.5, 400),
        compile_("trace", "<lambda>", 0.25, 2),
        compile_("lower", "train_step", 9.0),
        compile_("lower", "<lambda>", 0.75, 2),
        compile_("backend_compile", "train_step", 2.0, cache="hit"),
        compile_("backend_compile", "<lambda>", 1.0, cache="hit"),
        compile_("backend_compile", "convert_element_type", 0.125,
                 count=misses, cache="miss"),
        compile_("backend_compile", "eager", 0.0625, cache="off"),
        ((program_compile.CACHE_RETRIEVAL, ()), h(1.5, 2)),
        start("runtime", "1", 0.25, 1), start("first_task", "1", 0.125, 1),
        start("runtime", "0", 0.5, 2), start("first_task", "0", 80.0, 2),
        ((program_compile.LOAD_CODE,
          (("kind", "actor_class"), ("name", "_TrainWorker"))), h(2.5, 1)),
        ((program_compile.LOAD_CODE,
          (("kind", "actor_class"), ("name", "_QueueActor"))), h(0.02, 2)),
        ((program_compile.LOAD_CODE,
          (("kind", "function"), ("name", "read_back"))), h(0.01, 1)),
    ])
    if not misses:
        del hists[compile_("backend_compile", "convert_element_type", 0,
                           cache="miss")[0]]
    return {"counters": {}, "hists": hists, "gauges": {}, "digests": {},
            "meta": {}}


def test_the_stages_are_sums_of_own_times_over_every_function():
    table = program_counters.shape(_made_up_snapshot())
    assert program_compile.stage_seconds("trace", table) == 6.75
    assert program_compile.stage_seconds("lower", table) == 9.75
    assert program_compile.stage_seconds("backend_compile",
                                         table) == 3.1875
    assert program_compile.cache_misses(table) == 1
    # a warm start: the series is there, and it holds no miss
    warm = program_counters.shape(_made_up_snapshot(misses=0))
    assert program_compile.cache_misses(warm) == 0
    assert program_compile.stage_seconds("backend_compile", warm) == 3.0625


def test_the_train_workers_start_is_told_from_the_pools():
    table = program_counters.shape(_made_up_snapshot())
    # the processes whose first task held chips: not the pool's 40 s of wait
    assert program_compile.worker_register_seconds(table) == 0.375
    assert program_compile.class_load_seconds("_TrainWorker", table) == 2.5
    assert program_compile.class_load_seconds("_Absent", table) is None
    # no process held chips (a CPU gang): the mean over every process
    cpu = [dict(r, tags=dict(r["tags"], chips="0")) for r in table
           if r["name"] == program_compile.WORKER_START]
    assert program_compile.worker_register_seconds(cpu) == pytest.approx(
        0.75 / 3 + 80.125 / 3)
    # one phase alone is not a start
    assert program_compile.worker_register_seconds(
        [r for r in table if r["tags"].get("phase") != "runtime"]) is None


def test_the_progress_line_names_the_largest_rows():
    said = program_compile.summary(
        program_counters.shape(_made_up_snapshot()))
    assert said["kind"] == "program_compile"
    assert said["stages"]["trace"] == {"seconds": 6.75, "events": 403}
    assert said["cache"] == {"hit": 2, "miss": 1, "off": 1}
    assert said["cache_retrieval_s"] == 1.5
    assert len(said["largest"]) == 9 <= program_compile.LARGEST
    assert [(r["fun"], r["stage"], r["cache"]) for r in said["largest"][:3]
            ] == [("train_step", "lower", None),
                  ("train_step", "trace", None),
                  ("train_step", "backend_compile", "hit")]
    assert {r["name"] for r in said["start"]} == {
        program_compile.WORKER_START, program_compile.LOAD_CODE}
    json.dumps(said)


def test_the_six_readers_on_the_table_and_on_a_parent_without_it(
        monkeypatch, capsys):
    table = program_counters.shape(_made_up_snapshot())
    monkeypatch.setattr(program_counters, "_rows", table)
    monkeypatch.setattr(program_compile, "_said", False)
    cell = cells.resolve("gpt2m-steady")
    want = {"jax_trace_s": 6.75, "jax_lower_s": 9.75,
            "jax_backend_compile_s": 3.1875, "jax_cache_misses": 1,
            "gang_worker_register_s": 0.375,
            "gang_worker_class_load_s": 2.5}
    assert set(want) == set(READERS)
    for name, value in want.items():
        assert cells.layer_reader(cell, name)({}) == value, name
    # own times: the stages add up to no more than a start can hold
    assert sum(want[n] for n in READERS[:3]) == 19.6875
    # one progress line a process, however many readers ask
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert [x["kind"] for x in lines] == ["program_compile"]
    # the parent of the PR that added the series: every reader gives None
    monkeypatch.setattr(program_counters, "_rows", [])
    for name in READERS:
        assert cells.layer_reader(cell, name)({}) is None, name


@pytest.mark.parametrize("name", READERS)
def test_every_cell_reports_the_reader_for_setup_s(name):
    bench = cells.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    [entry] = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry["moves"] == "setup_s"
    # every cell's start reads them, but for the unpickling of the train
    # worker's class, which only the training cells have (the served cells
    # read their replica's: `serve_replica_class_load_s`)
    if name == "gang_worker_class_load_s":
        assert all(cells.resolve(w).traffic["kind"] == "train"
                   for w in entry["workloads"])
    else:
        assert "workloads" not in entry
    assert os.path.isfile(os.path.join(
        ROOT, "benchmarks", "layer_metrics", name + ".py"))
