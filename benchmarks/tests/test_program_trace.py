"""Checks of the readers that read what the program says about itself
(`program_trace.py`, `program_counters.py` and the per-layer metrics on top
of them). CPU only, not part of tier-1:

    python -m pytest benchmarks/tests -q
"""

import json
import math
import os
import struct
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmarks import cells, program_counters, program_trace  # noqa: E402
from benchmarks import trace_reduce  # noqa: E402

FIXTURES = os.path.join(HERE, "fixtures")
TINY = os.path.join(FIXTURES, "BENCHMARK.tiny.json")
RECORDED = os.path.join(FIXTURES, "v5e_gpt2_tiny_pr24.xplane.pb")
US = 1e3        # nanoseconds


# ---------------------------------------------------- a trace made by hand

def _op(name, path, start_us, dur_us, program=7):
    return (f"%{name} = f32[8]{{0}} fusion(f32[8]{{0}} %p), kind=kLoop",
            start_us * US, dur_us * US,
            {"tf_op": path, "program_id": program})


def _made_up_planes(offset_us=0.0):
    """Three executions of a step of 80 us: a `while` around scoped ops of
    every pass, a kernel, an op of another program inside the window, and
    two gaps: 80..100 under a span of the loop's thread, 180..260 under a
    span of ANOTHER thread only. Host times run `offset_us` ahead."""
    step = [
        ("while.1", "jit(train_step)/jvp()/while:", 0, 60),
        ("fusion.1", "jit(train_step)/jvp()/while/body/closed_call/mlp/"
                     "bsd,df->bsf/dot_general:", 0, 20),
        ("flash_fwd.3", "jit(train_step)/transpose(jvp())/while/body/"
                        "closed_call/checkpoint/rematted_computation/"
                        "attn_kernel/flash_fwd/pallas_call:", 20, 10),
        ("fusion.2", "jit(train_step)/transpose(jvp())/while/body/"
                     "closed_call/checkpoint/mlp/mul:", 30, 30),
        ("fusion.9", "jit(train_step)/transpose(jvp())/while/body/"
                     "squeeze:", 60, 10),
        ("fusion.3", "jit(train_step)/optimizer/add:", 70, 10),
    ]
    ops = [_op(n, p, s + base, d) for base in (0, 100)
           for n, p, s, d in step]
    ops.append(_op("fusion.1", step[1][1], 260, 20))     # the next window
    ops.append(_op("multiply_reduce_fusion", "jit(checksum)/mul:", 181, 1,
                   program=9))
    modules = [("jit_train_step(7)", b * US, 80 * US, {"run_id": i})
               for i, b in enumerate((0, 100, 260))]
    off = offset_us * US
    loop = [("rtpu:data::block_wait", 78 * US + off, 25 * US, {}),
            ("bench:batch_wait", 78 * US + off, 25 * US, {}),
            # nested: the innermost open span names the idle time
            ("rtpu:checkpoint::save", 179 * US + off, 40 * US, {}),
            ("rtpu:checkpoint::orbax_save", 185 * US + off, 30 * US, {}),
            ("np.asarray(jax.Array)", 186 * US + off, 4 * US, {}),
            ("np.asarray(jax.Array)", 195 * US + off, 6 * US, {})]
    flusher = [("rtpu:worker::telemetry_flush", 230 * US + off, 50 * US,
                {})]
    callbacks = [("CompleteCallbacks", (b + 80 + 0.5) * US + off, 1 * US,
                  {"run_id": i}) for i, b in enumerate((0, 100, 260))]
    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "id": 1, "events": ops},
            {"name": "XLA Modules", "id": 2, "events": modules}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python3", "id": 11, "events": loop},
            {"name": "python3", "id": 12, "events": flusher},
            {"name": "tpu-runtime/5", "id": 5, "events": callbacks}]}]


@pytest.mark.parametrize("offset_us", [0.0, 1500.0])
def test_program_trace_on_a_trace_made_by_hand(offset_us):
    got = program_trace.analyse(_made_up_planes(offset_us), "train_step")
    assert got["n_steps"] == 2
    assert math.isclose(got["window_s"], 260e-6)
    table = got["device_s_per_step"]
    assert math.isclose(table["mlp"]["forward"], 20e-6)
    assert math.isclose(table["mlp"]["backward"], 30e-6)
    assert math.isclose(table["attn_kernel"]["recompute"], 10e-6)
    assert math.isclose(table["optimizer"]["other"], 10e-6)
    # the scan's plumbing and the `while` itself (60 - 20 - 10 - 30 = 0)
    assert math.isclose(table["unscoped"]["backward"], 10e-6)
    assert math.isclose(got["step_device_s"], 80e-6)
    # the kernels are the names the trace holds, no closed list
    assert got["kernels_s_per_step"] == {"flash_fwd": pytest.approx(10e-6)}
    assert got["ops_of_other_programs"] == 1
    assert list(got["unscoped_top_s_per_step"])[0].startswith("fusion.9 ")
    # idle: 80..100 and 180..260 less the checksum's 1 us
    assert math.isclose(got["idle_s"], 99e-6)
    assert math.isclose(got["host_clock_offset_s"],
                        (offset_us + 0.5) * 1e-6)
    by = got["idle_s_by_thread_and_span"]
    # after the alignment host times read 0.5 us early (the quickest
    # callback's lag): block_wait 77.5..102.5 over the gap 80..100
    assert by["python3/11 data::block_wait"] == pytest.approx(20e-6)
    # save 178.5..218.5 around orbax_save 184.5..214.5, over the gaps
    # 180..181 and 182..260: the innermost open span names the time
    assert by["python3/11 checkpoint::orbax_save"] == pytest.approx(30e-6)
    assert by["python3/11 checkpoint::save"] == pytest.approx(7.5e-6)
    # the gap's tail is under a span of another thread only: 229.5..260
    assert by["python3/12 worker::telemetry_flush"] == pytest.approx(
        30.5e-6)
    # union over threads: all idle time but 218.5..229.5
    assert got["idle_program_s"] == pytest.approx(88e-6)
    assert got["saves"] == 1 and math.isclose(got["save_d2h_s"], 10e-6)

    run = {"cell": {"name": "made-up"}, "trace": {"step_module": "x"}}
    program_trace._cache["made-up"] = got
    try:
        assert program_trace.scope_share(run, ("mlp",)) == pytest.approx(
            62.5)
        assert program_trace.scope_share(
            run, (), ("recompute",)) == pytest.approx(12.5)
        assert program_trace.kernel_ms(run, "flash_fwd") == pytest.approx(
            0.01)
        assert program_trace.kernel_ms(run, "flash_bwd_dq") is None
        assert program_trace.kernels_seconds(
            run, "flash_") == pytest.approx(10e-6)
        assert program_trace.kernels_seconds(run, "gdn_rule_") is None
    finally:
        del program_trace._cache["made-up"]


def test_a_program_without_scopes_or_spans_reads_as_nothing():
    """The parent of the PR that added them: no scope in any path, no
    `rtpu:` annotation. Every reader gives None, none raises."""
    planes = _made_up_planes()
    for line in planes[0]["lines"]:
        line["events"] = [(n.replace("flash_fwd", "checkpoint"), s, d,
                           {**st, "tf_op": "jit(train_step)/mul:"})
                          for n, s, d, st in line["events"]]
    for line in planes[1]["lines"]:
        line["events"] = [e for e in line["events"]
                          if not e[0].startswith("rtpu:")]
    got = program_trace.analyse(planes, "train_step")
    assert got["scoped_ops"] == 0 and got["host_spans"] == 0
    run = {"cell": {"name": "parent"}, "trace": {"step_module": "x"}}
    program_trace._cache["parent"] = got
    try:
        for name in ("mlp_share", "attn_proj_share", "head_loss_share",
                     "optimizer_share", "recompute_share",
                     "unscoped_share", "flash_fwd_ms", "flash_bwd_ms",
                     "idle_program_share", "ckpt_d2h_s"):
            cell = cells.resolve("gpt2m-ckpt")
            assert cells.layer_reader(cell, name)(run) is None, name
    finally:
        del program_trace._cache["parent"]
    assert program_trace.analyse([], "train_step") is None
    assert program_trace.of_run({"cell": {"name": "x"}, "trace": None}) \
        is None


def test_paths_are_classified_by_what_jax_writes():
    path = ("jit(train_step)/transpose(jvp())/while/body/closed_call/"
            "checkpoint/rematted_computation/attn_qkv/jit(_var)/div:")
    assert program_trace.scope_of(path) == "attn_qkv"
    assert program_trace.pass_of(path) == "recompute"
    assert program_trace.scope_of(
        "jit(train_step)/transpose(jvp(head_loss))/mul:") == "head_loss"
    assert program_trace.pass_of(
        "jit(train_step)/transpose(jvp(head_loss))/mul:") == "backward"
    assert program_trace.pass_of("jit(train_step)/jvp(embed)/add:") == \
        "forward"
    assert program_trace.scope_of(
        "jit(train_step)/jvp()/while/body/dynamic_update_slice:") == \
        "unscoped"
    assert program_trace.pass_of("jit(train_step)/optimizer/add:") == \
        "other"
    # a kernel is whatever `pl.pallas_call(name=...)` named it
    assert program_trace.kernel_of(
        "a/attn_kernel/flash_fwd/pallas_call:") == "flash_fwd"
    assert program_trace.kernel_of(
        "a/shard_map/attn_kernel/gdn_rule/gdn_rule_bwd/pallas_call:") == \
        "gdn_rule_bwd"
    assert program_trace.kernel_of("a/mlp/mul:") is None
    assert program_trace.kernel_of("ragged-dot-none:") is None
    assert program_trace.kernel_of("") is None
    # a span that outlives its parent's record, back to back, nested
    pieces = program_trace.innermost_segments(
        [("a", 0, 10, {}), ("b", 2, 3, {}), ("c", 5, 1, {}),
         ("d", 20, 5, {})])
    assert pieces == [(0, 2, "a"), (2, 5, "b"), (5, 6, "c"), (6, 10, "a"),
                      (20, 25, "d")]


# ----------------------------------------------- the file format, by hand

def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number, value):
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def test_the_wire_reader_reads_an_xspace_written_by_hand():
    stat_names = {1: "tf_op", 2: "program_id", 3: "run_id", 4: "a/mlp/mul:"}
    stat_meta = b"".join(
        _field(5, _field(1, k) + _field(2, _field(1, k) + _field(2, v)))
        for k, v in stat_names.items())
    op_meta = (_field(1, 10) + _field(2, "%fusion.1 = f32[] fusion()")
               + _field(5, _field(1, 1) + _field(7, 4))       # ref_value
               + _field(5, _field(1, 2) + _field(3, 2 ** 63 + 5)))
    span_meta = _field(1, 11) + _field(4, "rtpu:train::report")
    events_meta = (_field(4, _field(1, 10) + _field(2, op_meta))
                   + _field(4, _field(1, 11) + _field(2, span_meta)))
    event = (_field(1, 10) + _field(2, 3_000_000) + _field(3, 7_000)
             + _field(4, _field(1, 3) + _field(4, 41))
             + _field(4, _field(1, 9) + _varint(2 << 3 | 1)
                      + struct.pack("<d", 1.5)))      # a double, not wanted
    line = (_field(1, 77) + _field(2, "XLA Ops") + _field(3, 1000)
            + _field(4, event)
            + _field(4, _field(1, 11) + _field(2, 0) + _field(3, 2_000)))
    plane = (_field(1, 0) + _field(2, "/device:TPU:0") + _field(3, line)
             + events_meta + stat_meta)
    planes = program_trace.read_xspace(_field(1, plane) + _field(2, "x"))
    assert [p["name"] for p in planes] == ["/device:TPU:0"]
    [got] = planes[0]["lines"]
    assert got["name"] == "XLA Ops" and got["id"] == 77
    assert got["events"] == [
        ("%fusion.1 = f32[] fusion()", 4000.0, 7.0,
         {"tf_op": "a/mlp/mul:", "program_id": 2 ** 63 + 5, "run_id": 41}),
        ("rtpu:train::report", 1000.0, 2.0, {})]


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded trace in the fixtures")
def test_program_trace_on_a_recorded_v5e_trace():
    """A trace recorded on a v5e chip (PR 24): the fixtures' tiny-ckpt cell
    through the instrumented program — three steps, one save, a sentinel.
    Holds the readers to the real format: the `tf_op` stat on the operations'
    metadata, the kernels' names, `rtpu:` annotations on host lines, the
    host clock's offset."""
    with open(RECORDED, "rb") as f:
        planes = program_trace.read_xspace(f.read())
    got = program_trace.analyse(planes, "train_step")
    expected = cells.load_json(RECORDED.replace(".xplane.pb",
                                                ".expected.json"))
    reduced = trace_reduce.reduce_file(RECORDED, "train_step")
    assert got["n_steps"] == reduced["n_steps"] == expected["n_steps"]
    assert math.isclose(got["window_s"], reduced["window_s"], rel_tol=1e-6)
    # the scopes cover the step, and sum to its device time
    table = got["device_s_per_step"]
    assert set(table) - {"unscoped"} == set(program_trace.SCOPES)
    step_s = sum(reduced["step_device_ms"]) / len(
        reduced["step_device_ms"]) / 1e3
    assert got["step_device_s"] <= step_s
    assert table["optimizer"]["other"] > 0
    assert table["mlp"]["forward"] > 0 and table["mlp"]["backward"] > 0
    assert table["attn_kernel"]["recompute"] > 0
    # 2 layers x (forward + recomputed forward, dq, dkv), and they are the
    # trace's custom calls
    kernels = got["kernels_s_per_step"]
    assert set(kernels) == {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}
    assert all(v > 0 for v in kernels.values())
    assert math.isclose(
        sum(kernels.values()),
        trace_reduce.op_seconds_per_step(reduced, r"^custom-call$"),
        rel_tol=1e-3)
    # the save's gap falls under the program's own span, on the device's
    # clock, in agreement with the benchmark's label
    assert reduced["idle_gaps"][0][0] == "ckpt_write"
    by = got["idle_s_by_thread_and_span"]
    top = max(by, key=by.get)
    assert top.endswith(" checkpoint::orbax_save")
    # (the rest of that gap is the checksum, the report and the refill)
    assert 0.85 * reduced["idle_gaps"][0][1] < by[top] \
        < reduced["idle_gaps"][0][1]
    assert got["idle_program_s"] / got["idle_s"] > 0.9
    assert got["host_spans"] >= expected["host_spans_at_least"]
    assert abs(got["host_clock_offset_s"]) < 60
    assert got["saves"] == 1 and got["save_d2h_s"] > 0


# ----------------------------------- the readers that read by name (PR 34)

@pytest.mark.parametrize("recorded,benchmark,workload", [
    ("v5e_gpt2_tiny_3steps", "BENCHMARK.tiny.json", "tiny-ckpt"),
    ("v5e_gpt2_tiny_pr24", "BENCHMARK.tiny.json", "tiny-ckpt"),
    ("v5e_olmoe_tiny_pr27", "BENCHMARK.olmoe_tiny.json", "tiny-olmoe")])
def test_the_readers_give_what_the_kernels_names_give(recorded, benchmark,
                                                      workload):
    """`attn_kernel_share`, `flash_attn_roofline`, `mlp_share`,
    `unscoped_share` and `moe_gmm_roofline` on the traces recorded on a v5e,
    against the same numbers taken from the operations' names in
    `trace_reduce`'s table: the flash calls are the kernels named `flash_*`
    and nothing else (not every custom call: the grouped matmuls and the
    compiler's own buffer calls are custom calls too), the `ragged-dot*`
    operations are the experts' and lie under `mlp`, and a trace whose
    kernels carry no name (PR 23's, recorded before they had one) reads as
    nothing."""
    from benchmarks import flops, moe_work
    path = os.path.join(FIXTURES, recorded + ".xplane.pb")
    with open(path, "rb") as f:
        planes = program_trace.read_xspace(f.read())
    whole = program_trace.analyse(planes, "train_step")
    reduced = trace_reduce.reduce_file(path, "train_step")
    cell = cells.resolve(workload, os.path.join(FIXTURES, benchmark))
    peak = cells.load_json(os.path.join(ROOT, "benchmarks/peaks.json"))[
        "TPU v5 lite"]
    model, seq = cell.config["model"], cell.traffic["seq_len"]
    rows = cell.config["batch_per_chip"]
    pairs = rows * seq * model.get("moe_top_k", 0) * model["n_layers"]
    run = {"cell": {"name": recorded, "chips": 1, "config": cell.config,
                    "traffic": cell.traffic},
           "peaks": peak, "trace": reduced,
           "window": {"first_window_record": 1, "step_records": [
               {"moe_expert_tokens": [1]},
               {"moe_expert_tokens": [pairs // 2, pairs - pairs // 2]}]}}
    experts = moe_work.analyse(planes, "train_step")
    program_trace._cache[recorded] = whole
    moe_work._cache[recorded] = experts and dict(
        experts, step_device_s=whole["step_device_s"])
    try:
        read = {m: cells.layer_reader(cells.resolve("olmoe-steady"), m)(run)
                for m in ("attn_kernel_share", "flash_attn_roofline",
                          "mlp_share", "unscoped_share", "moe_gmm_roofline",
                          "flash_fwd_ms", "step_device_ms")}
        # the recorded steps are PR 27's: their backward is the pair
        backward_ms = sum(program_trace.kernel_ms(run, name) or 0.0 for name
                          in ("flash_bwd_dq", "flash_bwd_dkv"))
    finally:
        del program_trace._cache[recorded], moe_work._cache[recorded]

    flash_s = trace_reduce.op_seconds_per_step(reduced, r"^flash_")
    ragged_s = trace_reduce.op_seconds_per_step(reduced, r"^ragged-dot")
    if recorded == "v5e_gpt2_tiny_3steps":
        assert flash_s == 0 and not whole["scoped_ops"]
        assert all(read[m] is None for m in read if m != "step_device_ms")
        return
    assert read["attn_kernel_share"] == pytest.approx(
        100 * flash_s / (read["step_device_ms"] / 1e3), rel=1e-3)
    assert read["attn_kernel_share"] == pytest.approx(
        (read["flash_fwd_ms"] + backward_ms) / read["step_device_ms"] * 100)
    work = flops.flash_attention_work(model, seq, rows)
    assert work["flops"] == 6 * model["n_layers"] * model["d_model"] * \
        seq * rows * seq
    assert read["flash_attn_roofline"] == pytest.approx(
        100 * flops.roofline_seconds(work, peak)["seconds"] / flash_s,
        rel=1e-3)
    assert 0 < read["flash_attn_roofline"] < 100
    # every custom call, as both read before: more than the flash calls
    # wherever the step has other kernels
    every = trace_reduce.op_seconds_per_step(reduced, r"^custom-call$")
    step = whole["step_device_s"]
    under_mlp = sum(whole["device_s_per_step"]["mlp"].values())
    assert read["mlp_share"] == pytest.approx(100 * under_mlp / step)
    assert not any(k.startswith("ragged-dot")
                   for k in whole["unscoped_top_s_per_step"])
    if ragged_s:
        assert every == pytest.approx(flash_s + ragged_s, rel=1e-2)
        in_scopes = sum(sum(row.values())
                        for row in experts["device_s_per_step"].values())
        assert under_mlp >= in_scopes + ragged_s * (1 - 1e-3)
        assert read["unscoped_share"] < 15
        took = experts["expert_matmul_s_per_step"]
        assert took == pytest.approx(ragged_s, rel=1e-3)
        # the pairs are the window's median of what the steps reported
        gmm = moe_work.expert_matmul_work(model, pairs)
        assert gmm["flops"] == 18 * pairs * model["d_model"] * model["d_ff"]
        assert read["moe_gmm_roofline"] == pytest.approx(
            100 * flops.roofline_seconds(gmm, peak)["seconds"] / took)
        assert 0 < read["moe_gmm_roofline"] < 100
    else:
        assert every == pytest.approx(flash_s, rel=1e-3)
        assert read["moe_gmm_roofline"] is None


# ---------------------------------------------------------------- counters

def _made_up_snapshot():
    h = lambda sum_, counts, buckets=(0.001, 0.01, 0.1): {  # noqa: E731
        "buckets": buckets, "counts": counts, "sum": sum_,
        "count": sum(counts), "exemplar": None}
    gang = "rtpu_train_gang_start_seconds"
    bg = "rtpu_worker_background_seconds"
    return {
        "counters": {
            ("rtpu_data_feed_batches_total", ()): 10.0,
            ("rtpu_data_feed_bytes_total", ()): 4.0e6,
            ("rtpu_checkpoint_save_bytes_total", ()): 6.0e9},
        "hists": {
            ("rtpu_train_report_seconds", ()): h(0.01, [2, 3, 0, 0]),
            ("rtpu_data_feed_wait_seconds", (("stage", "queue"),)):
                h(0.02, [5, 6, 0, 0]),
            ("rtpu_data_feed_wait_seconds", (("stage", "fetch"),)):
                h(0.005, [10, 0, 0, 0]),
            ("rtpu_data_feed_to_device_seconds", ()): h(0.004, [10, 0, 0, 0]),
            ("rtpu_checkpoint_save_seconds", ()): h(12.0, [0, 0, 0, 2]),
            # two workers: each observes each phase once
            (gang, (("phase", "spawn"),)): h(1.0, [0, 0, 0, 2]),
            (gang, (("phase", "load"),)): h(4.0, [0, 0, 0, 2]),
            (gang, (("phase", "run_wait"),)): h(1.0, [0, 0, 0, 2]),
            (bg, (("chips", "0"), ("thread", "telemetry_flush"))):
                h(9.0, [0, 0, 0, 3]),
            (bg, (("chips", "1"), ("thread", "telemetry_flush"))):
                h(0.02, [7, 2, 0, 0]),
            (bg, (("chips", "1"), ("thread", "sample_devices"))):
                h(0.003, [9, 0, 0, 0])},
        "gauges": {}, "digests": {}, "meta": {}}


def test_program_counters_on_a_table_made_by_hand(monkeypatch):
    table = program_counters.shape(_made_up_snapshot())
    assert program_counters.mean("rtpu_train_report_seconds",
                                 table) == pytest.approx(0.002)
    assert program_counters.total("rtpu_data_feed_batches_total",
                                  table) == 10
    assert program_counters.sum_count(
        "rtpu_data_feed_wait_seconds", table) == (pytest.approx(0.025), 21)
    assert program_counters.top_edge(
        "rtpu_worker_background_seconds", table, chips="1") == 0.01
    # the overflow bucket: the series' sum bounds one observation
    assert program_counters.top_edge(
        "rtpu_worker_background_seconds", table, chips="0") == 9.0
    assert program_counters.mean("rtpu_absent_seconds", table) is None
    assert program_counters.total("rtpu_absent_total", table) is None
    assert program_counters.top_edge("rtpu_absent_seconds", table) is None
    assert program_counters.gang_phase_seconds(
        ("load",), table) == pytest.approx(2.0)
    assert program_counters.gang_phase_seconds(
        table=table) == pytest.approx(3.0)
    assert program_counters.gang_phase_seconds(("load", "absent"),
                                               table) is None
    assert program_counters.gang_phase_seconds(table=[]) is None

    # the readers, on the same table
    monkeypatch.setattr(program_counters, "_rows", table)
    cell = cells.resolve("gpt2m-ckpt")
    want = {"report_put_ms": 2.0, "feed_block_wait_ms": 2.5,
            "feed_to_device_ms": 0.4, "feed_to_device_mb_per_s": 1000.0,
            "ckpt_save_s": 6.0, "ckpt_save_gb_per_s": 0.5,
            "gang_worker_spawn_s": 0.5, "gang_worker_load_s": 2.0,
            "gang_worker_start_s": 3.0, "worker_bg_max_ms": 10.0}
    for name, value in want.items():
        assert cells.layer_reader(cell, name)({}) == pytest.approx(value), \
            name
    for name in ("report_lag_ms", "ckpt_copy_s"):
        assert cells.layer_reader(cell, name)({}) is None, name
    # a program with none of the series: every reader gives None
    monkeypatch.setattr(program_counters, "_rows", [])
    for name in list(want) + ["report_lag_ms"]:
        assert cells.layer_reader(cell, name)({}) is None, name


# --------------------------------------------------------------- rehearsal

def _new_metrics():
    """The per-layer entries of the root BENCHMARK.json that the fixtures'
    own file does not have, with their cells renamed to the toy ones."""
    have = {m["name"] for m in cells.load_json(TINY)["per_layer"]}
    rename = {"gpt2m-steady": "tiny-steady", "gpt2m-ckpt": "tiny-ckpt",
              "gpt2xl-fsdp4": "tiny-fsdp4"}
    out = []
    for m in cells.load_json(os.path.join(ROOT, "BENCHMARK.json"))[
            "per_layer"]:
        if m["name"] not in have:
            m = dict(m)
            if "workloads" in m:
                # the cells this map knows; a metric of none of them (a
                # later model's own) has no toy cell here
                m["workloads"] = [rename[w] for w in m["workloads"]
                                  if w in rename]
                if not m["workloads"]:
                    continue
            out.append(m)
    return out


def test_the_toy_cell_runs_traced_with_the_new_readers(tmp_path):
    """`tiny-ckpt --trace 1` on the CPU with every new per-layer entry
    added to the fixtures' BENCHMARK file: the counters' readers give
    numbers, the device readers find no device plane and give None, and
    none raises (the run ends on the contract's line)."""
    bench = cells.load_json(TINY)
    new = _new_metrics()
    assert len(new) >= 20
    bench["per_layer"] += new
    path = tmp_path / "BENCHMARK.tiny24.json"
    path.write_text(json.dumps(bench))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=os.path.join(
                   ROOT, ".bench_runs", "test_cache"))
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "from benchmarks import run\n"
        "sys.exit(run.main(['--workload', 'tiny-ckpt', '--seed', '5', "
        "'--seconds', '3', '--trace', '1'], benchmark_file=%r, "
        "rehearsal={'num_tpus': 1}))\n" % (ROOT, str(path)))
    done = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    lines = [json.loads(x) for x in done.stdout.splitlines()
             if x.startswith("{")]
    metrics = lines[-1]["metrics"]
    counted = {"report_put_ms", "report_lag_ms", "feed_block_wait_ms",
               "feed_to_device_ms", "feed_to_device_mb_per_s",
               "ckpt_copy_s", "ckpt_save_s", "ckpt_save_gb_per_s",
               "gang_worker_spawn_s", "gang_worker_load_s",
               "gang_worker_start_s", "worker_bg_max_ms"}
    assert counted <= set(metrics), sorted(metrics)
    assert all(metrics[name]["value"] > 0 for name in counted)
    # no device plane on the CPU: nothing from the trace's readers
    assert not {m["name"] for m in new if m["source"] == "device_trace"} \
        & set(metrics)
    assert (metrics["gang_worker_spawn_s"]["value"]
            + metrics["gang_worker_load_s"]["value"]
            < metrics["gang_worker_start_s"]["value"]
            <= metrics["gang_start_s"]["value"])
    [counters] = [x for x in lines if x.get("kind") == "program_counters"]
    names = {row["name"] for row in counters["series"]}
    assert {"rtpu_train_report_seconds", "rtpu_train_gang_start_seconds",
            "rtpu_checkpoint_save_bytes_total",
            "rtpu_data_feed_bytes_total"} <= names
