"""The train path measures itself (ISSUE 24): spans through
``util/tracing.py`` and series through ``_private/telemetry.py`` at every
layer boundary of a ``JaxTrainer.fit`` — trainer, checkpoint, data feed,
gang start, the worker's background thread — plus the profiler-clock twin
of a span and the names the model puts on the device's operations."""

import glob
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import ray_tpu
import ray_tpu.data as rd
from ray_tpu._private import telemetry
from ray_tpu.state import api as state_api
from ray_tpu.train import (Checkpoint, CheckpointConfig, JaxTrainer,
                           RunConfig, ScalingConfig)
from ray_tpu.util import tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKER_SPANS = ("train::report", "train::report_checkpoint",
                "train::report_put", "checkpoint::save",
                "checkpoint::clear_target", "checkpoint::orbax_save",
                "data::block_wait", "data::block_get", "data::to_device")
DRIVER_SPANS = ("train::pg_ready", "train::drain",
                "train::persist_checkpoint", "train::prune_checkpoints")
# ISSUE 37 took them out: each timed a submission that does not block
REMOVED_SPANS = ("train::results_queue", "train::create_workers",
                 "train::streaming_split")
REMOVED_SERIES = ("rtpu_jax_compiles_total",)
JAX_STAGES = ("trace", "lower", "backend_compile")
FIRST_LINE = "first_line_of_the_loop"
# child -> parent, as ISSUE 24's table nests them
NESTING = {"train::report_checkpoint": "train::report",
           "train::report_put": "train::report",
           "checkpoint::clear_target": "checkpoint::save",
           "checkpoint::orbax_save": "checkpoint::save",
           "train::persist_checkpoint": "train::drain",
           "train::prune_checkpoints": "train::drain",
           "train::report": "actor_call::_TrainWorker.run"}


def _loop(config):
    """A function jitted on the first line; then two device batches, two
    pytree checkpoints (the second prunes the first: `num_to_keep=1`),
    reported with the worker's pid."""
    import jax
    import jax.numpy as jnp
    from ray_tpu import train

    @jax.jit
    def first_line_of_the_loop(x):
        return x + 1

    first_line_of_the_loop(jnp.zeros(3)).block_until_ready()
    batches = train.get_dataset_shard("train").iter_device_batches(
        batch_size=4, dtype=jnp.int32)
    for i, batch in zip(range(2), batches):
        ckpt = Checkpoint.from_pytree(
            {"w": jnp.ones((8, 8)) * i, "step": jnp.int32(i)},
            dir=os.path.join(config["dir"], f"save_{i}"))
        train.report({"i": i, "pid": os.getpid(),
                      "rows": int(batch["x"].shape[0])}, checkpoint=ckpt)


def _fit(tmp_path, granted=False):
    dataset = rd.Dataset(block_refs=[
        ray_tpu.put({"x": np.arange(4, dtype=np.int32) + 4 * i})
        for i in range(4)])
    return JaxTrainer(
        _loop, train_loop_config={"dir": str(tmp_path / "saves")},
        scaling_config=ScalingConfig(num_workers=1, use_tpu=granted),
        datasets={"train": dataset},
        run_config=RunConfig(name="fit", storage_path=str(tmp_path),
                             checkpoint_config=CheckpointConfig(
                                 num_to_keep=1))).fit()


def _wait_for(read, wanted, timeout=20.0):
    """Workers ship spans and series asynchronously: poll."""
    deadline = time.monotonic() + timeout
    while True:
        got = read()
        if wanted(got) or time.monotonic() > deadline:
            return got
        time.sleep(0.2)


def _run(tmp_path, traced, granted=False):
    """One fit in a runtime of its own, and what the control plane holds
    of it afterwards; the runtime is down again when this returns.
    `granted`: the worker holds a declared chip (JAX finds the CPU), so it
    is a process started for it after the driver's first `.remote()`."""
    ray_tpu.init(num_cpus=4, _system_config={"tracing_enabled": traced},
                 **({"num_tpus": 1} if granted else {}))
    try:
        tracing.drain()
        result = _fit(tmp_path, granted)
        wanted = set(WORKER_SPANS + DRIVER_SPANS) | {
            "worker::sample_devices", "worker::telemetry_flush",
            "worker::load_code", "jax::backend_compile"}
        spans = _wait_for(
            state_api.list_spans,
            lambda rows: not traced
            or wanted <= {r["name"] for r in rows})
        summary = _wait_for(
            state_api.summarize_metrics,
            lambda s: s.get("rtpu_train_report_seconds", {}).get("count")
            == 2 and "rtpu_data_feed_batches_total" in s
            and "rtpu_worker_background_seconds" in s
            and "rtpu_jax_compile_seconds" in s)
        return {"result": result, "spans": spans, "summary": summary,
                "metrics": state_api.list_metrics()}
    finally:
        ray_tpu.shutdown()
        tracing.drain()


@pytest.fixture(scope="module")
def traced_fit(tmp_path_factory):
    return _run(tmp_path_factory.mktemp("traced"), traced=True)


@pytest.fixture(scope="module")
def untraced_fit(tmp_path_factory):
    return _run(tmp_path_factory.mktemp("untraced"), traced=False)


@pytest.fixture(scope="module")
def granted_fit(tmp_path_factory):
    return _run(tmp_path_factory.mktemp("granted"), traced=False,
                granted=True)


@pytest.mark.parametrize("name", WORKER_SPANS + DRIVER_SPANS)
def test_traced_fit_yields_the_span(traced_fit, name):
    result, spans = traced_fit["result"], traced_fit["spans"]
    assert result.error is None
    rows = [s for s in spans if s["name"] == name]
    assert rows, sorted({s["name"] for s in spans})
    assert all(s["end_time"] >= s["start_time"] and s["status"] == "OK"
               for s in rows)
    worker_pid = result.metrics["pid"]
    assert worker_pid != os.getpid()
    expected = worker_pid if name in WORKER_SPANS else os.getpid()
    assert {s["pid"] for s in rows} == {expected}, name


@pytest.mark.parametrize("child", sorted(NESTING))
def test_spans_nest_as_stated(traced_fit, child):
    spans = traced_fit["spans"]
    by_id = {s["span_id"]: s for s in spans}
    rows = [s for s in spans if s["name"] == child]
    assert rows
    for row in rows:
        parent = by_id.get(row["parent_id"])
        assert parent is not None, (child, row)
        assert parent["name"] == NESTING[child]
        assert parent["trace_id"] == row["trace_id"]
        assert parent["pid"] == row["pid"]


def test_worker_thread_spans_come_from_the_flusher(traced_fit):
    """`worker::` spans are the telemetry flusher's activations, in the
    train worker as in every process; they sit in no task's trace."""
    mine = [s for s in traced_fit["spans"]
            if s["name"].startswith("worker::")
            and s["name"] != "worker::load_code"]
    assert {s["name"] for s in mine} == {"worker::sample_devices",
                                         "worker::telemetry_flush"}
    assert traced_fit["result"].metrics["pid"] in {s["pid"] for s in mine}
    assert all(s["parent_id"] is None for s in mine)


@pytest.mark.parametrize("name", REMOVED_SPANS + REMOVED_SERIES)
def test_what_measured_nothing_is_gone(traced_fit, name):
    assert name not in {s["name"] for s in traced_fit["spans"]}
    assert name not in {r["name"] for r in traced_fit["metrics"]}
    # the README lists a train span by its stage, a series by its name
    listed = "`" + name.split("::")[-1] + "`"
    for path, text in (("README.md", listed),
                       ("ray_tpu/train/trainer.py", name),
                       ("ray_tpu/_private/telemetry.py", name)):
        with open(os.path.join(ROOT, path)) as f:
            assert text not in f.read(), path


def _series(fit, series, **tags):
    return [r for r in fit["metrics"] if r["name"] == series
            and all(r["tags"].get(k) == v for k, v in tags.items())]


@pytest.mark.parametrize("stage", JAX_STAGES)
def test_a_function_jitted_on_the_loops_first_line_is_in_the_table(
        untraced_fit, stage):
    """The listeners are in before the loop's first line (the worker
    installs them where it loads `_TrainWorker`), not a flusher's tick
    later; tracing is off, and the series count all the same."""
    [row] = _series(untraced_fit, "rtpu_jax_compile_seconds", stage=stage,
                    fun=FIRST_LINE)
    assert row["count"] == 1 and 0 <= row["sum"] < 60
    assert ("cache" in row["tags"]) == (stage == "backend_compile")


def test_the_compile_of_the_loops_first_line_is_a_row_under_run(traced_fit):
    spans = traced_fit["spans"]
    by_id = {s["span_id"]: s for s in spans}
    for stage in JAX_STAGES:
        [row] = [s for s in spans if s["name"] == "jax::" + stage
                 and s["attributes"].get("fun") == FIRST_LINE]
        assert row["pid"] == traced_fit["result"].metrics["pid"]
        assert row["end_time"] >= row["start_time"]
        parent = by_id[row["parent_id"]]
        assert parent["name"] == "actor_call::_TrainWorker.run"
        assert parent["start_time"] <= row["start_time"]
    assert row["attributes"]["cache"] in ("hit", "miss", "off")


def test_loading_the_train_worker_is_one_row_in_its_creation(traced_fit):
    spans = traced_fit["spans"]
    by_id = {s["span_id"]: s for s in spans}
    loads = [s for s in spans if s["name"] == "worker::load_code"]
    # one a class (or function) a process
    assert len({(s["pid"], by_id[s["parent_id"]]["name"]) for s in loads}) \
        == len(loads)
    [mine] = [s for s in loads
              if s["pid"] == traced_fit["result"].metrics["pid"]]
    assert by_id[mine["parent_id"]]["name"] == \
        "actor_create::_TrainWorker.__init__"
    [row] = _series(traced_fit, "rtpu_worker_load_code_seconds",
                    kind="actor_class", name="_TrainWorker")
    assert row["count"] == 1
    assert row["sum"] == pytest.approx(
        mine["end_time"] - mine["start_time"], abs=0.05)


def test_a_granted_workers_start_divides_the_gangs_load_phase(granted_fit):
    """A process started for the gang's worker: `main()` to `REGISTER`, to
    the creation task's arrival, then `_TrainWorker` unpickled — in order,
    disjoint, inside `rtpu_train_gang_start_seconds{phase=load}`."""
    assert granted_fit["result"].error is None
    phases = {r["tags"]["phase"]: r for r in _series(
        granted_fit, "rtpu_worker_start_seconds", chips="1")}
    assert set(phases) == {"runtime", "first_task"}
    assert all(r["count"] == 1 and r["sum"] >= 0 for r in phases.values())
    [loaded] = _series(granted_fit, "rtpu_worker_load_code_seconds",
                       kind="actor_class", name="_TrainWorker")
    [load] = _series(granted_fit, "rtpu_train_gang_start_seconds",
                     phase="load")
    assert loaded["count"] == load["count"] == 1
    parts = sum(r["sum"] for r in phases.values()) + loaded["sum"]
    assert 0 < parts <= load["sum"]
    # every other process observed its own start too, once a phase
    others = _series(granted_fit, "rtpu_worker_start_seconds", chips="0")
    assert {r["tags"]["phase"]: r["count"] for r in others} == {
        "runtime": others[0]["count"], "first_task": others[0]["count"]}


def test_tracing_off_buffers_no_row_and_the_series_still_count(
        untraced_fit):
    result, summary = untraced_fit["result"], untraced_fit["summary"]
    assert result.error is None and len(result.metrics_history) == 2
    assert untraced_fit["spans"] == []
    assert summary["rtpu_train_report_seconds"]["count"] == 2
    assert summary["rtpu_train_report_lag_seconds"]["count"] == 2
    assert 0 <= summary["rtpu_train_report_lag_seconds"]["sum"] < 60
    assert summary["rtpu_data_feed_batches_total"]["total"] >= 2
    assert summary["rtpu_data_feed_bytes_total"]["total"] >= 2 * 16
    assert summary["rtpu_data_feed_to_device_seconds"]["count"] >= 2
    assert summary["rtpu_checkpoint_save_seconds"]["count"] == 2
    assert summary["rtpu_checkpoint_save_bytes_total"]["total"] == \
        2 * (8 * 8 * 4 + 4)
    assert summary["rtpu_train_checkpoint_persist_seconds"]["count"] == 2


def test_gang_start_phases_and_feed_stages_are_tagged(untraced_fit):
    rows = {(r["name"], tuple(sorted(r["tags"].items()))): r
            for r in untraced_fit["metrics"]}
    phases = {tags[0][1]: r for (name, tags), r in rows.items()
              if name == "rtpu_train_gang_start_seconds"}
    # observed once in each worker, from the driver's first `.remote()`
    assert set(phases) == {"spawn", "load", "run_wait"}
    assert all(r["count"] == 1 and r["sum"] >= 0 for r in phases.values())
    assert 0 < sum(r["sum"] for r in phases.values()) < 60
    stages = {tags[0][1]: r for (name, tags), r in rows.items()
              if name == "rtpu_data_feed_wait_seconds"}
    assert set(stages) == {"queue", "fetch"}
    assert stages["fetch"]["count"] >= 2 <= stages["queue"]["count"]
    background = [r for (name, _), r in rows.items()
                  if name == "rtpu_worker_background_seconds"]
    assert {r["tags"]["thread"] for r in background} == {
        "sample_devices", "telemetry_flush"}


def test_a_span_is_a_profiler_annotation_on_a_host_line(tmp_path):
    """Inside an open profiler trace `start_span(name)` is `rtpu:<name>`
    on its thread's line of a host plane — with tracing off, so no row."""
    from jax.profiler import ProfileData

    assert not tracing.enabled()
    tracing.drain()
    with jax.profiler.trace(str(tmp_path)):
        with tracing.start_span("train::x") as span:
            jnp.ones((4, 4)).sum().block_until_ready()
        with tracing.timed_span("train::y", "rtpu_train_report_seconds"):
            pass
    assert span is None and tracing.drain() == []
    found = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    assert found
    names = {(plane.name, ev.name)
             for plane in ProfileData.from_file(found[0]).planes
             for line in plane.lines for ev in line.events
             if ev.name.startswith(tracing.ANNOTATION_PREFIX)}
    # (this process's own flusher may wake inside the trace, on its line)
    assert {n for _, n in names if not n.startswith("rtpu:worker::")} == {
        "rtpu:train::x", "rtpu:train::y"}
    assert all(plane.startswith("/host:") for plane, _ in names)


def test_a_process_without_jax_imports_none():
    """Spans, timed spans, the telemetry core and the feed's module: none
    of the new code pulls jax into a process that has not imported it."""
    code = (
        "import sys\n"
        "import ray_tpu\n"
        "import ray_tpu.data.iterator\n"
        "from ray_tpu._private import telemetry\n"
        "from ray_tpu.util import tracing\n"
        "with tracing.start_span('train::x', force=True):\n"
        "    with tracing.timed_span('data::y', "
        "'rtpu_data_feed_wait_seconds'):\n"
        "        pass\n"
        "telemetry.sample_devices()\n"
        "assert [s['name'] for s in tracing.drain()] == ['train::x']\n"
        "assert ('rtpu_data_feed_wait_seconds', ()) in "
        "telemetry.snapshot_local()['hists']\n"
        "assert 'jax' not in sys.modules, 'jax was imported'\n")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    done = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr[-2000:]


def test_timed_span_counts_activations_and_busy_seconds():
    name = "rtpu_train_checkpoint_persist_seconds"
    before = telemetry.snapshot_local()["hists"].get((name, ()),
                                                     {"count": 0, "sum": 0})
    with pytest.raises(KeyError):
        with tracing.timed_span("train::persist_checkpoint", name):
            time.sleep(0.01)
            raise KeyError("the observation survives the exception")
    after = telemetry.snapshot_local()["hists"][(name, ())]
    assert after["count"] == before["count"] + 1
    assert after["sum"] - before["sum"] >= 0.01


def test_the_backend_check_never_queues_behind_jax_lock():
    """The flusher asks this every second beside a loop that may be opening
    the TPU runtime under jax's backend lock for 10 s or more: a held lock
    reads as "not open yet", at once."""
    from jax._src import xla_bridge
    from ray_tpu._private import accelerators
    jax.devices()
    assert accelerators.jax_backend_initialized()
    with xla_bridge._backend_lock:
        t0 = time.perf_counter()
        assert not accelerators.jax_backend_initialized()
        assert telemetry.sample_devices() == 0
        assert time.perf_counter() - t0 < 1.0
    assert accelerators.jax_backend_initialized()


SCOPES = ("embed", "attn_qkv", "attn_kernel", "attn_out", "mlp",
          "head_loss", "optimizer")


def _tiny_step(attention_impl):
    from ray_tpu.models import (GPT, init_train_state, make_optimizer,
                                make_train_step)
    from ray_tpu.models.gpt import GPTConfig

    config = GPTConfig(vocab_size=512, n_layers=2, d_model=256, n_heads=2,
                       max_seq_len=256, activation="gelu",
                       norm="layernorm", positions="learned",
                       tie_embeddings=True, remat=True,
                       remat_policy="dots", attention_impl=attention_impl)
    model, optimizer = GPT(config), make_optimizer()
    state = jax.eval_shape(
        lambda: init_train_state(model, optimizer, jax.random.PRNGKey(0)))
    return make_train_step(model, optimizer), state


def test_the_lowered_step_carries_every_scope():
    """The scopes are metadata (locations) on the lowered operations, in
    the forward, the recomputed forward and the backward pass."""
    step, state = _tiny_step("reference")
    text = step.lower(
        state, {"tokens": jax.ShapeDtypeStruct((2, 256), jnp.int32)}
    ).as_text(debug_info=True)
    for scope in SCOPES:
        assert f"/{scope}/" in text or f"({scope})" in text, scope
    assert "rematted_computation/mlp" in text
    assert "transpose(jvp(head_loss))" in text


def test_the_step_compiled_for_a_v5e_names_its_three_kernels():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")
    one = SingleDeviceSharding(topo.devices[0])

    def on(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
            tree)

    step, state = _tiny_step("pallas")
    text = step.lower(on(state), {"tokens": jax.ShapeDtypeStruct(
        (2, 256), jnp.int32, sharding=one)}).as_text()
    for kernel in ("flash_fwd", "flash_bwd"):
        assert f'kernel_name = "{kernel}"' in text, kernel
    # the pair that `flash_bwd` replaced at rows that fit in VMEM (PR 38)
    assert 'kernel_name = "flash_bwd_d' not in text
