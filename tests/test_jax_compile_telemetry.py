"""jax's compile path as telemetry (ISSUE 37): every trace, lowering,
backend compile and cache lookup that ``jax.monitoring`` reports becomes an
observation of ``rtpu_jax_compile_seconds{stage, fun, cache}`` (own time)
and, traced, a ``jax::<stage>`` row — through
``telemetry.install_jax_listeners()``. Also the pins of the private and
drifting jax surfaces the runtime leans on (ROADMAP D12): a jax that renames
one fails here, not in every worker's flusher."""

import json
import os
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from ray_tpu._private import accelerators, telemetry
from ray_tpu.util import tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SERIES = "rtpu_jax_compile_seconds"
TRACE, LOWER, BACKEND = telemetry.JAX_STAGE_EVENTS


@pytest.fixture(autouse=True)
def listeners():
    assert telemetry.install_jax_listeners()


def _rows(fun=None):
    """{(stage, fun, cache): (count, sum)} of this process's series."""
    out = {}
    for (name, tags), h in telemetry.snapshot_local()["hists"].items():
        tags = dict(tags)
        if name == SERIES and fun in (None, tags["fun"]):
            out[(tags["stage"], tags["fun"], tags.get("cache"))] = (
                h["count"], h["sum"])
    return out


def _stage_sums():
    sums = dict.fromkeys(telemetry.JAX_STAGE_EVENTS.values(), 0.0)
    for (stage, _, _), (_, total) in _rows().items():
        sums[stage] += total
    return sums


def _span(event, start, end, fun, inside=()):
    """What jax does around one stage: the scalar at its start, whatever
    runs inside, the time span at its end."""
    jax.monitoring.record_scalar(event, start, fun_name=fun)
    for args in inside:
        _span(*args)
    jax.monitoring.record_event_time_span(event, start, end, fun_name=fun)


# ------------------------------------------------------------- the series

def test_the_three_stages_of_a_jitted_function_share_one_fun_tag():
    @jax.jit
    def three_stages_one_tag(x):
        return x * 2 + 1

    three_stages_one_tag(jnp.ones(4)).block_until_ready()
    rows = _rows("three_stages_one_tag")
    assert {(stage, cache) for stage, _, cache in rows} == {
        ("trace", None), ("lower", None), ("backend_compile", "off")}
    assert all(count == 1 and 0 <= total < 60
               for count, total in rows.values())
    # a second call runs the compiled program: nothing is traced again
    three_stages_one_tag(jnp.ones(4)).block_until_ready()
    assert _rows("three_stages_one_tag") == rows


def test_lower_and_compile_by_hand_are_counted_too():
    def lowered_by_hand(x):
        return jnp.tanh(x).sum()

    lowered = jax.jit(lowered_by_hand).lower(jnp.ones((3, 3)))
    assert {stage for stage, _, _ in _rows("lowered_by_hand")} == {
        "trace", "lower"}
    lowered.compile()
    assert {stage for stage, _, _ in _rows("lowered_by_hand")} == {
        "trace", "lower", "backend_compile"}


@pytest.mark.parametrize("reported, fun", [
    ("jit(train_step)", "train_step"), ("train_step", "train_step"),
    ("pmap(step)", "step"), ("jit(<lambda>)", "<lambda>"),
    ("jit(jit(f))", "jit(f)"), ("jit(", "jit("), ("", "")])
def test_the_wrapper_is_stripped_from_the_modules_name(reported, fun):
    assert telemetry._jax_fun(reported) == fun


def test_nested_traces_are_counted_once():
    """jax traces a jitted function called inside another inside the outer
    trace: the stage's sum over every function stays under the wall time
    around the calls, which nested durations added up would pass."""
    @jax.jit
    def nested_inner(x):
        return (x @ x).sum()

    @jax.jit
    def nested_outer(x):
        return nested_inner(x) + nested_inner(x + 1) + jnp.sin(x).sum()

    before = _stage_sums()
    t0 = time.time()
    nested_outer(jnp.ones((8, 8))).block_until_ready()
    wall = time.time() - t0
    after = _stage_sums()
    spent = {stage: after[stage] - before[stage] for stage in after}
    assert all(seconds >= 0 for seconds in spent.values())
    assert 0 < sum(spent.values()) <= wall
    assert ("trace", "nested_inner", None) in _rows("nested_inner")


def test_own_time_is_the_duration_less_the_children():
    """Events made by hand, as jax orders them: the inner spans are
    reported first, the outer one contains them — across stages too (an
    eager operation compiled while a function is being traced)."""
    _span(TRACE, 100.0, 110.0, "own_outer", inside=[
        (TRACE, 101.0, 102.0, "own_inner"),
        (TRACE, 103.0, 105.5, "own_inner", [
            (TRACE, 104.0, 104.5, "own_leaf")]),
        (BACKEND, 106.0, 109.0, "jit(own_eager)")])
    _span(LOWER, 110.0, 111.0, "jit(own_outer)")
    assert _rows("own_outer") == {
        ("trace", "own_outer", None): (1, pytest.approx(10 - 1 - 2.5 - 3)),
        ("lower", "own_outer", None): (1, pytest.approx(1.0))}
    assert _rows("own_inner") == {
        ("trace", "own_inner", None): (2, pytest.approx(1 + 2.5 - 0.5))}
    assert _rows("own_leaf")[("trace", "own_leaf", None)] == (
        1, pytest.approx(0.5))
    assert _rows("own_eager")[("backend_compile", "own_eager", "off")] == (
        1, pytest.approx(3.0))
    # nothing is left to claim: the next outermost span keeps its whole time
    _span(TRACE, 100.5, 112.0, "own_later")
    assert _rows("own_later")[("trace", "own_later", None)] == (
        1, pytest.approx(11.5))


def test_spans_of_another_thread_are_not_children():
    def other():
        _span(TRACE, 201.0, 202.0, "thread_other")

    jax.monitoring.record_scalar(TRACE, 200.0, fun_name="thread_main")
    worker = threading.Thread(target=other)
    worker.start()
    worker.join()
    jax.monitoring.record_event_time_span(TRACE, 200.0, 204.0,
                                          fun_name="thread_main")
    assert _rows("thread_main")[("trace", "thread_main", None)] == (
        1, pytest.approx(4.0))
    assert _rows("thread_other")[("trace", "thread_other", None)] == (
        1, pytest.approx(1.0))


def test_installing_twice_registers_once():
    from jax._src import monitoring
    mine = [(monitoring._event_time_span_listeners, telemetry._on_jax_span),
            (monitoring._scalar_listeners, telemetry._on_jax_span_begin),
            (monitoring._event_listeners, telemetry._on_jax_cache_event),
            (monitoring._event_duration_secs_listeners,
             telemetry._on_jax_cache_retrieval)]
    assert telemetry.install_jax_listeners()
    assert telemetry.install_jax_listeners()
    for registered, listener in mine:
        assert registered.count(listener) == 1, listener


_CACHE_RUN = """
import json, sys
import jax, jax.numpy as jnp
from ray_tpu._private import telemetry
assert telemetry.install_jax_listeners()
if sys.argv[1]:
    jax.config.update("jax_compilation_cache_dir", sys.argv[1])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

def cached_or_not(x):
    return jnp.cos(x) @ x

for _ in range(2):
    jax.jit(cached_or_not)(jnp.ones((4, 4))).block_until_ready()
    jax.clear_caches()
hists = telemetry.snapshot_local()["hists"]
print(json.dumps({
    "compiles": {dict(tags)["cache"]: h["count"]
                 for (name, tags), h in hists.items()
                 if name == "rtpu_jax_compile_seconds"
                 and dict(tags)["fun"] == "cached_or_not"
                 and dict(tags)["stage"] == "backend_compile"},
    "retrievals": sum(h["count"] for (name, _), h in hists.items()
                      if name == "rtpu_jax_cache_retrieval_seconds")}))
"""


@pytest.mark.parametrize("cache_dir, compiles, retrieved", [
    (True, {"miss": 1, "hit": 1}, True), (False, {"off": 2}, False)])
def test_the_persistent_caches_verdict_is_the_cache_tag(
        tmp_path, cache_dir, compiles, retrieved):
    """Against a cache directory of its own the first compile is written
    (`miss`) and, the in-memory caches cleared, the second is read back
    (`hit`, with its retrieval timed); without one both are `off`."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    done = subprocess.run(
        [sys.executable, "-c", _CACHE_RUN,
         str(tmp_path) if cache_dir else ""],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr[-2000:]
    said = json.loads(done.stdout.splitlines()[-1])
    assert said["compiles"] == compiles
    assert (said["retrievals"] >= 1) == retrieved


# --------------------------------------------------------------- the rows

def test_an_outermost_span_is_a_row_under_the_open_span():
    assert not tracing.enabled()
    tracing.drain()
    with tracing.start_span("task::compiles", force=True) as parent:
        jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
        _span(BACKEND, 300.0, 302.5, "jit(row_outer)")
        _span(TRACE, 303.0, 304.0, "row_traced", inside=[
            (TRACE, 303.2, 303.4, "row_nested")])
    rows = {s["name"]: s for s in tracing.drain()
            if s["name"].startswith("jax::")}
    assert set(rows) == {"jax::backend_compile", "jax::trace"}
    compiled = rows["jax::backend_compile"]
    assert (compiled["start_time"], compiled["end_time"]) == (300.0, 302.5)
    assert compiled["attributes"] == {"fun": "row_outer", "cache": "hit"}
    assert compiled["parent_id"] == parent["span_id"]
    assert compiled["trace_id"] == parent["trace_id"]
    # the nested trace is in its parent's row, and in the series
    assert rows["jax::trace"]["attributes"] == {"fun": "row_traced"}
    assert _rows("row_nested")[("trace", "row_nested", None)][0] == 1


def test_tracing_off_and_no_span_open_is_no_row():
    assert not tracing.enabled()
    tracing.drain()
    _span(BACKEND, 400.0, 401.0, "jit(row_untraced)")
    tracing.record_span("jax::trace", 1.0, 2.0, {"fun": "by_hand"})
    assert tracing.drain() == []
    assert _rows("row_untraced")[
        ("backend_compile", "row_untraced", "off")] == (
            1, pytest.approx(1.0))


def test_a_finished_span_keeps_the_times_it_is_given():
    tracing.drain()
    with tracing.start_span("task::outer", force=True) as parent:
        tracing.record_span("jax::lower", 10.0, 12.0, {"fun": "f"})
    row, outer = tracing.drain()
    assert row["name"] == "jax::lower" and outer["name"] == "task::outer"
    assert (row["start_time"], row["end_time"]) == (10.0, 12.0)
    assert row["parent_id"] == parent["span_id"]
    assert row["status"] == "OK" and row["pid"] == os.getpid()


# ------------------------------------- what is gone, and what stays away

@pytest.mark.parametrize("name", ["M_JAX_COMPILES",
                                  "_install_jax_compile_listener"])
def test_the_counter_of_cache_requests_is_gone(name):
    assert not hasattr(telemetry, name)
    assert "rtpu_jax_compiles_total" not in telemetry.snapshot_local()["meta"]


def test_a_process_without_jax_installs_nothing_and_imports_none():
    code = (
        "import sys\n"
        "from ray_tpu._private import telemetry\n"
        "assert telemetry.install_jax_listeners() is False\n"
        "telemetry.sample_devices()\n"
        "assert 'jax' not in sys.modules, 'jax was imported'\n")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    done = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr[-2000:]


def test_a_jax_still_being_imported_is_left_alone(monkeypatch):
    """A thread that touches jax while another is inside `import jax`
    breaks that import: the install waits for a whole module."""
    monkeypatch.setattr(telemetry, "_jax_listeners_installed", False)
    monkeypatch.setattr(jax.__spec__, "_initializing", True, raising=False)
    assert telemetry.install_jax_listeners() is False
    assert telemetry._jax_listeners_installed is False


# -------------------- the jax surfaces the runtime leans on (ROADMAP D12)

def test_the_backend_table_and_its_lock_are_where_the_check_reads_them():
    from jax._src import xla_bridge
    assert isinstance(xla_bridge._backends, dict)
    assert hasattr(xla_bridge._backend_lock, "acquire")
    assert hasattr(xla_bridge._backend_lock, "release")
    jax.devices()
    assert xla_bridge._backends
    assert accelerators.jax_backend_initialized()


def test_the_installed_jax_emits_the_events_the_listeners_match(tmp_path):
    """The three stages as time spans and, at their start, as scalars, each
    with the keyword `fun_name`; the cache's verdicts as plain events and
    its retrieval as a duration (read in a process with a cache directory
    of its own)."""
    spans, scalars = [], []

    def on_span(event, start_time, end_time, **kw):
        spans.append((event, tuple(sorted(kw))))

    def on_scalar(event, value, **kw):
        scalars.append((event, tuple(sorted(kw))))

    jax.monitoring.register_event_time_span_listener(on_span)
    jax.monitoring.register_scalar_listener(on_scalar)
    try:
        jax.jit(lambda x: x - 3)(jnp.ones(5)).block_until_ready()
    finally:
        jax.monitoring.unregister_event_time_span_listener(on_span)
        jax.monitoring.unregister_scalar_listener(on_scalar)
    wanted = {(event, ("fun_name",))
              for event in telemetry.JAX_STAGE_EVENTS}
    assert wanted <= set(spans)
    assert wanted <= set(scalars)

    code = (
        "import sys, jax, jax.numpy as jnp\n"
        "jax.config.update('jax_compilation_cache_dir', sys.argv[1])\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "jax.config.update('jax_persistent_cache_min_entry_size_bytes', -1)\n"
        "jax.monitoring.register_event_listener(\n"
        "    lambda event, **kw: print('event', event))\n"
        "jax.monitoring.register_event_duration_secs_listener(\n"
        "    lambda event, secs, **kw: print('duration', event))\n"
        "for _ in range(2):\n"
        "    jax.jit(lambda x: x * 7)(jnp.ones(3)).block_until_ready()\n"
        "    jax.clear_caches()\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run([sys.executable, "-c", code, str(tmp_path)],
                          cwd=ROOT, env=env, capture_output=True, text=True,
                          timeout=120)
    assert done.returncode == 0, done.stderr[-2000:]
    said = set(done.stdout.splitlines())
    assert {"event " + e for e in telemetry.JAX_CACHE_EVENTS} <= said
    assert "duration " + telemetry.JAX_CACHE_RETRIEVAL_EVENT in said
