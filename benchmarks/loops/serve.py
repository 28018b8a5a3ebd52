"""Traffic kind `serve`: scoring requests through `ray_tpu.serve`.

Two halves, as in `loops/train.py`. `run()` is the driver and the client: it
starts the runtime without opening a JAX backend, deploys `Scorer` with
`serve.run` as one replica that holds the cell's chips (`ray_actor_options=
{"num_tpus": chips}`), waits until the replica has made its seeded weights
and compiled every bucket, warms the request path, and then offers load in
an open loop for `--seconds`: requests leave on the schedule
(`arrivals.schedule`) whether or not earlier ones have returned, and a
request's latency runs from the moment the schedule says it was due, so a
client that is late itself counts as latency (`serve_send_lag_ms`).

`Scorer` is "user code", the deployment class a competent user would write,
and runs inside the serve replica — the only process that holds the chips.
A request is one document of token ids (an int32 array of L ids); the answer
is the log-probability of each of its tokens 1..L-1 given the tokens before
it (L-1 float32 values) and, beside them, where and when the replica
handled it (the bucket, and the clock at arrival, at the batch's start and
at its end: what `serve_ingress_ms`, `serve_queue_ms` and `serve_reply_ms`
are read from). `@serve.batch` collects concurrent requests; a collected
batch is sorted by length, cut into groups by length bucket and each group
padded to a bucket of rows x length, every one of which was compiled before
the window. Rows are padded on the right: under the causal mask of the flash
forward kernel (`ops/attention.py`, the kernel the training cells run) no
real position sees a padded one, and no row sees another, so a document's
scores depend on nothing but the document.

What differs between models is named by the served group of the
configuration, `configs/<config>.serve.json` (beside the configuration's
file, which the training cells keep byte for byte): `dtype` the weights are
held in, `reference.module` (`token_logprobs(tokens, top, layers, config,
operands)`), `reference.glue`, `work.module` (`forward_flops(model,
lengths)`, `flash_forward_work(model, lengths)`), and the limits with their readings.

What `correct` compares (`serve_check.py`): a sample, drawn from the seed,
of the answers the window itself returned — every bucket that ran, burst
and quiet stretches, the longest document among them — against the plain
float32 reference of each document alone, run in the replica after the
window has closed, the peak has been read and the deployment's weights and
programs are freed, on seeded weights made again and rounded to `dtype`.

What a loop module must provide (see README.md): `run(cell, *, seed,
seconds, trace, process_start_wall, rehearsal, say) -> dict`.
"""

from __future__ import annotations

import gc
import glob
import importlib
import json
import os
import shutil
import statistics
import sys
import time
import urllib.request
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from benchmarks import arrivals, cells, serve_check, spans as spans_mod

DEPLOYMENT = "scorer"
STEP_MODULE = "score_bucket"    # in every bucket program's name in the trace
KEEP_TRACE_ENV = "BENCH_KEEP_TRACE_DIR"
REFERENCE_PAD = 64      # the reference's row: the document, then zeros to a
#                         multiple of this (see `_reference_scores`)


def served_group(cell_root: str, paths: Sequence[str], config_name: str
                 ) -> Dict[str, Any]:
    """`configs/<config>.serve.json`: what a served deployment of the
    configuration names."""
    return cells.load_json(cells._find(cell_root, list(paths), "configs",
                                       config_name + ".serve.json"))


def loop_config(cell: cells.Cell, served: Dict[str, Any],
                traffic: Dict[str, Any], seed: int, platform: str,
                patch: Optional[str] = None) -> Dict[str, Any]:
    """What `Scorer` is built from, in the replica."""
    return {"config": cell.config, "served": served, "traffic": traffic,
            "chips": cell.chips, "platform": platform, "seed": seed,
            "root": cell.root, "paths": cell.paths, "patch": patch,
            "run_called_wall": time.time()}


def bucket_for(n: int, sizes: Sequence[int]) -> int:
    """The smallest of `sizes` (ascending) that holds `n`."""
    for size in sizes:
        if n <= size:
            return size
    raise ValueError(f"{n} fits none of {list(sizes)}")


def plan_groups(lengths: Sequence[int], rows: Sequence[int],
                widths: Sequence[int]) -> List[Tuple[int, int, List[int]]]:
    """A collected batch as device calls: the documents sorted by length,
    cut by length bucket, each group cut into calls of at most the largest
    row bucket and padded up to a row bucket. Returns (rows, length, the
    documents' indices in the batch) a call."""
    by_width: Dict[int, List[int]] = {}
    for i in sorted(range(len(lengths)), key=lambda i: lengths[i]):
        by_width.setdefault(bucket_for(lengths[i], widths), []).append(i)
    calls = []
    for width, members in by_width.items():
        for lo in range(0, len(members), rows[-1]):
            part = members[lo:lo + rows[-1]]
            calls.append((bucket_for(len(part), rows), width, part))
    return calls


# ------------------------------------------------------------ replica side

def _dtype(name: str):
    import jax.numpy as jnp
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[name]


class Scorer:
    """The deployment: the configuration's model at its widths and depth,
    weights in the served `dtype`, one compiled program a bucket."""

    def __init__(self, cfg: Dict[str, Any]):
        t_first_line = time.time()
        if cfg.get("patch"):
            # tests only (`rehearsal`): break the program underneath, here
            # in the replica, before anything of it is imported by name
            module, _, name = cfg["patch"].partition(":")
            getattr(importlib.import_module(module), name)()
        import jax
        import jax.numpy as jnp
        import ray_tpu
        from ray_tpu import serve
        from ray_tpu.models.gpt import GPT, GPTConfig

        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        self.compiles: List[float] = []
        jax.monitoring.register_event_duration_secs_listener(
            lambda event, secs, **kw: self.compiles.append(secs)
            if event.endswith("backend_compile_duration") else None)
        self.cfg, self.jax = cfg, jax
        self.log = spans_mod.SpanLog()
        devices = jax.devices()
        self.facts: Dict[str, Any] = {
            "pid": os.getpid(), "platform": devices[0].platform,
            "device_kind": devices[0].device_kind, "count": len(devices),
            # (None in a process that is no worker: the readings script)
            "accelerator_ids":
                ray_tpu.get_runtime_context().get_accelerator_ids()["TPU"]
                if ray_tpu.is_initialized() else None,
            "replica_start_s": t_first_line - cfg["run_called_wall"],
            "backend_init_s": time.time() - t_first_line,
            "compile_cache_dir": os.environ.get("JAX_COMPILATION_CACHE_DIR")}
        if (devices[0].platform != cfg["platform"]
                or len(devices) != cfg["chips"]):
            raise RuntimeError(
                f"the replica's JAX found {len(devices)} x "
                f"{devices[0].platform}, the cell needs {cfg['chips']} x "
                f"{cfg['platform']}")

        # ---- weights, on the device, in one jitted call from the seed, in
        # the type they are served in
        t = time.perf_counter()
        served, batching = cfg["served"], cfg["traffic"]["batching"]
        kw = dict(cfg["config"]["model"])
        kw["dtype"] = _dtype(kw["dtype"])
        kw["param_dtype"] = _dtype(kw["param_dtype"])
        self.model = GPT(GPTConfig(**kw))
        held = _dtype(served["dtype"])
        self.init_params = jax.jit(lambda key: jax.tree_util.tree_map(
            lambda a: a.astype(held), self.model.init(key)))
        self.params = self.init_params(jax.random.PRNGKey(cfg["seed"]))
        jax.block_until_ready(self.params)
        self.facts["weights_s"] = time.perf_counter() - t
        self.facts["weight_bytes"] = sum(
            leaf.nbytes for leaf in jax.tree_util.tree_leaves(self.params))

        # ---- one program a bucket, compiled (or loaded) and run once
        t = time.perf_counter()
        model = self.model

        def score_bucket(params, tokens):
            logits = model.apply(params, tokens)[:, :-1]
            at_target = jnp.take_along_axis(
                logits, tokens[:, 1:, None], axis=-1)[..., 0]
            return at_target - jax.nn.logsumexp(logits, axis=-1)

        self.rows = sorted(int(r) for r in batching["rows"])
        self.widths = sorted(int(w) for w in batching["lengths"])
        self.programs: Dict[Tuple[int, int], Any] = {}
        program_bytes, hlo_calls = 0, 0
        for rows in self.rows:
            for width in self.widths:
                lowered = jax.jit(score_bucket).lower(
                    self.params,
                    jax.ShapeDtypeStruct((rows, width), jnp.int32))
                compiled = lowered.compile()
                mem = compiled.memory_analysis()
                program_bytes = max(program_bytes, (
                    mem.temp_size_in_bytes + mem.argument_size_in_bytes
                    + mem.output_size_in_bytes - mem.alias_size_in_bytes))
                if (rows, width) == (self.rows[-1], self.widths[-1]):
                    hlo_calls = lowered.as_text().count("tpu_custom_call")
                compiled(self.params, np.zeros((rows, width), np.int32)
                         ).block_until_ready()
                self.programs[rows, width] = compiled
        self.facts.update(
            programs_s=time.perf_counter() - t, programs=len(self.programs),
            program_bytes=program_bytes, pallas_custom_calls=hlo_calls,
            compile_seconds=list(self.compiles),
            init_s=time.time() - t_first_line)
        self.batches: List[Dict[str, Any]] = []
        self._collect: Tuple[Any, Optional[int]] = (None, None)
        self._submit = serve.batch(
            max_batch_size=int(batching["max_batch_size"]),
            batch_wait_timeout_s=float(batching["batch_wait_timeout_s"])
        )(Scorer._score_batch)

    # -- the request path

    def __call__(self, request):
        received = time.time()
        if isinstance(request, dict) and "control" in request:
            return getattr(self, "_control_" + request["control"])(request)
        as_json = isinstance(request, dict)     # through the HTTP proxy
        doc = (np.asarray(request["tokens"], np.int32) if as_json
               else request)
        answer = self._submit(self, doc)
        answer = dict(answer, received=received)
        if as_json:
            answer["logprobs"] = answer["logprobs"].tolist()
        return answer

    def _collecting(self, now_open: bool) -> None:
        """The stretch between two batches on the collector's thread
        (`serve/batching.py::_loop`: the futures resolved, the next batch
        collected), as a span `batcher_collect`."""
        if not now_open:
            note, t0 = self._collect
            if t0 is not None:
                self.log.rows.append(("batcher_collect", t0,
                                      time.perf_counter_ns()))
                if note is not None:
                    note.__exit__(None, None, None)
            self._collect = (None, None)
            return
        note = None
        if self.log.annotate:
            note = self.jax.profiler.TraceAnnotation(
                spans_mod.PREFIX + "batcher_collect")
            note.__enter__()
        self._collect = (note, time.perf_counter_ns())

    def _score_batch(self, docs: List[np.ndarray]) -> List[Dict[str, Any]]:
        fired = time.time()
        self._collecting(False)
        with self.log.span("assemble"):
            calls = plan_groups([len(d) for d in docs], self.rows,
                                self.widths)
            padded = []
            for rows, width, members in calls:
                tokens = np.zeros((rows, width), np.int32)
                for row, i in enumerate(members):
                    tokens[row, :len(docs[i])] = docs[i]
                padded.append(tokens)
        with self.log.span("enqueue"):
            outs = [self.programs[rows, width](self.params, tokens)
                    for (rows, width, _), tokens in zip(calls, padded)]
        with self.log.span("device_wait"):
            outs = [np.asarray(out) for out in outs]
        done = time.time()
        with self.log.span("split"):
            answers: List[Any] = [None] * len(docs)
            for (rows, width, members), out in zip(calls, outs):
                for row, i in enumerate(members):
                    answers[i] = {
                        "logprobs": out[row, :len(docs[i]) - 1].copy(),
                        "bucket": (rows, width), "fired": fired,
                        "done": done}
        self.batches.append({
            "fired": fired, "done": done, "exit": time.time(),
            "requests": len(docs),
            "calls": [(rows, width, [len(docs[i]) for i in members])
                      for rows, width, members in calls]})
        self._collecting(True)
        return answers

    # -- what the driver asks of the replica besides scores

    def _control_facts(self, request):
        return self.facts

    def _control_window_log(self, request):
        """Every batch fired since the last call, the spans' summary, the
        compiles so far and the device's memory."""
        batches, self.batches = self.batches, []
        memory = [d.memory_stats() or {} for d in self.jax.devices()]
        return {"batches": batches,
                "spans": self.log.summary(
                    int(request.get("since_ns", 0)),
                    int(request.get("until_ns", 2 ** 63))),
                "now_ns": time.perf_counter_ns(),
                "compiles": len(self.compiles),
                "peak_bytes_in_use": [m.get("peak_bytes_in_use")
                                      for m in memory],
                "bytes_limit": [m.get("bytes_limit") for m in memory]}

    def _control_trace_start(self, request):
        jax = self.jax
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        self._trace_dir = request["dir"]
        self.batches = []       # the log of the traced stretch starts here
        self._trace_t0 = time.perf_counter()
        jax.profiler.start_trace(self._trace_dir, profiler_options=options)
        self.log.annotate = True
        return True

    def _control_trace_stop(self, request):
        from benchmarks import trace_reduce
        self.log.annotate = False
        self.jax.profiler.stop_trace()
        t_reduce = time.perf_counter()
        found = sorted(glob.glob(os.path.join(
            self._trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
        reduced, error = None, None
        try:
            if found:
                reduced = trace_reduce.reduce_file(found[-1], STEP_MODULE)
        except Exception as e:      # noqa: BLE001 — reported, run goes on
            error = repr(e)
        keep = os.environ.get(KEEP_TRACE_ENV)
        if keep and found:
            os.makedirs(keep, exist_ok=True)
            shutil.copy(found[-1], keep)
        return {"reduced": reduced, "error": error,
                "xplane_bytes": os.path.getsize(found[-1]) if found else 0,
                "trace_s": t_reduce - self._trace_t0,
                "reduce_s": time.perf_counter() - t_reduce}

    def _control_reference(self, request):
        """After the window: the deployment's weights and programs go, the
        seeded weights are made again, and the plain reference scores each
        sampled document alone."""
        t0 = time.perf_counter()
        self.params = None
        self.programs.clear()
        self.jax.clear_caches()
        gc.collect()
        scores = _reference_scores(
            self.cfg, self.init_params, request["docs"],
            request.get("operands"))
        return {"scores": scores,
                "reference_check_s": time.perf_counter() - t0}


def _reference_scores(cfg, init_params, docs, operands=None):
    """[log-probabilities, float32 [L-1]] a document, by the configuration's
    plain reference on the seeded weights in the served type, through the
    glue. Each document alone in its row; the row is the document followed
    by zeros up to a multiple of `REFERENCE_PAD` tokens, so that a run's
    sample compiles a handful of shapes and not one a length — the
    reference's own causal mask keeps every real position from seeing them
    (`tests/test_serve_loop.py` holds the reference to that)."""
    import jax
    import jax.numpy as jnp
    group = cfg["served"]["reference"]
    reference = cells.module(cfg["root"], cfg["paths"], group["module"])
    glue = cells.module(cfg["root"], cfg["paths"], group["glue"])
    devices = jax.devices()
    top, layers = glue.reference_weights(
        init_params(jax.random.PRNGKey(cfg["seed"])), None, devices)
    layers = list(layers)
    kind = {None: None, "float8_e4m3fn": jnp.float8_e4m3fn,
            "bfloat16": jnp.bfloat16}[operands]
    limit = int(cfg["config"]["model"]["max_seq_len"])
    out = []
    for doc in docs:
        n = len(doc)
        width = min(-(-n // REFERENCE_PAD) * REFERENCE_PAD, limit)
        row = np.zeros((1, width), np.int32)
        row[0, :n] = doc
        scores = reference.token_logprobs(
            jnp.asarray(row), top, layers, cfg["config"], kind)
        out.append(np.asarray(scores)[0, :n - 1])
    return out


# -------------------------------------------------------------- client side

class _Client:
    """The client: the caller's thread submits each request through the
    handle at its due moment (`handle.remote`, which does not wait) and asks
    the answer's `ObjectRef` for a future; the runtime's reply thread
    resolves it, and the callback stamps the return. No thread of the client
    waits on a request — a pool of threads blocked in `get` made the
    sender itself seconds late under load (PERF.md section 6, PR 44) — so
    the number of requests in flight is the system's business and not the
    client's. A request's record: due, sent and returned on this process's
    clock, and the answer or the error."""

    def __init__(self, handle, docs: List[np.ndarray]):
        self.handle, self.docs = handle, docs
        self.records: Dict[int, Dict[str, Any]] = {}

    def _returned(self, index: int, record: Dict[str, Any], future) -> None:
        record["returned"] = time.time()
        try:
            record["answer"] = future.result()
        except Exception as e:      # noqa: BLE001 — a failed request
            record["error"] = repr(e)
        self.records[index] = record

    def send(self, index: int, due: float) -> None:
        record = {"due": due, "sent": time.time()}
        try:
            future = self.handle.remote(self.docs[index]).ref.future()
        except Exception as e:      # noqa: BLE001 — a failed request
            record.update(error=repr(e), returned=time.time())
            self.records[index] = record
            return
        future.add_done_callback(
            lambda f: self._returned(index, record, f))

    def offer(self, t0: float, send_s: np.ndarray, first: int) -> None:
        """Open loop: request `first + k` leaves at `t0 + send_s[k]`."""
        for k, at in enumerate(send_s):
            delay = t0 + at - time.time()
            if delay > 0:
                time.sleep(delay)
            self.send(first + k, t0 + at)

    def wait_for(self, count: int, until: float) -> None:
        while len(self.records) < count and time.time() < until:
            time.sleep(0.01)


def _http_sender(http_address: str):
    def by_http(doc, timeout_s):
        body = json.dumps({"tokens": doc.tolist()}).encode()
        request = urllib.request.Request(
            f"{http_address}/{DEPLOYMENT}", data=body, method="POST")
        with urllib.request.urlopen(request, timeout=timeout_s) as reply:
            answer = json.loads(reply.read())["result"]
        answer["logprobs"] = np.asarray(answer["logprobs"], np.float32)
        answer["bucket"] = tuple(answer["bucket"])
        return answer
    return by_http


def proxy_probe(handle, by_http, doc: np.ndarray, pairs: int,
                timeout_s: float) -> Dict[str, List[float]]:
    """What the HTTP proxy adds to a request of an otherwise idle system:
    the same document `pairs` times through the handle and through the
    per-node proxy, in turn, one request at a time; milliseconds each took
    (`serve_proxy_ms`)."""
    took: Dict[str, List[float]] = {"handle": [], "http": []}
    for _ in range(pairs):
        t = time.perf_counter()
        handle.remote(doc).result(timeout=timeout_s)
        took["handle"].append(1e3 * (time.perf_counter() - t))
        t = time.perf_counter()
        by_http(doc, timeout_s)
        took["http"].append(1e3 * (time.perf_counter() - t))
    return took


def request_rows(records: Dict[int, Dict[str, Any]], lengths: np.ndarray,
                 first: int, count: int, deadline_s: float
                 ) -> List[Dict[str, Any]]:
    """One row a request `first .. first + count - 1`: latency from the due
    moment (a request past the deadline, failed or unanswered counts at the
    deadline and as failed), and the stages of those that were answered."""
    rows = []
    for index in range(first, first + count):
        r = records.get(index)
        row = {"index": index, "length": int(lengths[index]),
               "due": None, "ok": False, "latency_s": deadline_s}
        if r is not None:
            latency = r["returned"] - r["due"]
            answer = r.get("answer")
            row.update(due=r["due"], send_lag_s=r["sent"] - r["due"],
                       returned=r["returned"], error=r.get("error"))
            if answer is not None:
                row.update(
                    bucket=tuple(answer["bucket"]),
                    ingress_s=answer["received"] - r["sent"],
                    queue_s=answer["fired"] - answer["received"],
                    batch_s=answer["done"] - answer["fired"],
                    reply_s=r["returned"] - answer["done"])
                if latency <= deadline_s:
                    row.update(ok=True, latency_s=latency)
        rows.append(row)
    return rows


def window_numbers(rows: List[Dict[str, Any]], batches: List[Dict[str, Any]],
                   t0: float, seconds: float, chips: int, work, model
                   ) -> Dict[str, Any]:
    """What the window's metrics are read from: the tails over every request
    due in the window, the real tokens of those answered inside it, the
    stages' medians, and the batches fired inside it."""
    close = t0 + seconds
    latencies = [r["latency_s"] for r in rows]
    inside = [r for r in rows if r["ok"] and r["returned"] <= close]
    handled = [r for r in rows if r.get("bucket")]
    fired = [b for b in batches if t0 <= b["fired"] < close]
    real = sum(sum(lengths) for b in fired for _, _, lengths in b["calls"])
    padded = sum(r * w for b in fired for r, w, _ in b["calls"])

    def median_ms(group, key):
        values = [r[key] for r in group if key in r]
        return 1e3 * statistics.median(values) if values else None

    answered_lengths = [r["length"] for r in inside]
    return {
        "seconds": seconds, "requests": len(rows),
        "answered_in_window": len(inside),
        "failed": sum(1 for r in rows if not r["ok"]),
        "latency_p50_ms": 1e3 * arrivals.percentile(latencies, 50),
        "latency_p95_ms": 1e3 * arrivals.percentile(latencies, 95),
        "latency_p99_ms": 1e3 * arrivals.percentile(latencies, 99),
        "latency_max_ms": 1e3 * max(latencies),
        "tokens_answered": int(sum(answered_lengths)),
        "serve_tokens_per_s_per_chip":
            sum(answered_lengths) / seconds / chips,
        "forward_flops_answered": work.forward_flops(model,
                                                     answered_lengths),
        "send_lag_p99_ms": 1e3 * arrivals.percentile(
            [r["send_lag_s"] for r in rows if "send_lag_s" in r] or [0.0],
            99),
        "ingress_ms": median_ms(handled, "ingress_s"),
        "queue_ms": median_ms(handled, "queue_s"),
        "batch_ms": median_ms(handled, "batch_s"),
        "reply_ms": median_ms(handled, "reply_s"),
        "batches": len(fired),
        "batch_requests_mean": (statistics.fmean(b["requests"]
                                                 for b in fired)
                                if fired else None),
        "device_calls": sum(len(b["calls"]) for b in fired),
        "real_tokens_fired": real, "padded_tokens_fired": padded,
        "buckets_run": sorted({(r, w) for b in fired
                               for r, w, _ in b["calls"]}),
    }


def pick_sample(rows: List[Dict[str, Any]], traffic: Dict[str, Any],
                t0: float, seed: int) -> List[int]:
    """The requests whose answers are held to the reference: drawn from the
    seed among those the window answered — `per_bucket` of every bucket
    that ran, the longest document, and then, alternately from burst and
    quiet stretches, up to `sample` in all."""
    check = traffic["check"]
    rng = np.random.default_rng([int(seed), 0x5A3F])
    answered = [r for r in rows if r.get("bucket") and not r.get("error")]
    if not answered:
        return []
    order = [answered[i] for i in rng.permutation(len(answered))]
    chosen: Dict[int, None] = {}
    longest = max(answered, key=lambda r: (r["length"], -r["index"]))
    chosen[longest["index"]] = None
    by_bucket: Dict[Any, int] = {}
    for r in order:
        if by_bucket.get(r["bucket"], 0) < int(check["per_bucket"]):
            by_bucket[r["bucket"]] = by_bucket.get(r["bucket"], 0) + 1
            chosen[r["index"]] = None

    def in_burst(r):
        return arrivals.rate_at(traffic["arrivals"], r["due"] - t0) > float(
            traffic["arrivals"]["rate_per_s"])

    strata = [[r for r in order if in_burst(r)],
              [r for r in order if not in_burst(r)]]
    turn = 0
    while len(chosen) < int(check["sample"]) and any(strata):
        stratum = strata[turn % 2] or strata[(turn + 1) % 2]
        chosen[stratum.pop()["index"]] = None
        turn += 1
    return sorted(chosen)


def run(cell: cells.Cell, *, seed: int, seconds: float, trace: bool,
        process_start_wall: float, rehearsal: Optional[Dict[str, Any]],
        say) -> Dict[str, Any]:
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu._private.accelerators import jax_backend_initialized

    config, traffic = cell.config, cell.traffic
    served = served_group(cell.root, cell.paths, cell.config_name)
    work = cells.module(cell.root, cell.paths, served["work"]["module"])
    platform = "cpu" if rehearsal else "tpu"
    peaks_table = cells.load_json(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "peaks.json"))
    problems: List[str] = []
    compared: List[List[Any]] = []      # [name, what, value, limit]

    t0 = time.perf_counter()
    if rehearsal:
        ray_tpu.init(num_cpus=4, num_tpus=rehearsal["num_tpus"])
    else:
        ray_tpu.init()          # the chips are detected, never declared
    try:
        advertised = ray_tpu.cluster_resources().get("TPU", 0)
        say(kind="cluster", tpu=advertised, cell=cell.name,
            init_s=time.perf_counter() - t0)
        if advertised < cell.chips:
            raise cells.NoResult(
                f"this machine offers {advertised} TPU chip(s), the cell "
                f"{cell.name} needs {cell.chips}")
        storage = os.path.join(cell.root, cells.RUNS_DIR, cell.name)
        shutil.rmtree(storage, ignore_errors=True)
        os.makedirs(storage)

        # ---- traffic: the schedule and the documents, from the seed
        t1 = time.perf_counter()
        warm = traffic["warmup"]
        trace_s = float(traffic["trace_seconds"]) if trace else 0.0
        plan = arrivals.schedule(traffic, seconds + trace_s, seed)
        send_s, lengths = plan["send_s"], plan["lengths"]
        in_window = int(np.searchsorted(send_s, seconds, side="left"))
        # the warm-up's documents: the pool's own, after the run's
        warm_lengths = np.concatenate([
            np.asarray(traffic["batching"]["lengths"], np.int64),
            np.resize(lengths, int(warm["requests"]))])
        all_lengths = np.concatenate([lengths, warm_lengths])
        docs = arrivals.documents(traffic, all_lengths, seed)
        say(kind="traffic", requests_in_window=in_window,
            traced_requests=len(send_s) - in_window,
            mean_length=float(lengths[:in_window].mean()),
            offered_per_s=in_window / seconds,
            make_s=time.perf_counter() - t1)

        # ---- the system: one replica that holds the chips
        deployment = serve.deployment(
            Scorer, name=DEPLOYMENT,
            ray_actor_options={"num_tpus": cell.chips},
            max_concurrent_queries=int(traffic["max_concurrent_queries"]))
        handle = serve.run(deployment.bind(loop_config(
            cell, served, traffic, seed, platform,
            (rehearsal or {}).get("patch"))))
        worker = handle.remote({"control": "facts"}).result(
            timeout=float(traffic["ready_timeout_s"]))
        say(kind="worker", **worker)

        # ---- warm the request path: one document a length bucket, then a
        # volley through the client pool (router, replica threads, batcher)
        deadline_s = float(traffic["deadline_ms"]) / 1e3
        client = _Client(handle, docs)
        base = len(lengths)
        limit = time.time() + float(traffic["ready_timeout_s"])
        for k in range(len(warm_lengths)):
            client.send(base + k, time.time())
            if k < len(traffic["batching"]["lengths"]):
                client.wait_for(k + 1, limit)      # one at a time
        client.wait_for(len(warm_lengths), limit)
        warm_errors = [r["error"] for r in client.records.values()
                       if "error" in r]
        if warm_errors or len(client.records) < len(warm_lengths):
            problems.append(f"the warm-up's requests failed: "
                            f"{warm_errors[:3]} ({len(client.records)} of "
                            f"{len(warm_lengths)} returned)")
        before = handle.remote({"control": "window_log"}).result(timeout=60)

        # ---- the window
        window_wall = time.time()
        client.offer(window_wall, send_s[:in_window], 0)
        closed = time.time()
        # every answer that is due: wait for each until its deadline
        client.wait_for(len(warm_lengths) + in_window,
                         closed + deadline_s + 1.0)
        after = handle.remote({
            "control": "window_log", "since_ns": before["now_ns"]}
        ).result(timeout=60)
        rows = request_rows(client.records, lengths, 0, in_window,
                            deadline_s)
        window = window_numbers(rows, after["batches"], window_wall, seconds,
                                cell.chips, work, config["model"])
        window.update(
            window_start_wall=window_wall, offer_s=closed - window_wall,
            drain_s=time.time() - closed,
            compiles_in_window=after["compiles"] - before["compiles"],
            peak_bytes_in_use=after["peak_bytes_in_use"],
            bytes_limit=after["bytes_limit"],
            program_bytes=worker["program_bytes"], spans=after["spans"])
        say(kind="window", **{k: v for k, v in window.items()
                              if k != "spans"}, spans=after["spans"])

        # ---- the traced stretch, after the window: the schedule goes on
        traced = None
        if trace:
            # the HTTP proxy sits in no timed request's way (it does not
            # sustain the rate: PERF.md); a traced run starts it once the
            # window has closed and sends `http_probes` pairs of one short
            # document through it and through the handle, so that its time
            # is told apart (`serve_proxy_ms`)
            probed = None
            if int(traffic.get("http_probes") or 0):
                addresses = serve.start(proxy_location="EveryNode")
                by_http = _http_sender(next(iter(addresses.values())))
                by_http(docs[base], deadline_s + 5.0)
                probed = proxy_probe(handle, by_http, docs[base],
                                     int(traffic["http_probes"]),
                                     deadline_s + 5.0)
            trace_dir = os.path.join(storage, "trace")
            handle.remote({"control": "trace_start", "dir": trace_dir}
                          ).result(timeout=120)
            trace_wall = time.time()
            client.offer(trace_wall, send_s[in_window:] - seconds,
                          in_window)
            client.wait_for(len(warm_lengths) + len(send_s),
                             time.time() + deadline_s + 1.0)
            traced = handle.remote({"control": "trace_stop"}).result(
                timeout=300)
            traced["log"] = handle.remote({"control": "window_log"}).result(
                timeout=60)
            traced["proxy_probe_ms"] = probed
            say(kind="trace", **{k: traced[k] for k in (
                "error", "xplane_bytes", "trace_s", "reduce_s")},
                traced_batches=len(traced["log"]["batches"]))

        # ---- the reference, after the window: the peak has been read; the
        # replica frees its weights and programs first
        sample = pick_sample(rows, traffic, window_wall, seed)
        answers = [client.records[i]["answer"]["logprobs"] for i in sample]
        followed = handle.remote({
            "control": "reference", "docs": [docs[i] for i in sample]}
        ).result(timeout=float(traffic["ready_timeout_s"]))
        window["reference_check_s"] = followed["reference_check_s"]
        say(kind="reference", sample=len(sample),
            reference_check_s=followed["reference_check_s"],
            buckets=sorted({rows[i]["bucket"] for i in sample}))

        # ---- what ran where
        device = {"platform": worker.get("platform"),
                  "kind": worker.get("device_kind"),
                  "count": worker.get("count", 0)}
        if worker.get("pid") == os.getpid():
            problems.append("the deployment ran in the driver's process")
        if device["platform"] != "tpu":
            problems.append(f"ran on {device['platform']!r}, not a TPU")
        peak = peaks_table.get(device["kind"])
        if peak is None:
            problems.append(f"no peaks on record for device kind "
                            f"{device['kind']!r} (peaks.json)")
        if device["count"] != cell.chips:
            problems.append(f"{device['count']} device(s), the cell has "
                            f"{cell.chips}")
        if window["compiles_in_window"]:
            problems.append(f"{window['compiles_in_window']} compilation(s) "
                            f"inside the window")
        if platform == "tpu" and not worker["pallas_custom_calls"]:
            problems.append("no tpu_custom_call in the largest bucket's "
                            "program: attention did not lower to the Pallas "
                            "kernel")
        if not window["answered_in_window"]:
            problems.append("no request was answered inside the window")
        limits = served["reference"]
        check_rows, check_problems = serve_check.compare(
            [docs[i] for i in sample], answers, followed["scores"], limits)
        problems.extend(check_problems)
        compared += check_rows
        if len(sample) < int(traffic["check"]["sample"]):
            problems.append(f"only {len(sample)} answered requests to "
                            f"compare, {traffic['check']['sample']} wanted")
        wrong_length = sum(
            1 for r in rows if r.get("bucket") and len(
                client.records[r["index"]]["answer"]["logprobs"])
            != r["length"] - 1)
        compared.append(["answers_of_wrong_length", "answers of the window "
                         "that do not hold L - 1 values for a document of L",
                         wrong_length, 0])
        if wrong_length:
            problems.append(f"{wrong_length} answer(s) do not hold L - 1 "
                            f"values")

        # ---- metrics
        setup_s = window_wall - process_start_wall
        end_to_end = {
            "latency_p99_ms": window["latency_p99_ms"],
            "serve_tokens_per_s_per_chip":
                window["serve_tokens_per_s_per_chip"],
            "setup_s": setup_s}
        run_facts = {
            "cell": {"name": cell.name, "chips": cell.chips,
                     "config": config, "traffic": traffic, "served": served},
            "peaks": peak, "device": device, "worker": worker,
            "window": window, "spans": window["spans"], "rows": rows,
            "setup_s": setup_s, "end_to_end": end_to_end,
            "trace": (traced or {}).get("reduced"), "traced": traced,
            "work": work,
        }
        wanted = cell.per_layer if trace else cell.end_to_end
        metrics: Dict[str, Dict[str, Any]] = {}
        for m in wanted:
            if trace:
                # `<reader>.<suffix>` names one reader's number twice, once
                # for each end-to-end metric it moves
                reader = m["name"].split(".")[0]
                value = cells.layer_reader(cell, reader)(run_facts)
            else:
                value = end_to_end.get(m["name"])
                if value is None:
                    problems.append(f"no value for {m['name']}")
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

        keep = os.environ.get(KEEP_TRACE_ENV)
        if trace and keep:
            # what the readers were handed, for `tests/fixtures/`
            from benchmarks import program_trace
            os.makedirs(keep, exist_ok=True)
            with open(os.path.join(keep, cell.name + ".run.json"), "w") as f:
                json.dump({"run": {k: v for k, v in run_facts.items()
                                   if k not in ("work", "rows")},
                           "program_trace": program_trace.of_run(run_facts)},
                          f, default=str)

        device["memory_peak_bytes"] = max(
            [window["program_bytes"]]
            + [b for b in window["peak_bytes_in_use"] if b])
        line: Dict[str, Any] = {
            "correct": not problems,
            "attempted": window["requests"], "failed": window["failed"],
            "metrics": metrics, "device": device}
        reduced = run_facts["trace"]
        if trace and reduced:
            from benchmarks import trace_reduce
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            line["breakdown"] = trace_reduce.breakdown(reduced)
            say(kind="trace_reduced",
                **{k: v for k, v in reduced.items() if k != "ops"},
                ops=reduced["ops"][:40])
        elif trace:
            problems.append("the traced stretch gave no device trace"
                            + (f": {traced['error']}" if traced else ""))
            line["correct"] = False
        if jax_backend_initialized():
            problems.append("the driver process opened a JAX backend")
            line["correct"] = False
        say(kind="verdict", problems=problems, compared=compared)
        line["compared"] = {name: {"value": value, "limit": limit}
                            for name, _, value, limit in compared}
        return line
    finally:
        try:
            serve.shutdown()
        except Exception:   # noqa: BLE001 — the runtime goes next
            pass
        ray_tpu.shutdown()
        shutil.rmtree(os.path.join(cell.root, cells.RUNS_DIR, cell.name),
                      ignore_errors=True)
        for name, what, value, limit in compared:
            print(f"compared: {name} = {value!r} (limit {limit!r}): {what}",
                  file=sys.stderr)
        for problem in problems:
            print(f"not correct: {problem}", file=sys.stderr)
