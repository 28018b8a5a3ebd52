"""@serve.batch — dynamic request batching inside a replica.

Reference: ``python/ray/serve/batching.py`` (``@serve.batch`` queues
concurrent calls, fires the underlying function once per batch).
Implementation: a per-function collector thread gathers requests until
``max_batch_size`` or ``batch_wait_timeout_s`` and invokes the wrapped
callable with the list; callers block on their slot's future. Works with
threaded actors (``max_concurrency > 1``) — concurrency is what creates
batchable simultaneous requests.

The collector times itself (ISSUE 55): its thread's life is tiled by four
spans — ``serve::batch_wait`` (no request to take), ``serve::batch_fill``
(first request taken -> batch closed), ``serve::batch_call`` (the wrapped
function) and ``serve::batch_resolve`` (the futures) — each also an
observation of ``rtpu_serve_batch_seconds{deployment, phase}``; a member's
own waits, into the batch and back out of it, are two digests.
"""

from __future__ import annotations

import functools
import queue as _queue
import threading
import time as _time

from .._private import locksan
from .._private import telemetry
from concurrent.futures import Future
from typing import Any, Callable, List, Optional

from ..util import tracing
from . import request_context as _rc

M_SERVE_BATCH_SIZE_DIGEST = telemetry.define(
    "digest", "rtpu_serve_batch_size_digest",
    "Streaming quantile digest of @serve.batch batch sizes per "
    "deployment (how well concurrent requests coalesce)")
M_SERVE_BATCH_SECONDS = telemetry.define(
    "histogram", "rtpu_serve_batch_seconds",
    "Seconds one phase of a @serve.batch collector's loop took, one "
    "observation a batch (phase=wait: blocked with no request to take; "
    "fill: first request taken -> batch closed; call: the wrapped "
    "function; resolve: the members' futures)",
    buckets=telemetry.SHORT_BUCKETS)
M_SERVE_BATCH_QUEUE = telemetry.define(
    "digest", "rtpu_serve_batch_queue_seconds",
    "Quantile digest of a request's wait in the @serve.batch queue: its "
    "submit -> its batch closed, one record a member")
M_SERVE_BATCH_WAKE = telemetry.define(
    "digest", "rtpu_serve_batch_wake_seconds",
    "Quantile digest of a batch member's way back: the collector's stamp "
    "before it resolves the batch's futures -> the member's thread "
    "returned from its future")

_PHASES = ("wait", "fill", "call", "resolve")


class _Batcher:
    def __init__(self, fn: Callable[[List[Any]], List[Any]],
                 max_batch_size: int, timeout_s: float):
        self.fn = fn
        self.max_batch_size = max_batch_size
        self.timeout_s = timeout_s
        self.q: "_queue.Queue" = _queue.Queue()
        self._thread: Optional[threading.Thread] = None
        self._lock = locksan.lock("serve.batcher")
        self._bind_series("default")

    def _bind_series(self, deployment: str) -> None:
        """The tags and prebound digests of this batcher's own series. A
        batcher serves one deployment for its life; which one it learns
        from the request context its members carry."""
        self._deployment = deployment
        self._phase_tags = {
            phase: (("deployment", deployment), ("phase", phase))
            for phase in _PHASES}
        self._queue_digest = telemetry.digest_series(
            M_SERVE_BATCH_QUEUE, (("deployment", deployment),))
        self._wake_digest = telemetry.digest_series(
            M_SERVE_BATCH_WAKE, (("deployment", deployment),))

    def _ensure_thread(self, meta: Optional[dict]):
        with self._lock:
            if self._thread is None or not self._thread.is_alive():
                if meta:    # before the collector's first wait opens
                    self._bind_series(meta.get("deployment", "default"))
                self._thread = threading.Thread(target=self._loop,
                                                daemon=True)
                self._thread.start()

    def _phase(self, phase: str):
        return tracing.timed_span("serve::batch_" + phase,
                                  M_SERVE_BATCH_SECONDS,
                                  self._phase_tags[phase])

    def _loop(self):
        while True:
            with self._phase("wait"):
                # (arg, future, req_meta, trace, submitted)
                item = self.q.get()
            with self._phase("fill"):
                t_first = _time.monotonic()
                batch = [item]
                # absolute deadline per batch: a fixed per-get timeout
                # would reset on every arrival, making the first caller
                # wait up to (max_batch_size-1)*timeout under a trickle
                # of requests
                deadline = t_first + self.timeout_s
                while len(batch) < self.max_batch_size:
                    remaining = deadline - _time.monotonic()
                    if remaining <= 0:
                        break
                    try:
                        batch.append(self.q.get(timeout=remaining))
                    except _queue.Empty:
                        break
            t_closed = _time.monotonic()
            args = [it[0] for it in batch]
            futures = [it[1] for it in batch]
            lead = next((it[2] for it in batch if it[2]), None)
            try:
                # accounting must never break the batch: an exception
                # here (thread exhaustion in a lazy flusher start,
                # interpreter teardown) would kill the collector with
                # every member's future unresolved — callers block on
                # fut.result() with no timeout
                deployment = (lead or {}).get("deployment", self._deployment)
                if deployment != self._deployment:
                    self._bind_series(deployment)
                self._note_batch(batch, t_first, t_closed)
            except Exception:   # noqa: BLE001 — observability only
                pass
            # bind the batch LEADER's request context around the user
            # function: one invocation serves N requests, so a single
            # id is inherently approximate, but get_request_id() inside
            # a batched body should name a member of THIS batch, not ""
            # (the per-member ids live in each access-log row)
            tok = _rc.bind(lead) if lead is not None else None
            results, error = None, None
            try:
                with self._phase("call"):
                    results = self.fn(args)
                if results is None or len(results) != len(args):
                    raise ValueError(
                        "@serve.batch function must return one result per "
                        f"input ({len(args)} inputs)")
            except Exception as e:
                error = e
            finally:
                if tok is not None:
                    _rc.unbind(tok)
            with self._phase("resolve"):
                # the members read the stamp back: their wake-up runs
                # from here (`submit`)
                stamp = _time.monotonic()
                for i, fut in enumerate(futures):
                    fut.resolving_at = stamp
                    if error is None:
                        fut.set_result(results[i])
                    else:
                        fut.set_exception(error)

    def _note_batch(self, batch, t_first: float, t_closed: float) -> None:
        """Accounting for one assembled batch: each member's wait in the
        queue; then, for the request plane, stamp each member request's
        batch size (the replica's access-log row reads it back), record
        the per-deployment batch-size digest, and emit one
        ``request::batch_assemble`` span parented to the first member's
        trace (span start = first arrival, end = invoke)."""
        for it in batch:
            telemetry.digest_record(self._queue_digest, t_closed - it[4])
        metas = [it[2] for it in batch if it[2]]
        if not metas:
            return                    # plane off / outside a request
        n = len(batch)
        for meta in metas:
            meta["batch_size"] = n
        deployment = metas[0].get("deployment", "default")
        telemetry.digest_observe(M_SERVE_BATCH_SIZE_DIGEST, float(n),
                                 (("deployment", deployment),))
        parent = next((it[3] for it in batch if it[3]), None)
        if parent is not None or tracing.enabled():
            span = tracing.begin_span(
                "request::" + "batch_assemble", parent,
                attributes={"deployment": deployment, "batch_size": n,
                            "request_id": metas[0].get("request_id")})
            wait = _time.monotonic() - t_first
            span["start_time"] = _time.time() - wait
            tracing.end_span(span)

    def submit(self, arg: Any) -> Any:
        fut: Future = Future()
        # carry the caller's request context + trace ctx to the
        # collector thread (contextvars/thread-locals don't cross)
        meta = _rc.current() if _rc.enabled() else None
        self._ensure_thread(meta)
        trace = tracing.get_current_context() if meta is not None else None
        self.q.put((arg, fut, meta, trace, _time.monotonic()))
        try:
            return fut.result()
        finally:
            stamp = getattr(fut, "resolving_at", None)
            if stamp is not None:   # None: thrown out of the wait itself
                telemetry.digest_record(self._wake_digest,
                                        _time.monotonic() - stamp)


def batch(_fn: Optional[Callable] = None, *, max_batch_size: int = 8,
          batch_wait_timeout_s: float = 0.01):
    """Decorate a method taking a LIST of requests; singular calls are
    coalesced into batches transparently."""

    def decorator(fn):
        attr = f"__rtpu_batcher_{fn.__name__}"

        @functools.wraps(fn)
        def wrapper(self, request):
            b = getattr(self, attr, None)
            if b is None:
                b = _Batcher(lambda args: fn(self, args), max_batch_size,
                             batch_wait_timeout_s)
                setattr(self, attr, b)
            return b.submit(request)

        wrapper._rtpu_is_batched = True
        return wrapper

    if _fn is not None:
        return decorator(_fn)
    return decorator
