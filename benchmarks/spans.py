"""Host spans recorded from the benchmark's own files, around calls into a layer.

Kept in memory on `time.perf_counter_ns`; when a profiler trace is being
taken each span is also a `jax.profiler.TraceAnnotation` named `bench:<name>`,
which puts it on the profiler's clock beside the device's operations —
`trace_reduce.py` labels idle gaps with them.
"""

from __future__ import annotations

import contextlib
import statistics
import time
from typing import Dict, List, Tuple

PREFIX = "bench:"


class SpanLog:
    def __init__(self) -> None:
        self.rows: List[Tuple[str, int, int]] = []      # name, start, end (ns)
        self.annotate = False       # True while a profiler trace is open

    @contextlib.contextmanager
    def span(self, name: str):
        note = None
        if self.annotate:
            import jax
            note = jax.profiler.TraceAnnotation(PREFIX + name)
            note.__enter__()
        t0 = time.perf_counter_ns()
        try:
            yield
        finally:
            self.rows.append((name, t0, time.perf_counter_ns()))
            if note is not None:
                note.__exit__(None, None, None)

    def summary(self, since_ns: int = 0, until_ns: int = 2 ** 63
                ) -> Dict[str, Dict[str, float]]:
        """Per name, over the spans that started in [since, until): count,
        total seconds, median / max milliseconds."""
        by_name: Dict[str, List[float]] = {}
        for name, t0, t1 in self.rows:
            if since_ns <= t0 < until_ns:
                by_name.setdefault(name, []).append((t1 - t0) / 1e6)
        return {name: {"count": len(ms), "total_s": sum(ms) / 1e3,
                       "median_ms": statistics.median(ms), "max_ms": max(ms)}
                for name, ms in by_name.items()}
