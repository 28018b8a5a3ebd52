"""The device time of the expert block's plumbing in a traced stretch: the
operations under the scopes `moe_router` (the router's product, its softmax
and the choice of the K), `moe_dispatch` (the sort by expert, the rows
gathered into that order) and `moe_combine` (the rows summed back a token)
of `ray_tpu/models/moe.py` — everything of the block that is not its
experts' matmuls. Kept with the benchmark beside `swa_work.py`, whose
`of_run` reads the experts' own scopes; the readers
`layer_metrics/serve_moe_router_ms.py` and
`serve_moe_plumbing_share.py` read this.

The seconds are `swa_work.analyse`'s `by_scope` (own device time by the
innermost scope of an operation's name-stack path, first to last start of
the step program on the first device that ran two), which `swa_work.of_run`
prints and does not keep: the three do not nest in the program as written,
so an operation's innermost scope is the one of them its path holds. A
program without the scopes, or a run without a device trace, reads as
nothing: `of_run` returns None and never raises.
"""

from __future__ import annotations

import json
import time
from typing import Any, Dict, Optional

from benchmarks import program_trace, swa_work

SCOPES = ("moe_router", "moe_dispatch", "moe_combine")


def analyse(planes, step_module: str) -> Optional[Dict[str, float]]:
    """Own device seconds of the traced stretch under each of `SCOPES`, all
    programs together; None without such a stretch or without any operation
    under one of them."""
    found = swa_work.analyse(planes, step_module)
    seconds = {scope: (found or {"by_scope": {}})["by_scope"].get(scope, 0.0)
               for scope in SCOPES}
    return seconds if any(seconds.values()) else None


_cache: Dict[str, Optional[Dict[str, float]]] = {}


def of_run(run: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """Seconds under each of `SCOPES` by the analysis of this run's raw
    trace; None without a trace or without the scopes. Never raises. Prints
    one progress line, `{"kind": "moe_scopes_trace", ...}`."""
    reduced = run.get("trace")
    if not reduced:
        return None
    name = run["cell"]["name"]
    if name not in _cache:
        t0 = time.perf_counter()
        result, error = None, None
        try:
            path = program_trace.trace_file(name)
            if path:
                with open(path, "rb") as f:
                    result = analyse(program_trace.read_xspace(
                        f.read(), ("tf_op",)), reduced["step_module"])
        except Exception as e:      # noqa: BLE001 — a reader never raises
            error = repr(e)
        _cache[name] = result
        print(json.dumps({"kind": "moe_scopes_trace", "cell": name,
                          "parse_s": time.perf_counter() - t0,
                          "error": error, "seconds": result}), flush=True)
    return _cache[name]
