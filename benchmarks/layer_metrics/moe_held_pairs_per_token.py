"""Model step: (token, expert) pairs routed to the experts held here, per
token and layer — `moe_routed_here` of the step's own metrics
(`models/gpt.py::loss`, through `train.report`) over the step's tokens, a
mean over the layers, the median over the window's steps. At uniform routing
it is top-k x held / routed over (0.625 for 32 of 512 at top-10); the held
experts' matmuls, gathers and adds grow with it."""

from benchmarks import moe_work


def read(run):
    try:
        return moe_work.pairs_per_token(run["window"])
    except Exception:   # noqa: BLE001 — a reader never raises
        return None
