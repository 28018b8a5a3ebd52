"""Serve, model step: device milliseconds a device call of the traced
stretch spends under the scope `moe_router` of `models/moe.py` — the
router's float32 product, its softmax and the choice of the K experts, all
layers, all programs of the stretch (`moe_scopes.of_run`, the raw trace's
name-stack paths) over the stretch's device calls. In a model whose router
reads the mixer's normed input these operations need nothing of the layer's
attention, and stand wherever the compiler puts them."""

from benchmarks import moe_scopes


def read(run):
    reduced = run.get("trace")
    seconds = moe_scopes.of_run(run)
    if not seconds or not reduced or not reduced.get("n_steps"):
        return None
    return 1e3 * seconds["moe_router"] / reduced["n_steps"]
