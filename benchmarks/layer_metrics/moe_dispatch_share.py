"""Model step: percent of the step's device time the sparse block spends
around its matmuls — the scopes `moe_router` (logits, softmax, top-k),
`moe_dispatch` (sort by expert, counts, the gather of rows) and `moe_combine`
(the gather back and the weighted sum over k) of `models/moe.py`, all passes.
`mlp_share` counts them too."""

from benchmarks import moe_work


def read(run):
    return moe_work.scope_share(run, ("moe_router", "moe_dispatch",
                                      "moe_combine"))
