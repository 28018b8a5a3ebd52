"""Model step: percent of the step's device time in an expert block that
holds a share of its experts — the scopes `moe_router` (logits over all the
router's outputs, softmax, top-k), `moe_dispatch` (the sort, the counts, the
gathers of the rows routed here), `moe_experts` with the grouped matmuls
(`ragged-dot*`, by name), `moe_combine` (the weighted add back into token
order) and `moe_shared` (the shared expert) of `models/moe.py`, all passes.
`mlp_share` counts them too, the grouped matmuls apart."""

from benchmarks import hybrid_work


def read(run):
    return hybrid_work.scope_share(run, hybrid_work.MOE_SCOPES,
                                   grouped_matmuls=True)
