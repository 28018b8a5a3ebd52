"""Model step: percent of the step's device time in the experts themselves
— the three grouped matmuls over the ragged groups (`ragged-dot*` custom
calls, found by name: they carry no scope), forward, recomputed and both
backward products, and what the scope `moe_experts` of `models/moe.py` holds
beside them (the SwiGLU product, the bf16 copies of the expert weights)."""

from benchmarks import moe_work


def read(run):
    return moe_work.scope_share(run, ("moe_experts",), grouped_matmuls=True)
