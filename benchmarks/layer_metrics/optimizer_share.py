"""Model step: percent of the step's device time under the scope
`optimizer` (gradient clipping, `optimizer.update`, `apply_updates`, the
gradient norm: `models/training.py::train_step`)."""

from benchmarks import program_trace


def read(run):
    return program_trace.scope_share(run, ("optimizer",))
