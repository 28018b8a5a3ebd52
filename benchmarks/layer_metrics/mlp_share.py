"""Model step: percent of the step's device time under the scope `mlp`
(second norm, up projection, activation, down projection, residual:
`models/gpt.py::_block`), forward, recomputation and backward together."""

from benchmarks import program_trace


def read(run):
    return program_trace.scope_share(run, ("mlp",))
