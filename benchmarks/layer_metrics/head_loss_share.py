"""Model step: percent of the step's device time under the scope
`head_loss` (final norm, the `[B, S, V]` logits, cross-entropy:
`models/gpt.py::forward_with_aux` and `loss`), forward and backward."""

from benchmarks import program_trace


def read(run):
    return program_trace.scope_share(run, ("head_loss",))
