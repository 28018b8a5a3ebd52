"""Model step: percent of the step's device time under none of the program's
scopes — the coverage check of the scope split. What is left there is the
layer scan's own plumbing (slicing saved activations out of their stacked
buffers, loop control) and a few conversions."""

from benchmarks import program_trace


def read(run):
    return program_trace.scope_share(run, (program_trace.UNSCOPED,))
