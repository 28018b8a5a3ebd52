"""Model step: percent of the step's device time spent on a rematerialised
path (`rematted_computation` in the operation's name stack), whatever the
scope: what `remat_policy` pays for the memory it saves. Per fusion, so
approximate: the profiler files a fusion whole under its heaviest
operation's path, and one that mixes recomputed with backward work counts
as backward. A lower bound — in `gpt2xl-fsdp4` ("full" remat, where the
recomputed time should equal the forward's) it read 21.0% for a forward of
23.7% (my chip run, PR 24)."""

from benchmarks import program_trace


def read(run):
    return program_trace.scope_share(run, (), ("recompute",))
