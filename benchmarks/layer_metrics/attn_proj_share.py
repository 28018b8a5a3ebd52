"""Model step: percent of the step's device time under the scopes
`attn_qkv` (first norm, q/k/v projections, rope) and `attn_out` (output
projection and residual) of `models/gpt.py::_block`, all passes — attention
without its kernel, which is `attn_kernel_share`."""

from benchmarks import program_trace


def read(run):
    return program_trace.scope_share(run, ("attn_qkv", "attn_out"))
