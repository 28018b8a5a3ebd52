"""Model step: percent of the step's device time in the Gated DeltaNet
mixers — the scopes `gdn_proj` (the q~ k~ v~ z and b a projections),
`gdn_conv` (the causal convolution, SiLU, the q / k normalisation, beta and
the decay), `gdn_rule` (the chunked gated delta rule) and `gdn_out` (the
gated norm and the out-projection) of `models/gpt.py::_linear_mixer`, all
passes. `attn_proj_share` and `attn_kernel_share`'s scopes hold them too."""

from benchmarks import hybrid_work


def read(run):
    return hybrid_work.scope_share(run, hybrid_work.GDN_SCOPES)
