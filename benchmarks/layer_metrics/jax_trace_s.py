"""Model step layer: wall seconds of the run's start spent tracing — Python
to jaxpr, for every jitted function a start traces, the step's kernel bodies
included: `_sum` of `rtpu_jax_compile_seconds{stage=trace}` over every
function. Own times (a span's duration less the jax spans nested inside it),
so the three stages add up to wall time and can be subtracted from
`setup_s`. The ten largest rows are on the `program_compile` progress
line."""

from benchmarks import program_compile


def read(run):
    return program_compile.stage_seconds("trace")
