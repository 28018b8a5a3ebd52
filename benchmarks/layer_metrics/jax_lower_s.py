"""Model step layer: wall seconds of the run's start spent lowering — jaxpr
to StableHLO, Pallas kernel bodies to Mosaic: `_sum` of
`rtpu_jax_compile_seconds{stage=lower}` over every function (own times, as
`jax_trace_s`)."""

from benchmarks import program_compile


def read(run):
    return program_compile.stage_seconds("lower")
