"""Model step layer: wall seconds of the run's start spent in the backend's
compile — XLA's compile where the persistent cache misses, the lookup and
the executable's deserialisation where it hits: `_sum` of
`rtpu_jax_compile_seconds{stage=backend_compile}` over every function (own
times, as `jax_trace_s`). `jax_cache_misses` says which of the two it was."""

from benchmarks import program_compile


def read(run):
    return program_compile.stage_seconds("backend_compile")
