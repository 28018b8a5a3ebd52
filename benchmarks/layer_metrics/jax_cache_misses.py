"""Model step layer: backend compiles of the run that XLA made and wrote to
the persistent cache — `_count` of `rtpu_jax_compile_seconds{stage=
backend_compile, cache=miss}`. 0 on a warm start; above 0 it says the cache
did not carry a program from the last run to this one, and the
`program_compile` line's largest rows say which."""

from benchmarks import program_compile


def read(run):
    return program_compile.cache_misses()
