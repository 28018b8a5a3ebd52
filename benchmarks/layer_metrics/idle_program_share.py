"""Device: percent of the traced window's idle device time during which some
thread of the worker had an `rtpu:` span open (`util/tracing.start_span`, on
the profiler's clock, host lines aligned to the device's): idle time the
program can name. `program_trace` prints it by thread and innermost span."""

from benchmarks import program_trace


def read(run):
    trace = program_trace.of_run(run)
    if not trace or not trace["host_spans"] or trace["idle_s"] <= 0:
        return None
    return 100.0 * trace["idle_program_s"] / trace["idle_s"]
