"""Serve, model step: percent of the traced stretch's busy device time in
the learned sparse attention mechanism — every operation under the scope
`dsa_index` (the indexer's projections, key norm and RoPE, and its kernel)
and under `dsa_attend` (the kernel `dsa_attend_fwd` and the transposes
around it), all programs of the stretch (`dsa_work.of_run`, the raw trace's
name-stack paths) over `busy_s`."""

from benchmarks import dsa_work


def read(run):
    reduced = run["trace"]
    seconds = dsa_work.of_run(run)
    if not seconds or not reduced or reduced["busy_s"] <= 0:
        return None
    return 100.0 * sum(seconds.values()) / reduced["busy_s"]
