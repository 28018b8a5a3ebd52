"""Serve, kernels: device milliseconds a bucket's program spends in
`dsa_index`, the indexer's kernel (`ops/sparse_index.py`: a tile of queries'
index scores against their causal keys, the cut of each query's `topk` best
and the choice written out), all layers, mean over the programs of the
traced stretch (`trace_reduce`'s table of operations, by the name
`pl.pallas_call` gave the kernel)."""

from benchmarks import dsa_work, trace_reduce


def read(run):
    reduced = run["trace"]
    if not reduced:
        return None
    return 1e3 * trace_reduce.op_seconds_per_step(
        reduced, dsa_work.INDEX_KERNEL) or None
