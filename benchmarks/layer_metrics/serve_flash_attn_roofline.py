"""Serve, kernels: the flash forward kernel's share of its roofline, in
percent — the least time the chip could take for causal attention over each
scored document's own triangle (`flops.flash_forward_work`, the real lengths
of the documents in the traced stretch's device calls: padded rows and tails
are not work the requests need) over the device time of the kernels named
`flash_*` in those calls, whatever program ran them (`trace_reduce`'s table
of operations: a served stretch runs one program a bucket)."""

from benchmarks import flops, trace_reduce


def read(run):
    reduced, traced = run["trace"], run.get("traced")
    peaks = run["peaks"]
    if not reduced or not traced or not peaks:
        return None
    per_call = trace_reduce.op_seconds_per_step(reduced, r"^flash_")
    if not per_call:
        return None
    calls = [lengths for batch in traced["log"]["batches"]
             for _, _, lengths in batch["calls"]][:reduced["n_steps"]]
    if len(calls) < reduced["n_steps"]:
        return None
    work = flops.flash_forward_work(
        run["cell"]["config"]["model"], [n for c in calls for n in c])
    return (100.0 * flops.roofline_seconds(work, peaks)["seconds"]
            / (per_call * reduced["n_steps"]))
