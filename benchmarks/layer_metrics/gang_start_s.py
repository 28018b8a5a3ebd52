"""Runtime layer: seconds from the driver's call of `fit()` to the first line
of the loop inside the granted worker (placement group, worker process,
actor start, shipping the loop). Both clocks are `time.time()` on one host."""


def read(run):
    return run["worker"].get("gang_start_s")
