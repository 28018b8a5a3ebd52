"""Serve: milliseconds from the client's call of the handle (router pick,
pickling, actor call) to the request's arrival in the replica's `__call__`,
the median over the window's answered requests. Host clock on one machine
(`time.time()` in both processes)."""


def read(run):
    return run["window"]["ingress_ms"]
