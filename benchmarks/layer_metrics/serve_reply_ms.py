"""Serve: milliseconds from the end of the request's batch on the replica
(scores on the host) to the answer's return in the client (futures,
unpickling, the object's way back), the median over the window's answered
requests. Host clock on one machine (`time.time()` in both processes)."""


def read(run):
    return run["window"]["reply_ms"]
