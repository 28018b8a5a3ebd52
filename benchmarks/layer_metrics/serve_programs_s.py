"""Serve, set-up: seconds the replica took to lower, compile (or load from
the persistent cache) and run once every bucket's program."""


def read(run):
    return run["worker"].get("programs_s")
