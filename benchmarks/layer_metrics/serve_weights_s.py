"""Serve, set-up: seconds the replica took to make its seeded weights on the
device in the served type (one jitted call, its compile or cache load
included)."""


def read(run):
    return run["worker"].get("weights_s")
