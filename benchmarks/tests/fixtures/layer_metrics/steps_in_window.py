"""A per-layer metric that exists only in the test's own files."""


def read(run):
    return float(run["window"]["steps"])
