"""Serve: requests a batch that `@serve.batch` fired inside the window, the
mean (the replica's own count; a fired batch becomes one device call a
length bucket it holds)."""


def read(run):
    return run["window"]["batch_requests_mean"]
