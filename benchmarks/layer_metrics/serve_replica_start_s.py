"""Serve, set-up: seconds from the driver's `serve.run` to the first line of
the deployment's `__init__` in the replica (controller, replica actor on the
granted chip, the worker process, unpickling the class)."""


def read(run):
    return run["worker"].get("replica_start_s")
