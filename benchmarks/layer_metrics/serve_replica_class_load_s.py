"""Serve, set-up: seconds the replica's worker spent unpickling the actor
class `Replica` (`ray_tpu/serve/replica.py`), which imports `ray_tpu.serve`
— span `worker::load_code`, series `rtpu_worker_load_code_seconds{kind=
actor_class, name=Replica}` (`gang_worker_class_load_s`'s reading, of the
served cells' own class; the deployment class itself is unpickled inside
`Replica.__init__` and is in `serve_replica_start_s`)."""

from benchmarks import program_compile


def read(run):
    return program_compile.class_load_seconds("Replica")
