"""Runtime layer: seconds from a train worker's `main()` to the arrival of
its first task (the actor's creation), the mean over the gang's workers —
`rtpu_worker_start_seconds{phase=runtime}` (the runtime's construction, its
socket, `REGISTER` sent) plus `{phase=first_task}` (the node's side of
registration and the dispatch), of the processes whose first task holds
chips. The first part of `gang_worker_load_s`; `gang_worker_class_load_s` is
the second."""

from benchmarks import program_compile


def read(run):
    return program_compile.worker_register_seconds()
