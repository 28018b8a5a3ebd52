"""Device: what the compiler says the step program needs on one chip —
`compiled.memory_analysis()`: temporaries (which on the TPU span the donated
state's buffers) plus the arguments that are not donated — in GB.
(`memory_stats()` counts live arrays only and under-reports.)"""


def read(run):
    return run["window"]["program_bytes"] / 1e9
