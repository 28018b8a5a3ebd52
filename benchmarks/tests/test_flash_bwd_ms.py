"""`flash_bwd_ms` reads the fused backward kernel by its `pallas_call` name
and nothing else: not the pair it replaced, whose names it is a prefix of.
CPU only, not part of tier-1:

    python -m pytest benchmarks/tests -q
"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmarks import cells, program_trace  # noqa: E402

US = 1e3        # nanoseconds
BWD = ("jit(train_step)/transpose(jvp())/while/body/closed_call/"
       "attn_kernel/")


def _planes(kernels):
    """Two executions of a step of 100 us whose backward pass holds the
    given (kernel name, us) calls one after the other."""
    ops, modules = [], []
    for i, base in enumerate((0, 200)):
        at = base
        for name, dur in kernels:
            ops.append((f"%{name}.3 = f32[8]{{0}} custom-call(f32[8]{{0}} "
                        f"%p), custom_call_target=\"tpu_custom_call\"",
                        at * US, dur * US,
                        {"tf_op": f"{BWD}{name}/pallas_call:",
                         "program_id": 7}))
            at += dur
        ops.append(("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop",
                    at * US, (base + 100 - at) * US,
                    {"tf_op": "jit(train_step)/optimizer/add:",
                     "program_id": 7}))
        modules.append(("jit_train_step(7)", base * US, 100 * US,
                        {"run_id": i}))
    return [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "id": 1, "events": ops},
        {"name": "XLA Modules", "id": 2, "events": modules}]}]


@pytest.mark.parametrize("kernels,want", [
    # the fused form: one backward kernel beside the forward
    ([("flash_fwd", 10), ("flash_bwd", 20)],
     {"flash_bwd_ms": 0.02, "flash_fwd_ms": 0.01}),
    # the pair (the parent's step, and rows too long for the fused form):
    # `flash_bwd` is a prefix of both names and reads neither
    ([("flash_fwd", 10), ("flash_bwd_dq", 12), ("flash_bwd_dkv", 14)],
     {"flash_bwd_ms": None, "flash_fwd_ms": 0.01}),
], ids=["fused", "pair"])
def test_flash_bwd_ms_reads_the_kernel_of_that_name(kernels, want):
    cell = cells.resolve("gpt2m-steady")
    assert any(m["name"] == "flash_bwd_ms" and m["layer"] == "Kernels"
               and m["source"] == "device_trace"
               and m["moves"] == "tokens_per_s_per_chip"
               for m in cell.per_layer)
    run = {"cell": {"name": "flash-bwd-made-up"},
           "trace": {"step_module": "x"}}
    program_trace._cache["flash-bwd-made-up"] = program_trace.analyse(
        _planes(kernels), "train_step")
    try:
        for metric, value in want.items():
            got = cells.layer_reader(cell, metric)(run)
            assert got == (None if value is None else pytest.approx(value)), \
                metric
        # the share and the roofline follow the prefix `flash_` by themselves
        assert program_trace.kernels_seconds(run, "flash_") == pytest.approx(
            1e-6 * sum(us for _, us in kernels))
    finally:
        del program_trace._cache["flash-bwd-made-up"]
