"""What decides `correct` for a training cell, tested where no chip is:

    python -m pytest benchmarks/tests/test_first_steps.py -q

- `reference/train_steps.py` (the objective's gradient layer by layer, AdamW
  written out) against the program's own step in float32 on the CPU;
- the control: the reference at the next lower precision, put in the
  program's place, comes out not correct at the fixtures' limits, and the
  bfloat16-operand one (the program's own precision) correct;
- the planted faults: a whole CPU rehearsal of a fixture cell with the
  program broken underneath (`faults.py`, in the worker) — a state returned
  unchanged, half of the batch left out, the exchange between chips left
  out, one group's gradient times 1.05, one rectangle's dk dropped, AdamW
  without its first bias correction — each reads not correct by a number of
  `step_check.compare`, and the sound program beside them has no problem but
  that it ran on the CPU.
"""

import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmarks import cells, step_check                      # noqa: E402

FIXTURES = os.path.join(HERE, "fixtures")
TINY = os.path.join(FIXTURES, "BENCHMARK.tiny.json")
# a rehearsal runs on the CPU and says so; nothing else may be wrong with it
ONLY_THE_CPU = ("ran on 'cpu', not a TPU", "no peaks on record for device")


def _tiny_gpt2(seed, dtype="float32"):
    """The `gpt2_tiny` fixture's model, its seeded parameters and three
    batches of the fixture's traffic."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import jax.numpy as jnp
    from benchmarks import traffic_gen
    from ray_tpu.models import GPT
    from ray_tpu.models.gpt import GPTConfig

    cell = cells.resolve("tiny-steady", TINY)
    kw = dict(cell.config["model"], dtype=getattr(jnp, dtype),
              param_dtype=jnp.float32, attention_impl="reference")
    model = GPT(GPTConfig(**kw))
    rows = traffic_gen.packed_rows(cell.traffic, 12, seed)["tokens"]
    batches = [np.asarray(rows[4 * i:4 * i + 4], np.int32) for i in range(3)]
    return cell, model, jax.random.PRNGKey(seed), batches


def test_train_steps_follows_the_programs_own_step_in_float32():
    """Three steps of `make_train_step` with `make_optimizer` (optax) in
    float32 against `train_steps.follow` from the same seeded parameters on
    the same batches: every step's loss and gradient norm, every leaf's
    first moment and every leaf's change agree to float32 rounding."""
    import jax
    import jax.numpy as jnp
    from benchmarks.loops.train import _first_moment
    from benchmarks.reference import gpt2, gpt2_glue, train_steps
    from ray_tpu.models import (init_train_state, make_optimizer,
                                make_train_step)

    cell, model, key, batches = _tiny_gpt2(3)
    optimizer = make_optimizer(**cell.config["optimizer"])
    state = init_train_state(model, optimizer, key)
    step = make_train_step(model, optimizer, donate=False)
    system = {"records": []}
    with jax.default_matmul_precision("highest"):
        for i, batch in enumerate(batches):
            state, metrics = step(state, {"tokens": jnp.asarray(batch)})
            system["records"].append({k: float(v)
                                      for k, v in metrics.items()})
            if i == 0:
                system["moment_sumsq"] = train_steps.named_sumsq(
                    *gpt2_glue.reference_weights(
                        _first_moment(state.opt_state), None, jax.devices()))
    change = jax.tree_util.tree_map(jnp.subtract, state.params,
                                    model.init(key))
    system["change_sumsq"] = train_steps.named_sumsq(
        *gpt2_glue.reference_weights(change, None, jax.devices()))

    followed = train_steps.follow(
        gpt2, cell.config,
        lambda: gpt2_glue.reference_weights(model.init(key), None,
                                            jax.devices()),
        batches, jax.devices())
    rows, _ = step_check.compare(system, followed, cell.config["reference"])
    read = {name: value for name, _, value, _ in rows}
    assert set(read) == {
        "step1_loss_gap", "step1_ce_gap", "step2_loss_gap", "step2_ce_gap",
        "step3_loss_gap", "step3_ce_gap", "grad_norm_gap", "grad_leaf_gap",
        "change_leaf_gap"}
    for name, value in read.items():
        assert abs(value) < (2e-5 if name.startswith("step") else 1e-4), \
            (name, value)
    # the steps moved: the first update is by lr(0) = 0, the next two not
    assert min(followed["change_sumsq"].values()) > 0
    assert followed["steps"][2]["loss"] < followed["steps"][0]["loss"]


def test_the_schedule_written_out_is_optaxs():
    import optax
    from benchmarks.reference import train_steps
    adamw = {"learning_rate": 3e-4, "warmup_steps": 100,
             "total_steps": 1000, "end_fraction": 0.1}
    theirs = optax.warmup_cosine_decay_schedule(0.0, 3e-4, 100, 1000, 3e-5)
    for n in (0, 1, 2, 50, 99, 100, 101, 500, 999, 1000, 5000):
        assert math.isclose(train_steps.learning_rate(adamw, n),
                            float(theirs(n)), rel_tol=1e-5, abs_tol=1e-12), n


def test_a_leafs_gap_is_of_norms_against_the_larger_of_leaf_and_median():
    reference = {"a": 1.0, "b": 2.0, "c": 1e-6, "d": 4.0, "e": 3.0}
    # the all-but-zero leaf is measured against the median leaf (2.0)
    gap, where = step_check.worst_gap(dict(reference, c=0.2), reference)
    assert where == "c" and math.isclose(gap, (0.2 - 1e-6) / 2.0)
    gap, where = step_check.worst_gap(dict(reference, d=4.4), reference)
    assert where == "d" and math.isclose(gap, 0.1)
    # a leaf that did not move reads 1, one that moved double reads 1
    assert step_check.worst_gap(dict(reference, e=0.0), reference)[0] == 1.0
    assert step_check.worst_gap(dict(reference, e=6.0), reference)[0] == 1.0
    # a leaf left out is not read; a NaN is the worst there is
    assert step_check.worst_gap(dict(reference, e=0.0), reference,
                                leave_out=["e"])[0] == 0.0
    assert math.isnan(step_check.worst_gap(
        dict(reference, a=float("nan")), reference)[0])
    # other leaves than the reference's: nothing to hold, which fails
    assert step_check.worst_gap({"a": 1.0}, reference) == (None, None)


@pytest.mark.parametrize("seed", [5, 6, 7])
def test_the_next_lower_precision_in_the_programs_place_is_not_correct(seed):
    """The control, at the fixture's size and limits: the reference with
    every matmul's operands rounded to float8_e4m3fn, put in the program's
    place, fails a limit; with bfloat16 operands, the precision the
    configuration states, it passes them all."""
    import jax
    import jax.numpy as jnp
    from benchmarks.reference import gpt2, gpt2_glue, train_steps

    cell, model, key, batches = _tiny_gpt2(seed)
    group = cell.config["reference"]

    def follow(**how):
        return train_steps.follow(
            gpt2, cell.config,
            lambda: gpt2_glue.reference_weights(model.init(key), None,
                                                jax.devices()),
            batches, jax.devices(), **how)

    sound = follow()
    for operands, correct in ((jnp.bfloat16, True),
                              (jnp.float8_e4m3fn, False)):
        _, problems = step_check.compare(
            step_check.as_system(follow(operands=operands), group["adamw"]),
            sound, group)
        assert (not problems) is correct, (operands, problems)


# ------------------------------------------------- the planted faults

def _rehearse(workload, devices, patch=None):
    """One fixture cell through `run.main` on the CPU, the program broken
    in the worker by `faults.<patch>` where one is named. Returns the
    result line and the verdict's problems."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=os.path.join(
                   ROOT, ".bench_runs", "test_cache"))
    if devices > 1:
        env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count="
                            f"{devices}")
    rehearsal = {"num_tpus": devices}
    if patch:
        rehearsal["patch"] = "benchmarks.tests.faults:" + patch
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "from benchmarks import run\n"
        "sys.exit(run.main(['--workload', %r, '--seed', '5', '--seconds', "
        "'3', '--trace', '0'], benchmark_file=%r, rehearsal=%r))\n"
        % (ROOT, workload, TINY, rehearsal))
    done = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    lines = [json.loads(x) for x in done.stdout.splitlines()
             if x.startswith("{")]
    [verdict] = [x for x in lines if x.get("kind") == "verdict"]
    own = [p for p in verdict["problems"] if not p.startswith(ONLY_THE_CPU)]
    # the run's last lines on standard error: each number beside its limit
    assert [x for x in done.stderr.splitlines()
            if x.startswith("compared: ")][-1].startswith(
                "compared: change_leaf_gap = ")
    return lines[-1], own


# (cell, devices, fault, the compared number that must catch it)
FAULTS = [
    ("tiny-steady", 1, None, None),
    ("tiny-steady", 1, "state_unchanged", "parameters' change"),
    ("tiny-steady", 1, "half_batch", "first gradient: the worst leaf"),
    ("tiny-fsdp4", 4, "no_exchange", "first gradient: the worst leaf"),
    ("tiny-steady", 1, "gradient_group_scaled",
     "first gradient: the worst leaf's gap of norms (0/mlp.c_fc.w)"),
    ("tiny-steady", 1, "flash_dk_dropped", "parameters' change"),
    ("tiny-steady", 1, "no_bias_correction", "parameters' change"),
]


@pytest.mark.parametrize("workload,devices,fault,caught_by", FAULTS,
                         ids=[f or "sound" for _, _, f, _ in FAULTS])
def test_a_planted_fault_reads_not_correct(workload, devices, fault,
                                           caught_by):
    line, problems = _rehearse(workload, devices, fault)
    assert line["correct"] is False         # a CPU run never is
    compared = line["compared"]
    assert list(line)[-1] == "compared" and all(
        set(v) == {"value", "limit"} for v in compared.values())
    if fault is None:
        assert problems == []
        assert all(abs(v["value"]) <= v["limit"]
                   for k, v in compared.items() if k.endswith("_gap"))
        return
    assert any(p.startswith(caught_by) for p in problems), problems
    if fault == "state_unchanged":
        # by the measure of norms a leaf that did not move reads 1 (to the
        # rounding of a second seeded initialisation, made inside the sums)
        assert compared["change_leaf_gap"]["value"] == pytest.approx(
            1.0, abs=1e-5)
    if fault == "gradient_group_scaled":
        # the one group, by the factor, and nothing else notices
        assert 0.04 < compared["grad_leaf_gap"]["value"] < 0.06
        assert len(problems) == 1
