"""What the tests that compile for the chip share: the described TPU v5e and
the readers of a compiled program's text.

The TPU compiler is installed with jax and compiles for a topology that is
described and not attached (`on-chip-measurement` guide, section 2). This
catches what interpret mode cannot — a kernel the Mosaic compiler refuses,
a step that does not fit 16 GB of HBM, a shard_map that cannot be
partitioned — at no chip time. Nothing runs: these tests say nothing about
results or speed, and a pass here is not a chip run (a cell of
`benchmarks/run.py` is).

They are in three files, so that the long compiles (a Qwen3-Next step takes
minutes) go to different workers of the driver's `-n 6 --dist loadfile`:
`test_chip_compile.py` (kernels, the GPT-2 steps, the host's four chips)
and, closing the files of their configurations' other tests,
`test_olmoe_reference.py` and `test_qwen3_next_ops.py` (the one-chip
steps). pytest-xdist hands a file whole to one worker and hands out the
files of many tests first, so a long test in a file of few tests starts
last: a drawn architecture's compile-fit test goes at the end of the file
that holds its configuration's other tests, not in a file of its own. Each
file imports the `v5e` fixture below and describes the topology in the
worker that runs it. Only one process at a time may load the TPU's library
unless `ALLOW_MULTIPLE_LIBTPU_LOAD=1` is set, as the driver's tier-1
command sets it: without it, under several workers, all but one of the
files skip these tests.

Skipped where the topology cannot be described. The persistent compile
cache is off around them: such a compile can be written to it but not read
back without a chip.
"""

import json
import math
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest

HBM_BYTES = 15.75 * 2 ** 30     # what the v5e compiler reports as capacity


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _on(sharding, tree):
    """ShapeDtypeStructs of `tree`, every leaf placed by `sharding`."""
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _asks_no_vmem(compiled, name):
    """Whether the kernel's calls state no scoped-VMEM limit of their own
    (beside a call that states one the compiler writes its default, 16 MiB
    on the v5e, on the others): what they hold then fits in that default,
    or the compile would have failed."""
    calls = _kernel_calls(compiled, name)
    stated = [int(size) for call in calls for size in re.findall(
        r'"scoped_memory_configs":\[\{[^}]*"size":"(\d+)"', call)]
    return bool(calls) and all(size <= 16 * 2 ** 20 for size in stated)


def _kernel_calls(compiled, name=""):
    """The compiled program's Pallas calls whose `pallas_call` name starts
    with `name` (every Mosaic call, the compiler's own grouped matmuls
    among them, with none): a scanned block's calls count once each. The
    name closes
    the call's path, `.../flash_bwd/pallas_call` or, under a transform
    with no scope around it, `.../transpose(jvp(flash_bwd))/pallas_call`."""
    return [line for line in compiled.as_text().splitlines()
            if 'custom_call_target="tpu_custom_call"' in line
            and (not name
                 or re.search(rf"[/(]{name}\w*\)*/pallas_call", line))]


def _kernel_names(compiled, name):
    """Those calls' `pallas_call` names, sorted."""
    return sorted(
        re.search(rf"[/(]({name}\w*)\)*/pallas_call", line).group(1)
        for line in _kernel_calls(compiled, name))


def _operations(compiled, entry=False):
    """The compiled program's operations by name: (result type, opcode,
    the text from its operands on). With `entry`, those of the program's
    entry computation alone: what runs as an operation of its own and
    writes its result out, where the whole text also lists what a fusion
    holds inside (a scan over one layer is part of the entry computation:
    the compiler writes its one trip out)."""
    text = compiled.as_text()
    if entry:
        text = text[text.index("\nENTRY "):]
        text = text[:text.index("\n}")]
    return {m.group(1): m.groups()[1:]
            for line in text.splitlines()
            if (m := re.match(
                r"\s*(?:ROOT )?%?([\w.\-]+) = (.*?)\s([\w\-]+)\((.*)", line))}


_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s32": 4,
          "u32": 4, "f32": 4}


def _bytes_written_to_rows(compiled, rows):
    """The bytes each of the program's own operations writes to arrays
    whose leading dimension is `rows` (the members of a tuple each; a
    recomputation, `.remat`, is an operation of its own), by name."""
    sizes = {}
    for name, (made, op, _) in _operations(compiled, entry=True).items():
        if op in ("parameter", "bitcast", "get-tuple-element", "tuple"):
            continue
        size = sum(
            _BYTES[kind] * rows * math.prod(map(int, filter(None,
                                                            dims.split(","))))
            for kind, dims in re.findall(
                rf"\b({'|'.join(_BYTES)})\[{rows},?([\d,]*)\]", made))
        if size:
            sizes[name] = size
    return sizes


def _grouped_matmul_weights(compiled):
    """Where each of the compiled program's grouped matmuls
    (`ragged-dot-none*`, the compiler's own custom calls) gets its weights
    from: the opcode that writes its last operand, read through `bitcast`s
    and `get-tuple-element`s. "parameter" (the program's, or the loop
    body's whose tuple holds the program's) says the weights are read where
    they lie; a "fusion" or a "copy" says they were written out first: a
    custom call's operand cannot be a slice fused into it (PERF.md, PR
    54)."""
    made = _operations(compiled)
    sources = []
    for name, (_, op, args) in made.items():
        if op != "custom-call" or not name.startswith("ragged-dot-none"):
            continue
        at = re.findall(r"%([\w.\-]+)", args.split("), ")[0])[-1]
        while made[at][1] in ("bitcast", "get-tuple-element"):
            at = re.search(r"%([\w.\-]+)", made[at][2]).group(1)
        sources.append(made[at][1])
    return sources


def _writes_of(compiled, *shapes):
    """The names of the compiled program's operations that write an array
    of one of `shapes` ("bf16[128,2048,1024]"), alone or in a tuple: every
    operation but the ones that only name bytes that are there."""
    return [name for name, (made, op, _) in _operations(compiled).items()
            if any(shape in made for shape in shapes)
            and op not in ("parameter", "bitcast", "get-tuple-element")]


def benchmark_config(name):
    """`benchmarks/configs/<name>.json`, read."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), f"benchmarks/configs/{name}.json")
    with open(path) as f:
        return json.load(f)


def served_bucket(v5e, name, rows, length):
    """`benchmarks/configs/<name>.json` as `loops/serve.py::Scorer` builds
    it (bfloat16 weights, the bucket program's own text), one bucket's
    program compiled for the described chip: (the weights' shapes, the
    compiled program)."""
    from jax.sharding import SingleDeviceSharding
    from ray_tpu.models.gpt import GPT, GPTConfig

    kw = dict(benchmark_config(name)["model"], attention_impl="pallas")
    kw["dtype"] = getattr(jnp, kw["dtype"])
    kw["param_dtype"] = getattr(jnp, kw["param_dtype"])
    model = GPT(GPTConfig(**kw))
    one_chip = SingleDeviceSharding(v5e.devices[0])
    params = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, jnp.bfloat16,
                                       sharding=one_chip),
        jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0))))

    def score_bucket(params, tokens):
        logits = model.apply(params, tokens)[:, :-1]
        at_target = jnp.take_along_axis(
            logits, tokens[:, 1:, None], axis=-1)[..., 0]
        return at_target - jax.nn.logsumexp(logits, axis=-1)

    return params, jax.jit(score_bucket).lower(
        params, jax.ShapeDtypeStruct((rows, length), jnp.int32,
                                     sharding=one_chip)).compile()


def _qwen3_next_config():
    return benchmark_config("qwen3_next_80b_a3b")


def _moved(text, rows):
    """The compiled program's copies, pads, slices, concatenates and
    transposes (inside fusions too) that write a [rows, 8192, 1024 or more]
    array under one of the Gated DeltaNet layer's scopes."""
    return [line for line in text.splitlines() if re.search(
        rf"= (?:bf16|f32)\[{rows},8192,[0-9]{{4,}}\]\S* "
        r"(?:copy|pad|slice|concatenate|transpose)\(", line)
        and "/gdn_" in line]


def _qwen3_next_step(config, batch, mesh=None):
    """The one-period Qwen3-Next step of the benchmark's
    `qwen3_next_80b_a3b` configuration, as its cell builds it, at `batch`
    rows of 8,192 (on a mesh the step's own in_shardings place the state)."""
    from ray_tpu.models import (GPT, init_train_state, make_optimizer,
                                make_train_step)
    from ray_tpu.models.gpt import GPTConfig

    kw = dict(config["model"], attention_impl="pallas")
    kw["dtype"] = getattr(jnp, kw["dtype"])
    kw["param_dtype"] = getattr(jnp, kw["param_dtype"])
    model = GPT(GPTConfig(**kw), **({"mesh": mesh} if mesh else {}))
    opt = make_optimizer(**config["optimizer"])
    state = jax.eval_shape(
        lambda: init_train_state(model, opt, jax.random.PRNGKey(0)))
    tokens = jax.ShapeDtypeStruct((batch, kw["max_seq_len"]), jnp.int32)
    return make_train_step(model, opt, mesh=mesh), state, tokens
