"""The first training steps, plain: what `loops/train.py` holds the timed
`train_step`'s first steps to. Float32 `jax.numpy` at
`jax.default_matmul_precision("highest")`; no optax, nothing from `ray_tpu/`.

`follow(reference, config, start, batches, devices)` takes a reference
module's pieces (`reference.training(config, operands)`: `embed`, `block`,
`head`, `routes`), the seeded parameters in the reference's own layout
(`start()` gives what the glue hands over: `top` and one dict a layer) and
the token batches of the first steps, all rows of each, and for every step
computes the training objective, its gradient and one AdamW update.

The objective is the configuration's `reference.objective`: the mean
next-token cross-entropy + `z_loss` x the mean squared log-sum-exp of the
logits + for each other name there (`load_balance`, `router_z`) its
coefficient x the mean over the layers of the term of that name a block
returns. A block's term over several rows is linear in what each row adds
once the share of the (token, choice) pairs each expert was sent (`fraction`,
which has no gradient) is known for the whole batch, so a model that routes
goes over a batch twice: forward for the counts, then forward and backward.

AdamW is the configuration's `reference.adamw`, written out: the gradient
times min(1, clip / its norm over all leaves); m = b1 m + (1 - b1) g,
v = b2 v + (1 - b2) g^2; with t steps taken, p <- p - lr(t - 1) x ((m / (1 -
b1^t)) / (sqrt(v / (1 - b2^t)) + eps) + weight_decay x p), every leaf
decayed; lr(n) = peak x n / warmup_steps while n < warmup_steps (so the first
update is by 0), a half cosine from peak down to `end_fraction` x peak at
`total_steps` after it.

How it fits (none of this changes a number but by the order of float32
sums): rows go through in passes of `rows_per_pass`, sharded over the devices
where there are several, and the gradient is accumulated in float32; a layer
is differentiated on its own (`jax.vjp` of one block at a saved input, the
forward run again), from the head down, so no more than one layer's
activations are alive; with several devices the layers' parameters, gradients
and moments are spread round-robin and a layer is copied to all of them while
it is in use; `follow` keeps no copy of where it started and asks `start()`
for it again at the end, when gradient and moments are gone.
"""

from __future__ import annotations

import gc
import math
from typing import Any, Callable, Dict, List, Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

_PRECISION = "highest"


def learning_rate(adamw: Mapping[str, Any], n: int) -> float:
    """The schedule at `n` updates already made."""
    peak, warm = float(adamw["learning_rate"]), int(adamw["warmup_steps"])
    if n < warm:
        return peak * n / warm
    total = max(int(adamw["total_steps"]), warm + 1)
    end = float(adamw["end_fraction"]) * peak
    along = min(1.0, (n - warm) / (total - warm))
    return end + (peak - end) * 0.5 * (1.0 + math.cos(math.pi * along))


def _sumsq(tree) -> Dict[str, Any]:
    return {k: jnp.sum(jnp.square(v.astype(jnp.float32)))
            for k, v in tree.items()}


leaf_sumsq = jax.jit(_sumsq)


def traced_sumsq(top, layers) -> Dict[str, Any]:
    """Every leaf's sum of squares by name: `top/<leaf>`, `<layer>/<leaf>`.
    Traceable: the loop jits it around the glue, so that a tree shaped like
    the program's parameters is summed where it lies, no layer copied out."""
    out = {f"top/{k}": v for k, v in _sumsq(dict(top)).items()}
    for index, w in enumerate(layers):
        out.update({f"{index}/{k}": v for k, v in _sumsq(dict(w)).items()})
    return out


def named_sumsq(top, layers) -> Dict[str, float]:
    """`traced_sumsq` of arrays that are there, a layer at a time, as
    floats."""
    out = {f"top/{k}": v for k, v in leaf_sumsq(dict(top)).items()}
    for index, w in enumerate(layers):
        out.update({f"{index}/{k}": v
                    for k, v in leaf_sumsq(dict(w)).items()})
    return {k: float(v) for k, v in jax.device_get(out).items()}


class _Pieces:
    """The reference's functions, jitted once each: forward, and the
    pull-back at a saved input."""

    def __init__(self, parts: Mapping[str, Any]):
        self.parts = parts
        self._jitted: Dict[Any, Any] = {}

    def _once(self, key, make):
        if key not in self._jitted:
            self._jitted[key] = jax.jit(make())
        return self._jitted[key]

    def embed(self, top, tokens):
        return self._once("embed", lambda: self.parts["embed"])(top, tokens)

    def embed_back(self, top, tokens, dx):
        def make():
            def back(top, tokens, dx):
                _, pull = jax.vjp(lambda t: self.parts["embed"](t, tokens),
                                  top)
                return pull(dx)[0]
            return back
        return self._once("embed_back", make)(top, tokens, dx)

    def block(self, index, w, x, fraction):
        fn = self.parts["block"](index)
        return self._once(("block", id(fn)), lambda: fn)(w, x, fraction)

    def block_back(self, index, w, x, fraction, dy, dterms):
        fn = self.parts["block"](index)

        def make():
            def back(w, x, fraction, dy, dterms):
                def forward(w, x):
                    y, terms, _ = fn(w, x, fraction)
                    return y, terms
                _, pull = jax.vjp(forward, w, x)
                return pull((dy, dterms))
            return back
        return self._once(("block_back", id(fn)), make)(
            w, x, fraction, dy, dterms)

    def head_back(self, top, x, tokens, weight, z_loss):
        """(ce, lse2) and the pull-back of weight x (ce + z_loss x lse2)."""
        def make():
            def back(top, x, tokens, weight, z_loss):
                (ce, lse2), pull = jax.vjp(
                    lambda t, x: self.parts["head"](t, x, tokens), top, x)
                dtop, dx = pull((weight, weight * z_loss))
                return ce, lse2, dtop, dx
            return back
        return self._once("head_back", make)(top, x, tokens, weight, z_loss)


def _adamw_leaf(p, g, m, v, scale, lr, c1, c2, adamw):
    b1, b2 = adamw["b1"], adamw["b2"]
    g = g * scale
    m = b1 * m + (1.0 - b1) * g
    v = b2 * v + (1.0 - b2) * g * g
    update = (m / c1) / (jnp.sqrt(v / c2) + adamw["eps"])
    return p - lr * (update + adamw["weight_decay"] * p), m, v


def follow(reference, config: Mapping[str, Any],
           start: Callable[[], Any], batches: Sequence[np.ndarray], devices,
           *, operands=None, fault: Optional[str] = None) -> Dict[str, Any]:
    """Drive the reference through `len(batches)` training steps from the
    parameters `start()` gives. Returns `steps` (per step `loss`, `ce`,
    every term, `grad_norm`), every leaf's sum of squares of the first
    gradient (`grad_sumsq`, by `named_sumsq`'s names) and of the parameters'
    change over the steps (`change_sumsq`) and, where the model routes, the
    first batch's `counts` [L, E] and `chosen` [L, T, k].

    `fault` plants one, for the controls only (`tests/`, and the builder's
    readings): "half_batch" takes the gradient over the first half of each
    batch's rows, "no_exchange" over the rows one device of several is given
    (the gradient never summed over the devices), "no_bias_correction"
    leaves Adam's 1 / (1 - b1^t) out."""
    group = config["reference"]
    objective = dict(group["objective"])
    adamw = {k: (float(v) if k not in ("warmup_steps", "total_steps") else v)
             for k, v in group["adamw"].items()}
    z_loss = float(objective.pop("z_loss", 0.0))
    pieces = _Pieces(reference.training(config, operands))
    routes = bool(pieces.parts["routes"])

    mesh = Mesh(np.array(devices), ("rows",))
    by_rows = NamedSharding(mesh, PartitionSpec("rows"))
    everywhere = NamedSharding(mesh, PartitionSpec())
    n_dev = len(devices)

    def home(index):
        return devices[index % n_dev]

    # a copy of its own (an update donates it) on each layer's home device
    copy = jax.jit(lambda tree: {k: jnp.copy(v.astype(jnp.float32))
                                 for k, v in tree.items()})
    gc.collect()        # what a cycle still holds on the device goes first
    top, layers = start()
    top = jax.device_put(copy(dict(top)), devices[0])
    layers = [jax.device_put(copy(dict(w)), home(i))
              for i, w in enumerate(layers)]
    n_layers = len(layers)

    def zeros(tree):    # where the leaf lies (a jitted zeros_like has no
        # input left to follow and lands on the first device)
        return {k: jnp.zeros(v.shape, v.dtype, device=v.sharding)
                for k, v in tree.items()}

    groups = [top] + layers                     # 0: top, 1 + i: layer i
    moment1 = [zeros(g) for g in groups]
    moment2 = [zeros(g) for g in groups]
    add = jax.jit(lambda a, b: {k: a[k] + b[k] for k in a},
                  donate_argnums=0)

    def update(p, g, m, v, scale, lr, c1, c2):
        out = {k: _adamw_leaf(p[k], g[k], m[k], v[k], scale, lr, c1, c2,
                              adamw) for k in p}
        return tuple({k: o[i] for k, o in out.items()} for i in range(3))

    update = jax.jit(update, donate_argnums=(0, 2, 3))

    rows_per_pass = int(group.get("rows_per_pass", 1)) * n_dev
    steps: List[Dict[str, Any]] = []
    result: Dict[str, Any] = {"steps": steps}

    with jax.default_matmul_precision(_PRECISION):
        for t, tokens in enumerate(batches, start=1):
            tokens = np.asarray(tokens, np.int32)
            if fault == "half_batch":
                tokens = tokens[: max(n_dev, tokens.shape[0] // 2)]
            elif fault == "no_exchange":
                tokens = tokens[: max(n_dev, tokens.shape[0] // n_dev)]
            n_rows = tokens.shape[0]
            chunks = [jax.device_put(tokens[i:i + rows_per_pass], by_rows)
                      for i in range(0, n_rows, rows_per_pass)]
            fractions: List[Any] = [None] * n_layers
            if routes:
                counts = [0] * n_layers
                chosen: List[List[Any]] = [[] for _ in range(n_layers)]
                for chunk in chunks:
                    x = pieces.embed(jax.device_put(top, everywhere), chunk)
                    for i, w in enumerate(layers):
                        x, _, facts = pieces.block(
                            i, jax.device_put(w, everywhere), x, None)
                        counts[i] = counts[i] + facts["counts"]
                        if t == 1:
                            chosen[i].append(np.asarray(facts["chosen"]))
                n_tokens = tokens.size
                fractions = [c.astype(jnp.float32) / n_tokens for c in counts]
                if t == 1:
                    result["counts"] = np.stack(
                        [np.asarray(c) for c in counts])
                    result["chosen"] = np.stack(
                        [np.concatenate(c, axis=0) for c in chosen])

            grads = [zeros(g) for g in groups]
            terms_sum: Dict[str, float] = {}
            for chunk in chunks:
                weight = jnp.float32(chunk.shape[0] / n_rows)
                top_all = jax.device_put(top, everywhere)
                inputs = [pieces.embed(top_all, chunk)]
                terms = []
                for i, w in enumerate(layers):
                    x, layer_terms, _ = pieces.block(
                        i, jax.device_put(w, everywhere), inputs[-1],
                        fractions[i])
                    inputs.append(x)
                    terms.append(layer_terms)
                ce, lse2, dtop, dx = pieces.head_back(
                    top_all, inputs.pop(), chunk, weight, jnp.float32(z_loss))
                parts = {"ce": ce, "z_loss": lse2}
                for name in objective:
                    parts[name] = sum(tm[name] for tm in terms) / n_layers
                for name, value in parts.items():
                    terms_sum[name] = (terms_sum.get(name, 0.0)
                                       + float(weight) * float(value))
                for i in reversed(range(n_layers)):
                    dterms = {name: weight * jnp.float32(
                        objective[name] / n_layers) for name in terms[i]}
                    dw, dx = pieces.block_back(
                        i, jax.device_put(layers[i], everywhere),
                        inputs.pop(), fractions[i], dx, dterms)
                    grads[1 + i] = add(grads[1 + i],
                                       jax.device_put(dw, home(i)))
                dtop = add(dtop, pieces.embed_back(top_all, chunk, dx))
                grads[0] = add(grads[0], jax.device_put(dtop, devices[0]))

            loss = (terms_sum["ce"] + z_loss * terms_sum["z_loss"]
                    + sum(objective[n] * terms_sum[n] for n in objective))
            sumsq = named_sumsq(grads[0], grads[1:])
            norm = math.sqrt(sum(sumsq.values()))
            steps.append({"loss": loss, "grad_norm": norm, **terms_sum})
            if t == 1:
                result["grad_sumsq"] = sumsq

            clip = float(adamw["clip"])
            scale = 1.0 if norm < clip else clip / norm
            c1 = 1.0 if fault == "no_bias_correction" else (
                1.0 - adamw["b1"] ** t)
            c2 = 1.0 - adamw["b2"] ** t
            lr = learning_rate(adamw, t - 1)
            for j in range(len(groups)):
                groups[j], moment1[j], moment2[j] = update(
                    groups[j], grads[j], moment1[j], moment2[j],
                    jnp.float32(scale), jnp.float32(lr), jnp.float32(c1),
                    jnp.float32(c2))
            top, layers = groups[0], groups[1:]

    del grads, moment1, moment2, groups
    gc.collect()        # what a cycle still holds on the device goes first
    result["change_sumsq"] = _named_change_sumsq(top, layers, *start())
    return result


_change_sumsq = jax.jit(lambda a, b: {
    k: jnp.sum(jnp.square(a[k] - b[k].astype(jnp.float32))) for k in a})


def _named_change_sumsq(top, layers, top0, layers0) -> Dict[str, float]:
    """Every leaf's sum of squares of the parameters after the steps less
    the ones they started from, handed over again one layer at a time."""
    out = {f"top/{k}": v for k, v in _change_sumsq(
        top, jax.device_put(dict(top0), next(iter(top.values())).sharding)
    ).items()}
    for index, (w, w0) in enumerate(zip(layers, layers0)):
        w0 = jax.device_put(dict(w0), next(iter(w.values())).sharding)
        out.update({f"{index}/{k}": v
                    for k, v in _change_sumsq(w, w0).items()})
    return {k: float(v) for k, v in jax.device_get(out).items()}
