"""Faults planted in the program for `test_serve_loop.py`: each function
breaks `ray_tpu` underneath a CPU rehearsal of a served cell, in the
replica, before the deployment is built (`loops/serve.py::Scorer` calls the
one named by `rehearsal["patch"]`, which no command line can set). Nothing
here is reachable from a run of the benchmark."""

from __future__ import annotations

import jax.numpy as jnp


def answers_swapped():
    """Two requests' answers swapped inside a batch (`serve/batching.py`:
    the collector hands the first two members each other's result)."""
    from ray_tpu.serve import batching
    real = batching._Batcher.__init__

    def init(self, fn, max_batch_size, timeout_s):
        def swapped(args):
            results = list(fn(args))
            if len(results) >= 2:
                results[0], results[1] = results[1], results[0]
            return results
        real(self, swapped, max_batch_size, timeout_s)
    batching._Batcher.__init__ = init


def neighbour_leak():
    """A row's scores depend on its neighbour's tokens: every row's keys
    and values carry half of the row's before it (at seeded weights the
    attention is close to uniform, so keys alone move little: 0.026 at the
    toy width, under the limit; the values carry the leak)."""
    from ray_tpu.models import gpt
    real = gpt.dot_product_attention

    def leaky(q, k, v, **kw):
        return real(q, k + 0.5 * jnp.roll(k, 1, axis=0),
                    v + 0.5 * jnp.roll(v, 1, axis=0), **kw)
    gpt.dot_product_attention = leaky


def positions_shifted():
    """Positions shifted by one: token i is embedded at position i + 1."""
    from ray_tpu.models.gpt import GPT
    real = GPT.forward_with_aux

    def shifted(self, params, tokens, positions=None):
        last = self.config.max_seq_len - 1
        positions = jnp.broadcast_to(jnp.minimum(
            jnp.arange(tokens.shape[1], dtype=jnp.int32) + 1, last),
            tokens.shape)
        return real(self, params, tokens, positions)
    GPT.forward_with_aux = shifted


def last_rows_truncated(rows: int = 4):
    """The largest row bucket's rows truncated: of a call with `rows` rows
    only the first is computed, the others' logits are nought."""
    from ray_tpu.models.gpt import GPT
    real = GPT.apply

    def truncated(self, params, tokens, positions=None):
        logits = real(self, params, tokens, positions)
        if tokens.shape[0] != rows:
            return logits
        return logits * (jnp.arange(rows) < 1)[:, None, None]
    GPT.apply = truncated
