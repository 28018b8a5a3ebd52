"""Streaming dataset shards for distributed ingest.

Reference analogue: ``python/ray/train/_internal/data_config.py`` +
``DataIterator`` (``python/ray/data/iterator.py``): a Dataset is split
into N live streams, one consumed by each training worker while the
read/transform pipeline keeps running on the cluster.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, Iterator, List, Optional

import numpy as np

from .. import get
from .._private import telemetry
from ..util import tracing
from .block import Block, block_concat, block_num_rows, block_slice

# Spans sit around the statements that wait or copy, never across a
# ``yield``: a generator's frame is suspended inside the consumer's time.
M_FEED_WAIT = telemetry.define(
    "histogram", "rtpu_data_feed_wait_seconds",
    "Seconds a consumer of a streaming shard waited for one block: the "
    "shard queue's get (stage=queue) and the object's fetch (stage=fetch)")
M_FEED_TO_DEVICE = telemetry.define(
    "histogram", "rtpu_data_feed_to_device_seconds",
    "Seconds iter_device_batches spent turning one batch into jax Arrays "
    "(asarray and device_put)")
M_FEED_BATCHES = telemetry.define(
    "counter", "rtpu_data_feed_batches_total",
    "Device batches yielded by iter_device_batches")
M_FEED_BYTES = telemetry.define(
    "counter", "rtpu_data_feed_bytes_total",
    "Bytes of the device batches yielded by iter_device_batches")


class DataIterator:
    """One worker's shard of a streaming split: block refs arrive
    through a bounded queue (backpressure: the driver-side feeder stalls
    when consumers lag). Picklable — pass into remote workers."""

    def __init__(self, queue):
        self._queue = queue

    # ------------------------------------------------------------ blocks
    def iter_block_refs(self) -> Iterator[Any]:
        while True:
            with tracing.timed_span("data::block_wait", M_FEED_WAIT,
                                    (("stage", "queue"),)):
                item = self._queue.get(block=True, timeout=None)
            if item is None:
                return
            if isinstance(item, tuple) and item[0] == "__stream_error__":
                # the pipeline died upstream: surface it instead of
                # hanging the consumer on a stream that will never end
                raise RuntimeError(
                    f"dataset stream failed upstream: {item[1]}")
            # refs ride WRAPPED in a 1-list: a bare ObjectRef queue item
            # would be auto-resolved into its value at the actor call
            # boundary (nested refs pass through as borrowed refs)
            yield item[0]

    def shutdown(self) -> None:
        """Tear down this shard's queue actor (trainer teardown between
        elastic restarts); the feeder thread exits on its next put."""
        try:
            self._queue.shutdown()
        except Exception:
            pass

    def iter_blocks(self) -> Iterator[Block]:
        for ref in self.iter_block_refs():
            with tracing.timed_span("data::block_get", M_FEED_WAIT,
                                    (("stage", "fetch"),)):
                block = get(ref)
            yield block

    # ----------------------------------------------------------- batches
    def iter_batches(self, *, batch_size: int = 256,
                     drop_last: bool = False) -> Iterator[Block]:
        """Re-batch across block boundaries to exactly batch_size."""
        carry: Optional[Block] = None
        for blk in self.iter_blocks():
            if not blk:
                continue
            if carry:
                blk = block_concat([carry, blk])
                carry = None
            n = block_num_rows(blk)
            lo = 0
            while n - lo >= batch_size:
                yield block_slice(blk, lo, lo + batch_size)
                lo += batch_size
            if lo < n:
                carry = block_slice(blk, lo, n)
        if carry and not drop_last:
            yield carry

    def iter_device_batches(self, *, batch_size: int = 256,
                            sharding: Optional[Any] = None,
                            dtype: Optional[Any] = None
                            ) -> Iterator[Dict[str, Any]]:
        """Batches as jax Arrays, optionally placed with ``sharding``
        (e.g. the mesh's batch sharding for SPMD input). Partial final
        batches are dropped — jit'd train steps need static shapes."""
        import jax
        import jax.numpy as jnp

        for batch in self.iter_batches(batch_size=batch_size,
                                       drop_last=True):
            out = {}
            with tracing.timed_span("data::to_device", M_FEED_TO_DEVICE):
                for k, v in batch.items():
                    arr = jnp.asarray(v, dtype=dtype) if dtype is not None \
                        else jnp.asarray(v)
                    if sharding is not None:
                        arr = jax.device_put(arr, sharding)
                    out[k] = arr
            telemetry.counter_inc(M_FEED_BATCHES)
            telemetry.counter_inc(M_FEED_BYTES, float(sum(
                a.nbytes for a in out.values())))
            yield out

    def __reduce__(self):
        return (DataIterator, (self._queue,))


def streaming_split(dataset, n: int, *,
                    queue_size: int = 4) -> List[DataIterator]:
    """Split a dataset into ``n`` concurrently-consumable streams.

    A driver-side feeder thread drives the dataset's streaming executor
    and deals block refs round-robin into n bounded queues; total
    cluster residency stays (operator windows + n*queue_size) blocks.
    Round-robin + bounded queues couple the shards' pace — which is what
    lockstep SPMD training wants (every rank steps together anyway).
    """
    from ..util.queue import Queue

    if n < 1:
        raise ValueError("streaming_split needs n >= 1")
    queues = [Queue(maxsize=queue_size) for _ in range(n)]

    def feed() -> None:
        end_item: Any = None
        try:
            for i, ref in enumerate(dataset.streaming_block_refs()):
                queues[i % n].put([ref], block=True, timeout=None)
        except Exception as e:  # noqa: BLE001 — delivered to consumers
            # the PIPELINE failed (bad file, missing optional dep, task
            # error): every consumer must see the error, not hang on a
            # stream that never ends
            end_item = ("__stream_error__", repr(e))
        from ..util.queue import Full
        # The sentinel MUST land: a consumer that is merely slow
        # (bounded queue full across a long train step) raises Full on
        # timeout — keep retrying. Round-robin over the still-pending
        # queues so one permanently-full queue (dead consumer, live
        # queue actor) can't starve the others of their sentinel. Drop
        # a queue only when its actor is gone (shutdown/teardown).
        pending = list(queues)
        while pending:
            still = []
            for q in pending:
                try:
                    q.put(end_item, block=True, timeout=2.0)
                except Full:
                    still.append(q)
                except Exception:
                    pass
            pending = still

    threading.Thread(target=feed, daemon=True,
                     name="rtpu-data-feeder").start()
    return [DataIterator(q) for q in queues]
