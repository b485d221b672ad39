"""Sharded host→device data pipeline.

Deterministic epoch shuffling (seed fold-in), global-batch sharding over the
mesh data axes, and a one-step prefetch thread (double buffering) so host
batch assembly overlaps device compute — the data-pipeline substrate for the
streaming miner (DESIGN.md §9).
"""

from __future__ import annotations

import queue
import threading
import time
from contextlib import nullcontext

import jax
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P


class ShardedBatchIterator:
    """Wraps a host batch generator; device_puts each pytree leaf with the
    given sharding and prefetches `prefetch` batches on a worker thread.

    ``close()`` actually terminates the worker: the worker's queue puts are
    timeout-loops that re-check the stop event (a plain blocking ``put``
    would deadlock forever on a full queue once the consumer stops taking),
    and ``close()`` drains the queue so a mid-put worker unblocks, then
    joins the thread. Iteration after ``close()`` raises StopIteration.
    Context-managed; exhausting the iterator also joins the worker.

    ``place_span``: a factory of the context that each placement runs in,
    on the worker thread (the caller's span; none by default).
    """

    def __init__(self, gen, mesh, spec_fn, prefetch: int = 2, place_span=nullcontext):
        self._gen = gen
        self._mesh = mesh
        self._place_span = place_span
        self._spec_fn = spec_fn  # leaf_path-free: array -> PartitionSpec
        self._q: queue.Queue = queue.Queue(maxsize=prefetch)
        self._stop = threading.Event()
        self._err: BaseException | None = None
        self._thread = threading.Thread(target=self._worker, daemon=True)
        self._thread.start()

    def _place(self, batch):
        if self._mesh is None:
            return jax.tree.map(jax.numpy.asarray, batch)
        return jax.tree.map(
            lambda x: jax.device_put(x, NamedSharding(self._mesh, self._spec_fn(x))), batch
        )

    def _put(self, item) -> bool:
        """Timeout-put loop: returns False (item dropped) once stopped."""
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def _worker(self):
        try:
            for batch in self._gen:
                if self._stop.is_set():
                    return
                with self._place_span():
                    placed = self._place(batch)
                if not self._put(placed):
                    return
        except BaseException as e:  # surface generator/placement failures to
            self._err = e           # the consumer — NOT a clean end-of-stream
        finally:
            # end-of-stream sentinel: wait politely while the consumer is
            # live; only force room (dropping a stale batch) once stopped
            while True:
                try:
                    self._q.put(None, timeout=0.05)
                    break
                except queue.Full:
                    if self._stop.is_set():
                        try:
                            self._q.get_nowait()
                        except queue.Empty:
                            pass

    def __iter__(self):
        return self

    def __next__(self):
        item = self._q.get()
        if item is None:
            self._thread.join()
            if self._err is not None:
                raise self._err
            raise StopIteration
        return item

    def close(self, timeout: float = 10.0):
        """Stop the worker, drain buffered batches, and join the thread."""
        self._stop.set()
        deadline = time.monotonic() + timeout
        while self._thread.is_alive() and time.monotonic() < deadline:
            try:   # unblock a worker waiting in its timeout-put
                self._q.get_nowait()
            except queue.Empty:
                pass
            self._thread.join(timeout=0.05)
        while True:   # drop stale buffered batches
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        try:   # guarantee subsequent __next__ sees end-of-stream
            self._q.put_nowait(None)
        except queue.Full:
            pass

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        self.close()


def batch_spec(data_axes=("data",)):
    """PartitionSpec factory: shard axis 0 (global batch) over the data axes."""

    def fn(x):
        return P(data_axes, *([None] * (np.ndim(x) - 1)))

    return fn
