"""Host data pipeline: background prefetch + straggler mitigation.

The paper's Fig. 5 shows exposed I/O of ~20% on W&D-class models; the fix is
a deep enough prefetch queue plus *backup batches*: if the generator thread
misses its deadline (slow remote read / skewed shard), the iterator yields
the most recent spare instead of stalling the whole synchronous step. A
producer that fails is never mistaken for the end of the stream: its error
reaches the consumer. The consumer's wait (``batch_wait``) and the
producer's make and device put (``batch_make``, ``batch_put``) are host
spans on the profiler's timeline (``repro.obs``).
"""
from __future__ import annotations

import queue
import threading
import time
from typing import Any, Callable, Dict, Iterator, Optional

import jax

from repro import obs

_END = object()


class BatchProducerError(RuntimeError):
    """The prefetch thread's batch generator or device put raised; the
    original exception is the ``__cause__``."""


class Prefetcher:
    def __init__(self, gen: Iterator, depth: int = 4, timeout_s: float = 5.0,
                 put_fn: Optional[Callable] = None):
        self.gen = gen
        self.q: "queue.Queue" = queue.Queue(maxsize=depth)
        self.timeout_s = timeout_s
        self.put_fn = put_fn or (lambda x: x)
        self.backup: Any = None
        self.stats = {"produced": 0, "backup_served": 0}
        self.error: Optional[BaseException] = None  # what killed the producer
        self._stop = False
        self._thread = threading.Thread(target=self._work, daemon=True)
        self._thread.start()

    def _work(self):
        try:
            gen = iter(self.gen)
            while True:
                with obs.span(obs.BATCH_MAKE):
                    item = next(gen, _END)
                if item is _END or self._stop:
                    return
                with obs.span(obs.BATCH_PUT):
                    out = self.put_fn(item)
                # bounded put that stays responsive to close(): a blocking
                # q.put() on a full queue would never observe _stop and the
                # worker thread would hang forever after close()
                while not self._stop:
                    try:
                        self.q.put(out, timeout=0.1)
                        break
                    except queue.Full:
                        continue
                if self._stop:
                    return
                self.stats["produced"] += 1
        except BaseException as e:  # noqa: BLE001 — handed to the consumer
            self.error = e

    def __iter__(self):
        return self

    def __next__(self):
        """The next batch. A producer that raised re-raises here (as
        ``BatchProducerError`` from its exception); one that is alive but
        late past ``timeout_s`` yields the last batch again (straggler
        mitigation), or raises ``TimeoutError`` when there is none yet. Only
        a generator that ended normally ends the iteration."""
        with obs.span(obs.BATCH_WAIT):
            return self._next()

    def _next(self):
        deadline = time.monotonic() + self.timeout_s
        while True:
            try:
                item = self.q.get(timeout=min(0.05, self.timeout_s))
            except queue.Empty:
                if not self._thread.is_alive():
                    try:  # an item put just before the worker returned
                        item = self.q.get_nowait()
                    except queue.Empty:
                        if self.error is not None:
                            raise BatchProducerError(
                                "batch producer thread died") from self.error
                        raise StopIteration from None
                elif time.monotonic() < deadline:
                    continue
                elif self.backup is not None:
                    self.stats["backup_served"] += 1
                    return self.backup
                else:
                    raise TimeoutError(
                        f"no batch within {self.timeout_s}s and no earlier "
                        "batch to serve in its place") from None
            self.backup = item
            return item

    def close(self, join_timeout_s: float = 5.0):
        """Stop the worker and reap it: raise the stop flag, then drain the
        queue until the (possibly put-blocked) worker observes the flag and
        exits. Idempotent; the thread is daemonic, so a generator stuck
        inside ``next()`` past the timeout cannot wedge interpreter exit."""
        self._stop = True
        deadline = time.monotonic() + join_timeout_s
        while self._thread.is_alive() and time.monotonic() < deadline:
            try:  # make room so a blocked put() can complete and re-check
                self.q.get_nowait()
            except queue.Empty:
                pass
            self._thread.join(timeout=0.05)


class ReplayableStream:
    """Seekable wrapper over a positional stream factory.

    ``make_iter(start)`` must return an iterator whose first item is the
    batch at absolute position ``start`` (see ``synthetic.batch_stream``'s
    per-index seeding). The wrapper tracks the current position so a
    supervisor can ``seek(step)`` after a checkpoint rollback and replay the
    exact batches the failed stretch consumed — without it, every batch
    between the checkpoint step and the failure step is silently skipped.

    ``rewrap(make_iter)`` swaps the factory at the current position (e.g.
    re-binding device placement after an elastic reshard changes the mesh).
    Underlying iterators with a ``close()`` (Prefetcher) are closed on
    seek/rewrap/close so their worker threads are reaped.
    """

    def __init__(self, make_iter: Callable[[int], Iterator], start: int = 0):
        self._make = make_iter
        self.pos = start
        self._it: Optional[Iterator] = None
        self._closed_stats: Dict[str, int] = {}  # counters of closed iterators

    def _open(self):
        if self._it is None:
            self._it = self._make(self.pos)
        return self._it

    def __iter__(self):
        return self

    def __next__(self):
        item = next(self._open())
        self.pos += 1
        return item

    def seek(self, step: int) -> "ReplayableStream":
        if step != self.pos or self._it is None:
            self.close()
            self.pos = step
        return self

    def rewrap(self, make_iter: Callable[[int], Iterator]) -> "ReplayableStream":
        self.close()
        self._make = make_iter
        return self

    @property
    def stats(self) -> Dict[str, int]:
        """Prefetcher counters (``produced``, ``backup_served``) summed over
        every iterator this stream has opened."""
        out = dict(self._closed_stats)
        for k, v in getattr(self._it, "stats", {}).items():
            out[k] = out.get(k, 0) + v
        return out

    def close(self):
        it, self._it = self._it, None
        for k, v in getattr(it, "stats", {}).items():
            self._closed_stats[k] = self._closed_stats.get(k, 0) + v
        if it is not None and hasattr(it, "close"):
            it.close()


def device_put_stream(gen: Iterator, mesh, specs_fn: Callable, depth: int = 2
                      ) -> Iterator:
    """Prefetch + async device_put with the right shardings."""
    from repro.dist.sharding import to_named

    def put(batch):
        return jax.device_put(batch, to_named(mesh, specs_fn(batch)))

    return Prefetcher(gen, depth=depth, put_fn=put)
