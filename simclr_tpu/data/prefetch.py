"""Background prefetcher: overlap batch assembly + H2D with the device step.

The capability the reference buys with ``DataLoader(num_workers=8,
pin_memory=True)`` (``/root/reference/main.py:170-173``) — keeping the
accelerator fed while the CPU prepares the next batch — reshaped for SPMD:
one daemon thread per process assembles upcoming batches (native C++ row
gather, ``simclr_tpu/native``) and ``device_put``s them so the transfer
overlaps the in-flight XLA step. Queue depth 2 is enough: JAX dispatch is
async, so the host loop runs ahead of the device by design; the prefetcher
just keeps gather+transfer off the critical path.

The queue-and-drain discipline here is the template the serving batcher
(``simclr_tpu/serve/batcher.py``) reuses: every blocking queue operation is
bounded by a timeout against a liveness flag, so a wedged producer can
neither deadlock the consumer nor hang interpreter shutdown.
"""

from __future__ import annotations

import queue
import threading
import time
from collections.abc import Iterator
from typing import Any

from simclr_tpu.obs.trace import recorder

_SENTINEL = object()

# bound on every internal blocking queue op: long enough to stay off the hot
# path, short enough that stop/done flags are observed promptly
_POLL_S = 0.1


class Prefetcher:
    """Wraps any batch iterator; yields the same batches, prefetched.

    Exceptions in the worker are re-raised in the consumer's ``__next__``
    (after any batches produced before the failure — they are valid work).
    Always used as a context manager or fully drained; ``close()`` stops
    early and returns within its join timeout even if the producer is
    wedged inside the wrapped iterator.
    """

    def __init__(self, iterator: Iterator[Any], depth: int = 2):
        self._q: queue.Queue = queue.Queue(maxsize=max(depth, 1))
        self._error: BaseException | None = None
        self._stop = threading.Event()
        self._done = threading.Event()

        def worker():
            try:
                for item in iterator:
                    if self._stop.is_set():
                        return
                    # bounded put: a consumer that stopped reading (close(),
                    # crash) must not leave this thread blocked forever on a
                    # full queue
                    while not self._stop.is_set():
                        try:
                            self._q.put(item, timeout=_POLL_S)
                            break
                        except queue.Full:
                            continue
            except BaseException as e:  # noqa: BLE001 - relayed to consumer
                self._error = e
            finally:
                # publish completion BEFORE the sentinel: if the queue is
                # full the sentinel is dropped and __next__ falls back to
                # the done flag, so termination (and the error) still
                # reaches the consumer
                self._done.set()
                try:
                    self._q.put_nowait(_SENTINEL)
                except queue.Full:
                    pass

        self._thread = threading.Thread(target=worker, daemon=True, name="prefetch")
        self._thread.start()

    def __iter__(self):
        return self

    def __next__(self):
        # the consumer blocked on the queue: how long the host loop waited
        # for its input
        with recorder().span("input_wait"):
            return self._next()

    def _next(self):
        while True:
            try:
                item = self._q.get(timeout=_POLL_S)
            except queue.Empty:
                if self._done.is_set():
                    item = _SENTINEL  # sentinel was dropped on a full queue
                else:
                    continue
            if item is _SENTINEL:
                self._thread.join(timeout=5)
                if self._error is not None:
                    raise self._error
                raise StopIteration
            return item

    def close(self, timeout: float = 5.0) -> None:
        """Stop the worker and join it, draining the queue so a producer
        blocked on a full queue can exit. Returns after at most ``timeout``
        seconds: the worker is a daemon thread, so a producer wedged inside
        the wrapped iterator (e.g. a hung device transfer) is abandoned
        rather than allowed to hang interpreter shutdown."""
        self._stop.set()
        deadline = time.monotonic() + timeout
        while self._thread.is_alive():
            try:
                while True:
                    self._q.get_nowait()
            except queue.Empty:
                pass
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                break
            self._thread.join(timeout=min(_POLL_S, remaining))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def prefetch(iterator: Iterator[Any], depth: int = 2) -> Prefetcher:
    return Prefetcher(iterator, depth=depth)
