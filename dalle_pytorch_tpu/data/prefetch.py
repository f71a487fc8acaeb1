"""Host-side input/compute overlap.

The reference overlaps input with compute via DataLoader worker processes
(`/root/reference/train_dalle.py:309-316`). The TPU-native equivalent here
is a background assembly thread + bounded queue: while step N runs on
device, batch N+1 is decoded/tokenized/`device_put` on the host, so the
chip never idles on PIL decode. One thread is enough — batch assembly is
numpy/PIL work that releases the GIL, and `device_put` overlaps with device
execution by design.

`Prefetcher.wait_fraction` is the measured input-boundedness: the share of
wall time the consumer spent blocked on the queue. ~0 means fully
overlapped; ~1 means the input pipeline is the bottleneck (add workers or
precompute tokens).
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Any, Callable, Iterable, Iterator, Optional

from dalle_pytorch_tpu.obs.tracing import host_span


class _Sentinel:
    pass


_DONE = _Sentinel()


class Prefetcher:
    """Wrap a batch iterator; assemble + transform batches ahead of use.

    transform: host->device assembly (e.g. jnp.asarray + device_put with
    shardings) run in the background thread. depth bounds host memory:
    at most `depth` assembled batches exist beyond the one in use.
    """

    def __init__(
        self,
        batches: Iterable[Any],
        transform: Optional[Callable[[Any], Any]] = None,
        depth: int = 2,
    ):
        self._q: queue.Queue = queue.Queue(maxsize=max(1, depth))
        self._transform = transform
        self._err: Optional[BaseException] = None
        self._wait_s = 0.0
        self._t_start = time.perf_counter()
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._produce, args=(iter(batches),), daemon=True
        )
        self._thread.start()

    def _produce(self, it: Iterator[Any]) -> None:
        try:
            while True:
                with host_span("input.assemble"):
                    raw = next(it, _DONE)
                    if raw is _DONE:
                        break
                    batch = self._transform(raw) if self._transform else raw
                while not self._stop.is_set():
                    try:
                        self._q.put(batch, timeout=0.1)
                        break
                    except queue.Full:
                        continue
                if self._stop.is_set():
                    return
        except BaseException as e:  # propagate into the consumer
            self._err = e
        finally:
            while not self._stop.is_set():
                try:
                    self._q.put(_DONE, timeout=0.1)
                    return
                except queue.Full:
                    continue

    def __iter__(self):
        return self

    def __next__(self):
        t0 = time.perf_counter()
        with host_span("input.wait"):
            item = self._q.get()
        self._wait_s += time.perf_counter() - t0
        if isinstance(item, _Sentinel):
            if self._err is not None:
                err, self._err = self._err, None
                raise err
            raise StopIteration
        return item

    def close(self) -> None:
        """Stop the producer early (break out of a partial epoch)."""
        self._stop.set()
        # drain so a blocked producer can observe the stop flag
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout=5.0)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):
        # last-resort cleanup if the consumer abandoned iteration (e.g. the
        # train step raised): unblock the producer so it stops pinning
        # device-resident prefetched batches
        try:
            self._stop.set()
        except AttributeError:  # partially-constructed instance
            pass

    @property
    def wait_fraction(self) -> float:
        """Fraction of consumer wall time spent waiting on input."""
        total = time.perf_counter() - self._t_start
        return self._wait_s / total if total > 0 else 0.0
