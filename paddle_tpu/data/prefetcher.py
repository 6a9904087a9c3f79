"""Async host→device batch feeder.

The reference's trainers never block on input: `DataFeed` threads parse
and stage batches while the device consumes the previous one
(`/root/reference/paddle/fluid/framework/data_feed.h` channels,
`MiniBatchGpuPack` data_feed.h:528 staging GPU batches ahead). Here the
same double-buffering wraps any host-batch iterator: a daemon thread
applies ``transform`` (e.g. ``jnp.asarray`` / ``jax.device_put``) and
keeps ``depth`` device-resident batches in flight, so the train loop's
dispatch overlaps the H2D transfer of the next batch.
"""

from __future__ import annotations

import queue
import threading
import weakref
from typing import Any, Callable, Iterable, Iterator, Optional

__all__ = ["DevicePrefetcher", "device_prefetch"]

_STOP = object()


class DevicePrefetcher:
    """Iterate ``source`` with ``depth`` transformed batches in flight."""

    def __init__(self, source: Iterable, depth: int = 2,
                 transform: Optional[Callable[[Any], Any]] = None) -> None:
        q: queue.Queue = queue.Queue(maxsize=max(depth, 1))
        stop = threading.Event()
        err_box: list = []
        self._q = q
        self._err_box = err_box
        self._stop = stop

        def run() -> None:  # closes over locals ONLY — never `self`, so
            try:            # an abandoned prefetcher can be GC'd
                for item in source:
                    if stop.is_set():
                        return
                    if transform is not None:
                        item = transform(item)
                    while True:
                        try:
                            q.put(item, timeout=0.5)
                            break
                        except queue.Full:
                            if stop.is_set():
                                return
            except BaseException as e:  # noqa: BLE001 — re-raised in consumer
                err_box.append(e)
            finally:
                while True:  # always deliver the terminator
                    try:
                        q.put(_STOP, timeout=0.5)
                        return
                    except queue.Full:
                        if stop.is_set():
                            return

        self._thread = threading.Thread(target=run, daemon=True,
                                        name="device-prefetcher")
        self._thread.start()
        # abandoned mid-stream → stop the producer (it would otherwise
        # spin forever pinning `depth` device batches)
        weakref.finalize(self, stop.set)

    def __iter__(self) -> Iterator:
        return self

    def __next__(self):
        item = self._q.get()
        if item is _STOP:
            if self._err_box:
                raise self._err_box[0]
            raise StopIteration
        return item

    def close(self) -> None:
        """Stop early; drains so the producer can exit."""
        self._stop.set()
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass


def device_prefetch(source: Iterable, depth: int = 2):
    """Prefetch with the default transform: every array leaf of a
    tuple/list/dict batch goes to the default device via jnp.asarray."""
    import jax.numpy as jnp
    import numpy as np

    def to_device(item):
        if isinstance(item, (tuple, list)):
            return type(item)(to_device(x) for x in item)
        if isinstance(item, dict):
            return {k: to_device(v) for k, v in item.items()}
        if isinstance(item, np.ndarray):
            return jnp.asarray(item)
        return item

    return DevicePrefetcher(source, depth=depth, transform=to_device)
