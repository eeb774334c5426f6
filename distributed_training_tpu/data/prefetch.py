"""Device prefetch: overlap host augmentation + transfer with device compute.

The reference's loop blocks on ``images.to(device)`` inside the hot loop
(``resnet/pytorch_ddp/ddp_train.py:62-63``) and leans on worker processes
(``num_workers``) only for host-side decode. The TPU-native version overlaps
the *entire* host path — augmentation, dtype conversion, and the
host→device transfer onto the mesh placement — with the previous step's
device compute: a background thread stays ``depth`` batches ahead, and
because JAX dispatch is async, ``device_put`` in the worker thread just
enqueues DMA that proceeds while the main thread's step runs.

Plain Python threading is enough: the augment work releases the GIL in the
native path (``ops/native``) and numpy ops, and the transfer itself is
asynchronous. A full ahead-of-time pipeline (tf.data/grain) is unnecessary
for the in-memory datasets this framework ships.
"""

from __future__ import annotations

import queue
import threading
from typing import Any, Callable, Iterable, Iterator

import jax

from distributed_training_tpu.observability import trace as trace_lib

_END = object()


class DevicePrefetcher:
    """Wraps a batch iterable; yields device-resident batches ``depth`` ahead.

    ``place`` maps a host batch to its device placement (e.g.
    ``lambda b: jax.device_put(b, shardings)``). Exceptions in the worker
    propagate to the consumer at the next ``__next__``.
    """

    def __init__(self, batches: Iterable, place: Callable[[Any], Any],
                 depth: int = 2):
        if depth < 1:
            raise ValueError(f"prefetch depth must be >= 1, got {depth}")
        self._batches = batches
        self._place = place
        self._depth = depth

    def __iter__(self) -> Iterator:
        q: queue.Queue = queue.Queue(maxsize=self._depth)
        stop = threading.Event()

        def put(item) -> bool:
            # Bounded-wait put: if the consumer abandoned the loop (error,
            # ctrl-C), the stop flag unblocks the worker instead of leaving
            # a thread pinned forever on a full queue holding device-resident
            # batches in HBM.
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        def worker():
            try:
                # The worker's two halves, keyed by the batch's ordinal:
                # the loader's next() and the placement onto the devices.
                for n, batch in enumerate(
                        trace_lib.spanned(self._batches, "data.next")):
                    if stop.is_set():
                        return
                    with trace_lib.span("data.place", key=n):
                        placed = self._place(batch)
                    if not put(placed):
                        return
            except BaseException as e:  # noqa: BLE001 — reraised in consumer
                put(("__error__", e))
                return
            put(_END)

        t = threading.Thread(target=worker, daemon=True)
        t.start()
        try:
            while True:
                item = q.get()
                if item is _END:
                    return
                if isinstance(item, tuple) and len(item) == 2 and \
                        item[0] == "__error__":
                    raise item[1]
                yield item
        finally:
            stop.set()
            try:  # drain so a blocked worker put() unblocks promptly
                while True:
                    q.get_nowait()
            except queue.Empty:
                pass


def prefetch_to_mesh(loader, mesh, shardings, depth: int = 2):
    """Iterate ``loader`` with batches pre-placed onto ``shardings``.

    ``shardings`` may be a pytree matching each batch or a callable
    ``batch -> shardings`` (for loaders whose batch structure varies, e.g.
    eval batches carrying a mask).
    """
    def place(batch):
        sh = shardings(batch) if callable(shardings) else shardings
        return jax.device_put(batch, sh)

    return DevicePrefetcher(loader, place, depth=depth)
