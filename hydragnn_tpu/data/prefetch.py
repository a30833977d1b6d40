"""Background-prefetching loader wrapper.

The TPU analog of the reference's HydraDataLoader (hydragnn/preprocess/
load_data.py:94-204: ThreadPoolExecutor batch fetch with per-worker CPU
affinity pinning — an HPC workaround for torch DataLoader hangs). Here
the host assembles padded batches in a worker thread one step ahead and
moves them to the device asynchronously (jax.device_put), overlapping
host collation + H2D transfer with device compute.
"""

from __future__ import annotations

import os
import queue
import threading
from typing import Iterator, Optional, Sequence

import jax

from hydragnn_tpu.utils import tracer as tr


def _pin_affinity(offset: int, width: int) -> None:
    """Pin the worker thread to a CPU range (reference
    HYDRAGNN_AFFINITY/_WIDTH/_OFFSET + sched_setaffinity,
    load_data.py:121-159)."""
    try:
        n = os.cpu_count() or 1
        cores = {c % n for c in range(offset, offset + width)}
        os.sched_setaffinity(0, cores)
    except (AttributeError, OSError):
        pass


class PrefetchLoader:
    """Wraps any batch iterable; yields device-resident batches with
    ``depth`` batches in flight."""

    def __init__(
        self,
        loader,
        *,
        depth: int = 2,
        device=None,
        to_device: bool = True,
        affinity_offset: Optional[int] = None,
        affinity_width: int = 1,
    ):
        """``to_device=False`` skips the device_put — for wrapped loaders
        (DPLoader) that already place batches on a mesh; the worker
        thread then only runs collation + transfer ahead of compute."""
        self.loader = loader
        self.depth = max(1, int(depth))
        self.device = device
        self.to_device = to_device
        self.affinity_offset = affinity_offset
        self.affinity_width = affinity_width

    def set_epoch(self, epoch: int) -> None:
        if hasattr(self.loader, "set_epoch"):
            self.loader.set_epoch(epoch)

    # Pure delegation: the resume machinery (train/loop.py
    # _feed_supports_skip) must probe the WRAPPED loader's capability,
    # not this always-present method.
    _skip_to_delegates = True

    def skip_to(self, step: int) -> None:
        """Mid-epoch resume cursor: pure delegation — the wrapped
        loader (SuperstepLoader / DPLoader / pipeline / GraphLoader)
        owns the plan-domain fast-forward."""
        inner = getattr(self.loader, "skip_to", None)
        if inner is None:
            raise AttributeError(
                "PrefetchLoader wraps "
                f"{type(self.loader).__name__}, which has no skip_to "
                "fast-forward — callers must probe the wrapped loader "
                "(train/loop._feed_supports_skip) before arming a "
                "mid-epoch cursor"
            )
        inner(step)

    def __len__(self) -> int:
        return len(self.loader)

    def __iter__(self) -> Iterator:
        q: "queue.Queue" = queue.Queue(maxsize=self.depth)
        stop = threading.Event()
        _SENTINEL = object()

        def stop_aware_put(item) -> bool:
            """Bounded-queue put that aborts on shutdown: a plain
            ``q.put`` can block forever when the consumer closed the
            generator early (the one-shot drain below empties the queue
            once, then this worker refills it and blocks with nobody
            left to read — the pre-fix leak)."""
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        def worker():
            if self.affinity_offset is not None:
                _pin_affinity(self.affinity_offset, self.affinity_width)
            try:
                it = iter(self.loader)
                while True:
                    # the feed thread's work as spans on the profiler's
                    # clock (no-ops without a live capture)
                    with tr.span("feed/collate"):
                        batch = next(it, _SENTINEL)
                    if batch is _SENTINEL:
                        break
                    if stop.is_set():
                        return
                    if self.to_device:
                        with tr.span("feed/h2d"):
                            if self.device is not None:
                                batch = jax.device_put(batch, self.device)
                            else:
                                batch = jax.device_put(batch)
                    if not stop_aware_put(batch):
                        return
            except BaseException as e:  # surface worker errors
                stop_aware_put(e)
                return
            stop_aware_put(_SENTINEL)

        t = threading.Thread(
            target=worker, daemon=True, name="hgtpu-prefetch"
        )
        t.start()
        try:
            while True:
                item = q.get()
                if item is _SENTINEL:
                    return
                if isinstance(item, BaseException):
                    raise item
                yield item
        finally:
            stop.set()
            # drain so a put-blocked worker can move, then bound the
            # wait for its exit (it re-checks ``stop`` between puts).
            while not q.empty():
                try:
                    q.get_nowait()
                except queue.Empty:
                    break
            t.join(timeout=5.0)
