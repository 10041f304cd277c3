"""Async feed-path primitives for the TPU secret scanner.

The feed path keeps the host→device link busy while the kernel runs.
That needs three host-side properties, and this module supplies the two
data-structure halves (the thread topology lives in
:mod:`trivy_tpu.secret.tpu_scanner`):

- **ChunkArena** — a fixed pool of preallocated, reusable row slabs
  (``[batch, chunk_len]`` uint8). Slabs are acquired by the batch
  assembler, handed through the dispatch queue to a transfer stream, and
  released only after the device fetch completes, so a slab can never be
  refilled while a transfer may still be reading it (the CPU backend's
  zero-copy aliasing cares).
  The pool bound doubles as feed backpressure: when every slab is in
  flight the assembler blocks instead of growing RSS — the equivalent of
  the reference's bounded channel between walker goroutines and workers.
  Addresses are stable for the life of a scan ("pinned" in the transfer
  sense: the PJRT layer sees the same host buffers batch after batch).

- **FileStream** — a byte-bounded handoff queue that turns a push-style
  producer (the secret analyzer's ``collect()`` during the artifact walk)
  into the pull-style iterable ``scan_files`` consumes, so file reads and
  device scanning overlap instead of alternating in 64 MB bursts. The
  byte bound is the walk-side backpressure: a stalled device pipeline
  blocks the walk at a fixed buffered-bytes budget instead of buffering
  the tree.

Batch assembly itself is vectorized in the scanner: a large file's full
rows are gathered into a slab with ONE strided-fancy-index copy
(``sliding_window_view(data)[starts]``) instead of a Python loop of
per-row slice copies, and counters accumulate per file, not per row.
"""

from __future__ import annotations

import threading
from collections import deque
from collections.abc import Callable, Iterator

import numpy as np

__all__ = ["ChunkArena", "FileStream", "row_windows"]


def row_windows(arr: np.ndarray, row_len: int):
    """All ``row_len`` windows of a 1-D uint8 array as a zero-copy view,
    or None when the array is shorter than one row. Fancy-indexing the
    view with a list of chunk starts gathers every full row of a file in
    a single C-level copy."""
    if arr.size < row_len:
        return None
    from numpy.lib.stride_tricks import sliding_window_view

    return sliding_window_view(arr, row_len)


class ChunkArena:
    """Fixed pool of reusable ``[rows, row_len]`` uint8 slabs.

    ``acquire`` blocks while every slab is in flight (bounded feed);
    ``release`` returns a slab after its transfer is provably finished.
    ``acquire`` takes an ``abort`` predicate so a shutting-down or
    degrading pipeline can stop waiting instead of deadlocking on slabs
    that will never come back.
    """

    def __init__(self, n_slabs: int, rows: int, row_len: int):
        if n_slabs < 1:
            raise ValueError("ChunkArena needs at least one slab")
        self._bufs = [
            np.zeros((rows, row_len), dtype=np.uint8) for _ in range(n_slabs)
        ]
        self._free: deque[int] = deque(range(n_slabs))
        self._cond = threading.Condition()
        self.n_slabs = n_slabs
        self.rows = rows
        self.row_len = row_len
        self.acquires = 0  # lifetime acquisitions: reuse proof for tests

    def acquire(
        self, abort: Callable[[], bool] | None = None, poll: float = 0.2
    ) -> tuple[int, np.ndarray] | None:
        """``(slab_id, slab)`` of a free slab, or None once ``abort()``
        turns true while waiting."""
        with self._cond:
            while not self._free:
                if abort is not None and abort():
                    return None
                self._cond.wait(poll)
            i = self._free.popleft()
            self.acquires += 1
            return i, self._bufs[i]

    def release(self, slab_id: int) -> None:
        with self._cond:
            if slab_id in self._free:
                raise ValueError(f"slab {slab_id} released twice")
            self._free.append(slab_id)
            self._cond.notify()

    def grow(self, n: int, max_slabs: int | None = None) -> int:
        """Add up to ``n`` fresh slabs (online tuning: a controller that
        raises the stream count or window depth grows the pool so slab
        backpressure doesn't starve the new capacity), bounded by
        ``max_slabs``. Returns the new slab count. Growth only — slabs may
        be in flight at any moment, so shrinking would mean tracking
        retirement; the bound comes from the controller's limits."""
        with self._cond:
            if max_slabs is not None:
                n = min(n, max_slabs - self.n_slabs)
            for _ in range(max(0, n)):
                self._bufs.append(
                    np.zeros((self.rows, self.row_len), dtype=np.uint8)
                )
                self._free.append(len(self._bufs) - 1)
                self.n_slabs += 1
            self._cond.notify_all()
            return self.n_slabs

    @property
    def free_slabs(self) -> int:
        with self._cond:
            return len(self._free)

    def nbytes(self) -> int:
        return self.n_slabs * self.rows * self.row_len


class _Closed:
    pass


_CLOSED = _Closed()


class FileStream:
    """Byte-bounded (path, bytes) handoff queue, iterable exactly once.

    Producer side: :meth:`put` blocks while ``max_bytes`` of content is
    already buffered (walk-side backpressure); :meth:`close` ends the
    stream; :meth:`fail` poisons it so a blocked/future producer raises
    the consumer's error instead of hanging on a dead pipeline.
    Consumer side: iterate — each item is popped as soon as the scanner
    takes it, releasing its bytes from the budget.
    """

    def __init__(self, max_bytes: int):
        self.max_bytes = max(1, max_bytes)
        self._q: deque = deque()
        self._buffered = 0
        self._closed = False
        self._error: BaseException | None = None
        self._cond = threading.Condition()

    def put(self, path: str, data: bytes) -> None:
        with self._cond:
            while (
                self._buffered >= self.max_bytes
                and self._error is None
                and not self._closed
            ):
                self._cond.wait(0.2)
            if self._error is not None:
                raise self._error
            if self._closed:
                raise RuntimeError("FileStream is closed")
            self._q.append((path, data))
            self._buffered += len(data)
            self._cond.notify_all()

    def close(self) -> None:
        with self._cond:
            self._closed = True
            self._cond.notify_all()

    def fail(self, err: BaseException) -> None:
        """Poison the stream: drop buffered items (the consumer is gone)
        and make every producer call raise ``err``."""
        with self._cond:
            self._error = err
            self._q.clear()
            self._buffered = 0
            self._cond.notify_all()

    def __iter__(self) -> Iterator[tuple[str, bytes]]:
        while True:
            with self._cond:
                while not self._q and not self._closed and self._error is None:
                    self._cond.wait(0.2)
                if self._error is not None:
                    return
                if not self._q:
                    return  # closed and drained
                path, data = self._q.popleft()
                self._buffered -= len(data)
                self._cond.notify_all()
            yield path, data
