"""Host buffers that were touched once, kept for the next call.

A large ``np.empty`` hands its caller pages the kernel has never mapped:
the first write to each one is a fault (and, with transparent huge
pages, whatever the host has to do to find one), so a loop that makes a
fresh 384 MiB result a call pays more for the memory than for the bytes
it puts there.  A ``HostArena`` keeps the buffers such a loop drops and
hands them to the next call.

Two ways to borrow, one lock:

* ``take`` / ``give``: a flat ``uint8`` buffer of at least so many
  bytes, given back by the borrower itself, who alone knows that nothing
  reads or writes it any more (the staging of a slab pipeline).
* ``lease``: an array of a given shape for a caller to KEEP.  Its
  memory's owner is a ``_Lease``, which is not an ndarray: numpy
  collapses ``.base`` through ndarrays, so a finalizer hung on an
  ndarray fires while a slice of it is still alive; every view of a
  leased array, however it was cut, ends its ``.base`` chain in the
  ``_Lease``, and the buffer comes back (``weakref.finalize``) only when
  the last array over it is gone.  Until then the bytes are the
  caller's own.

At rest the arena keeps at most ``cap`` bytes: a buffer that would pass
it is dropped when it comes back.  Finalizers run on whatever thread
drops the last reference, so every take and give is under the lock.
"""

from __future__ import annotations

import math
import threading
import weakref

import numpy as np


class _Lease:
    """Owner of one borrowed buffer's memory for as long as any array
    views it (``__array_interface__``: numpy makes it the ``.base`` of
    the array it builds, and of every view cut from that)."""

    def __init__(self, buf: np.ndarray, shape: tuple[int, ...]) -> None:
        self.__array_interface__ = {
            "version": 3, "typestr": "|u1", "shape": shape,
            "data": (buf.ctypes.data, False)}


class HostArena:
    def __init__(self, cap: int) -> None:
        self.cap = cap
        self._lock = threading.Lock()
        self._kept: list[np.ndarray] = []       # flat uint8, at rest

    def take(self, nbytes: int, perf=None) -> np.ndarray:
        """A flat ``uint8`` buffer of ``nbytes`` or more: the smallest
        kept one that fits (``perf``: ``arena_hits``), else a fresh
        allocation (``arena_misses``).  The borrower's until ``give``."""
        with self._lock:
            fit = min((i for i, kept in enumerate(self._kept)
                       if kept.nbytes >= nbytes),
                      key=lambda i: self._kept[i].nbytes, default=None)
            buf = None if fit is None else self._kept.pop(fit)
        if perf is not None:
            perf.inc("arena_misses" if buf is None else "arena_hits")
        return np.empty(nbytes, np.uint8) if buf is None else buf

    def give(self, buf: np.ndarray) -> None:
        """``buf`` back to rest, for the next ``take``; dropped where it
        would pass the cap.  Nothing may read or write it any more."""
        with self._lock:
            if self._resting() + buf.nbytes <= self.cap:
                self._kept.append(buf)

    def lease(self, shape: tuple[int, ...], perf=None) -> np.ndarray:
        """A C-ordered writeable ``uint8`` array of exactly ``shape``
        over a taken buffer, given back when the last array that shares
        its memory is gone."""
        buf = self.take(math.prod(shape), perf)
        owner = _Lease(buf, tuple(shape))
        weakref.finalize(owner, self.give, buf).atexit = False
        # lint: disable=device-path-host-sync -- host memory only: the ndarray over the lease's buffer, no device array comes here
        return np.asarray(owner)

    def at_rest(self) -> int:
        """Bytes the arena holds that nobody has borrowed."""
        with self._lock:
            return self._resting()

    def _resting(self) -> int:
        return sum(buf.nbytes for buf in self._kept)

    def clear(self) -> None:
        with self._lock:
            self._kept.clear()
