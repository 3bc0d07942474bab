"""Plain reference for a block image under overwrites.

An image is a ``bytearray``.  Its first content and the payload of
every write are made from the run's seed; applying the acknowledged
writes in the order they were acknowledged gives the bytes a read must
return.  The image is cut into data objects of ``object_bytes`` the way
rbd's default layout cuts it (stripe_count 1: object n holds the bytes
from n * object_bytes), and an object's stored shards follow from its
bytes through ``ec.shards_of``.  Nothing here imports the program or
reads anything the program has made.
"""

from __future__ import annotations

import numpy as np


def first_content(seed: int, image: int, size: int) -> bytearray:
    """What the prefill writes: every byte of the image, seeded (whole
    64-bit draws straight into the buffer: a gigabyte in a second)."""
    if size % 8:
        raise ValueError("the image is not a whole number of 8-byte words")
    data = bytearray(size)
    np.frombuffer(data, np.uint64)[:] = np.random.default_rng(
        [seed, image]).integers(0, 2**64 - 1, size // 8, dtype=np.uint64,
                                endpoint=True)
    return data


def write_payload(seed: int, image: int, block: int, times: int,
                  size: int) -> bytes:
    """The ``times``-th write of one block of one image."""
    return np.random.default_rng([seed, image, block, times]).bytes(size)


class Image:
    """The bytes an image must hold, and which of its objects took an
    acknowledged write since ``mark()``."""

    def __init__(self, seed: int, image: int, size: int,
                 object_bytes: int) -> None:
        if size % object_bytes:
            raise ValueError("the image is not a whole number of objects")
        self.object_bytes = object_bytes
        self.data = first_content(seed, image, size)
        self.written: dict[int, int] = {}        # object -> writes since mark

    def mark(self) -> None:
        self.written = {}

    def write(self, off: int, data: bytes) -> None:
        """One acknowledged write; it may straddle objects."""
        end = off + len(data)
        if off < 0 or end > len(self.data):
            raise ValueError("write outside the image")
        self.data[off:end] = data
        for n in range(off // self.object_bytes,
                       (end - 1) // self.object_bytes + 1):
            self.written[n] = self.written.get(n, 0) + 1

    def read(self, off: int, length: int) -> bytes:
        return bytes(memoryview(self.data)[off:off + length])

    def object(self, n: int) -> bytes:
        """The whole of data object ``n``."""
        return self.read(n * self.object_bytes, self.object_bytes)
