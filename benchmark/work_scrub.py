"""Bytes a deep scrub's digest launch needs, from the launch's shape.

The least a CRC32C of whole shards can move through HBM: read every
byte of every shard once and write one 4-byte register a shard.
``rows`` are the shards the scrubs digested on the device, not the
batch padding; ``shard_bytes`` is a shard's own length, not the
power-of-two row the program pads it to.  What the program does beside
(bit planes, segment registers, the fold) is its own business and is
not counted.
"""

from __future__ import annotations

from benchmark.work import stripes_per_object


def shard_bytes(k: int, stripe_unit: int, object_bytes: int) -> int:
    """Length of one shard of an object: whole stripe rows."""
    return stripes_per_object(k, stripe_unit, object_bytes) * stripe_unit


def digest_bytes(rows: int, shard_len: int) -> int:
    return rows * (shard_len + 4)
