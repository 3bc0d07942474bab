"""Bytes a reconstruction launch needs, from the configuration's shapes.

The least a decode can move through HBM for the reads it serves: read
the k surviving chunks and write the recovered chunks of ``stripe_unit``
bytes for every stripe the clients' reads needed.  ``rows`` is the
number of chunks a read lacks (the shards that are down), not what the
program chooses to compute beside them; stripes are the ones read, not
the batch padding.
"""

from __future__ import annotations


def decode_bytes(k: int, rows: int, stripe_unit: int, stripes: int) -> int:
    return stripes * (k + rows) * stripe_unit
