"""Bytes an encode launch needs, from the configuration's shapes.

The least an encode can move through HBM: read k data chunks and write
m parity chunks of ``stripe_unit`` bytes for every stripe it was given
(the fused checksum re-reads bytes already counted and writes 4 bytes a
chunk, which is not counted).  Stripes are the ones clients wrote, not
the batch padding.
"""

from __future__ import annotations


def stripes_per_object(k: int, stripe_unit: int, object_bytes: int) -> int:
    """Stripe rows an object occupies; a ragged tail takes a whole row."""
    width = k * stripe_unit
    return -(-object_bytes // width)


def encode_bytes(k: int, m: int, stripe_unit: int, stripes: int) -> int:
    return stripes * (k + m) * stripe_unit


def roofline_share(bytes_needed: float, peak_bytes_per_s: float,
                   device_s: float) -> float:
    """Least time over measured time, in percent."""
    return 100.0 * (bytes_needed / peak_bytes_per_s) / device_s
