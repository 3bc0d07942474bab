"""Bytes a partial-stripe parity update (an rmw launch) needs, from the
configuration's shapes.

The least the launch can move through HBM for the stripes it was
launched with: read the m old parity chunks and the k delta chunks
(new XOR old data; zeros where a chunk did not change, and the launch
cannot know which) and write the m new parity chunks of ``stripe_unit``
bytes, per stripe.  Stripes are the launched batch, padding included:
the padded rows are operands the program moves like any other, and a
one-stripe overwrite launched alone is a batch of one.  The same count
whatever implements the launch.
"""

from __future__ import annotations


def rmw_bytes(k: int, m: int, stripe_unit: int, stripes: int) -> int:
    return stripes * (m + k + m) * stripe_unit
