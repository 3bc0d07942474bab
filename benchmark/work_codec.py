"""Bytes the registry codec's launches need, from the configuration's
shapes.

The least one launch can move through HBM: read the k chunks of
``stripe_unit`` bytes it is given and write the ``rows`` chunks it is
asked for, for every stripe the caller handed in: ``rows`` is m for an
encode and the number of erased chunks for a decode.  The bit matrix
(a few KiB), what an engine writes beside the result (a padded layout,
a relayout) and a first launch's parity gate are not counted: the
figure is the same whatever engine serves.
"""

from __future__ import annotations


def launch_bytes(k: int, rows: int, stripe_unit: int, stripes: int) -> int:
    return stripes * (k + rows) * stripe_unit


def slice_bytes(k: int, stripe_unit: int,
                stripes_by_rows: dict[int, int]) -> int:
    """A slice's launches, ``{output rows: stripes handed in}``."""
    return sum(launch_bytes(k, rows, stripe_unit, stripes)
               for rows, stripes in stripes_by_rows.items())
