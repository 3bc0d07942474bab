"""Plain reference for reads of a degraded erasure-coded pool.

Any k of an object's k+m shards determine it: take the k rows of the
profile's systematic generator (identity over ``ec.coding_matrix``)
that belong to the shards in hand, invert that k x k matrix over
GF(2^8)/0x11d by Gauss-Jordan elimination, and multiply the survivors
out into the k data shards.  Bytes at one offset of every shard are one
codeword, so all stripe rows of a shard go through the same inverse;
the object is the data chunks of each stripe row in order, cut to its
size.  Field tables and generator come from ``ec.py``; nothing here
imports the program or reads anything the program has computed.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference import ec


def generator(profile: dict) -> np.ndarray:
    """(k+m, k): row i says how shard i is made of the data shards."""
    k, m = profile["k"], profile["m"]
    return np.concatenate(
        [np.eye(k, dtype=np.uint8),
         ec.coding_matrix(profile["technique"], k, m)])


def gf_invert(matrix: np.ndarray) -> np.ndarray:
    """Inverse of a square matrix over GF(2^8) by Gauss-Jordan
    elimination; ``ValueError`` when it is singular."""
    n = matrix.shape[0]
    work = np.concatenate([matrix.astype(np.uint8),
                           np.eye(n, dtype=np.uint8)], axis=1)
    for col in range(n):
        pivot = next((r for r in range(col, n) if work[r, col]), None)
        if pivot is None:
            raise ValueError("singular over GF(2^8)")
        if pivot != col:
            work[[col, pivot]] = work[[pivot, col]]
        work[col] = ec.GF_MUL[ec.gf_inv(int(work[col, col]))][work[col]]
        for r in range(n):
            if r != col and work[r, col]:
                work[r] ^= ec.GF_MUL[int(work[r, col])][work[col]]
    return work[:, n:]


def data_shards(profile: dict, shards: dict[int, bytes]) -> np.ndarray:
    """(k, shard_len) data shards from any k (the k lowest ids given)
    of the stored shards ``{shard id: bytes}``."""
    k = profile["k"]
    ids = sorted(shards)[:k]
    if len(ids) < k:
        raise ValueError(f"{len(ids)} shards cannot decode k={k}")
    lens = {len(shards[i]) for i in ids}
    if len(lens) != 1:
        raise ValueError(f"shards of different lengths {sorted(lens)}")
    inverse = gf_invert(generator(profile)[ids])
    survivors = np.stack([np.frombuffer(shards[i], np.uint8) for i in ids])
    return ec.gf_matmul(inverse, survivors)


def object_from_shards(profile: dict, shards: dict[int, bytes],
                       size: int) -> bytes:
    """The object of ``size`` bytes that the shards in hand store."""
    k, unit = profile["k"], profile["stripe_unit"]
    data = data_shards(profile, shards)
    rows = data.reshape(k, -1, unit).transpose(1, 0, 2)
    return rows.tobytes()[:size]
