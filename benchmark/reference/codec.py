"""Plain reference for the registry codec configuration: what
``ceph_erasure_code_benchmark`` hands a plugin and what must come back.

``ec.py`` has the field and the generator's product, ``ec_decode.py``
the Gauss-Jordan inverse of the generator's k x k submatrix.  What they
lack is here: the tool's chunking of one object (``ErasureCode::
encode_prepare``: k chunks of ceil(size / k) bytes rounded up to the
plugin's alignment, the tail zero-padded), the survivors a decode reads
(the first k chunk ids that are not erased, ascending: the isa plugin's
``decode_index``), the product over a whole batch of stripes, and the
erased chunks of one stripe from its survivors.  Nothing here imports
the program or reads anything the program has computed.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference import ec, ec_decode

ISA_ALIGNMENT = 32          # EC_ISA_ADDRESS_ALIGNMENT, ErasureCodeIsa.h:33
BLOCK_STRIPES = 128         # stripes a product handles at once: 16 MiB a row


def chunk_bytes(k: int, object_bytes: int) -> int:
    """Length of each chunk of an object of ``object_bytes``."""
    size = -(-object_bytes // k)
    return size + (-size % ISA_ALIGNMENT)


def chunks_of(k: int, payload: bytes) -> np.ndarray:
    """(k, chunk_bytes) data chunks of one object, the tail zero-padded."""
    size = chunk_bytes(k, len(payload))
    padded = payload + b"\0" * (k * size - len(payload))
    return np.frombuffer(padded, np.uint8).reshape(k, size)


def survivors(k: int, n: int, erased) -> list[int]:
    """The k chunk ids a decode reads: the lowest that are not erased."""
    alive = [i for i in range(n) if i not in set(erased)]
    if len(alive) < k:
        raise ValueError(f"{len(alive)} of {n} chunks cannot decode k={k}")
    return alive[:k]


def parity_of(profile: dict, data: np.ndarray) -> np.ndarray:
    """(B, k, L) data chunks -> (B, m, L) parity chunks: the generator's
    parity rows times every stripe, ``BLOCK_STRIPES`` stripes at a time."""
    k, m = profile["k"], profile["m"]
    matrix = ec.coding_matrix(profile["technique"], k, m)
    out = np.zeros((data.shape[0], m, data.shape[2]), np.uint8)
    for lo in range(0, data.shape[0], BLOCK_STRIPES):
        block = data[lo:lo + BLOCK_STRIPES]
        for r in range(m):
            for j in range(k):
                out[lo:lo + BLOCK_STRIPES, r] ^= \
                    ec.GF_MUL[int(matrix[r, j])][block[:, j]]
    return out


def recovered(profile: dict, stripe: np.ndarray, erased) -> np.ndarray:
    """(len(erased), L): the erased chunks of one stripe ``(k+m, L)``,
    from its survivors alone (the rows at erased ids are never read):
    the data chunks by ``ec_decode``'s inverse, an erased parity chunk
    as the generator's row times them."""
    k, m = profile["k"], profile["m"]
    alive = survivors(k, k + m, erased)
    data = ec_decode.data_shards(
        profile, {i: stripe[i].tobytes() for i in alive})
    parity = ec.coding_matrix(profile["technique"], k, m)
    return np.stack([data[e] if e < k
                     else ec.gf_matmul(parity[e - k:e - k + 1], data)[0]
                     for e in erased])
