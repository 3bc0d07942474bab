"""Plain reference for a call over objects of unequal size: what the
registry codec must hand back when ``ceph_erasure_code_benchmark``'s
objects differ in ``--size`` inside one call.

``codec.py`` has the tool's chunking of one object (``chunk_bytes``,
``chunks_of``: k chunks of ceil(size / k) bytes rounded up to 32, the
tail zero), the survivors a decode reads and the erased chunks of one
stripe from them; it is used as it is.  What a list of objects of
unequal size lacks is here: the parity of every object, by the
generator's parity rows (``ec.coding_matrix``) and the field's table
(``ec.GF_MUL``), the objects' chunks laid side by side in blocks so
that the table is read over long rows and not over 416 bytes at a
time; each object's columns are its own, so no byte of one reaches
another's parity.  Also the count of bytes that are not zero past an
object's end in a data chunk.  Nothing here imports the program or
reads anything the program has computed.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference import codec, ec

BLOCK_LANES = 1 << 22         # columns a product handles at once: 4 MiB a row


def parity_of_objects(profile: dict, payloads: list) -> list[np.ndarray]:
    """Per object (``bytes`` or a flat uint8 array) its ``(m, L_i)``
    parity chunks, ``L_i = codec.chunk_bytes(k, size_i)``."""
    k, m = profile["k"], profile["m"]
    matrix = ec.coding_matrix(profile["technique"], k, m)
    out: list[np.ndarray] = []
    block: list[np.ndarray] = []
    lanes = 0

    def product() -> None:
        nonlocal lanes
        if not block:
            return
        data = np.concatenate(block, axis=1)
        parity = np.zeros((m, data.shape[1]), np.uint8)
        for r in range(m):
            for j in range(k):
                parity[r] ^= ec.GF_MUL[int(matrix[r, j])][data[j]]
        at = 0
        for chunks in block:
            out.append(parity[:, at:at + chunks.shape[1]].copy())
            at += chunks.shape[1]
        block.clear()
        lanes = 0

    for payload in payloads:
        chunks = codec.chunks_of(k, bytes(payload))
        if lanes and lanes + chunks.shape[1] > BLOCK_LANES:
            product()
        block.append(chunks)
        lanes += chunks.shape[1]
    product()
    return out


def tail_nonzero(k: int, size: int, chunk_id: int,
                 chunk: np.ndarray) -> int:
    """Bytes of data chunk ``chunk_id`` of an object of ``size`` bytes
    that lie past the object's end and are not zero (``encode_prepare``
    leaves them zero)."""
    length = codec.chunk_bytes(k, size)
    held = min(max(size - chunk_id * length, 0), length)
    return int(np.count_nonzero(chunk[held:]))
