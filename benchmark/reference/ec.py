"""Plain reference for the erasure-coded store configurations.

Straightforward numpy over GF(2^8), polynomial 0x11d, with the ISA-L
generator constructions the profile names (``reed_sol_van``: parity row
r is [g^0 .. g^(k-1)] with g = 2^r; ``cauchy``: 1/(i ^ j) under the
identity).  CRC32C comes from ``google_crc32c`` (its C implementation),
in Ceph's convention: seed 0xffffffff, no final inversion.  Nothing here
imports the program or reads anything the program has made.
"""

from __future__ import annotations

import google_crc32c
import numpy as np

GF_POLY = 0x11D


def _mul_table() -> np.ndarray:
    exp = np.zeros(510, np.int64)
    log = np.zeros(256, np.int64)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= GF_POLY
    exp[255:] = exp[:255]
    table = exp[log[:, None] + log[None, :]].astype(np.uint8)
    table[0, :] = 0
    table[:, 0] = 0
    return table


GF_MUL = _mul_table()                 # GF_MUL[a, b] = a * b


def gf_inv(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("0 has no inverse in GF(2^8)")
    return int(np.flatnonzero(GF_MUL[a] == 1)[0])


def coding_matrix(technique: str, k: int, m: int) -> np.ndarray:
    """(m, k) parity rows of the systematic generator."""
    out = np.zeros((m, k), np.uint8)
    if technique == "reed_sol_van":
        gen = 1
        for r in range(m):
            p = 1
            for j in range(k):
                out[r, j] = p
                p = int(GF_MUL[p, gen])
            gen = int(GF_MUL[gen, 2])
    elif technique == "cauchy":
        for r in range(m):
            for j in range(k):
                out[r, j] = gf_inv((k + r) ^ j)
    else:
        raise ValueError(f"no reference construction for {technique!r}")
    return out


def gf_matmul(matrix: np.ndarray, data: np.ndarray) -> np.ndarray:
    """(r, k) coefficients x (k, n) bytes -> (r, n) bytes."""
    out = np.zeros((matrix.shape[0], data.shape[1]), np.uint8)
    for r in range(matrix.shape[0]):
        for j in range(matrix.shape[1]):
            out[r] ^= GF_MUL[int(matrix[r, j])][data[j]]
    return out


def shards_of(profile: dict, payload: bytes) -> list[bytes]:
    """The k+m shard contents an object of ``payload`` must be stored
    as: zero-padded to a whole stripe row, chunk i of every row
    concatenated into shard i, parity rows encoded per stripe."""
    k, m, unit = profile["k"], profile["m"], profile["stripe_unit"]
    width = k * unit
    padded = payload + b"\0" * (-len(payload) % width)
    rows = np.frombuffer(padded, np.uint8).reshape(-1, k, unit)
    data = rows.transpose(1, 0, 2).reshape(k, -1)
    parity = gf_matmul(coding_matrix(profile["technique"], k, m), data)
    return [bytes(s) for s in data] + [bytes(s) for s in parity]


def ceph_crc32c(data: bytes) -> int:
    return google_crc32c.value(data) ^ 0xFFFFFFFF
