"""Plain reference for bulk CRUSH placement on a uniform straw2 tree.

Follows src/crush/mapper.c for the one rule the configuration states
(take root, chooseleaf firstn over the OSDs' parent type, emit) under
the jewel tunables (choose_total_tries 50, chooseleaf_descend_once,
vary_r 1, stable 1, no local retries): rjenkins1 hashes, the
fixed-point ``crush_ln`` with the published tables, straw2 draws as a
truncating 64-bit division, first-n retry flow with collision and
"is out" rejection.  It is numpy over all lanes at once; one lane is
the scalar algorithm.  Nothing here imports the program.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

ITEM_NONE = 0x7FFFFFFF
HASH_SEED = 1315423911
S64_MIN = -(1 << 63)
CHOOSE_TRIES = 51                # choose_total_tries counts retries: +1

_T = json.loads((Path(__file__).parent / "crush_ln_tables.json").read_text())
_RH_LH, _LL = _T["rh_lh"], _T["ll"]


def crush_ln(xin: int) -> int:
    """2^44 * log2(xin + 1) for xin in [0, 0xffff] (mapper.c crush_ln)."""
    x = xin + 1
    iexpon = 15
    if not x & 0x18000:
        bits = 16 - (x & 0x1FFFF).bit_length()
        x <<= bits
        iexpon = 15 - bits
    index1 = (x >> 8) << 1
    rh, lh = _RH_LH[index1 - 256], _RH_LH[index1 + 1 - 256]
    ll = _LL[((x * rh) >> 48) & 0xFF]
    return (iexpon << 44) + ((lh + ll) >> 4)


# straw2 reads ln(u) - 2^48 for a 16-bit u: all 65536 values, once
_LN_MINUS = np.array([crush_ln(u) - (1 << 48) for u in range(1 << 16)],
                     np.int64)


def _mix(a, b, c):
    a = a - b; a = a - c; a = a ^ (c >> 13)
    b = b - c; b = b - a; b = b ^ (a << 8)
    c = c - a; c = c - b; c = c ^ (b >> 13)
    a = a - b; a = a - c; a = a ^ (c >> 12)
    b = b - c; b = b - a; b = b ^ (a << 16)
    c = c - a; c = c - b; c = c ^ (b >> 5)
    a = a - b; a = a - c; a = a ^ (c >> 3)
    b = b - c; b = b - a; b = b ^ (a << 10)
    c = c - a; c = c - b; c = c ^ (b >> 15)
    return a, b, c


def _u32(v) -> np.ndarray:
    return np.asarray(v).astype(np.int64).astype(np.uint32)


def hash32_2(a, b) -> np.ndarray:
    a, b = np.broadcast_arrays(_u32(a), _u32(b))
    h = np.uint32(HASH_SEED) ^ a ^ b
    x = np.full(a.shape, 231232, np.uint32)
    y = np.full(a.shape, 1232, np.uint32)
    a, b, h = _mix(a, b, h)
    x, a, h = _mix(x, a, h)
    b, y, h = _mix(b, y, h)
    return h


def hash32_3(a, b, c) -> np.ndarray:
    a, b, c = np.broadcast_arrays(_u32(a), _u32(b), _u32(c))
    h = np.uint32(HASH_SEED) ^ a ^ b ^ c
    x = np.full(a.shape, 231232, np.uint32)
    y = np.full(a.shape, 1232, np.uint32)
    a, b, h = _mix(a, b, h)
    c, x, h = _mix(c, x, h)
    y, a, h = _mix(y, a, h)
    b, x, h = _mix(b, x, h)
    y, c, h = _mix(y, c, h)
    return h


class UniformTree:
    """``fanouts[l]`` children per bucket at level l, OSDs under the
    last; bucket ids count down from -1 in depth-first preorder; a
    bucket at level l has type ``depth - l``, an OSD type 0; a bucket's
    weight is the sum of its children's."""

    def __init__(self, fanouts: list[int], osd_weight: int) -> None:
        self.fanouts = list(fanouts)
        self.osd_weight = osd_weight
        depth = len(fanouts)
        self.n_osds = int(np.prod(fanouts))
        # per level: child item ids and the children's row at level+1
        self.ids = [[] for _ in range(depth)]
        self.child_row = [[] for _ in range(depth)]
        self.bucket_ids = [[] for _ in range(depth)]
        next_id = [-1]

        def build(level: int, osd_base: int) -> tuple[int, int]:
            bid = next_id[0]
            next_id[0] -= 1
            row = len(self.bucket_ids[level])
            self.bucket_ids[level].append(bid)
            self.ids[level].append(None)
            self.child_row[level].append(None)
            span = int(np.prod(fanouts[level + 1:]))
            items, rows = [], []
            for c in range(fanouts[level]):
                if level == depth - 1:
                    items.append(osd_base + c)
                    rows.append(0)
                else:
                    sub, sub_row = build(level + 1, osd_base + c * span)
                    items.append(sub)
                    rows.append(sub_row)
            self.ids[level][row] = items
            self.child_row[level][row] = rows
            return bid, row

        build(0, 0)
        self.ids = [np.array(t, np.int64) for t in self.ids]
        self.child_row = [np.array(t, np.int64) for t in self.child_row]
        # every child of a level-l bucket weighs the same
        self.child_weight = [osd_weight * int(np.prod(fanouts[l + 1:]))
                             for l in range(depth)]

    def buckets(self) -> list[dict]:
        """The map as plain data, root first: what the system under
        test is given to build its own map from."""
        depth = len(self.fanouts)
        out = []
        for level in range(depth):
            for row, bid in enumerate(self.bucket_ids[level]):
                out.append({"id": bid, "type": depth - level,
                            "items": [int(i) for i in self.ids[level][row]],
                            "item_weights": [self.child_weight[level]]
                            * self.fanouts[level]})
        return sorted(out, key=lambda b: -b["id"])

    def _straw2(self, level: int, rows, xs, r) -> np.ndarray:
        """Column of the winning child for each lane."""
        items = self.ids[level][rows]                       # (L, F)
        u = hash32_3(xs[:, None], items, r[:, None]) & np.uint32(0xFFFF)
        ln = _LN_MINUS[u.astype(np.int64)]                  # <= 0
        w = self.child_weight[level]
        # C division truncates toward zero; ln <= 0 < w
        draws = -((-ln) // w) if w else np.full(ln.shape, S64_MIN)
        return np.argmax(draws, axis=1)                     # first max wins

    def map_pgs(self, xs, numrep: int, osd_weights) -> np.ndarray:
        """(len(xs), numrep) OSD ids, ``ITEM_NONE`` where firstn gave
        up; ``osd_weights`` are the 16.16 in/out weights."""
        xs = np.asarray(xs, np.int64)
        osd_weights = np.asarray(osd_weights, np.int64)
        n, depth = xs.shape[0], len(self.fanouts)
        out_host = np.full((n, numrep), ITEM_NONE, np.int64)
        out_osd = np.full((n, numrep), ITEM_NONE, np.int64)
        outpos = np.zeros(n, np.int64)
        for rep in range(numrep):
            ftotal = np.zeros(n, np.int64)
            trying = np.ones(n, bool)
            while trying.any():
                idx = np.flatnonzero(trying)
                x, r = xs[idx], rep + ftotal[idx]
                rows = np.zeros(idx.shape[0], np.int64)
                lane = np.arange(idx.shape[0])
                for level in range(depth - 1):
                    col = self._straw2(level, rows, x, r)
                    host = self.ids[level][rows, col]
                    rows = self.child_row[level][rows, col]
                collide = (out_host[idx] == host[:, None]).any(axis=1)
                # one leaf try (descend_once), sub_r = r >> (vary_r - 1)
                col = self._straw2(depth - 1, rows, x, r)
                osd = self.ids[depth - 1][rows, col]
                leaf_collide = (out_osd[idx] == osd[:, None]).any(axis=1)
                w = osd_weights[osd]
                is_out = (w == 0) | ((w < 0x10000) & (
                    (hash32_2(x, osd) & np.uint32(0xFFFF)).astype(np.int64)
                    >= w))
                ok = ~collide & ~leaf_collide & ~is_out
                won = idx[ok]
                out_host[won, outpos[won]] = host[ok]
                out_osd[won, outpos[won]] = osd[ok]
                outpos[won] += 1
                trying[won] = False
                lost = idx[~ok]
                ftotal[lost] += 1
                trying[lost[ftotal[lost] >= CHOOSE_TRIES]] = False
        return out_osd
