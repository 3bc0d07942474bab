"""Vectorized CRUSH on TPU: bulk PG->OSD mapping as one XLA launch.

The reference recomputes full-cluster mappings on host thread pools
(ParallelPGMapper, src/osd/OSDMapMapping.h:18; used by the balancer and
OSDMonitor's PrimeTempJob).  Here the whole job is one data-parallel
program over the PG axis: a straw2 draw is a lookup of 2^48 - crush_ln(u),
an exact quotient by the item weight and a lexicographic argmin, all in
32-bit limbs (the TPU has no 64-bit integers, and the program holds no
64-bit type whether or not an embedding process runs jax with x64 on);
the firstn/indep retry loops become bounded `lax.while_loop`s with
per-lane masks (in a long launch over what is still unplaced alone,
firstn's lanes and indep's (lane, slot) pairs: RETRY_MIN_LANES) --
decision-identical to the scalar mapper (ceph_tpu/crush/mapper.py),
which is itself pinned to mapper.c.

A map's bucket tables (hashed ids, weights, reciprocal bits, children)
are OPERANDS of the device program: a ``VectorCrush`` is a pytree whose
leaves are the tables and whose static part (``Structure``) is what
decides the program, so jit keys on the structure, the tables' shapes,
``numrep`` and the lane count, not on the mapper instance.  An OSDMap
epoch that changed weights alone -- ``osd crush reweight``, a rack
raised step by step -- uploads its tables and launches the executable
that is there, in this process and in the persistent cache; a change of
structure compiles once.

Supported map shape for the fused path: uniform-depth straw2
hierarchies of ANY depth (root->osds up through root->row->rack->host->
osd and deeper) with the standard replicated (chooseleaf firstn) /
erasure (chooseleaf indep) rules, jewel tunables, and optional
choose_args weight-sets (the balancer's crush-compat overrides,
mapper.c:289-306).  Anything else falls back to the scalar engine.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from ..common.tracing import section
from .ln import LL_TBL, RH_LH_TBL, crush_ln
from .types import (
    CrushMap,
    CRUSH_BUCKET_STRAW2,
    CRUSH_ITEM_NONE,
    CRUSH_RULE_TAKE,
    CRUSH_RULE_EMIT,
    CRUSH_RULE_CHOOSE_FIRSTN,
    CRUSH_RULE_CHOOSE_INDEP,
    CRUSH_RULE_CHOOSELEAF_FIRSTN,
    CRUSH_RULE_CHOOSELEAF_INDEP,
    CRUSH_RULE_SET_CHOOSE_TRIES,
    CRUSH_RULE_SET_CHOOSELEAF_TRIES,
)

CRUSH_HASH_SEED = np.uint32(1315423911)


class FusedUnsupported(ValueError):
    """The fused path declines a (map, rule): the scalar engine maps
    it.  ``reason`` is one word, for a counter's name."""

    def __init__(self, reason: str, message: str) -> None:
        super().__init__(message)
        self.reason = reason


# lanes per device launch: bounds a launch's device memory and the number
# of compiled shapes whatever size a caller hands in (a shape is a
# program: the lane count is part of jit's key beside the structure).
# The value dates from the 64-bit program (PR 21: a 2M-lane launch
# aborted the v5e runtime); nothing has measured the 32-bit program,
# whose tables are operands of a few hundred KB, beyond it.
MAX_LANES = 1 << 17

# crush_firstn retries at the width of what is left to retry: a launch
# of RETRY_MIN_LANES lanes or more tries a replica at full width until
# the lanes still unplaced fit lanes // RETRY_NARROW (all weights in, a
# replica collides with an earlier one's bucket in one to three lanes of
# a hundred: one pass; many OSDs out or few failure domains: more) and
# finishes them compacted to that width.  A shorter launch keeps the one
# full-width loop: at 4,096 lanes the narrow stage still saves a fifth
# of a call on the chip (PERF.md section 6, PR 37); a few dozen narrow
# lanes save nothing and are a second loop body to compile.
# crush_indep takes both for its unit, the (lane, slot) pair: full-width
# passes over every pair until those still undefined fit
# numrep * lanes // RETRY_NARROW (eleven slots over a hundred hosts
# leave a twentieth after the first), then these alone (PR 42).
RETRY_MIN_LANES = 1 << 12
RETRY_NARROW = 16


def _u32(v):
    return jnp.asarray(v, dtype=jnp.uint32)


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


def hash32_2_jnp(a, b):
    a, b = _u32(a), _u32(b)
    h = _u32(CRUSH_HASH_SEED) ^ a ^ b
    x = jnp.full_like(h, 231232)
    y = jnp.full_like(h, 1232)
    a, b, h = _mix(a, b, h)
    x, a, h = _mix(x, a, h)
    b, y, h = _mix(b, y, h)
    return h


def hash32_3_jnp(a, b, c):
    a, b, c = _u32(a), _u32(b), _u32(c)
    a, b, c = jnp.broadcast_arrays(a, b, c)
    h = _u32(CRUSH_HASH_SEED) ^ a ^ b ^ c
    x = jnp.full_like(h, 231232)
    y = jnp.full_like(h, 1232)
    a, b, h = _mix(a, b, h)
    c, x, h = _mix(c, x, h)
    y, a, h = _mix(y, a, h)
    b, x, h = _mix(b, x, h)
    y, c, h = _mix(y, c, h)
    return h


_U32_MAX = np.uint32(0xFFFFFFFF)
# typed, so that no weak 64-bit scalar is traced when x64 is on
_NONE = np.int32(CRUSH_ITEM_NONE)


def _byte_limbs(columns) -> tuple[np.ndarray, tuple[int, ...]]:
    """A lookup table for ``_lookup``: each (values, shift, n) of
    ``columns`` becomes the n low bytes of values >> shift as float32
    columns (a byte is exact in bfloat16).  Returns the table (entries,
    bytes) and every n."""
    cols = [(np.asarray(values, dtype=object) >> (shift + 8 * b)) & 0xFF
            for values, shift, n in columns for b in range(n)]
    return (np.stack(cols, -1).astype(np.float32),
            tuple(n for _, _, n in columns))


def _ln_tables():
    """crush_ln's two tables as ``_byte_limbs``, for the steps of
    ``crush_ln_jnp``.  By k = (x >> 8) - 128 of the normalized x (the
    129th entry, x = 2^16, is the one u = 0xffff and stands apart): RH
    and x's product with it at its low byte 0, A = (128 + k) * 256 * RH,
    both in 24-bit limbs (of A's top limb only bits 48-55 are used), and
    LH; by index2: LL."""
    rh = [int(v) for v in RH_LH_TBL[0:256:2]]
    lh = [int(v) for v in RH_LH_TBL[1:256:2]]
    a = [(128 + k) * 256 * v for k, v in enumerate(rh)]
    ll = [int(v) for v in LL_TBL]
    return (_byte_limbs([(rh, 0, 3), (rh, 24, 4), (a, 0, 3), (a, 24, 3),
                         (a, 48, 1), (lh, 0, 3), (lh, 24, 3)]),
            _byte_limbs([(ll, 0, 3), (ll, 24, 3)]))


_BY_K, _BY_INDEX2 = _ln_tables()
# 2^48 - crush_ln(0xffff) in 24-bit limbs
_M_LAST = tuple(np.uint32(v) for v in
                divmod((1 << 48) - crush_ln(0xFFFF), 1 << 24))


def _lookup(table, index):
    """table[index] for a ``_byte_limbs`` table as uint32 values, one
    per column: a one-hot row times the byte columns on the MXU (one
    term a sum, so exact): a gather costs the v5e 6-10 ns an index,
    this a fraction of it (PERF.md section 5)."""
    limbs, widths = table
    hot = index[..., None] == jnp.arange(limbs.shape[0], dtype=jnp.uint32)
    got = jnp.einsum("...k,kc->...c", hot.astype(jnp.bfloat16),
                     jnp.asarray(limbs, jnp.bfloat16),
                     preferred_element_type=jnp.float32).astype(jnp.uint32)
    out, c = [], 0
    for n in widths:
        value = got[..., c]
        for b in range(1, n):
            value = value | (got[..., c + b] << (8 * b))
        out.append(value)
        c += n
    return out


def straw2_recip(weights) -> np.ndarray:
    """Host-built per-item factor of ``straw2_quotient``:
    float32((1 - 2^-20) / w), 0 where w == 0."""
    w = np.asarray(weights, np.float64)
    return np.where(w > 0, (1.0 - 2.0 ** -20) / np.maximum(w, 1.0),
                    0.0).astype(np.float32)


def crush_ln_jnp(u):
    """M = 2^48 - crush_ln(u) over uint32 u in [0, 0xffff] as two uint32
    limbs (bits 24 and up, bits 0-23): 0 < M <= 2^48, 49 bits, u = 0
    giving 2^48 itself.  mapper.c's steps (crush_ln, :229-269) in
    32-bit arithmetic; crush_ln is not monotone in u, so nothing
    shorter than its value orders two draws."""
    u32 = jnp.uint32
    x = u + u32(1)
    # x << bits so that bit 15 leads; iexpon = 15 - bits.  (x = 2^16,
    # which u = 0xffff alone gives, looks up nothing and is set below.)
    iexpon = u32(31) - jax.lax.clz(x)
    x = x << (u32(15) - iexpon)
    low = x & u32(0xFF)
    rh_lo, rh_hi, a_lo, a_mid, a_top, lh_lo, lh_hi = _lookup(
        _BY_K, (x >> 8) - u32(128))
    # index2 = bits 48-55 of x * RH = A + low * RH, 24 bits at a time
    t = low * rh_hi + a_mid + ((low * rh_lo + a_lo) >> 24)
    ll_lo, ll_hi = _lookup(_BY_INDEX2, ((t >> 24) + a_top) & u32(0xFF))
    # LH + LL (49 bits), then ln = (iexpon << 44) + ((LH + LL) >> 4)
    lo = lh_lo + ll_lo
    hi = lh_hi + ll_hi + (lo >> 24)
    ln_lo = ((hi & u32(0xF)) << 20) | ((lo & u32(0xFFFFFF)) >> 4)
    ln_hi = (iexpon << 20) + (hi >> 4)
    m_lo = (u32(1 << 24) - ln_lo) & u32(0xFFFFFF)
    m_hi = u32(1 << 24) - ln_hi - (ln_lo != 0).astype(u32)
    last = u == u32(0xFFFF)
    return (jnp.where(last, _M_LAST[0], m_hi),
            jnp.where(last, _M_LAST[1], m_lo))


def straw2_quotient(m_hi, m_lo, w, rw):
    """Exact M // w as uint32 (bits 32 and up, bits 0-31) for
    M = m_hi * 2^24 + m_lo <= 2^48 and uint32 0 < w < 2^31, with
    rw = straw2_recip(w); w == 0 gives a value without meaning.

    Three estimate-and-subtract steps and one compare.  An estimate is
    floor(float32(R) * rw) for the running remainder R: rw's bias
    outweighs every rounding in it (2^-24 each: rw's own on the host,
    R's conversion and the product on the device), so it never exceeds
    R / w and falls short by under R / w * 2^-19 + 1.  The remainder
    R - q * w is therefore never negative and under w + R * 2^-19: under
    w + 2^29 < 2^32 after the first step, so exact in wrapping uint32
    arithmetic from there on, under w * (1 + 2^-19) + 2^10 after the
    second and under 2 * w after the third."""
    f32, u32 = jnp.float32, jnp.uint32
    f = (m_hi.astype(f32) * f32(2.0 ** 24) + m_lo.astype(f32)) * rw
    # floor(f) < 2^49 as limbs: both products and the difference are exact
    f_hi = jnp.floor(f * f32(2.0 ** -24))
    q_lo = (f_hi.astype(u32) << 24) + (f - f_hi * f32(2.0 ** 24)).astype(u32)
    q_hi = f_hi.astype(u32) >> 8
    r = ((m_hi << 24) | m_lo) - q_lo * w
    for _ in range(2):
        q = (r.astype(f32) * rw).astype(u32)
        r = r - q * w
        q_lo = q_lo + q
        q_hi = q_hi + (q_lo < q).astype(u32)
    last = (r >= w).astype(u32)
    q_lo = q_lo + last
    return q_hi + (q_lo < last).astype(u32), q_lo


def straw2_draws(x, item_ids, r, weights, recips):
    """Draws of one bucket per lane, items along axis 0.

    x, r: (lanes,) int32; item_ids, weights: (n, lanes) int32; recips:
    straw2_recip of the weights.  Returns q = (2^48 - crush_ln(u)) // w
    as two (n, lanes) uint32 limbs: mapper.c's draw div64_s64(crush_ln(u)
    - 2^48, w) is -q (a non-positive numerator truncates toward zero),
    so the item it picks, the largest draw and the first of equals, is
    ``straw2_choose``'s smallest q.  Both limbs all-ones where w == 0."""
    u = hash32_3_jnp(x[None], item_ids, r[None]) & np.uint32(0xFFFF)
    # metadata only: names the draw's operations in a device trace
    with jax.named_scope("straw2_draw"):
        w = weights.astype(jnp.uint32)
        # w == 0 divides nothing (its factor is 0) and is masked here
        q_hi, q_lo = straw2_quotient(*crush_ln_jnp(u), w, recips)
        return (jnp.where(w > 0, q_hi, _U32_MAX),
                jnp.where(w > 0, q_lo, _U32_MAX))


def straw2_choose(q_hi, q_lo):
    """Index along axis 0 of the smallest (q_hi, q_lo), the first of
    equals: an all-zero-weight bucket picks item 0, as mapper.c does."""
    top = q_hi == jnp.min(q_hi, axis=0)
    low = jnp.where(top, q_lo, _U32_MAX)
    return jax.lax.argmax(top & (low == jnp.min(low, axis=0)), 0, jnp.int32)


def is_out_jnp(osd_weights, item, x):
    """Vector is_out (mapper.c:419-433): weight is 16.16 reweight."""
    w = osd_weights[item]
    h = hash32_2_jnp(x, item.astype(jnp.uint32)) & np.uint32(0xFFFF)
    probably_out = h.astype(jnp.int32) >= w
    return jnp.where(w >= 0x10000, False,
                     jnp.where(w == 0, True, probably_out))


@dataclass
class CompiledMap:
    """Flattened uniform-depth straw2 hierarchy for the fused path.

    Level l holds every bucket at distance l from the take root as
    padded tables; the choose phase descends them in lockstep (one
    straw2 draw + argmin per level per lane), exactly the recursive
    descent of mapper.c crush_choose_firstn/indep, but data-parallel
    over the lane axis.  Arbitrary depth (root->rack->host->osd and
    deeper) compiles; non-uniform leaf depth or non-straw2 buckets
    fall back to the scalar engine.

    child_ids carry the CRUSH item ids (what straw2 hashes);
    child_idx carry the row index into the NEXT level's tables (or the
    osd id at the last level).  weights are the bucket item weights,
    0-padded; cw holds the choose_args weight-set override per output
    position when the map has one (mapper.c get_choose_arg_weights).
    """

    n_levels: int                       # bucket levels (root = level 0)
    child_ids: list                     # [(B_l, N_l) int32]
    child_idx: list                     # [(B_l, N_l) int32]
    weights: list                       # [(B_l, N_l) int32]
    cw: list | None                     # [(P, B_l, N_l)] or None
    bucket_ids: list                    # [(B_l,) int32] crush ids per level
    max_devices: int
    leaf_parent_types: frozenset = frozenset()

    @classmethod
    def from_map(cls, crush_map: CrushMap, root_id: int,
                 choose_args: dict | None = None) -> "CompiledMap":
        levels: list[list] = [[crush_map.buckets[root_id]]]
        while True:
            cur = levels[-1]
            kinds = set()
            for b in cur:
                if b.alg != CRUSH_BUCKET_STRAW2:
                    raise FusedUnsupported(
                        "bucket_alg", "fused path requires straw2")
                if not b.items:
                    # mapper.c rejects a choice that lands in one; a
                    # table row has to point somewhere
                    raise FusedUnsupported(
                        "empty_bucket", "empty bucket under the root")
                for i in b.items:
                    kinds.add(i < 0)
            if kinds == {True}:
                levels.append([crush_map.buckets.get(i)
                               for b in cur for i in b.items])
                if any(b is None for b in levels[-1]):
                    raise FusedUnsupported(
                        "dangling", "dangling bucket reference")
            elif kinds == {False}:
                break                   # this level's items are osds
            else:
                raise FusedUnsupported(
                    "mixed_children", "mixed osd/bucket children "
                    "unsupported by the fused path")
        # dense row index per bucket id per level
        idx_of = [{b.id: j for j, b in enumerate(lv)} for lv in levels]
        child_ids, child_idx, weights, cw, bids = [], [], [], [], []
        ca = choose_args if choose_args is not None else \
            getattr(crush_map, "choose_args", None)
        positions = 1
        if ca:
            for arg in ca.values():
                if arg.get("weight_set"):
                    positions = max(positions, len(arg["weight_set"]))
        for l, lv in enumerate(levels):
            maxn = max(b.size for b in lv)
            ids = np.zeros((len(lv), maxn), np.int32)
            idx = np.zeros((len(lv), maxn), np.int32)
            w = np.zeros((len(lv), maxn), np.int32)
            cwl = np.zeros((positions, len(lv), maxn), np.int32)
            for j, b in enumerate(lv):
                arg = (ca or {}).get(b.id) or {}
                hash_ids = arg.get("ids") or b.items
                ids[j, :b.size] = hash_ids
                ids[j, b.size:] = hash_ids[0] if b.size else 0
                w[j, :b.size] = b.item_weights
                ws = arg.get("weight_set")
                for pos in range(positions):
                    src = (ws[min(pos, len(ws) - 1)] if ws
                           else b.item_weights)
                    cwl[pos, j, :b.size] = src
                if l + 1 < len(levels):
                    idx[j, :b.size] = [idx_of[l + 1][i]
                                       for i in b.items]
                    idx[j, b.size:] = idx[j, 0] if b.size else 0
                else:
                    idx[j, :b.size] = b.items
                    idx[j, b.size:] = b.items[0] if b.size else 0
            child_ids.append(ids)
            child_idx.append(idx)
            weights.append(w)
            cw.append(cwl)
            bids.append(np.asarray([b.id for b in lv], np.int32))
        has_ca = bool(ca) and any(
            a.get("weight_set") or a.get("ids") for a in ca.values())
        return cls(len(levels), child_ids, child_idx, weights,
                   cw if has_ca else None, bids,
                   crush_map.max_devices,
                   frozenset(b.type for b in levels[-1]))


def _rule_shape(crush_map: CrushMap, ruleno: int):
    """Parse a rule into (root_id, firstn, leaf, choose_tries, leaf_tries)."""
    rule = crush_map.rules[ruleno]
    t = crush_map.tunables
    choose_tries = t.choose_total_tries + 1
    leaf_tries = 0
    root_id = None
    mode = None
    choose_type = 0
    for step in rule.steps:
        if step.op == CRUSH_RULE_SET_CHOOSE_TRIES:
            choose_tries = step.arg1
        elif step.op == CRUSH_RULE_SET_CHOOSELEAF_TRIES:
            leaf_tries = step.arg1
        elif step.op == CRUSH_RULE_TAKE:
            root_id = step.arg1
        elif step.op in (CRUSH_RULE_CHOOSE_FIRSTN, CRUSH_RULE_CHOOSELEAF_FIRSTN,
                         CRUSH_RULE_CHOOSE_INDEP, CRUSH_RULE_CHOOSELEAF_INDEP):
            mode = step.op
            choose_type = step.arg2
    firstn = mode in (CRUSH_RULE_CHOOSE_FIRSTN, CRUSH_RULE_CHOOSELEAF_FIRSTN)
    leaf = mode in (CRUSH_RULE_CHOOSELEAF_FIRSTN, CRUSH_RULE_CHOOSELEAF_INDEP)
    return root_id, firstn, leaf, choose_tries, leaf_tries, choose_type


def _narrow(left, width, lanes, state, loop):
    """``loop(lanes, state)`` -> state over the lanes of ``left``
    alone, compacted to ``width`` lanes (at most that many are left),
    and not run where none is: the count is read on the device, both
    ways are in the one program.  lanes and state: tuples of (L,) int32
    rows, what the loop reads of a lane and what it changes; a lane
    outside ``left`` passes through the loop unchanged."""
    n = len(lanes)
    # items lead, (rows, L): one gather lands a lane's rows as (rows, width)
    rows = jnp.stack(lanes + state)

    def run():
        idx = _first_lanes(left, width)
        sub = rows[:, idx]
        got = loop(tuple(sub[:n]), tuple(sub[n:]))
        return rows[n:].at[:, idx].set(jnp.stack(got), unique_indices=True)

    return tuple(jax.lax.cond(jnp.any(left), run, lambda: rows[n:]))


def _first_lanes(left, width):
    """The lanes of ``left`` in order, then lanes outside it, ``width``
    in all and each once: the pad lanes are real ones that a loop
    passes through unchanged, so nothing marks or drops them."""
    L = left.shape[0]
    lane = jnp.arange(L, dtype=jnp.int32)
    first = jax.lax.sort(jnp.where(left, lane, lane + np.int32(L)))[:width]
    return jnp.where(first >= L, first - np.int32(L), first)


# device programs traced so far (a jit cache miss of crush_firstn or
# crush_indep): map_pgs reads it around a launch
_programs_traced = 0


class Structure(NamedTuple):
    """What decides a mapper's device program besides ``numrep``, the
    lane count and the shapes of its tables (buckets and items a level,
    weight-set positions): the depth, the rule's mode and tries with the
    tunables folded in (jewel's vary_r and stable are all the fused path
    takes), and the two retry constants as they stood when the mapper
    was made.  The static part of the ``VectorCrush`` pytree: compared
    by value, so maps of one structure share an executable whatever
    their weights."""

    n_levels: int
    firstn: bool
    leaf: bool
    choose_tries: int
    recurse_tries: int
    retry_min_lanes: int
    retry_narrow: int


@jax.tree_util.register_pytree_node_class
class VectorCrush:
    """Bulk mapper for one (map, rule) pair, any uniform depth: the
    map's level tables on the device (uploaded once, here) and the
    ``Structure`` of the program they are operands of.  As an argument
    of ``crush_firstn`` / ``crush_indep`` the mapper flattens to its
    tables; nothing of a bucket's weights reaches the program any other
    way."""

    def __init__(self, crush_map: CrushMap, ruleno: int,
                 choose_args: dict | None = None) -> None:
        (root_id, firstn, leaf, choose_tries, leaf_tries,
         choose_type) = _rule_shape(crush_map, ruleno)
        self.cm = CompiledMap.from_map(crush_map, root_id, choose_args)
        # chooseleaf picks buckets at the LAST bucket level then
        # recurses to an osd; plain choose must name the device level
        if leaf:
            # only the tree under THIS rule's take root matters: a
            # second hierarchy's leaf parents must not veto the map
            if self.cm.leaf_parent_types != {choose_type}:
                raise FusedUnsupported(
                    "leaf_type", "chooseleaf type must be the "
                    "osd-parent level for the fused path")
        elif choose_type != 0:
            raise FusedUnsupported(
                "choose_type", "plain choose of a bucket type needs "
                "the scalar engine")
        t = crush_map.tunables
        if not t.chooseleaf_stable or t.chooseleaf_vary_r != 1:
            # scalar fallback covers other tunable profiles
            raise FusedUnsupported(
                "tunables", "fused path implements jewel tunables")
        if firstn:
            recurse_tries = (leaf_tries if leaf_tries
                             else (1 if t.chooseleaf_descend_once
                                   else choose_tries))
        else:
            recurse_tries = leaf_tries if leaf_tries else 1
        self.structure = Structure(
            self.cm.n_levels, firstn, leaf, choose_tries, recurse_tries,
            RETRY_MIN_LANES, RETRY_NARROW)
        self.tables = self._tables()
        # running totals of map_pgs: device launches; crush_firstn's
        # lanes finished by a narrow retry loop; full-width passes after
        # a replica's first (firstn) or a launch's first (indep, where
        # the launch has a narrow stage); crush_indep's passes, at
        # either width, and the (lane, slot) pairs handed to its narrow
        # stage.  Apart from them: launches that had to trace their
        # program first
        self.launches = self.retry_lanes = self.wide_retries = 0
        self.indep_passes = self.indep_retry_pairs = 0
        self.programs_built = 0

    def tree_flatten(self):
        return self.tables, self.structure

    @classmethod
    def tree_unflatten(cls, structure, tables):
        """The mapper a traced program sees: tables and structure,
        no map and no totals."""
        vc = object.__new__(cls)
        vc.structure, vc.tables = structure, tuple(tables)
        return vc

    def _tables(self) -> tuple:
        """Per level one int32 table (4 * N, P, B) on the device, all
        that a choice in a bucket needs: every item's hashed id, its
        weight at each weight-set position, the bits of that weight's
        straw2_recip (built here, on the host), and its child (a row of
        the next level, or the osd).  Items lead, so that one gather of
        a lane's bucket row lands all four as (n, lanes)."""
        cm = self.cm
        per_pos = cm.cw if cm.cw is not None else \
            [t[None] for t in cm.weights]                # (P, B, N)
        return tuple(jnp.asarray(np.concatenate(
            [np.broadcast_to(ids, w.shape), w,
             straw2_recip(w).view(np.int32), np.broadcast_to(idx, w.shape)],
            axis=2).transpose(2, 0, 1))
            for ids, idx, w in zip(cm.child_ids, cm.child_idx, per_pos))

    def _choose(self, tables, lvl, xs, cur, r, pos):
        """One straw2 choice per lane in bucket row ``cur`` of level
        ``lvl``: the chosen item's child.  ``pos`` is the choose_args
        weight-set position -- a scalar, or a PER-LANE vector when
        lanes have placed different counts (firstn's outpos)."""
        table = tables[lvl]
        p = jnp.clip(jnp.asarray(pos, jnp.int32), np.int32(0),
                     np.int32(table.shape[1] - 1))
        ids, w, rw, child = jnp.split(table[:, p, cur], 4)
        j = straw2_choose(*straw2_draws(
            xs, ids, r, w, jax.lax.bitcast_convert_type(rw, jnp.float32)))
        # the chosen row of ``child``: a select, not a second gather
        rows = jnp.arange(child.shape[0], dtype=jnp.int32)[:, None]
        return jnp.sum(jnp.where(rows == j, child, np.int32(0)), axis=0,
                       dtype=jnp.int32)

    def _descend(self, tables, xs, r, pos, upto: int):
        """Lockstep descent: levels 0..upto-1, one draw per level.
        Returns row indices into level ``upto``'s tables (or osd ids
        when upto == n_levels)."""
        cur = jnp.zeros(xs.shape, jnp.int32)
        for l in range(upto):
            cur = self._choose(tables, l, xs, cur, r, pos)
        return cur

    def _leaf_descend(self, tables, xs, host_idx, sub_r, rep,
                      numrep, osd_weights, taken, pos):
        """chooseleaf recursion into the chosen last-level bucket:
        up to recurse_tries draws, rejecting out osds and (firstn)
        collisions with already-placed osds."""
        lvl = self.structure.n_levels - 1
        L = xs.shape[0]

        def cond(st):
            ft, found, _ = st
            # one shared try counter: a still-searching lane's personal
            # ftotal equals the iteration count (it either found and
            # froze, or rejected every round so far)
            return jnp.any(~found) & (ft < self.structure.recurse_tries)

        def body(st):
            ft, found, osd = st
            if self.structure.firstn:
                # leaf recursion: numrep=1, rep'=0 (stable), so
                # r_leaf = sub_r + ftotal_leaf
                r_leaf = (sub_r + ft).astype(jnp.int32)
            else:
                r_leaf = (rep + sub_r + numrep * ft).astype(jnp.int32)
            cand = self._choose(tables, lvl, xs, host_idx, r_leaf, pos)
            bad = is_out_jnp(osd_weights, cand, xs)
            if taken is not None:
                for t in taken:
                    bad |= t == cand
            ok = ~found & ~bad
            osd = jnp.where(ok, cand, osd)
            return ft + 1, found | ok, osd

        init = (jnp.int32(0), jnp.zeros((L,), bool),
                jnp.full((L,), _NONE))
        _, found, osd = jax.lax.while_loop(cond, body, init)
        return osd, found

    # -- firstn -------------------------------------------------------------
    def _firstn_try(self, tables, rep, numrep, osd_weights, lanes, state):
        """Replica ``rep``'s next try in every lane that holds no osd
        yet: the body of the retry loop, one for all replicas (``rep``
        is traced) and at the width of the rows it is given.  lanes:
        (xs, placed, the osd column of each replica slot, its bucket
        column), ``_NONE``, which no candidate equals, in the slots
        not placed yet; state: (ftotal, bucket, osd), the osd ``_NONE``
        until a try is accepted."""
        xs, placed = lanes[:2]
        out, out_sel = lanes[2:2 + numrep], lanes[2 + numrep:]
        ftotal, sel, osd = state
        done = osd != _NONE
        r = rep + ftotal
        # chooseleaf targets the last bucket level; plain choose (no
        # leaf recursion) targets the device level
        cand_sel = self._descend(
            tables, xs, r, placed,
            self.structure.n_levels - (1 if self.structure.leaf else 0))
        collide = jnp.zeros(xs.shape, bool)
        for prev in out_sel:
            collide |= prev == cand_sel
        if self.structure.leaf:
            # vary_r=1: sub_r = r >> 0 = r
            cand_osd, found = self._leaf_descend(
                tables, xs, cand_sel, r, rep, numrep, osd_weights, out,
                placed)
            reject = ~found
        else:
            cand_osd = cand_sel
            reject = is_out_jnp(osd_weights, cand_osd, xs)
            for prev in out:
                reject |= prev == cand_osd
        ok = ~done & ~collide & ~reject
        return (jnp.where(done | ok, ftotal, ftotal + 1),
                jnp.where(ok, cand_sel, sel), jnp.where(ok, cand_osd, osd))

    @partial(jax.jit, static_argnames=("numrep",))
    def crush_firstn(self, xs: jnp.ndarray, numrep: int,
                     osd_weights: jnp.ndarray):
        """(osd ids (lanes, numrep), int32 [lanes finished by a narrow
        retry loop, full-width passes after a replica's first])."""
        global _programs_traced
        _programs_traced += 1
        tables, shape = self.tables, self.structure
        L = xs.shape[0]
        width = (L // shape.retry_narrow if L >= shape.retry_min_lanes
                 else 0)

        def left(state):
            ftotal, _, osd = state
            return (osd == _NONE) & (ftotal < self.structure.choose_tries)

        def tries(one_try, lanes, state, fit):
            """Passes of ``one_try`` until at most ``fit`` lanes are
            left: (passes made, state)."""
            return jax.lax.while_loop(
                lambda st: jnp.sum(left(st[1]), dtype=jnp.int32) > fit,
                lambda st: (st[0] + 1, one_try(lanes, st[1])),
                (jnp.int32(0), state))

        def replica(carry, rep):
            # out, out_sel: one column per replica slot, osd and chosen
            # bucket.  placed: per-lane count of PLACED replicas, the
            # scalar engine's outpos, which is the choose_args
            # weight-set position (a lane whose earlier slot exhausted
            # its tries keeps drawing later slots at the unadvanced
            # position, exactly as mapper.c does)
            out, out_sel, placed, retry_lanes, wide_retries = carry
            one_try = partial(self._firstn_try, tables, rep, numrep,
                              osd_weights)
            lanes = (xs, placed, *out, *out_sel)
            state = (jnp.zeros((L,), jnp.int32), jnp.full((L,), _NONE),
                     jnp.full((L,), _NONE))
            passes, state = tries(one_try, lanes, state, width)
            wide_retries += passes - 1
            if width:
                # metadata only, as straw2_draw: what a call spends on
                # the lanes its full-width passes left
                with jax.named_scope("crush_retry"):
                    todo = left(state)
                    retry_lanes += jnp.sum(todo, dtype=jnp.int32)
                    state = _narrow(
                        todo, width, lanes, state,
                        lambda *rows: tries(one_try, *rows, 0)[1])
            _, sel, osd = state
            return (tuple(jnp.where(rep == i, osd, o)
                          for i, o in enumerate(out)),
                    tuple(jnp.where(rep == i, sel, o)
                          for i, o in enumerate(out_sel)),
                    placed + (osd != _NONE).astype(jnp.int32),
                    retry_lanes, wide_retries), None

        # one body for all replicas: the program's size and its compile
        # time do not grow with numrep.  The columns are a tuple, each
        # updated by a select: a stacked (numrep, L) array costs the
        # chip 1.5 ms a launch in row slices and dynamic updates
        unplaced = (jnp.full((L,), _NONE),) * numrep
        (out, _, _, retry_lanes, wide_retries), _ = jax.lax.scan(
            replica,
            (unplaced, unplaced, jnp.zeros((L,), jnp.int32), jnp.int32(0),
             jnp.int32(0)), jnp.arange(numrep, dtype=jnp.int32))
        # scalar firstn COMPACTS (an exhausted slot leaves no hole):
        # shift placed entries left, NONE-pad the tail
        out = jnp.stack(out, axis=1)
        ids = jax.lax.sort(
            ((out == _NONE).astype(jnp.int32), out),
            dimension=1, is_stable=True, num_keys=1)[1]
        return ids, jnp.stack([retry_lanes, wide_retries])

    # -- indep --------------------------------------------------------------
    def _indep_draw(self, tables, numrep, osd_weights, xs, rep, ftotal):
        """The candidates of try ``ftotal``, one per row of ``xs``, for
        slot ``rep`` (traced: a scalar, or a vector when the rows are
        (lane, slot) pairs): (bucket, osd), the osd ``_NONE`` where the
        leaf tries found none.  A function of the seed, the slot and
        the try alone, of nothing placed so far (mapper.c hands the
        leaf recursion one empty slot): whether a candidate is taken is
        ``crush_indep``'s ``resolve``."""
        shape = self.structure
        r = rep + numrep * ftotal
        # weight-set position is the top call's OUTPOS (0), not the
        # replica slot (crush_choose_indep passes its own outpos down);
        # the leaf recursion's outpos IS the slot, so _leaf_descend
        # keeps rep
        sel = self._descend(tables, xs, r, 0,
                            shape.n_levels - (1 if shape.leaf else 0))
        if shape.leaf:
            osd, _ = self._leaf_descend(tables, xs, sel, r, rep, numrep,
                                        osd_weights, None, rep)
        else:
            osd = jnp.where(is_out_jnp(osd_weights, sel, xs), _NONE, sel)
        return sel, osd

    @partial(jax.jit, static_argnames=("numrep",))
    def crush_indep(self, xs: jnp.ndarray, numrep: int,
                    osd_weights: jnp.ndarray):
        """(osd ids (lanes, numrep), ``_NONE`` at its position where a
        slot stayed unfilled; int32 [passes made (every try, at either
        width), (lane, slot) pairs handed to the narrow stage,
        full-width passes after the first of a launch that has one])."""
        global _programs_traced
        _programs_traced += 1
        tables, shape = self.tables, self.structure
        L = xs.shape[0]
        UNDEF = jnp.int32(0x7FFFFFFE)
        pairs = numrep * L
        width = (pairs // shape.retry_narrow if L >= shape.retry_min_lanes
                 else 0)
        draw = partial(self._indep_draw, tables, numrep, osd_weights)

        def undefined(out_h):
            return jnp.stack(out_h) == UNDEF                # (numrep, L)

        def resolve(state, sel, osd):
            """A pass's second half, at full width and in slot order:
            slot ``rep`` takes its candidate (rows ``rep`` of ``sel``
            and ``osd``) where it is undefined, an osd was found and no
            column holds the bucket, the earlier slots' as this pass
            left them.  Compares and selects alone."""
            ftotal, out_h, out_o = state
            out_h, out_o = list(out_h), list(out_o)
            for rep in range(numrep):
                collide = jnp.zeros((L,), bool)
                for col in out_h:
                    collide |= col == sel[rep]
                ok = (out_h[rep] == UNDEF) & (osd[rep] != _NONE) & ~collide
                out_h[rep] = jnp.where(ok, sel[rep], out_h[rep])
                out_o[rep] = jnp.where(ok, osd[rep], out_o[rep])
            return ftotal + 1, tuple(out_h), tuple(out_o)

        def tries(one_pass, state, fit):
            """Passes until at most ``fit`` pairs are undefined."""
            return jax.lax.while_loop(
                lambda st: (st[0] < shape.choose_tries) & (jnp.sum(
                    undefined(st[1]), dtype=jnp.int32) > fit),
                one_pass, state)

        def wide_pass(state):
            """Every pair drawn, one body for all slots (``rep`` is
            traced: the program's size and its compile time do not grow
            with numrep).  A while_loop: a scan's body is lowered as a
            function of its own, whose operations lose ``crush_indep``
            from their names in the lowered text."""
            def slot(carry):
                rep, sel, osd = carry
                s, o = draw(xs, rep, state[0])
                return rep + 1, sel.at[rep].set(s), osd.at[rep].set(o)

            none = jnp.zeros((numrep, L), jnp.int32)
            _, sel, osd = jax.lax.while_loop(
                lambda carry: carry[0] < numrep, slot,
                (jnp.int32(0), none, none))
            return resolve(state, sel, osd)

        def narrow(state):
            """The pairs still undefined (at most ``width``), drawn
            alone until none is left or out of tries: compacted once,
            a pair placed meanwhile is drawn on and ignored, as the pad
            pairs are (real ones, placed before)."""
            idx = _first_lanes(undefined(state[1]).reshape(-1), width)
            rep, lane = jnp.divmod(idx, np.int32(L))
            sub_xs = xs[lane]

            def narrow_pass(st):
                cand = jnp.stack(draw(sub_xs, rep, st[0]))
                sel, osd = jnp.zeros((2, pairs), jnp.int32).at[:, idx].set(
                    cand, unique_indices=True).reshape(2, numrep, L)
                return resolve(st, sel, osd)

            return tries(narrow_pass, state, 0)

        undef = (jnp.full((L,), UNDEF, jnp.int32),) * numrep
        retry_pairs = wide_retries = jnp.int32(0)
        # metadata only, as crush_retry: the erasure rule's loops, which
        # are all of the program but its last select
        with jax.named_scope("crush_indep"):
            state = tries(wide_pass, (jnp.int32(0), undef, undef), width)
            if width:
                wide_retries = jnp.maximum(state[0] - 1, 0)
                with jax.named_scope("crush_retry"):
                    retry_pairs = jnp.sum(undefined(state[1]),
                                          dtype=jnp.int32)
                    state = jax.lax.cond(retry_pairs > 0, narrow,
                                         lambda st: st, state)
        passes, _, out_o = state
        out_o = jnp.stack(out_o, axis=1)
        return (jnp.where(out_o == UNDEF, _NONE, out_o),
                jnp.stack([passes, retry_pairs, wide_retries]))

    def totals(self) -> dict[str, int]:
        """The running totals under their names in the monitor's
        ``placement_cache`` set (mon/pg_mapping.py)."""
        return {"fused_launches": self.launches,
                "retry_lanes": self.retry_lanes,
                "wide_retries": self.wide_retries,
                "indep_passes": self.indep_passes,
                "indep_retry_pairs": self.indep_retry_pairs}

    def map_pgs(self, xs, numrep: int, osd_weights) -> np.ndarray:
        """Map every placement seed in ``xs``: (len(xs), numrep) osd
        ids.  At most ``MAX_LANES`` lanes go into one device launch
        (longer inputs run as equal-sized launches, the tail padded),
        which bounds device memory and the number of compiled shapes
        whatever size a caller hands in."""
        fn = self.crush_firstn if self.structure.firstn else self.crush_indep
        w = jnp.asarray(osd_weights, jnp.int32)
        # lint: disable=device-path-host-sync -- host-side input marshal of the seeds, no device array involved
        xs = np.asarray(xs).astype(np.int32)
        n = xs.shape[0]
        if n <= MAX_LANES:
            parts = [xs]
        else:
            parts = np.concatenate(
                [xs, np.zeros(-n % MAX_LANES, np.int32)]
            ).reshape(-1, MAX_LANES)
        out = []
        for part in parts:
            traced = _programs_traced
            # the calling thread, from the launch until its result is
            # on the host
            with section("device_wait.crush"):
                # lint: disable=device-path-host-sync -- one materialization per bounded launch of the bulk map
                ids, counts = jax.device_get(
                    fn(jnp.asarray(part), numrep, w))
            out.append(ids)
            self.launches += 1
            self.programs_built += _programs_traced - traced
            if self.structure.firstn:
                retry_lanes, wide_retries = counts
                self.retry_lanes += int(retry_lanes)
            else:
                passes, retry_pairs, wide_retries = counts
                self.indep_passes += int(passes)
                self.indep_retry_pairs += int(retry_pairs)
            self.wide_retries += int(wide_retries)
        return np.concatenate(out)[:n]
