"""Vectorized CRUSH on TPU: bulk PG->OSD mapping as one XLA launch.

The reference recomputes full-cluster mappings on host thread pools
(ParallelPGMapper, src/osd/OSDMapMapping.h:18; used by the balancer and
OSDMonitor's PrimeTempJob).  Here the whole job is one data-parallel
program over the PG axis: straw2 draws become gathers into the fixed-point
log tables plus an argmax, and the firstn/indep retry loops become bounded
`lax.while_loop`s with per-lane masks -- decision-identical to the scalar
mapper (ceph_tpu/crush/mapper.py), which is itself pinned to mapper.c.

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

import numpy as np

import jax

# straw2 draws are 64-bit fixed-point, which needs jax's x64 mode -- but
# flipping the PROCESS-GLOBAL flag at import time would change numeric
# promotion for every other jax user in an embedding process (importing
# ceph_tpu must be side-effect free).  The x64 requirement is scoped to
# the mapper entry points instead via the thread-local jax.enable_x64
# context (the jit caches key on it, so fused-mapper traces always see
# x64 while the rest of the package traces unchanged).
import jax.numpy as jnp

from ..common.tracing import section
from .ln import RH_LH_TBL, LL_TBL
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

# numpy constant: materializing a jnp.int64 here would require x64 at
# import time (exactly what this module must not demand)
S64_MIN = np.int64(-(2**63))
CRUSH_HASH_SEED = np.uint32(1315423911)

# lanes per device launch.  The retry loops carry (lanes, n_items)
# int64 tables whose minor dimension the TPU pads to 128, so a launch
# costs kilobytes of HBM per lane: a 2M-lane launch aborted the v5e
# runtime outright, and every new lane count is a new multi-minute
# compile of the emulated-int64 program (CHANGES.md, PR 21).
MAX_LANES = 1 << 17


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


# keep the int64 log tables as NUMPY at module scope: a jnp.asarray
# here would run outside the enable_x64 scope and silently truncate to
# int32.  They become trace-time constants inside crush_ln_jnp, which
# only ever traces under x64.
_RH_LH_NP = np.asarray(RH_LH_TBL, np.int64)   # (258,)
_LL_NP = np.asarray(LL_TBL, np.int64)         # (256,)


def crush_ln_jnp(u):
    """Vector crush_ln over int32 u in [0, 0xffff] -> int64."""
    _RH_LH = jnp.asarray(_RH_LH_NP)
    _LL = jnp.asarray(_LL_NP)
    x = u.astype(jnp.int64) + 1
    need = (x & 0x18000) == 0
    masked = (x & 0x1FFFF).astype(jnp.int32)
    # bit_length via 31 - clz
    bl = 32 - jax.lax.clz(masked)
    bits = jnp.where(need, 16 - bl, 0).astype(jnp.int64)
    x = x << bits
    iexpon = (15 - bits).astype(jnp.int64)
    index1 = ((x >> 8) << 1).astype(jnp.int32)
    rh = _RH_LH[index1 - 256]
    lh = _RH_LH[index1 + 1 - 256]
    xl64 = (x * rh) >> 48
    index2 = (xl64 & 0xFF).astype(jnp.int32)
    ll = _LL[index2]
    return (iexpon << 44) + ((lh + ll) >> 4)


def straw2_draws(x, item_ids, r, weights):
    """Draw values for one bucket: shapes broadcast over (..., n_items).

    x: (...,) int32 lanes; item_ids/weights: (..., n) int32.
    Returns (..., n) int64 draws (S64_MIN where weight==0).
    """
    u = (hash32_3_jnp(x[..., None], item_ids, r[..., None])
         & np.uint32(0xFFFF)).astype(jnp.int32)
    # metadata only: names the draw's operations in a device trace
    with jax.named_scope("straw2_draw"):
        ln = crush_ln_jnp(u) - jnp.int64(0x1000000000000)
        w = weights.astype(jnp.int64)
        draws = jax.lax.div(ln, jnp.maximum(w, 1))
        return jnp.where(w > 0, draws, S64_MIN)


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
    straw2 draw + argmax per level per lane), exactly the recursive
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
                    raise ValueError("fused path requires straw2")
                for i in b.items:
                    kinds.add(i < 0)
            if kinds == {True}:
                levels.append([crush_map.buckets.get(i)
                               for b in cur for i in b.items])
                if any(b is None for b in levels[-1]):
                    raise ValueError("dangling bucket reference")
            elif kinds == {False}:
                break                   # this level's items are osds
            else:
                raise ValueError("mixed osd/bucket children "
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


class VectorCrush:
    """Bulk mapper for one (map, rule) pair, any uniform depth."""

    def __init__(self, crush_map: CrushMap, ruleno: int,
                 choose_args: dict | None = None) -> None:
        (root_id, firstn, leaf, choose_tries, leaf_tries,
         choose_type) = _rule_shape(crush_map, ruleno)
        self.cm = CompiledMap.from_map(crush_map, root_id, choose_args)
        # chooseleaf picks buckets at the LAST bucket level then
        # recurses to an osd; plain choose must name the device level
        self.leaf = leaf
        if leaf:
            # only the tree under THIS rule's take root matters: a
            # second hierarchy's leaf parents must not veto the map
            if self.cm.leaf_parent_types != {choose_type}:
                raise ValueError(
                    "chooseleaf type must be the osd-parent level for "
                    "the fused path")
        elif choose_type != 0:
            raise ValueError("plain choose of a bucket type needs the "
                             "scalar engine")
        t = crush_map.tunables
        self.firstn = firstn
        self.choose_tries = choose_tries
        self.leaf_tries = leaf_tries
        self.vary_r = t.chooseleaf_vary_r
        self.stable = t.chooseleaf_stable
        self.descend_once = t.chooseleaf_descend_once
        if firstn:
            self.recurse_tries = (leaf_tries if leaf_tries
                                  else (1 if self.descend_once
                                        else choose_tries))
        else:
            self.recurse_tries = leaf_tries if leaf_tries else 1
        if not self.stable or self.vary_r != 1:
            # scalar fallback covers other tunable profiles
            raise ValueError("fused path implements jewel tunables")

    def _tables(self):
        cm = self.cm
        ids = [jnp.asarray(t) for t in cm.child_ids]
        idx = [jnp.asarray(t) for t in cm.child_idx]
        if cm.cw is not None:
            w = [jnp.asarray(t) for t in cm.cw]      # (P, B, N)
        else:
            w = [jnp.asarray(t)[None] for t in cm.weights]
        return ids, idx, w

    def _descend(self, ids, idx, w, xs, r, pos, upto: int):
        """Lockstep descent: levels 0..upto-1, one draw per level.
        Returns row indices into level ``upto``'s tables (or osd ids
        when upto == n_levels).  ``pos`` is the choose_args weight-set
        position -- a scalar, or a PER-LANE vector when lanes have
        placed different counts (firstn's outpos)."""
        L = xs.shape[0]
        cur = jnp.zeros((L,), jnp.int32)
        for l in range(upto):
            wl = w[l]
            p = jnp.clip(jnp.asarray(pos), 0, wl.shape[0] - 1)
            draws = straw2_draws(xs, ids[l][cur], r, wl[p, cur])
            j = jnp.argmax(draws, axis=-1)
            cur = idx[l][cur, j]
        return cur

    def _leaf_descend(self, ids, idx, w, xs, host_idx, sub_r, rep,
                      numrep, osd_weights, taken, pos):
        """chooseleaf recursion into the chosen last-level bucket:
        up to recurse_tries draws, rejecting out osds and (firstn)
        collisions with already-placed osds."""
        lvl = self.cm.n_levels - 1
        L = xs.shape[0]
        wl = w[lvl]
        pos = jnp.clip(jnp.asarray(pos), 0, wl.shape[0] - 1)

        def cond(st):
            ft, found, _ = st
            # one shared try counter: a still-searching lane's personal
            # ftotal equals the iteration count (it either found and
            # froze, or rejected every round so far)
            return jnp.any(~found) & (ft < self.recurse_tries)

        def body(st):
            ft, found, osd = st
            if self.firstn:
                # leaf recursion: numrep=1, rep'=0 (stable), so
                # r_leaf = sub_r + ftotal_leaf
                r_leaf = (sub_r + ft).astype(jnp.int32)
            else:
                r_leaf = (rep + sub_r + numrep * ft).astype(jnp.int32)
            draws = straw2_draws(xs, ids[lvl][host_idx], r_leaf,
                                 wl[pos, host_idx])
            j = jnp.argmax(draws, axis=-1)
            cand = idx[lvl][host_idx, j]
            bad = is_out_jnp(osd_weights, cand, xs)
            if taken is not None:
                for t in taken:
                    bad |= t == cand
            ok = ~found & ~bad
            osd = jnp.where(ok, cand, osd)
            return ft + 1, found | ok, osd

        init = (jnp.int32(0), jnp.zeros((L,), bool),
                jnp.full((L,), CRUSH_ITEM_NONE, jnp.int32))
        _, found, osd = jax.lax.while_loop(cond, body, init)
        return osd, found

    # -- firstn -------------------------------------------------------------
    @partial(jax.jit, static_argnames=("self", "numrep"))
    def crush_firstn(self, xs: jnp.ndarray, numrep: int,
                   osd_weights: jnp.ndarray) -> jnp.ndarray:
        cm = self.cm
        ids, idx, w = self._tables()
        L = xs.shape[0]
        # chooseleaf targets the last bucket level; plain choose (no
        # leaf recursion) targets the device level
        bucket_levels = cm.n_levels - 1 if self.leaf else cm.n_levels
        out = jnp.full((L, numrep), CRUSH_ITEM_NONE, jnp.int32)
        out_sel = jnp.full((L, numrep), jnp.int32(2**31 - 1), jnp.int32)
        # per-lane count of PLACED replicas: the scalar engine's
        # outpos, which is the choose_args weight-set position (a lane
        # whose earlier slot exhausted its tries keeps drawing later
        # slots at the unadvanced position, exactly as mapper.c does)
        placed = jnp.zeros((L,), jnp.int32)

        for rep in range(numrep):
            def cond(state):
                ftotal, done, _, _ = state
                return jnp.any(~done & (ftotal < self.choose_tries))

            def body(state):
                ftotal, done, sel, osd = state
                r = (rep + ftotal).astype(jnp.int32)
                cand_sel = self._descend(ids, idx, w, xs, r, placed,
                                         bucket_levels)
                collide = jnp.zeros((L,), bool)
                for j in range(rep):
                    collide |= out_sel[:, j] == cand_sel
                if self.leaf:
                    # vary_r=1: sub_r = r >> 0 = r
                    cand_osd, found = self._leaf_descend(
                        ids, idx, w, xs, cand_sel, r, rep, numrep,
                        osd_weights,
                        [out[:, j] for j in range(rep)], placed)
                    reject = ~found
                else:
                    cand_osd = cand_sel
                    reject = is_out_jnp(osd_weights, cand_osd, xs)
                    for j in range(rep):
                        reject |= out[:, j] == cand_osd
                ok = ~done & ~collide & ~reject
                sel = jnp.where(ok, cand_sel, sel)
                osd = jnp.where(ok, cand_osd, osd)
                newdone = done | ok
                ftotal = jnp.where(~newdone, ftotal + 1, ftotal)
                return ftotal, newdone, sel, osd

            init = (jnp.zeros((L,), jnp.int32), jnp.zeros((L,), bool),
                    jnp.full((L,), 2**31 - 1, jnp.int32),
                    jnp.full((L,), CRUSH_ITEM_NONE, jnp.int32))
            ftotal, done, sel, osd = jax.lax.while_loop(cond, body, init)
            out = out.at[:, rep].set(
                jnp.where(done, osd, CRUSH_ITEM_NONE))
            out_sel = out_sel.at[:, rep].set(
                jnp.where(done, sel, 2**31 - 1))
            placed = placed + done.astype(jnp.int32)
        # scalar firstn COMPACTS (an exhausted slot leaves no hole):
        # shift placed entries left, NONE-pad the tail
        is_none = out == CRUSH_ITEM_NONE
        order = jnp.argsort(is_none, axis=1, stable=True)
        return jnp.take_along_axis(out, order, axis=1)

    # -- indep --------------------------------------------------------------
    @partial(jax.jit, static_argnames=("self", "numrep"))
    def crush_indep(self, xs: jnp.ndarray, numrep: int,
                  osd_weights: jnp.ndarray) -> jnp.ndarray:
        cm = self.cm
        ids, idx, w = self._tables()
        L = xs.shape[0]
        UNDEF = jnp.int32(0x7FFFFFFE)
        bucket_levels = cm.n_levels - 1 if self.leaf else cm.n_levels

        def cond(state):
            ftotal, out_h, out_o = state
            return (ftotal < self.choose_tries) & jnp.any(out_h == UNDEF)

        def body(state):
            ftotal, out_h, out_o = state
            for rep in range(numrep):
                slot_undef = out_h[:, rep] == UNDEF
                r = (rep + numrep * ftotal).astype(jnp.int32)
                # weight-set position is the top call's OUTPOS (0),
                # not the replica slot (crush_choose_indep passes its
                # own outpos down); the leaf recursion's outpos IS the
                # slot, so _leaf_descend keeps rep
                cand_sel = self._descend(ids, idx, w, xs, r, 0,
                                         bucket_levels)
                collide = jnp.zeros((L,), bool)
                for j in range(numrep):
                    collide |= out_h[:, j] == cand_sel
                if self.leaf:
                    osd, found = self._leaf_descend(
                        ids, idx, w, xs, cand_sel, r, rep, numrep,
                        osd_weights, None, rep)
                else:
                    osd = cand_sel
                    found = ~is_out_jnp(osd_weights, osd, xs)
                ok = slot_undef & ~collide & found
                out_h = out_h.at[:, rep].set(
                    jnp.where(ok, cand_sel, out_h[:, rep]))
                out_o = out_o.at[:, rep].set(
                    jnp.where(ok, osd, out_o[:, rep]))
            return ftotal + 1, out_h, out_o

        init = (jnp.int32(0),
                jnp.full((L, numrep), UNDEF, jnp.int32),
                jnp.full((L, numrep), UNDEF, jnp.int32))
        _, out_h, out_o = jax.lax.while_loop(cond, body, init)
        return jnp.where(out_o == UNDEF, CRUSH_ITEM_NONE, out_o)

    def map_pgs(self, xs, numrep: int, osd_weights) -> np.ndarray:
        """Map every placement seed in ``xs``: (len(xs), numrep) osd
        ids.  At most ``MAX_LANES`` lanes go into one device launch
        (longer inputs run as equal-sized launches, the tail padded),
        which bounds device memory and the number of compiled shapes
        whatever size a caller hands in."""
        fn = self.crush_firstn if self.firstn else self.crush_indep
        with jax.enable_x64(True):
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
                # the calling thread, from the launch until its result is
                # on the host
                with section("device_wait.crush"):
                    # lint: disable=device-path-host-sync -- one materialization per bounded launch of the bulk map
                    out.append(np.asarray(fn(jnp.asarray(part), numrep, w)))
            return np.concatenate(out)[:n]
