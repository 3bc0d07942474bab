"""PGMapping: the epoch-memoized full-cluster placement table.

The reference never runs CRUSH per client op: OSDMapMapping
(src/osd/OSDMapMapping.h:175) holds the whole pg->osd table, recomputed
in bulk by ParallelPGMapper whenever a new map epoch lands, and every
lookup is an array read.  This module is that table for this repo: ONE
bulk recompute per OSDMap epoch -- a single VectorCrush launch over all
(pool, ps) lanes when the (map, rule) compiles for the fused path, a
batched scalar sweep otherwise -- followed by numpy-vectorized
application of the existing placement semantics (pps hashing, upmap
rewrite, nonexistent/down filtering with EC holes normalized to -1,
pg_temp overrides), so every cached entry is identical to what
``OSDMap.pg_to_up_acting`` computed per PG.

``OSDMap.pg_to_up_acting`` becomes an O(1) read of this table behind an
epoch-keyed memo (mon/osdmap.py), and ``OSD._on_map_change`` consumes
``delta(prev)`` so an epoch bump touches only the PGs whose up/acting
actually changed.  Placement cost then scales with map CHURN, not op
count -- the same shift the codec batching made for EC math.
"""

from __future__ import annotations

import os
import time

import numpy as np

from ..crush import crush_do_rule
from ..crush.hashes import crush_hash32_2_np
from ..crush.types import CRUSH_ITEM_NONE

# below this many lanes (sum of pg_num over same-rule pools) the fused
# JAX path is not worth its trace/compile cost -- the scalar sweep wins
# on the small maps unit tests and the chaos smoke run.  Large maps
# (the bench, real clusters) clear it easily.
FUSED_MIN_LANES = int(os.environ.get("CEPH_TPU_PLACEMENT_FUSED_MIN",
                                     "2048"))


# structurally-identical maps share ONE compiled instance process-wide
# (bounded: stale structures age out).  An in-process cluster runs one
# CrushMap object PER DAEMON, all deserialized from the same mon map;
# without structural sharing each of 64 OSDs would pay its own
# multi-second jit compile for byte-identical hierarchies.
_VC_SHARED: dict[tuple, object] = {}
_VC_SHARED_MAX = 8


def _crush_digest(crush_map) -> str:
    """Structural fingerprint of a CrushMap (buckets/rules/tunables/
    choose_args), cached on the object (maps are replaced wholesale on
    change, never mutated in place)."""
    dig = crush_map.__dict__.get("_structure_digest")
    if dig is None:
        import hashlib
        import json as _json
        from .osdmap import crush_to_dict
        # choose_args are baked into the compiled instance
        # (CompiledMap.from_map falls back to map.choose_args) but are
        # NOT part of crush_to_dict -- digest them explicitly
        blob = _json.dumps(
            {"crush": crush_to_dict(crush_map),
             "choose_args": getattr(crush_map, "choose_args", None)},
            sort_keys=True, default=str)
        dig = hashlib.sha256(blob.encode()).hexdigest()
        crush_map.__dict__["_structure_digest"] = dig
    return dig


def _vector_crush_for(crush_map, ruleno: int):
    """Compiled VectorCrush for a (map, rule), shared two ways: per
    CrushMap object (the jit stays warm across weight-only epochs),
    and across structurally-identical maps process-wide (every daemon
    of an in-process cluster deserializes its own copy of the same
    map; one compile serves them all)."""
    cache = crush_map.__dict__.setdefault("_vc_cache", {})
    ca = getattr(crush_map, "choose_args", None)
    key = (ruleno, id(ca) if ca else None)
    if key not in cache:
        shared_key = (_crush_digest(crush_map), ruleno)
        vc = _VC_SHARED.get(shared_key)
        if vc is None:
            from ..crush.vectorized import VectorCrush
            vc = VectorCrush(crush_map, ruleno)
            while len(_VC_SHARED) >= _VC_SHARED_MAX:
                _VC_SHARED.pop(next(iter(_VC_SHARED)))
            _VC_SHARED[shared_key] = vc
        cache[key] = vc
    return cache[key]


def bulk_crush(crush_map, ruleno: int, xs, numrep: int, weights,
               fused: str = "auto", min_lanes: int | None = None,
               perf=None) -> tuple[np.ndarray, bool]:
    """Map every x in ``xs`` through one rule: (rows, used_fused).

    rows is (len(xs), numrep) int64 with CRUSH_ITEM_NONE holes -- the
    raw result vector, before any OSDMap-level filtering.  ``fused``:
    'auto' tries the vectorized engine when the lane count clears
    ``min_lanes`` and the (map, rule) shape compiles; 'always' forces
    it (raising if the shape cannot compile); 'never' is the pure
    scalar sweep.  crushtool --test and the placement cache both ride
    this helper so offline what-ifs exercise the exact production path.
    ``perf`` takes what the fused launches add to the mapper's running
    totals: ``fused_launches``, ``retry_lanes``, ``wide_retries``.
    """
    xs = np.asarray(xs, dtype=np.int64)
    lanes = int(xs.shape[0])
    threshold = FUSED_MIN_LANES if min_lanes is None else min_lanes
    # a WARM VectorCrush for this (map, rule) makes the fused launch
    # all but free -- the threshold only guards the one-time
    # trace/compile cost, so it does not apply once that cost is sunk
    # (the epoch-recompute path hits the same map object dozens of
    # times during peering/recovery churn on a big cluster)
    ca = getattr(crush_map, "choose_args", None)
    warm = ((ruleno, id(ca) if ca else None)
            in crush_map.__dict__.get("_vc_cache", {})
            or (_crush_digest(crush_map), ruleno) in _VC_SHARED)
    if fused == "always" or (fused == "auto"
                             and (warm or lanes >= threshold)):
        try:
            vc = _vector_crush_for(crush_map, ruleno)
            before = vc.totals()
            rows = np.asarray(vc.map_pgs(xs, numrep, list(weights)),
                              dtype=np.int64)
            if perf is not None:
                for name, total in vc.totals().items():
                    perf.inc(name, total - before[name])
            return rows, True
        except ValueError:
            if fused == "always":
                raise
    rows = np.full((lanes, numrep), CRUSH_ITEM_NONE, dtype=np.int64)
    for i, x in enumerate(xs):
        got = crush_do_rule(crush_map, ruleno, int(x), numrep,
                            weights)[:numrep]
        rows[i, :len(got)] = got
    return rows, False


def pool_pps(pool) -> np.ndarray:
    """pps seed per raw pg of a pool, vectorized (pg_pool_t::
    raw_pg_to_pps for ps in [0, pg_num))."""
    pgs = np.arange(pool.pg_num, dtype=np.int64)
    stable = np.where((pgs & pool.pgp_num_mask) < pool.pgp_num,
                      pgs & pool.pgp_num_mask,
                      pgs & (pool.pgp_num_mask >> 1))
    if pool.flags & 1:      # FLAG_HASHPSPOOL
        return crush_hash32_2_np(
            stable.astype(np.uint32),
            np.full(pool.pg_num, pool.pool_id,
                    dtype=np.int64).astype(np.uint32)).astype(np.int64)
    return stable + pool.pool_id


class PGMapping:
    """The full-cluster placement table for one OSDMap epoch.

    ``up`` and ``acting`` per (pool, raw pg), entry-identical to the
    per-PG ``pg_to_up_acting`` result.  Instances are immutable
    snapshots: a new epoch builds a new PGMapping (OSDMap memoizes one
    per mutation generation and hands the previous one to ``delta``)."""

    def __init__(self, epoch: int) -> None:
        self.epoch = epoch
        self.fused_pools = 0
        self.scalar_pools = 0
        # pool_id -> list[list[int]] indexed by raw pg
        self._up: dict[int, list[list[int]]] = {}
        self._acting: dict[int, list[list[int]]] = {}
        self._pg_num: dict[int, int] = {}
        self._pg_num_mask: dict[int, int] = {}

    # -- construction -------------------------------------------------------
    @classmethod
    def build(cls, osdmap, perf=None, fused: str = "auto",
              min_lanes: int | None = None) -> "PGMapping":
        t0 = time.perf_counter()
        pm = cls(osdmap.epoch)
        weights = osdmap.osd_weights()
        # live[o] <=> the post-CRUSH filter keeps osd o (exists + up)
        n = len(weights) + 1
        live = np.zeros(n, dtype=bool)
        for o, info in osdmap.osds.items():
            if info.up and o < n:
                live[o] = True
        for pool_id, pool in osdmap.pools.items():
            pps = pool_pps(pool)
            rows, used_fused = bulk_crush(
                osdmap.crush, pool.crush_rule, pps, pool.size, weights,
                fused=fused, min_lanes=min_lanes, perf=perf)
            if used_fused:
                pm.fused_pools += 1
            else:
                pm.scalar_pools += 1
            pm._ingest_pool(osdmap, pool_id, pool, rows, live)
        dt = time.perf_counter() - t0
        if perf is not None:
            perf.inc("bulk_recomputes")
            perf.inc("fused_pools", pm.fused_pools)
            perf.inc("scalar_pools", pm.scalar_pools)
            perf.tinc("recompute", dt)
            total = sum(pm._pg_num.values())
            if dt > 0:
                perf.set_gauge("recompute_pgs_per_s",
                               round(total / dt, 1))
        return pm

    def _ingest_pool(self, osdmap, pool_id: int, pool,
                     rows: np.ndarray, live: np.ndarray) -> None:
        """Raw CRUSH rows -> up/acting lists with the full OSDMap
        semantics applied in bulk (OSDMap.cc _apply_upmap,
        _raw_to_up_osds, pg_temp), vectorized where the data is dense
        and per-entry only for the sparse override dicts."""
        n_live = live.shape[0]
        # upmap rewrite first (it edits the RAW result): sparse dict,
        # touch only the pgs that carry items
        prefix = f"{pool_id}."
        upmapped = [k for k in osdmap.pg_upmap_items
                    if k.startswith(prefix)]
        for pgid in upmapped:
            try:
                pg = int(pgid.split(".", 1)[1], 16)
            except ValueError:
                continue
            if 0 <= pg < pool.pg_num:
                rows[pg] = osdmap._apply_upmap(
                    pgid, [int(o) for o in rows[pg]])
        # live filter, holes normalized to -1 (EC shard ids ride the
        # position, so indep pools keep holes; replicated compact)
        valid = ((rows != CRUSH_ITEM_NONE) & (rows >= 0)
                 & (rows < n_live))
        ok = np.zeros_like(valid)
        ok[valid] = live[rows[valid]]
        if pool.can_shift_osds():
            up = [[int(o) for o in row[okr]]
                  for row, okr in zip(rows, ok)]
        else:
            filt = np.where(ok, rows, -1)
            up = [[int(o) for o in row] for row in filt]
        acting = list(up)           # shared rows until pg_temp overrides
        for pgid, temp in osdmap.pg_temp.items():
            if not pgid.startswith(prefix):
                continue
            try:
                pg = int(pgid.split(".", 1)[1], 16)
            except ValueError:
                continue
            if not (0 <= pg < pool.pg_num) or not temp:
                continue
            act = [int(o) if (o != CRUSH_ITEM_NONE and o >= 0
                              and o < n_live and live[o]) else -1
                   for o in temp]
            if pool.can_shift_osds():
                act = [o for o in act if o >= 0]
            acting[pg] = act if act else up[pg]
        self._up[pool_id] = up
        self._acting[pool_id] = acting
        self._pg_num[pool_id] = pool.pg_num
        self._pg_num_mask[pool_id] = pool.pg_num_mask

    # -- queries ------------------------------------------------------------
    def raw_pg(self, pool_id: int, ps: int) -> int:
        b, mask = self._pg_num[pool_id], self._pg_num_mask[pool_id]
        return ps & mask if (ps & mask) < b else ps & (mask >> 1)

    def lookup(self, pool_id: int,
               ps: int) -> tuple[list[int], list[int]]:
        """(up, acting) for a pg: one table read.  Returns fresh lists
        (callers historically mutate/keep the per-call result)."""
        pg = self.raw_pg(pool_id, ps)
        return list(self._up[pool_id][pg]), \
            list(self._acting[pool_id][pg])

    def iter_all(self):
        """Yield (pool_id, pg, up, acting) over the whole table."""
        for pool_id, ups in self._up.items():
            acts = self._acting[pool_id]
            for pg in range(len(ups)):
                yield pool_id, pg, ups[pg], acts[pg]

    def pg_count(self) -> int:
        return sum(self._pg_num.values())

    # -- deltas -------------------------------------------------------------
    def delta(self, prev: "PGMapping",
              perf=None) -> list[tuple[int, int]]:
        """(pool_id, pg) for every entry whose up OR acting differs
        from ``prev``, including pgs of pools present in only one of
        the two tables (pool create/delete, pg_num resize).  Exactly
        the brute-force entry-for-entry diff, so a map consumer can
        retarget only what moved."""
        if prev is self:
            # placement-neutral epochs (up_thru/blocklist-only) carry
            # the table object across generations: nothing moved
            return []
        changed: list[tuple[int, int]] = []
        pools = set(self._up) | set(prev._up)
        for pool_id in sorted(pools):
            cur_u = self._up.get(pool_id)
            old_u = prev._up.get(pool_id)
            if cur_u is None or old_u is None:
                src = cur_u if cur_u is not None else old_u
                changed.extend((pool_id, pg) for pg in range(len(src)))
                continue
            cur_a = self._acting[pool_id]
            old_a = prev._acting[pool_id]
            span = max(len(cur_u), len(old_u))
            for pg in range(span):
                if (pg >= len(cur_u) or pg >= len(old_u)
                        or cur_u[pg] != old_u[pg]
                        or cur_a[pg] != old_a[pg]):
                    changed.append((pool_id, pg))
        if perf is not None:
            perf.inc("delta_pgs", len(changed))
        return changed
