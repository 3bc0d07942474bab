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

from ..common.tracing import section
from ..crush import crush_do_rule
from ..crush.hashes import crush_hash32_2_np
from ..crush.types import CRUSH_ITEM_NONE

# below this many lanes (a pool's pg_num) the fused JAX path is not
# worth its trace/compile cost -- the scalar sweep wins on the small
# maps unit tests and the chaos smoke run.  Large maps (the bench, real
# clusters) clear it easily.
FUSED_MIN_LANES = int(os.environ.get("CEPH_TPU_PLACEMENT_FUSED_MIN",
                                     "2048"))

# (structure key, rule) pairs this process has launched fused: the
# device program is jit's, keyed by a map's structure and not by its
# weights (crush/vectorized.py), so a map of a launched structure --
# the next weight step of an expansion, another daemon's copy of the
# same map -- finds its executable there
_FUSED_WARM: set[tuple[int, int]] = set()


def _structure_key(crush_map) -> int:
    """What of a CrushMap decides the fused program and nothing of its
    weights: buckets with their items, rules, tunables, the weight-set
    positions.  A hash, cached on the object (maps are replaced
    wholesale on change, never mutated in place)."""
    key = crush_map.__dict__.get("_structure_key")
    if key is None:
        ca = getattr(crush_map, "choose_args", None) or {}
        key = hash((
            tuple((b.id, b.type, b.alg, tuple(b.items))
                  for b in crush_map.buckets.values()),
            tuple((r.rule_id, tuple((s.op, s.arg1, s.arg2)
                                    for s in r.steps))
                  for r in crush_map.rules.values()),
            tuple(vars(crush_map.tunables).values()),
            tuple((bid, len(arg.get("weight_set") or ()))
                  for bid, arg in ca.items())))
        crush_map.__dict__["_structure_key"] = key
    return key


def _vector_crush_for(crush_map, ruleno: int):
    """This map's VectorCrush for a rule, kept on the CrushMap object:
    its level tables, uploaded once.  A new map (every ``osd crush
    reweight`` makes one) builds its own; the executable it launches is
    shared process-wide by structure.  Only ``osd reweight`` / ``out``
    leave the map object alone: they travel in the launch's
    ``osd_weights`` operand."""
    cache = crush_map.__dict__.setdefault("_vc_cache", {})
    ca = getattr(crush_map, "choose_args", None)
    key = (ruleno, id(ca) if ca else None)
    if key not in cache:
        from ..crush.vectorized import VectorCrush
        cache[key] = VectorCrush(crush_map, ruleno)
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
    ``perf`` takes the fused call's time (``launch``: tables and seeds
    up, ids on the host), what it added to the mapper's running totals
    (``VectorCrush.totals``), ``programs_built`` (launches that traced
    their program first) and, where the engine declined the map,
    ``fused_declined`` and ``fused_declined_<reason>``.
    """
    from ..crush.vectorized import FusedUnsupported

    xs = np.asarray(xs, dtype=np.int64)
    lanes = int(xs.shape[0])
    threshold = FUSED_MIN_LANES if min_lanes is None else min_lanes
    # a launched structure makes the fused launch all but free -- the
    # threshold only guards the one-time trace/compile cost, so it does
    # not apply once that cost is sunk (the epoch-recompute path hits
    # one structure dozens of times during an expansion's weight steps
    # and the peering churn after each)
    warm = (_structure_key(crush_map), ruleno)
    if fused == "always" or (fused == "auto"
                             and (lanes >= threshold
                                  or warm in _FUSED_WARM)):
        try:
            t0 = time.perf_counter()
            with section("placement.launch"):
                vc = _vector_crush_for(crush_map, ruleno)
                before, built = vc.totals(), vc.programs_built
                rows = np.asarray(vc.map_pgs(xs, numrep, list(weights)),
                                  dtype=np.int64)
            _FUSED_WARM.add(warm)
            if perf is not None:
                perf.tinc("launch", time.perf_counter() - t0)
                for name, total in vc.totals().items():
                    perf.inc(name, total - before[name])
                perf.inc("programs_built", vc.programs_built - built)
            return rows, True
        except FusedUnsupported as e:
            if fused == "always":
                raise
            if perf is not None:
                perf.inc("fused_declined")
                perf.inc(f"fused_declined_{e.reason}")
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
    per-PG ``pg_to_up_acting`` result.  A pool's rows are ONE
    ``(pg_num, size)`` int64 array, as the live filter left it: holes
    -1, a replicated row's survivors first with -1 behind them (and,
    only for a pool in which some row lost an OSD, the per-row count
    of survivors beside it).  ``acting`` is that same array except for
    the PGs a ``pg_temp`` overrides, kept as lists in a small dict per
    pool (an override may be of any length).  Python lists are made for
    the rows somebody looks up, never for a table.  Instances are
    immutable snapshots: a new epoch builds a new PGMapping (OSDMap
    memoizes one per mutation generation and hands the previous one to
    ``delta``)."""

    def __init__(self, epoch: int) -> None:
        self.epoch = epoch
        self.fused_pools = 0
        self.scalar_pools = 0
        self.shifted_pgs = 0        # rows that lost an OSD and closed up
        # pool_id -> (pg_num, size) rows indexed by raw pg
        self._up: dict[int, np.ndarray] = {}
        # pool_id -> survivors per row, for the shifting pools with a
        # short row; pool_id -> {pg: acting list} where pg_temp rules
        self._kept: dict[int, np.ndarray] = {}
        self._temp: dict[int, dict[int, list[int]]] = {}
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
        ingest = 0.0
        for pool_id, pool in osdmap.pools.items():
            with section("placement.pps"):
                pps = pool_pps(pool)
            rows, used_fused = bulk_crush(
                osdmap.crush, pool.crush_rule, pps, pool.size, weights,
                fused=fused, min_lanes=min_lanes, perf=perf)
            if used_fused:
                pm.fused_pools += 1
            else:
                pm.scalar_pools += 1
            t1 = time.perf_counter()
            with section("placement.ingest"):
                pm._ingest_pool(osdmap, pool_id, pool, rows, live)
            ingest += time.perf_counter() - t1
        dt = time.perf_counter() - t0
        if perf is not None:
            perf.tinc("ingest", ingest)
            perf.inc("bulk_recomputes")
            perf.inc("fused_pools", pm.fused_pools)
            perf.inc("scalar_pools", pm.scalar_pools)
            perf.inc("ingest_shifted_pgs", pm.shifted_pgs)
            perf.inc("acting_overrides",
                     sum(len(t) for t in pm._temp.values()))
            perf.tinc("recompute", dt)
            total = sum(pm._pg_num.values())
            if dt > 0:
                perf.set_gauge("recompute_pgs_per_s",
                               round(total / dt, 1))
        return pm

    def _ingest_pool(self, osdmap, pool_id: int, pool,
                     rows: np.ndarray, live: np.ndarray) -> None:
        """Raw CRUSH rows -> the pool's table with the full OSDMap
        semantics applied in bulk (OSDMap.cc _apply_upmap,
        _raw_to_up_osds, pg_temp).  ``rows`` is edited in place and
        kept as the table: it never leaves numpy (24,576 lists an
        epoch cost the chip's host 9.5 ms to make and 9.1 to compare,
        ledger, PR 42); only the sparse override dicts are walked per
        entry."""
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
        ok = ((rows >= 0) & (rows < n_live)     # CRUSH_ITEM_NONE is past
              & live.take(rows, mode="clip"))   # any osd id
        shifts = pool.can_shift_osds()
        if not ok.all():
            rows[~ok] = -1
            if shifts:
                # survivors first and in order (a stable sort on
                # "dropped"), for the rows that lost one alone
                short = np.flatnonzero(~ok.all(axis=1))
                rows[short] = np.take_along_axis(
                    rows[short], np.argsort(~ok[short], axis=1,
                                            kind="stable"), axis=1)
                self._kept[pool_id] = ok.sum(axis=1)
                self.shifted_pgs += len(short)
        temps = self._temp[pool_id] = {}
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
            if shifts:
                act = [o for o in act if o >= 0]
            if act:                 # else: acting falls back to up
                temps[pg] = act
        self._up[pool_id] = rows
        self._pg_num[pool_id] = pool.pg_num
        self._pg_num_mask[pool_id] = pool.pg_num_mask

    # -- queries ------------------------------------------------------------
    def raw_pg(self, pool_id: int, ps: int) -> int:
        b, mask = self._pg_num[pool_id], self._pg_num_mask[pool_id]
        return ps & mask if (ps & mask) < b else ps & (mask >> 1)

    def _row(self, pool_id: int, pg: int) -> tuple[list[int], list[int]]:
        kept = self._kept.get(pool_id)
        row = self._up[pool_id][pg]
        up = (row if kept is None else row[:kept[pg]]).tolist()
        return up, list(self._temp[pool_id].get(pg, up))

    def lookup(self, pool_id: int,
               ps: int) -> tuple[list[int], list[int]]:
        """(up, acting) for a pg: one array read.  Returns fresh lists
        of Python ints (callers historically mutate/keep the per-call
        result, and an ``np.int64`` breaks the wire encoders)."""
        return self._row(pool_id, self.raw_pg(pool_id, ps))

    def iter_all(self):
        """Yield (pool_id, pg, up, acting) over the whole table, the
        lists made as it goes."""
        for pool_id, rows in self._up.items():
            for pg in range(rows.shape[0]):
                yield pool_id, pg, *self._row(pool_id, pg)

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
        t0 = time.perf_counter()
        with section("placement.delta"):
            changed = self._diff(prev)
        if perf is not None:
            perf.tinc("delta", time.perf_counter() - t0)
            perf.inc("delta_pgs", len(changed))
        return changed

    def _lens(self, pool_id: int, n: int):
        """A pool's row lengths over its first ``n`` pgs: the width, or
        the survivors per row where a row is short."""
        kept = self._kept.get(pool_id)
        return self._up[pool_id].shape[1] if kept is None else kept[:n]

    def _diff(self, prev: "PGMapping") -> list[tuple[int, int]]:
        changed: list[tuple[int, int]] = []
        for pool_id in sorted(set(self._up) | set(prev._up)):
            cur = self._up.get(pool_id)
            old = prev._up.get(pool_id)
            if cur is None or old is None:
                src = cur if cur is not None else old
                changed.extend((pool_id, pg) for pg in range(len(src)))
                continue
            # rows are equal where they are equally long and agree over
            # the common width (what lies behind a row's length is -1)
            n, w = min(len(cur), len(old)), min(cur.shape[1], old.shape[1])
            moved = (cur[:n, :w] != old[:n, :w]).any(axis=1)
            moved |= self._lens(pool_id, n) != prev._lens(pool_id, n)
            for pg in self._temp[pool_id].keys() | prev._temp[pool_id].keys():
                if pg < n and not moved[pg]:
                    moved[pg] = (self._row(pool_id, pg)[1]
                                 != prev._row(pool_id, pg)[1])
            pgs = np.flatnonzero(moved).tolist()
            pgs.extend(range(n, max(len(cur), len(old))))
            changed.extend((pool_id, pg) for pg in pgs)
        return changed
