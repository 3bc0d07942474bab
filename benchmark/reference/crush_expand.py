"""Plain reference for an expansion stepped through OSDMap epochs: a
weighted straw2 tree that grows, both pool kinds' rules, the OSDMap's
filter and the diff of two tables.

Follows src/crush/mapper.c for a map whose every bucket is straw2 with
a weight per item, under the jewel tunables (choose_total_tries 50,
chooseleaf_descend_once, vary_r 1, stable 1, no local retries), and
src/osd/OSDMap.cc for what becomes of a raw mapping:

  crush_choose_firstn   ``take root; chooseleaf firstn 0 type <t>``:
                        replica by replica, a collision or a rejected
                        leaf retries the descent with r + 1, 51 tries;
                        a replica that runs out leaves no hole
  crush_choose_indep    ``set_chooseleaf_tries 5; set_choose_tries 100;
                        take root; chooseleaf indep 0 type <t>``: pass
                        after pass over the slots still undefined,
                        r = slot + numrep * pass; the leaf's r adds the
                        slot again and numrep per leaf try; a slot that
                        never fills is a hole at its position
  is_out                the 16.16 reweight test on the chosen device
  _pg_to_raw_osds       pps = hash32_2(ps, pool) (FLAG_HASHPSPOOL,
                        pgp_num = pg_num, a power of two)
  _raw_to_up_osds       what is not up goes: a replicated pool closes
                        the gap, an erasure pool keeps a hole (-1)
  CrushWrapper          add_bucket (the next free id below the lowest),
                        insert_item / move_bucket (appended to the
                        parent's items), adjust_subtree_weight (device
                        by device, every ancestor the sum of its items)

Departures: numpy over all lanes at once (one lane is the scalar
algorithm); a hole is -1 in a table, as the OSDMap's consumers read it,
and a replicated row is padded with -1 after its last OSD; no pg_temp,
no upmap (the deployment sets none): acting is up.  Shares ``crush_ln``
and the hashes with ``crush.py``; nothing here imports the program.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference.crush import (ITEM_NONE, S64_MIN, _LN_MINUS,
                                       hash32_2, hash32_3)

ITEM_UNDEF = 0x7FFFFFFE
HOLE = -1


class WeightedTree:
    """Straw2 buckets with a weight per item.  ``buckets``: plain dicts
    (id, type, name, items, item_weights), kept as given; the arrays a
    choice reads (row ``-1 - id``, padded: a pad weighs 0 and lies
    after the real items, so the first of equal draws is never one)
    are rebuilt when the map changed."""

    def __init__(self, buckets: list[dict]) -> None:
        self.buckets = {b["id"]: {**b, "items": list(b["items"]),
                                  "item_weights": list(b["item_weights"])}
                        for b in buckets}
        self._arrays = None

    @classmethod
    def uniform(cls, fanouts: list[int], osd_weight: int,
                names: list[str]) -> "WeightedTree":
        """``fanouts[l]`` children per bucket at level l, OSDs under
        the last, numbered left to right; bucket ids count down from -1
        in depth-first preorder; a bucket at level l has type ``depth -
        l`` and the name ``names[l]`` with its child indices from the
        root appended (``row2``, ``rack2-0``, ``host2-0-3``)."""
        depth = len(fanouts)
        out: list[dict] = []

        def build(level: int, osd_base: int, path: tuple) -> tuple[int, int]:
            me = {"id": -1 - len(out), "type": depth - level,
                  "name": names[level] + "-".join(map(str, path)),
                  "items": [], "item_weights": []}
            out.append(me)
            span = int(np.prod(fanouts[level + 1:]))
            for c in range(fanouts[level]):
                if level == depth - 1:
                    item, weight = osd_base + c, osd_weight
                else:
                    item, weight = build(level + 1, osd_base + c * span,
                                         path + (c,))
                me["items"].append(item)
                me["item_weights"].append(weight)
            return me["id"], sum(me["item_weights"])

        build(0, 0, ())
        return cls(out)

    # -- the operator's edits -------------------------------------------------
    def by_name(self, name: str) -> dict:
        return next(b for b in self.buckets.values() if b["name"] == name)

    def add_bucket(self, name: str, type_id: int) -> int:
        bid = min(self.buckets) - 1
        self.buckets[bid] = {"id": bid, "type": type_id, "name": name,
                             "items": [], "item_weights": []}
        self._arrays = None
        return bid

    def _holder(self, item: int):
        for b in self.buckets.values():
            if item in b["items"]:
                return b, b["items"].index(item)
        return None

    def _carry_up(self, bucket: dict) -> None:
        """Every ancestor weighs the sum of its items again."""
        held = self._holder(bucket["id"])
        while held is not None:
            parent, col = held
            parent["item_weights"][col] = sum(bucket["item_weights"])
            bucket, held = parent, self._holder(parent["id"])
        self._arrays = None

    def insert(self, item: int, weight: int, parent_name: str) -> None:
        """``osd crush add`` of a device, ``osd crush move`` of a bucket
        that has no parent yet: appended to the parent's items."""
        parent = self.by_name(parent_name)
        parent["items"].append(item)
        parent["item_weights"].append(weight)
        self._carry_up(parent)

    def weight_of(self, bid: int) -> int:
        return sum(self.buckets[bid]["item_weights"])

    def devices_under(self, item: int) -> list[int]:
        if item >= 0:
            return [item]
        return [d for child in self.buckets[item]["items"]
                for d in self.devices_under(child)]

    def reweight_subtree(self, name: str, weight: int) -> None:
        for dev in self.devices_under(self.by_name(name)["id"]):
            bucket, col = self._holder(dev)
            bucket["item_weights"][col] = weight
            self._carry_up(bucket)

    def as_buckets(self) -> list[dict]:
        """The map as plain data, root first: what the system under
        test is given to build its own map from."""
        return [dict(b) for _, b in sorted(self.buckets.items(),
                                           reverse=True)]

    # -- one straw2 choice ----------------------------------------------------
    def _tables(self):
        if self._arrays is None:
            n = -min(self.buckets)
            width = max(len(b["items"]) for b in self.buckets.values())
            items = np.zeros((n, width), np.int64)
            weights = np.zeros((n, width), np.int64)
            types = np.zeros(n, np.int64)
            for bid, b in self.buckets.items():
                size = len(b["items"])
                types[-1 - bid] = b["type"]
                if size:
                    items[-1 - bid, :size] = b["items"]
                    items[-1 - bid, size:] = b["items"][0]
                    weights[-1 - bid, :size] = b["item_weights"]
            self._arrays = items, weights, types
        return self._arrays

    def choose(self, rows, x, r) -> np.ndarray:
        """bucket_straw2_choose: the item of each lane's bucket with the
        largest draw, the first of equals (an all-zero bucket: its
        first item)."""
        all_items, all_weights, _ = self._tables()
        items, w = all_items[rows], all_weights[rows]
        u = hash32_3(x[:, None], items, r[:, None]) & np.uint32(0xFFFF)
        ln = _LN_MINUS[u.astype(np.int64)]                   # <= 0
        # C division truncates toward zero; ln <= 0 < w
        draws = np.where(w > 0, -((-ln) // np.maximum(w, 1)), S64_MIN)
        return items[np.arange(items.shape[0]), np.argmax(draws, axis=1)]

    def descend(self, rows, x, r, want_type: int) -> np.ndarray:
        """From bucket ``rows`` down, choice by choice at one r, to an
        item of ``want_type`` (0: a device)."""
        types = self._tables()[2]
        out = np.empty(rows.shape[0], np.int64)
        idx = np.arange(rows.shape[0])
        while idx.size:
            item = self.choose(rows, x[idx], r[idx])
            kind = np.where(item < 0, types[np.where(item < 0, -1 - item,
                                                     0)], 0)
            hit = kind == want_type
            if (~hit & (item >= 0)).any():
                raise ValueError("a device above the wanted bucket type")
            out[idx[hit]] = item[hit]
            idx, rows = idx[~hit], -1 - item[~hit]
        return out

    # -- the two rules --------------------------------------------------------
    def chooseleaf_firstn(self, root: int, xs, numrep: int, want_type: int,
                          osd_weights, tries: int = 51,
                          leaf_tries: int = 1) -> np.ndarray:
        """(len(xs), numrep) devices, placed ones first, ``ITEM_NONE``
        after them."""
        xs = np.asarray(xs, np.int64)
        w = np.asarray(osd_weights, np.int64)
        n = xs.shape[0]
        out = np.full((n, numrep), ITEM_NONE, np.int64)      # buckets
        out2 = np.full((n, numrep), ITEM_NONE, np.int64)     # devices
        outpos = np.zeros(n, np.int64)
        for rep in range(numrep):
            ftotal = np.zeros(n, np.int64)
            trying = np.ones(n, bool)
            while trying.any():
                idx = np.flatnonzero(trying)
                x, r = xs[idx], rep + ftotal[idx]
                item = self.descend(np.full(idx.shape[0], -1 - root), x, r,
                                    want_type)
                ok = ~(out[idx] == item[:, None]).any(axis=1)
                # the leaf: numrep 1, stable, parent_r = r (vary_r 1)
                leaf = np.full(idx.shape[0], ITEM_NONE, np.int64)
                for ft in range(leaf_tries):
                    todo = np.flatnonzero(ok & (leaf == ITEM_NONE))
                    if not todo.size:
                        break
                    cand = self.descend(-1 - item[todo], x[todo],
                                        r[todo] + ft, 0)
                    good = ~(out2[idx[todo]] == cand[:, None]).any(axis=1) \
                        & ~is_out(w, cand, x[todo])
                    leaf[todo[good]] = cand[good]
                ok &= leaf != ITEM_NONE
                won = idx[ok]
                out[won, outpos[won]] = item[ok]
                out2[won, outpos[won]] = leaf[ok]
                outpos[won] += 1
                trying[won] = False
                lost = idx[~ok]
                ftotal[lost] += 1
                trying[lost[ftotal[lost] >= tries]] = False
        return out2

    def chooseleaf_indep(self, root: int, xs, numrep: int, want_type: int,
                         osd_weights, tries: int = 100,
                         leaf_tries: int = 5) -> np.ndarray:
        """(len(xs), numrep) devices by position, ``ITEM_NONE`` where a
        slot never filled."""
        xs = np.asarray(xs, np.int64)
        w = np.asarray(osd_weights, np.int64)
        n = xs.shape[0]
        out = np.full((n, numrep), ITEM_UNDEF, np.int64)     # buckets
        out2 = np.full((n, numrep), ITEM_UNDEF, np.int64)    # devices
        for ftotal in range(tries):
            if not (out == ITEM_UNDEF).any():
                break
            for rep in range(numrep):
                idx = np.flatnonzero(out[:, rep] == ITEM_UNDEF)
                if not idx.size:
                    continue
                x = xs[idx]
                r = np.full(idx.shape[0], rep + numrep * ftotal, np.int64)
                item = self.descend(np.full(idx.shape[0], -1 - root), x, r,
                                    want_type)
                ok = ~(out[idx] == item[:, None]).any(axis=1)
                # the leaf: one slot (its outpos is rep), its own tries,
                # parent_r = r
                leaf = np.full(idx.shape[0], ITEM_NONE, np.int64)
                for ft in range(leaf_tries):
                    todo = np.flatnonzero(ok & (leaf == ITEM_NONE))
                    if not todo.size:
                        break
                    cand = self.descend(-1 - item[todo], x[todo],
                                        rep + r[todo] + numrep * ft, 0)
                    good = ~is_out(w, cand, x[todo])
                    leaf[todo[good]] = cand[good]
                ok &= leaf != ITEM_NONE
                out[idx[ok], rep] = item[ok]
                out2[idx[ok], rep] = leaf[ok]
        return np.where(out == ITEM_UNDEF, ITEM_NONE, out2)


def is_out(osd_weights, item, x) -> np.ndarray:
    w = osd_weights[item]
    h = (hash32_2(x, item) & np.uint32(0xFFFF)).astype(np.int64)
    return (w == 0) | ((w < 0x10000) & (h >= w))


def pool_pps(pool_id: int, pg_num: int) -> np.ndarray:
    return hash32_2(np.arange(pg_num), pool_id).astype(np.int64)


def up_osds(raw: np.ndarray, up: np.ndarray, shift: bool) -> np.ndarray:
    """OSDMap::_raw_to_up_osds over every row: what is not up goes; a
    pool that can shift (replicated) closes the gap and is padded with
    ``HOLE``, one that cannot (erasure: the position is the shard)
    keeps ``HOLE`` there."""
    keep = raw != ITEM_NONE
    keep[keep] = up[raw[keep]]
    held = np.where(keep, raw, HOLE)
    if not shift:
        return held
    order = np.argsort(~keep, axis=1, kind="stable")
    return np.take_along_axis(held, order, axis=1)


def table(tree: WeightedTree, root: int, pools: list[dict], host_type: int,
          osd_weights, up) -> dict[int, np.ndarray]:
    """pool id -> (pg_num, size) up sets (acting is up: no pg_temp)."""
    out = {}
    for pool in pools:
        erasure = pool["type"] == "erasure"
        rule = tree.chooseleaf_indep if erasure else tree.chooseleaf_firstn
        raw = rule(root, pool_pps(int(pool["pool_id"]), int(pool["pg_num"])),
                   int(pool["size"]), host_type, osd_weights)
        out[int(pool["pool_id"])] = up_osds(raw, np.asarray(up, bool),
                                            shift=not erasure)
    return out


def table_diff(old: dict[int, np.ndarray],
               new: dict[int, np.ndarray]) -> set[tuple[int, int]]:
    """(pool, pg) of every entry that differs between two tables of
    the same pools."""
    return {(pid, int(pg)) for pid in new
            for pg in np.flatnonzero((new[pid] != old[pid]).any(axis=1))}
