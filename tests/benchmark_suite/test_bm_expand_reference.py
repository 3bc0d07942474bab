"""The expansion cell's plain reference (reference/crush_expand.py)
against the program's scalar ``crush_do_rule`` and its ``VectorCrush``,
lane for lane, on a small weighted tree with OSDs out, reweighted OSDs
and zero-weight buckets; its OSDMap filter, its diff and its map edits.
"""

from __future__ import annotations

import numpy as np
import pytest

import bm_toy  # noqa: F401  (puts the repo root on sys.path)
from benchmark.reference import crush_expand as ref
from benchmark.reference.crush import ITEM_NONE, UniformTree

W = 0x10000
FANOUTS = [2, 3, 3, 3]              # 18 hosts, 54 osds
NAMES = ["default", "row", "rack", "host"]
# (rule, slots, short): ``short`` leaves 10 hosts with weight, one
# fewer than the slots, so every lane keeps a hole and runs out of tries
CASES = {"firstn3": (0, 3, False), "indep_k4m2": (1, 6, False),
         "indep_11": (1, 11, False), "indep_11_holes": (1, 11, True)}


def weighted_tree(short: bool = False) -> ref.WeightedTree:
    """Uneven weights: a rack at weight 0 (a new one), a host at weight
    0 inside a live rack, one device at 2.5."""
    tree = ref.WeightedTree.uniform(FANOUTS, W, NAMES)
    tree.reweight_subtree("rack1-2", 0)
    tree.reweight_subtree("host0-1-0", 0)
    if short:
        tree.reweight_subtree("rack1-1", 0)
        tree.reweight_subtree("host1-0-0", 0)
    tree.reweight_subtree("host0-0-1", W // 2)
    bucket, col = tree._holder(3)
    bucket["item_weights"][col] = 5 * W // 2
    tree._carry_up(bucket)
    return tree


def osd_weights() -> np.ndarray:
    w = np.full(54, W, np.int64)
    w[[2, 11, 40]] = 0                   # out
    w[[5, 23]] = [0x8000, 0x2000]        # reweighted
    return w


def program_map(tree: ref.WeightedTree):
    from ceph_tpu.crush.builder import erasure_rule, replicated_rule
    from ceph_tpu.crush.types import Bucket, CrushMap

    cm = CrushMap()
    for b in tree.as_buckets():
        cm.add_bucket(Bucket(id=b["id"], type=b["type"], items=b["items"],
                             item_weights=b["item_weights"]), b["name"])
    cm.add_rule(replicated_rule(0, -1, choose_type=1, leaf=True))
    cm.add_rule(erasure_rule(1, -1, choose_type=1, leaf=True))
    return cm


def reference_rows(tree, ruleno, xs, numrep, w):
    rule = tree.chooseleaf_indep if ruleno else tree.chooseleaf_firstn
    return rule(-1, xs, numrep, 1, w)


@pytest.mark.parametrize("case", list(CASES))
def test_reference_equals_scalar_crush_do_rule(case):
    from ceph_tpu.crush.mapper import crush_do_rule

    ruleno, numrep, short = CASES[case]
    tree, w = weighted_tree(short), osd_weights()
    cm = program_map(tree)
    xs = ref.pool_pps(3, 64 if short else 512)
    got = reference_rows(tree, ruleno, xs, numrep, w)
    for x, row in zip(xs, got):
        want = crush_do_rule(cm, ruleno, int(x), numrep, list(w))
        want = (want + [ITEM_NONE] * numrep)[:numrep]
        assert list(row) == want, (case, x)
    # a hole stays at its position, between placed slots
    assert (got == ITEM_NONE).any(axis=1).all() == short
    if short:
        assert ((got[:, :-1] == ITEM_NONE) & (got[:, 1:] != ITEM_NONE)).any()


@pytest.mark.parametrize("case", list(CASES))
def test_reference_equals_vectorcrush_lane_for_lane(case):
    from ceph_tpu.crush.vectorized import VectorCrush

    ruleno, numrep, short = CASES[case]
    tree, w = weighted_tree(short), osd_weights()
    xs = ref.pool_pps(5, 128 if short else 2048)
    got = VectorCrush(program_map(tree), ruleno).map_pgs(xs, numrep, list(w))
    want = reference_rows(tree, ruleno, xs, numrep, w)
    assert np.array_equal(np.asarray(got, np.int64), want)
    placed = want[want != ITEM_NONE]
    assert not np.isin(placed, [2, 11, 40]).any()        # none out
    assert not np.isin(placed, tree.devices_under(
        tree.by_name("rack1-2")["id"])).any()            # none at weight 0


def test_uniform_weights_give_the_accepted_references_mapping():
    """On the bulk cell's kind of tree the weighted reference is the
    accepted ``UniformTree``, lane for lane."""
    fanouts = [3, 2, 4]
    old = UniformTree(fanouts, W)
    new = ref.WeightedTree.uniform(fanouts, W, ["default", "rack", "host"])
    assert [b["id"] for b in new.as_buckets()] == [
        b["id"] for b in old.buckets()]
    w = np.full(24, W, np.int64)
    w[[1, 9]] = [0, 0x4000]
    xs = np.arange(3000) * 7
    assert np.array_equal(new.chooseleaf_firstn(-1, xs, 3, 1, w),
                          old.map_pgs(xs, 3, w))


def test_pps_is_the_programs():
    from ceph_tpu.mon.osdmap import PoolSpec
    from ceph_tpu.mon.pg_mapping import pool_pps

    for pool_id, pg_num in ((1, 64), (2, 1024), (9, 8192)):
        pool = PoolSpec(pool_id=pool_id, name="p", pg_num=pg_num,
                        pgp_num=pg_num)
        assert np.array_equal(ref.pool_pps(pool_id, pg_num), pool_pps(pool))


def test_up_osds_shifts_a_replicated_row_and_keeps_an_erasure_hole():
    raw = np.array([[4, 1, 7], [ITEM_NONE, 2, 3], [5, ITEM_NONE, 6],
                    [1, 1, 1]])
    up = np.ones(8, bool)
    up[[1, 5]] = False
    assert ref.up_osds(raw.copy(), up, shift=True).tolist() == [
        [4, 7, -1], [2, 3, -1], [6, -1, -1], [-1, -1, -1]]
    assert ref.up_osds(raw.copy(), up, shift=False).tolist() == [
        [4, -1, 7], [-1, 2, 3], [-1, -1, 6], [-1, -1, -1]]


def test_table_is_the_osdmaps_filtering_for_both_pool_kinds():
    """``table`` against the program's per-PG scalar pipeline on a map
    with OSDs down: a replicated row compacts, an erasure row keeps -1."""
    from ceph_tpu.mon.osdmap import (POOL_TYPE_ERASURE, OSDMap, OsdInfo,
                                     PoolSpec)

    tree, w = weighted_tree(), osd_weights()
    up = np.ones(54, bool)
    up[[0, 7, 19, 33, 50]] = False
    pools = [{"pool_id": 1, "type": "replicated", "size": 3, "pg_num": 128},
             {"pool_id": 2, "type": "erasure", "size": 6, "pg_num": 64}]
    got = ref.table(tree, -1, pools, 1, w, up)
    m = OSDMap()
    m.epoch, m.crush, m.max_osd = 1, program_map(tree), 54
    for o in range(54):
        m.osds[o] = OsdInfo(up=bool(up[o]), in_cluster=w[o] > 0,
                            weight=int(w[o]) or W)
    m.pools[1] = PoolSpec(pool_id=1, name="r", size=3, pg_num=128,
                          pgp_num=128)
    m.pools[2] = PoolSpec(pool_id=2, name="e", type=POOL_TYPE_ERASURE,
                          size=6, min_size=5, pg_num=64, pgp_num=64,
                          crush_rule=1)
    shifted = holes = 0
    for pid, rows in got.items():
        for pg, row in enumerate(rows.tolist()):
            want, _ = m._pg_to_up_acting_scalar(pid, pg)
            if pid == 1:
                assert [o for o in row if o >= 0] == want
                assert row == want + [-1] * (3 - len(want))
                shifted += len(want) < 3
            else:
                assert row == want
                holes += -1 in row
    assert shifted and holes


def test_table_diff_names_exactly_the_rows_that_differ():
    old = {1: np.array([[1, 2], [3, 4], [5, 6]]), 2: np.array([[7], [8]])}
    new = {1: np.array([[1, 2], [4, 3], [5, -1]]), 2: np.array([[7], [8]])}
    assert ref.table_diff(old, new) == {(1, 1), (1, 2)}
    assert ref.table_diff(old, old) == set()


def test_the_trees_edits_are_crushwrappers():
    """add_bucket takes the next id below the lowest, an inserted item
    goes to the end of its parent, every ancestor is the sum of its
    items, and the program's commands build the same map."""
    from ceph_tpu.crush.builder import crush_command

    tree = ref.WeightedTree.uniform([2, 2, 2], W, ["default", "rack", "host"])
    cm = program_map(tree)
    cm.type_names = {0: "osd", 1: "host", 2: "rack", 3: "root"}
    rack = tree.add_bucket("rack9", 2)
    host = tree.add_bucket("host9", 1)
    assert (rack, host) == (-8, -9)
    tree.insert(host, 0, "rack9")
    tree.insert(8, 0, "host9")
    tree.insert(9, 0, "host9")
    tree.insert(rack, tree.weight_of(rack), "default")
    tree.reweight_subtree("rack9", W // 4)
    assert tree.by_name("default")["items"] == [-2, -5, -8]
    assert tree.by_name("default")["item_weights"] == [4 * W, 4 * W, W // 2]
    assert tree.weight_of(host) == W // 2
    for cmd, args in [
            ("osd crush add-bucket", {"name": "rack9", "type": "rack"}),
            ("osd crush add-bucket", {"name": "host9", "type": "host"}),
            ("osd crush move", {"name": "host9", "loc": {"rack": "rack9"}}),
            ("osd crush add", {"name": "osd.8", "weight": 0.0,
                               "loc": {"host": "host9"}}),
            ("osd crush add", {"name": "osd.9", "weight": 0.0,
                               "loc": {"host": "host9"}}),
            ("osd crush move", {"name": "rack9",
                                "loc": {"root": "default"}}),
            ("osd crush reweight-subtree", {"name": "rack9",
                                            "weight": 0.25})]:
        cm = crush_command(cm, cmd, args)
    for b in tree.as_buckets():
        mine = cm.buckets[b["id"]]
        assert (mine.type, mine.items, mine.item_weights) == (
            b["type"], b["items"], b["item_weights"]), b["name"]
        assert cm.bucket_names[b["id"]] == b["name"]
    assert len(cm.buckets) == len(tree.buckets)
