"""Placement-cache parity suite (mon/pg_mapping.py).

The epoch-memoized full-cluster table must be ENTRY-IDENTICAL to the
per-PG scalar pipeline it replaced (`OSDMap._pg_to_up_acting_scalar`)
across randomized maps -- depths, holes, down/out OSDs, reweights,
upmaps, pg_temp, EC + replicated pools -- plus delta-correctness
(changed-PG set == brute-force diff) and invalidation (a stale-epoch
read is impossible after apply_incremental)."""

import random
from dataclasses import asdict

import numpy as np
import pytest

from ceph_tpu.crush.builder import build_hierarchy
from ceph_tpu.mon.osdmap import (
    OSDMap, OsdInfo, PoolSpec, Incremental, POOL_TYPE_ERASURE,
    crush_to_dict,
)
from ceph_tpu.mon.pg_mapping import PGMapping, pool_pps, bulk_crush


def make_map(seed: int, fanouts=None, pg_num: int = 16,
             down_frac: float = 0.15, out_frac: float = 0.1) -> OSDMap:
    """Randomized OSDMap: hierarchy depth, down/out/reweighted OSDs,
    upmap rewrites (incl. dangling targets), pg_temp overrides (incl.
    dead members and empty lists), one replicated + one EC pool."""
    rnd = random.Random(seed)
    fanouts = fanouts or rnd.choice([[6], [4, 4], [3, 3, 4], [2, 3, 2, 3]])
    n = 1
    for f in fanouts:
        n *= f
    m = OSDMap()
    m.epoch = 1
    m.crush = build_hierarchy(fanouts)
    m.max_osd = n
    for o in range(n):
        m.osds[o] = OsdInfo(
            up=rnd.random() >= down_frac,
            in_cluster=rnd.random() >= out_frac,
            weight=rnd.choice([0x10000, 0x10000, 0x8000, 0x4000]))
    m.pools[1] = PoolSpec(pool_id=1, name="rep", size=3, pg_num=pg_num,
                          pgp_num=pg_num)
    m.pools[2] = PoolSpec(pool_id=2, name="ec", type=POOL_TYPE_ERASURE,
                          size=4, min_size=3, pg_num=pg_num,
                          pgp_num=pg_num, crush_rule=1)
    m.pool_names = {"rep": 1, "ec": 2}
    every = list(range(n))
    for pid in (1, 2):
        for _ in range(rnd.randrange(4)):
            pg = rnd.randrange(pg_num)
            m.pg_upmap_items[f"{pid}.{pg:x}"] = [
                (rnd.choice(every), rnd.choice(every + [n + 3]))]
        for _ in range(rnd.randrange(3)):
            pg = rnd.randrange(pg_num)
            m.pg_temp[f"{pid}.{pg:x}"] = rnd.choice([
                [], rnd.sample(every, 3),
                [rnd.choice(every), -1, rnd.choice(every)]])
    return m


def assert_table_matches_scalar(m: OSDMap, pm: PGMapping) -> None:
    for pid, pool in m.pools.items():
        # past pg_num too: lookups take RAW ps and must stable_mod
        for ps in range(pool.pg_num * 2 + 3):
            want = m._pg_to_up_acting_scalar(pid, ps)
            got = pm.lookup(pid, ps)
            assert got == want, (pid, ps, got, want)


@pytest.mark.parametrize("seed", range(8))
def test_cached_table_entry_identical_to_scalar(seed):
    m = make_map(seed)
    assert_table_matches_scalar(m, m.placement_cache())


def test_fused_and_scalar_builds_agree():
    """The SAME table must come out of the fused VectorCrush launch
    and the batched scalar sweep -- divergence here is a mapper bug
    and must fail fast (tier-1)."""
    m = make_map(3, fanouts=[4, 8], pg_num=64, down_frac=0.1)
    fused = PGMapping.build(m, fused="always")
    scalar = PGMapping.build(m, fused="never")
    assert fused.fused_pools == len(m.pools)
    assert scalar.scalar_pools == len(m.pools)
    assert list(fused.iter_all()) == list(scalar.iter_all())
    assert_table_matches_scalar(m, fused)


def test_pool_pps_matches_scalar_hash():
    for seed in range(4):
        rnd = random.Random(seed)
        pool = PoolSpec(pool_id=rnd.randrange(1, 9), name="x",
                        pg_num=rnd.choice([8, 12, 32]),
                        pgp_num=rnd.choice([8, 12, 32]))
        got = pool_pps(pool)
        want = [pool.raw_pg_to_pps(ps) for ps in range(pool.pg_num)]
        assert list(got) == want


def test_bulk_crush_scalar_and_fused_rows_agree():
    m = make_map(5, fanouts=[3, 4], pg_num=32)
    xs = np.arange(0, 500, 7)
    w = m.osd_weights()
    for rule in (0, 1):
        srows, sf = bulk_crush(m.crush, rule, xs, 3, w, fused="never")
        frows, ff = bulk_crush(m.crush, rule, xs, 3, w, fused="always")
        assert not sf and ff
        assert np.array_equal(srows, frows), rule


@pytest.mark.parametrize("pool,rule,count", [
    (1, 0, "retry_lanes"), (2, 1, "indep_retry_pairs")],
    ids=["replicated", "erasure"])
def test_build_adds_the_mappers_retry_counts_to_its_perf_set(
        pool, rule, count, monkeypatch):
    """A warm build over one pool hands the perf set it was given what
    its fused launches added to the mapper's running totals: the
    monitor's ``perf dump`` shows how many lanes (a replicated rule) or
    (lane, slot) pairs (an erasure rule) the narrow retry loops
    finished and how many full-width passes followed a first."""
    import ceph_tpu.crush.vectorized as V
    from ceph_tpu.common.perf import PerfCounters
    from ceph_tpu.mon.pg_mapping import _vector_crush_for

    monkeypatch.setattr(V, "RETRY_MIN_LANES", 256)
    # a structure no other test compiles: the mapper is this test's own
    m = make_map(11, fanouts=[5, 7], pg_num=512, down_frac=0.0,
                 out_frac=0.1)
    del m.pools[3 - pool]
    PGMapping.build(m, fused="always")          # compiles and warms
    vc = _vector_crush_for(m.crush, rule)
    before = vc.totals()
    assert before["fused_launches"] == 1 and before[count] > 0
    perf = PerfCounters("placement_cache")
    pm = PGMapping.build(m, perf=perf)          # warm: fused by itself
    assert pm.fused_pools == 1
    assert vc.totals() == {k: 2 * v for k, v in before.items()}
    for name, total in vc.totals().items():
        assert perf.get(name) == total - before[name], name
    assert perf.get(count) > 0
    assert {"retry_lanes", "wide_retries", "indep_passes",
            "indep_retry_pairs"} <= set(perf.dump())


def brute_delta(old: OSDMap, new: OSDMap) -> set:
    """Reference diff: every (pool, pg) whose scalar (up, acting)
    differs between the two maps, plus pools in only one of them."""
    changed = set()
    pools = set(old.pools) | set(new.pools)
    for pid in pools:
        if pid not in old.pools or pid not in new.pools:
            src = old.pools.get(pid) or new.pools.get(pid)
            changed |= {(pid, pg) for pg in range(src.pg_num)}
            continue
        span = max(old.pools[pid].pg_num, new.pools[pid].pg_num)
        for pg in range(span):
            if (pg >= old.pools[pid].pg_num
                    or pg >= new.pools[pid].pg_num
                    or old._pg_to_up_acting_scalar(pid, pg)
                    != new._pg_to_up_acting_scalar(pid, pg)):
                changed.add((pid, pg))
    return changed


@pytest.mark.parametrize("seed", range(4))
def test_delta_matches_bruteforce_diff(seed):
    rnd = random.Random(100 + seed)
    m = make_map(100 + seed, pg_num=16)
    before = OSDMap.from_dict(m.to_dict())     # independent snapshot
    prev = m.placement_cache()
    ups = sorted(m.osds)
    inc = Incremental(epoch=m.epoch + 1)
    inc.new_down = rnd.sample(ups, 2)
    inc.new_out = [rnd.choice(ups)]
    inc.new_weights = {rnd.choice(ups): 0x6000}
    inc.new_pg_temp = {f"1.{rnd.randrange(16):x}": rnd.sample(ups, 3),
                       f"2.{rnd.randrange(16):x}": []}
    inc.new_pg_upmap_items = {
        f"2.{rnd.randrange(16):x}": [[rnd.choice(ups),
                                      rnd.choice(ups)]]}
    inc.new_pools = {3: {"pool_id": 3, "name": "fresh", "pg_num": 8,
                         "pgp_num": 8, "size": 3}}
    m.apply_incremental(inc)
    cur = m.placement_cache()
    got = set(cur.delta(prev))
    want = brute_delta(before, m)
    assert got == want


def test_epoch_invalidation_no_stale_reads():
    m = make_map(42, fanouts=[4, 4], pg_num=16, down_frac=0.0)
    gen0 = m._mutation_gen
    up0, act0 = m.pg_to_up_acting(1, 5)
    victim = up0[0]
    inc = Incremental(epoch=m.epoch + 1, new_down=[victim])
    m.apply_incremental(inc)
    assert m._mutation_gen != gen0
    # the very next read reflects the kill -- and stays scalar-exact
    up1, act1 = m.pg_to_up_acting(1, 5)
    assert victim not in up1
    assert (up1, act1) == m._pg_to_up_acting_scalar(1, 5)
    assert m.placement_cache().epoch == m.epoch
    # pg_temp/upmap mutations invalidate too
    pgid = m.pg_name(1, 5)
    m.apply_incremental(Incremental(
        epoch=m.epoch + 1, new_pg_temp={pgid: list(reversed(up1))}))
    up2, act2 = m.pg_to_up_acting(1, 5)
    assert act2 == list(reversed(up1))
    assert (up2, act2) == m._pg_to_up_acting_scalar(1, 5)


def test_osd_weights_memoized_per_generation():
    m = make_map(7, fanouts=[4, 4], pg_num=8)
    w0 = m.osd_weights()
    assert m.osd_weights() is w0            # same generation: memo hit
    m.apply_incremental(Incremental(epoch=m.epoch + 1,
                                    new_weights={0: 0x2000}))
    w1 = m.osd_weights()
    assert w1 is not w0 and w1[0] == 0x2000
    # out-of-band surgery path
    m.osds[1].weight = 0x3000
    m.invalidate_placement_cache()
    assert m.osd_weights()[1] == 0x3000


def test_balancer_full_mapping_rides_the_cache():
    from ceph_tpu.mgr.balancer import full_mapping
    m = make_map(9, pg_num=16)
    got = full_mapping(m)
    assert len(got) == sum(p.pg_num for p in m.pools.values())
    for pid, pool in m.pools.items():
        for pg in range(pool.pg_num):
            up, _ = m._pg_to_up_acting_scalar(pid, pg)
            assert got[f"{pid}.{pg:x}"] == up, (pid, pg)


def test_serialized_roundtrip_keeps_parity():
    m = make_map(13)
    m2 = OSDMap.from_dict(m.to_dict())
    assert_table_matches_scalar(m2, m2.placement_cache())
    # and the two tables agree with each other
    a, b = m.placement_cache(), m2.placement_cache()
    assert list(a.iter_all()) == list(b.iter_all())


def test_lookup_counters_and_recompute_counter():
    m = make_map(21, fanouts=[4, 4], pg_num=8)
    m.pg_to_up_acting(1, 0)
    m.pg_to_up_acting(1, 1)
    d = m.placement_perf.dump()
    assert d["bulk_recomputes"] == 1
    assert d["lookups"] == 2
    m.apply_incremental(Incremental(epoch=m.epoch + 1, new_down=[0]))
    m.pg_to_up_acting(1, 0)
    assert m.placement_perf.dump()["bulk_recomputes"] == 2


# -- the table as arrays (PR 43): every case through the public readers ------

def plain_map(pg_num: int = 16) -> OSDMap:
    """16 OSDs in 4 hosts, all up and in; a replicated pool (1, size 3)
    and an erasure pool (2, size 4); no upmap, no pg_temp."""
    m = make_map(0, fanouts=[4, 4], pg_num=pg_num, down_frac=0.0,
                 out_frac=0.0)
    m.pg_temp.clear()
    m.pg_upmap_items.clear()
    m.invalidate_placement_cache()
    return m


def pool_dict(m: OSDMap, pid: int, **changes) -> dict:
    return dict(asdict(m.pools[pid]), **changes)


def holders(m: OSDMap, pid: int, n: int) -> list[int]:
    """The n OSDs that hold most PGs of a pool (so taking them down
    leaves short rows behind)."""
    count: dict[int, int] = {}
    for p, _, up, _ in m.placement_cache().iter_all():
        for o in up if p == pid else ():
            count[o] = count.get(o, 0) + 1
    return sorted(count, key=lambda o: (-count[o], o))[:n]


def case_replicated_two_down(m):
    yield dict(new_down=holders(m, 1, 2))
    pm, perf = m.placement_cache(), m.placement_perf
    assert perf.get("ingest_shifted_pgs") > 0          # the sort path ran
    assert any(len(up) < 3 for p, _, up, _ in pm.iter_all() if p == 1)
    assert all(o >= 0 for p, _, up, _ in pm.iter_all() if p == 1
               for o in up)                            # closed up, no holes


def case_erasure_one_down(m):
    shifted = m.placement_perf.get("ingest_shifted_pgs")
    (victim,) = holders(m, 2, 1)
    del m.pools[1]
    m.invalidate_placement_cache()
    yield dict(new_down=[victim])
    rows = [up for p, _, up, _ in m.placement_cache().iter_all() if p == 2]
    assert all(len(up) == 4 and victim not in up for up in rows)
    assert any(-1 in up[:-1] for up in rows)           # a hole in position
    assert m.placement_perf.get("ingest_shifted_pgs") == shifted


def case_pg_temp_set_and_cleared(m):
    down = holders(m, 1, 1)
    yield dict(new_down=down)
    live = [o for o in sorted(m.osds) if o not in down]
    temps = {"1.3": live[:1], "1.5": live[2:6],        # shorter, longer
             "1.7": down + [99],                       # nobody live: up
             "2.2": [live[4], down[0], live[5], live[6]]}
    before = m.placement_perf.get("acting_overrides")
    delta = yield dict(new_pg_temp=temps)
    assert delta == [(1, 3), (1, 5), (2, 2)]
    assert m.placement_perf.get("acting_overrides") - before == 3
    assert m.pg_to_up_acting(1, 3)[1] == live[:1]
    assert m.pg_to_up_acting(1, 5)[1] == live[2:6]
    up, acting = m.pg_to_up_acting(1, 7)
    assert acting == up and acting is not up
    assert m.pg_to_up_acting(2, 2)[1] == [live[4], -1, live[5], live[6]]
    delta = yield dict(new_pg_temp={k: [] for k in temps})
    assert delta == [(1, 3), (1, 5), (2, 2)]
    assert m.placement_perf.get("acting_overrides") - before == 3


def case_pg_upmap_items(m):
    moved = []
    items = {}
    for pid in (1, 2):
        up, _ = m.pg_to_up_acting(pid, 4)
        to = next(o for o in sorted(m.osds) if o not in up)
        items[f"{pid}.4"] = [[up[1], to]]
        moved.append((pid, 4))
    delta = yield dict(new_pg_upmap_items=items)
    assert delta == moved
    assert m.pg_to_up_acting(1, 4)[0][1] == items["1.4"][0][1]
    delta = yield dict(removed_pg_upmap_items=list(items))
    assert delta == moved


def case_pool_created_then_deleted(m):
    delta = yield dict(new_pools={3: {"pool_id": 3, "name": "fresh",
                                      "pg_num": 8, "pgp_num": 8,
                                      "size": 2}})
    assert delta == [(3, pg) for pg in range(8)]
    delta = yield dict(removed_pools=[1])
    assert delta == [(1, pg) for pg in range(16)]


def case_pg_num_doubled(m):
    delta = yield dict(new_pools={2: pool_dict(m, 2, pg_num=32,
                                               pgp_num=32)})
    assert [pg for pid, pg in delta if pg >= 16] == list(range(16, 32))
    assert {pid for pid, _ in delta} == {2}


def case_size_three_to_two(m):
    yield dict(new_down=holders(m, 1, 1))     # some rows are two long
    delta = yield dict(new_pools={1: pool_dict(m, 1, size=2)})
    # a row that had lost one reads the same at either width
    assert 0 < len(delta) < 16 and {pid for pid, _ in delta} == {1}


CASES = [case_replicated_two_down, case_erasure_one_down,
         case_pg_temp_set_and_cleared, case_pg_upmap_items,
         case_pool_created_then_deleted, case_pg_num_doubled,
         case_size_three_to_two]


@pytest.mark.parametrize(
    "case", CASES, ids=[c.__name__.removeprefix("case_") for c in CASES])
def test_table_and_delta_through_the_public_readers(case):
    """Each case is a walk of epochs: after every step the whole table
    is the scalar pipeline's, entry for entry, in lists of Python ints,
    and ``delta`` is the brute-force diff, sorted."""
    m = plain_map()
    assert m.placement_cache().delta(m.placement_cache()) == []
    walk = case(m)
    step = next(walk)
    while True:
        before = OSDMap.from_dict(m.to_dict())
        prev = m.placement_cache()
        m.apply_incremental(Incremental(epoch=m.epoch + 1, **step))
        cur = m.placement_cache()
        assert cur is not prev
        assert_table_matches_scalar(m, cur)
        for pid, pg, up, acting in cur.iter_all():
            assert (up, acting) == m._pg_to_up_acting_scalar(pid, pg)
            assert all(type(o) is int for o in up + acting)
        delta = cur.delta(prev, perf=m.placement_perf)
        assert delta == sorted(brute_delta(before, m))
        assert all(type(x) is int for pair in delta for x in pair)
        assert cur.delta(cur) == []
        try:
            step = walk.send(delta)
        except StopIteration:
            break


def test_a_table_of_the_expansion_cells_shape_makes_no_per_pg_object(
        monkeypatch):
    """The mechanism (PR 43): two builds and a delta over 16,384 x 3
    and 8,192 x 11 rows leave the launch's arrays as the table, where
    a list a PG is 24,576 tracked objects a build; a lookup hands back
    lists of Python ints, for both pool kinds and an overridden PG."""
    import gc
    import ceph_tpu.mon.pg_mapping as pgm

    m = OSDMap()
    m.epoch, m.max_osd = 1, 1000
    m.osds = {o: OsdInfo(up=True, in_cluster=True) for o in range(1000)}
    m.pools[1] = PoolSpec(pool_id=1, name="rep", size=3, pg_num=16384,
                          pgp_num=16384)
    m.pools[2] = PoolSpec(pool_id=2, name="ec", type=POOL_TYPE_ERASURE,
                          size=11, min_size=9, pg_num=8192, pgp_num=8192,
                          crush_rule=1)
    m.pg_temp = {"1.10": [5, 6], "2.20": list(range(40, 51))}
    epoch = [0]

    def rows_of_epoch(crush, rule, xs, numrep, weights, **kw):
        """A launch's answer: the same rows every epoch but for 100
        PGs a pool that the epoch moves."""
        rows = np.random.default_rng(rule).integers(
            0, 1000, (len(xs), numrep)).astype(np.int64)
        moved = np.random.default_rng([rule, epoch[0]]).choice(
            len(xs), 100, replace=False)
        rows[moved, 0] = (rows[moved, 0] + 1 + epoch[0]) % 1000
        return rows, True

    monkeypatch.setattr(pgm, "bulk_crush", rows_of_epoch)
    gc.collect()
    gc.disable()
    try:
        before = len(gc.get_objects())
        tables = []
        for epoch[0] in (1, 2):
            tables.append(PGMapping.build(m, perf=m.placement_perf))
        delta = tables[1].delta(tables[0], perf=m.placement_perf)
        made = len(gc.get_objects()) - before
    finally:
        gc.enable()
    # each table moved its own 100 PGs a pool off the common rows
    assert 300 < len(delta) <= 400 and made < 1000, (len(delta), made)
    perf = m.placement_perf.dump()
    assert perf["ingest_shifted_pgs"] == 0 and perf["acting_overrides"] == 4
    for pid, pg, width in ((1, 0, 3), (2, 0, 11), (1, 0x10, 3),
                           (2, 0x20, 11)):
        up, acting = tables[1].lookup(pid, pg)
        assert type(up) is list and type(acting) is list
        assert len(up) == width and acting is not up
        assert all(type(o) is int for o in up + acting)
    assert tables[1].lookup(1, 0x10)[1] == [5, 6]
    assert tables[1].lookup(2, 0x20)[1] == list(range(40, 51))
