"""An expansion stepped through OSDMap epochs on a small map with both
pool kinds: every epoch's table (``PGMapping.build``) is the per-PG
scalar sweep's, every ``delta`` the brute-force diff, the stages are
timed into the ``placement_cache`` set, a declined fused path is
counted, and the weight steps build no program after the first.
"""

from __future__ import annotations

import ast
from pathlib import Path

import numpy as np
import pytest

from ceph_tpu.common import tracing
from ceph_tpu.common.perf import PerfCounters
from ceph_tpu.crush.builder import build_hierarchy, crush_command
from ceph_tpu.mon import pg_mapping
from ceph_tpu.mon.osdmap import (POOL_TYPE_ERASURE, Incremental, OSDMap,
                                 OsdInfo, PoolSpec, crush_to_dict)
from ceph_tpu.mon.pg_mapping import PGMapping, bulk_crush

from test_tracing_sections import ROOT, _is_section

W = 0x10000
STEPS = (0.25, 0.5, 1.0)


def expanding_map(pg_num: int = 64) -> tuple[OSDMap, list[dict]]:
    """2 racks x 3 hosts x 3 osds, the second rack at weight 0 (the
    expansion), osd.1 out, osd.4 down, osd.7 reweighted; a replicated
    pool and an erasure pool (k=2, m=2 over six hosts); and the CRUSH
    maps of a three-step raise of the new rack."""
    cm = build_hierarchy([2, 3, 3])
    cm.bucket_names = {bid: f"b{bid}" for bid in cm.buckets}
    cm.type_names = {0: "osd", 1: "host", 2: "rack", 3: "root"}
    cm = crush_command(cm, "osd crush reweight-subtree",
                       {"name": "b-6", "weight": 0.0})
    m = OSDMap()
    m.epoch, m.crush, m.max_osd = 1, cm, 18
    for o in range(18):
        m.osds[o] = OsdInfo(up=o != 4, in_cluster=o != 1,
                            weight=0x9000 if o == 7 else W)
    m.pools[1] = PoolSpec(pool_id=1, name="rep", size=3, pg_num=pg_num,
                          pgp_num=pg_num)
    m.pools[2] = PoolSpec(pool_id=2, name="ec", type=POOL_TYPE_ERASURE,
                          size=4, min_size=3, pg_num=pg_num // 2,
                          pgp_num=pg_num // 2, crush_rule=1)
    m.pool_names = {"rep": 1, "ec": 2}
    steps = [crush_to_dict(crush_command(
        cm, "osd crush reweight-subtree", {"name": "b-6", "weight": w}))
        for w in STEPS]
    return m, steps


def scalar_table(m: OSDMap) -> dict:
    return {(pid, pg): m._pg_to_up_acting_scalar(pid, pg)
            for pid, pool in m.pools.items() for pg in range(pool.pg_num)}


@pytest.mark.parametrize("fused", ["always", "never"])
def test_every_epoch_of_an_expansion_is_the_scalar_sweeps(fused,
                                                          monkeypatch):
    monkeypatch.setattr(pg_mapping, "FUSED_MIN_LANES",
                        1 if fused == "always" else 1 << 30)
    monkeypatch.setattr(pg_mapping, "_FUSED_WARM", set())
    m, steps = expanding_map()
    perf = m.placement_perf
    before = scalar_table(m)
    prev = m.placement_cache()
    moved_total = 0
    for i, crush in enumerate(steps):
        m.apply_incremental(Incremental(epoch=m.epoch + 1, new_crush=crush))
        assert m.peek_placement_cache() is None      # never a stale table
        cur = m.placement_cache()
        assert cur.epoch == m.epoch == 2 + i
        want = scalar_table(m)
        for (pid, pg), (up, acting) in want.items():
            assert cur.lookup(pid, pg) == (up, acting), (i, pid, pg)
        moved = cur.delta(prev, perf=perf)
        brute = sorted(k for k in want if want[k] != before[k])
        assert sorted(moved) == brute and len(set(moved)) == len(moved)
        assert brute, "a weight step that moves nothing tests nothing"
        moved_total += len(moved)
        prev, before = cur, want
    # the new rack's osds hold PGs now, the erasure pool kept its holes
    last = [up for _, _, up, _ in prev.iter_all()]
    assert any(o >= 9 for up in last for o in up)
    assert all(len(up) == 4 for pid, _, up, _ in prev.iter_all()
               if pid == 2)
    dump = perf.dump()
    assert dump["bulk_recomputes"] == 4 and dump["delta_pgs"] == moved_total
    for timer, count in (("recompute", 4), ("ingest", 4), ("delta", 3)):
        assert dump[timer]["avgcount"] == count and dump[timer]["sum"] > 0
    if fused == "always":
        assert dump["fused_pools"] == 8 and dump["fused_launches"] == 8
        assert dump["launch"]["avgcount"] == 8
        assert dump["launch"]["sum"] <= dump["recompute"]["sum"]
        assert dump["indep_passes"] >= 4
        # both programs built for the first table at the latest, none
        # by a weight step
        assert dump["programs_built"] <= 2
    else:
        assert dump["scalar_pools"] == 8 and "launch" not in dump
        assert "fused_declined" not in dump          # not tried, not declined


def test_weight_steps_build_no_program_and_a_launched_structure_is_warm(
        monkeypatch):
    """After the first table of a structure every later map of it
    launches fused whatever its lane count, on the executable that is
    there."""
    monkeypatch.setattr(pg_mapping, "_FUSED_WARM", set())
    m, steps = expanding_map(pg_num=128)
    PGMapping.build(m, fused="always")               # builds both programs
    perf = PerfCounters("placement_cache")
    for crush in steps:
        m.apply_incremental(Incremental(epoch=m.epoch + 1, new_crush=crush))
        pm = PGMapping.build(m, perf=perf)           # 'auto', 128 < 2048
        assert pm.fused_pools == 2 and pm.scalar_pools == 0
    assert perf.get("fused_launches") == 6
    assert perf.get("programs_built") == 0
    # a map of another structure is not warm: the scalar sweep
    other = OSDMap()
    other.epoch, other.crush, other.max_osd = 1, build_hierarchy([4, 2]), 8
    for o in range(8):
        other.osds[o] = OsdInfo(up=True)
    other.pools[1] = PoolSpec(pool_id=1, name="rep", size=2, pg_num=16,
                              pgp_num=16)
    assert PGMapping.build(other).scalar_pools == 1


def test_the_structural_key_ignores_weights_and_nothing_else():
    key = pg_mapping._structure_key
    m, steps = expanding_map()
    from ceph_tpu.mon.osdmap import crush_from_dict
    maps = [crush_from_dict(s) for s in steps]
    assert len({key(cm) for cm in maps} | {key(m.crush)}) == 1
    assert maps[0].__dict__["_structure_key"] == key(maps[0])   # kept
    grown = crush_command(m.crush, "osd crush add", {
        "name": "osd.18", "weight": 1.0, "loc": {"host": "b-3"}})
    assert key(grown) != key(m.crush)
    retuned = crush_from_dict(steps[0])
    retuned.tunables.choose_total_tries = 19
    assert key(retuned) != key(maps[0])
    ruled = crush_from_dict(steps[0])
    ruled.rules[1].steps[0].arg1 = 7
    assert key(ruled) != key(maps[0])
    sets = crush_from_dict(steps[0])
    sets.create_choose_args(2)
    assert key(sets) != key(maps[0])


@pytest.mark.parametrize("spoil,reason", [
    (lambda cm: setattr(cm.buckets[-3], "alg", 4), "bucket_alg"),
    (lambda cm: setattr(cm.tunables, "chooseleaf_vary_r", 0), "tunables"),
])
def test_a_declined_fused_path_is_counted_by_reason(spoil, reason):
    m, _ = expanding_map()
    spoil(m.crush)
    perf = PerfCounters("placement_cache")
    xs = np.arange(40)
    rows, used = bulk_crush(m.crush, 0, xs, 3, m.osd_weights(),
                            min_lanes=1, perf=perf)
    assert not used and rows.shape == (40, 3)
    assert perf.get("fused_declined") == 1
    assert perf.get(f"fused_declined_{reason}") == 1
    assert perf.get("fused_launches") == 0
    with pytest.raises(ValueError):
        bulk_crush(m.crush, 0, xs, 3, m.osd_weights(), fused="always")
    # the scalar sweep's rows are the scalar mapper's
    want, _ = bulk_crush(m.crush, 0, xs, 3, m.osd_weights(), fused="never")
    assert np.array_equal(rows, want)


def test_placement_is_a_layer_with_its_five_sections():
    """``placement.pps``, ``.launch``, ``.ingest``, ``.delta`` in the
    table's module and ``.apply`` around ``apply_incremental``; nothing
    else opens a ``placement.*`` section."""
    assert "placement" in tracing.SECTION_LAYERS
    found: dict[str, str] = {}
    for path in (ROOT / "ceph_tpu").rglob("*.py"):
        if "section(" not in path.read_text():
            continue
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, ast.With):
                for i in node.items:
                    if _is_section(i) and i.context_expr.args[0].value \
                            .startswith("placement."):
                        found[i.context_expr.args[0].value] = str(
                            path.relative_to(ROOT))
    assert found == {
        "placement.pps": "ceph_tpu/mon/pg_mapping.py",
        "placement.launch": "ceph_tpu/mon/pg_mapping.py",
        "placement.ingest": "ceph_tpu/mon/pg_mapping.py",
        "placement.delta": "ceph_tpu/mon/pg_mapping.py",
        "placement.apply": "ceph_tpu/mon/osdmap.py"}
    assert isinstance(Path(ROOT), Path)


@pytest.mark.parametrize("numrep", [6, 11])
def test_indep_program_carries_its_scope_around_the_loop(numrep):
    """``crush_indep`` names the erasure rule's one loop in a device
    trace; its draws nest inside it."""
    import re

    import jax.numpy as jnp
    from ceph_tpu.crush.vectorized import VectorCrush

    vc = VectorCrush(build_hierarchy([4, 4, 2]), 1)
    text = vc.crush_indep.lower(
        vc, jnp.arange(32, dtype=jnp.int32), numrep,
        jnp.full((32,), W, jnp.int32)).as_text(debug_info=True)
    assert "module @jit_crush_indep " in text
    assert re.search(r'crush_indep/[^"]*straw2_draw', text)
    assert "crush_retry" not in text


def test_indep_program_over_the_threshold_nests_its_narrow_stage(
        monkeypatch):
    """A launch long enough for a narrow stage: ``crush_retry`` inside
    ``crush_indep`` around the narrow loop's draws, and one draw body a
    stage and bucket level (the full-width loop's and the narrow
    loop's), however many slots the rule fills."""
    import re

    import jax.numpy as jnp
    import ceph_tpu.crush.vectorized as V
    from ceph_tpu.crush.vectorized import VectorCrush

    monkeypatch.setattr(V, "RETRY_MIN_LANES", 64)
    vc = VectorCrush(build_hierarchy([4, 4, 2]), 1)
    draws = []
    for numrep in (6, 11):
        text = vc.crush_indep.lower(
            vc, jnp.arange(64, dtype=jnp.int32), numrep,
            jnp.full((32,), W, jnp.int32)).as_text(debug_info=True)
        assert re.search(r'crush_indep/crush_retry/[^"]*straw2_draw', text)
        assert re.search(r'crush_indep/while/[^"]*straw2_draw', text)
        assert "crush_retry/crush_indep" not in text
        # crush_ln's two lookups, a one-hot product each: two a draw
        draws.append(len(re.findall(r"stablehlo\.dot_general", text)))
    # two stages x three levels x two lookups, not one set a slot
    assert draws == [12, 12]
