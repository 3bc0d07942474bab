"""Lane-by-lane equivalence of the fused JAX mapper vs the scalar engine."""

import numpy as np
import pytest

from ceph_tpu.crush import crush_do_rule, build_flat_map, build_two_level_map
from ceph_tpu.crush.types import CRUSH_ITEM_NONE
from ceph_tpu.crush.vectorized import VectorCrush


def scalar_batch(m, rule, xs, numrep, weights):
    out = []
    for x in xs:
        got = crush_do_rule(m, rule, x=int(x), result_max=numrep,
                            weights=weights)
        got = got + [CRUSH_ITEM_NONE] * (numrep - len(got))
        out.append(got)
    return np.asarray(out, dtype=np.int64)


def test_flat_firstn_matches_scalar():
    m = build_flat_map(12)
    weights = [0x10000] * 12
    vc = VectorCrush(m, 0)
    xs = np.arange(300, dtype=np.int32)
    got = vc.map_pgs(xs, 3, weights)
    want = scalar_batch(m, 0, xs, 3, weights)
    assert np.array_equal(got, want)


def test_flat_firstn_with_reweights():
    rng = np.random.default_rng(0)
    m = build_flat_map(10)
    weights = [0x10000] * 10
    weights[3] = 0           # out
    weights[7] = 0x8000      # half reweight
    vc = VectorCrush(m, 0)
    xs = rng.integers(0, 2**31 - 1, size=256).astype(np.int32)
    got = vc.map_pgs(xs, 4, weights)
    want = scalar_batch(m, 0, xs, 4, weights)
    assert np.array_equal(got, want)


def test_two_level_firstn_matches_scalar():
    m = build_two_level_map(6, 4)
    weights = [0x10000] * 24
    vc = VectorCrush(m, 0)
    xs = np.arange(0, 4000, 13, dtype=np.int32)
    got = vc.map_pgs(xs, 3, weights)
    want = scalar_batch(m, 0, xs, 3, weights)
    assert np.array_equal(got, want)


def test_two_level_firstn_degraded():
    m = build_two_level_map(5, 3)
    weights = [0x10000] * 15
    weights[4] = 0
    weights[11] = 0x4000
    vc = VectorCrush(m, 0)
    xs = np.arange(500, dtype=np.int32)
    got = vc.map_pgs(xs, 3, weights)
    want = scalar_batch(m, 0, xs, 3, weights)
    assert np.array_equal(got, want)


def test_two_level_indep_matches_scalar():
    m = build_two_level_map(8, 2)
    weights = [0x10000] * 16
    vc = VectorCrush(m, 1)
    xs = np.arange(0, 2000, 7, dtype=np.int32)
    got = vc.map_pgs(xs, 5, weights)
    want = scalar_batch(m, 1, xs, 5, weights)
    assert np.array_equal(got, want)


def test_two_level_indep_degraded():
    m = build_two_level_map(6, 2)
    weights = [0x10000] * 12
    weights[0] = 0
    weights[5] = 0
    vc = VectorCrush(m, 1)
    xs = np.arange(400, dtype=np.int32)
    got = vc.map_pgs(xs, 4, weights)
    want = scalar_batch(m, 1, xs, 4, weights)
    assert np.array_equal(got, want)


def test_weighted_hosts_match_scalar():
    m = build_two_level_map(4, 4,
                            host_weights=[0x40000, 0x20000, 0x10000, 0x40000])
    weights = [0x10000] * 16
    vc = VectorCrush(m, 0)
    xs = np.arange(600, dtype=np.int32)
    got = vc.map_pgs(xs, 2, weights)
    want = scalar_batch(m, 0, xs, 2, weights)
    assert np.array_equal(got, want)


def test_depth4_firstn_and_indep_lane_exact():
    """Arbitrary-depth descent (root->row->rack->host->osd): the fused
    engine must match the scalar mapper lane-for-lane on randomized
    deep maps with reweighted/out OSDs (the balancer's real map shape,
    mapper.c:441-825)."""
    from ceph_tpu.crush.builder import build_hierarchy
    from ceph_tpu.crush.vectorized import VectorCrush
    from ceph_tpu.crush import crush_do_rule

    rng = np.random.default_rng(5)
    cm = build_hierarchy([3, 4, 5, 4])       # 240 osds, 4 levels
    n = 240
    weights = [int(w) for w in rng.choice(
        [0, 0x8000, 0xc000, 0x10000], size=n, p=[.05, .1, .15, .7])]
    xs = rng.integers(0, 2**31 - 1, size=200, dtype=np.int64)
    for ruleno in (0, 1):
        vc = VectorCrush(cm, ruleno)
        assert vc.cm.n_levels == 4
        got = vc.map_pgs(xs, 3, weights)
        for i, x in enumerate(xs):
            want = crush_do_rule(cm, ruleno, int(x), 3, weights)
            assert list(got[i]) == list(want), (ruleno, i)


def test_choose_args_weight_set_scalar_and_vector():
    """choose_args weight-sets (mapper.c:289 get_choose_arg_weights):
    a per-position weight override must steer placement identically in
    the scalar and fused engines, and differently from the base map."""
    from ceph_tpu.crush.builder import build_hierarchy
    from ceph_tpu.crush.vectorized import VectorCrush
    from ceph_tpu.crush import crush_do_rule

    rng = np.random.default_rng(7)
    cm = build_hierarchy([4, 4, 4])          # 64 osds, 3 levels
    weights = [0x10000] * 64
    xs = rng.integers(0, 2**31 - 1, size=200, dtype=np.int64)

    base = [list(crush_do_rule(cm, 0, int(x), 3, weights)) for x in xs]
    # the balancer zeroes the first rack for position 0 and doubles
    # the last for later positions
    cm.choose_args = {-1: {"weight_set": [
        [0, 0x40000, 0x40000, 0x40000],
        [0x40000, 0x40000, 0x40000, 0x80000],
    ]}}
    steered = [list(crush_do_rule(cm, 0, int(x), 3, weights))
               for x in xs]
    assert steered != base, "weight-set had no effect"
    # position-0 never lands in the zeroed first rack (osds 0..15)
    assert all(s[0] >= 16 for s in steered)

    vc = VectorCrush(cm, 0)
    got = vc.map_pgs(xs, 3, weights)
    for i in range(len(xs)):
        assert list(got[i]) == steered[i], (i, list(got[i]), steered[i])

    # explicit override parameter beats the map's own choose_args
    plain = [list(crush_do_rule(cm, 0, int(x), 3, weights,
                                choose_args={})) for x in xs]
    assert plain == base

    # indep (erasure) rules: the weight-set position is the top-level
    # OUTPOS (0), not the replica slot -- lane-exact there too
    steered_i = [list(crush_do_rule(cm, 1, int(x), 3, weights))
                 for x in xs]
    vci = VectorCrush(cm, 1)
    goti = vci.map_pgs(xs, 3, weights)
    for i in range(len(xs)):
        assert list(goti[i]) == steered_i[i], \
            (i, list(goti[i]), steered_i[i])


def test_firstn_exhausted_slot_compacts_like_scalar():
    """When a replica slot exhausts every try (nearly-all-out
    cluster), scalar firstn compacts -- the fused engine must produce
    the same compacted prefix, including drawing later slots at the
    UNADVANCED weight-set position."""
    from ceph_tpu.crush.builder import build_hierarchy
    from ceph_tpu.crush.vectorized import VectorCrush
    from ceph_tpu.crush import crush_do_rule

    rng = np.random.default_rng(17)
    cm = build_hierarchy([3, 3])             # 9 osds
    cm.choose_args = {-1: {"weight_set": [
        [0x10000, 0x20000, 0x30000],
        [0x30000, 0x10000, 0x20000],
        [0x20000, 0x30000, 0x10000]]}}
    # only two osds in: most lanes cannot place 3 replicas
    weights = [0] * 9
    weights[2] = weights[7] = 0x10000
    xs = rng.integers(0, 2**31 - 1, size=128, dtype=np.int64)
    vc = VectorCrush(cm, 0)
    got = vc.map_pgs(xs, 3, weights)
    from ceph_tpu.crush.types import CRUSH_ITEM_NONE as NONE
    for i, x in enumerate(xs):
        want = crush_do_rule(cm, 0, int(x), 3, weights)
        trimmed = [v for v in got[i] if v != NONE]
        assert trimmed == list(want), (i, trimmed, want)


def test_inputs_beyond_max_lanes_run_as_bounded_launches(monkeypatch):
    """map_pgs bounds lanes per device launch: a long input runs as
    equal-sized launches (tail padded) and maps exactly like the
    scalar engine, ragged tail included."""
    import ceph_tpu.crush.vectorized as V

    monkeypatch.setattr(V, "MAX_LANES", 128)
    m = build_two_level_map(4, 3)
    weights = [0x10000] * 12
    vc = VectorCrush(m, 0)
    xs = np.random.default_rng(5).integers(0, 2**31 - 1, size=300)
    got = vc.map_pgs(xs, 3, weights)
    assert got.shape == (300, 3)
    assert np.array_equal(got, scalar_batch(m, 0, xs, 3, weights))
