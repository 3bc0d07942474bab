"""Triple-implementation CRUSH validation: the independent C oracle
(native/crush_oracle.cc), the Python scalar engine (decision-level
mapper.c rendering) and the fused JAX vectorized mapper must agree
lane-for-lane over randomized maps, weights and failure patterns --
a placement bug cannot hide in all three (the crushtool --test /
CrushTester discipline)."""

import numpy as np
import pytest

from ceph_tpu.crush import crush_do_rule
from ceph_tpu.crush.builder import build_two_level_map
from ceph_tpu.crush.types import CRUSH_ITEM_NONE
from ceph_tpu.native import available, crush_oracle_do_rule


pytestmark = pytest.mark.skipif(not available(),
                                reason="native toolchain unavailable")


def random_cluster(rng):
    nh = int(rng.integers(2, 9))
    per = int(rng.integers(2, 9))
    hw = [int(0x10000 * per * rng.uniform(0.5, 2.0)) for _ in range(nh)]
    cm = build_two_level_map(nh, per, host_weights=hw)
    n_osd = nh * per
    w = [0x10000] * n_osd
    for i in rng.integers(0, n_osd, size=max(1, n_osd // 4)):
        w[int(i)] = int(rng.choice([0, 0x4000, 0x8000, 0x10000]))
    return cm, w


@pytest.mark.parametrize("ruleno", [0, 1], ids=["firstn", "indep"])
def test_oracle_matches_scalar_engine(ruleno):
    rng = np.random.default_rng(41 + ruleno)
    checked = 0
    for _ in range(8):
        cm, w = random_cluster(rng)
        for x in rng.integers(0, 2**31 - 1, size=150):
            numrep = int(rng.integers(2, 5))
            want = crush_do_rule(cm, ruleno, int(x), numrep, w)
            got = crush_oracle_do_rule(cm, ruleno, int(x), numrep, w)
            assert got == want, (int(x), numrep, want, got)
            checked += 1
    assert checked >= 1000


def test_all_three_agree_vectorized_shape():
    """On the map shape the fused path serves (uniform straw2,
    chooseleaf, jewel), C oracle == scalar == vectorized, lane-exact."""
    from ceph_tpu.crush.vectorized import VectorCrush

    rng = np.random.default_rng(99)
    cm = build_two_level_map(6, 5)
    w = [0x10000] * 30
    for i in (3, 11, 27):
        w[i] = 0
    xs = rng.integers(0, 2**31 - 1, size=256).astype(np.int64)
    for ruleno in (0, 1):
        vc = VectorCrush(cm, ruleno)
        vec = vc.map_pgs(xs, 3, w)
        for lane, x in enumerate(xs):
            scalar = crush_do_rule(cm, ruleno, int(x), 3, w)
            oracle = crush_oracle_do_rule(cm, ruleno, int(x), 3, w)
            assert oracle == scalar, (ruleno, int(x))
            assert list(vec[lane]) == scalar, (ruleno, int(x), lane)


@pytest.mark.parametrize("ruleno", [0, 1], ids=["firstn", "indep"])
def test_all_three_agree_depth4(ruleno):
    """Randomized depth-4 maps (root->row->rack->host->osd): C oracle,
    scalar engine and the fused vectorized mapper agree lane-exact."""
    from ceph_tpu.crush.builder import build_hierarchy
    from ceph_tpu.crush.vectorized import VectorCrush

    rng = np.random.default_rng(61 + ruleno)
    for trial in range(3):
        fan = [int(rng.integers(2, 4)), int(rng.integers(2, 4)),
               int(rng.integers(2, 4)), int(rng.integers(2, 6))]
        cm = build_hierarchy(fan)
        n = fan[0] * fan[1] * fan[2] * fan[3]
        w = [0x10000] * n
        for i in rng.integers(0, n, size=max(1, n // 5)):
            w[int(i)] = int(rng.choice([0, 0x4000, 0x8000]))
        xs = rng.integers(0, 2**31 - 1, size=128).astype(np.int64)
        vc = VectorCrush(cm, ruleno)
        vec = vc.map_pgs(xs, 3, w)
        for i, x in enumerate(xs):
            want = crush_do_rule(cm, ruleno, int(x), 3, w)
            oracle = crush_oracle_do_rule(cm, ruleno, int(x), 3, w)
            assert oracle == want, (trial, i, want, oracle)
            assert list(vec[i]) == want, (trial, i, want, list(vec[i]))


@pytest.mark.parametrize("case", ["firstn3", "indep11", "weighted"])
def test_all_three_agree_on_the_cell_tree(case):
    """crush_1000osd_bulk's own tree (fanouts 5, 5, 4, 10, chooseleaf
    over hosts, jewel) with 4,096 of its driver's placement seeds: as
    configured, under the erasure rule at 11 positions, and with
    non-uniform item weights, reweighted and out OSDs."""
    from ceph_tpu.crush.vectorized import VectorCrush
    from test_crush_vectorized import cell_pps, cell_tree

    cm, ruleno, numrep, w = cell_tree(case)
    xs = cell_pps(4096, 2)
    vec = VectorCrush(cm, ruleno).map_pgs(xs, numrep, w)
    none = [CRUSH_ITEM_NONE]
    for lane, x in enumerate(xs):
        want = crush_do_rule(cm, ruleno, int(x), numrep, w)
        oracle = crush_oracle_do_rule(cm, ruleno, int(x), numrep, w)
        assert oracle == want, (case, lane)
        assert list(vec[lane]) == want + none * (numrep - len(want)), \
            (case, lane)
