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


def test_import_does_not_flip_global_x64():
    import jax
    import ceph_tpu.crush.vectorized  # noqa: F401 -- the old offender
    assert jax.config.jax_enable_x64 is False


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


# -- the straw2 draw in 32-bit limbs ----------------------------------------

DRAW_WEIGHTS = [1, 2, 3, 0xFFFF, 0x10000, 0x10001, 0xA0000, 0x280000,
                0xC80000, 2**30 + 12345, 2**31 - 1]
_rng = np.random.default_rng(32)
DRAW_WEIGHTS += [int(w) for w in _rng.integers(1, 2**31, size=10)]
DRAW_WEIGHTS += [int(2 ** e) for e in _rng.uniform(0, 31, size=10)]


@pytest.mark.parametrize("w", DRAW_WEIGHTS, ids=hex)
def test_draw_is_the_exact_quotient_for_every_u(w):
    """(2^48 - crush_ln(u)) // w for all 65,536 u, against numpy int64;
    a zero weight never wins against w, equal draws pick the first
    item, an all-zero bucket picks item 0."""
    import jax
    import jax.numpy as jnp
    from ceph_tpu.crush.ln import crush_ln_np
    from ceph_tpu.crush.vectorized import (
        crush_ln_jnp, hash32_3_jnp, straw2_choose, straw2_draws,
        straw2_quotient, straw2_recip)

    u = np.arange(0x10000)
    want = ((1 << 48) - crush_ln_np(u)) // w
    ws = np.full(u.shape, w, np.uint32)
    q_hi, q_lo = jax.jit(lambda u, ws, rw: straw2_quotient(
        *crush_ln_jnp(u), ws, rw))(jnp.asarray(u, jnp.uint32), ws,
                                   straw2_recip(ws))
    assert q_hi.dtype == q_lo.dtype == jnp.uint32
    assert np.array_equal(np.asarray(q_hi), want >> 32)
    assert np.array_equal(np.asarray(q_lo), want & 0xFFFFFFFF)

    # buckets of four items hashing alike, so every positive weight
    # draws the same q: [0, w, w, 0] picks item 1, [0, 0, 0, 0] item 0
    xs = jnp.arange(4096, dtype=jnp.int32)
    r = jnp.zeros_like(xs)
    ids = jnp.full((4, 4096), -7, jnp.int32)
    for weights, picked in (([0, w, w, 0], 1), ([0, 0, 0, 0], 0)):
        wt = np.repeat(np.asarray(weights, np.int32)[:, None], 4096, 1)
        q_hi, q_lo = straw2_draws(xs, ids, r, wt, straw2_recip(wt))
        zero = np.asarray(wt) == 0
        assert (np.asarray(q_hi)[zero] == 0xFFFFFFFF).all()
        assert (np.asarray(q_lo)[zero] == 0xFFFFFFFF).all()
        assert (np.asarray(straw2_choose(q_hi, q_lo)) == picked).all()
    # what a bucket draws is the quotient of the u its hash gives
    one = np.full((1, 4096), w, np.int32)
    q_hi, q_lo = straw2_draws(xs, ids[:1], r, one, straw2_recip(one))
    us = np.asarray(hash32_3_jnp(xs, ids[0], r)) & 0xFFFF
    assert np.array_equal(np.asarray(q_hi)[0], want[us] >> 32)
    assert np.array_equal(np.asarray(q_lo)[0], want[us] & 0xFFFFFFFF)


def _wide_avals(jaxpr):
    """Every 64-bit operand or result of the jaxpr's equations, the
    bodies of its loops and calls included."""
    import jax

    found = []
    for eqn in jaxpr.eqns:
        for v in (*eqn.invars, *eqn.outvars):
            dtype = getattr(getattr(v, "aval", None), "dtype", None)
            if dtype is not None and dtype.itemsize > 4:
                found.append((eqn.primitive.name, str(v.aval)))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _wide_avals(sub)
    return found


def _primitives(jaxpr) -> set:
    import jax

    names = {eqn.primitive.name for eqn in jaxpr.eqns}
    for eqn in jaxpr.eqns:
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names |= _primitives(sub)
    return names


@pytest.mark.parametrize("x64", [False, True], ids=["x64_off", "x64_on"])
@pytest.mark.parametrize("ruleno,narrow", [(0, False), (1, False), (0, True),
                                           (1, True)],
                         ids=["firstn", "indep", "firstn_narrow",
                              "indep_narrow"])
def test_no_64_bit_type_reaches_the_device(ruleno, narrow, x64, monkeypatch):
    """The mapper's program holds no 64-bit type whether an embedding
    process has jax's x64 flag on or off, maps like the scalar engine
    under both, and map_pgs never touches the flag; ``narrow``: the
    program of a long launch, with the compaction of the lanes (or, of
    the erasure rule, the pairs) left, their gather, the narrow loop and
    the scatter back."""
    import jax
    import jax.numpy as jnp
    import ceph_tpu.crush.vectorized as V
    from ceph_tpu.crush.builder import build_hierarchy

    if narrow:
        monkeypatch.setattr(V, "RETRY_MIN_LANES", 128)
        monkeypatch.setattr(V, "RETRY_NARROW", 2)
    cm = build_hierarchy([3, 3, 4])
    weights = [0x10000] * 36
    weights[5], weights[20] = 0, 0x8000
    vc = VectorCrush(cm, ruleno)
    fn = vc.crush_firstn if ruleno == 0 else vc.crush_indep
    xs = np.arange(0, 6400, 25, dtype=np.int32)
    want = scalar_batch(cm, ruleno, xs, 3, weights)
    with jax.enable_x64(x64):
        jaxpr = jax.make_jaxpr(lambda xs, w: fn(xs, 3, w))(
            jnp.asarray(xs), jnp.asarray(weights, jnp.int32))
        assert _wide_avals(jaxpr.jaxpr) == []
        assert len(jaxpr.jaxpr.eqns) > 0
        # the narrow stage is the program's one conditional
        assert ("cond" in _primitives(jaxpr.jaxpr)) == narrow
        # no way into the flag from the mapper
        monkeypatch.setattr(jax, "enable_x64", None)
        assert np.array_equal(vc.map_pgs(xs, 3, weights), want)
    assert (vc.retry_lanes + vc.indep_retry_pairs > 0) == narrow


# -- the benchmark cell's own widths ----------------------------------------

CELL_FANOUTS = [5, 5, 4, 10]         # crush_1000osd_3rep: 1000 osds


def cell_pps(lanes: int, pool: int) -> np.ndarray:
    """The placement seeds benchmark/drivers/crush_bulk.py maps."""
    from ceph_tpu.crush.vectorized import hash32_2_jnp

    return np.asarray(hash32_2_jnp(np.arange(lanes, dtype=np.uint32),
                                   np.uint32(pool))) & 0x7FFFFFFF


def cell_tree(case: str):
    """(map, ruleno, numrep, osd weights) of the cell's tree: as the
    configuration states it, under the erasure rule at 11 positions, or
    with everything a deployment's map can carry."""
    from ceph_tpu.crush.builder import build_hierarchy

    cm = build_hierarchy(CELL_FANOUTS)
    weights = [0x10000] * 1000
    if case == "firstn3":
        return cm, 0, 3, weights
    if case == "indep11":
        return cm, 1, 11, weights
    if case == "one_replica":
        return cm, 0, 1, weights
    if case == "third_out":
        for osd in np.random.default_rng(37).choice(1000, size=333,
                                                    replace=False):
            weights[int(osd)] = 0
        return cm, 0, 3, weights
    rng = np.random.default_rng(3200)
    for b in cm.buckets.values():
        # some under 2^17, so that the quotient passes 32 bits
        b.item_weights = [int(rng.integers(1, 1 << 17)) if rng.random() < .3
                          else int(rng.integers(1 << 15, 1 << 24))
                          for _ in b.items]
    for osd in rng.choice(1000, size=50, replace=False):
        weights[int(osd)] = 0x8000
    for osd in rng.choice(1000, size=20, replace=False):
        weights[int(osd)] = 0
    if case == "weighted_choose_args":
        root = cm.buckets[-1]
        cm.choose_args = {-1: {"weight_set": [
            [int(rng.integers(1, 1 << 20)) for _ in root.items],
            [int(rng.integers(1 << 12, 1 << 26)) for _ in root.items]]}}
    return cm, 0, 3, weights


@pytest.mark.parametrize("case", ["weighted", "weighted_choose_args"])
def test_cell_tree_weighted_lane_exact(case):
    """The cell's tree with non-uniform item weights (a choose_args
    weight-set of two positions on top), 5 % of the OSDs reweighted and
    2 % out: 4,096 of the driver's seeds, lane for lane."""
    cm, ruleno, numrep, weights = cell_tree(case)
    xs = cell_pps(4096, 1)
    got = VectorCrush(cm, ruleno).map_pgs(xs, numrep, weights)
    assert np.array_equal(got, scalar_batch(cm, ruleno, xs, numrep, weights))


# -- the retry loop at the width of what is left ----------------------------

def small_tree(case: str):
    """(map, ruleno, numrep, osd weights): the map of
    test_firstn_exhausted_slot_compacts_like_scalar (two OSDs in, three
    weight-set positions: most lanes run out of tries in some slot and
    draw the later ones at the unadvanced position) or of
    test_choose_args_weight_set_scalar_and_vector."""
    from ceph_tpu.crush.builder import build_hierarchy

    if case == "exhausting":
        cm = build_hierarchy([3, 3])
        cm.choose_args = {-1: {"weight_set": [
            [0x10000, 0x20000, 0x30000],
            [0x30000, 0x10000, 0x20000],
            [0x20000, 0x30000, 0x10000]]}}
        weights = [0] * 9
        weights[2] = weights[7] = 0x10000
        return cm, 0, 3, weights
    cm = build_hierarchy([4, 4, 4])
    cm.choose_args = {-1: {"weight_set": [
        [0, 0x40000, 0x40000, 0x40000],
        [0x40000, 0x40000, 0x40000, 0x80000]]}}
    return cm, 0, 3, [0x10000] * 64


# tree, lanes, RETRY_NARROW (None: the module's own widths, which these
# lane counts are under), whether a narrow loop finished any lane (None:
# as the last full-width pass happens to leave it), whether a replica
# made a full-width pass after its first
RETRY_CASES = {
    # the cell's tree, all weights in: replicas 1 and 2 leave 1-4 lanes
    # of a hundred, which fit a sixteenth
    "cell-narrow": ("firstn3", 1024, 16, True, False),
    "cell-wide": ("firstn3", 1024, 128, None, True),
    "cell-below_threshold": ("firstn3", 1024, None, False, True),
    # a third of the OSDs out: what a first try leaves overflows a
    # sixteenth, so full-width passes go on until it fits
    "third_out-wide": ("third_out", 1024, 16, None, True),
    "third_out-below_threshold": ("third_out", 1024, None, False, True),
    # every lane fits (RETRY_NARROW 1): the lanes that run out of tries
    # do so inside the narrow loop, at their own weight-set position
    "exhausting-narrow": ("exhausting", 128, 1, True, False),
    "exhausting-wide": ("exhausting", 128, 16, None, True),
    "choose_args-narrow": ("choose_args", 512, 2, True, False),
    "choose_args-wide": ("choose_args", 512, 64, None, True),
    # no lane is left by the one replica's first try: no retry runs
    "one_replica-narrow": ("one_replica", 1024, 16, False, False),
}


@pytest.mark.parametrize("case", list(RETRY_CASES))
def test_retry_at_the_width_of_what_is_left(case, monkeypatch):
    """crush_firstn over a launch long enough to finish each replica's
    leftover lanes in a narrow loop: lane for lane the scalar mapper's
    result whichever width ran, the mapper's totals saying which did."""
    import ceph_tpu.crush.vectorized as V

    tree, lanes, narrow, narrow_ran, wide_ran = RETRY_CASES[case]
    if narrow is not None:
        monkeypatch.setattr(V, "RETRY_MIN_LANES", lanes)
        monkeypatch.setattr(V, "RETRY_NARROW", narrow)
    cm, ruleno, numrep, weights = (
        small_tree(tree) if tree in ("exhausting", "choose_args")
        else cell_tree(tree))
    xs = cell_pps(lanes, 2)
    vc = VectorCrush(cm, ruleno)
    got = vc.map_pgs(xs, numrep, weights)
    assert np.array_equal(got, scalar_batch(cm, ruleno, xs, numrep, weights))
    assert vc.launches == 1
    if narrow_ran is not None:
        assert (vc.retry_lanes > 0) == narrow_ran, vc.totals()
    assert (vc.wide_retries > 0) == wide_ran, vc.totals()
    if tree == "exhausting":
        # slots were left unplaced, in lanes that placed a later one
        assert (got == CRUSH_ITEM_NONE).any()


# -- the erasure rule's retry at the width of what is left ------------------

def indep_tree(case: str):
    """(map, ruleno, numrep, osd weights) under an indep rule: the
    cell's tree at 11 slots (all weights in, or a third of the OSDs
    out: the rule's five leaf tries fail in some hosts, so not only
    collisions leave a pair undefined); nine hosts for 11 slots (two
    slots of every lane stay undefined until the tries run out); a map
    whose root and hosts carry weight-sets of two positions (the
    descent draws at position 0, the leaf at the slot's); a flat map
    under plain ``choose indep`` with OSDs out and reweighted."""
    from ceph_tpu.crush.builder import (ROOT_ID, build_hierarchy,
                                        erasure_rule)

    if case in ("indep11", "third_out"):
        cm, _, _, weights = cell_tree(case)
        return cm, 1, 11, weights
    if case == "short":
        return build_hierarchy([3, 3, 2]), 1, 11, [0x10000] * 18
    rng = np.random.default_rng(4200)
    if case == "choose_args":
        cm = build_hierarchy([4, 4, 4])
        cm.choose_args = {
            bid: {"weight_set": [
                [int(rng.integers(1 << 14, 1 << 18)) for _ in b.items]
                for _ in range(2)]}
            for bid, b in cm.buckets.items()
            if bid == ROOT_ID or b.items[0] >= 0}
        return cm, 1, 6, [0x10000] * 64
    cm = build_flat_map(40)
    cm.add_rule(erasure_rule(1, ROOT_ID, choose_type=0, leaf=False))
    weights = [0x10000] * 40
    for osd in rng.choice(40, size=12, replace=False):
        weights[int(osd)] = int(rng.choice([0, 0x8000]))
    return cm, 1, 6, weights


# tree, lanes, RETRY_NARROW (None: the module's own widths, which these
# lane counts are under), whether pairs went to the narrow stage, whether
# a full-width pass ran after the first (None: as the passes happen to
# leave it)
INDEP_RETRY_CASES = {
    # all weights in: pass one leaves ~5 % of the 11,264 pairs
    "cell-narrow": ("indep11", 1024, 16, True, False),
    "cell-wide": ("indep11", 1024, 128, None, True),
    "cell-below_threshold": ("indep11", 1024, None, False, False),
    "third_out-narrow": ("third_out", 1024, 8, True, False),
    "third_out-wide": ("third_out", 1024, 64, None, True),
    "third_out-below_threshold": ("third_out", 1024, None, False, False),
    # the pairs that no host is left for run out of tries inside the
    # narrow loop
    "short-narrow": ("short", 128, 2, True, None),
    "choose_args-narrow": ("choose_args", 512, 4, True, False),
    "plain_choose-narrow": ("plain_choose", 512, 2, True, False),
}


@pytest.mark.parametrize("case", list(INDEP_RETRY_CASES))
def test_indep_retry_at_the_width_of_what_is_left(case, monkeypatch):
    """crush_indep over a launch long enough to finish the (lane, slot)
    pairs its full-width passes leave in a narrow loop: id for id the
    scalar mapper's result (and the C oracle's, where it is built and
    knows the map) whichever width ran, holes at their positions, the
    mapper's totals saying which did."""
    import ceph_tpu.crush.vectorized as V
    from ceph_tpu.native import available, crush_oracle_do_rule

    tree, lanes, narrow, narrow_ran, wide_ran = INDEP_RETRY_CASES[case]
    if narrow is not None:
        monkeypatch.setattr(V, "RETRY_MIN_LANES", lanes)
        monkeypatch.setattr(V, "RETRY_NARROW", narrow)
    cm, ruleno, numrep, weights = indep_tree(tree)
    xs = cell_pps(lanes, 2)
    vc = VectorCrush(cm, ruleno)
    got = vc.map_pgs(xs, numrep, weights)
    want = scalar_batch(cm, ruleno, xs, numrep, weights)
    assert np.array_equal(got, want)
    if available() and tree != "choose_args":
        # the oracle takes no choose_args
        for lane, x in enumerate(xs):
            assert crush_oracle_do_rule(cm, ruleno, int(x), numrep,
                                        weights) == list(want[lane]), lane
    totals = vc.totals()
    assert totals["fused_launches"] == 1 and totals["indep_passes"] >= 2
    if narrow_ran is not None:
        assert (totals["indep_retry_pairs"] > 0) == narrow_ran, totals
    if wide_ran is not None:
        assert (totals["wide_retries"] > 0) == wide_ran, totals
    if tree == "short":
        # nine hosts: two holes a lane, wherever the collisions fell,
        # and every try was made for them
        assert ((got == CRUSH_ITEM_NONE).sum(axis=1) == 2).all()
        assert len({tuple(row) for row in got == CRUSH_ITEM_NONE}) > 8
        assert totals["indep_passes"] == 100


def test_indep_resolves_a_pass_in_slot_order(monkeypatch):
    """A lane in which two slots b < a are both undefined after pass
    one and draw the same host in pass two: b takes it, so a's
    candidate collides with what was placed in the same pass and a
    waits for pass three.  The lanes are found with the scalar mapper
    alone (the rule cut to one and to two tries, the candidates by its
    own bucket choice); the narrow stage, which draws both candidates
    at once, must resolve them in slot order."""
    import copy

    import ceph_tpu.crush.vectorized as V
    from ceph_tpu.crush.mapper import CrushWork, _crush_bucket_choose
    from ceph_tpu.crush.types import CRUSH_RULE_SET_CHOOSE_TRIES

    cm, ruleno, numrep, weights = cell_tree("indep11")
    host_of = {osd: b.id for b in cm.buckets.values() if b.items[0] >= 0
               for osd in b.items}
    work = CrushWork(cm)

    def candidate(x: int, r: int) -> int:
        """The host the descent from the root draws for (x, r)."""
        item = -1
        while item not in host_of.values():
            bucket = cm.buckets[item]
            item = _crush_bucket_choose(bucket, work.work[bucket.id], x, r)
        return item

    def with_tries(n: int):
        m = copy.deepcopy(cm)
        for step in m.rules[ruleno].steps:
            if step.op == CRUSH_RULE_SET_CHOOSE_TRIES:
                step.arg1 = n
        return m

    xs = cell_pps(4096, 2)[576:704]
    one, two = (scalar_batch(with_tries(n), ruleno, xs, numrep, weights)
                for n in (1, 2))
    same_pass = []
    for lane, x in enumerate(xs):
        holes = np.flatnonzero(one[lane] == CRUSH_ITEM_NONE)
        for i, b in enumerate(holes):
            for a in holes[i + 1:]:
                host = candidate(int(x), int(b) + numrep)
                if (host == candidate(int(x), int(a) + numrep)
                        and host_of.get(int(two[lane, b])) == host):
                    same_pass.append((lane, int(b), int(a)))
    assert same_pass, "no lane here tests the order"
    for lane, _, a in same_pass:
        assert two[lane, a] == CRUSH_ITEM_NONE

    monkeypatch.setattr(V, "RETRY_MIN_LANES", len(xs))
    monkeypatch.setattr(V, "RETRY_NARROW", 4)
    vc = VectorCrush(with_tries(2), ruleno)
    assert np.array_equal(vc.map_pgs(xs, numrep, weights), two)
    # pass two was the narrow stage's
    assert vc.indep_retry_pairs > 0 and vc.wide_retries == 0
    got = VectorCrush(cm, ruleno).map_pgs(xs, numrep, weights)
    assert np.array_equal(got, scalar_batch(cm, ruleno, xs, numrep, weights))
    for lane, b, a in same_pass:
        assert got[lane, b] == two[lane, b]
        assert host_of[int(got[lane, a])] != host_of[int(got[lane, b])]
