"""The mapper's bucket tables are operands of its device program: maps
of one structure share one executable whatever their weights, a change
of structure compiles once, and every result is the scalar mapper's,
lane for lane.
"""

from __future__ import annotations

import jax
import numpy as np
import pytest
from jax import monitoring

import ceph_tpu.crush.vectorized as V
from ceph_tpu.crush.builder import build_hierarchy, crush_command
from ceph_tpu.crush.mapper import crush_do_rule
from ceph_tpu.crush.types import CRUSH_ITEM_NONE
from ceph_tpu.crush.vectorized import Structure, VectorCrush

W = 0x10000
_compiles = [0]
monitoring.register_event_duration_secs_listener(
    lambda event, secs, **_: _compiles.__setitem__(0, _compiles[0] + 1)
    if event == "/jax/core/compile/backend_compile_duration" else None)


def tree(fanouts):
    """A uniform tree with every bucket named ``b<id>``."""
    cm = build_hierarchy(fanouts)
    cm.bucket_names = {bid: f"b{bid}" for bid in cm.buckets}
    return cm


def scalar(cm, ruleno, xs, numrep, weights):
    out = np.full((len(xs), numrep), CRUSH_ITEM_NONE, np.int64)
    for i, x in enumerate(xs):
        got = crush_do_rule(cm, ruleno, int(x), numrep, weights)[:numrep]
        out[i, :len(got)] = got
    return out


def weight_steps(cm, n: int):
    """``n`` maps of one structure: a subtree raised step by step from
    0, one device reweighted differently in each."""
    for step in range(n):
        m = crush_command(cm, "osd crush reweight-subtree",
                          {"name": "b-2", "weight": step / (n - 1)})
        yield crush_command(m, "osd crush reweight",
                            {"name": f"osd.{20 + step}",
                             "weight": 0.3 + 0.1 * step})


@pytest.mark.parametrize("ruleno,numrep", [(0, 3), (1, 6)],
                         ids=["firstn", "indep"])
def test_weight_steps_compile_nothing_after_the_first(ruleno, numrep):
    """N weight vectors on one structure: 0 backend compiles and 0
    programs traced after the first map's launch, each lane-exact."""
    cm = tree([3, 4, 3])                 # 36 osds; no other test's shape
    weights = [W] * 36
    weights[7], weights[30] = 0, 0x6000
    xs = np.arange(0, 1200 * 13, 13)
    built, compiles = [], []
    for m in weight_steps(cm, 5):
        before = _compiles[0]
        vc = VectorCrush(m, ruleno)
        got = vc.map_pgs(xs, numrep, weights)
        built.append(vc.programs_built)
        compiles.append(_compiles[0] - before)
        assert np.array_equal(got, scalar(m, ruleno, xs, numrep, weights))
    assert built == [1, 0, 0, 0, 0]
    assert compiles[0] >= 1 and compiles[1:] == [0, 0, 0, 0]


def test_a_change_of_structure_compiles_once():
    cm = tree([2, 5, 3])                 # 30 osds
    xs = np.arange(800)
    weights = [W] * 31
    VectorCrush(cm, 0).map_pgs(xs, 3, weights)
    grown = crush_command(cm, "osd crush add", {
        "name": "osd.30", "weight": 1.0, "loc": {"host": "b-3"}})
    first = VectorCrush(grown, 0)
    got = first.map_pgs(xs, 3, weights)
    assert first.programs_built == 1
    assert np.array_equal(got, scalar(grown, 0, xs, 3, weights))
    again = VectorCrush(crush_command(grown, "osd crush reweight", {
        "name": "osd.30", "weight": 0.5}), 0)
    before = _compiles[0]
    again.map_pgs(xs, 3, weights)
    assert again.programs_built == 0 and _compiles[0] == before


def test_the_mapper_flattens_to_its_tables_and_its_structure():
    cm = tree([3, 4])
    vc = VectorCrush(cm, 1)
    leaves, treedef = jax.tree_util.tree_flatten(vc)
    assert len(leaves) == vc.structure.n_levels == 2
    assert all(isinstance(x, jax.Array) and x.dtype == np.int32
               for x in leaves)
    # (4 * items, positions, buckets): ids, weights, recip bits, children
    assert [x.shape for x in leaves] == [(12, 1, 1), (16, 1, 3)]
    assert vc.structure == Structure(
        n_levels=2, firstn=False, leaf=True, choose_tries=100,
        recurse_tries=5, retry_min_lanes=V.RETRY_MIN_LANES,
        retry_narrow=V.RETRY_NARROW)
    back = jax.tree_util.tree_unflatten(treedef, leaves)
    assert back.structure == vc.structure and not hasattr(back, "cm")
    # two maps of one structure: one treedef, so one key for jit
    other = VectorCrush(crush_command(cm, "osd crush reweight", {
        "name": "osd.3", "weight": 0.5}), 1)
    assert jax.tree_util.tree_structure(other) == treedef
    assert not np.array_equal(np.asarray(other.tables[1]),
                              np.asarray(vc.tables[1]))
    # another rule of the same map is another program
    assert jax.tree_util.tree_structure(VectorCrush(cm, 0)) != treedef


def test_the_weights_reach_the_program_as_arguments_only():
    """The lowered program of one map has the tables as parameters of
    their shapes and no constant of a table's size."""
    cm = tree([3, 4])
    vc = VectorCrush(cm, 0)
    text = vc.crush_firstn.lower(
        vc, jax.numpy.arange(64, dtype=jax.numpy.int32), 2,
        jax.numpy.full((12,), W, jax.numpy.int32)).as_text()
    assert "tensor<12x1x1xi32>" in text and "tensor<16x1x3xi32>" in text
    assert "dense<[[[" not in text.split("func.func public @main")[1] \
        .split("{", 1)[0]
    # a bucket's 16.16 weights (65536, 262144) are in no constant
    assert "262144" not in text


@pytest.mark.parametrize("ruleno", [0, 1], ids=["firstn", "indep"])
def test_retry_constants_are_part_of_the_programs_key(ruleno, monkeypatch):
    cm = tree([3, 4])
    wide = VectorCrush(cm, ruleno).structure
    monkeypatch.setattr(V, "RETRY_MIN_LANES", 64)
    monkeypatch.setattr(V, "RETRY_NARROW", 4)
    narrow = VectorCrush(cm, ruleno).structure
    assert (narrow.retry_min_lanes, narrow.retry_narrow) == (64, 4)
    assert narrow != wide and narrow._replace(
        retry_min_lanes=wide.retry_min_lanes,
        retry_narrow=wide.retry_narrow) == wide


def test_totals_count_indep_passes_and_leave_programs_built_apart():
    cm = tree([3, 4])
    vc = VectorCrush(cm, 1)
    vc.map_pgs(np.arange(300), 3, [W] * 12)
    first = vc.totals()
    assert set(first) == {"fused_launches", "retry_lanes", "wide_retries",
                          "indep_passes", "indep_retry_pairs"}
    assert first["fused_launches"] == 1 and first["indep_passes"] >= 1
    assert first["retry_lanes"] == first["wide_retries"] == 0
    # 300 lanes are under the threshold: one loop, no narrow stage
    assert first["indep_retry_pairs"] == 0
    vc.map_pgs(np.arange(300), 3, [W] * 12)
    assert vc.totals() == {k: 2 * v for k, v in first.items()}
    assert vc.programs_built <= 1


def test_two_launches_with_a_narrow_stage_build_one_program(monkeypatch):
    """The erasure rule's program of a launch over the threshold holds
    both widths: maps of one structure launch it whatever their
    weights, and each launch counts the pairs its narrow stage took."""
    monkeypatch.setattr(V, "RETRY_MIN_LANES", 256)
    monkeypatch.setattr(V, "RETRY_NARROW", 2)
    cm = tree([3, 5, 2])                 # 30 osds; no other test's shape
    weights = [W] * 30
    weights[4] = 0
    xs = np.arange(0, 256 * 17, 17)
    built, pairs = [], []
    for m in weight_steps(cm, 2):
        vc = VectorCrush(m, 1)
        got = vc.map_pgs(xs, 6, weights)
        assert np.array_equal(got, scalar(m, 1, xs, 6, weights))
        built.append(vc.programs_built)
        pairs.append(vc.totals()["indep_retry_pairs"])
    assert built == [1, 0]
    assert min(pairs) > 0 and pairs[0] != pairs[1]


@pytest.mark.parametrize("make,reason", [
    (lambda cm: setattr(cm.buckets[-2], "alg", 1), "bucket_alg"),
    (lambda cm: cm.buckets[-2].items.clear(), "empty_bucket"),
    (lambda cm: cm.buckets[-1].items.append(40), "mixed_children"),
    (lambda cm: cm.buckets[-1].items.append(-77), "dangling"),
    (lambda cm: setattr(cm.tunables, "chooseleaf_stable", 0), "tunables"),
    (lambda cm: setattr(cm.rules[0].steps[1], "arg2", 2), "leaf_type"),
])
def test_a_declined_map_says_why_in_one_word(make, reason):
    cm = tree([3, 4])
    make(cm)
    with pytest.raises(V.FusedUnsupported) as e:
        VectorCrush(cm, 0)
    assert e.value.reason == reason and isinstance(e.value, ValueError)
