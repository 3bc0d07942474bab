"""The plain references under benchmark/reference import nothing of the
program; here they are held against the program's own host code, so a
copy that drifted from the semantics shows at once."""

from __future__ import annotations

import numpy as np
import pytest

import bm_toy  # noqa: F401
from benchmark.reference import crush, ec


@pytest.mark.parametrize("k,m,technique", [
    (8, 3, "reed_sol_van"), (10, 4, "cauchy"), (2, 1, "reed_sol_van")])
def test_generator_rows_match_the_plugins(k, m, technique):
    from ceph_tpu.ec import registry
    codec = registry().factory("tpu", {"k": str(k), "m": str(m),
                                       "technique": technique})
    assert np.array_equal(codec.encode_matrix[k:],
                          ec.coding_matrix(technique, k, m))


def test_gf_product_and_crc_match_the_programs_host_code():
    from ceph_tpu.gf import gf_matmul
    from ceph_tpu.ops.crc32c_batch import crc32c_batch
    rng = np.random.default_rng(3)
    data = rng.integers(0, 256, (10, 3000), dtype=np.uint8)
    mat = ec.coding_matrix("cauchy", 10, 4)
    assert np.array_equal(ec.gf_matmul(mat, data), gf_matmul(mat, data))
    blob = rng.bytes(5000)
    assert ec.ceph_crc32c(blob) == int(crc32c_batch([blob])[0])
    assert ec.ceph_crc32c(b"123456789") == 0xE3069283 ^ 0xFFFFFFFF


def test_shards_pad_the_ragged_last_stripe_row():
    profile = {"k": 10, "m": 4, "stripe_unit": 4096,
               "technique": "cauchy"}
    payload = np.random.default_rng(4).bytes(40960 * 2 + 100)
    shards = ec.shards_of(profile, payload)
    assert len(shards) == 14 and {len(s) for s in shards} == {3 * 4096}
    data = b"".join(
        shards[i][r * 4096:(r + 1) * 4096]
        for r in range(3) for i in range(10))
    assert data[:len(payload)] == payload and not any(data[len(payload):])


@pytest.mark.parametrize("fanouts", [[5, 5, 4, 10], [2, 3, 4]])
@pytest.mark.parametrize("some_out", [False, True])
def test_reference_mapper_equals_scalar_crush_do_rule(fanouts, some_out):
    from ceph_tpu.crush import crush_do_rule
    from ceph_tpu.crush.builder import build_hierarchy
    tree = crush.UniformTree(fanouts, 0x10000)
    cm = build_hierarchy(fanouts)
    for b in tree.buckets():
        have = cm.buckets[b["id"]]
        assert (have.items, have.item_weights, have.type) == (
            b["items"], b["item_weights"], b["type"])
    rng = np.random.default_rng(5)
    weights = [0x10000] * tree.n_osds
    if some_out:
        for i in rng.choice(tree.n_osds, tree.n_osds // 3, replace=False):
            weights[i] = int(rng.integers(0, 0x10000))
    xs = rng.integers(0, 2**31 - 1, size=300)
    got = tree.map_pgs(xs, 3, weights)
    for x, row in zip(xs, got):
        want = crush_do_rule(cm, 0, int(x), 3, weights)
        want += [crush.ITEM_NONE] * (3 - len(want))
        assert list(row) == want


def test_crush_ln_matches_the_programs_table_walk():
    from ceph_tpu.crush.ln import crush_ln
    for u in range(0, 1 << 16, 13):
        assert crush.crush_ln(u) == crush_ln(u)
