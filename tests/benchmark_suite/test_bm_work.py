"""benchmark/work.py and peaks.json: bytes per encode launch and the
roofline arithmetic, at the two pools' shapes."""

from __future__ import annotations

import json

import pytest

import bm_toy  # noqa: F401
from benchmark import harness, work
from benchmark.readers import ratio, roofline

MIB4 = 4 << 20


@pytest.mark.parametrize("k,m,rows,padded_to,bytes_needed", [
    (8, 3, 128, 128, 128 * 11 * 4096),        # 4 MiB / 32 KiB, exact
    (10, 4, 103, 128, 103 * 14 * 4096),       # 102.4 rows: ragged 103rd
])
def test_encode_bytes_of_a_4mib_object(k, m, rows, padded_to, bytes_needed):
    from benchmark.drivers.store_closed_loop import launch_buckets
    assert work.stripes_per_object(k, 4096, MIB4) == rows
    assert work.encode_bytes(k, m, 4096, rows) == bytes_needed
    profile = {"k": k, "m": m, "stripe_unit": 4096}
    assert launch_buckets(profile, MIB4, 64) == [padded_to]


def test_small_objects_reach_every_bucket_up_to_the_flush_size():
    from benchmark.drivers.store_closed_loop import launch_buckets
    profile = {"k": 8, "m": 3, "stripe_unit": 4096}
    assert work.stripes_per_object(8, 4096, 65536) == 2
    assert launch_buckets(profile, 65536, 64) == [2, 4, 8, 16, 32, 64]


def test_roofline_share_is_least_time_over_measured_time():
    # 819 MB at 819 GB/s is 1 ms; measured 10 ms -> 10 %
    assert work.roofline_share(819e6, 819e9, 0.010) == pytest.approx(10.0)


def test_peaks_name_their_source_and_unknown_kind_is_an_error():
    peaks = json.loads((harness.BENCH / "peaks.json").read_text())
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
    assert all(p["source"] for p in peaks.values())
    facts = {"trace.programs": {"jit_fn": 0.5}, "slice.stripes": 1280,
             "config.profile.k": 8, "config.profile.m": 3,
             "config.profile.stripe_unit": 4096, "device.kind": "TPU v9"}
    spec = {"stripes": "slice.stripes", "programs": "^jit_"}
    with pytest.raises(harness.HarnessError):
        roofline.read(spec, facts)
    facts["device.kind"] = "TPU v5 lite"
    want = 100 * (1280 * 11 * 4096 / 819e9) / 0.5
    assert roofline.read(spec, facts) == pytest.approx(want)
    assert roofline.read(spec, dict(facts, **{"trace.programs": {}})) is None


def test_ratio_reader_leaves_out_what_it_cannot_read():
    spec = {"num": ["a", "b"], "den": ["c"], "scale": 10.0}
    assert ratio.read(spec, {"a": 2, "b": 3, "c": 4}) == pytest.approx(15.0)
    assert ratio.read(spec, {"a": 2, "b": 3}) is None
    assert ratio.read(spec, {"a": 2, "b": 3, "c": 0}) is None


def test_percentile_is_nearest_rank():
    vals = list(range(1, 101))
    assert harness.percentile(vals, 95) == 95
    assert harness.percentile(vals, 50) == 50
    assert harness.percentile([7.0], 95) == 7.0
    with pytest.raises(ValueError):
        harness.percentile([], 95)
