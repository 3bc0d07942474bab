"""Bench harness regressions.

* importing ceph_tpu must not flip process-global JAX precision
  (jax_enable_x64 stays scoped to the fused CRUSH entry points);
* every bench mode's --smoke is a tier-1 tripwire, run against THIS
  checkout (the tree under test, wherever it is unpacked);
* a config that raises makes bench.py exit non-zero.
"""

import importlib
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _bench():
    sys.path.insert(0, REPO)
    import bench
    return importlib.reload(bench)


def test_import_does_not_flip_global_x64():
    import jax
    import ceph_tpu.crush.vectorized  # noqa: F401 -- the old offender
    assert jax.config.jax_enable_x64 is False


def test_integrity_smoke_exits_zero_with_parity_and_counters():
    """bench.py --integrity --smoke is the tier-1 tripwire for the
    batched CRC pipeline: every backend must match the scalar oracle,
    and the codec-batcher + deep-scrub proof paths must record ZERO
    scalar CRC calls."""
    import json
    import os
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "bench.py", "--integrity", "--smoke"],
        capture_output=True, text=True, cwd=REPO, env=env,
        timeout=240)
    assert r.returncode == 0, r.stderr[-2000:]
    res = json.loads(r.stdout.strip().splitlines()[-1])
    assert res["metric"] == "integrity_crc32c_batched_GiBps"
    assert res["scalar_calls_on_batched_paths"] == 0
    assert res["value"] > 0
    assert res["fused_launches"] >= 1


def test_osd_path_mesh_smoke_gates_hold():
    """bench.py --osd-path --mesh --smoke is the tier-1 tripwire for
    the sharded data plane: under 8 forced host devices the mesh
    parity must match the scalar oracle, EXACTLY ONE device launch
    must serve each coalesced batch (unit drive AND the in-process
    cluster), and zero scalar CRC calls may appear on the mesh path."""
    import json
    import os
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    r = subprocess.run(
        [sys.executable, "bench.py", "--osd-path", "--mesh",
         "--smoke"],
        capture_output=True, text=True, cwd=REPO, env=env,
        timeout=240)
    assert r.returncode == 0, r.stderr[-2000:]
    res = json.loads(r.stdout.strip().splitlines()[-1])
    assert res["metric"] == "ec_osd_path_write_GiBps"
    assert res["value"] > 0
    gates = res["mesh_gates"]
    assert gates["parity"] == "ok"
    assert gates["n_devices"] == 8
    assert gates["launches_per_batch"] == 1.0
    assert gates["scalar_calls_on_batched_paths"] == 0
    cluster = res["mesh"]
    assert cluster["launches"] >= 1
    assert cluster["launches_per_batch"] == 1.0
    assert cluster["n_devices"] == 8
    # the XOR-schedule rows: >=30% term reduction on the Cauchy
    # k=8,m=3 headline matrix, a CPU wall-clock win on the bitmatrix
    # host row
    xs = res["xor_schedule"]
    assert xs["reduction_pct"] >= 30.0
    assert xs["sched_xor_terms"] < xs["naive_xor_terms"]
    assert xs["bitmatrix_host"]["speedup"] > 1.0
    assert xs["batched_xla"]["speedup"] > 1.0


def test_datapath_smoke_gates_hold():
    """bench.py --datapath --smoke is the tier-1 tripwire for the
    device-resident shard data path: cached and host-round-trip drives
    must be byte-identical, the cached steady phases (read-verify /
    scrub / degraded-read) must hit the cache and move ZERO shard
    bytes through the store, and no scalar CRC call may appear on the
    batched paths."""
    import json
    import os
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "bench.py", "--datapath", "--smoke"],
        capture_output=True, text=True, cwd=REPO, env=env,
        timeout=240)
    assert r.returncode == 0, r.stderr[-2000:]
    res = json.loads(r.stdout.strip().splitlines()[-1])
    assert res["metric"] == "datapath_write_scrub_degraded_GiBps"
    assert res["parity"] == "ok"
    assert res["value"] > 0
    assert res["cache_hits"] > 0
    assert res["steady_host_bytes_read"] == 0
    assert res["steady_host_reads"] == 0
    assert res["scalar_calls_on_batched_paths"] == 0
    assert res["host_bytes_avoided"] > 0
    # the cached spine must beat the host round trip even at smoke
    # scale (the >=5x acceptance bar applies to the full artifact)
    assert res["vs_baseline"] > 1.0


def test_cluster_smoke_exits_zero_with_no_failed_ops():
    """bench.py --cluster --smoke is the tier-1 tripwire for the
    traffic harness: a small deterministic swarm + OSD kill/revive
    must complete with ZERO failed/wedged client ops, non-degenerate
    latency (p50 <= p99), interference phases that actually saw the
    kill, and dmClock client dispatches recorded."""
    import json
    import os
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "bench.py", "--cluster", "--smoke"],
        capture_output=True, text=True, cwd=REPO, env=env,
        timeout=240)
    assert r.returncode == 0, r.stderr[-2000:]
    res = json.loads(r.stdout.strip().splitlines()[-1])
    assert res["metric"] == "cluster_steady_client_ops_per_s"
    assert res["value"] > 0
    assert res["failed_ops"] == 0 and res["wedged_ops"] == 0
    for kind in ("read", "write", "rmw"):
        lat = res["latency"][kind]
        assert lat["count"] > 0
        assert lat["p50_s"] <= lat["p99_s"] <= lat["max_s"]
    assert res["interference"]["down_detected"]
    assert res["interference"]["revived"]
    assert res["qos"]["steady"]["dispatched_client"] > 0
    assert res["p99_degradation"]["degraded"]
    # the pipelined write spine's overlap counters are LIVE (PR 12):
    # batches staged ahead of the in-flight launch, commits awaited
    # outside the PG lock, sub-op flush windows shipped
    pipe = res["counters"]["ec_pipeline"]
    assert pipe["staged_batches"] > 0
    assert pipe["overlapped_commits"] > 0
    assert pipe["commit_overlap_ms"] > 0
    assert pipe["flush_windows"] > 0


def test_straggler_smoke_gates_hold():
    """bench.py --straggler --smoke is the tier-1 tripwire for the
    hedged-read engine: under an identical seeded heavy-tail delay
    schedule the hedged variant's p99 must beat the unhedged fixed
    gather by >= 2x with <= 1.5x extra sub-reads, zero failed/wedged
    ops, zero leaked sub-read tasks, hedges actually fired AND won,
    and first-k decode byte-identical to the written ground truth in
    both variants (the unhedged full-set gather is the oracle)."""
    import json
    import os
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "bench.py", "--straggler", "--smoke"],
        capture_output=True, text=True, cwd=REPO, env=env,
        timeout=240)
    assert r.returncode == 0, r.stderr[-2000:]
    res = json.loads(r.stdout.strip().splitlines()[-1])
    assert res["metric"] == \
        "straggler_read_p99_speedup_hedged_vs_unhedged"
    assert res["value"] >= 2.0
    assert 0 < res["extra_subread_ratio"] <= 1.5
    assert res["failed_ops"] == 0 and res["wedged_ops"] == 0
    assert res["leaked_tasks"] == 0
    assert res["byte_mismatches"] == []
    assert res["hedged"]["hedges_fired"] > 0
    assert res["hedged"]["hedges_won"] > 0
    # the straggler schedule is deterministic and identical per
    # variant: both drives saw the same number of injected delays
    assert res["hedged"]["straggler_delays"] == \
        res["unhedged"]["straggler_delays"]
    # hedging never engaged the retry ladder
    assert res["hedged"]["gather_retries"] == 0


def test_recovery_smoke_gates_hold():
    """bench.py --recovery --smoke is the tier-1 tripwire for the
    recovery-bandwidth-optimal codes: the same kill/recover drive on
    RS vs LRC vs PMSR pools must converge byte-correct with zero
    failed objects, LRC single-failure repair must read <= 0.5x the
    RS bytes through the local group, and PMSR must take the
    fragment path with helper traffic under k full chunks -- all via
    the ec_recovery counters, never assumed."""
    import json
    import os
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "bench.py", "--recovery", "--smoke"],
        capture_output=True, text=True, cwd=REPO, env=env,
        timeout=240)
    assert r.returncode == 0, r.stderr[-2000:]
    res = json.loads(r.stdout.strip().splitlines()[-1])
    assert res["metric"] == "recovery_repair_read_ratio_lrc_vs_rs"
    assert 0 < res["value"] <= 0.5
    assert res["failed_objects"] == 0 and res["errors"] == 0
    codes = res["codes"]
    for name, c in codes.items():
        assert c["recovered_clean"], name
        assert c["repair_bytes_shipped"] > 0, name
        assert c["mismatched"] == [], name
    # RS reads k full chunks per rebuilt shard; LRC the local group;
    # PMSR d beta-fragments (d/alpha chunks, strictly under k)
    assert codes["rs"]["read_per_shipped"] == codes["rs"]["k"]
    assert codes["lrc"]["read_per_shipped"] <= codes["lrc"]["l"] + 1
    assert codes["lrc"]["repair_local_repairs"] > 0
    assert 0 < codes["pmsr"]["read_per_shipped"] < codes["pmsr"]["k"]
    assert codes["pmsr"]["repair_fragment_pulls"] > 0


def test_placement_smoke_exits_zero_with_fused_parity():
    """bench.py --placement --smoke is the tier-1 tripwire for
    fused/scalar placement divergence: it forces the fused path on a
    toy map, asserts entry parity against the scalar oracle, and must
    emit its JSON line and exit 0."""
    import json
    import os
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "bench.py", "--placement", "--smoke"],
        capture_output=True, text=True, cwd=REPO, env=env,
        timeout=240)
    assert r.returncode == 0, r.stderr[-2000:]
    res = json.loads(r.stdout.strip().splitlines()[-1])
    assert res["metric"] == "placement_epoch_recompute_pgs_per_s"
    assert res["fused_path"] is True
    assert res["value"] > 0


def test_bench_exits_nonzero_when_a_config_raises(monkeypatch, capsys):
    """A failing config fails the run: the JSON line carries the error
    and the exit code is non-zero -- it is not logged and dropped from
    an otherwise healthy-looking result."""
    import json
    bench = _bench()
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    monkeypatch.setattr(bench, "_headline", lambda rng: {
        "encode_GiBps": 1.0, "decode_GiBps": 1.0, "batch": 8,
        "stripe_bytes": 1 << 20})
    monkeypatch.setattr(bench, "_marshal_4k", lambda rng: 1.0)
    monkeypatch.setattr(bench, "_crush_batch", lambda: 1.0)

    def boom(rng):
        raise RuntimeError("cauchy config on fire")
    monkeypatch.setattr(bench, "_cauchy_decode", boom)
    assert bench.run() == 1
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert "cauchy config on fire" in res["error"]
    assert res["value"] == 0.0

    # and the same run with no failing config exits 0
    bench = _bench()
    monkeypatch.setattr(bench, "_headline", lambda rng: {
        "encode_GiBps": 1.0, "decode_GiBps": 1.0, "batch": 8,
        "stripe_bytes": 1 << 20})
    for name in ("_cauchy_decode", "_marshal_4k"):
        monkeypatch.setattr(bench, name, lambda rng: 1.0)
    monkeypatch.setattr(bench, "_crush_batch", lambda: 1.0)
    assert bench.run() == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert "error" not in res and res["value"] == 1.0


def test_crush_bench_cli_times_verified_launches():
    """python -m ceph_tpu.tools.crush_bench (BASELINE config 5's own
    entry point) runs end to end: exit 0, a mapping rate, and the
    verified lanes sampled from the timed launches."""
    import json
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-m", "ceph_tpu.tools.crush_bench",
         "--pgs", "20000", "--batch", "10000", "--verify", "16"],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=240)
    assert r.returncode == 0, r.stderr[-2000:]
    res = json.loads(r.stdout.strip().splitlines()[-1])
    assert res["metric"] == "crush_bulk_mappings_per_s"
    assert res["value"] > 0
    assert res["n_mappings"] == 20000 and res["launches"] == 2
    assert res["verified_lanes"] == 16
    assert res["lane_exact_vs_scalar"] is True
