"""BASELINE config 5: bulk CRUSH placement throughput.

Measures the vectorized straw2 mapper (ceph_tpu/crush/vectorized.py)
computing PG->OSD mappings for a large PG population over a 1000-OSD
two-level (host/osd) crushmap -- the OSDMapMapping / ParallelPGMapper
job (src/osd/OSDMapMapping.h:175) the reference spreads over a thread
pool, here one device launch per batch.  Prints ONE JSON line:

  {"metric": "crush_bulk_mappings_per_s", "value": ..., "unit": "pg/s",
   "n_mappings": ..., "n_osds": ..., "lane_exact_vs_scalar": true}

The lanes checked against the scalar engine are sampled from the
launches that were timed; a mismatch raises (non-zero exit, no number).

Usage: python -m ceph_tpu.tools.crush_bench [--pgs 10000000]
       [--osds 1000] [--replicas 3] [--verify 512]
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

from ..common.compile_cache import enable_compile_cache


def run_crush_bench(pgs: int = 10_000_000, osds: int = 1000,
                    replicas: int = 3, verify: int = 512,
                    batch: int = 2_000_000) -> dict:
    """Map ``pgs`` placement seeds in ``batch``-lane launches through
    ``VectorCrush.map_pgs`` (the entry point the placement cache
    calls, bounded launches included), then
    check ``verify`` lanes SAMPLED FROM THE TIMED LAUNCHES against the
    scalar ``crush_do_rule``.  Raises on any mismatch; returns the
    report dict.  Launch time includes the seed upload and the result
    download (``map_pgs`` hands back numpy)."""
    import jax

    from ..crush import crush_do_rule
    from ..crush.builder import build_hierarchy
    from ..crush.vectorized import VectorCrush

    # depth-4 (root->row->rack->host->osd), the realistic shape the
    # balancer chews on: 5 rows x 5 racks x 4 hosts x 10 osds = 1000
    osds_per_host = 10
    hosts = max(1, osds // osds_per_host)
    racks = max(1, hosts // 4)
    rows = max(1, racks // 5)
    fanouts = [rows, max(1, racks // rows), max(1, hosts // racks),
               osds_per_host]
    cm = build_hierarchy(fanouts)
    n_osds = int(np.prod(fanouts))
    ruleno = 0                       # replicated chooseleaf firstn
    weights = [0x10000] * n_osds
    vc = VectorCrush(cm, ruleno)

    rng = np.random.default_rng(0)
    # pps values as the balancer would feed them (hashed placement seeds)
    xs = rng.integers(0, 2**31 - 1, size=pgs, dtype=np.int64)
    batch = min(batch, pgs)
    n_batches = pgs // batch
    total = batch * n_batches

    t0 = time.perf_counter()
    vc.map_pgs(xs[:batch], replicas, weights)     # compile + warm
    warm_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    outs = [vc.map_pgs(xs[b * batch:(b + 1) * batch], replicas, weights)
            for b in range(n_batches)]
    dt = time.perf_counter() - t0

    got = np.concatenate(outs)
    lanes = rng.choice(total, size=min(verify, total), replace=False)
    for i in lanes:
        want = crush_do_rule(cm, ruleno, int(xs[i]), replicas, weights)
        if list(got[i]) != list(want):
            raise RuntimeError(
                f"crush lane {i} (x={int(xs[i])}): vectorized "
                f"{list(got[i])} != scalar {list(want)}")
    dev = jax.devices()[0]
    return {
        "metric": "crush_bulk_mappings_per_s",
        "value": round(total / dt, 1),
        "unit": "pg/s",
        "n_mappings": total,
        "n_osds": n_osds, "depth": 4,
        "replicas": replicas,
        "batch": batch,
        "launches": n_batches,
        "elapsed_s": round(dt, 3),
        "first_launch_s": round(warm_s, 3),
        "backend": jax.default_backend(),
        "device_kind": dev.device_kind,
        "verified_lanes": len(lanes),
        "lane_exact_vs_scalar": True,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--pgs", type=int, default=10_000_000)
    ap.add_argument("--osds", type=int, default=1000)
    ap.add_argument("--replicas", type=int, default=3)
    ap.add_argument("--verify", type=int, default=512,
                    help="lanes of the timed launches cross-checked "
                         "against the scalar engine")
    ap.add_argument("--batch", type=int, default=2_000_000,
                    help="lanes per device launch")
    args = ap.parse_args(argv)
    enable_compile_cache()
    print(json.dumps(run_crush_bench(
        pgs=args.pgs, osds=args.osds, replicas=args.replicas,
        verify=args.verify, batch=args.batch)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
