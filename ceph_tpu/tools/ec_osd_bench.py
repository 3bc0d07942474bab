"""OSD-path EC benchmark: concurrent client writes through a cluster.

The raw-codec bench (bench.py headline) measures the kernel; this one
measures the SYSTEM: a vstart-style in-process cluster (mon + N OSDs),
an erasure-coded pool on the `tpu` profile, and many concurrent client
writes — the shape where per-op codec dispatch used to pay one launch
per write and the CodecBatcher now coalesces stripes across ops and
PGs into shared ``encode_batch`` launches.

Reports achieved client throughput AND batch occupancy (stripes per
launch, pad waste, flush reasons) from the per-OSD "ec_batch" perf
counters, so a round's BENCH artifact shows what batch sizes the data
path actually reached — not just what the kernel could do.

    python -m ceph_tpu.tools.ec_osd_bench --objects 64 --obj-kib 64
    python bench.py --osd-path          # same engine, bench JSON shape
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time

from ..common.compile_cache import enable_compile_cache


async def run_osd_path_bench(*, n_osds: int = 3, k: int = 2, m: int = 1,
                             n_objects: int = 48,
                             obj_bytes: int = 64 * 1024,
                             concurrency: int = 16,
                             pg_num: int = 8,
                             batch_max: int = 64,
                             batch_timeout: float = 0.002,
                             rounds: int = 2) -> dict:
    """Drive N concurrent EC writes; return throughput + occupancy.

    The report carries the per-OSD mesh occupancy: device launches
    per coalesced batch (the exactly-one gate), devices in the mesh,
    and padded stripes per device per launch (the sharding factor)."""
    import numpy as np
    from ..client.rados import Rados
    from ..mon import Monitor
    from ..osd import OSD

    mon = Monitor(rank=0, config={"mon_osd_min_down_reporters": 1})
    addr = await mon.start()
    osds = []
    for i in range(n_osds):
        cfg = {
            "osd_ec_batch_max": batch_max,
            "osd_ec_batch_timeout": batch_timeout,
        }
        osd = OSD(host=f"host{i}", config=cfg)
        await osd.start(addr)
        osds.append(osd)
    rados = await Rados(addr).connect()
    try:
        await rados.mon_command(
            "osd erasure-code-profile set",
            {"name": "bench", "profile": {
                "plugin": "tpu", "k": str(k), "m": str(m),
                "technique": "reed_sol_van"}})
        await rados.mon_command(
            "osd pool create",
            {"name": "ecbench", "type": "erasure", "pg_num": pg_num,
             "erasure_code_profile": "bench"})
        io = await rados.open_ioctx("ecbench")
        rng = np.random.default_rng(0)
        payloads = [rng.integers(0, 256, obj_bytes,
                                 dtype=np.uint8).tobytes()
                    for _ in range(min(8, n_objects))]
        sem = asyncio.Semaphore(concurrency)

        async def one(i: int) -> None:
            async with sem:
                await io.write_full(f"obj-{i}",
                                    payloads[i % len(payloads)])

        # warm round: peering settles, codecs compile, caches fill
        await asyncio.gather(*(one(i) for i in range(n_objects)))
        t0 = time.perf_counter()
        for _ in range(rounds):
            await asyncio.gather(*(one(i) for i in range(n_objects)))
        dt = time.perf_counter() - t0
        total_bytes = rounds * n_objects * obj_bytes

        # roll up batch occupancy over every OSD's aggregation stage
        batches = stripes = pad = fallback = 0
        mesh_launches = mesh_padded = 0
        n_devices = 0
        flush: dict[str, int] = {}
        for osd in osds:
            dump = osd.perf.dump().get("ec_batch", {})
            batches += dump.get("batches", 0)
            stripes += dump.get("stripes", 0)
            pad += dump.get("pad_waste_bytes", 0)
            fallback += dump.get("fallback_ops", 0)
            mesh_launches += dump.get("mesh_launches", 0)
            mesh_padded += dump.get("mesh_padded_stripes", 0)
            n_devices = max(n_devices,
                            int(dump.get("mesh_devices", 0)))
        for osd in osds:
            dump = osd.perf.dump().get("ec_batch", {})
            for key, v in dump.items():
                if key.startswith("flush_"):
                    flush[key] = flush.get(key, 0) + v
        # the write-pipeline set (PR 12): staged launches, overlap
        # windows, stalls, deferred-commit overlap, coalesced flushes
        pipeline: dict[str, int] = {}
        for osd in osds:
            for key, v in osd.perf.dump().get("ec_pipeline",
                                              {}).items():
                if isinstance(v, (int, float)):
                    pipeline[key] = pipeline.get(key, 0) + v
        mesh_report = {
            "launches": mesh_launches,
            "launches_per_batch": round(mesh_launches / batches, 3)
            if batches else 0.0,
            "n_devices": n_devices,
            "per_device_stripes": round(
                mesh_padded / mesh_launches / n_devices, 2)
            if mesh_launches and n_devices else 0.0,
        }
        return {
            "osd_path_GiBps": round(total_bytes / dt / 2**30, 3),
            "writes_per_s": round(rounds * n_objects / dt, 1),
            "stripes_per_launch": round(stripes / batches, 2)
            if batches else 0.0,
            "batches": batches,
            "stripes": stripes,
            "pad_waste_bytes": pad,
            "fallback_ops": fallback,
            "mesh": mesh_report,
            "ec_pipeline": pipeline,
            "flush_reasons": flush,
            "n_osds": n_osds, "k": k, "m": m,
            "objects": n_objects, "obj_bytes": obj_bytes,
            "concurrency": concurrency, "rounds": rounds,
        }
    finally:
        await rados.shutdown()
        for osd in osds:
            await osd.stop()
        await mon.stop()


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="ec_osd_bench")
    p.add_argument("--osds", type=int, default=3)
    p.add_argument("--k", type=int, default=2)
    p.add_argument("--m", type=int, default=1)
    p.add_argument("--objects", type=int, default=48)
    p.add_argument("--obj-kib", type=int, default=64)
    p.add_argument("--concurrency", type=int, default=16)
    p.add_argument("--pg-num", type=int, default=8)
    p.add_argument("--batch-max", type=int, default=64)
    p.add_argument("--rounds", type=int, default=2)
    args = p.parse_args(argv)
    enable_compile_cache()
    res = asyncio.run(run_osd_path_bench(
        n_osds=args.osds, k=args.k, m=args.m, n_objects=args.objects,
        obj_bytes=args.obj_kib * 1024, concurrency=args.concurrency,
        pg_num=args.pg_num, batch_max=args.batch_max,
        rounds=args.rounds))
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
