"""Closed loop of librados writers against an in-process EC cluster.

Set-up compiles every launch shape the traffic can produce (before any
daemon runs, so no compile ever stalls the cluster's event loop), boots
monitor and OSDs, creates the pool and lets the writers run
``warmup_ops`` operations; the window opens on the same running loop.
A failed operation is counted, never raised.  After the window the
writers drain, a sample of the objects acknowledged inside it is read
back through the client and, shard by shard, out of the OSDs' stores,
and held to the plain reference (reference/ec.py).
"""

from __future__ import annotations

import asyncio
import time

import numpy as np

from benchmark import work
from benchmark.harness import (Trace, counter_delta, elapsed, flatten,
                               percentile, say)
from benchmark.reference import ec

MARKED_DOWN = "marked down"


def object_bytes(seed: int, i: int, size: int) -> bytes:
    return np.random.default_rng([seed, i]).bytes(size)


def launch_buckets(profile: dict, object_size: int, max_batch: int) -> list:
    """Power-of-two launch batches the batcher can reach: an object
    submits all its stripe rows at once, and a group is flushed as soon
    as it holds ``max_batch`` stripes (or earlier, when no writer adds
    to it for one pass of the loop)."""
    rows = work.stripes_per_object(profile["k"], profile["stripe_unit"],
                                   object_size)
    buckets = set()
    t = 1
    while (t - 1) * rows < max_batch:
        buckets.add(1 << max(0, (t * rows - 1).bit_length()))
        t += 1
    return sorted(buckets)


def precompile(profile: dict, buckets: list) -> None:
    """Every (batch, k, stripe_unit) encode with fused checksums, through
    the launch engine the OSDs' batchers share process-wide."""
    from ceph_tpu.ec import registry
    from ceph_tpu.parallel.mesh_codec import MeshCodec

    k, unit = profile["k"], profile["stripe_unit"]
    codec = registry().factory(profile["plugin"], {
        "k": str(k), "m": str(profile["m"]),
        "technique": profile["technique"]})
    mesh = MeshCodec()
    for b in buckets:
        mesh.encode(codec, np.zeros((mesh.pad_batch(b), k, unit), np.uint8),
                    with_crc=True)


def stored_shards(cluster, pgid: str, oid: str, stored_as: dict) -> dict:
    """What the OSDs hold of one object: {shard: (bytes, crc, label)}."""
    found = {}
    for osd in cluster.osds:
        pg = osd.pgs.get(pgid)
        if pg is None or osd.whoami not in pg.acting:
            continue
        try:
            raw = osd.store.read(pg.coll, oid, 0, None)
        except FileNotFoundError:
            continue
        crc, label = (osd.store.getattr(pg.coll, oid, stored_as[name])
                      for name in ("crc_xattr", "shard_xattr"))
        found[pg.acting.index(osd.whoami)] = (
            bytes(raw), None if crc is None else int(crc),
            None if label is None else int(label))
    return found


def check_shards(found: dict, profile: dict, payload: bytes) -> dict:
    """Faults of one object's stored shards against the reference."""
    faults = {"shards_missing": 0, "shard_bytes_wrong": 0,
              "crc_xattr_wrong": 0, "shard_label_wrong": 0}
    for shard, ref in enumerate(ec.shards_of(profile, payload)):
        if shard not in found:
            faults["shards_missing"] += 1
            continue
        raw, crc, label = found[shard]
        faults["shard_bytes_wrong"] += raw != ref
        faults["crc_xattr_wrong"] += crc != ec.ceph_crc32c(raw)
        faults["shard_label_wrong"] += label != shard
    return faults


async def _measure(cell, seed: int, seconds: float, traced: bool,
                   meter) -> dict:
    from ceph_tpu.client.rados import Rados
    from ceph_tpu.loadgen.cluster import SimCluster

    cfg, mix = cell.config, cell.traffic
    profile, size = cfg["profile"], int(mix["object_bytes"])
    loop = asyncio.get_running_loop()
    cluster = await SimCluster.create(
        int(cfg["cluster"]["osds"]),
        mon_config=cfg["cluster"]["mon_config"],
        osd_config=cfg["cluster"]["osd_config"])
    rados = None
    trace = Trace(cell.name) if traced else None
    try:
        rados = await Rados(cluster.addr, name="client.benchmark").connect()
        await rados.mon_command("osd erasure-code-profile set", {
            "name": "bench-profile",
            "profile": {key: str(val) for key, val in profile.items()}})
        await rados.pool_create(cfg["pool"]["name"],
                                pg_num=int(cfg["pool"]["pg_num"]),
                                pool_type="erasure",
                                erasure_code_profile="bench-profile")
        ioctx = await rados.open_ioctx(cfg["pool"]["name"])
        say(f"cluster up: {len(cluster.osds)} OSDs, pool "
            f"{cfg['pool']['name']} pg_num {cfg['pool']['pg_num']}")

        records: list[tuple[int, float, float, bool]] = []
        errors: list[str] = []
        state = {"next": 0, "stop": False}

        async def writer() -> None:
            while not state["stop"]:
                i = state["next"]
                state["next"] += 1
                data = object_bytes(seed, i, size)
                t0 = time.perf_counter()
                try:
                    await ioctx.write_full(f"obj-{i}", data)
                    ok = True
                except Exception as e:       # a failed op is data
                    ok = False
                    if len(errors) < 5:
                        errors.append(f"obj-{i}: {type(e).__name__}: {e}")
                records.append((i, t0, time.perf_counter(), ok))

        def counters() -> dict:
            return cluster.perf_counters("ec_batch")

        writers = [loop.create_task(writer())
                   for _ in range(int(mix["in_flight"]))]
        while len(records) < int(mix["warmup_ops"]):
            await asyncio.sleep(0.02)
            if all(w.done() for w in writers):
                break

        # ---- the window -----------------------------------------------------
        t_open = time.perf_counter()
        setup_s = elapsed()
        cpu0, programs0, c_open = time.process_time(), meter.programs, \
            counters()
        facts: dict = {}

        slice_s = min(float(mix["trace_slice_s"]), 0.5 * seconds)
        if traced:
            # the steady slice is the window's end, so that the profiler
            # is stopped (off the loop's thread) while the writers drain
            await asyncio.sleep(max(0.0, seconds - slice_s))
            t1 = time.perf_counter()
            await loop.run_in_executor(None, trace.start)
            say(f"profiler started in {time.perf_counter() - t1:.2f}s")
            c0 = counters()
            with trace.mark():
                await asyncio.sleep(slice_s)
            counter_delta("slice.ec_batch", c0, counters(), facts)
        else:
            await asyncio.sleep(seconds)
        t_close = time.perf_counter()
        cpu_s = time.process_time() - cpu0
        compiles = meter.programs - programs0
        counter_delta("window.ec_batch", c_open, counters(), facts)

        state["stop"] = True
        if traced:
            await loop.run_in_executor(None, trace.stop)
            say(f"profiler stopped and trace written in "
                f"{time.perf_counter() - t_close:.2f}s")
        await asyncio.gather(*writers)
        say(f"window {t_close - t_open:.2f}s closed, writers drained "
            f"{time.perf_counter() - t_close:.2f}s later")

        inside = [r for r in records if t_open <= r[2] <= t_close]
        acked = [r[0] for r in inside if r[3]]
        failed = len(inside) - len(acked)
        lat_ms = [1e3 * (r[2] - r[1]) for r in inside]
        window_s = t_close - t_open
        downs = sum(MARKED_DOWN in e["message"]
                    for e in cluster.mon.services.cluster_log)

        # ---- correct: outside the window ------------------------------------
        rng = np.random.default_rng([seed, 0xC0FFEE])
        n_read, n_shard = int(mix["readback_objects"]), \
            int(mix["shard_check_objects"])
        picks = [int(i) for i in rng.choice(
            acked, size=min(n_read, len(acked)), replace=False)] \
            if acked else []
        faults = {"readback_differs": 0, "shards_missing": 0,
                  "shard_bytes_wrong": 0, "crc_xattr_wrong": 0,
                  "shard_label_wrong": 0}
        t_check = time.perf_counter()
        for n, i in enumerate(picks):
            payload = object_bytes(seed, i, size)
            try:
                got = await ioctx.read(f"obj-{i}")
            except Exception as e:           # unreadable is as wrong as it gets
                got = None
                errors.append(f"read obj-{i}: {type(e).__name__}: {e}")
            faults["readback_differs"] += got != payload
            if n < n_shard:
                pgid, _ = rados.objecter.calc_target(ioctx.pool_id,
                                                     f"obj-{i}")
                found = stored_shards(cluster, pgid, f"obj-{i}",
                                      cfg["stored_as"])
                for key, val in check_shards(found, profile,
                                             payload).items():
                    faults[key] += val
        correct = bool(picks) and not any(faults.values())
        say(f"correct={correct}: {len(picks)} objects read back, "
            f"{min(n_shard, len(picks))} with all "
            f"{profile['k'] + profile['m']} stored shards against the "
            f"reference, in {time.perf_counter() - t_check:.1f}s; "
            + "; ".join(f"{k} {v} (limit 0)" for k, v in faults.items()))
    finally:
        if rados is not None:
            await rados.shutdown()
        await cluster.stop()

    w = {k.removeprefix("window.ec_batch."): v for k, v in facts.items()
         if k.startswith("window.ec_batch.")}
    fifths = [sum(t_open + j * window_s / 5 <= r[2] < t_open + (j + 1)
                  * window_s / 5 for r in inside) for j in range(5)]
    say(f"ops in window: {len(inside)} finished ({failed} failed), "
        f"median {percentile(lat_ms, 50):.1f} ms, by fifth of the window "
        f"{fifths}" if lat_ms else "ops in window: none finished")
    say(f"compiles_in_window {compiles} (must be 0); OSDs marked down "
        f"{downs}; launches: encode {w.get('encode_launches', 0)}, decode "
        f"{w.get('decode_launches', 0)}, rmw {w.get('rmw_launches', 0)}, "
        f"mesh {w.get('mesh_launches', 0)}, fused-crc "
        f"{w.get('crc_fused_launches', 0)}; crc_host_batches "
        f"{w.get('crc_host_batches', 0)}; fallback_ops "
        f"{w.get('fallback_ops', 0)}")
    for line in errors:
        say(f"error: {line}")

    flatten("config", cfg, facts)
    facts.update({"run.ops": len(acked), "run.cpu_s": cpu_s,
                  "run.window_s": window_s})
    end_to_end = {"setup_s": setup_s}
    if lat_ms:
        end_to_end["client_mibps"] = len(acked) * size / 2**20 / window_s
        end_to_end["op_p95_ms"] = percentile(lat_ms, 95)
    return {"correct": correct, "attempted": len(inside), "failed": failed,
            "end_to_end": end_to_end, "facts": facts,
            "trace_file": trace.file() if traced else None}


def run(cell, seed: int, seconds: float, traced: bool, meter) -> dict:
    mix, cfg = cell.traffic, cell.config
    buckets = launch_buckets(cfg["profile"], int(mix["object_bytes"]),
                             int(cfg["cluster"]["osd_config"]
                                 ["osd_ec_batch_max"]))
    t0 = time.perf_counter()
    precompile(cfg["profile"], buckets)
    say(f"encode launches of {buckets} stripes compiled or loaded in "
        f"{time.perf_counter() - t0:.1f}s ({meter.hits} cache hits, "
        f"{meter.misses} misses)")
    return asyncio.run(_measure(cell, seed, seconds, traced, meter))
