"""Closed loop of small random overwrites through RBD images whose data
objects live on an erasure-coded pool.

Set-up compiles every launch shape the traffic can produce (the
prefill's whole-object encodes with fused checksums, the parity update
at every power-of-two batch up to ``osd_ec_batch_max``, and the decode
a ranged gather makes when a hedge completes it with a parity shard;
all before any daemon runs), boots monitor and OSDs, creates the
replicated header pool and the erasure data pool, creates and opens
the images (no exclusive lock, no cache), writes every block of every
image once, and lets the writers run ``warmup_ops`` writes; the window
opens on the same running loop.  Each writer draws its next block
uniformly from its image (a block may be drawn again; one that has a
write in flight is drawn again at once).  A failed write is counted,
never raised.  After the window the writers drain, and a sample of the
data objects that took an overwrite inside the window is read back
through the image and, shard by shard, out of the OSDs' stores, and
held to the plain references (reference/image.py, reference/ec.py).
"""

from __future__ import annotations

import asyncio
import time

import numpy as np

from benchmark.drivers.store_closed_loop import (MARKED_DOWN, check_shards,
                                                 launch_buckets, stored_shards)
from benchmark.drivers.store_closed_loop import precompile as warm_encodes
from benchmark.drivers.store_read_loop import served_erasures
from benchmark.harness import (HarnessError, Trace, counter_delta, elapsed,
                               flatten, percentile, say)
from benchmark.reference import image as ref_image

COUNTER_SETS = ("ec_batch", "ec_pipeline", "ec_hedge", "osd")


def precompile(profile: dict, buckets: list[int]) -> None:
    """The parity update at every batch, and the decode of a ranged
    gather that ends on a parity shard (one dense program a batch: the
    matrix is an operand), through the launch engine the OSDs' batchers
    share process-wide."""
    from ceph_tpu.ec import registry
    from ceph_tpu.parallel.mesh_codec import MeshCodec

    k, m, unit = profile["k"], profile["m"], profile["stripe_unit"]
    codec = registry().factory(profile["plugin"], {
        "k": str(k), "m": str(m), "technique": profile["technique"]})
    mesh = MeshCodec()
    for b in buckets:
        b = mesh.pad_batch(b)
        mesh.rmw(codec, np.zeros((b, m, unit), np.uint8),
                 np.zeros((b, k, unit), np.uint8))
        mesh.decode(codec, served_erasures(codec, 0),
                    np.zeros((b, k, unit), np.uint8))


async def _prefill(images: list, refs: list, chunk: int, lanes: int) -> None:
    todo = iter([(n, off) for n, ref in enumerate(refs)
                 for off in range(0, len(ref.data), chunk)])

    async def writer() -> None:
        for n, off in todo:
            await images[n].write(off, refs[n].read(off, chunk))

    try:
        await asyncio.gather(*(writer() for _ in range(lanes)))
    except Exception as e:
        raise HarnessError(f"prefill failed: {type(e).__name__}: {e}") \
            from e


def _header_log_versions(cluster, pool_id: int) -> int:
    """Sum of the header pool's PG log heads at their primaries: it
    moves with every write the pool takes."""
    prefix = f"{pool_id}."
    return sum(pg.info.last_update.version
               for osd in cluster.osds if not osd.is_stopped()
               for pgid, pg in osd.pgs.items()
               if pgid.startswith(prefix) and pg.is_primary())


async def _measure(cell, seed: int, seconds: float, traced: bool,
                   meter) -> dict:
    from ceph_tpu.client.rados import Rados
    from ceph_tpu.loadgen.cluster import SimCluster
    from ceph_tpu.rbd import RBD, Image, RbdError

    cfg, mix = cell.config, cell.traffic
    if (mix["op"], mix["order"]) != ("write", "rand"):
        raise HarnessError(f"this driver overwrites blocks in random "
                           f"order, not {mix['op']!r} / {mix['order']!r}")
    profile, pools, vols = cfg["profile"], cfg["pools"], cfg["images"]
    io, size = int(mix["io_bytes"]), int(vols["bytes"])
    obj = 1 << int(vols["order"])
    n_blocks, n_images = size // io, int(vols["count"])
    loop = asyncio.get_running_loop()
    cluster = await SimCluster.create(
        int(cfg["cluster"]["osds"]),
        mon_config=cfg["cluster"]["mon_config"],
        osd_config=cfg["cluster"]["osd_config"])
    rados = None
    images: list = []
    trace = Trace(cell.name) if traced else None
    try:
        rados = await Rados(cluster.addr, name="client.benchmark").connect()
        await rados.mon_command("osd erasure-code-profile set", {
            "name": "bench-profile",
            "profile": {key: str(val) for key, val in profile.items()}})
        await rados.pool_create(pools["header"]["name"],
                                pg_num=int(pools["header"]["pg_num"]),
                                size=int(pools["header"]["size"]))
        await rados.pool_create(pools["data"]["name"],
                                pg_num=int(pools["data"]["pg_num"]),
                                pool_type="erasure",
                                erasure_code_profile="bench-profile")
        hio = await rados.open_ioctx(pools["header"]["name"])
        dio = await rados.open_ioctx(pools["data"]["name"])
        say(f"cluster up: {len(cluster.osds)} OSDs, header pool "
            f"{pools['header']['name']} (replicated x"
            f"{pools['header']['size']}, pg_num {pools['header']['pg_num']}"
            f"), data pool {pools['data']['name']} (erasure, pg_num "
            f"{pools['data']['pg_num']})")

        rbd = RBD()
        names = [vols["names"].replace("<i>", str(n))
                 for n in range(n_images)]
        for name in names:
            await rbd.create(hio, name, size, order=int(vols["order"]),
                             stripe_count=int(vols["stripe_count"]),
                             features=list(vols["features"]), data_pool=dio)
            images.append(await Image.open(hio, name, exclusive=False))
        t0 = time.perf_counter()
        refs = [ref_image.Image(seed, n, size, obj) for n in range(n_images)]
        t1 = time.perf_counter()
        await _prefill(images, refs, int(mix["prefill_bytes"]),
                       int(mix["prefill_in_flight"]))
        say(f"{n_images} images of {size} bytes ({n_images * size // obj} "
            f"data objects of {obj}) made in {t1 - t0:.1f}s and written in "
            f"{time.perf_counter() - t1:.1f}s, every block once")

        def counters() -> dict:
            return {name: cluster.perf_counters(name)
                    for name in COUNTER_SETS}

        def deltas(prefix: str, before: dict, out: dict) -> None:
            for name, after in counters().items():
                counter_delta(f"{prefix}.{name}", before[name], after, out)

        # (image, block, call, ack, ok)
        records: list[tuple[int, int, float, float, bool]] = []
        errors: list[str] = []
        tainted: set[tuple[int, int]] = set()    # (image, object)
        state = {"stop": False}
        busy = [set() for _ in range(n_images)]
        times: list[dict[int, int]] = [{} for _ in range(n_images)]

        async def writer(n: int, lane: int) -> None:
            draw = np.random.default_rng([seed, 0x4B10, n, lane])
            img, ref = images[n], refs[n]
            while not state["stop"]:
                block = int(draw.integers(n_blocks))
                while block in busy[n]:
                    block = int(draw.integers(n_blocks))
                busy[n].add(block)
                nth = times[n].get(block, 0) + 1
                data = ref_image.write_payload(seed, n, block, nth, io)
                t0 = time.perf_counter()
                try:
                    await img.write(block * io, data)
                    ok = True
                except Exception as e:       # a failed op is data
                    ok = False
                    if len(errors) < 5:
                        errors.append(f"{names[n]} block {block}: "
                                      f"{type(e).__name__}: {e}")
                t1 = time.perf_counter()
                busy[n].discard(block)
                if ok:
                    times[n][block] = nth
                    ref.write(block * io, data)
                else:
                    # it may or may not have been applied
                    tainted.add((n, block * io // obj))
                records.append((n, block, t0, t1, ok))

        writers = [loop.create_task(writer(n, lane))
                   for n in range(n_images)
                   for lane in range(int(mix["iodepth"]))]
        while len(records) < int(mix["warmup_ops"]):
            await asyncio.sleep(0.02)
            if all(w.done() for w in writers):
                break

        # ---- the window -----------------------------------------------------
        t_open = time.perf_counter()
        setup_s = elapsed()
        for ref in refs:
            ref.mark()
        cpu0, programs0, c_open = time.process_time(), meter.programs, \
            counters()
        header0 = _header_log_versions(cluster, hio.pool_id)
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
            deltas("slice", c0, facts)
        else:
            await asyncio.sleep(seconds)
        t_close = time.perf_counter()
        overwritten = [dict(ref.written) for ref in refs]
        cpu_s = time.process_time() - cpu0
        compiles = meter.programs - programs0
        deltas("window", c_open, facts)
        header_writes = _header_log_versions(cluster, hio.pool_id) - header0

        state["stop"] = True
        if traced:
            await loop.run_in_executor(None, trace.stop)
            say(f"profiler stopped and trace written in "
                f"{time.perf_counter() - t_close:.2f}s")
        await asyncio.gather(*writers)
        say(f"window {t_close - t_open:.2f}s closed, writers drained "
            f"{time.perf_counter() - t_close:.2f}s later")

        inside = [r for r in records if t_open <= r[3] <= t_close]
        acked = [r for r in inside if r[4]]
        failed = len(inside) - len(acked)
        lat_ms = [1e3 * (r[3] - r[2]) for r in inside]
        window_s = t_close - t_open
        downs = sum(MARKED_DOWN in e["message"]
                    for e in cluster.mon.services.cluster_log)

        # ---- correct: outside the window ------------------------------------
        # objects that took an acknowledged overwrite inside the window
        # and no write whose outcome is unknown
        pool = sorted((n, o) for n in range(n_images)
                      for o in overwritten[n] if (n, o) not in tainted)
        rng = np.random.default_rng([seed, 0xC0FFEE])
        picks = [pool[int(i)] for i in rng.choice(
            len(pool), size=min(int(mix["check_objects"]), len(pool)),
            replace=False)] if pool else []
        faults = {"readback_differs": 0, "shards_missing": 0,
                  "shard_bytes_wrong": 0, "crc_xattr_wrong": 0,
                  "shard_label_wrong": 0}
        t_check = time.perf_counter()
        for n, o in picks:
            want = refs[n].object(o)
            try:
                got = await images[n].read(o * obj, obj)
            except Exception as e:           # unreadable is as wrong as it gets
                got = None
                errors.append(f"read {names[n]} object {o}: "
                              f"{type(e).__name__}: {e}")
            faults["readback_differs"] += got != want
            oid = images[n]._data_obj(o)
            pgid, _ = rados.objecter.calc_target(dio.pool_id, oid)
            found = stored_shards(cluster, pgid, oid, cfg["stored_as"])
            for key, val in check_shards(found, profile, want).items():
                faults[key] += val
        correct = bool(picks) and not any(faults.values())
        say(f"correct={correct}: {len(picks)} data objects of the "
            f"{len(pool)} overwritten inside the window ("
            + ", ".join(f"{names[n]}/{o} x{overwritten[n][o]}"
                        for n, o in picks)
            + f") read back whole through the image and with all "
            f"{profile['k'] + profile['m']} stored shards against the "
            f"references, in {time.perf_counter() - t_check:.1f}s; "
            + "; ".join(f"{k} {v} (limit 0)" for k, v in faults.items()))
    finally:
        for img in images:
            try:
                await img.close()
            except (RbdError, OSError, asyncio.TimeoutError) as e:
                say(f"closing {img.name}: {type(e).__name__}: {e}")
        if rados is not None:
            await rados.shutdown()
        await cluster.stop()

    def window(name: str) -> dict:
        return {key.removeprefix(f"window.{name}."): val
                for key, val in facts.items()
                if key.startswith(f"window.{name}.")}

    w, pipe, hedge, osd = (window(name) for name in COUNTER_SETS)
    fifths = [sum(t_open + j * window_s / 5 <= r[3] < t_open + (j + 1)
                  * window_s / 5 for r in inside) for j in range(5)]
    repeats = sum(1 for r in acked if times[r[0]].get(r[1], 0) > 1)
    say(f"writes in window: {len(inside)} finished ({failed} failed), "
        f"{len(acked) / window_s:.1f} IOPS, median "
        f"{percentile(lat_ms, 50):.1f} ms, by fifth of the window {fifths}; "
        f"{repeats} to a block written more than once in the run"
        if lat_ms else "writes in window: none finished")
    say(f"compiles_in_window {compiles} (must be 0); OSDs marked down "
        f"{downs}; launches: rmw {w.get('rmw_launches', 0)} (mesh "
        f"{w.get('mesh_rmw_launches', 0)}), encode "
        f"{w.get('encode_launches', 0)}, decode "
        f"{w.get('decode_launches', 0)}; runs: delta "
        f"{w.get('rmw_delta_runs', 0)}, full {w.get('rmw_full_runs', 0)}; "
        f"fallback_ops {w.get('fallback_ops', 0)}")
    say(f"served by: writes_blind {pipe.get('writes_blind', 0)}, "
        f"write_old_gathers {pipe.get('write_old_gathers', 0)}, stripes "
        f"read {pipe.get('rmw_stripes_read', 'n/a')} (ExtentCache "
        f"{pipe.get('rmw_stripes_cached', 'n/a')}), version-only "
        f"sub-writes {pipe.get('rmw_subwrites_empty', 'n/a')}; sub-reads "
        f"{hedge.get('subreads', 0)} ({hedge.get('subread_bytes', 0)} "
        f"bytes), hedges fired {hedge.get('hedges_fired', 0)}; OSD ops "
        f"{osd.get('op', 0)} of which writes {osd.get('op_w', 0)}, reads "
        f"{osd.get('op_r', 0)}; header-pool writes {header_writes}")
    for line in errors:
        say(f"error: {line}")

    flatten("config", cfg, facts)
    facts.update({"run.ops": len(acked), "run.written_bytes": len(acked) * io,
                  "run.cpu_s": cpu_s, "run.window_s": window_s,
                  "run.header_pool_writes": header_writes,
                  "run.objects_overwritten": len(pool),
                  "run.rmw_runs": w.get("rmw_delta_runs", 0)
                  + w.get("rmw_full_runs", 0)})
    end_to_end = {"setup_s": setup_s}
    if lat_ms:
        end_to_end["client_mibps"] = len(acked) * io / 2**20 / window_s
        # printed with every run; the manifest lists it per layer
        # (rmw_op_p95_ms reads the fact): run to run it spreads by more
        # than half of the largest bound
        end_to_end["op_p95_ms"] = percentile(lat_ms, 95)
        facts["run.op_p95_ms"] = end_to_end["op_p95_ms"]
    return {"correct": correct, "attempted": len(inside), "failed": failed,
            "end_to_end": end_to_end, "facts": facts,
            "trace_file": trace.file() if traced else None}


def run(cell, seed: int, seconds: float, traced: bool, meter) -> dict:
    import inspect

    from ceph_tpu.rbd import RBD
    if "data_pool" not in inspect.signature(RBD.create).parameters:
        raise HarnessError("this program's RBD.create takes no data_pool: "
                           "it cannot put an image's data objects on "
                           "another pool than its header")
    mix, cfg = cell.traffic, cell.config
    max_batch = int(cfg["cluster"]["osd_config"]["osd_ec_batch_max"])
    encodes = launch_buckets(cfg["profile"], int(mix["prefill_bytes"]),
                             max_batch)
    # an overwrite of one block submits one stripe
    updates = launch_buckets(cfg["profile"], int(mix["io_bytes"]), max_batch)
    t0 = time.perf_counter()
    warm_encodes(cfg["profile"], encodes)
    precompile(cfg["profile"], updates)
    say(f"encode launches of {encodes} stripes, parity-update and decode "
        f"launches of {updates} stripes compiled or loaded in "
        f"{time.perf_counter() - t0:.1f}s ({meter.hits} cache hits, "
        f"{meter.misses} misses)")
    return asyncio.run(_measure(cell, seed, seconds, traced, meter))
