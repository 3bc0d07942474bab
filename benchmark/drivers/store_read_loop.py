"""Closed loop of librados readers against an in-process EC cluster
that has lost an OSD.

Set-up compiles every launch shape (the populate's encodes with fused
checksums and the reconstruction's decode, before any daemon runs),
boots monitor and OSDs, creates the pool, writes the population, stops
the configuration's victim, waits until the monitor's map shows it
down (the cluster's own heartbeats and failure reports, at the
configured grace) and every PG of the pool is active again, and lets
the readers run ``warmup_ops`` reads; the window opens on the same
running loop.  Each reader draws its next name uniformly from the
population (``rados bench rand``).  A failed read is counted, never
raised.  ``correct`` is decided after the window on bytes the timed
reads themselves returned: a sample drawn from ``--seed`` before the
window, half of it reads that had to reconstruct, held to the seeded
payload and to the plain reference's reconstruction (reference/
ec_decode.py) from the shards the live OSDs store, each of which must
match its checksum and its shard label.
"""

from __future__ import annotations

import asyncio
import time

import numpy as np

from benchmark.drivers.store_closed_loop import (MARKED_DOWN, launch_buckets,
                                                 object_bytes)
from benchmark.harness import (HarnessError, Trace, counter_delta, elapsed,
                               flatten, percentile, say)
from benchmark.reference import ec, ec_decode

RECONSTRUCTED, PLAIN = "reconstructed", "plain"
KEPT_BYTES_MAX = 64 << 20
COUNTER_SETS = ("ec_batch", "ec_degraded", "ec_hedge")


def served_erasures(codec, lost: int) -> tuple:
    """The erasure signature the primary decodes with when shard
    ``lost`` is the hole: it gathers the codec's minimum set for the
    data shards and names every shard outside it."""
    n = codec.get_chunk_count()
    k = codec.get_data_chunk_count()
    have = set(codec.minimum_to_decode(set(range(k)),
                                       set(range(n)) - {lost}))
    return tuple(sorted(set(range(n)) - have))


def precompile(profile: dict, buckets: list) -> bool:
    """Every launch the run can make, through the launch engine the
    OSDs' batchers share process-wide: the populate's (batch, k,
    stripe_unit) encodes with fused checksums, and the reconstruction's
    decode for each data shard the victim can hold, with the real
    single-loss matrix.  Returns whether the decode is the dense
    program (the matrix an operand: one executable for every loss
    position) or a scheduled one per matrix; either way all k
    positions are launched here."""
    import jax
    from ceph_tpu.ec import registry
    from ceph_tpu.ops import xor_schedule
    from ceph_tpu.ops.gf2kernels import bitmatrix_i8
    from ceph_tpu.parallel.mesh_codec import MeshCodec

    k, unit = profile["k"], profile["stripe_unit"]
    codec = registry().factory(profile["plugin"], {
        "k": str(k), "m": str(profile["m"]),
        "technique": profile["technique"]})
    mesh = MeshCodec()
    dense = True
    for b in buckets:
        shape = (mesh.pad_batch(b), k, unit)
        mesh.encode(codec, np.zeros(shape, np.uint8), with_crc=True)
        for lost in range(k):
            erasures = served_erasures(codec, lost)
            dense &= xor_schedule.want_scheduled(
                bitmatrix_i8(codec.decode_matrix_for(list(erasures))),
                unit, jax.default_backend()) is None
            mesh.decode(codec, erasures, np.zeros(shape, np.uint8))
    return dense


def stored_shards(cluster, pgid: str, oid: str, stored_as: dict) -> dict:
    """What the live OSDs hold of one object: {shard: (bytes, crc,
    label)}.  The stopped victim's store is not looked at."""
    found = {}
    for osd in cluster.osds:
        pg = osd.pgs.get(pgid)
        if osd.is_stopped() or pg is None or osd.whoami not in pg.acting:
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


def check_read(got: bytes, payload: bytes, found: dict, expected: set,
               profile: dict) -> dict:
    """Faults of one timed read: its bytes against the payload and
    against the reference's reconstruction from the shards the live
    OSDs store, and those shards against their own checksums and
    labels."""
    faults = {"readback_differs": int(got != payload),
              "reference_differs": 0,
              "crc_xattr_wrong": 0, "shard_label_wrong": 0,
              "shards_missing": len(expected - set(found))}
    for shard, (raw, crc, label) in found.items():
        faults["crc_xattr_wrong"] += crc != ec.ceph_crc32c(raw)
        faults["shard_label_wrong"] += label != shard
    try:
        ref = ec_decode.object_from_shards(
            profile, {s: raw for s, (raw, _, _) in found.items()},
            len(payload))
    except ValueError:
        ref = None              # fewer than k live shards, or ragged
    faults["reference_differs"] += got != ref
    return faults


class Sample:
    """Bytes of timed reads kept for the comparison: per kind the
    ``per_kind`` objects of lowest rank (a permutation drawn from the
    seed before the window) among those a read returned inside the
    window, one read each, so at most 2 x per_kind objects are held."""

    def __init__(self, rank: dict[int, int], per_kind: int) -> None:
        self.rank = rank
        self.per_kind = per_kind
        self.kept: dict[str, dict[int, tuple[float, bytes]]] = {
            RECONSTRUCTED: {}, PLAIN: {}}

    def offer(self, kind: str, i: int, t_done: float, data: bytes) -> None:
        kept = self.kept[kind]
        if i in kept:
            return
        if len(kept) >= self.per_kind:
            worst = max(kept, key=self.rank.__getitem__)
            if self.rank[i] > self.rank[worst]:
                return
            del kept[worst]
        kept[i] = (t_done, data)

    def inside(self, t_open: float, t_close: float) -> list[tuple]:
        """(kind, object, bytes) of the kept reads that finished in
        the window, reconstructed ones first."""
        return [(kind, i, data) for kind in (RECONSTRUCTED, PLAIN)
                for i, (t, data) in sorted(self.kept[kind].items())
                if t_open <= t <= t_close]


async def _populate(ioctx, seed: int, n: int, size: int, lanes: int) -> None:
    todo = iter(range(n))

    async def writer() -> None:
        for i in todo:
            await ioctx.write_full(f"obj-{i}", object_bytes(seed, i, size))

    try:
        await asyncio.gather(*(writer() for _ in range(lanes)))
    except Exception as e:
        raise HarnessError(f"populate failed: {type(e).__name__}: {e}") \
            from e


async def _fail_victim(cluster, victim: int, pool_pgs: int,
                       timeout: float = 90.0) -> None:
    """Stop osd.<victim>, wait for the monitor's map to show it down,
    then for every live OSD to have that map and every PG of the pool
    to be active under a live primary (the program's own PG states)."""
    index = next((n for n, o in enumerate(cluster.osds)
                  if o.whoami == victim), None)
    if index is None:
        raise HarnessError(f"no osd.{victim} in the cluster")
    t0 = time.perf_counter()
    await cluster.kill_osd(index)
    if not await cluster.wait_down(victim, timeout=timeout):
        raise HarnessError(f"osd.{victim} not marked down in {timeout}s")
    t_down = time.perf_counter()
    epoch = cluster.mon.osdmap.epoch
    while True:
        live = [o for o in cluster.osds if not o.is_stopped()]
        if all(o.osdmap.epoch >= epoch for o in live) \
                and cluster.pg_states() == {"active": pool_pgs}:
            break
        if time.perf_counter() - t_down > timeout:
            raise HarnessError(f"PGs not active {timeout}s after osd."
                               f"{victim} went down: {cluster.pg_states()}")
        await asyncio.sleep(0.1)
    say(f"osd.{victim} stopped, marked down {t_down - t0:.1f}s later "
        f"(map epoch {epoch}), all {pool_pgs} PGs active again "
        f"{time.perf_counter() - t_down:.1f}s after that")


def _read_kinds(cluster, pool_id: int, n: int, k: int) -> dict[int, str]:
    """By the monitor's map: a read reconstructs when the hole in its
    PG's acting set is a data shard's position."""
    osdmap = cluster.mon.osdmap
    kinds = {}
    for i in range(n):
        _, ps = osdmap.object_to_pg(pool_id, f"obj-{i}")
        acting = osdmap.pg_to_up_acting_osds(pool_id, ps)
        hole = any(o < 0 or not osdmap.is_up(o) for o in acting[:k])
        kinds[i] = RECONSTRUCTED if hole else PLAIN
    return kinds


async def _measure(cell, seed: int, seconds: float, traced: bool,
                   meter) -> dict:
    from ceph_tpu.client.rados import Rados
    from ceph_tpu.loadgen.cluster import SimCluster

    cfg, mix = cell.config, cell.traffic
    if (mix["op"], mix["order"]) != ("read", "rand"):
        raise HarnessError(f"this driver reads whole objects in random "
                           f"order, not {mix['op']!r} / {mix['order']!r}")
    profile, size = cfg["profile"], int(mix["object_bytes"])
    n_obj, victim = int(mix["populate_objects"]), \
        int(cfg["failure"]["victim"])
    k, n_shards = profile["k"], profile["k"] + profile["m"]
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
        say(f"cluster up: {len(cluster.osds)} OSDs (ids in boot order "
            f"{[o.whoami for o in cluster.osds]}), pool "
            f"{cfg['pool']['name']} pg_num {cfg['pool']['pg_num']}")

        t0 = time.perf_counter()
        await _populate(ioctx, seed, n_obj, size, int(mix["in_flight"]))
        say(f"{n_obj} objects of {size} bytes written and acknowledged in "
            f"{time.perf_counter() - t0:.1f}s")
        await _fail_victim(cluster, victim, int(cfg["pool"]["pg_num"]))
        kinds = _read_kinds(cluster, ioctx.pool_id, n_obj, k)
        say(f"of {n_obj} objects {sum(v == RECONSTRUCTED for v in kinds.values())}"
            f" have their hole at a data shard")

        def downs() -> list[str]:
            return [e["message"] for e in cluster.mon.services.cluster_log
                    if MARKED_DOWN in e["message"]]

        datapath = next(o for o in cluster.osds
                        if not o.is_stopped()).perf.get("datapath")

        def counters() -> dict:
            """Each OSD's own sets summed over the live OSDs; the shard
            cache's set is process-wide and adopted by every OSD, so it
            is read once."""
            out = {name: cluster.perf_counters(name)
                   for name in COUNTER_SETS}
            out["datapath"] = {key: val for key, val in
                               datapath.dump().items()
                               if isinstance(val, (int, float))}
            return out

        def deltas(prefix: str, before: dict, out: dict) -> None:
            for name, after in counters().items():
                counter_delta(f"{prefix}.{name}", before[name], after, out)

        # the sample: a rank for every object, drawn before the window
        rng = np.random.default_rng([seed, 0xC0FFEE])
        rank = {int(i): n for n, i in enumerate(rng.permutation(n_obj))}
        per_kind = min(int(mix["check_reads"]) // 2,
                       KEPT_BYTES_MAX // (2 * size))
        sample = Sample(rank, max(1, per_kind))

        records: list[tuple[int, float, float, bool]] = []
        errors: list[str] = []
        state = {"stop": False, "open": False}

        async def reader(lane: int) -> None:
            draw = np.random.default_rng([seed, 0x2EAD, lane])
            while not state["stop"]:
                i = int(draw.integers(n_obj))
                t0 = time.perf_counter()
                try:
                    got = await ioctx.read(f"obj-{i}")
                    ok = True
                except Exception as e:       # a failed op is data
                    ok = False
                    if len(errors) < 5:
                        errors.append(f"obj-{i}: {type(e).__name__}: {e}")
                t1 = time.perf_counter()
                records.append((i, t0, t1, ok))
                if ok and state["open"]:
                    sample.offer(kinds[i], i, t1, got)

        downs_setup = len(downs())
        readers = [loop.create_task(reader(lane))
                   for lane in range(int(mix["in_flight"]))]
        while len(records) < int(mix["warmup_ops"]):
            await asyncio.sleep(0.02)
            if all(r.done() for r in readers):
                break

        # ---- the window -----------------------------------------------------
        t_open = time.perf_counter()
        setup_s = elapsed()
        state["open"] = True
        cpu0, programs0, c_open = time.process_time(), meter.programs, \
            counters()
        facts: dict = {}

        slice_s = min(float(mix["trace_slice_s"]), 0.5 * seconds)
        if traced:
            # the steady slice is the window's end, so that the profiler
            # is stopped (off the loop's thread) while the readers drain
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
        state["open"] = False
        cpu_s = time.process_time() - cpu0
        compiles = meter.programs - programs0
        deltas("window", c_open, facts)
        marked = downs()
        downs_window = len(marked) - downs_setup

        state["stop"] = True
        if traced:
            await loop.run_in_executor(None, trace.stop)
            say(f"profiler stopped and trace written in "
                f"{time.perf_counter() - t_close:.2f}s")
        await asyncio.gather(*readers)
        say(f"window {t_close - t_open:.2f}s closed, readers drained "
            f"{time.perf_counter() - t_close:.2f}s later")

        inside = [r for r in records if t_open <= r[2] <= t_close]
        done = [r for r in inside if r[3]]
        failed = len(inside) - len(done)
        lat_ms = [1e3 * (r[2] - r[1]) for r in inside]
        window_s = t_close - t_open

        # ---- correct: outside the window ------------------------------------
        faults = {"readback_differs": 0, "reference_differs": 0,
                  "crc_xattr_wrong": 0, "shard_label_wrong": 0,
                  "shards_missing": 0}
        t_check = time.perf_counter()
        picks = sample.inside(t_open, t_close)
        for _, i, got in picks:
            pgid, _ = rados.objecter.calc_target(ioctx.pool_id, f"obj-{i}")
            pg = next(o.pgs[pgid] for o in cluster.osds
                      if not o.is_stopped() and pgid in o.pgs)
            expected = {s for s in range(n_shards)
                        if pg.acting[s] >= 0 and pg.acting[s] != victim}
            found = stored_shards(cluster, pgid, f"obj-{i}",
                                  cfg["stored_as"])
            for key, val in check_read(got, object_bytes(seed, i, size),
                                       found, expected, profile).items():
                faults[key] += val
        n_rec = sum(kind == RECONSTRUCTED for kind, _, _ in picks)
        correct = n_rec > 0 and not any(faults.values())
        say(f"correct={correct}: {len(picks)} timed reads ({n_rec} "
            f"reconstructed) against the payload, the stored shards of the "
            f"live OSDs and the reference's reconstruction, in "
            f"{time.perf_counter() - t_check:.1f}s; "
            + "; ".join(f"{k_} {v} (limit 0)" for k_, v in faults.items()))
    finally:
        if rados is not None:
            await rados.shutdown()
        await cluster.stop()

    def window(name: str) -> dict:
        return {key.removeprefix(f"window.{name}."): val
                for key, val in facts.items()
                if key.startswith(f"window.{name}.")}

    w, deg, hedge, cache = (window(name) for name in
                            ("ec_batch", "ec_degraded", "ec_hedge",
                             "datapath"))
    fifths = [sum(t_open + j * window_s / 5 <= r[2] < t_open + (j + 1)
                  * window_s / 5 for r in inside) for j in range(5)]
    say(f"reads in window: {len(inside)} finished ({failed} failed), "
        f"median {percentile(lat_ms, 50):.1f} ms, by fifth of the window "
        f"{fifths}" if lat_ms else "reads in window: none finished")
    by_kind = {kind: [1e3 * (r[2] - r[1]) for r in done
                      if kinds[r[0]] == kind]
               for kind in (RECONSTRUCTED, PLAIN)}
    say("reads by kind: " + ", ".join(
        f"{kind} {len(ms)}" + (f" (median {percentile(ms, 50):.1f} ms)"
                               if ms else "")
        for kind, ms in by_kind.items())
        + f"; ec_degraded: degraded_reads {deg.get('degraded_reads', 0)}, "
        f"reconstructions {deg.get('reconstructions', 0)}, gather_retries "
        f"{deg.get('gather_retries', 0)}, crc_mismatch "
        f"{deg.get('crc_mismatch', 0)}, shard_mismatch "
        f"{deg.get('shard_mismatch', 0)}")
    say(f"compiles_in_window {compiles} (must be 0); OSDs marked down "
        f"{downs_setup} in set-up, {downs_window} in the window {marked}; "
        f"launches: "
        f"encode {w.get('encode_launches', 0)}, decode "
        f"{w.get('decode_launches', 0)}, rmw {w.get('rmw_launches', 0)}, "
        f"mesh {w.get('mesh_launches', 0)}; fallback_ops "
        f"{w.get('fallback_ops', 0)}; sub-reads {hedge.get('subreads', 0)}"
        f", hedges fired {hedge.get('hedges_fired', 0)} "
        f"({hedge.get('hedge_bytes', 0)} bytes); shard cache hits "
        f"{cache.get('hits', 0)}, misses {cache.get('misses', 0)}")
    for line in errors:
        say(f"error: {line}")

    flatten("config", cfg, facts)
    facts.update({"run.ops": len(done), "run.read_bytes": len(done) * size,
                  "run.cpu_s": cpu_s, "run.window_s": window_s,
                  "run.downs_setup": downs_setup,
                  "run.downs_window": downs_window,
                  "window.datapath.lookups": cache.get("hits", 0)
                  + cache.get("misses", 0)})
    end_to_end = {"setup_s": setup_s}
    if lat_ms:
        end_to_end["client_mibps"] = len(done) * size / 2**20 / window_s
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
    dense = precompile(cfg["profile"], buckets)
    say(f"encode and decode launches of {buckets} stripes compiled or "
        f"loaded in {time.perf_counter() - t0:.1f}s ({meter.hits} cache "
        f"hits, {meter.misses} misses); the decode is "
        + ("one dense program for every loss position" if dense
           else "a scheduled program per loss position"))
    return asyncio.run(_measure(cell, seed, seconds, traced, meter))
