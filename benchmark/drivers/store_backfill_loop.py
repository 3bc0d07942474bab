"""Closed loop of librados writers against an in-process EC cluster
that is backfilling: one OSD dead and marked out, its shards being
rebuilt onto the survivors while the clients write.

Set-up compiles every launch the run can make (the writes' encodes
with fused checksums; the rebuild's decode for every erasure signature
a rebuild of any position can present, one hole and two, before any
daemon runs), boots monitor and OSDs, creates the pool, writes the
population, stops the configuration's victim, waits until the
monitor's map shows it down and every PG is active again, sends the
monitor ``osd out`` as an operator or the down-out timer would, waits
until every PG is active on its new up set, and lets the writers run
``warmup_ops`` writes; the window opens on the same running loop.
From ``osd out`` on the cluster heals itself: the driver makes no
recovery call.  A failed write is counted, never raised.

After the window the writers drain, the driver waits (outside the
window, at most ``clean_timeout_s``) until no primary has pending
recovery, and ``correct`` is decided on a sample drawn from ``--seed``:
objects written and acknowledged inside the window, and objects of the
population that a ``pg.backfill_push`` span of the program rebuilt
inside the window (a push with an ``ec.recover_decode`` under it).
Each is read back through the client; its shards are taken out of the
stores of the OSDs the monitor's final map gives the PG and held to
the plain reference (reference/ec.py), to their checksums and to their
positions' labels.
"""

from __future__ import annotations

import asyncio
import time

import numpy as np

from benchmark.drivers.store_closed_loop import (MARKED_DOWN, check_shards,
                                                 launch_buckets, object_bytes)
from benchmark.drivers.store_closed_loop import precompile as warm_encodes
from benchmark.drivers.store_read_loop import _fail_victim, _populate
from benchmark.harness import (HarnessError, Trace, counter_delta, elapsed,
                               flatten, percentile, say)

COUNTER_SETS = ("ec_batch", "ec_recovery", "ec_hedge")
PUSH = "pg.backfill_push"
PUSH_TREE = (PUSH, "ec.recover_gather", "ec.recover_decode", "pg.push")
POLL_S = 0.25


def new_object_bytes(seed: int, i: int, size: int) -> bytes:
    return np.random.default_rng([seed, 1, i]).bytes(size)


def rebuild_signatures(codec) -> list[tuple]:
    """The erasure signatures a rebuild can decode with: shard ``want``
    is never a source of itself, a second position may be a hole (the
    dead OSD's, not rebuilt yet, or a survivor's that moved), the
    primary gathers the codec's minimum set for ``want`` from the rest
    and names every shard outside it."""
    n, k = codec.get_chunk_count(), codec.get_data_chunk_count()
    found = set()
    for want in range(n):
        for hole in (None, *range(n)):
            have = set(range(n)) - {want, hole}
            if hole == want or len(have) < k:
                continue
            got = set(codec.minimum_to_decode({want}, have))
            found.add(tuple(sorted(set(range(n)) - got)))
    return sorted(found)


def precompile(profile: dict, buckets: list) -> tuple[int, bool]:
    """The rebuild's decode at every launch batch and for every
    signature of ``rebuild_signatures``, with its real matrix, through
    the launch engine the OSDs' batchers share process-wide.  Returns
    the number of signatures and whether the decode is the dense
    program (the matrix an operand: one executable a batch)."""
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
    signatures = rebuild_signatures(codec)
    dense = True
    for b in buckets:
        shape = (mesh.pad_batch(b), k, unit)
        for erasures in signatures:
            dense &= xor_schedule.want_scheduled(
                bitmatrix_i8(codec.decode_matrix_for(list(erasures))),
                unit, jax.default_backend()) is None
            mesh.decode(codec, erasures, np.zeros(shape, np.uint8))
    return len(signatures), dense


def pool_mapping(osdmap, pool_id: int, pg_num: int) -> dict[str, list[int]]:
    """{pgid: up set} of every PG of the pool by the monitor's map."""
    return {osdmap.pg_name(pool_id, ps): list(
        osdmap.pg_to_up_acting(pool_id, ps)[0]) for ps in range(pg_num)}


def positions_moved(before: dict, after: dict) -> tuple:
    """(PGs in which a survivor serves another position than before,
    survivors' positions moved, PGs with a hole before).  ``before``
    is the map with the victim down: its positions are holes."""
    pgs = moved = held = 0
    for pgid, old in before.items():
        new = after[pgid]
        n = sum(1 for s, o in enumerate(new)
                if o >= 0 and o in old and old.index(o) != s)
        moved += n
        pgs += n > 0
        held += min(old) < 0
    return pgs, moved, held


async def _mark_out(cluster, rados, victim: int, pool_pgs: int,
                    timeout: float = 90.0) -> float:
    """Send ``osd out`` and wait until every live OSD has the map that
    carries it and every PG of the pool is active on an up set without
    a hole.  Returns the instant the command was sent."""
    t_out = time.perf_counter()
    await rados.mon_command("osd out", {"osd_id": victim})
    epoch = cluster.mon.osdmap.epoch
    while True:
        live = [o for o in cluster.osds if not o.is_stopped()]
        if all(o.osdmap.epoch >= epoch for o in live) \
                and cluster.pg_states() == {"active": pool_pgs} \
                and all(min(pg.up) >= 0 for o in live
                        for pg in o.pgs.values() if pg.is_primary()):
            break
        if time.perf_counter() - t_out > timeout:
            raise HarnessError(f"PGs not active on their new up sets "
                               f"{timeout}s after osd out: "
                               f"{cluster.pg_states()}")
        await asyncio.sleep(0.1)
    say(f"osd.{victim} marked out (map epoch {epoch}), every PG active on "
        f"its new up set {time.perf_counter() - t_out:.1f}s later")
    return t_out


def pending(cluster) -> bool:
    return any(o.has_pending_recovery() for o in cluster.osds
               if not o.is_stopped())


def pushes_between(spans: list[dict], lo: float, hi: float) -> list[dict]:
    """The ``pg.backfill_push`` spans that ended in [lo, hi] (seconds
    of the wall clock the program's spans are stamped with)."""
    return [s for s in spans if s["name"] == PUSH and s["end"] is not None
            and lo <= s["end"] <= hi]


def backfill_spans() -> list[dict]:
    """Every finished span of a push's tree still in the rings."""
    from ceph_tpu.common import tracing
    return [s.to_dict() for t in list(tracing._TRACERS.values())
            for s in list(t.finished) if s.name in PUSH_TREE]


def shards_by_map(cluster, up: list[int], pgid: str, oid: str,
                  stored_as: dict) -> dict:
    """What the OSDs of the monitor's up set hold of one object, by
    position: {shard: (bytes, crc, label)}."""
    by_id = {o.whoami: o for o in cluster.osds if not o.is_stopped()}
    found = {}
    for shard, osd_id in enumerate(up):
        osd = by_id.get(osd_id)
        pg = osd.pgs.get(pgid) if osd is not None else None
        if pg is None:
            continue
        try:
            raw = osd.store.read(pg.coll, oid, 0, None)
        except FileNotFoundError:
            continue
        crc, label = (osd.store.getattr(pg.coll, oid, stored_as[name])
                      for name in ("crc_xattr", "shard_xattr"))
        found[shard] = (bytes(raw), None if crc is None else int(crc),
                        None if label is None else int(label))
    return found


async def _measure(cell, seed: int, seconds: float, traced: bool,
                   meter) -> dict:
    from ceph_tpu.client.rados import Rados
    from ceph_tpu.loadgen.cluster import SimCluster

    cfg, mix = cell.config, cell.traffic
    if mix["op"] != "write_full":
        raise HarnessError(f"this driver writes whole objects, not "
                           f"{mix['op']!r}")
    profile, size = cfg["profile"], int(mix["object_bytes"])
    n_obj, victim = int(mix["populate_objects"]), \
        int(cfg["failure"]["victim"])
    pg_num = int(cfg["pool"]["pg_num"])
    loop = asyncio.get_running_loop()
    cluster = await SimCluster.create(
        int(cfg["cluster"]["osds"]),
        mon_config=cfg["cluster"]["mon_config"],
        osd_config=cfg["cluster"]["osd_config"])
    rados = None
    watcher = None
    trace = Trace(cell.name) if traced else None
    try:
        rados = await Rados(cluster.addr, name="client.benchmark").connect()
        await rados.mon_command("osd erasure-code-profile set", {
            "name": "bench-profile",
            "profile": {key: str(val) for key, val in profile.items()}})
        await rados.pool_create(cfg["pool"]["name"], pg_num=pg_num,
                                pool_type="erasure",
                                erasure_code_profile="bench-profile")
        ioctx = await rados.open_ioctx(cfg["pool"]["name"])
        say(f"cluster up: {len(cluster.osds)} OSDs (ids in boot order "
            f"{[o.whoami for o in cluster.osds]}), pool "
            f"{cfg['pool']['name']} pg_num {pg_num}")

        t0 = time.perf_counter()
        await _populate(ioctx, seed, n_obj, size,
                        int(mix["populate_in_flight"]))
        say(f"{n_obj} objects of {size} bytes written and acknowledged in "
            f"{time.perf_counter() - t0:.1f}s")
        await _fail_victim(cluster, victim, pg_num)
        before = pool_mapping(cluster.mon.osdmap, ioctx.pool_id, pg_num)
        t_out = await _mark_out(cluster, rados, victim, pg_num)
        after = pool_mapping(cluster.mon.osdmap, ioctx.pool_id, pg_num)
        moved_pgs, moved, held = positions_moved(before, after)
        requeued = cluster.perf_counters("ec_recovery").get(
            "backfill_positions_moved", 0)
        say(f"of {pg_num} PGs {held} held osd.{victim}; in {moved_pgs} a "
            f"survivor serves another position than before ({moved} "
            f"positions moved, {requeued} shards queued for re-recovery: "
            f"ec_recovery backfill_positions_moved)")

        # when the cluster is clean again: watched from here to the end
        clean = {"at": None}

        async def watch_clean() -> None:
            while pending(cluster):
                await asyncio.sleep(POLL_S)
            clean["at"] = time.perf_counter()

        watcher = loop.create_task(watch_clean())

        def counters() -> dict:
            return {name: cluster.perf_counters(name)
                    for name in COUNTER_SETS}

        def deltas(prefix: str, before: dict, out: dict) -> None:
            for name, after in counters().items():
                counter_delta(f"{prefix}.{name}", before[name], after, out)

        records: list[tuple[int, float, float, bool]] = []
        errors: list[str] = []
        state = {"next": 0, "stop": False}

        async def writer() -> None:
            while not state["stop"]:
                i = state["next"]
                state["next"] += 1
                data = new_object_bytes(seed, i, size)
                t0 = time.perf_counter()
                try:
                    await ioctx.write_full(f"new-{i}", data)
                    ok = True
                except Exception as e:       # a failed op is data
                    ok = False
                    if len(errors) < 5:
                        errors.append(f"new-{i}: {type(e).__name__}: {e}")
                records.append((i, t0, time.perf_counter(), ok))

        writers = [loop.create_task(writer())
                   for _ in range(int(mix["in_flight"]))]
        while len(records) < int(mix["warmup_ops"]):
            await asyncio.sleep(0.02)
            if all(w.done() for w in writers):
                break

        # ---- the window -----------------------------------------------------
        t_open, wall_open = time.perf_counter(), time.time()
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
            deltas("slice", c0, facts)
        else:
            await asyncio.sleep(seconds)
        t_close, wall_close = time.perf_counter(), time.time()
        cpu_s = time.process_time() - cpu0
        compiles = meter.programs - programs0
        deltas("window", c_open, facts)
        # the rings hold 2048 spans a daemon and the repair goes on:
        # what the window's pushes left is taken now
        spans = backfill_spans()
        downs = sum(MARKED_DOWN in e["message"]
                    for e in cluster.mon.services.cluster_log)

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

        # ---- until clean: outside the window --------------------------------
        limit = float(mix["clean_timeout_s"])
        try:
            await asyncio.wait_for(asyncio.shield(watcher), limit)
        except asyncio.TimeoutError:
            pass
        not_clean = clean["at"] is None
        t_clean = time.perf_counter() if not_clean else clean["at"]
        say(f"from osd out to the window's end {t_close - t_out:.1f}s, "
            + (f"not clean {limit:.0f}s after the window: "
               f"{cluster.pg_states()}" if not_clean else
               f"to clean {t_clean - t_out:.1f}s"))
        active_s = max(0.0, min(t_clean, t_close) - t_open)

        # ---- correct: outside the window ------------------------------------
        pushed = pushes_between(spans, wall_open, wall_close)
        decoded = {s["parent_id"] for s in spans
                   if s["name"] == "ec.recover_decode"}
        # rebuilt: a shard of it came out of a decode, not off a
        # survivor that held it whole
        rebuilt = sorted({(s["tags"]["pgid"], s["tags"]["oid"])
                          for s in pushed if s["span_id"] in decoded
                          and s["tags"]["oid"].startswith("obj-")})
        rng = np.random.default_rng([seed, 0xC0FFEE])
        n_new, n_reb = int(mix["check_new_objects"]), \
            int(mix["check_rebuilt_objects"])
        new_picks = [f"new-{int(i)}" for i in rng.choice(
            acked, size=min(n_new, len(acked)), replace=False)] \
            if acked else []
        reb_picks = [rebuilt[int(i)][1] for i in rng.choice(
            len(rebuilt), size=min(n_reb, len(rebuilt)), replace=False)] \
            if rebuilt else []
        by_half = {half: {"readback_differs": 0, "shards_missing": 0,
                          "shard_bytes_wrong": 0, "crc_xattr_wrong": 0,
                          "shard_label_wrong": 0}
                   for half in ("new", "rebuilt")}
        osdmap = cluster.mon.osdmap
        t_check = time.perf_counter()
        for half, oid in [("new", o) for o in new_picks] \
                + [("rebuilt", o) for o in reb_picks]:
            i = int(oid.split("-")[1])
            payload = (new_object_bytes if half == "new"
                       else object_bytes)(seed, i, size)
            try:
                got = await ioctx.read(oid)
            except Exception as e:       # unreadable is as wrong as it gets
                got = None
                errors.append(f"read {oid}: {type(e).__name__}: {e}")
            by_half[half]["readback_differs"] += got != payload
            _, ps = osdmap.object_to_pg(ioctx.pool_id, oid)
            up, _ = osdmap.pg_to_up_acting(ioctx.pool_id, ps)
            found = shards_by_map(cluster, up,
                                  osdmap.pg_name(ioctx.pool_id, ps), oid,
                                  cfg["stored_as"])
            for key, val in check_shards(found, profile, payload).items():
                by_half[half][key] += val
        faults = {key: by_half["new"][key] + by_half["rebuilt"][key]
                  for key in by_half["new"]}
        faults["not_clean"] = int(not_clean)
        correct = len(new_picks) == n_new and len(reb_picks) == n_reb \
            and not any(faults.values())
        say(f"correct={correct}: {len(new_picks)} of {n_new} objects "
            f"written inside the window and {len(reb_picks)} of {n_reb} "
            f"objects of the population rebuilt inside it ({len(rebuilt)} "
            f"were, by {len(pushed)} pushes) read back and with all "
            f"{profile['k'] + profile['m']} shards of the monitor's up "
            f"set against the reference, in "
            f"{time.perf_counter() - t_check:.1f}s; "
            + "; ".join(f"{k} {v} (limit 0)" for k, v in faults.items())
            + "; of which on the rebuilt half: " + ", ".join(
                f"{k} {v}" for k, v in by_half["rebuilt"].items()))
    finally:
        if watcher is not None:
            watcher.cancel()
        if rados is not None:
            await rados.shutdown()
        await cluster.stop()

    def window(name: str) -> dict:
        return {key.removeprefix(f"window.{name}."): val
                for key, val in facts.items()
                if key.startswith(f"window.{name}.")}

    w, rec, hedge = (window(name) for name in COUNTER_SETS)
    fifths = [sum(t_open + j * window_s / 5 <= r[2] < t_open + (j + 1)
                  * window_s / 5 for r in inside) for j in range(5)]
    push_fifths = [sum(wall_open + j * window_s / 5 <= s["end"]
                       < wall_open + (j + 1) * window_s / 5 for s in pushed)
                   for j in range(5)]
    say(f"ops in window: {len(inside)} finished ({failed} failed), "
        f"median {percentile(lat_ms, 50):.1f} ms, by fifth of the window "
        f"{fifths}" if lat_ms else "ops in window: none finished")
    say(f"repair in window: backfill pushes {rec.get('backfill_pushes', 0)}"
        f" (dirty {rec.get('backfill_dirty_pushes', 0)}), by fifth of the "
        f"window {push_fifths}; repair_reads {rec.get('repair_reads', 0)}: "
        f"global decodes {rec.get('repair_global_decodes', 0)}, relabeled "
        f"copies {rec.get('repair_relabeled_copies', 0)}, local repairs "
        f"{rec.get('repair_local_repairs', 0)}; bytes read "
        f"{rec.get('repair_bytes_read', 0)}, shipped "
        f"{rec.get('repair_bytes_shipped', 0)}; backfill active "
        f"{active_s:.1f}s of the window's {window_s:.1f}s")
    say(f"compiles_in_window {compiles} (must be 0); OSDs marked down "
        f"{downs}; launches: encode {w.get('encode_launches', 0)} "
        f"({w.get('encode_stripes', 0)} stripes), decode "
        f"{w.get('decode_launches', 0)} ({w.get('decode_stripes', 0)} "
        f"stripes), rmw {w.get('rmw_launches', 0)}, mesh "
        f"{w.get('mesh_launches', 0)}; fallback_ops "
        f"{w.get('fallback_ops', 0)}; sub-reads {hedge.get('subreads', 0)} "
        f"({hedge.get('subread_bytes', 0)} bytes), hedges fired "
        f"{hedge.get('hedges_fired', 0)}")
    for line in errors:
        say(f"error: {line}")

    flatten("config", cfg, facts)
    facts.update({"run.ops": len(acked), "run.cpu_s": cpu_s,
                  "run.window_s": window_s,
                  "run.backfill_active_s": active_s,
                  "run.out_to_close_s": t_close - t_out,
                  "run.out_to_clean_s": t_clean - t_out,
                  "run.rebuilt_in_window": len(rebuilt),
                  "run.pgs_positions_moved": moved_pgs,
                  "run.positions_moved": moved,
                  "run.shards_requeued": requeued,
                  "run.wall_open": wall_open, "run.wall_close": wall_close,
                  "spans.backfill": spans})
    facts.update({f"check.{key}": val for key, val in faults.items()})
    facts.update({f"check.{key}.{half}": val
                  for half, counts in by_half.items()
                  for key, val in counts.items()})
    end_to_end = {"setup_s": setup_s}
    if lat_ms:
        end_to_end["client_mibps"] = len(acked) * size / 2**20 / window_s
        end_to_end["op_p95_ms"] = percentile(lat_ms, 95)
    return {"correct": correct, "attempted": len(inside), "failed": failed,
            "end_to_end": end_to_end, "facts": facts,
            "trace_file": trace.file() if traced else None}


def run(cell, seed: int, seconds: float, traced: bool, meter) -> dict:
    from ceph_tpu.common import tracing
    if "recovery" not in tracing.SECTION_LAYERS:
        raise HarnessError("this program has no recovery layer in its "
                           "tracing (no pg.backfill_push span, no "
                           "recovery.* section): a backfill it runs cannot "
                           "be told from the clients' writes")
    mix, cfg = cell.traffic, cell.config
    buckets = launch_buckets(cfg["profile"], int(mix["object_bytes"]),
                             int(cfg["cluster"]["osd_config"]
                                 ["osd_ec_batch_max"]))
    t0 = time.perf_counter()
    warm_encodes(cfg["profile"], buckets)
    signatures, dense = precompile(cfg["profile"], buckets)
    say(f"encode launches of {buckets} stripes and the rebuild's decode "
        f"for {signatures} erasure signatures compiled or loaded in "
        f"{time.perf_counter() - t0:.1f}s ({meter.hits} cache hits, "
        f"{meter.misses} misses); the decode is "
        + ("one dense program for every signature" if dense
           else "a scheduled program per signature"))
    return asyncio.run(_measure(cell, seed, seconds, traced, meter))
