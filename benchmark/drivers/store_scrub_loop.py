"""Closed loop of librados writers against an in-process EC cluster
whose OSDs deep-scrub every PG, round and round, with silent
corruption planted on a few shards.

Set-up compiles every launch the run can make (the writes' encodes
with fused checksums, the repair's decode for every single-shard
erasure signature, the scrub's digest launch at every batch a chunk can
reach, before any daemon runs), boots monitor and OSDs with scheduled
scrubs off, creates the pool, writes the population, plants the
configuration's faults (reference/scrub.py draws them from ``--seed``)
each by one transaction on the store that holds the shard, turns
scheduling on by the monitor's ``config set osd_scrub_interval`` as an
operator would, and lets the writers run ``warmup_ops`` writes; the
window opens on the same running loop.  From ``config set`` on the
OSDs scrub as they schedule themselves: the driver makes no scrub
call.  A failed write is counted, never raised.

A primary keeps the last deep scrub of each PG and the PGs come round
again, so the driver reads every primary's kept results four times a
second from the plant on and keeps each one once; the same poll notes
whether a scrub chunk is open anywhere and how many chunks have been
compared.  After the window the writers drain and the driver waits
(outside the window, at most ``scrub_timeout_s``) until every PG has
finished a deep scrub begun after the faults were planted.  ``correct``:
the union of all reports is exactly the reference's set (none missed,
none other, no object written in the window among them, and the OSDs
counted as many errors as were collected); each faulted shard, read
from its OSD's store, is the reference's repaired shard with its label
and ``_crc``; cell 1's checks on a sample of the window's writes; a
sample of the population reads back; a chunk was compared in every
``progress_every_s`` of the window in which the driver's own poll ran.
"""

from __future__ import annotations

import asyncio
import time

import numpy as np

from benchmark.drivers.store_backfill_loop import new_object_bytes
from benchmark.drivers.store_closed_loop import (MARKED_DOWN, check_shards,
                                                 launch_buckets,
                                                 object_bytes, stored_shards)
from benchmark.drivers.store_closed_loop import precompile as warm_encodes
from benchmark.drivers.store_read_loop import _populate
from benchmark.harness import (HarnessError, Trace, counter_delta, elapsed,
                               flatten, percentile, say)
from benchmark.reference import scrub as ref

COUNTER_SETS = ("ec_batch", "scrub", "ec_hedge", "ec_recovery")
SCRUB_TREE = ("pg.scrub", "scrub.reserve", "scrub.chunk", "scrub.maps",
              "scrub.digest", "scrub.compare", "scrub.repair")
POLL_S = 0.25
FAULTS = ("missed", "false_reports", "errors_uncollected",
          "repaired_bytes_wrong", "repaired_crc_wrong",
          "repaired_label_wrong", "repaired_missing", "readback_differs",
          "shards_missing", "shard_bytes_wrong", "crc_xattr_wrong",
          "shard_label_wrong", "population_differs", "seconds_idle",
          "pgs_unscrubbed")


def repair_signatures(codec) -> list[tuple]:
    """The erasure signatures a scrub's repair decodes with: the bad
    shard is never a source of itself, every other shard is up, the
    primary gathers the codec's minimum set for it from the rest and
    names every shard outside it."""
    n = codec.get_chunk_count()
    found = set()
    for want in range(n):
        got = set(codec.minimum_to_decode({want}, set(range(n)) - {want}))
        found.add(tuple(sorted(set(range(n)) - got)))
    return sorted(found)


def precompile(profile: dict, buckets: list, shard_len: int,
               max_rows: int) -> tuple[int, list]:
    """The repair's decode for every signature of ``repair_signatures``
    at every launch batch, and the digest program for whole shards of
    ``shard_len`` bytes at every batch up to ``max_rows`` rows (a
    chunk's names and the writes that were in flight inside its range
    when it opened: an OSD digests all of them in one launch), through
    the launch engine the OSDs' batchers share process-wide.  Returns
    the number of signatures and the digest batches."""
    from ceph_tpu.ec import registry
    from ceph_tpu.ops.crc32c_batch import digest_lane
    from ceph_tpu.parallel.mesh_codec import MeshCodec

    k, unit = profile["k"], profile["stripe_unit"]
    codec = registry().factory(profile["plugin"], {
        "k": str(k), "m": str(profile["m"]),
        "technique": profile["technique"]})
    mesh = MeshCodec()
    signatures = repair_signatures(codec)
    for b in buckets:
        for erasures in signatures:
            mesh.decode(codec, erasures,
                        np.zeros((mesh.pad_batch(b), k, unit), np.uint8))
    rows = sorted({mesh.pad_batch(n) for n in range(1, max_rows + 1)})
    for b in rows:
        mesh.digest(np.zeros((b, digest_lane(shard_len)), np.uint8))
    return len(signatures), rows


def holder(cluster, pgid: str, shard: int):
    """(osd, pg) of the OSD that serves position ``shard`` of a PG."""
    for osd in cluster.osds:
        pg = osd.pgs.get(pgid)
        if pg is not None and osd.whoami in pg.acting \
                and pg.acting.index(osd.whoami) == shard:
            return osd, pg
    raise HarnessError(f"no OSD serves shard {shard} of pg {pgid}")


def plant_fault(cluster, pgid: str, fault: dict, stored_as: dict) -> None:
    """One fault, by one transaction on the store that holds the
    shard: the store's boundary drops the shard cache's copy."""
    from ceph_tpu.os.transaction import Transaction
    osd, pg = holder(cluster, pgid, fault["shard"])
    txn = Transaction()
    if fault["kind"] == "missing_shard":
        txn.remove(pg.coll, fault["oid"])
    elif fault["kind"] == "tag_rot":
        txn.setattr(pg.coll, fault["oid"], stored_as["crc_xattr"],
                    str(fault["crc"]).encode())
    else:
        raw = bytes(osd.store.read(pg.coll, fault["oid"], 0, None))
        txn.write(pg.coll, fault["oid"], fault["offset"],
                  ref.rotted(raw, fault["offset"]))
    osd.store.queue_transaction(txn)


def scrub_spans() -> list[dict]:
    """Every finished span of a scrub's tree still in the rings."""
    from ceph_tpu.common import tracing
    return [s.to_dict() for t in list(tracing._TRACERS.values())
            for s in list(t.finished) if s.name in SCRUB_TREE]


class Watch:
    """The poll: every primary's kept scrub results, each kept once by
    (pgid, started); per sample whether a chunk is open anywhere and
    the chunks compared so far."""

    def __init__(self, cluster) -> None:
        self.cluster = cluster
        self.results: dict[tuple[str, float], dict] = {}
        self.samples: list[tuple[float, bool, int]] = []

    def poll(self) -> None:
        running = False
        for osd in self.cluster.osds:
            running |= bool(osd.scrubs_running())
            for pgid, res in list(osd.scrub_results.items()):
                self.results.setdefault((pgid, res["started"]), res)
        self.samples.append((
            time.perf_counter(), running,
            self.cluster.perf_counters("scrub").get("chunks", 0)))

    async def run(self) -> None:
        while True:
            self.poll()
            await asyncio.sleep(POLL_S)

    def scrubbed_since(self, wall: float) -> set[str]:
        """PGs with a finished deep scrub that began after ``wall``."""
        return {pgid for (pgid, started), res in self.results.items()
                if started >= wall and res["stamp"] is not None}

    def seconds(self, lo: float, hi: float, every: float
                ) -> tuple[int, int, int, int]:
        """Over the whole seconds of [lo, hi]: how many there are and
        in how many a sample saw a chunk open or the count of chunks
        move; over its spans of ``every`` seconds: in how many no chunk
        was compared, and how many were left unjudged because the poll
        itself ran in under half of them (the whole process was off
        the CPU, PERF.md section 6: nothing could make progress, the
        clients neither)."""
        inside = [s for s in self.samples if lo <= s[0] <= hi]
        whole = int(hi - lo)
        active = 0
        for n in range(whole):
            sec = [s for s in inside if lo + n <= s[0] < lo + n + 1]
            before = [s for s in self.samples if s[0] < lo + n]
            moved = bool(sec) and sec[-1][2] > (
                before[-1][2] if before else 0)
            active += moved or any(s[1] for s in sec)
        idle = stalled = 0
        for n in range(int((hi - lo) // every)):
            a, b = lo + n * every, lo + (n + 1) * every
            span = [s[2] for s in self.samples if a - POLL_S <= s[0] <= b]
            if len(span) < 0.5 * every / POLL_S:
                stalled += 1
            else:
                idle += span[-1] == span[0]
        return whole, active, idle, stalled


async def _measure(cell, seed: int, seconds: float, traced: bool,
                   meter) -> dict:
    from ceph_tpu.client.rados import Rados
    from ceph_tpu.loadgen.cluster import SimCluster

    cfg, mix = cell.config, cell.traffic
    if mix["op"] != "write_full":
        raise HarnessError(f"this driver writes whole objects, not "
                           f"{mix['op']!r}")
    profile, size = cfg["profile"], int(mix["object_bytes"])
    n_obj, per_kind = int(mix["populate_objects"]), \
        int(mix["faults_per_kind"])
    pg_num = int(cfg["pool"]["pg_num"])
    osd_config = dict(cfg["cluster"]["osd_config"])
    interval = osd_config.pop("osd_scrub_interval")
    loop = asyncio.get_running_loop()
    cluster = await SimCluster.create(
        int(cfg["cluster"]["osds"]),
        mon_config=cfg["cluster"]["mon_config"], osd_config=osd_config)
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
        say(f"cluster up: {len(cluster.osds)} OSDs, pool "
            f"{cfg['pool']['name']} pg_num {pg_num}, scheduled scrubs off")

        t0 = time.perf_counter()
        await _populate(ioctx, seed, n_obj, size,
                        int(mix["populate_in_flight"]))
        say(f"{n_obj} objects of {size} bytes written and acknowledged in "
            f"{time.perf_counter() - t0:.1f}s")

        def pg_of(oid: str) -> str:
            return rados.objecter.calc_target(ioctx.pool_id, oid)[0]

        faults = ref.plant(seed, profile, n_obj, size, per_kind)
        for fault in faults:
            plant_fault(cluster, pg_of(fault["oid"]), fault,
                        cfg["stored_as"])
        if any(o.scrub_results for o in cluster.osds):
            raise HarnessError("a scrub ran before scheduling was on")
        wall_planted = time.time()
        watch = Watch(cluster)
        watcher = loop.create_task(watch.run())
        await rados.mon_command("config set", {
            "who": "osd", "name": "osd_scrub_interval", "value": interval})
        t_on = time.perf_counter()
        while not all(float(o.config.get("osd_scrub_interval", 0))
                      == float(interval) for o in cluster.osds):
            if time.perf_counter() - t_on > 30:
                raise HarnessError("osd_scrub_interval did not reach "
                                   "every OSD in 30 s")
            await asyncio.sleep(0.05)
        say(f"{len(faults)} faults planted on {len(faults)} objects ("
            + ", ".join(f"{sum(f['kind'] == k for f in faults)} {k}"
                        for k in ref.KINDS)
            + f"); osd_scrub_interval {interval} s set through the "
            f"monitor, on every OSD {time.perf_counter() - t_on:.2f}s later")

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
            c0, t_mark = counters(), time.perf_counter()
            with trace.mark():
                await asyncio.sleep(slice_s)
                # a twelfth of the bytes take the device route (what is
                # resident is what was written in the last seconds), so a
                # slice may hold no digest launch, and three metrics read
                # one: the slice stays open until a launch's bytes are
                # counted, for at most trace_slice_max_s in all
                while cluster.perf_counters("scrub").get(
                        "bytes_digested_device", 0) == c0["scrub"].get(
                        "bytes_digested_device", 0) \
                        and time.perf_counter() - t_mark < float(
                            mix["trace_slice_max_s"]):
                    await asyncio.sleep(POLL_S)
            facts["run.slice_s"] = time.perf_counter() - t_mark
            deltas("slice", c0, facts)
        else:
            await asyncio.sleep(seconds)
        t_close, wall_close = time.perf_counter(), time.time()
        cpu_s = time.process_time() - cpu0
        compiles = meter.programs - programs0
        deltas("window", c_open, facts)
        # the rings hold 2048 spans a daemon and the scrubs go on: what
        # the window's chunks left is taken now
        spans = scrub_spans()
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

        # ---- until every PG has been scrubbed: outside the window -----------
        limit = float(mix["scrub_timeout_s"])
        pgids = {pg_of(f"obj-{i}") for i in range(n_obj)}
        while len(watch.scrubbed_since(wall_planted) & pgids) < len(pgids) \
                and time.perf_counter() - t_close < limit:
            await asyncio.sleep(POLL_S)
        t_all = time.perf_counter()
        watcher.cancel()
        watch.poll()
        unscrubbed = len(pgids - watch.scrubbed_since(wall_planted))
        whole_s, active_s, idle, stalled = watch.seconds(
            t_open, t_close, float(mix["progress_every_s"]))
        say(f"every PG scrubbed {t_all - t_close:.1f}s after the window"
            if not unscrubbed else
            f"{unscrubbed} of {len(pgids)} PGs not scrubbed {limit:.0f}s "
            f"after the window")

        # ---- correct: outside the window ------------------------------------
        t_check = time.perf_counter()
        reported = [tuple(e) for res in watch.results.values()
                    for e in res["errors"]]
        found = cluster.perf_counters("scrub").get("errors_found", 0)
        counts = dict.fromkeys(FAULTS, 0)
        counts.update(ref.check_reports(reported, faults))
        counts["errors_uncollected"] = abs(found - len(reported))
        counts["seconds_idle"] = idle
        counts["pgs_unscrubbed"] = unscrubbed
        for fault in faults:
            osd, pg = holder(cluster, pg_of(fault["oid"]), fault["shard"])
            raw, crc, label = ref.repaired_shard(seed, profile, fault, size)
            try:
                got = bytes(osd.store.read(pg.coll, fault["oid"], 0, None))
            except FileNotFoundError:
                counts["repaired_missing"] += 1
                continue
            have_crc, have_label = (
                osd.store.getattr(pg.coll, fault["oid"],
                                  cfg["stored_as"][name])
                for name in ("crc_xattr", "shard_xattr"))
            counts["repaired_bytes_wrong"] += got != raw
            counts["repaired_crc_wrong"] += have_crc is None \
                or int(have_crc) != crc
            counts["repaired_label_wrong"] += have_label is None \
                or int(have_label) != label
        rng = np.random.default_rng([seed, 0xC0FFEE])
        n_read, n_shard, n_pop = (int(mix[key]) for key in (
            "readback_objects", "shard_check_objects",
            "check_population_objects"))
        picks = [int(i) for i in rng.choice(
            acked, size=min(n_read, len(acked)), replace=False)] \
            if acked else []
        pop_picks = [int(i) for i in rng.choice(n_obj, size=min(
            n_pop, n_obj), replace=False)]
        for n, i in enumerate(picks):
            payload = new_object_bytes(seed, i, size)
            try:
                got = await ioctx.read(f"new-{i}")
            except Exception as e:       # unreadable is as wrong as it gets
                got = None
                errors.append(f"read new-{i}: {type(e).__name__}: {e}")
            counts["readback_differs"] += got != payload
            if n < n_shard:
                shards = stored_shards(cluster, pg_of(f"new-{i}"),
                                       f"new-{i}", cfg["stored_as"])
                for key, val in check_shards(shards, profile,
                                             payload).items():
                    counts[key] += val
        for i in pop_picks:
            try:
                got = await ioctx.read(f"obj-{i}")
            except Exception as e:
                got = None
                errors.append(f"read obj-{i}: {type(e).__name__}: {e}")
            counts["population_differs"] += got != object_bytes(seed, i,
                                                               size)
        correct = len(picks) == n_read and not any(counts.values())
        by_kind = {}
        for oid, shard, kind in reported:
            by_kind[kind] = by_kind.get(kind, 0) + 1
        say(f"correct={correct}: {len(watch.results)} scrub results "
            f"collected from {len(pgids)} PGs, their reports "
            f"{dict(sorted(by_kind.items()))} against the reference's "
            f"{len(faults)} (errors_found {found}); {len(faults)} faulted "
            f"shards read from their stores against the reference's "
            f"repaired shards; {len(picks)} of {n_read} objects written "
            f"inside the window read back, {min(n_shard, len(picks))} with "
            f"all {profile['k'] + profile['m']} shards; {len(pop_picks)} "
            f"objects of the population read back; in "
            f"{time.perf_counter() - t_check:.1f}s; "
            + "; ".join(f"{k} {v} (limit 0)" for k, v in counts.items()))
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

    w, scr, hedge, rec = (window(name) for name in COUNTER_SETS)
    digested = scr.get("bytes_digested_host", 0) \
        + scr.get("bytes_digested_device", 0)
    fifths = [sum(t_open + j * window_s / 5 <= r[2] < t_open + (j + 1)
                  * window_s / 5 for r in inside) for j in range(5)]
    rounds = [sum(1 for (pgid, _) in watch.results if pgid == p)
              for p in pgids]
    say(f"ops in window: {len(inside)} finished ({failed} failed), "
        f"median {percentile(lat_ms, 50):.1f} ms, by fifth of the window "
        f"{fifths}" if lat_ms else "ops in window: none finished")
    say(f"scrub in window: chunks {scr.get('chunks', 0)}, objects "
        f"{scr.get('objects', 0)}, digested "
        f"{scr.get('bytes_digested_host', 0)} bytes on the host and "
        f"{scr.get('bytes_digested_device', 0)} on the device, maps "
        f"{scr.get('map_bytes', 0)} bytes, errors found "
        f"{scr.get('errors_found', 0)}, shards repaired "
        f"{scr.get('shards_repaired', 0)}, writes blocked "
        f"{scr.get('writes_blocked', 0)}, reservations rejected "
        f"{scr.get('reserve_rejects', 0)}; a chunk open or compared in "
        f"{active_s} of the window's {whole_s} whole seconds ({stalled} "
        f"spans of {mix['progress_every_s']} s unjudged: the poll did not "
        f"run in them); scrubs finished a PG from the plant to the end: "
        f"min {min(rounds)}, max {max(rounds)}")
    say(f"compiles_in_window {compiles} (must be 0); OSDs marked down "
        f"{downs}; launches: encode {w.get('encode_launches', 0)} "
        f"({w.get('encode_stripes', 0)} stripes), digest "
        f"{w.get('digest_launches', 0)} ({w.get('digest_stripes', 0)} "
        f"rows), decode {w.get('decode_launches', 0)}, mesh "
        f"{w.get('mesh_launches', 0)}; fallback_ops "
        f"{w.get('fallback_ops', 0)}; sub-reads {hedge.get('subreads', 0)} "
        f"({hedge.get('subread_bytes', 0)} bytes: a repair's gathers); "
        f"repair bytes shipped {rec.get('repair_bytes_shipped', 0)}")
    for line in errors:
        say(f"error: {line}")

    flatten("config", cfg, facts)
    facts.update({"run.ops": len(acked), "run.cpu_s": cpu_s,
                  "run.window_s": window_s,
                  "run.window_whole_s": whole_s,
                  "run.scrub_active_s": active_s,
                  "run.scrub_bytes_digested": digested,
                  "run.scrub_results": len(watch.results),
                  "run.all_scrubbed_after_s": t_all - t_close,
                  "run.spans_unjudged": stalled,
                  "run.wall_open": wall_open, "run.wall_close": wall_close,
                  "run.reports": sorted(reported),
                  "spans.scrub": spans})
    if "slice.scrub.bytes_digested_host" in facts:
        facts["slice.scrub.bytes_digested"] = \
            facts["slice.scrub.bytes_digested_host"] \
            + facts["slice.scrub.bytes_digested_device"]
    facts.update({f"check.{key}": val for key, val in counts.items()})
    end_to_end = {"setup_s": setup_s}
    if lat_ms:
        end_to_end["client_mibps"] = len(acked) * size / 2**20 / window_s
        end_to_end["op_p95_ms"] = percentile(lat_ms, 95)
    return {"correct": correct, "attempted": len(inside), "failed": failed,
            "end_to_end": end_to_end, "facts": facts,
            "trace_file": trace.file() if traced else None}


def run(cell, seed: int, seconds: float, traced: bool, meter) -> dict:
    from ceph_tpu.common import tracing
    if "scrub" not in tracing.SECTION_LAYERS:
        raise HarnessError("this program has no scrub layer in its "
                           "tracing (no pg.scrub span, no scrub.* "
                           "section, no scrub counters): it keeps no "
                           "result of a scheduled scrub to hold to the "
                           "reference")
    mix, cfg = cell.traffic, cell.config
    osd_config = cfg["cluster"]["osd_config"]
    buckets = launch_buckets(cfg["profile"], int(mix["object_bytes"]),
                             int(osd_config["osd_ec_batch_max"]))
    shard_len = ref.shard_bytes(cfg["profile"], int(mix["object_bytes"]))
    t0 = time.perf_counter()
    warm_encodes(cfg["profile"], buckets)
    signatures, rows = precompile(
        cfg["profile"], buckets, shard_len,
        int(osd_config["osd_scrub_chunk_max"]) + int(mix["in_flight"]))
    say(f"encode launches of {buckets} stripes, the repair's decode for "
        f"{signatures} erasure signatures and the digest launch of {rows} "
        f"rows of {shard_len} bytes compiled or loaded in "
        f"{time.perf_counter() - t0:.1f}s ({meter.hits} cache hits, "
        f"{meter.misses} misses)")
    return asyncio.run(_measure(cell, seed, seconds, traced, meter))
