"""Datapath bench rig: the OSD shard data spine, cached vs host path.

Drives write -> read-verify -> scrub -> degraded-read over REAL
BlockStores (one per shard, checksum-on-read, WAL group commit) with
the production primitives -- StripeInfo/CodecBatcher encode+decode
launches, fused write-time CRCs, and the DeviceShardCache
(os/device_cache.py) -- twice over identical inputs:

* **baseline** (``cached=False``): every consumer round-trips the
  store, exactly as the pre-cache pipeline did -- shard reads pay
  pread + per-block checksum verify + extent assembly, every gathered
  shard is re-hashed against its tag, scrub reads every shard back;
* **cached**: the write's encoded shards flow into residency, and the
  read-verify / scrub / degraded-decode phases serve from the cache --
  the ``datapath`` perf counters prove the steady phases move ZERO
  shard bytes through the store.

Byte-identity is asserted between the two runs (and against the
source data) before any number is reported -- a throughput without
parity is meaningless, as everywhere else in this repo.
"""

from __future__ import annotations

import asyncio
import os
import shutil
import tempfile
import time

import numpy as np

from ..ec import registry
from ..ops.crc32c_batch import PERF as INTEGRITY_PERF
from ..ops.crc32c_batch import crc32c_batch, crc32c_rows
from ..os.blockstore import BlockStore
from ..os.device_cache import DeviceShardCache, PERF as DATAPATH_PERF
from ..os.transaction import Transaction
from ..osd.codec_batcher import CodecBatcher
from ..osd.ec_util import StripeInfo

COLL = "pg_dp"
SIZE_XATTR = "_size"
CRC_XATTR = "_crc"


class _Rig:
    """k+m shard stores + a codec batcher + (optionally) shard caches:
    the single-process rendering of one EC PG's data plane."""

    def __init__(self, k: int, m: int, stripe_unit: int,
                 cached: bool, base_dir: str,
                 cache_bytes: int = 256 << 20) -> None:
        self.codec = registry().factory(
            "tpu", {"k": str(k), "m": str(m),
                    "technique": "reed_sol_van"})
        self.sinfo = StripeInfo.for_codec(self.codec,
                                          stripe_unit=stripe_unit)
        self.k, self.m = k, m
        self.batcher = CodecBatcher(max_batch=64, flush_timeout=0.05)
        self.cached = cached
        self.stores: list[BlockStore] = []
        for i in range(k + m):
            st = BlockStore(os.path.join(base_dir, f"shard{i}"))
            if cached:
                st.attach_shard_cache(DeviceShardCache(
                    max_bytes=cache_bytes))
            st.mount()
            st.queue_transaction(
                Transaction().create_collection(COLL))
            self.stores.append(st)
        # oid -> (size, shard_len, per-shard crc tags)
        self.meta: dict[str, tuple[int, int, list[int]]] = {}

    def close(self) -> None:
        self.batcher.close()
        for st in self.stores:
            st.umount()

    # -- phases ---------------------------------------------------------------
    async def write(self, objects: dict[str, bytes]) -> None:
        """Encode (fused CRC) + commit every object; the encode output
        flows into residency when caching is on.  Commits coalesce into
        one transaction per shard store (the group-commit shape)."""
        sw = self.sinfo.stripe_width

        async def enc(oid, data):
            padded = data + b"\0" * (
                self.sinfo.logical_to_next_stripe_offset(len(data))
                - len(data))
            shards, crcs = await self.sinfo.encode_async(
                self.codec, padded, batcher=self.batcher,
                with_crc=True)
            return oid, data, shards, crcs

        encoded = await asyncio.gather(
            *(enc(oid, data) for oid, data in objects.items()))
        txns = [Transaction() for _ in self.stores]
        puts = []
        for oid, data, shards, crcs in encoded:
            shard_len = self.sinfo.object_size_to_shard_size(len(data))
            self.meta[oid] = (len(data), shard_len,
                              [int(crcs[s]) for s in range(len(
                                  self.stores))])
            for s, txn in enumerate(txns):
                buf = shards[s].tobytes()
                txn.write(COLL, oid, 0, buf)
                txn.setattr(COLL, oid, SIZE_XATTR,
                            str(len(data)).encode())
                txn.setattr(COLL, oid, CRC_XATTR,
                            str(int(crcs[s])).encode())
                if self.cached:
                    puts.append((s, oid, shards[s], len(data),
                                 int(crcs[s])))
        for st, txn in zip(self.stores, txns):
            st.queue_transaction(txn)
        for s, oid, buf, size, crc in puts:
            self.stores[s].shard_cache.put(
                COLL, oid, buf, size=size, ver=(1, 1), shard=s,
                crc=crc)

    def _shard(self, s: int, oid: str) -> np.ndarray:
        """One shard's bytes: residency first, else the store's
        checksum-on-read path (counted as a host round trip).  The
        baseline also pays the identity-xattr lookups the resident
        entry carries for free -- exactly what ``_local_entry``
        replaced in the OSD read path."""
        st = self.stores[s]
        if self.cached:
            e = st.shard_cache.get(COLL, oid)
            if e is not None:
                return e.buf
        raw = st.read(COLL, oid, 0, None)
        st.getattr(COLL, oid, SIZE_XATTR)
        st.getattr(COLL, oid, CRC_XATTR)
        DATAPATH_PERF.inc("host_reads")
        DATAPATH_PERF.inc("host_bytes_read", len(raw))
        return np.frombuffer(raw, np.uint8)

    async def read_verify(self, oids: list[str]) -> dict[str, bytes]:
        """The client read path: gather the k data shards, verify tags
        (residency is trusted -- verified at write time), interleave
        logical bytes.  Objects submit CONCURRENTLY so their decode
        work coalesces in the batcher, as concurrent client ops do."""
        async def one(oid):
            bufs = {s: self._shard(s, oid) for s in range(self.k)}
            if not self.cached:
                tags = self.meta[oid][2]
                got = crc32c_batch([bufs[s] for s in range(self.k)])
                for s in range(self.k):
                    if int(got[s]) != tags[s]:
                        raise RuntimeError(f"tag mismatch {oid}/{s}")
            data = await self.sinfo.reconstruct_logical_async(
                self.codec, bufs, batcher=self.batcher)
            return oid, data[:self.meta[oid][0]]

        return dict(await asyncio.gather(*(one(o) for o in oids)))

    async def scrub(self, oids: list[str]) -> None:
        """Deep-scrub verify.

        Cached: the write-time tags were computed IN the encode launch
        that produced the parity, so verifying every resident shard's
        CRC against its tag in ONE batched pass attests the parity
        relationship transitively -- zero store reads, zero re-encode
        (the deep scrub's fast path, osd/scrub.py).  Baseline: the
        pre-cache deep scrub -- read every shard back through the store, reconstruct the
        logical object, RE-ENCODE it, byte-compare every stored shard
        against the canonical encode."""
        if self.cached:
            rows, want = [], []
            for oid in oids:
                tags = self.meta[oid][2]
                for s in range(len(self.stores)):
                    rows.append(self._shard(s, oid))
                    want.append(tags[s])
            lens = {r.size for r in rows}
            if len(lens) == 1:
                got = crc32c_rows(np.stack(rows))
            else:
                got = crc32c_batch(rows)
            bad = [i for i in range(len(rows))
                   if int(got[i]) != want[i]]
            if bad:
                raise RuntimeError(f"scrub mismatch at {bad[:4]}")
            DATAPATH_PERF.inc("scrub_fast_verifies", len(oids))
            return

        async def one(oid):
            stored = {s: self._shard(s, oid)
                      for s in range(len(self.stores))}
            logical = await self.sinfo.reconstruct_logical_async(
                self.codec, {s: stored[s] for s in range(self.k)},
                batcher=self.batcher)
            canonical = await self.sinfo.encode_async(
                self.codec, logical, batcher=self.batcher)
            for s in range(len(self.stores)):
                if not np.array_equal(canonical[s], stored[s]):
                    raise RuntimeError(f"scrub mismatch {oid}/{s}")

        await asyncio.gather(*(one(o) for o in oids))

    async def degraded_read(self, oids: list[str],
                            down: int) -> dict[str, bytes]:
        """Reads with data shard ``down`` erased: decode from the k
        surviving shards minimum_to_decode picks (cache-resident when
        on) and rebuild the logical bytes.  Concurrent submission, so
        every object's reconstruction shares one decode launch."""
        keep = [s for s in range(len(self.stores)) if s != down][
            :self.k]

        async def one(oid):
            survivors = {s: self._shard(s, oid) for s in keep}
            if not self.cached:
                tags = self.meta[oid][2]
                got = crc32c_batch([survivors[s] for s in keep])
                for s, g in zip(keep, got):
                    if int(g) != tags[s]:
                        raise RuntimeError(f"tag mismatch {oid}/{s}")
            data = await self.sinfo.reconstruct_logical_async(
                self.codec, survivors, batcher=self.batcher)
            return oid, data[:self.meta[oid][0]]

        return dict(await asyncio.gather(*(one(o) for o in oids)))


async def _drive(cached: bool, *, k: int, m: int, n_objects: int,
                 obj_bytes: int, passes: int, reads_per_pass: int,
                 stripe_unit: int, base_dir: str,
                 seed: int = 7) -> dict:
    rng = np.random.default_rng(seed)
    objects = {
        f"obj-{i:04d}": rng.integers(
            0, 256, obj_bytes, dtype=np.uint8).tobytes()
        for i in range(n_objects)}
    oids = sorted(objects)
    rig = _Rig(k, m, stripe_unit, cached, base_dir)
    phases: dict[str, dict] = {}
    digests: dict[str, int] = {}
    try:
        def snap():
            return {key: DATAPATH_PERF.get(key) for key in
                    ("hits", "misses", "host_reads",
                     "host_bytes_read", "host_bytes_avoided",
                     "evictions")} | {
                "scalar_calls": INTEGRITY_PERF.get("scalar_calls")}

        async def timed(name, fn, nbytes):
            before = snap()
            t0 = time.perf_counter()
            res = fn()
            if asyncio.iscoroutine(res):
                res = await res
            dt = time.perf_counter() - t0
            after = snap()
            phases[name] = {
                "seconds": round(dt, 4),
                "GiBps": round(nbytes / dt / 2**30, 3),
                "bytes": nbytes,
                "counters": {key: after[key] - before[key]
                             for key in after}}
            return res

        logical = n_objects * obj_bytes
        stored = sum(rig.sinfo.object_size_to_shard_size(obj_bytes)
                     for _ in range(k + m)) * n_objects
        # degraded reads hit a subset: with one shard down, only the
        # objects a client actually touches during the recovery window
        # pay the decode -- not the whole population every pass
        degr_oids = oids[:max(2, len(oids) // 12)]
        await timed("write", lambda: rig.write(objects), logical)
        reads = degraded = {}
        for p in range(passes):
            # the steady-state serving mix: hot read-verifies (the
            # dominant op in a Zipf read-mostly workload), a deep-scrub
            # verify sweep, and degraded-read decodes
            for r in range(reads_per_pass):
                reads = await timed(
                    f"read_verify_{p}_{r}",
                    lambda: rig.read_verify(oids), logical)
            await timed(f"scrub_{p}", lambda: rig.scrub(oids), stored)
            degraded = await timed(
                f"degraded_read_{p}",
                lambda: rig.degraded_read(degr_oids, down=0),
                len(degr_oids) * obj_bytes)
        # byte-identity gates: reads and degraded reads must equal the
        # source bytes exactly
        for oid in oids:
            if reads[oid] != objects[oid]:
                raise RuntimeError(f"read parity failure {oid}")
        for oid in degr_oids:
            if degraded[oid] != objects[oid]:
                raise RuntimeError(
                    f"degraded-read parity failure {oid}")
        import zlib
        digests = {oid: zlib.crc32(reads[oid]) for oid in oids}
        digests.update({f"{oid}@degraded": zlib.crc32(degraded[oid])
                        for oid in degr_oids})
    finally:
        rig.close()
    total_s = sum(ph["seconds"] for ph in phases.values())
    total_b = sum(ph["bytes"] for ph in phases.values())
    steady = {key: sum(
        ph["counters"][key] for name, ph in phases.items()
        if not name.startswith("write"))
        for key in ("hits", "host_bytes_read", "host_reads",
                    "host_bytes_avoided", "scalar_calls")}
    return {"cached": cached,
            "end_to_end_GiBps": round(total_b / total_s / 2**30, 3),
            "seconds": round(total_s, 4),
            "bytes": total_b,
            "phases": phases,
            "steady_counters": steady,
            "digests": digests}


def _bench_dir() -> str:
    """Shard stores live on tmpfs when available: the bench measures
    the DATA PATH, not the container filesystem's fsync latency (which
    both sides pay identically in the write phase)."""
    for base in ("/dev/shm", None):
        try:
            return tempfile.mkdtemp(prefix="ceph_tpu_dp_", dir=base)
        except OSError:
            continue
    return tempfile.mkdtemp(prefix="ceph_tpu_dp_")


async def run_datapath_bench(*, k: int = 4, m: int = 2,
                             n_objects: int = 24,
                             obj_bytes: int = 256 << 10,
                             passes: int = 10,
                             reads_per_pass: int = 5,
                             stripe_unit: int = 4096,
                             keep_dirs: bool = False) -> dict:
    """Both drives over identical inputs + the comparison report.

    Gates (the caller turns violations into a non-zero exit):
    * byte identity: cached and baseline reads/degraded-reads return
      identical bytes (and both equal the source data);
    * cache effectiveness: hit-rate > 0 and the cached steady phases
      (read-verify / scrub / degraded-read) moved ZERO bytes through
      the store;
    * zero scalar CRC calls in the steady phases (the write phase's
      WAL record framing CRCs are metadata, not shard payload).
    """
    base_dir = _bench_dir()
    try:
        kwargs = dict(k=k, m=m, n_objects=n_objects,
                      obj_bytes=obj_bytes, passes=passes,
                      reads_per_pass=reads_per_pass,
                      stripe_unit=stripe_unit)
        # warmup: one full-shape baseline drive compiles every launch
        # family (write encode, scrub re-encode, degraded decode) at
        # the SAME batch buckets the timed drives use, so neither side
        # pays first-run jit costs -- compile asymmetry would flatter
        # whichever drive runs second
        await _drive(False, base_dir=os.path.join(base_dir, "warm"),
                     **{**kwargs, "passes": 1, "reads_per_pass": 1})
        baseline = await _drive(False, base_dir=os.path.join(
            base_dir, "base"), **kwargs)
        cached = await _drive(True, base_dir=os.path.join(
            base_dir, "cached"), **kwargs)
    finally:
        if not keep_dirs:
            shutil.rmtree(base_dir, ignore_errors=True)
    if baseline["digests"] != cached["digests"]:
        raise RuntimeError(
            "byte-identity failure: cached reads differ from the "
            "host-round-trip baseline")
    for run in (baseline, cached):
        run.pop("digests")
    steady = cached["steady_counters"]
    ratio = (cached["end_to_end_GiBps"]
             / max(baseline["end_to_end_GiBps"], 1e-9))
    return {
        "k": k, "m": m, "n_objects": n_objects,
        "obj_bytes": obj_bytes, "passes": passes,
        "reads_per_pass": reads_per_pass,
        "datapath_GiBps": cached["end_to_end_GiBps"],
        "baseline_GiBps": baseline["end_to_end_GiBps"],
        "vs_host_roundtrip": round(ratio, 2),
        "cache_hits": steady["hits"],
        "steady_host_bytes_read": steady["host_bytes_read"],
        "steady_host_reads": steady["host_reads"],
        "host_bytes_avoided": steady["host_bytes_avoided"],
        "scalar_calls_on_batched_paths": steady["scalar_calls"],
        "parity": "ok",
        "cached_run": cached,
        "baseline_run": baseline,
    }
