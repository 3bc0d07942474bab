"""Round benchmark: erasure-code throughput on TPU vs the CPU baseline.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.

Headline config (BASELINE.md): RS k=8 m=3, 1 MiB stripes, device-
resident stripe batches, single chip, encode+decode combined
(harmonic).  Byte parity vs the host oracle is asserted before timing
-- a number without parity is meaningless.

Secondary configs (each its own entry under "configs"):
  * cauchy_k10m4_decode: Cauchy k=10,m=4, 2-erasure decode (the
    matrix-inverse path), 1 MiB stripes.
  * rs_k8m3_4k_marshal: RS k=8,m=3 on 4 KiB chunks INCLUDING the
    host->device upload -- the marshaling-bound regime the reference's
    ISA-L benchmark runs in (SURVEY hard part d).
  * crush_10m: 10M PG->OSD straw2 mappings over a 1000-OSD map
    (vectorized placement; value in M mappings/s).

Modes: --osd-path drives the OSD data path (see _osd_path_mode);
--placement measures the epoch-memoized placement cache -- bulk
epoch-recompute throughput (pg/s) vs the per-PG scalar loop plus
cached lookup latency (--smoke = tier-1 fused-parity tripwire);
--cluster runs the closed-loop traffic harness (ceph_tpu/loadgen):
a client swarm against an in-process >=64-OSD cluster with an OSD
kill mid-run, reporting ops/s + tail latency per op class and
recovery interference (--smoke = tier-1 zero-failed-ops tripwire).

vs_baseline is the repo's own native C++ AVX2 encoder (native/gf8.cc,
ISA-L's split-nibble SIMD technique, single thread) -- stated plainly:
this is an ISA-L-technique reimplementation, not a linked ISA-L build
(none exists in this image).  Role analog:
src/test/erasure-code/ceph_erasure_code_benchmark.cc:155-193.

Harness discipline:
  * stripe batches are GENERATED ON DEVICE and stay resident in HBM
    (the deployment shape) except the 4k marshaling config, which
    deliberately times the upload;
  * progress lines go to stderr immediately at every phase;
  * everything runs in THIS process on whatever backend jax gives it
    (an accelerator belongs to one process at a time), and the result
    names that backend;
  * any config that raises fails the run: the JSON line carries the
    error and the exit code is non-zero.  Nothing is retried at a
    smaller size and no earlier result is ever substituted.
"""

import json
import os
import sys
import time

import numpy as np

T0 = time.monotonic()
RESULT = {
    "metric": "ec_rs_k8m3_encode_decode_GiBps_tpu_vs_cpu_avx2",
    "value": 0.0,
    "unit": "GiB/s",
    "vs_baseline": 0.0,
}
_EMITTED = False


def log(msg: str) -> None:
    print(f"[bench +{time.monotonic() - T0:6.1f}s] {msg}",
          file=sys.stderr, flush=True)


def emit() -> None:
    global _EMITTED
    if not _EMITTED:
        _EMITTED = True
        print(json.dumps(RESULT), flush=True)


def _device_batch(rng, batch, k, chunk):
    """(batch, k, chunk) random bytes, device-resident, tiny host upload.

    A small host-random seed block is tiled on device: GF math is
    data-independent so timing is unaffected, and parity correctness
    is validated separately on fully random data.
    """
    import jax
    import jax.numpy as jnp
    seed_rows = min(batch, 8)
    seed = rng.integers(0, 256, size=(seed_rows, k, chunk), dtype=np.uint8)
    dev = jax.device_put(seed)
    reps = batch // seed_rows
    out = jnp.tile(dev, (reps, 1, 1))
    out.block_until_ready()
    return out


def _time_launches(fn, block, min_iters=3, max_iters=12):
    """Simple timing: async dispatch loop, block at the end (about
    3 s of launches, between min_iters and max_iters)."""
    out = fn()
    block(out)                      # warm / compile
    t1 = time.perf_counter()
    out = fn()
    block(out)
    per = time.perf_counter() - t1  # one-launch estimate
    iters = max(min_iters, min(max_iters, int(3.0 / max(per, 1e-4))))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn()
    block(out)
    return (time.perf_counter() - t0) / iters, iters, out


def _headline(rng):
    from ceph_tpu.gf import gen_rs_matrix, gf_matmul
    from ceph_tpu.ec import registry
    import jax.numpy as jnp

    k, m = 8, 3
    stripe = 1 << 20
    chunk = stripe // k
    batch = int(os.environ.get("BENCH_BATCH", "512"))
    batch = max(8, (batch // 8) * 8)
    gen = gen_rs_matrix(k + m, k)
    codec = registry().factory("tpu", {"k": str(k), "m": str(m),
                                       "technique": "reed_sol_van"})

    log("parity gate: 4 stripes x 4 KiB vs host GF oracle")
    sample = rng.integers(0, 256, size=(4, k, 4096), dtype=np.uint8)
    got = np.asarray(codec.encode_batch(sample, out_np=True))
    for b in range(4):
        want = gf_matmul(gen[k:], sample[b])
        if not np.array_equal(got[b], want):
            raise RuntimeError("byte parity failure")
    log("parity gate passed")

    log(f"staging {batch * k * chunk / 2**30:.2f} GiB on device "
        f"(batch={batch})")
    data = _device_batch(rng, batch, k, chunk)

    log("encode: compile + timing")
    enc_dt, enc_iters, parity = _time_launches(
        lambda: codec.encode_batch(data),
        lambda o: o.block_until_ready())
    gibps = batch * k * chunk / enc_dt / 2**30
    log(f"encode: {gibps:.1f} GiB/s ({enc_iters} iters, "
        f"{enc_dt*1e3:.2f} ms/launch)")

    erasures = [1, 9]
    decode_index = [i for i in range(k + m) if i not in erasures][:k]
    full = jnp.concatenate([data, parity], axis=1)
    full.block_until_ready()
    lost = full[:, jnp.asarray(erasures)]
    survivors = full[:, jnp.asarray(decode_index)]
    survivors.block_until_ready()
    del data, parity, full
    log("decode: compile + timing")
    dec_dt, dec_iters, rec = _time_launches(
        lambda: codec.decode_batch(erasures, survivors),
        lambda o: o.block_until_ready())
    dec_gibps = batch * k * chunk / dec_dt / 2**30
    log(f"decode: {dec_gibps:.1f} GiB/s ({dec_iters} iters)")
    if not bool(jnp.array_equal(rec, lost)):
        raise RuntimeError("decode parity failure")
    log("decode recovered chunks byte-exact")
    return {"encode_GiBps": round(gibps, 2),
            "decode_GiBps": round(dec_gibps, 2),
            "batch": batch, "stripe_bytes": stripe}


def _cauchy_decode(rng):
    """Cauchy k=10,m=4, 2-erasure decode: the matrix-inverse path."""
    from ceph_tpu.ec import registry
    import jax.numpy as jnp

    k, m = 10, 4
    chunk = 1 << 17                  # ~1.25 MiB stripes
    batch = 128
    codec = registry().factory("tpu", {"k": str(k), "m": str(m),
                                       "technique": "cauchy"})
    data = _device_batch(rng, batch, k, chunk)
    parity = codec.encode_batch(data)
    parity.block_until_ready()
    erasures = [2, 11]
    decode_index = [i for i in range(k + m) if i not in erasures][:k]
    full = jnp.concatenate([data, parity], axis=1)
    lost = full[:, jnp.asarray(erasures)]
    survivors = full[:, jnp.asarray(decode_index)]
    survivors.block_until_ready()
    del data, parity, full
    dt, iters, rec = _time_launches(
        lambda: codec.decode_batch(erasures, survivors),
        lambda o: o.block_until_ready())
    if not bool(jnp.array_equal(rec, lost)):
        raise RuntimeError("cauchy decode parity failure")
    gibps = batch * k * chunk / dt / 2**30
    log(f"cauchy k10m4 decode: {gibps:.1f} GiB/s ({iters} iters)")
    return round(gibps, 2)


def _marshal_4k(rng):
    """RS k8m3 on 4 KiB chunks INCLUDING host->device upload and
    parity download -- the small-op marshaling regime."""
    import jax
    from ceph_tpu.ec import registry

    k, m = 8, 3
    chunk = 4096
    batch = 2048                     # 64 MiB of 4 KiB chunks
    codec = registry().factory("tpu", {"k": str(k), "m": str(m),
                                       "technique": "reed_sol_van"})
    host = rng.integers(0, 256, size=(batch, k, chunk), dtype=np.uint8)

    def once():
        dev = jax.device_put(host)
        return np.asarray(codec.encode_batch(dev))

    once()                           # compile + warm
    iters = 4
    # EVERY iteration pays upload AND download -- the whole point of
    # this config is the marshaling cost, so nothing may amortize
    t0 = time.perf_counter()
    for _ in range(iters):
        once()
    dt = (time.perf_counter() - t0) / iters
    gibps = batch * k * chunk / dt / 2**30
    log(f"4KiB marshaling encode (upload+launch+download): "
        f"{gibps:.1f} GiB/s ({iters} iters)")
    return round(gibps, 2)


def _crush_batch():
    """10M PG->OSD mappings over a 1000-OSD straw2 map, vectorized
    (BASELINE config 5), in this process (a child could never get a
    chip this process already holds)."""
    from ceph_tpu.tools.crush_bench import run_crush_bench
    mps = run_crush_bench(pgs=10_000_000, verify=128)["value"] / 1e6
    log(f"crush bulk: {mps:.1f} M mappings/s")
    return round(mps, 2)


def _make_placement_map(fanouts, pg_num, down_frac=0.05, seed=11):
    """Synthetic OSDMap for placement benchmarking: a uniform straw2
    hierarchy, one replicated + one EC pool, a sprinkle of down OSDs,
    upmap items and a pg_temp override -- every branch of the cached
    pipeline is on the clock."""
    import random
    from ceph_tpu.crush.builder import build_hierarchy
    from ceph_tpu.mon.osdmap import (
        OSDMap, OsdInfo, PoolSpec, POOL_TYPE_ERASURE)

    rnd = random.Random(seed)
    n = 1
    for f in fanouts:
        n *= f
    m = OSDMap()
    m.epoch = 1
    m.crush = build_hierarchy(fanouts)
    m.max_osd = n
    for o in range(n):
        m.osds[o] = OsdInfo(up=(rnd.random() >= down_frac),
                            in_cluster=True, weight=0x10000)
    for pid, (name, extra) in enumerate((
            ("rep", {}),
            ("ecpool", {"type": POOL_TYPE_ERASURE, "size": 4,
                        "min_size": 3, "crush_rule": 1}),), start=1):
        spec = PoolSpec(pool_id=pid, name=name, pg_num=pg_num,
                        pgp_num=pg_num, **extra)
        m.pools[pid] = spec
        m.pool_names[name] = pid
    # overrides: a few upmap rewrites and one pg_temp per pool
    ups = [o for o, i in m.osds.items() if i.up]
    for pid in m.pools:
        for pg in range(0, min(pg_num, 64), 7):
            m.pg_upmap_items[f"{pid}.{pg:x}"] = [
                (rnd.choice(ups), rnd.choice(ups))]
        m.pg_temp[f"{pid}.1"] = rnd.sample(ups, 3)
    return m


def _placement_mode(smoke: bool) -> int:
    """--placement: epoch-recompute throughput (pg/s) of the bulk
    placement cache vs the per-PG scalar pg_to_up_acting loop, plus
    per-op cached lookup latency.  Parity is asserted before timing --
    entry-identical tables or no number."""
    from ceph_tpu.mon.pg_mapping import PGMapping

    if smoke:
        fanouts, pg_num = [4, 8], 256
        # the smoke's whole point is fused-vs-scalar divergence failing
        # fast: force the fused path even at toy lane counts
        import ceph_tpu.mon.pg_mapping as _pgm
        _pgm.FUSED_MIN_LANES = 1
    else:
        fanouts = [int(x) for x in os.environ.get(
            "BENCH_PLACE_FANOUTS", "8,8,8").split(",")]
        pg_num = int(os.environ.get("BENCH_PLACE_PGS", "16384"))
    m = _make_placement_map(fanouts, pg_num)
    total = pg_num * len(m.pools)
    log(f"placement mode: {len(m.osds)} osds, {len(m.pools)} pools x "
        f"{pg_num} pgs ({total} table entries), smoke={smoke}")

    # parity gate: the fused bulk table must equal the scalar oracle
    # entry-for-entry on a sample (the full suite lives in
    # tests/test_placement_cache.py; the bench re-asserts a slice so a
    # drifted build can never publish a throughput number)
    pm = PGMapping.build(m, fused="always" if smoke else "auto")
    fused = pm.scalar_pools == 0
    rng = np.random.default_rng(3)
    for pid in m.pools:
        for ps in rng.integers(0, pg_num * 4, size=48 if smoke else 24):
            want = m._pg_to_up_acting_scalar(pid, int(ps))
            got = pm.lookup(pid, int(ps))
            if got != want:
                raise RuntimeError(
                    f"placement parity failure pool {pid} ps {ps}: "
                    f"cached {got} != scalar {want}")
    log(f"parity gate passed (fused_path={fused})")

    # scalar baseline: the pre-cache per-PG loop, sampled + extrapolated
    sample = min(total, 256 if smoke else 1024)
    pids = sorted(m.pools)
    t0 = time.perf_counter()
    for i in range(sample):
        m._pg_to_up_acting_scalar(pids[i % len(pids)],
                                  i // len(pids))
    scalar_dt = time.perf_counter() - t0
    scalar_pgs = sample / scalar_dt
    log(f"scalar loop: {scalar_pgs:.0f} pg/s "
        f"({sample} pgs in {scalar_dt:.2f}s)")

    # bulk recompute, steady state: first build above warmed the jit
    # caches; each timed round invalidates and rebuilds the whole
    # table, exactly what a new epoch costs
    iters = 2 if smoke else 3
    t0 = time.perf_counter()
    for _ in range(iters):
        m.invalidate_placement_cache()
        pm = m.placement_cache()
    bulk_dt = (time.perf_counter() - t0) / iters
    bulk_pgs = total / bulk_dt
    log(f"bulk recompute: {bulk_pgs:.0f} pg/s "
        f"({bulk_dt * 1e3:.1f} ms/epoch, {iters} epochs)")

    lookups = 20000 if smoke else 200000
    t0 = time.perf_counter()
    for i in range(lookups):
        m.pg_to_up_acting(pids[i & 1], i % pg_num)
    lookup_us = (time.perf_counter() - t0) / lookups * 1e6
    log(f"cached lookup: {lookup_us:.2f} us/op")

    ratio = bulk_pgs / scalar_pgs
    RESULT.update({
        "metric": "placement_epoch_recompute_pgs_per_s",
        "value": round(bulk_pgs, 1),
        "unit": "pg/s",
        "vs_baseline": round(ratio, 2),
        "scalar_pgs_per_s": round(scalar_pgs, 1),
        "lookup_us": round(lookup_us, 3),
        "fused_path": fused,
        "table_entries": total,
        "osds": len(m.osds),
        "smoke": smoke,
    })
    emit()
    if smoke and not fused:
        log("ERROR: smoke demands the fused path")
        return 1
    return 0


def _integrity_parity_gate(rng) -> None:
    """Byte-identity tripwire: every batched backend (dispatch ladder,
    forced numpy engine, device kernel) must agree with the scalar
    ``native.crc32c`` on a randomized ragged batch (empty, 1-byte,
    non-multiple-of-slice lengths), and the GF(2) combine identity
    must hold.  Raises on any divergence -- a number without parity is
    meaningless."""
    import numpy as np
    from ceph_tpu import native
    from ceph_tpu.ops import crc32c_batch as cb

    lens = [0, 1, 7, 8, 9, 63, 65, 511, 513, 1000, 4096]
    lens += [int(x) for x in rng.integers(0, 20000, size=8)]
    bufs = [rng.integers(0, 256, size=n, dtype=np.uint8).tobytes()
            for n in lens]
    want = [native.crc32c(b) for b in bufs]
    for backend in (None, "numpy"):
        got = cb.crc32c_batch(bufs, backend=backend)
        for ln, g, w in zip(lens, got, want):
            if int(g) != w:
                raise RuntimeError(
                    f"crc batch parity failure (backend={backend}, "
                    f"len={ln}): {int(g):#x} != {w:#x}")
    dev = np.asarray(cb.crc32c_device_chunks(
        np.stack([np.frombuffer(b[:256].ljust(256, b"\1"), np.uint8)
                  for b in bufs if len(b) >= 1])))
    for i, b in enumerate(b2 for b2 in bufs if len(b2) >= 1):
        if int(dev[i]) != native.crc32c(b[:256].ljust(256, b"\1")):
            raise RuntimeError("device crc kernel parity failure")
    for _ in range(8):
        na, nb = int(rng.integers(0, 5000)), int(rng.integers(0, 5000))
        a = rng.integers(0, 256, na, dtype=np.uint8).tobytes()
        b = rng.integers(0, 256, nb, dtype=np.uint8).tobytes()
        if cb.crc32c_combine(native.crc32c(a), native.crc32c(b),
                             nb) != native.crc32c(a + b):
            raise RuntimeError("crc combine identity failure")
    log("integrity parity gate passed (ladder, numpy, device, combine)")


def _integrity_counter_proof(rng) -> dict:
    """Prove the hot paths ride the batched API: run a codec-batcher
    encode (with fused CRC) and a deep-scrub digest pass, and report
    the scalar-call delta observed by ``native.crc32c`` -- the
    acceptance bar is ~0."""
    import asyncio
    import numpy as np
    from ceph_tpu.ec import registry
    from ceph_tpu.ops.crc32c_batch import PERF
    from ceph_tpu.os.store import MemStore
    from ceph_tpu.os.transaction import Transaction
    from ceph_tpu.osd.codec_batcher import CodecBatcher
    from ceph_tpu.osd.ec_util import StripeInfo
    from ceph_tpu.osd.scrub import build_scrub_map

    codec = registry().factory("tpu", {"k": "4", "m": "2",
                                       "technique": "reed_sol_van"})
    si = StripeInfo.for_codec(codec, stripe_unit=1024)
    batcher = CodecBatcher(max_batch=32, flush_timeout=0.05)
    datas = [rng.integers(0, 256, si.stripe_width * n,
                          dtype=np.uint8).tobytes() for n in (3, 2, 4)]
    store = MemStore()
    store.queue_transaction(Transaction().create_collection("c"))
    for i in range(24):
        t = Transaction()
        t.write("c", f"obj-{i}", 0, rng.integers(
            0, 256, 4096, dtype=np.uint8).tobytes())
        store.queue_transaction(t)

    async def drive():
        enc = await asyncio.gather(*(
            si.encode_async(codec, d, batcher=batcher, with_crc=True)
            for d in datas))
        smap = await build_scrub_map(store, "c", deep=True)
        return enc, smap

    before = {k: PERF.get(k) for k in
              ("scalar_calls", "batched_calls", "fused_launches")}
    enc, smap = asyncio.new_event_loop().run_until_complete(drive())
    after = {k: PERF.get(k) for k in before}
    delta = {k: after[k] - before[k] for k in before}
    # spot-check the scrub digests against scalar recompute
    for oid in list(smap)[:4]:
        want = __import__("ceph_tpu").native.crc32c(
            bytes(store.read("c", oid, 0, None)))
        assert smap[oid]["data_digest"] == want, oid
    log(f"counter proof: scalar_calls_delta={delta['scalar_calls']} "
        f"batched_calls_delta={delta['batched_calls']} "
        f"fused_launches_delta={delta['fused_launches']}")
    return {"scalar_calls_on_batched_paths": delta["scalar_calls"],
            "batched_calls": delta["batched_calls"],
            "fused_launches": delta["fused_launches"]}


def _integrity_mode(smoke: bool) -> int:
    """--integrity: batched CRC32C throughput vs the per-buffer scalar
    loop the integrity pipeline used to run (one ``native.crc32c``
    ctypes call per shard/block/object), plus parity tripwires and the
    perf-counter proof that the codec-batcher and deep-scrub paths
    make ~0 scalar calls.  --smoke keeps the workload tiny (tier-1
    tripwire via test_bench_harness)."""
    import numpy as np
    from ceph_tpu import native
    from ceph_tpu.ops import crc32c_batch as cb

    rng = np.random.default_rng(5)
    log(f"integrity mode: smoke={smoke}")
    _integrity_parity_gate(rng)
    proof = _integrity_counter_proof(rng)

    total = (2 << 20) if smoke else (96 << 20)
    configs = {}
    head_ratio = head_gibps = 0.0

    def best_of(fn, reps=2):
        # best-of-n: first-touch page faults and allocator churn
        # belong to neither side of the comparison
        times, out = [], None
        for _ in range(reps):
            t0 = time.perf_counter()
            out = fn()
            times.append(time.perf_counter() - t0)
        return min(times), out

    # each config is measured in its real consumer's call shape:
    #   * ec_chunk_rows: EC chunks sit in the codec batcher's (B, k, L)
    #     tensors -- the batched call is crc32c_rows on a contiguous 2D
    #     view, ZERO marshaling (the headline: this is the buffer the
    #     codec launch just touched);
    #   * frames/blocks arrive as separate bytes objects (messenger
    #     frames, blockstore block contents) -- crc32c_batch pays its
    #     own marshaling, honestly;
    #   * mix: an op stream hashes several wire frames per data block,
    #     4 frames : 2 chunks : 1 block.
    shapes = {"ec_chunk_rows_1KiB": ("rows", 1024),
              "frame_256B": ("ragged", [256]),
              "block_4KiB": ("ragged", [4096]),
              "mix_ragged": ("ragged", [256, 256, 256, 256,
                                        1024, 1024, 4096])}
    for name, (form, spec) in shapes.items():
        if form == "rows":
            arr = rng.integers(0, 256, size=(total // spec, spec),
                               dtype=np.uint8)
            bufs = None
            lens = [spec] * arr.shape[0]

            def scalar_loop(arr=arr):
                # the pre-batching per-chunk path: bytes() conversion
                # included, exactly as shard_crc(buf) paid it
                for row in arr:
                    native.crc32c(row.tobytes())

            def batched(arr=arr):
                return cb.crc32c_rows(arr)

            def batched_numpy(arr=arr):
                return cb.crc32c_rows(arr, backend="numpy")

            check = lambda got, arr=arr: all(         # noqa: E731
                int(g) == native.crc32c(arr[i].tobytes())
                for i, g in enumerate(got[:8]))
        else:
            pool = spec
            if len(pool) == 1:
                lens = [pool[0]] * (total // pool[0])
            else:
                lens = [pool[int(i)] for i in
                        rng.integers(0, len(pool), size=total // 1500)]
            bufs = [rng.integers(0, 256, size=ln,
                                 dtype=np.uint8).tobytes()
                    for ln in lens]

            def scalar_loop(bufs=bufs):   # the pre-batching loop
                for b in bufs:
                    native.crc32c(b)

            def batched(bufs=bufs):
                return cb.crc32c_batch(bufs)

            def batched_numpy(bufs=bufs):
                return cb.crc32c_batch(bufs, backend="numpy")

            check = lambda got, bufs=bufs: all(       # noqa: E731
                int(g) == native.crc32c(b)
                for g, b in zip(got[:8], bufs[:8]))
        nbytes = sum(lens)
        scalar_dt, _ = best_of(scalar_loop)
        batch_dt, got = best_of(batched)
        numpy_dt, _ = best_of(batched_numpy, reps=1 if smoke else 2)
        assert check(got), name
        ratio = scalar_dt / batch_dt
        configs[name] = {
            "scalar_GiBps": round(nbytes / scalar_dt / 2**30, 3),
            "batched_GiBps": round(nbytes / batch_dt / 2**30, 3),
            "numpy_GiBps": round(nbytes / numpy_dt / 2**30, 3),
            "buffers": len(lens),
            "ratio": round(ratio, 1),
        }
        log(f"{name}: scalar {configs[name]['scalar_GiBps']} GiB/s, "
            f"batched {configs[name]['batched_GiBps']} GiB/s "
            f"({ratio:.1f}x), numpy engine "
            f"{configs[name]['numpy_GiBps']} GiB/s")
        if name == "ec_chunk_rows_1KiB":
            head_ratio = ratio
            head_gibps = nbytes / batch_dt / 2**30

    RESULT.update({
        "metric": "integrity_crc32c_batched_GiBps",
        "value": round(head_gibps, 3),
        "unit": "GiB/s",
        "vs_baseline": round(head_ratio, 2),
        "baseline_note": "per-chunk scalar native.crc32c loop over the "
                         "same EC chunk rows (the pre-batching "
                         "shard_crc path); other call shapes under "
                         "configs",
        "configs": configs,
        "smoke": smoke,
        **proof,
    })
    emit()
    if proof["scalar_calls_on_batched_paths"] != 0:
        log("ERROR: scalar CRC calls observed on batched paths")
        return 1
    return 0


def _agg_phases(phases: dict) -> dict:
    """Aggregate per-pass phase rows into one row per phase kind."""
    agg: dict = {}
    for name, d in phases.items():
        key = name.rstrip("0123456789_") or name
        cur = agg.setdefault(key, {"seconds": 0.0, "bytes": 0})
        cur["seconds"] = round(cur["seconds"] + d["seconds"], 4)
        cur["bytes"] += d["bytes"]
    for cur in agg.values():
        cur["GiBps"] = round(
            cur["bytes"] / max(cur["seconds"], 1e-9) / 2**30, 3)
    return agg


def _datapath_mode(smoke: bool) -> int:
    """--datapath: the device-resident shard data path, end-to-end.

    Drives write -> read-verify -> scrub -> degraded-read over real
    BlockStores with the production encode/decode/CRC primitives,
    twice over identical inputs: the host-round-trip baseline (every
    consumer re-materializes shard bytes through the store; deep scrub
    reconstructs + re-encodes) vs the DeviceShardCache path (hot shard
    buffers stay resident; scrub verifies write-time tags over the
    resident bytes).  Byte identity between the two runs is asserted
    before any number is reported, and the ``datapath`` perf counters
    must show the cached steady phases moved ZERO shard bytes through
    the store.  --smoke keeps the workload tier-1 sized and exits
    non-zero on any gate failure (parity, hit-rate, steady host bytes,
    scalar CRC calls)."""
    import asyncio
    from ceph_tpu.tools.datapath_bench import run_datapath_bench

    if smoke:
        kwargs = dict(k=2, m=1, n_objects=6, obj_bytes=32 << 10,
                      passes=2, reads_per_pass=2)
    else:
        kwargs = dict(
            k=int(os.environ.get("BENCH_DP_K", "4")),
            m=int(os.environ.get("BENCH_DP_M", "2")),
            n_objects=int(os.environ.get("BENCH_DP_OBJECTS", "24")),
            obj_bytes=int(os.environ.get("BENCH_DP_OBJ_KIB",
                                         "256")) << 10,
            passes=int(os.environ.get("BENCH_DP_PASSES", "10")),
            reads_per_pass=int(os.environ.get("BENCH_DP_READS", "5")))
    log(f"datapath mode: {kwargs} smoke={smoke}")
    res = asyncio.new_event_loop().run_until_complete(
        run_datapath_bench(**kwargs))
    log(f"datapath: {res['datapath_GiBps']} GiB/s cached vs "
        f"{res['baseline_GiBps']} GiB/s host round trip "
        f"({res['vs_host_roundtrip']}x); steady host bytes "
        f"{res['steady_host_bytes_read']}, hits {res['cache_hits']}")
    RESULT.update({
        "metric": "datapath_write_scrub_degraded_GiBps",
        "value": res["datapath_GiBps"],
        "unit": "GiB/s",
        "vs_baseline": res["vs_host_roundtrip"],
        "baseline_note": "identical drive with the shard cache "
                         "detached: every read re-materializes "
                         "through the store and deep scrub "
                         "reconstructs + re-encodes (the pre-cache "
                         "pipeline)",
        "smoke": smoke,
        **{key: res[key] for key in
           ("k", "m", "n_objects", "obj_bytes", "passes",
            "reads_per_pass", "baseline_GiBps", "cache_hits",
            "steady_host_bytes_read", "steady_host_reads",
            "host_bytes_avoided", "scalar_calls_on_batched_paths",
            "parity")},
        "cached_phases": _agg_phases(res["cached_run"]["phases"]),
        "baseline_phases": _agg_phases(res["baseline_run"]["phases"]),
    })
    emit()
    rc = 0
    if res["parity"] != "ok":
        log("ERROR: datapath parity gate failed")
        rc = 1
    if not res["cache_hits"]:
        log("ERROR: the cached drive never hit the cache")
        rc = 1
    if res["steady_host_bytes_read"] != 0:
        log("ERROR: cache-hit steady phases moved shard bytes "
            "through the store")
        rc = 1
    if res["scalar_calls_on_batched_paths"] != 0:
        log("ERROR: scalar CRC calls observed on the datapath "
            "steady phases")
        rc = 1
    return rc


def _recovery_mode(smoke: bool) -> int:
    """--recovery: repair I/O under RS vs LRC vs PMSR
    (ceph_tpu/tools/recovery_bench.py).

    The same kill -> degraded-write -> revive -> recover drive on
    identical seeds, one cluster per code family, reporting repair
    GiB read/shipped (the new ``ec_recovery`` counters) and recovery
    wall clock.  Gates: zero failed/wedged ops and byte-identical
    read-back through every drive (verified against a survivor kill),
    LRC single-failure repair reads <= 0.5x the RS bytes at the
    k=8-class config, and PMSR helper traffic strictly under k full
    chunks (fragment pulls counted, not assumed)."""
    import asyncio
    from ceph_tpu.tools.recovery_bench import run_recovery_bench

    if smoke:
        kwargs = dict(n_objects=4, obj_size=32 << 10, pg_num=8)
    else:
        kwargs = dict(
            n_objects=int(os.environ.get("BENCH_REC_OBJECTS", "16")),
            obj_size=int(os.environ.get("BENCH_REC_OBJ_KIB",
                                        "128")) << 10,
            pg_num=int(os.environ.get("BENCH_REC_PGS", "16")))
    log(f"recovery mode: {kwargs} smoke={smoke}")
    res = asyncio.new_event_loop().run_until_complete(
        run_recovery_bench(**kwargs, smoke=smoke, log=log))
    codes = res["codes"]
    log(f"recovery: read/shipped rs={codes['rs']['read_per_shipped']}"
        f"x lrc={codes['lrc']['read_per_shipped']}x "
        f"pmsr={codes['pmsr']['read_per_shipped']}x "
        f"(lrc vs rs {res['lrc_vs_rs_read_ratio']}x)")
    RESULT.update({
        "metric": "recovery_repair_read_ratio_lrc_vs_rs",
        "value": res["lrc_vs_rs_read_ratio"],
        "unit": "x",
        "vs_baseline": res["lrc_vs_rs_read_ratio"],
        "baseline_note": "identical kill/recover drive on an RS "
                         "(plugin=tpu) pool of the same k,m: repair "
                         "reads k full chunks per rebuilt shard",
        "smoke": smoke,
        **{key: res[key] for key in
           ("spec", "codes", "lrc_vs_rs_read_ratio",
            "pmsr_read_chunks", "failed_objects", "errors")},
    })
    emit()
    rc = 0
    if res["failed_objects"] or res["errors"]:
        log(f"ERROR: {res['failed_objects']} corrupt/wedged objects, "
            f"{res['errors']} drive errors")
        rc = 1
    for name, c in codes.items():
        if not c["recovered_clean"]:
            log(f"ERROR: {name} recovery never converged")
            rc = 1
        if not c["repair_bytes_shipped"]:
            log(f"ERROR: {name} recovery shipped no counted bytes")
            rc = 1
    if res["lrc_vs_rs_read_ratio"] > 0.5 \
            or not res["lrc_vs_rs_read_ratio"]:
        log(f"ERROR: lrc repair reads "
            f"{res['lrc_vs_rs_read_ratio']}x of RS (gate: <= 0.5x)")
        rc = 1
    if not (0 < res["pmsr_read_chunks"] < codes["pmsr"]["k"]):
        log(f"ERROR: pmsr helper traffic "
            f"{res['pmsr_read_chunks']} chunks not under k="
            f"{codes['pmsr']['k']}")
        rc = 1
    if not codes["pmsr"]["repair_fragment_pulls"]:
        log("ERROR: pmsr recovery never took the fragment path")
        rc = 1
    if not codes["lrc"]["repair_local_repairs"]:
        log("ERROR: lrc recovery never repaired locally")
        rc = 1
    return rc


def _straggler_mode(smoke: bool) -> int:
    """--straggler: hedged vs unhedged EC reads under deterministic
    heavy-tail delays (ceph_tpu/tools/straggler_bench.py).

    One loadgen read phase driven twice -- identical workload,
    identical per-peer lognormal straggler schedule -- first with
    ``osd_ec_hedge_enabled=false`` (the fixed-gather baseline), then
    with the HedgedGather engine live.  Gates (the ISSUE-11 acceptance
    set): hedged p99 >= 2x better, extra sub-reads <= 1.5x, zero
    failed/wedged ops, zero leaked sub-read tasks, and every object
    byte-identical to ground truth in BOTH variants (the unhedged
    full-set gather is the oracle the first-k decode must match).
    --smoke keeps it tier-1 sized."""
    import asyncio
    from ceph_tpu.tools.straggler_bench import run_straggler_bench

    if smoke:
        kwargs = dict(n_osds=5, pg_num=32, n_objects=16,
                      obj_bytes=8 << 10, n_reads=72, n_clients=6)
    else:
        kwargs = dict(
            n_osds=int(os.environ.get("BENCH_STRAG_OSDS", "6")),
            pg_num=int(os.environ.get("BENCH_STRAG_PGS", "64")),
            n_objects=int(os.environ.get("BENCH_STRAG_OBJECTS", "48")),
            obj_bytes=int(os.environ.get("BENCH_STRAG_OBJ_KIB",
                                         "16")) << 10,
            n_reads=int(os.environ.get("BENCH_STRAG_READS", "240")),
            n_clients=int(os.environ.get("BENCH_STRAG_CLIENTS", "8")))
    log(f"straggler mode: {kwargs} smoke={smoke}")
    res = asyncio.new_event_loop().run_until_complete(
        run_straggler_bench(**kwargs, log=log))
    log(f"straggler: p99 {res['p99_unhedged_s']}s unhedged -> "
        f"{res['p99_hedged_s']}s hedged ({res['p99_speedup']}x), "
        f"extra sub-reads {res['extra_subread_ratio']}x, "
        f"fired={res['hedged']['hedges_fired']} "
        f"won={res['hedged']['hedges_won']}")
    RESULT.update({
        "metric": "straggler_read_p99_speedup_hedged_vs_unhedged",
        "value": res["p99_speedup"],
        "unit": "x",
        "vs_baseline": res["p99_speedup"],
        "baseline_note": "identical workload + identical seeded "
                         "heavy-tail delay schedule with "
                         "osd_ec_hedge_enabled=false (fixed-set "
                         "gathers await the straggler)",
        "smoke": smoke,
        **{key: res[key] for key in
           ("spec", "p99_unhedged_s", "p99_hedged_s",
            "extra_subread_ratio", "extra_byte_ratio", "failed_ops",
            "wedged_ops", "leaked_tasks", "byte_mismatches",
            "unhedged", "hedged")},
    })
    emit()
    rc = 0
    if res["byte_mismatches"]:
        log(f"ERROR: byte mismatches {res['byte_mismatches'][:4]}")
        rc = 1
    if res["failed_ops"] or res["wedged_ops"]:
        log(f"ERROR: {res['failed_ops']} failed / "
            f"{res['wedged_ops']} wedged ops under stragglers")
        rc = 1
    if res["leaked_tasks"]:
        log(f"ERROR: {res['leaked_tasks']} leaked sub-read tasks")
        rc = 1
    if not res["hedged"]["hedges_fired"]:
        log("ERROR: the hedged drive never fired a hedge")
        rc = 1
    if res["p99_speedup"] < 2.0:
        log(f"ERROR: p99 speedup {res['p99_speedup']}x < 2x floor")
        rc = 1
    ratio = res["extra_subread_ratio"]
    if not ratio or ratio > 1.5:
        log(f"ERROR: extra sub-read ratio {ratio}x outside (0, 1.5]")
        rc = 1
    return rc


def _cluster_spec(smoke: bool):
    """The --cluster WorkloadSpec: smoke = small, deterministic,
    tier-1-fast; full = the >=64-OSD / >=10k-object acceptance shape
    (BENCH_CLUSTER_* env overrides for exploration)."""
    from ceph_tpu.loadgen import WorkloadSpec

    if smoke:
        return WorkloadSpec(
            n_osds=5, pg_num=32, n_objects=96, obj_size=8 << 10,
            n_ops=400, n_clients=8, recovery_ops=160, kill_osds=1,
            seed=7).validate()
    return WorkloadSpec(
        n_osds=int(os.environ.get("BENCH_CLUSTER_OSDS", "64")),
        pg_num=int(os.environ.get("BENCH_CLUSTER_PGS", "256")),
        n_objects=int(os.environ.get("BENCH_CLUSTER_OBJECTS", "10000")),
        obj_size=int(os.environ.get("BENCH_CLUSTER_OBJ_KIB", "16")) << 10,
        n_ops=int(os.environ.get("BENCH_CLUSTER_OPS", "6000")),
        n_clients=int(os.environ.get("BENCH_CLUSTER_CLIENTS", "32")),
        recovery_ops=int(os.environ.get("BENCH_CLUSTER_REC_OPS",
                                        "1200")),
        kill_osds=1, size_dist="lognormal",
        seed=int(os.environ.get("BENCH_CLUSTER_SEED", "1"))).validate()


def _cluster_mode(smoke: bool) -> int:
    """--cluster: the closed-loop traffic harness (ceph_tpu/loadgen)
    against an in-process cluster — ops/s, GiB/s, p50/p95/p99/p99.9
    per op class, and client-latency degradation across an OSD
    kill/revive (degraded + backfill interference phases), with the
    dmClock per-class dispatch counts showing client-vs-recovery QoS
    behavior.  --smoke is the tier-1 tripwire: any failed/wedged
    client op, a non-converging cluster, or a degenerate latency
    distribution (p50 >= max, empty class) exits non-zero."""
    import asyncio
    from ceph_tpu.loadgen import (degradation_ratios, run_workload,
                                  deterministic_view)

    spec = _cluster_spec(smoke)
    log(f"cluster mode: {spec.n_osds} osds, {spec.n_objects} objects,"
        f" {spec.n_ops} steady ops, smoke={smoke}")
    report = asyncio.new_event_loop().run_until_complete(
        run_workload(spec, log=log))

    phases = report["phases"]
    failed = sum(ph.get("failed_ops", 0) for ph in phases.values())
    wedged = sum(ph.get("wedged_ops", 0) for ph in phases.values())
    steady = phases["steady"]["timing"]
    total_ops = sum(ph["ops"] for ph in phases.values())
    total_bytes = sum(ph["bytes_read"] + ph["bytes_written"]
                      for ph in phases.values())
    degr = {p: degradation_ratios(report, p)
            for p in ("degraded", "backfill") if p in phases}
    qos = report["qos"]
    import hashlib
    det_digest = hashlib.sha256(json.dumps(
        deterministic_view(report), sort_keys=True).encode()
    ).hexdigest()[:16]

    RESULT.update({
        "metric": "cluster_steady_client_ops_per_s",
        "value": steady["ops_per_s"],
        "unit": "ops/s",
        "vs_baseline": 0.0,
        "steady_GiBps": steady["GiBps"],
        "latency": steady["latency"],
        "p99_degradation": degr,
        "interference": report.get("interference"),
        "qos": qos,
        "total_ops": total_ops,
        "total_GiB": round(total_bytes / 2**30, 3),
        "failed_ops": failed,
        "wedged_ops": wedged,
        "osds": spec.n_osds,
        "objects": spec.n_objects,
        "pg_num": spec.pg_num,
        "deterministic_digest": det_digest,
        "schedule": report["schedule"],
        "counters": report["counters"],
        "timing": report["timing"],
        "smoke": smoke,
    })
    emit()

    rc = 0
    if failed or wedged:
        log(f"ERROR: {failed} failed / {wedged} wedged client ops")
        rc = 1
    interference = report.get("interference") or {}
    if spec.recovery_ops and not (interference.get("down_detected")
                                  and interference.get("revived")):
        log("ERROR: interference phase never saw the kill/revive")
        rc = 1
    for kind, lat in steady["latency"].items():
        if lat["count"] and lat["p50_s"] > lat["max_s"]:
            log(f"ERROR: degenerate {kind} latency distribution")
            rc = 1
    if not qos.get("steady", {}).get("dispatched_client"):
        log("ERROR: scheduler perf set recorded no client dispatch")
        rc = 1
    # pipelined write spine: the overlap counters must be LIVE
    pipe = report["counters"].get("ec_pipeline", {})
    for key in ("staged_batches", "overlapped_commits",
                "commit_overlap_ms", "flush_windows"):
        if not pipe.get(key):
            log(f"ERROR: ec_pipeline.{key} never moved")
            rc = 1
    return rc


def _mesh_gates(smoke: bool) -> dict:
    """The --mesh acceptance gates, run before the cluster drive:

    * PARITY: sharded-mesh encode/decode/RMW (+ fused chunk CRCs)
      byte-identical to the single-device scalar codec oracle,
      including a ragged-lane co-submission;
    * LAUNCH ACCOUNTING: a mesh-backed CodecBatcher runs EXACTLY ONE
      device launch per coalesced batch (mesh_launches == batches)
      -- the CRC side-path rides inside it;
    * ``scalar_calls_on_batched_paths == 0``: the drive makes no
      scalar ``native.crc32c`` call.

    Raises on parity failure; returns the gate report dict."""
    import asyncio
    import numpy as np
    from ceph_tpu import native
    from ceph_tpu.common.perf import PerfCounters
    from ceph_tpu.ec import registry
    from ceph_tpu.ops.crc32c_batch import PERF
    from ceph_tpu.osd.codec_batcher import CodecBatcher
    from ceph_tpu.parallel.mesh_codec import MeshCodec

    rng = np.random.default_rng(12)
    codec = registry().factory("tpu", {"k": "4", "m": "2",
                                       "technique": "reed_sol_van"})
    mesh = MeshCodec()
    n, lane = (16, 256) if smoke else (64, 4096)
    log(f"mesh gates: {mesh.n_devices} devices, "
        f"{n} stripes x {lane} B chunks")

    data = rng.integers(0, 256, (n, 4, lane), dtype=np.uint8)
    parity, crcs = mesh.encode(codec, data, with_crc=True)
    full = np.concatenate([data, parity], axis=1)
    for s in range(0, n, max(1, n // 8)):
        want = codec.encode(set(range(6)), data[s].tobytes())
        for r in range(2):
            if not np.array_equal(parity[s, r], want[4 + r]):
                raise RuntimeError(f"mesh encode parity failure @{s}")
        for c in range(6):
            if int(crcs[s, c]) != native.crc32c(full[s, c].tobytes()):
                raise RuntimeError(f"mesh fused-CRC failure @{s},{c}")
    erasures = [1, 4]
    didx = [i for i in range(6) if i not in erasures][:4]
    rec = mesh.decode(codec, erasures, full[:, didx])
    for s in range(0, n, max(1, n // 8)):
        for p, e in enumerate(erasures):
            if not np.array_equal(rec[s, p], full[s, e]):
                raise RuntimeError(f"mesh decode parity failure @{s}")
    delta = np.zeros_like(data)
    delta[:, 2, : lane // 4] = rng.integers(
        0, 256, (n, lane // 4), dtype=np.uint8)
    newdata = data ^ delta
    if not np.array_equal(mesh.rmw(codec, parity, delta),
                          mesh.encode(codec, newdata)):
        raise RuntimeError("mesh RMW delta parity failure")
    log("mesh parity gate passed (encode+crc, decode, rmw)")

    perf = PerfCounters("ec_batch")
    batcher = CodecBatcher(max_batch=8, flush_timeout=0.2, perf=perf)
    a1 = rng.integers(0, 256, (3, 4, lane), dtype=np.uint8)
    a2 = rng.integers(0, 256, (2, 4, lane // 2), dtype=np.uint8)

    async def drive():
        enc = asyncio.gather(batcher.encode(codec, a1, with_crc=True),
                             batcher.encode(codec, a2, with_crc=True))
        (p1, c1), (p2, c2) = await enc
        dec = await batcher.decode(
            codec, tuple(erasures),
            np.concatenate([a1, p1], axis=1)[:, didx])
        return (p1, c1), (p2, c2), dec

    scalar0 = PERF.get("scalar_calls")
    (p1, c1), (p2, c2), dec = asyncio.new_event_loop() \
        .run_until_complete(drive())
    scalar_delta = PERF.get("scalar_calls") - scalar0
    for arr, par, cc in ((a1, p1, c1), (a2, p2, c2)):
        fl = np.concatenate([arr, par], axis=1)
        for s in range(arr.shape[0]):
            want = codec.encode(set(range(6)), arr[s].tobytes())
            for r in range(2):
                assert np.array_equal(par[s, r], want[4 + r]), s
            for c in range(6):
                assert int(cc[s, c]) == native.crc32c(
                    fl[s, c].tobytes()), (s, c)
    batches = perf.get("batches")
    launches = perf.get("mesh_launches")
    lpb = launches / batches if batches else 0.0
    padded = perf.get("mesh_padded_stripes")
    gates = {
        "n_devices": mesh.n_devices,
        "launches_per_batch": round(lpb, 3),
        "per_device_stripes": round(
            padded / launches / mesh.n_devices, 2) if launches else 0.0,
        "scalar_calls_on_batched_paths": scalar_delta,
        "parity": "ok",
    }
    log(f"mesh launch gate: {launches} launches / {batches} batches "
        f"(= {lpb:.2f}), "
        f"scalar_calls_delta={scalar_delta}")
    return gates


def _xor_sched_rows(smoke: bool) -> dict:
    """The XOR-schedule compiler's bench rows (ops/xor_schedule.py):

    * static: XOR-term reduction of the CSE-minimized schedule vs the
      naive row-by-row XOR on the Cauchy k=8,m=3 bitmatrix (the
      ISSUE/ROADMAP headline; acceptance floor 30%);
    * bitmatrix host row: wall-clock of the scheduled host executor vs
      the naive ``xor_matmul`` on the same plane batch (the
      BitMatrixCodec data path, min-of-N so the comparison is about
      work, not scheduler noise);
    * batched XLA row: the scheduled (B, k, L) kernel family vs the
      dense bit-matmul on the current backend (the CodecBatcher path).
    """
    import numpy as np
    from ceph_tpu.gf.gf2w import (cauchy_improve_coding_matrix,
                                  cauchy_original_coding_matrix,
                                  matrix_to_bitmatrix, xor_matmul)
    from ceph_tpu.gf import gen_rs_matrix, gf_matmul
    from ceph_tpu.ops import gf2kernels as G
    from ceph_tpu.ops import xor_schedule as XS

    k, m, w = 8, 3, 8
    bm = matrix_to_bitmatrix(
        cauchy_improve_coding_matrix(
            cauchy_original_coding_matrix(k, m, w), k, m, w), k, m, w)
    sched = XS.schedule_for(bm)
    rows: dict = {
        "matrix": f"cauchy_good k={k} m={m} w={w}",
        "naive_xor_terms": sched.naive_terms,
        "sched_xor_terms": sched.n_terms,
        "reduction_pct": round(100 * sched.reduction, 1),
        "peak_registers": sched.peak_registers,
    }
    log(f"xor-schedule: cauchy k=8,m=3 {sched.naive_terms} -> "
        f"{sched.n_terms} terms ({rows['reduction_pct']}% reduction, "
        f"peak {sched.peak_registers} regs)")

    def best_of(fn, reps: int) -> float:
        fn()                                 # warm
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return best

    rng = np.random.default_rng(0)
    # above the HOST_MIN_LANE crossover even in smoke: the row exists
    # to show the scheduled engine winning where the cost model would
    # actually deploy it
    n = 32768 if smoke else 131072
    planes = rng.integers(0, 256, size=(k * w, n), dtype=np.uint8)
    reps = 5 if smoke else 9
    dt_naive = best_of(lambda: xor_matmul(bm, planes), reps)
    dt_sched = best_of(lambda: XS.apply_host(sched, planes), reps)
    assert np.array_equal(XS.apply_host(sched, planes),
                          xor_matmul(bm, planes))
    rows["bitmatrix_host"] = {
        "planes_bytes": int(planes.size),
        "naive_ms": round(dt_naive * 1000, 3),
        "sched_ms": round(dt_sched * 1000, 3),
        "speedup": round(dt_naive / dt_sched, 2),
    }
    log(f"xor-schedule host row: naive {dt_naive * 1000:.2f} ms vs "
        f"scheduled {dt_sched * 1000:.2f} ms "
        f"({dt_naive / dt_sched:.2f}x)")

    import jax
    import jax.numpy as jnp
    gen = gen_rs_matrix(k + m, k)
    mat = np.ascontiguousarray(gen[k:], np.uint8)
    b, lane = (8, 4096) if smoke else (64, 65536)
    data = rng.integers(0, 256, size=(b, k, lane), dtype=np.uint8)
    xd = jnp.asarray(data)
    rs_sched = XS.schedule_for(G.bitmatrix_i8(mat))

    def run_dense():
        os.environ["CEPH_TPU_XOR_SCHED"] = "0"
        try:
            G.gf_matmul_batch_device(mat, xd).block_until_ready()
        finally:
            os.environ.pop("CEPH_TPU_XOR_SCHED", None)

    def run_sched():
        XS.sched_matmul_batch_device(rs_sched, mat, xd, b, k,
                                     lane).block_until_ready()

    dt_dense = best_of(run_dense, 3 if smoke else 5)
    dt_xla = best_of(run_sched, 3 if smoke else 5)
    got = np.asarray(XS.sched_matmul_batch_device(rs_sched, mat, xd,
                                                  b, k, lane))
    assert np.array_equal(got[0], gf_matmul(mat, data[0]))
    rows["batched_xla"] = {
        "backend": jax.default_backend(),
        "shape": [b, k, lane],
        "dense_ms": round(dt_dense * 1000, 3),
        "sched_ms": round(dt_xla * 1000, 3),
        "speedup": round(dt_dense / dt_xla, 2),
    }
    log(f"xor-schedule XLA row ({jax.default_backend()}): dense "
        f"{dt_dense * 1000:.2f} ms vs scheduled {dt_xla * 1000:.2f} "
        f"ms ({dt_dense / dt_xla:.2f}x)")
    return rows


def _osd_path_mode(mesh: bool = False, smoke: bool = False) -> int:
    """--osd-path: drive the OSD DATA PATH — concurrent client EC
    writes through an in-process mon+OSD cluster — instead of the raw
    codec, so the artifact reports what the system achieves (including
    the CodecBatcher's achieved stripes-per-launch), not just what the
    kernel could do.  --mesh adds the sharded-data-plane gates (mesh
    parity vs the scalar oracle, exactly one device launch per
    coalesced batch, scalar_calls_on_batched_paths=0) and reports the
    mesh occupancy the cluster actually achieved; --smoke keeps the
    workload tier-1 sized and exits non-zero on any gate failure."""
    import asyncio
    from ceph_tpu.tools.ec_osd_bench import run_osd_path_bench

    gates = _mesh_gates(smoke) if mesh else None
    log(f"osd-path mode: in-process cluster, concurrent EC writes"
        f" (mesh={mesh}, smoke={smoke})")
    res = asyncio.run(run_osd_path_bench(
        n_osds=int(os.environ.get("BENCH_OSD_N", "3")),
        k=int(os.environ.get("BENCH_OSD_K", "2")),
        m=int(os.environ.get("BENCH_OSD_M", "1")),
        n_objects=int(os.environ.get("BENCH_OSD_OBJECTS",
                                     "12" if smoke else "48")),
        obj_bytes=int(os.environ.get(
            "BENCH_OSD_OBJ_KIB", "16" if smoke else "64")) * 1024,
        concurrency=int(os.environ.get("BENCH_OSD_CONCURRENCY",
                                       "8" if smoke else "16")),
        batch_max=int(os.environ.get("BENCH_OSD_BATCH", "64")),
    ))
    log(f"osd path: {res['osd_path_GiBps']} GiB/s, "
        f"{res['stripes_per_launch']} stripes/launch "
        f"({res['batches']} launches)")
    res["xor_schedule"] = _xor_sched_rows(smoke)
    if gates is not None:
        gates["cluster_launches_per_batch"] = \
            res.get("mesh", {}).get("launches_per_batch", 0.0)
        res["mesh_gates"] = gates
    RESULT.update({
        "metric": "ec_osd_path_write_GiBps",
        "value": res["osd_path_GiBps"],
        "unit": "GiB/s",
        "vs_baseline": 0.0,
        "smoke": smoke,
        **res,
    })
    emit()
    rc = 0
    xs = res.get("xor_schedule", {})
    if smoke:
        # the XOR-schedule acceptance gates: >=30% term reduction on
        # the Cauchy k=8,m=3 bitmatrix and a CPU wall-clock win on
        # the bitmatrix host row
        if xs.get("reduction_pct", 0.0) < 30.0:
            log("ERROR: xor-schedule term reduction below the 30% "
                "floor")
            rc = 1
        if xs.get("bitmatrix_host", {}).get("speedup", 0.0) <= 1.0:
            log("ERROR: scheduled bitmatrix row lost to the naive "
                "XOR on CPU")
            rc = 1
    if gates is None:
        return rc
    if gates["launches_per_batch"] != 1.0:
        log("ERROR: mesh gate demands exactly one device launch per "
            "coalesced batch")
        rc = 1
    if gates["scalar_calls_on_batched_paths"] != 0:
        log("ERROR: scalar CRC calls observed on the mesh path")
        rc = 1
    cluster = res.get("mesh", {})
    if cluster.get("launches", 0) == 0 \
            or cluster.get("launches_per_batch") != 1.0:
        log("ERROR: the cluster drive did not ride the mesh")
        rc = 1
    return rc


def main() -> int:
    from ceph_tpu.common.compile_cache import enable_compile_cache
    enable_compile_cache()

    smoke = "--smoke" in sys.argv[1:]
    if "--osd-path" in sys.argv[1:] or os.environ.get("BENCH_OSD_PATH"):
        return _osd_path_mode(
            mesh=("--mesh" in sys.argv[1:]
                  or bool(os.environ.get("BENCH_OSD_MESH"))),
            smoke=smoke)
    if "--datapath" in sys.argv[1:] or os.environ.get("BENCH_DATAPATH"):
        return _datapath_mode(smoke)
    if "--cluster" in sys.argv[1:] or os.environ.get("BENCH_CLUSTER"):
        return _cluster_mode(smoke)
    if "--straggler" in sys.argv[1:] or os.environ.get("BENCH_STRAGGLER"):
        return _straggler_mode(smoke)
    if "--recovery" in sys.argv[1:] or os.environ.get("BENCH_RECOVERY"):
        return _recovery_mode(smoke)
    if "--placement" in sys.argv[1:] or os.environ.get("BENCH_PLACEMENT"):
        return _placement_mode(smoke)
    if "--integrity" in sys.argv[1:] or os.environ.get("BENCH_INTEGRITY"):
        return _integrity_mode(smoke)

    from ceph_tpu.native import gf8_matmul
    from ceph_tpu.gf import gen_rs_matrix
    import jax

    dev = jax.devices()[0]
    log(f"jax backend={jax.default_backend()} devices={jax.devices()}")
    RESULT["device"] = {"platform": dev.platform,
                        "kind": dev.device_kind,
                        "count": len(jax.devices())}
    rng = np.random.default_rng(0)

    head = _headline(rng)
    # a config that raises fails the whole run (run() turns it into
    # the error line + a non-zero exit): a JSON with a config quietly
    # missing reads as a result
    configs = {
        "cauchy_k10m4_decode_GiBps": _cauchy_decode(rng),
        "rs_k8m3_4k_marshal_GiBps": _marshal_4k(rng),
        "crush_10m_Mmapss": _crush_batch(),
    }

    # CPU baseline (native AVX2, single thread, ISA-L split-nibble
    # technique -- the repo's own build; no linked ISA-L exists here)
    log("cpu baseline: native gf8.cc AVX2 single thread")
    k, m = 8, 3
    gen = gen_rs_matrix(k + m, k)
    base_n = 1 << 22
    base_data = rng.integers(0, 256, size=(k, base_n), dtype=np.uint8)
    gf8_matmul(gen[k:], base_data)  # warm tables
    t0 = time.perf_counter()
    base_iters = 6
    for _ in range(base_iters):
        gf8_matmul(gen[k:], base_data)
    base_dt = (time.perf_counter() - t0) / base_iters
    base_gibps = k * base_n / base_dt / 2**30
    log(f"cpu baseline: {base_gibps:.2f} GiB/s")

    enc, dec = head["encode_GiBps"], head["decode_GiBps"]
    combined = 2 / (1 / enc + 1 / dec)
    RESULT.update({
        "value": round(combined, 2),
        "vs_baseline": round(combined / base_gibps, 2),
        "cpu_baseline_GiBps": round(base_gibps, 2),
        "baseline_note": "own AVX2 gf8.cc single-thread "
                         "(ISA-L technique; no linked ISA-L in image)",
        "configs": configs,
        **head,
    })
    emit()
    return 0


def run() -> int:
    """main() with the failure contract: any exception becomes the
    JSON line's ``error`` and exit code 1."""
    try:
        return main()
    except Exception as e:
        log(f"FATAL: {type(e).__name__}: {e}")
        RESULT["error"] = f"{type(e).__name__}: {e}"
        emit()
        return 1


if __name__ == "__main__":
    sys.exit(run())
