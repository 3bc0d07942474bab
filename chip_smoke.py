#!/usr/bin/env python3
"""chip_smoke: the system's main path, once, on the accelerator.

One process, four phases, real widths (k=8, m=3 -- BASELINE.json's
north-star profile); the quickest proof that the program still starts
on the chip:

  kernels  every Pallas kernel family compiled and byte-checked against
           the native host oracle at the k=8,m=3 and Cauchy k=10,m=4
           encode/decode shapes;
  codec    BASELINE config 2 through the plugin registry: 1024 stripes
           x 1 MiB device-resident, encode_batch then decode_batch of
           erasures [1, 9];
  store    the OSD EC data path: a 12-OSD in-process cluster, an
           erasure pool on profile plugin=tpu k=8 m=3 (stripe_unit
           4096), 256 x 4 MiB objects written through librados at 16
           in flight, read back, one OSD killed, 64 objects read
           degraded -- all byte-identical to what was written, and the
           OSDs' ec_batch counters must show every launch rode the mesh
           with device-fused CRCs;
  crush    BASELINE config 5: 10M PG->OSD mappings over the 1000-OSD
           depth-4 map in 2M-lane launches, 512 lanes of the timed
           launches checked against the scalar crush_do_rule.

It requires ``jax.devices()[0].platform == "tpu"`` and exits non-zero,
printing no result, otherwise.  Everything that needs the chip runs in
THIS process (a chip belongs to one process at a time); the only child
is ``make``, which builds native/ from the committed sources.  Any
phase that fails makes the exit code non-zero.  The last line of stdout
is one JSON object: {"ok": true, "device": {...}}.

``--cpu-rehearsal`` is for debugging the script itself in a sandbox
with no accelerator: toy sizes on the CPU backend, its result marked
``"rehearsal": true``.  It is never the default and never chosen by
what devices are found.  ``--objects`` is the one permitted cut of a
chip run (the store phase's object count); a cut is printed.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import subprocess
import sys
import time
import traceback

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
FULL_OBJECTS = 256
T0 = time.monotonic()


def say(msg: str) -> None:
    print(f"[chip_smoke +{time.monotonic() - T0:7.1f}s] {msg}", flush=True)


class CompileMeter:
    """Compile seconds and persistent-cache hits/misses, from jax's own
    monitoring events."""

    def __init__(self) -> None:
        import jax.monitoring as mon
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            self.seconds += secs

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> tuple[float, int, int]:
        return self.seconds, self.hits, self.misses


def build_native() -> None:
    """native/ from the committed sources: the .so/.o files are
    untracked, and a copied tree may carry stale ones."""
    subprocess.run(["make", "-C", os.path.join(ROOT, "native"),
                    "clean", "all"], check=True, stdout=subprocess.DEVNULL)
    from ceph_tpu import native
    if not native.available():
        raise RuntimeError("native library failed to load after make")
    if native.get_dencfast() is None:
        raise RuntimeError("native denc codec failed to load after make")


def oracle(matrix: np.ndarray, data: np.ndarray) -> np.ndarray:
    """Host GF(2^8) oracle: the C++ gf8.cc (independent of every device
    engine), itself pinned to the pure-numpy gf_matmul on a slice."""
    from ceph_tpu.gf import gf_matmul
    from ceph_tpu.native import gf8_matmul
    want = gf8_matmul(matrix, data)
    if not np.array_equal(want[:, :512], gf_matmul(matrix, data[:, :512])):
        raise RuntimeError("native gf8 oracle disagrees with numpy oracle")
    return want


def code_matrices() -> list[tuple[str, int, np.ndarray]]:
    """(tag, k, coefficient matrix): encode and 2-erasure decode of the
    two BASELINE codes, through the plugin's own matrix builders."""
    from ceph_tpu.ec import registry
    out = []
    for tag, k, m, tech, erasures in (("rs_k8m3", 8, 3, "reed_sol_van",
                                       [1, 9]),
                                      ("cauchy_k10m4", 10, 4, "cauchy",
                                       [2, 11])):
        codec = registry().factory("tpu", {"k": str(k), "m": str(m),
                                           "technique": tech})
        out.append((f"{tag}_encode", k,
                    np.ascontiguousarray(codec.encode_matrix[k:], np.uint8)))
        out.append((f"{tag}_decode", k, np.ascontiguousarray(
            codec.decode_matrix_for(erasures), np.uint8)))
    return out


# -- phase: kernels ----------------------------------------------------------

def phase_kernels(sz: dict, seed: int) -> dict:
    import jax
    from ceph_tpu.ops import gf2kernels as G

    if G._interpret() != sz["rehearsal"]:
        raise RuntimeError("pallas interpret mode on a chip run")
    b, lane = sz["kernel_batch"], sz["kernel_lane"]
    tile = G._pick_tile(lane)
    rng = np.random.default_rng(seed)
    ok: list[str] = []
    broken: list[str] = []
    for tag, k, mat in code_matrices():
        data = rng.integers(0, 256, size=(b, k, lane), dtype=np.uint8)
        xd = jax.device_put(data)
        want = np.stack([oracle(mat, data[i]) for i in range(b)])
        r8 = 8 * mat.shape[0]
        w = G.bitmatrix_device(mat)
        cfg = G._g2_cfg(k)
        plan = G._gN_plan(k, b, lane, cfg)
        families = {
            "v1": lambda: G._make_pallas_fn(
                r8, k, lane, min(G.LANE_TILE, lane))(w, xd[0])[None],
            "v1_batch": lambda: G._make_pallas_batch_fn(
                r8, k, b, lane, tile)(w, xd),
        }
        if plan:            # the shape rule keeps k=10 off the packed kernel
            families["gN"] = lambda: G._run_gN(mat, xd, b, k, lane, cfg,
                                               *plan)
        elif k == 8:
            broken.append(f"gN:{tag}: not selected at the north-star width")
        for name, launch in families.items():
            # every family x matrix gets its run: one chip call shows
            # everything the compiler refuses
            try:
                got = np.asarray(launch())
                if not np.array_equal(got, want[:got.shape[0]]):
                    raise RuntimeError("bytes differ from the host oracle")
                ok.append(f"{name}:{tag}")
            except Exception as e:
                traceback.print_exc()
                broken.append(f"{name}:{tag}: {type(e).__name__}: "
                              f"{str(e).splitlines()[0][:200]}")
    say(f"kernels: {len(ok)} pallas family x matrix launches byte-exact "
        f"at B={b} L={lane} (gN cfg {G.G2_DEFAULT}); {len(broken)} broken")
    if broken:
        raise RuntimeError(f"pallas kernels refused or wrong: {broken}")
    return {"byte_exact": ok, "batch": b, "lane": lane,
            "devices": [str(jax.devices()[0])]}      # bare device_put


# -- phase: codec ------------------------------------------------------------

def phase_codec(sz: dict, seed: int) -> dict:
    import jax
    import jax.numpy as jnp
    from ceph_tpu.ec import registry
    from ceph_tpu.ops.gf2kernels import batch_engine

    k, m = 8, 3
    stripes, chunk = sz["codec_stripes"], sz["codec_chunk"]
    erasures = [1, 9]
    codec = registry().factory("tpu", {"k": str(k), "m": str(m),
                                       "technique": "reed_sol_van"})
    enc_mat = codec.encode_matrix[k:]
    dec_mat = codec.decode_matrix_for(erasures)
    engines = {"encode": batch_engine(enc_mat, stripes, k, chunk),
               "decode": batch_engine(dec_mat, stripes, k, chunk)}
    say(f"codec: engines {engines}")
    if not sz["rehearsal"] and not all(
            e in ("gN", "v1") for e in engines.values()):
        raise RuntimeError(f"a non-pallas engine would serve: {engines}")

    # seeded bytes generated ON the device, in blocks (the generator's
    # 32-bit intermediates would triple a one-shot 1 GiB draw)
    block = min(stripes, 128)
    draw = jax.jit(lambda key: jax.random.bits(
        key, (block, k, chunk), jnp.uint8))
    keys = jax.random.split(jax.random.key(seed), stripes // block)
    data = jnp.concatenate([draw(key) for key in keys])
    data.block_until_ready()
    gib = data.size / 2**30
    say(f"codec: {stripes} stripes x {k * chunk >> 10} KiB on "
        f"{sorted(str(d) for d in data.devices())} ({gib:.2f} GiB)")

    def timed(fn):
        fn().block_until_ready()                # compile + warm
        t0 = time.perf_counter()
        out = fn()
        out.block_until_ready()
        return out, time.perf_counter() - t0

    parity, enc_s = timed(lambda: codec.encode_batch(data))

    def chunks(ids):
        # static slices: an index-array gather over the 1.4 GiB batch
        # cost XLA 37 s of compile on the v5e
        return jnp.stack([data[:, i] if i < k else parity[:, i - k]
                          for i in ids], axis=1)

    lost = chunks(erasures)
    survivors = chunks([i for i in range(k + m) if i not in erasures][:k])
    survivors.block_until_ready()
    rec, dec_s = timed(lambda: codec.decode_batch(erasures, survivors))
    if rec.shape != lost.shape or not bool(jnp.array_equal(rec, lost)):
        raise RuntimeError("decode_batch did not recover the lost chunks")

    # sampled stripes, byte for byte against the host oracle
    rng = np.random.default_rng(seed)
    for s in sorted(rng.choice(stripes, size=min(8, stripes),
                               replace=False)):
        d = np.asarray(data[s])
        if not np.array_equal(np.asarray(parity[s]), oracle(enc_mat, d)):
            raise RuntimeError(f"stripe {s}: parity differs from the "
                               f"host oracle")
        if not np.array_equal(
                np.asarray(rec[s]),
                oracle(dec_mat, np.asarray(survivors[s]))):
            raise RuntimeError(f"stripe {s}: recovered chunks differ "
                               f"from the host oracle")
    say(f"codec: encode {enc_s * 1e3:.1f} ms, decode {dec_s * 1e3:.1f} ms "
        f"per {gib:.2f} GiB launch; recovered == lost; sampled stripes "
        f"== host oracle")
    return {"engines": engines, "stripes": stripes,
            "stripe_bytes": k * chunk,
            "encode_launch_s": round(enc_s, 4),
            "decode_launch_s": round(dec_s, 4),
            "devices": sorted(str(d) for d in parity.devices())}


# -- phase: store ------------------------------------------------------------

def object_bytes(seed: int, i: int, size: int) -> bytes:
    return np.random.default_rng([seed, i]).bytes(size)


async def _store(sz: dict, seed: int) -> dict:
    import jax
    from ceph_tpu.client.rados import Rados
    from ceph_tpu.loadgen.cluster import SimCluster

    n_obj, size = sz["objects"], sz["obj_bytes"]
    n_degraded, inflight = min(64, n_obj), 16
    cluster = await SimCluster.create(12)
    rados = None
    try:
        rados = await Rados(cluster.addr, name="client.chip-smoke").connect()
        await rados.mon_command(
            "osd erasure-code-profile set",
            {"name": "smoke-k8m3", "profile": {
                "plugin": "tpu", "k": "8", "m": "3",
                "technique": "reed_sol_van"}})
        await rados.pool_create("smoke", pg_num=64, pool_type="erasure",
                                erasure_code_profile="smoke-k8m3")
        ioctx = await rados.open_ioctx("smoke")
        gate = asyncio.Semaphore(inflight)

        async def write(i: int) -> None:
            async with gate:
                await ioctx.write_full(f"obj-{i}",
                                       object_bytes(seed, i, size))

        async def verify(i: int) -> None:
            async with gate:
                got = await ioctx.read(f"obj-{i}")
            if got != object_bytes(seed, i, size):
                raise RuntimeError(f"obj-{i}: read back differs from "
                                   f"what was written")

        async def timed(coros) -> float:
            t0 = time.perf_counter()
            await asyncio.gather(*coros)
            return time.perf_counter() - t0

        write_s = await timed(write(i) for i in range(n_obj))
        say(f"store: wrote {n_obj} x {size >> 20} MiB in {write_s:.1f}s")
        read_s = await timed(verify(i) for i in range(n_obj))
        say(f"store: read back all, byte-identical, in {read_s:.1f}s")

        victim = cluster.osds[3].whoami
        await cluster.kill_osd(3)
        if not await cluster.wait_down(victim, timeout=60.0):
            raise RuntimeError(f"osd.{victim} never marked down")
        degraded_s = await timed(verify(i) for i in range(n_degraded))
        say(f"store: osd.{victim} killed; {n_degraded} degraded reads "
            f"byte-identical in {degraded_s:.1f}s")

        c = cluster.perf_counters("ec_batch")
        gauges = {int(osd.perf.get("ec_batch").dump().get("mesh_devices", 0))
                  for osd in cluster.osds if not osd.is_stopped()}
        downs = {int(e["message"].split()[0].removeprefix("osd."))
                 for e in cluster.mon.services.cluster_log
                 if "marked down" in e["message"]}
    finally:
        if rados is not None:
            await rados.shutdown()
        await cluster.stop()

    checks = {
        "encode launches > 0": c.get("encode_launches", 0) > 0,
        "decode launches > 0": c.get("decode_launches", 0) > 0,
        "every batch rode the mesh":
            c.get("mesh_launches", 0) == c.get("batches", -1),
        "no per-op fallback": c.get("fallback_ops", 0) == 0,
        "CRCs fused on device": c.get("crc_fused_launches", 0) > 0
            and c.get("crc_host_batches", 0) == 0,
        "mesh spans every device":
            gauges - {0} == {jax.device_count()},
        "only the victim went down": downs == {victim},
    }
    bad = [name for name, ok in checks.items() if not ok]
    if bad:
        raise RuntimeError(f"store gates failed: {bad}; ec_batch={c} "
                           f"mesh_devices={gauges} marked_down={downs}")
    say(f"store: {c['batches']} batches = {c['mesh_launches']} mesh "
        f"launches ({c['encode_launches']} encode, "
        f"{c['decode_launches']} decode, {c['crc_fused_launches']} with "
        f"fused CRC) on {jax.device_count()} device(s); only "
        f"osd.{victim} went down")
    return {"objects": n_obj, "obj_bytes": size,
            "write_s": round(write_s, 2), "read_s": round(read_s, 2),
            "degraded_reads": n_degraded,
            "degraded_s": round(degraded_s, 2),
            "batches": c["batches"],
            # mesh_devices == device_count was just asserted
            "devices": sorted(str(d) for d in jax.devices())}


def phase_store(sz: dict, seed: int) -> dict:
    return asyncio.run(_store(sz, seed))


# -- phase: crush ------------------------------------------------------------

def phase_crush(sz: dict, seed: int) -> dict:
    from ceph_tpu.tools.crush_bench import run_crush_bench
    res = run_crush_bench(pgs=sz["crush_pgs"], batch=sz["crush_batch"],
                          verify=sz["crush_verify"])
    import jax
    res["devices"] = [str(jax.devices()[0])]         # bare jnp.asarray
    say(f"crush: {res['n_mappings']} mappings in {res['launches']} "
        f"launches of {res['batch']} lanes, {res['elapsed_s']}s "
        f"(first launch {res['first_launch_s']}s); "
        f"{res['verified_lanes']} sampled lanes == scalar crush_do_rule")
    return res


# -- phase: mesh (every device of the host) ----------------------------------

def phase_mesh(sz: dict, seed: int) -> dict:
    """A MeshCodec launch must place output shards on EVERY device, and
    on a multi-chip host the sharded dry run takes the real devices."""
    import jax
    from ceph_tpu.ec import registry
    from ceph_tpu.parallel.mesh_codec import MeshCodec

    codec = registry().factory("tpu", {"k": "8", "m": "3",
                                       "technique": "reed_sol_van"})
    mesh = MeshCodec()
    rng = np.random.default_rng(seed)
    batch = rng.integers(0, 256, size=(mesh.pad_batch(128), 8, 4096),
                         dtype=np.uint8)
    out = mesh.encode(codec, batch, out_np=False)
    on = {s.device for s in out.addressable_shards}
    if on != set(jax.devices()):
        raise RuntimeError(f"mesh output on {on}, host has "
                           f"{jax.devices()}")
    if not np.array_equal(np.asarray(out[0]),
                          oracle(codec.encode_matrix[8:], batch[0])):
        raise RuntimeError("mesh encode differs from the host oracle")
    n = jax.device_count()
    if n > 1:
        import __graft_entry__
        __graft_entry__.dryrun_multichip(n)
    say(f"mesh: one launch sharded over {sorted(str(d) for d in on)}"
        + (f"; dryrun_multichip({n}) on the real devices" if n > 1 else ""))
    return {"devices": sorted(str(d) for d in on)}


FULL = dict(rehearsal=False, kernel_batch=8, kernel_lane=1 << 17,
            codec_stripes=1024, codec_chunk=1 << 17,
            objects=FULL_OBJECTS, obj_bytes=4 << 20,
            crush_pgs=10_000_000, crush_batch=2_000_000, crush_verify=512)
REHEARSAL = dict(rehearsal=True, kernel_batch=2, kernel_lane=512,
                 codec_stripes=8, codec_chunk=4096,
                 objects=6, obj_bytes=4 << 20,
                 crush_pgs=20_000, crush_batch=10_000, crush_verify=16)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--objects", type=int, default=None,
                    help=f"store-phase object count (default "
                         f"{FULL_OBJECTS}; fewer is a CUT and is printed)")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="debug the script on the CPU backend at toy "
                         "sizes (not a chip result)")
    args = ap.parse_args(argv)

    if not all(os.path.isdir(os.path.join(ROOT, d))
               for d in ("ceph_tpu", "native")):
        print("chip_smoke: run it from a tpu-rados checkout (ceph_tpu/ "
              "and native/ sit beside this script)", file=sys.stderr)
        return 2
    import jax
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if dev.platform != ("cpu" if args.cpu_rehearsal else "tpu"):
        print(f"chip_smoke: needs a TPU, jax found {device}",
              file=sys.stderr)
        return 2
    say(f"device: {device}")

    sz = dict(REHEARSAL if args.cpu_rehearsal else FULL)
    if args.objects is not None:
        sz["objects"] = args.objects
    if not args.cpu_rehearsal and sz["objects"] < FULL_OBJECTS:
        say(f"CUT: store phase writes {sz['objects']} objects, not "
            f"{FULL_OBJECTS}")

    build_native()
    from ceph_tpu.common.compile_cache import enable_compile_cache
    say(f"native/ built from source; compile cache at "
        f"{enable_compile_cache()}")
    meter = CompileMeter()

    report: dict = {}
    failed: list[str] = []
    for name, phase in (("kernels", phase_kernels), ("codec", phase_codec),
                        ("store", phase_store), ("crush", phase_crush),
                        ("mesh", phase_mesh)):
        t0, c0 = time.perf_counter(), meter.snapshot()
        try:
            report[name] = phase(sz, args.seed)
        except Exception:
            # boundary: every phase gets its run, so one chip call shows
            # everything that is broken; any failure fails the script
            traceback.print_exc()
            failed.append(name)
            report[name] = {"error": traceback.format_exc(limit=1)
                            .strip().splitlines()[-1]}
        c1 = meter.snapshot()
        report[name].update(
            wall_s=round(time.perf_counter() - t0, 2),
            compile_s=round(c1[0] - c0[0], 2),
            cache_hits=c1[1] - c0[1], cache_misses=c1[2] - c0[2])
        say(f"phase {name}: {'FAILED' if name in failed else 'ok'} "
            f"wall {report[name]['wall_s']}s compile "
            f"{report[name]['compile_s']}s cache hits/misses "
            f"{report[name]['cache_hits']}/{report[name]['cache_misses']}")

    say("devices by phase: " + "; ".join(
        f"{name}: {', '.join(r.get('devices', ['-']))}"
        for name, r in report.items()))
    total = meter.snapshot()
    print(json.dumps({"phases": report, "total_s": round(
        time.monotonic() - T0, 1), "compile_s": round(total[0], 2),
        "cache_hits": total[1], "cache_misses": total[2]}), flush=True)
    result = {"ok": not failed, "device": device}
    if failed:
        result["failed"] = failed
    if args.cpu_rehearsal:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
