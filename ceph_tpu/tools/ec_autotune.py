"""Autotune the GF(2) kernel families on the live backend.

Two sweeps, both parity-gated against the host oracle:

  * the MXU-packed family: the (unpack, mm dtype, pack, tile, group)
    space of ceph_tpu/ops/gf2kernels._make_pallas_batch_fn_gN on a
    device-resident stripe batch (TPU only -- needs pallas);
  * dense vs scheduled: the dense bit-matmul against the
    CSE-minimized XOR schedule (ops/xor_schedule.py) per (k, m,
    chunk), recording the winner under the "xor_sched" key of
    ceph_tpu/ops/gf2_tuned.json -- the cost model
    (xor_schedule.want_scheduled) serves it by default from then on.
    ``--codes lrc,pmsr`` extends this sweep to the recovery-code
    matrix families (LRC local-parity/local-repair rows, PMSR
    parity/fragment-aggregate matrices): exactly the sparse GF(2)
    shapes where the schedule should win on CPU, keyed by their
    matrix dims (the key the runtime cost model looks up; same dims
    = same kernel family, so the winner transfers).

The reference tunes its SIMD technique per-CPU at plugin load
(src/erasure-code/isa/ErasureCodeIsa.cc picks AVX2/AVX512 paths); this
is the accelerator equivalent, run once per hardware generation:

    python -m ceph_tpu.tools.ec_autotune --k 8 --m 3 --write

``--cpu-smoke`` shrinks the shapes, skips the pallas sweep and runs
the dense-vs-scheduled sweep on the CPU backend, so the sweep harness
itself is exercised by tier-1 (tests/test_xor_schedule.py) instead of
rotting as TPU-only dead code; pair it with ``--out`` to keep smoke
winners out of the real tuned file.
"""

from __future__ import annotations

import argparse
import itertools
import json
import sys
import time

import numpy as np

from ..common.compile_cache import enable_compile_cache


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def stage_batch(rng, batch: int, k: int, chunk: int):
    import jax
    import jax.numpy as jnp
    assert batch % 8 == 0, "batch must be a multiple of 8"
    seed_rows = min(batch, 8)
    seed = rng.integers(0, 256, size=(seed_rows, k, chunk),
                        dtype=np.uint8)
    dev = jax.device_put(seed)
    out = jnp.tile(dev, (batch // seed_rows, 1, 1))
    out.block_until_ready()
    return out


def time_fn(fn, w, xd, iters: int = 8) -> float:
    out = fn(w, xd)
    out.block_until_ready()          # compile + warm
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(w, xd)
    out.block_until_ready()
    return (time.perf_counter() - t0) / iters


def sweep(k: int, m: int, batch: int, chunk: int,
          budget_s: float = 600.0) -> list[dict]:
    from ..gf import gen_rs_matrix, gf_matmul
    from ..ops import gf2kernels as G
    import jax.numpy as jnp

    t_start = time.monotonic()
    gen = gen_rs_matrix(k + m, k)
    mat = np.ascontiguousarray(gen[k:], np.uint8)
    rng = np.random.default_rng(0)
    xd = stage_batch(rng, batch, k, chunk)
    # oracle slice for the parity gate
    sample = np.asarray(xd[:2, :, :512])
    want = [gf_matmul(mat, sample[i]) for i in range(2)]

    g_max = G.pick_group(k, batch)
    groups = sorted({g for g in (1, 2, 4) if g <= g_max})
    tiles = [t for t in (4096, 8192, 16384, 32768) if chunk % t == 0]
    results = []
    combos = list(itertools.product(
        groups, ("concat", "bcast"), ("int8", "bf16"), ("vpu", "mxu"),
        tiles))
    log(f"sweeping {len(combos)} configs (k={k} m={m} batch={batch} "
        f"chunk={chunk})")
    for g, unpack, mm, pack, tile in combos:
        if time.monotonic() - t_start > budget_s:
            log("budget exhausted; stopping sweep")
            break
        tag = f"g={g} unpack={unpack} mm={mm} pack={pack} tile={tile}"
        try:
            fn = G._make_pallas_batch_fn_gN(
                8 * m, k, batch, chunk, g, tile, unpack, mm, pack)
            w = G._w_gN_device(mat.tobytes(), m, k, g, mm)
            out = fn(w, xd)
            got = np.asarray(out[:2, :, :512])
            if not all(np.array_equal(got[i], want[i]) for i in (0, 1)):
                log(f"  {tag}: PARITY FAIL")
                continue
            dt = time_fn(fn, w, xd)
            gibps = batch * k * chunk / dt / 2**30
            log(f"  {tag}: {gibps:.1f} GiB/s")
            results.append({"g": g, "unpack": unpack, "mm": mm,
                            "pack": pack, "tile": tile,
                            "gibps": round(gibps, 2)})
        except Exception as e:
            log(f"  {tag}: ERROR {type(e).__name__}: {str(e)[:100]}")
    return sorted(results, key=lambda r: -r["gibps"])


def sweep_engines(k: int, m: int, batch: int, chunk: int,
                  iters: int = 8) -> dict | None:
    """Dense vs scheduled on the RS (k, m) parity matrix (the
    headline family): see ``sweep_matrix_engines``."""
    from ..gf import gen_rs_matrix
    gen = gen_rs_matrix(k + m, k)
    return sweep_matrix_engines(
        np.ascontiguousarray(gen[k:], np.uint8), batch, chunk,
        iters=iters)


def code_matrices(codes: list[str],
                  smoke: bool = False) -> list[tuple[str, np.ndarray]]:
    """The recovery-code GF(2^8) matrix families worth a tuned entry:
    LRC local-parity/local-repair rows and PMSR parity/repair-
    aggregate matrices -- the sparse shapes where the CSE-minimized
    schedule should beat the dense contraction on CPU.  Tags name the
    provenance; the tuned keys are derived from the matrix dims (the
    same key ``want_scheduled`` looks up at run time).  Smoke swaps
    the pmsr shape down to k=3 so the tier-1 harness never pays the
    dense k=5 parity matrix's multi-second CSE pass."""
    from ..ec import registry
    out: list[tuple[str, np.ndarray]] = []
    if "lrc" in codes:
        lrc = registry().factory(
            "lrc", {"k": "8", "m": "4", "l": "3"})
        out.append(("lrc_k8m4l3_parity", lrc.parity_matrix))
        # single-loss local repair: the lost chunk over its group
        lost = 0
        src = tuple(sorted(
            lrc.minimum_to_decode({lost},
                                  set(range(16)) - {lost}).keys()))
        out.append(("lrc_k8m4l3_local_repair",
                    lrc.repair_matrix(src, (lost,))))
    if "pmsr" in codes:
        pk, pm = (3, 2) if smoke else (5, 4)
        pmsr = registry().factory("pmsr",
                                  {"k": str(pk), "m": str(pm)})
        out.append((f"pmsr_k{pk}m{pm}_parity", pmsr.parity_matrix))
        helpers = tuple(range(1, 1 + pmsr.d))
        out.append((f"pmsr_k{pk}m{pm}_aggregate",
                    pmsr.aggregate_matrix(0, helpers)))
    return out


def sweep_matrix_engines(mat: np.ndarray, batch: int, lane: int,
                         iters: int = 8,
                         tag: str = "") -> dict | None:
    """Dense vs scheduled on one (matrix, batch, lane) shape: time the
    dense bit-matmul family against the CSE-minimized XOR schedule on
    identical device-resident batches, byte-parity-gate both against
    the host oracle, and return the winner record the cost model
    consumes (None when the scheduled family cannot serve)."""
    import os
    from ..gf import gf_matmul
    from ..ops import gf2kernels as G
    from ..ops import xor_schedule as XS

    mat = np.ascontiguousarray(mat, np.uint8)
    m, k = mat.shape
    rng = np.random.default_rng(0)
    xd = stage_batch(rng, batch, k, lane)
    ncheck = min(512, lane)
    sample = np.asarray(xd[:1, :, :ncheck])
    want = gf_matmul(mat, sample[0])

    def timed(fn) -> tuple[float, np.ndarray]:
        out = fn()
        out.block_until_ready()
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn()
        out.block_until_ready()
        return (time.perf_counter() - t0) / iters, \
            np.asarray(out[:1, :, :ncheck])

    os.environ["CEPH_TPU_XOR_SCHED"] = "0"
    try:
        dt_dense, got_dense = timed(
            lambda: G.gf_matmul_batch_device(mat, xd))
    finally:
        os.environ.pop("CEPH_TPU_XOR_SCHED", None)
    if not np.array_equal(got_dense[0], want):
        log("engine sweep: dense PARITY FAIL")
        return None
    sched = XS.schedule_for(G.bitmatrix_i8(mat))

    def run_sched():
        out = XS.sched_matmul_batch_device(sched, mat, xd, batch, k,
                                           lane)
        if out is None:
            raise RuntimeError("scheduled kernel rejected")
        return out

    try:
        dt_sched, got_sched = timed(run_sched)
    except Exception as e:
        log(f"engine sweep: scheduled ERROR {type(e).__name__}: "
            f"{str(e)[:100]}")
        return None
    if not np.array_equal(got_sched[0], want):
        log("engine sweep: scheduled PARITY FAIL")
        return None
    gibps = lambda dt: batch * k * lane / dt / 2**30  # noqa: E731
    rec = {
        "engine": "scheduled" if dt_sched < dt_dense else "dense",
        "dense_gibps": round(gibps(dt_dense), 3),
        "sched_gibps": round(gibps(dt_sched), 3),
        "sched_terms": sched.n_terms,
        "naive_terms": sched.naive_terms,
        "reduction_pct": round(100 * sched.reduction, 1),
    }
    log(f"engine sweep {tag or f'{k},{m}'} batch={batch} "
        f"lane={lane}: dense={rec['dense_gibps']} GiB/s "
        f"sched={rec['sched_gibps']} GiB/s -> {rec['engine']} "
        f"(xor terms {sched.n_terms}/{sched.naive_terms})")
    return rec


def _write_tuned(path: str, update: dict) -> None:
    try:
        with open(path) as f:
            tuned = json.load(f)
    except Exception:
        tuned = {}
    for key, val in update.items():
        if isinstance(val, dict) and isinstance(tuned.get(key), dict):
            tuned[key].update(val)
        else:
            tuned[key] = val
    with open(path, "w") as f:
        json.dump(tuned, f, indent=2, sort_keys=True)
    log(f"wrote {path}: {sorted(update)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--k", type=int, default=8)
    ap.add_argument("--m", type=int, default=3)
    ap.add_argument("--batch", type=int, default=256,
                    help="stripes per launch (rounded to a multiple of 8)")
    ap.add_argument("--chunk", type=int, default=1 << 17)
    ap.add_argument("--budget-s", type=float, default=600.0)
    ap.add_argument("--write", action="store_true",
                    help="persist the winners to the tuned file")
    ap.add_argument("--out", default=None,
                    help="tuned-file path (default: the live "
                         "ceph_tpu/ops/gf2_tuned.json)")
    ap.add_argument("--cpu-smoke", action="store_true",
                    help="tier-1 harness mode: tiny shapes, skip the "
                         "pallas sweep, engine sweep only")
    ap.add_argument("--codes", default="",
                    help="comma list of recovery-code matrix families "
                         "to sweep into xor_sched entries (lrc,pmsr): "
                         "local-parity / repair / fragment-aggregate "
                         "matrices keyed by their matrix dims")
    args = ap.parse_args(argv)
    enable_compile_cache()

    import jax
    log(f"backend={jax.default_backend()} devices={jax.devices()}")
    if args.cpu_smoke:
        args.batch = min(args.batch, 8)
        args.chunk = min(args.chunk, 4096)
    args.batch = max(8, (args.batch // 8) * 8)

    results = []
    if not args.cpu_smoke:
        results = sweep(args.k, args.m, args.batch, args.chunk,
                        args.budget_s)
        if not results:
            log("no working pallas config found")
    iters = 2 if args.cpu_smoke else 8
    engines = sweep_engines(args.k, args.m, args.batch, args.chunk,
                            iters=iters)
    code_recs: dict[str, dict] = {}
    codes = [c for c in args.codes.split(",") if c]
    for tag, mat in code_matrices(codes, smoke=args.cpu_smoke):
        r, c = mat.shape
        # lane at the granularity the runtime launches with: the flat
        # sub-chunk dialect reshapes chunks, so tune at a sub-lane
        lane = max(512, min(args.chunk, 4096)) if args.cpu_smoke \
            else args.chunk
        rec = sweep_matrix_engines(mat, args.batch, lane,
                                   iters=iters, tag=tag)
        if rec is not None:
            rec["tag"] = tag
            code_recs[f"{c},{r}"] = rec
    if not results and engines is None and not code_recs:
        log("no working config found")
        return 1
    report = {"k": args.k, "m": args.m, "chunk": args.chunk,
              "xor_sched": engines}
    if code_recs:
        report["xor_sched_codes"] = code_recs
    if results:
        report["best"] = results[0]
        report["top5"] = results[:5]
    print(json.dumps(report, indent=2))
    if args.write:
        from ..ops.gf2kernels import _TUNED_PATH
        path = args.out or _TUNED_PATH
        update: dict = {}
        if results:
            update[str(args.k)] = {kk: results[0][kk] for kk in
                                   ("g", "unpack", "mm", "pack",
                                    "tile")}
        sched_update = {}
        if engines is not None:
            sched_update.update({
                f"{args.k},{args.m},{args.chunk}": engines,
                f"{args.k},{args.m}": engines,
            })
        sched_update.update(code_recs)
        if sched_update:
            update["xor_sched"] = sched_update
        _write_tuned(path, update)
    return 0


if __name__ == "__main__":
    sys.exit(main())
