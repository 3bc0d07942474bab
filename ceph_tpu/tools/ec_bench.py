"""Erasure-code micro-benchmark, harness-compatible with the reference.

Mirrors ceph_erasure_code_benchmark's contract
(src/test/erasure-code/ceph_erasure_code_benchmark.cc): plugin selected by
name+profile only (:170), encode loop over a fixed buffer, output one
tab-separated line "<seconds>\t<total KiB>" (:193), decode mode with random
or exhaustive erasures and byte-for-byte verification of recovered chunks
(:234-244).

Extra (TPU-native) mode: --batch B hands the plugin B objects a call
through its batch entry points (``encode_batch`` / ``decode_stripes``),
host buffers in and host buffers out, one call in flight: what is timed
is what a caller of the interface waits for, upload and copy-out
included, not a kernel over device-resident data.  It is the op that the
benchmark cell ``rs_k8m3_codec_1m_b1024`` measures (benchmark/drivers/
codec_loop.py reaches the plugin through the same two calls); the first
call of a shape compiles and is not timed.  With several sizes
(``--size 4096,65536,1048576``) a call is B objects of EACH size, their
order shuffled, through ``encode_objects`` / ``decode_objects``: the op
of ``cauchy_k10m4_codec_mixed_4k_1m`` (benchmark/drivers/
codec_objects_loop.py).  The engine that served is printed on stderr;
stdout stays the one contract line.
"""

from __future__ import annotations

import argparse
import itertools
import sys
import time

import numpy as np

from ..common.compile_cache import enable_compile_cache
from ..ec import registry


def parse_profile(args) -> dict:
    profile = {}
    for kv in args.parameter or []:
        k, _, v = kv.partition("=")
        profile[k] = v
    profile.setdefault("k", str(args.k))
    profile.setdefault("m", str(args.m))
    return profile


def _mixed_objects(codec, sizes: list[int], batch: int) -> list[np.ndarray]:
    """``batch`` objects of each of ``sizes``, one flat array each, in a
    shuffled order."""
    if not hasattr(codec, "decode_objects"):
        raise SystemExit("several sizes need a plugin with entry points "
                         "over objects of unequal size (encode_objects, "
                         "decode_objects): tpu")
    rng = np.random.default_rng(0)
    objects = [rng.integers(0, 256, size, dtype=np.uint8)
               for size in sizes for _ in range(batch)]
    return [objects[i] for i in rng.permutation(len(objects))]


def run_mixed(codec, sizes: list[int], iterations: int, batch: int,
              workload: str, erasures: int, exhaustive: bool,
              verify: bool) -> tuple[float, int]:
    """The encode or decode loop over ``batch`` objects of each size a
    call, host buffers in and out."""
    n, k = codec.get_chunk_count(), codec.get_data_chunk_count()
    objects = _mixed_objects(codec, sizes, batch)
    parity = codec.encode_objects(objects)          # compiles: not timed
    kib = sum(obj.size for obj in objects) * iterations // 1024
    if workload == "encode":
        begin = time.perf_counter()
        for _ in range(iterations):
            codec.encode_objects(objects)
        return time.perf_counter() - begin, kib
    maps = [np.concatenate([np.stack([
        codec.encode_prepare(obj)[i] for i in range(k)]), par])
        for obj, par in zip(objects, parity)]
    codec.decode_objects(list(range(erasures)), maps)
    begin = time.perf_counter()
    for erased in _draws(n, erasures, exhaustive, iterations):
        got = codec.decode_objects(erased, maps)
        if verify and not all(np.array_equal(lost, stripe[erased])
                              for lost, stripe in zip(got, maps)):
            raise SystemExit(f"byte parity FAILED for erasures {erased}")
    return time.perf_counter() - begin, kib


def run_encode(codec, size: int, iterations: int, batch: int) -> tuple[float, int]:
    n = codec.get_chunk_count()
    want = set(range(n))
    if batch > 1:
        data = _batch_data(codec, size, batch)
        codec.encode_batch(data, out_np=True)       # compiles: not timed
        begin = time.perf_counter()
        for _ in range(iterations):
            codec.encode_batch(data, out_np=True)
        elapsed = time.perf_counter() - begin
        return elapsed, batch * size * iterations // 1024
    buf = b"X" * size
    begin = time.perf_counter()
    for _ in range(iterations):
        codec.encode(want, buf)
    elapsed = time.perf_counter() - begin
    return elapsed, size * iterations // 1024


def _batch_data(codec, size: int, batch: int) -> np.ndarray:
    """(batch, k, chunk) data chunks of ``batch`` objects of ``size``
    bytes, in host memory."""
    if not hasattr(codec, "decode_stripes"):
        raise SystemExit("--batch needs a plugin with batch entry points "
                         "(encode_batch, decode_stripes): tpu")
    chunk = codec.get_chunk_size(size)
    return np.random.default_rng(0).integers(
        0, 256, size=(batch, codec.get_data_chunk_count(), chunk),
        dtype=np.uint8)


def report_engine(codec) -> None:
    """Which engine served the batch launches, on stderr."""
    perf = getattr(codec, "perf", None)
    served = {key.removeprefix("engine_"): val
              for key, val in (perf.dump() if perf else {}).items()
              if key.startswith("engine_")}
    if served:
        print("engine: " + ", ".join(
            f"{name} x{val} launches" for name, val in sorted(served.items())),
            file=sys.stderr)


def count_erasures(n: int, erasures: int):
    for combo in itertools.combinations(range(n), erasures):
        yield list(combo)


def _draws(n: int, erasures: int, exhaustive: bool, iterations: int):
    """The erased ids of each of ``iterations`` batch decodes: every
    pattern in turn, or drawn at random."""
    patterns = list(count_erasures(n, erasures)) if exhaustive else None
    rng = np.random.default_rng(42)
    for i in range(iterations):
        if patterns is not None:
            yield patterns[i % len(patterns)]
        else:
            yield sorted(int(e) for e in
                         rng.choice(n, size=erasures, replace=False))


def run_decode_batch(codec, size: int, iterations: int, erasures: int,
                     exhaustive: bool, verify: bool,
                     batch: int) -> tuple[float, int]:
    """The decode loop over ``batch`` objects a call: the chunk map of
    every object in host memory, the erased chunks back in host memory."""
    n = codec.get_chunk_count()
    data = _batch_data(codec, size, batch)
    stripes = np.concatenate(
        [data, codec.encode_batch(data, out_np=True)], axis=1)
    codec.decode_stripes(list(range(erasures)), stripes, out_np=True)
    begin = time.perf_counter()
    for erased in _draws(n, erasures, exhaustive, iterations):
        got = codec.decode_stripes(erased, stripes, out_np=True)
        if verify and not np.array_equal(got, stripes[:, erased]):
            raise SystemExit(f"byte parity FAILED for erasures {erased}")
    elapsed = time.perf_counter() - begin
    return elapsed, batch * size * iterations // 1024


def run_decode(codec, size: int, iterations: int, erasures: int,
               exhaustive: bool, verify: bool) -> tuple[float, int]:
    n = codec.get_chunk_count()
    rng = np.random.default_rng(42)
    raw = rng.integers(0, 256, size=size, dtype=np.uint8).tobytes()
    encoded = codec.encode(set(range(n)), raw)

    if exhaustive:
        patterns = list(count_erasures(n, erasures))
    else:
        patterns = None

    begin = time.perf_counter()
    done = 0
    i = 0
    while done < iterations:
        if patterns is not None:
            erased = patterns[i % len(patterns)]
        else:
            erased = sorted(rng.choice(n, size=erasures, replace=False))
        i += 1
        avail = {j: encoded[j] for j in range(n) if j not in erased}
        decoded = codec.decode(set(range(n)), avail)
        if verify:
            for e in erased:
                if not np.array_equal(decoded[e], encoded[e]):
                    raise SystemExit(
                        f"byte parity FAILED for chunk {e} erasures {erased}")
        done += 1
    elapsed = time.perf_counter() - begin
    return elapsed, size * iterations // 1024


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="ec_bench")
    p.add_argument("-P", "--parameter", action="append",
                   help="profile k=v (repeatable)")
    p.add_argument("--plugin", default="tpu")
    p.add_argument("-k", type=int, default=8)
    p.add_argument("-m", type=int, default=3)
    p.add_argument("-s", "--size", default=str(1 << 20),
                   help="object size per op (bytes); several, comma-"
                        "separated: --batch objects of each in one call")
    p.add_argument("-i", "--iterations", type=int, default=10)
    p.add_argument("-w", "--workload", choices=("encode", "decode"),
                   default="encode")
    p.add_argument("-e", "--erasures", type=int, default=1)
    p.add_argument("--erasures-generation", choices=("random", "exhaustive"),
                   default="random")
    p.add_argument("--erased", type=int, action="append",
                   help="explicit chunk ids to erase")
    p.add_argument("--batch", type=int, default=1,
                   help="objects a call through the plugin's batch entry "
                        "points, host buffers in and out")
    p.add_argument("--verify", action="store_true")
    args = p.parse_args(argv)
    enable_compile_cache()

    profile = parse_profile(args)
    codec = registry().factory(args.plugin, profile)

    sizes = [int(size) for size in args.size.split(",")]
    size = sizes[0]
    exhaustive = args.erasures_generation == "exhaustive"
    verify = args.verify or exhaustive
    if len(sizes) > 1:
        elapsed, kib = run_mixed(codec, sizes, args.iterations, args.batch,
                                 args.workload, args.erasures, exhaustive,
                                 verify)
    elif args.workload == "encode":
        elapsed, kib = run_encode(codec, size, args.iterations,
                                  args.batch)
    else:
        if args.batch > 1:
            elapsed, kib = run_decode_batch(
                codec, size, args.iterations, args.erasures,
                exhaustive, verify, args.batch)
        else:
            elapsed, kib = run_decode(codec, size, args.iterations,
                                      args.erasures, exhaustive, verify)
    report_engine(codec)
    print(f"{elapsed:.6f}\t{kib}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
