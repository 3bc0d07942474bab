"""Erasure-code micro-benchmark, harness-compatible with the reference.

Mirrors ceph_erasure_code_benchmark's contract
(src/test/erasure-code/ceph_erasure_code_benchmark.cc): plugin selected by
name+profile only (:170), encode loop over a fixed buffer, output one
tab-separated line "<seconds>\t<total KiB>" (:193), decode mode with random
or exhaustive erasures and byte-for-byte verification of recovered chunks
(:234-244).

Extra (TPU-native) mode: --batch B runs the batched device pipeline --
B stripes per launch, data device-resident, which is the deployment shape
(stripes stream through HBM; the OSD EC backend batches stripes across
PGs the same way).
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


def run_encode(codec, size: int, iterations: int, batch: int) -> tuple[float, int]:
    k = codec.get_data_chunk_count()
    n = codec.get_chunk_count()
    want = set(range(n))
    if batch > 1:
        # device-resident batched pipeline
        chunk = codec.get_chunk_size(size)
        rng = np.random.default_rng(0)
        data = rng.integers(0, 256, size=(batch, k, chunk), dtype=np.uint8)
        # warm up compile
        out = codec.encode_batch(data)
        _block(out)
        begin = time.perf_counter()
        for _ in range(iterations):
            out = codec.encode_batch(data)
        _block(out)
        elapsed = time.perf_counter() - begin
        total_kib = batch * k * chunk * iterations // 1024
        return elapsed, total_kib
    buf = b"X" * size
    begin = time.perf_counter()
    for _ in range(iterations):
        codec.encode(want, buf)
    elapsed = time.perf_counter() - begin
    return elapsed, size * iterations // 1024


def _block(out):
    try:
        out.block_until_ready()
    except AttributeError:
        pass


def count_erasures(n: int, erasures: int):
    for combo in itertools.combinations(range(n), erasures):
        yield list(combo)


def run_decode(codec, size: int, iterations: int, erasures: int,
               exhaustive: bool, verify: bool) -> tuple[float, int]:
    k = codec.get_data_chunk_count()
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
    p.add_argument("-s", "--size", type=int, default=1 << 20,
                   help="object size per op (bytes)")
    p.add_argument("-i", "--iterations", type=int, default=10)
    p.add_argument("-w", "--workload", choices=("encode", "decode"),
                   default="encode")
    p.add_argument("-e", "--erasures", type=int, default=1)
    p.add_argument("--erasures-generation", choices=("random", "exhaustive"),
                   default="random")
    p.add_argument("--erased", type=int, action="append",
                   help="explicit chunk ids to erase")
    p.add_argument("--batch", type=int, default=1,
                   help="stripes per device launch (TPU pipeline mode)")
    p.add_argument("--verify", action="store_true")
    args = p.parse_args(argv)
    enable_compile_cache()

    profile = parse_profile(args)
    codec = registry().factory(args.plugin, profile)

    if args.workload == "encode":
        elapsed, kib = run_encode(codec, args.size, args.iterations,
                                  args.batch)
    else:
        exhaustive = args.erasures_generation == "exhaustive"
        verify = args.verify or exhaustive
        elapsed, kib = run_decode(codec, args.size, args.iterations,
                                  args.erasures, exhaustive, verify)
    print(f"{elapsed:.6f}\t{kib}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
