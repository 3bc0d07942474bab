#!/usr/bin/env python3
"""Controls: runs that have to come out as not correct.

The configurations state guarantees, not a precision, so a control
breaks one guarantee where the answer is produced and drives the
cell's own run on top of it:

  parity  every encode launch hands back parity with one byte of each
          stripe flipped (stored parity != generator rows x data);
  crc     every encode launch hands back checksums with one bit flipped
          (a shard's _crc xattr != CRC32C of its bytes);
  lane    every map_pgs call hands back one lane in a thousand with its
          replicas in another order (a mapping != crush_do_rule's);
  none    the sound program, for reading ``correct`` on many seeds in
          one process.

    python benchmark/control.py --workload <name> --fault <f> --seeds 1,2,3 --seconds 8

One JSON line per seed; needs the chip like run.py.  The benchmark's
own runs never come here.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmark import harness               # noqa: E402
from benchmark import run as bench_run      # noqa: E402


@contextlib.contextmanager
def _patched(owner, name: str, wrap):
    sound = getattr(owner, name)
    setattr(owner, name, wrap(sound))
    try:
        yield
    finally:
        setattr(owner, name, sound)


def _encode_fault(alter):
    from ceph_tpu.parallel.mesh_codec import MeshCodec

    def wrap(sound):
        def encode(self, codec, batch, with_crc=False, out_np=True):
            out = sound(self, codec, batch, with_crc=with_crc, out_np=out_np)
            if not with_crc:
                return out
            parity, crcs = (np.array(a) for a in out)
            alter(parity, crcs, codec.k)
            return parity, crcs
        return encode
    return _patched(MeshCodec, "encode", wrap)


def _flip_parity(parity, crcs, k) -> None:
    parity[:, 0, 0] ^= 1


def _flip_crc(parity, crcs, k) -> None:
    crcs[:, k] ^= 1


def _lane_fault():
    from ceph_tpu.crush.vectorized import VectorCrush

    def wrap(sound):
        def map_pgs(self, xs, numrep, osd_weights):
            out = np.array(sound(self, xs, numrep, osd_weights))
            out[::1000] = out[::1000, ::-1]
            return out
        return map_pgs
    return _patched(VectorCrush, "map_pgs", wrap)


FAULTS = {
    "parity": lambda: _encode_fault(_flip_parity),
    "crc": lambda: _encode_fault(_flip_crc),
    "lane": _lane_fault,
    "none": contextlib.nullcontext,
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fault", choices=sorted(FAULTS), required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args(argv)
    try:
        harness.require_program()
        cell = harness.Cell(args.workload)
        device = harness.require_chips(cell.chips)
        harness.build_native()
        harness.enable_compile_cache()
    except harness.HarnessError as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    for seed in (int(s) for s in args.seeds.split(",")):
        harness.T0 = time.monotonic()
        with FAULTS[args.fault]():
            res = bench_run.run_cell(harness.Cell(args.workload), seed,
                                     args.seconds, False, device)
        print(json.dumps({"control": args.fault, "seed": seed,
                          "correct": res["correct"],
                          "attempted": res["attempted"],
                          "failed": res["failed"],
                          "metrics": {k: v["value"] for k, v in
                                      res["metrics"].items()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
