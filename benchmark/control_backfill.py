#!/usr/bin/env python3
"""Control for the cells that backfill: a run that has to come out as
not correct.  One fault beside those of control.py, run the same way:

  rebuilt  every decode launch hands back its first stripe with the
           first byte of each recovered chunk flipped.  The sender
           stamps the checksum over the bytes it was handed, so the
           target accepts the push and every _crc matches its shard;
           the clients' writes are sound.  Only a rebuilt shard held to
           the generator's product from the seeded payload shows it.

    python benchmark/control_backfill.py --workload <name> --fault rebuilt --seeds 1,2,3 --seconds 8

The run must come out not correct by ``shard_bytes_wrong`` on every
object of the rebuilt half of the sample, with every checksum, label
and shard in place.  The same fault shows twice more: where the
rebuilt shard is one of the k data shards (8 positions in 11) the
object also reads back wrong through the client, which reads those and
trusts their checksums (``readback_differs``); and a fresh object
whose write was skipped past a backfill target's cursor got that shard
by a dirty push, rebuilt and as wrong.  The benchmark's own runs never
come
here.
"""

from __future__ import annotations

import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmark import control               # noqa: E402


def _rebuilt_fault():
    from ceph_tpu.parallel.mesh_codec import MeshCodec

    def wrap(sound):
        def decode(self, codec, erasures, batch, out_np=True):
            out = np.array(sound(self, codec, erasures, batch,
                                 out_np=out_np))
            out[0, :, 0] ^= 1
            return out
        return decode
    return control._patched(MeshCodec, "decode", wrap)


FAULTS = control.FAULTS
FAULTS["rebuilt"] = _rebuilt_fault


if __name__ == "__main__":
    sys.exit(control.main())
