#!/usr/bin/env python3
"""Control for the cells that read: a run that has to come out as not
correct.  One fault beside those of control.py, run the same way:

  decode  every decode launch hands back its recovered chunks with one
          byte of each stripe flipped (a read acknowledged while an OSD
          is down != the bytes of the last acknowledged write).

    python benchmark/control_read.py --workload <name> --fault decode --seeds 1,2,3 --seconds 8

The populate's encodes are sound, so what the stores hold is sound:
only what the timed reads return is wrong.  The benchmark's own runs
never come here.
"""

from __future__ import annotations

import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmark import control               # noqa: E402


def _decode_fault():
    from ceph_tpu.parallel.mesh_codec import MeshCodec

    def wrap(sound):
        def decode(self, codec, erasures, batch, out_np=True):
            out = np.array(sound(self, codec, erasures, batch,
                                 out_np=out_np))
            out[:, 0, 0] ^= 1
            return out
        return decode
    return control._patched(MeshCodec, "decode", wrap)


FAULTS = control.FAULTS
FAULTS["decode"] = _decode_fault


if __name__ == "__main__":
    sys.exit(control.main())
