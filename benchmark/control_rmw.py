#!/usr/bin/env python3
"""Control for the cells that overwrite: a run that has to come out as
not correct.  One fault beside those of control.py, run the same way:

  stale_parity  every parity-update launch withholds the update of its
                first stripe: that overwrite's new data chunk goes out,
                its three parity chunks go out as they were (stored
                parity != generator rows x stored data, though every
                shard still matches its own _crc and the image reads
                back right).

    python benchmark/control_rmw.py --workload <name> --fault stale_parity --seeds 1,2,3 --seconds 8

The prefill's encodes are sound, so the images start sound: only what
an overwrite leaves behind is wrong, and only where the comparison
looks at stored parity.  The benchmark's own runs never come here.
"""

from __future__ import annotations

import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmark import control               # noqa: E402


def _stale_parity_fault():
    from ceph_tpu.parallel.mesh_codec import MeshCodec

    def wrap(sound):
        def rmw(self, codec, old_parity, delta, out_np=True):
            stale = np.array(old_parity[0])
            out = np.array(sound(self, codec, old_parity, delta,
                                 out_np=out_np))
            out[0] = stale
            return out
        return rmw
    return control._patched(MeshCodec, "rmw", wrap)


FAULTS = control.FAULTS
FAULTS["stale_parity"] = _stale_parity_fault


if __name__ == "__main__":
    sys.exit(control.main())
