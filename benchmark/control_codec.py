#!/usr/bin/env python3
"""Control for the registry codec cell: a run that has to come out as
not correct.  Two faults beside those of control.py, run the same way:

  coefficient     the tpu plugin's generator has one coefficient of its
                  first parity row changed (parity != the generator's
                  GF(2^8) product, != the host isa plugin's bytes);
  survivor_order  every batch decode is handed its first two survivors
                  in each other's place (a decode's chunks != the
                  erased chunks' original bytes).

    python benchmark/control_codec.py --workload rs_k8m3_codec_1m_b1024 --fault coefficient --seeds 1,2 --seconds 8

The host isa plugin, the reference and the seeded payloads are sound:
only what the timed ops return is wrong.  The benchmark's own runs
never come here.
"""

from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmark import control               # noqa: E402


def _coefficient_fault():
    from ceph_tpu.ec.plugins.tpu import ErasureCodeTpu

    def wrap(sound):
        def prepare(self):
            sound(self)
            self.encode_matrix[self.k, 0] ^= 1
        return prepare
    return control._patched(ErasureCodeTpu, "prepare", wrap)


def _survivor_order_fault():
    from ceph_tpu.ec.plugins.tpu import ErasureCodeTpu
    from ceph_tpu.gf.matrices import decode_index_for

    def wrap(sound):
        def decode_stripes(self, erasures, stripes, out_np=False):
            index = decode_index_for(self.k, set(erasures))
            index[:2] = index[1::-1]
            return self.decode_batch(erasures, stripes[:, index],
                                     out_np=out_np)
        return decode_stripes
    return control._patched(ErasureCodeTpu, "decode_stripes", wrap)


FAULTS = control.FAULTS
FAULTS["coefficient"] = _coefficient_fault
FAULTS["survivor_order"] = _survivor_order_fault


if __name__ == "__main__":
    sys.exit(control.main())
