#!/usr/bin/env python3
"""Controls for the registry codec cell over objects of unequal size:
runs that have to come out as not correct.  Two faults of its own
beside ``control_codec.py``'s two, run the same way:

  tail_pad        where a slab is filled, the tail of every object's
                  last data chunk holds the next object's bytes and not
                  zeros (parity != the generator's product of the
                  tool's chunks, != the host isa plugin's bytes; a
                  decode that erased that chunk reads bytes past the
                  object's end back);
  object_offset   in every slab one object's lanes lie 32 to the right
                  of where its result is read (one object's parity in
                  every slab != the generator's product);
  coefficient     ``control_codec.py``'s, as it is;
  survivor_order  ``control_codec.py``'s, and here also for the call
                  over objects: every decode is handed its first two
                  survivors in each other's place.

    python benchmark/control_codec_mixed.py --workload cauchy_k10m4_codec_mixed_4k_1m --fault tail_pad --seeds 1,2 --seconds 8

The host isa plugin, the reference and the seeded payloads are sound:
only what the timed ops return is wrong.  The benchmark's own runs
never come here.
"""

from __future__ import annotations

import contextlib
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmark import control, control_codec        # noqa: E402


def _tail_pad_fault():
    from ceph_tpu.ops.gf2kernels import LanePieces

    def wrap(sound):
        def fill(self, lo, hi, into):
            sound(self, lo, hi, into)
            for i, a, b, off in self.within(lo, hi):
                if self.tails[i] is None:
                    continue
                row, col = self.tails[i]
                held = min(max(col - a, 0), b - a)
                after = self.blocks[(i + 1) % len(self.blocks)][0][1]
                into[0, row, off + held:off + b - a] = np.resize(
                    after.reshape(-1) | 1, b - a - held)
            return into
        return fill
    return control._patched(LanePieces, "fill", wrap)


def _object_offset_fault():
    from ceph_tpu.ops.gf2kernels import LanePieces

    def wrap(sound):
        def fill(self, lo, hi, into):
            sound(self, lo, hi, into)
            for i, a, b, off in self.within(lo, hi):
                if a == 0 and b == self.lengths[i] and b >= 64:
                    into[0, :, off + 32:off + b] = \
                        into[0, :, off:off + b - 32].copy()
                    break
            return into
        return fill
    return control._patched(LanePieces, "fill", wrap)


def _survivor_order_fault():
    import ceph_tpu.ec.plugins.tpu as plugin

    def wrap(sound):
        def decode_index_for(k, erasures):
            index = sound(k, erasures)
            index[:2] = index[1::-1]
            return index
        return decode_index_for

    @contextlib.contextmanager
    def both():
        with control_codec._survivor_order_fault(), \
                control._patched(plugin, "decode_index_for", wrap):
            yield
    return both()


FAULTS = control.FAULTS
FAULTS["tail_pad"] = _tail_pad_fault
FAULTS["object_offset"] = _object_offset_fault
FAULTS["survivor_order"] = _survivor_order_fault


if __name__ == "__main__":
    sys.exit(control.main())
