#!/usr/bin/env python3
"""Control for the cell that deep-scrubs: a run that has to come out as
not correct.  One fault beside those of control.py, run the same way:

  trusting  every OSD's scrub map gives a shard's stored ``_crc`` as
            its digest: a scrub that lists, compares sizes, versions
            and labels, and takes the tags' word for the bytes.  It
            still finds the removed shards; the rotted bytes and the
            replaced tags it cannot see.

    python benchmark/control_scrub.py --workload <name> --fault trusting --seeds 1,2,3 --seconds 8

The run must come out not correct by ``missed`` (24 of the 32 planted
faults at 8 a kind: every ``data_rot``, ``parity_rot`` and ``tag_rot``)
and by the unrepaired shards that follow from it
(``repaired_bytes_wrong`` for the rots, ``repaired_crc_wrong`` for the
tags), with no false report and the clients' writes sound.  control.py's
``rebuilt`` of control_backfill.py fits the cell too: the repair's
decode hands back a flipped byte, the push's checksum is stamped over
it, and only the repaired shard held to the reference shows it.  The
benchmark's own runs never come here.
"""

from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmark import control               # noqa: E402


def _trusting_fault():
    from ceph_tpu.osd import scrub

    def wrap(sound):
        async def build_shard_map(*args, **kwargs):
            out = await sound(*args, **kwargs)
            for entry in out.values():
                if entry["crc"] is not None:
                    entry["digest"] = entry["crc"]
            return out
        return build_shard_map
    return control._patched(scrub, "build_shard_map", wrap)


FAULTS = control.FAULTS
FAULTS["trusting"] = _trusting_fault


if __name__ == "__main__":
    sys.exit(control.main())
