"""Share of the HBM roofline for the decode launches of a traced slice:
the bytes the reconstructions needed (work_read.py, from the
configuration's k and stripe_unit, the chunks a read lacks and the
stripes the slice decoded) over the device kind's HBM peak
(peaks.json), divided by the device time of the programs whose names
match ``programs``.  ``roofline.py`` reckons an encode's bytes from m;
a decode writes only what was lost.  An unknown device kind is an
error, not a default."""

from __future__ import annotations

import re

from benchmark import work, work_read
from benchmark.harness import BENCH, HarnessError, load_json


def read(spec: dict, facts: dict) -> float | None:
    programs = facts.get("trace.programs")
    stripes = facts.get(spec["stripes"])
    rows = facts.get(spec["rows"])
    if not programs or not stripes or not rows:
        return None
    device_s = sum(secs for name, secs in programs.items()
                   if re.search(spec["programs"], name))
    if device_s <= 0:
        return None
    peaks = load_json(BENCH / "peaks.json")
    kind = facts["device.kind"]
    if kind not in peaks:
        raise HarnessError(f"no peaks for device kind {kind!r} in peaks.json")
    need = work_read.decode_bytes(int(facts["config.profile.k"]), int(rows),
                                  int(facts["config.profile.stripe_unit"]),
                                  int(stripes))
    return work.roofline_share(need, peaks[kind]["hbm_bytes_per_s"], device_s)
