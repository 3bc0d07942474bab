"""Share of the HBM roofline for the parity-update launches of a traced
slice: the bytes the launches needed (work_rmw.py, from the
configuration's k, m and stripe_unit and the stripes launched, padding
included) over the device kind's HBM peak (peaks.json), divided by the
device time of the programs whose names match ``programs``.
``roofline.py`` reckons an encode (k chunks read, m written); an update
also reads the m old parity chunks.  An unknown device kind is an
error, not a default."""

from __future__ import annotations

import re

from benchmark import work, work_rmw
from benchmark.harness import BENCH, HarnessError, load_json


def read(spec: dict, facts: dict) -> float | None:
    programs = facts.get("trace.programs")
    stripes = facts.get(spec["stripes"])
    if not programs or not stripes:
        return None
    device_s = sum(secs for name, secs in programs.items()
                   if re.search(spec["programs"], name))
    if device_s <= 0:
        return None
    peaks = load_json(BENCH / "peaks.json")
    kind = facts["device.kind"]
    if kind not in peaks:
        raise HarnessError(f"no peaks for device kind {kind!r} in peaks.json")
    need = work_rmw.rmw_bytes(int(facts["config.profile.k"]),
                              int(facts["config.profile.m"]),
                              int(facts["config.profile.stripe_unit"]),
                              int(stripes))
    return work.roofline_share(need, peaks[kind]["hbm_bytes_per_s"], device_s)
