"""Share of the HBM roofline for the registry codec's launches of a
traced slice: the bytes they needed (work_codec.py: k chunks read and
the asked chunks written per stripe, summed by the launches' output
rows: m for an encode, the erasure count for a decode) over the device
kind's HBM peak (peaks.json), divided by the device time of the
programs whose names match ``programs``.  ``roofline.py`` reckons every
stripe at k+m, which a slice of encodes and decodes of 1 to m erasures
is not.  The stripes are the driver's own count of what it handed in
(facts ``<stripes_prefix><rows>``), not what the program says it ran.
``None`` outside a traced run and where no program matches (a program
without the registry's program names).  An unknown device kind is an
error, not a default."""

from __future__ import annotations

import re

from benchmark import work, work_codec
from benchmark.harness import BENCH, HarnessError, load_json


def read(spec: dict, facts: dict) -> float | None:
    programs = facts.get("trace.programs")
    prefix = spec["stripes_prefix"]
    by_rows = {int(key[len(prefix):]): int(val)
               for key, val in facts.items() if key.startswith(prefix)}
    if not programs or not any(by_rows.values()):
        return None
    device_s = sum(secs for name, secs in programs.items()
                   if re.search(spec["programs"], name))
    if device_s <= 0:
        return None
    peaks = load_json(BENCH / "peaks.json")
    kind = facts["device.kind"]
    if kind not in peaks:
        raise HarnessError(f"no peaks for device kind {kind!r} in peaks.json")
    need = work_codec.slice_bytes(int(facts["config.profile.k"]),
                                  int(facts["config.profile.stripe_unit"]),
                                  by_rows)
    return work.roofline_share(need, peaks[kind]["hbm_bytes_per_s"], device_s)
