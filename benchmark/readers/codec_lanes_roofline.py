"""Share of the HBM roofline for the registry codec's launches of a
traced slice whose calls mix objects of unequal size: the bytes they
needed (``work_codec.launch_bytes`` at one byte a lane: the k operand
rows read and the asked rows written for every lane, summed by the
launches' output rows: m for an encode, the erasure count for a decode)
over the device kind's HBM peak (peaks.json), divided by the device
time of the programs whose names match ``programs``.  ``codec_roofline``
reckons stripes of one ``stripe_unit``, which a call of nine chunk
lengths has not.  The lanes are the driver's own count of the chunk
bytes a row it handed in (facts ``<lanes_prefix><rows>``), not what the
program says it launched: a slab's spare lanes are time and no work.
``None`` outside a traced run and where no program matches (a program
without the registry's program names).  An unknown device kind is an
error, not a default."""

from __future__ import annotations

import re

from benchmark import work, work_codec
from benchmark.harness import BENCH, HarnessError, load_json


def read(spec: dict, facts: dict) -> float | None:
    programs = facts.get("trace.programs")
    prefix = spec["lanes_prefix"]
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
    k = int(facts["config.profile.k"])
    need = sum(work_codec.launch_bytes(k, rows, 1, lanes)
               for rows, lanes in by_rows.items())
    return work.roofline_share(need, peaks[kind]["hbm_bytes_per_s"], device_s)
