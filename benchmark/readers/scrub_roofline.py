"""Share of the HBM roofline for the digest launches of a traced slice:
the bytes the scrubs' device digests needed (work_scrub.py: every
digested shard read once, 4 bytes a shard written; the shard's length
from the configuration's k and stripe_unit and the population's object
size, the rows the slice digested from the fact ``spec["rows"]``) over
the device kind's HBM peak (peaks.json), divided by the device time of
the programs whose names match ``programs``.  ``None`` outside a traced
run, without a digest launch in the slice and where no program matches.
An unknown device kind is an error, not a default."""

from __future__ import annotations

import re

from benchmark import work, work_scrub
from benchmark.harness import BENCH, HarnessError, load_json


def read(spec: dict, facts: dict) -> float | None:
    programs = facts.get("trace.programs")
    rows = facts.get(spec["rows"])
    if not programs or not rows:
        return None
    device_s = sum(secs for name, secs in programs.items()
                   if re.search(spec["programs"], name))
    if device_s <= 0:
        return None
    peaks = load_json(BENCH / "peaks.json")
    kind = facts["device.kind"]
    if kind not in peaks:
        raise HarnessError(f"no peaks for device kind {kind!r} in peaks.json")
    need = work_scrub.digest_bytes(int(rows), work_scrub.shard_bytes(
        int(facts["config.profile.k"]),
        int(facts["config.profile.stripe_unit"]),
        int(facts["config.population.object_bytes"])))
    return work.roofline_share(need, peaks[kind]["hbm_bytes_per_s"], device_s)
