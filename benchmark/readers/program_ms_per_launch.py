"""Device milliseconds per launch of the programs whose names match
``spec["programs"]``: their device time in the traced slice
(``trace.programs``) over the launches the program counted in it (the
fact ``spec["launches"]``).  ``device_ms_per_launch.*`` of the other
cells divide the whole busy time by every mesh launch; a slice that
mixes the clients' encodes with the repair's decodes needs the one
kind's time over the one kind's count.  ``None`` outside a traced run,
without a launch of the kind, and where no program matches."""

from __future__ import annotations

import re


def read(spec: dict, facts: dict) -> float | None:
    programs = facts.get("trace.programs")
    launches = facts.get(spec["launches"])
    if not programs or not launches:
        return None
    device_s = sum(secs for name, secs in programs.items()
                   if re.search(spec["programs"], name))
    if device_s <= 0:
        return None
    return 1e3 * device_s / launches
