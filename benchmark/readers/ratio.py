"""``scale`` x product of the ``num`` facts / product of the ``den``
facts.  A fact that is missing, or a denominator of zero, means there
was nothing to read: the metric is left out of the line."""

from __future__ import annotations

import math


def read(spec: dict, facts: dict) -> float | None:
    names = spec["num"] + spec["den"]
    if any(name not in facts for name in names):
        return None
    den = math.prod(facts[name] for name in spec["den"])
    if den == 0:
        return None
    num = math.prod(facts[name] for name in spec["num"])
    return spec.get("scale", 1.0) * num / den
