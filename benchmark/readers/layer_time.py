"""Time of the loop's thread by host layer, with the layers named by
the metric: ``span_time`` for any cell.

``span_time`` knows six layers, ``read_span_time`` adds ``osd_read.``
and ``backfill_span_time`` ``recovery.``, each with its list in the
module.  Here the list is ``spec["layers"]``: the section prefixes
among which an instant is given to the innermost open section
(``span_time.innermost``), on the thread and inside the slice that
``span_time.load`` finds.  Metrics of one cell give the same list, so
their times and the uncovered time add up to the slice.

``read`` returns the self time of the sections ``spec["prefix"]``
selects (a name prefix: a whole layer such as ``scrub.``, or one
section such as ``scrub.digest_host``), or with ``"invert": true`` the
time no section of the layers covers, in milliseconds per

  ``spec["per"]``       section of that name started inside the slice
                        (``client.complete``: once per finished op), or
  ``spec["per_fact"]``  that fact times ``spec["per_scale"]`` (bytes a
                        route digested in the slice -> MiB).

``None`` outside a traced run, for a program without sections, and
where the divisor is 0.
"""

from __future__ import annotations

from benchmark.readers import span_time
from benchmark.xplane import DEVICE_PLANE, SLICE_MARK, _events

_cache: dict[tuple, dict | None] = {}
_reported: set[tuple] = set()


def load(path, layers: tuple[str, ...]) -> dict | None:
    """The marked slice over ``layers``: ``span_time``'s bounds and
    idle gaps, the pieces and counts taken again."""
    key = (str(path), layers)
    if key not in _cache:
        _cache[key] = _load(*key)
    return _cache[key]


def _load(path: str, layers: tuple[str, ...]) -> dict | None:
    import jax

    base = span_time.load(path)
    if base is None:
        return None
    lo, hi = base["lo"], base["hi"]
    line = next(
        line for plane in jax.profiler.ProfileData.from_file(path).planes
        if not plane.name.startswith(DEVICE_PLANE)
        for line in plane.lines
        if any(e.name == SLICE_MARK for e in line.events))
    started: dict[str, int] = {}
    for e in line.events:
        if e.name.startswith(layers) and lo <= e.start_ns * 1e-9 < hi:
            started[e.name] = started.get(e.name, 0) + 1
    sections = [ev for ev in _events(line, lo, hi)
                if ev[2].startswith(layers)]
    return dict(base, pieces=span_time.innermost(sections, lo, hi),
                started=started)


def read(spec: dict, facts: dict) -> float | None:
    if "trace.window_s" not in facts:
        return None
    path = span_time.newest_trace()
    layers = tuple(spec["layers"])
    sl = load(path, layers) if path is not None else None
    if sl is None:
        return None
    if "per_fact" in spec:
        per = facts.get(spec["per_fact"], 0) * spec.get("per_scale", 1.0)
    else:
        per = sl["started"].get(spec["per"], 0)
    if not per:
        return None
    if "per" in spec and (str(path), layers) not in _reported:
        _reported.add((str(path), layers))
        span_time.report(sl, spec["per"])
    times = span_time.self_times(sl["pieces"])
    if spec.get("invert"):
        secs = times.get(None, 0.0)
    else:
        secs = sum(s for name, s in times.items()
                   if name is not None and name.startswith(spec["prefix"]))
    return 1e3 * secs / per
