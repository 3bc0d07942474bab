"""Share of the device's busy time spent under one ``jax.named_scope``
of the program, from the run's own trace.

The program names parts of its device programs (``gf_encode`` and
``crc32c`` in an encode launch, ``straw2_draw`` in the CRUSH mapper).
The scope path
(``jit(ec_encode_crc)/crc32c/jit(crc32c_chunks)/while/body/...``) is
metadata of each operation.  On a TPU plane the profiler keeps it in the
``tf_op`` stat of the operation's *event metadata*, which
``jax.profiler.ProfileData`` does not hand out (an ``XLA Ops`` event
shows only ``device_offset_ps``, ``device_duration_ps`` and ``Time
Scale Multiplier``), so the few fields needed are read from the file
with a protobuf wire reader: per device plane, operation name ->
``tf_op``.  Times come from ``ProfileData`` as everywhere else, and an
event finds its path by its name, the whole HLO instruction.

An instant counts for the innermost operation running at it (a
``while`` holds its body's operations), so nothing is counted twice;
the slice is the one ``span_time`` clips to, and ``spec["scope"]``
matches as a whole word of the path.  Returns percent of
``trace.busy_s``; ``None`` outside a traced run, where the file cannot
be read this way, and where no operation carries the scope (a program
without it, or an executable out of the compile cache that was built
without it).
"""

from __future__ import annotations

import re

from benchmark.readers import span_time
from benchmark.xplane import DEVICE_PLANE, OPS_LINE

SCOPE_STAT = "tf_op"

# field numbers of tsl/profiler/protobuf/xplane.proto
SPACE_PLANES = 1
PLANE_NAME, PLANE_EVENT_METADATA, PLANE_STAT_METADATA = 2, 4, 5
MAP_VALUE = 2
META_ID, META_NAME, EVENT_META_STATS = 1, 2, 5
STAT_METADATA_ID, STAT_STR, STAT_REF = 1, 5, 7

_cache: dict[str, list] = {}


def wire_fields(buf):
    """(field number, value) of one protobuf message: an int for a
    varint, a memoryview for bytes and fixed-width values."""
    i, n = 0, len(buf)

    def varint() -> int:
        nonlocal i
        out = shift = 0
        while True:
            b = buf[i]
            i += 1
            out |= (b & 0x7F) << shift
            if b < 0x80:
                return out
            shift += 7

    while i < n:
        key = varint()
        kind = key & 7
        if kind == 0:
            val = varint()
        else:
            size = {1: 8, 5: 4}.get(kind)
            if size is None:
                if kind != 2:
                    raise ValueError(f"wire type {kind}")
                size = varint()
            val = buf[i:i + size]
            i += size
        yield key >> 3, val


def _sub(buf, number: int) -> list:
    return [val for num, val in wire_fields(buf) if num == number]


def _text(buf) -> str:
    return bytes(buf).decode("utf-8", "replace")


def op_scopes(path: str) -> dict[str, dict[str, str]]:
    """{device plane name: {operation name: its scope paths}} out of
    the planes' event metadata."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: dict[str, dict[str, str]] = {}
    for plane in _sub(space, SPACE_PLANES):
        name = _text(_sub(plane, PLANE_NAME)[0])
        if not name.startswith(DEVICE_PLANE):
            continue
        stat_names = {}
        for entry in _sub(plane, PLANE_STAT_METADATA):
            meta = dict(wire_fields(_sub(entry, MAP_VALUE)[0]))
            stat_names[meta.get(META_ID)] = _text(meta.get(META_NAME, b""))
        scopes: dict[str, str] = {}
        for entry in _sub(plane, PLANE_EVENT_METADATA):
            meta = _sub(entry, MAP_VALUE)[0]
            for stat in _sub(meta, EVENT_META_STATS):
                fields = dict(wire_fields(stat))
                if stat_names.get(fields.get(STAT_METADATA_ID)) \
                        != SCOPE_STAT:
                    continue
                if STAT_STR in fields:
                    scope = _text(fields[STAT_STR])
                else:
                    scope = stat_names.get(fields.get(STAT_REF), "")
                op = _text(_sub(meta, META_NAME)[0])
                scopes[op] = f"{scopes[op]}\n{scope}" if op in scopes \
                    else scope
        out[name] = scopes
    return out


def op_events(path: str, lo: float, hi: float) -> list[list[tuple]]:
    """Per device plane: (start, end, scope paths) of every operation
    clipped to [lo, hi], sorted by start and longer first."""
    if path in _cache:
        return _cache[path]
    import jax

    scopes = op_scopes(path)
    planes = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith(DEVICE_PLANE):
            continue
        line = {ln.name: ln for ln in plane.lines}.get(OPS_LINE)
        if line is None:
            continue
        paths = scopes.get(plane.name, {})
        out = []
        for e in line.events:
            start = e.start_ns * 1e-9
            end = start + e.duration_ns * 1e-9
            start, end = max(start, lo), min(end, hi)
            if end > start:
                out.append((start, end, paths.get(e.name, "")))
        out.sort(key=lambda t: (t[0], -t[1]))
        planes.append(out)
    _cache[path] = planes
    return planes


def matched_time(events, matches, lo: float, hi: float) -> float:
    """Seconds of [lo, hi] whose innermost operation ``matches(paths)``
    accepts: every busy instant belongs to one operation, the one that
    started last, so the shares of disjoint scopes add up to at most
    the busy time even where asynchronous operations overlap."""
    flagged = [(start, end, bool(matches(paths)))
               for start, end, paths in events]
    return sum(end - start
               for start, end, hit in span_time.innermost(flagged, lo, hi)
               if hit)


def read(spec: dict, facts: dict) -> float | None:
    busy = facts.get("trace.busy_s")
    if not busy or "trace.window_s" not in facts:
        return None
    path = span_time.newest_trace()
    sl = span_time.load(path) if path is not None else None
    if sl is None:
        return None
    try:
        planes = op_events(str(path), sl["lo"], sl["hi"])
    except (OSError, ValueError, IndexError, KeyError):
        return None          # not a file this reader understands
    if not planes:
        return None
    word = re.compile(r"(?<!\w)" + re.escape(spec["scope"]) + r"(?!\w)")
    secs = sum(matched_time(ev, word.search, sl["lo"], sl["hi"])
               for ev in planes)
    if secs <= 0:
        return None
    return 100.0 * secs / len(planes) / busy
