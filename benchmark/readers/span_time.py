"""Time of the loop's thread by host layer, from the run's own trace.

The program brackets synchronous work on its event loop's thread with
``ceph_tpu.common.tracing.section("<layer>.<what>")``; inside a
profiler session each section is a host event on the profiler's clock,
the clock the device's own events are on.  This reader opens the newest
``*.xplane.pb`` under the harness's trace directory (one process makes
one trace), takes the host line that holds the ``benchmark_slice`` mark
and clips to it.  Sections nest; an instant belongs to the innermost
section open at it, so a layer's time is its sections' self time.

``read`` returns the self time of the sections whose names start with
``spec["prefix"]``, in milliseconds per section named ``spec["per"]``
that started inside the slice (``client.complete``: once per finished
op; ``device_wait.crush``: once per launch of the bulk mapper).  A
slice has to hold enough of them: 1.5 s of 4 MiB writes hold ten to
twelve completions, 0.4 s of 64 KiB writes hold a handful, in bursts,
so no metric of this reader lists that cell.  ``"invert": true`` gives
the time no section covers: other daemons' timers, the loop's own
bookkeeping, the thread asleep in ``select``.  The layers' times and
the uncovered time add up to the slice.  Only a traced run has
``trace.window_s`` among its facts; any other run, and a program
without sections, gives ``None``.

Once per process it also prints, through ``harness.say``, the self time
of every section name and the ten longest device idle gaps of the slice,
each with the sections the thread was in meanwhile.
"""

from __future__ import annotations

from pathlib import Path

from benchmark import harness
from benchmark.xplane import (DEVICE_PLANE, MODULES_LINE, OPS_LINE,
                              SLICE_MARK, TOP, _events, _program, _union)

# the program's ceph_tpu.common.tracing.SECTION_LAYERS, as prefixes
LAYERS = ("client.", "wire.", "osd_op.", "store.", "batcher.",
          "device_wait.")
UNCOVERED = "(no section)"

_cache: dict[str, dict | None] = {}
_reported: set[str] = set()


def newest_trace(root: Path | None = None) -> Path | None:
    root = root or harness.SCRATCH / "trace"
    found = sorted(root.rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    return found[-1] if found else None


def innermost(events, lo: float, hi: float) -> list[tuple]:
    """Nested (start, end, name) events, sorted by start and longer
    first, as consecutive (start, end, name) pieces that cover [lo, hi]:
    each piece carries the innermost event open over it, ``None`` where
    none is."""
    pieces: list[tuple] = []
    stack: list[tuple[float, str]] = []          # (end, name)
    cur = lo

    def emit(upto: float) -> None:
        nonlocal cur
        upto = min(upto, hi)
        if upto > cur:
            pieces.append((cur, upto, stack[-1][1] if stack else None))
            cur = upto

    for start, end, name in events:
        while stack and stack[-1][0] <= start:
            emit(stack[-1][0])
            stack.pop()
        emit(start)
        stack.append((end, name))
    while stack:
        emit(stack[-1][0])
        stack.pop()
    emit(hi)
    return pieces


def load(path: str | Path) -> dict | None:
    """The marked slice of one trace file: its pieces by innermost
    section, the sections counted by name, and the device's idle gaps.
    ``None`` when the file has no marked host line."""
    key = str(path)
    if key not in _cache:
        _cache[key] = _load(key)
    return _cache[key]


def _load(path: str) -> dict | None:
    import jax

    try:
        planes = list(jax.profiler.ProfileData.from_file(path).planes)
    except Exception:        # the reader raises its own C++ error types
        return None
    marked = None
    for plane in planes:
        if plane.name.startswith(DEVICE_PLANE):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == SLICE_MARK:
                    marked = (line, e.start_ns * 1e-9,
                              (e.start_ns + e.duration_ns) * 1e-9)
    if marked is None:
        return None
    line, lo, hi = marked
    started: dict[str, int] = {}
    for e in line.events:
        if e.name.startswith(LAYERS) and lo <= e.start_ns * 1e-9 < hi:
            started[e.name] = started.get(e.name, 0) + 1
    sections = [ev for ev in _events(line, lo, hi)
                if ev[2].startswith(LAYERS)]
    pieces = innermost(sections, lo, hi)

    gaps: list[tuple[float, float, str]] = []    # (start, end, after)
    for plane in planes:
        if not plane.name.startswith(DEVICE_PLANE):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        busy = lines.get(MODULES_LINE) or lines.get(OPS_LINE)
        if busy is None:
            continue
        edge, last = lo, "window start"
        for start, end, name in _union(_events(busy, lo, hi)):
            if start > edge:
                gaps.append((edge, start, last))
            edge, last = max(edge, end), _program(name)
        if hi > edge:
            gaps.append((edge, hi, last))
    gaps.sort(key=lambda g: g[0] - g[1])
    return {"lo": lo, "hi": hi, "pieces": pieces, "started": started,
            "gaps": gaps[:TOP]}


def self_times(pieces) -> dict[str | None, float]:
    out: dict[str | None, float] = {}
    for start, end, name in pieces:
        out[name] = out.get(name, 0.0) + (end - start)
    return out


def covering(pieces, lo: float, hi: float) -> list[tuple[str, float]]:
    """Section names over [lo, hi] with the seconds each covers, most
    first; the uncovered part under ``UNCOVERED``."""
    over: dict[str, float] = {}
    for start, end, name in pieces:
        secs = min(end, hi) - max(start, lo)
        if secs > 0:
            name = name or UNCOVERED
            over[name] = over.get(name, 0.0) + secs
    return sorted(over.items(), key=lambda kv: -kv[1])


def report(sl: dict, per: str) -> None:
    window = sl["hi"] - sl["lo"]
    ops = sl["started"].get(per, 0)
    harness.say(f"sections: slice {1e3 * window:.1f} ms on the marked "
                f"thread, {ops} x {per}; self time by section:")
    for name, secs in sorted(self_times(sl["pieces"]).items(),
                             key=lambda kv: -kv[1]):
        label = name or UNCOVERED
        harness.say(f"  {label:28s} {1e3 * secs:9.2f} ms "
                    f"{100 * secs / window:5.1f}%  x"
                    f"{sl['started'].get(name, 0):<6d}"
                    + (f" {1e3 * secs / ops:8.3f} ms/op" if ops else ""))
    for start, end, after in sl["gaps"]:
        over = covering(sl["pieces"], start, end)
        harness.say(f"  device idle {1e3 * (end - start):8.2f} ms after "
                    f"{after}: " + ", ".join(
                        f"{name} {100 * secs / (end - start):.0f}%"
                        for name, secs in over[:3]))


def read(spec: dict, facts: dict) -> float | None:
    if "trace.window_s" not in facts:
        return None
    path = newest_trace()
    sl = load(path) if path is not None else None
    if sl is None:
        return None
    ops = sl["started"].get(spec["per"], 0)
    if not ops:
        return None
    if str(path) not in _reported:
        _reported.add(str(path))
        report(sl, spec["per"])
    times = self_times(sl["pieces"])
    if spec.get("invert"):
        secs = times.get(None, 0.0)
    else:
        secs = sum(s for name, s in times.items()
                   if name is not None and name.startswith(spec["prefix"]))
    return 1e3 * secs / ops
