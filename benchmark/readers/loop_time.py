"""What the event loop's thread does in the time no layer's section
covers, from the run's own trace.

``span_time`` gives every instant of the marked slice to the innermost
section of a host layer and calls the rest uncovered.  The program also
brackets its event loop (``ceph_tpu.common.tracing.install_loop_probe``):
``loop.select`` around the selector's ``select`` (the thread asleep or
polling), ``loop.read_ready`` around a transport's read callback (the
layers' ``wire.recv`` nests in it, so its self time is ``get_buffer``,
``recv_into`` and the transport's bookkeeping) and ``loop.write_ready``
around a write callback (the ``sendmsg`` that ``writelines`` deferred).
This reader keeps ``loop.`` beside every layer's prefix and applies the
same rule: an instant belongs to the innermost section open at it.

``read`` returns one part of the uncovered time in milliseconds per
section named ``spec["per"]`` that started in the slice: ``"select"``,
``"recv"`` and ``"send"`` are the self times of the three sections,
``"steps"`` is the slice in no section at all (task steps, future
callbacks, timers, the load generator).  The four add up to what the
cell's ``span_time`` reader gives with ``"invert": true``.  ``None``
outside a traced run and for a trace without ``loop.select`` (a
program that has no probe).

Once per trace it prints, through ``harness.say``, the four parts, the
count of each ``loop.*`` section and the ten longest device idle gaps
of the slice with the sections over them, ``loop.*`` included.
"""

from __future__ import annotations

from benchmark import harness
from benchmark.readers import backfill_span_time, read_span_time, span_time
from benchmark.xplane import DEVICE_PLANE, SLICE_MARK, _events

LOOP = "loop."
# every host layer an accepted reader knows, then the loop's own
LAYERS = tuple(dict.fromkeys(span_time.LAYERS + read_span_time.LAYERS
                             + backfill_span_time.LAYERS)) + (LOOP,)
PARTS = {"select": "loop.select", "recv": "loop.read_ready",
         "send": "loop.write_ready", "steps": None}

_cache: dict[str, dict | None] = {}
_reported: set[str] = set()


def load(path) -> dict | None:
    """The marked slice (``span_time``'s bounds and idle gaps) with its
    pieces and counts taken over ``LAYERS``; ``None`` when the file has
    no marked line or the line no ``loop.select``."""
    key = str(path)
    if key not in _cache:
        _cache[key] = _load(key)
    return _cache[key]


def _load(path: str) -> dict | None:
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
        if e.name.startswith(LAYERS) and lo <= e.start_ns * 1e-9 < hi:
            started[e.name] = started.get(e.name, 0) + 1
    if PARTS["select"] not in started:
        return None
    sections = [ev for ev in _events(line, lo, hi)
                if ev[2].startswith(LAYERS)]
    return dict(base, pieces=span_time.innermost(sections, lo, hi),
                started=started)


def parts(sl: dict) -> dict[str, float]:
    """Seconds of the slice in each of the four parts."""
    times = span_time.self_times(sl["pieces"])
    return {part: times.get(name, 0.0) for part, name in PARTS.items()}


def report(sl: dict, per: str) -> None:
    window = sl["hi"] - sl["lo"]
    ops = sl["started"].get(per, 0)
    split = parts(sl)
    harness.say(
        f"loop: of the slice's {1e3 * window:.1f} ms, "
        f"{1e3 * sum(split.values()):.2f} ms in no layer's section, "
        f"{ops} x {per}: " + ", ".join(
            f"{part} {1e3 * secs:.2f} ms ({1e3 * secs / ops:.3f} ms/op)"
            for part, secs in split.items())
        + "; sections: " + ", ".join(
            f"{name} x{n}" for name, n in sorted(sl["started"].items())
            if name.startswith(LOOP)))
    for start, end, after in sl["gaps"]:
        over = span_time.covering(sl["pieces"], start, end)
        harness.say(f"  device idle {1e3 * (end - start):8.2f} ms after "
                    f"{after}: " + ", ".join(
                        f"{name} {100 * secs / (end - start):.0f}%"
                        for name, secs in over[:4]))


def read(spec: dict, facts: dict) -> float | None:
    if "trace.window_s" not in facts:
        return None
    path = span_time.newest_trace()
    sl = load(path) if path is not None else None
    if sl is None:
        return None
    ops = sl["started"].get(spec["per"], 0)
    if not ops:
        return None
    if str(path) not in _reported:
        _reported.add(str(path))
        report(sl, spec["per"])
    return 1e3 * parts(sl)[spec["part"]] / ops
