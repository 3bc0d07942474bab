"""Mean time an operation spends between two edges of its op-stage
spans, from the rings the program keeps.

``ceph_tpu.common.tracing`` keeps each daemon's finished spans in a
ring (2048 a daemon) in a registry that lives as long as the process,
so it outlives the cluster a driver ran.  A client write is a tree:
``client.osd_op`` (root, at the client) -> ``osd.do_op`` (primary) ->
``ec.encode`` (submit to the batcher until parity and checksums are
back).  ``read`` keeps
the operations whose root ended within ``run.window_s`` seconds before
the last root ended (the writers drain right after the window closes),
that have exactly one ``osd.do_op`` and one ``ec.encode`` still in the
rings, and returns the mean of ``spec["to"]`` minus ``spec["from"]`` in
milliseconds, each ``"<span name>.<start|end>"``.  An operation the
client sent again has more than one ``osd.do_op``; those are counted on
a printed line and left out, as are the ones a ring has already
dropped part of.  ``None`` without ``run.window_s``, and where no
operation has both spans (a program without them).
"""

from __future__ import annotations

from benchmark import harness

ROOT, PRIMARY, ENCODE = "client.osd_op", "osd.do_op", "ec.encode"

_reported = False


def edge(spans: dict, where: str) -> float:
    name, _, side = where.rpartition(".")
    return spans[name][side]


def whole_ops(dumps: list[dict], window_s: float) -> tuple[list, dict]:
    """Operations with a whole tree ({span name: span}), and counts of
    what was left out."""
    trees: dict[str, list] = {}
    for s in dumps:
        if s.get("end") is not None:
            trees.setdefault(s["trace_id"], []).append(s)
    ops, left = [], {"resent": 0, "partial": 0}
    for spans in trees.values():
        by = {}
        for s in spans:
            by.setdefault(s["name"], []).append(s)
        if ENCODE not in by and PRIMARY not in by:
            continue                     # not a write that reached an OSD
        if len(by.get(PRIMARY, ())) > 1:
            left["resent"] += 1
        elif any(len(by.get(n, ())) != 1 for n in (ROOT, PRIMARY, ENCODE)):
            left["partial"] += ENCODE in by
        else:
            ops.append({n: by[n][0] for n in (ROOT, PRIMARY, ENCODE)})
    if ops:
        last = max(o[ROOT]["end"] for o in ops)
        ops = [o for o in ops if o[ROOT]["end"] >= last - window_s]
    return ops, left


def read(spec: dict, facts: dict) -> float | None:
    window_s = facts.get("run.window_s")
    if window_s is None:
        return None
    from ceph_tpu.common import tracing
    tracers = list(getattr(tracing, "_TRACERS", {}).values())
    ops, left = whole_ops([s for t in tracers for s in t.dump()], window_s)
    if not ops:
        return None
    global _reported
    if not _reported:
        _reported = True
        mean = sum(o[ROOT]["end"] - o[ROOT]["start"] for o in ops) / len(ops)
        before = sum(o[ENCODE]["start"] - o[PRIMARY]["start"]
                     for o in ops) / len(ops)
        harness.say(f"op stages: {len(ops)} writes with a whole span tree "
                    f"in the rings, mean latency {1e3 * mean:.1f} ms, of "
                    f"which {1e3 * before:.1f} ms between {PRIMARY} start "
                    f"and {ENCODE} start; left out: {left['resent']} sent "
                    f"again, {left['partial']} partly dropped by a ring")
    return 1e3 * sum(edge(o, spec["to"]) - edge(o, spec["from"])
                     for o in ops) / len(ops)
