"""Mean time a read spends in each stage, from the op-stage spans the
program keeps in its rings (``ceph_tpu.common.tracing``, 2048 a
daemon, in a registry that outlives the cluster a driver ran).

A client read is a tree: ``client.osd_op`` (root, at the client) ->
``osd.do_op`` (primary) -> ``ec.gather`` (first sub-read issued until a
verified sufficient set is in hand) and, where a data shard had to be
reconstructed, ``ec.decode`` (submit to the batcher until the
recovered chunks are back).  ``op_stage.py`` asks for an ``ec.encode``
span, which a read does not have.  ``read`` keeps the reads whose root
ended within ``run.window_s`` seconds before the last root ended (the
readers drain right after the window closes) and that have exactly one
``osd.do_op``, one ``ec.gather`` and at most one ``ec.decode`` still
in the rings; a read the client sent again has more than one
``osd.do_op`` and is left out, counted on a printed line, as are those
a ring has dropped part of.  ``spec["stage"]`` is one of

  to_osd  client.osd_op start -> osd.do_op start
  gather  the ec.gather span
  decode  the ec.decode span; a plain read has none and counts 0, so
          that the stages add up (the printed line gives the mean over
          the reads that have it)
  reply   osd.do_op end -> client.osd_op end
  rest    what is left of osd.do_op: the PG's lock, the look whether
          the object exists, assembling the object, sending the reply

in milliseconds, averaged over the kept reads: the five add up to
their mean latency.  ``None`` without ``run.window_s`` and where no
read has the spans (a program without them).
"""

from __future__ import annotations

from benchmark import harness

ROOT, PRIMARY, GATHER, DECODE = ("client.osd_op", "osd.do_op", "ec.gather",
                                 "ec.decode")

_reported = False


def _length(span: dict | None) -> float:
    return 0.0 if span is None else span["end"] - span["start"]


STAGES = {
    "to_osd": lambda o: o[PRIMARY]["start"] - o[ROOT]["start"],
    "gather": lambda o: _length(o[GATHER]),
    "decode": lambda o: _length(o.get(DECODE)),
    "reply": lambda o: o[ROOT]["end"] - o[PRIMARY]["end"],
    "rest": lambda o: _length(o[PRIMARY]) - _length(o[GATHER])
    - _length(o.get(DECODE)),
}


def whole_reads(dumps: list[dict], window_s: float) -> tuple[list, dict]:
    """Reads with a whole tree ({span name: span}), and counts of what
    was left out."""
    trees: dict[str, list] = {}
    for s in dumps:
        if s.get("end") is not None:
            trees.setdefault(s["trace_id"], []).append(s)
    ops, left = [], {"resent": 0, "partial": 0}
    for spans in trees.values():
        by: dict[str, list] = {}
        for s in spans:
            by.setdefault(s["name"], []).append(s)
        if GATHER not in by:
            continue                     # not a read that reached an OSD
        if len(by.get(PRIMARY, ())) > 1:
            left["resent"] += 1
        elif any(len(by.get(n, ())) != 1 for n in (ROOT, PRIMARY, GATHER)) \
                or len(by.get(DECODE, ())) > 1:
            left["partial"] += 1
        else:
            ops.append({n: by[n][0] for n in by
                        if n in (ROOT, PRIMARY, GATHER, DECODE)})
    if ops:
        last = max(o[ROOT]["end"] for o in ops)
        ops = [o for o in ops if o[ROOT]["end"] >= last - window_s]
    return ops, left


def mean_ms(ops: list, stage: str) -> float:
    return 1e3 * sum(STAGES[stage](o) for o in ops) / len(ops)


def read(spec: dict, facts: dict) -> float | None:
    window_s = facts.get("run.window_s")
    if window_s is None:
        return None
    from ceph_tpu.common import tracing
    tracers = list(getattr(tracing, "_TRACERS", {}).values())
    ops, left = whole_reads([s for t in tracers for s in t.dump()],
                            window_s)
    if not ops:
        return None
    global _reported
    if not _reported:
        _reported = True
        decoded = [o for o in ops if DECODE in o]
        total = sum(mean_ms(ops, stage) for stage in STAGES)
        harness.say(
            f"read stages: {len(ops)} reads with a whole span tree in the "
            f"rings, mean latency {total:.1f} ms; {len(decoded)} "
            f"reconstructed"
            + (f", their {DECODE} {mean_ms(decoded, 'decode'):.1f} ms and "
               f"{GATHER} {mean_ms(decoded, 'gather'):.1f} ms"
               if decoded else "")
            + f"; left out: {left['resent']} sent again, {left['partial']} "
            f"partly dropped by a ring")
    return mean_ms(ops, spec["stage"])
