"""Mean time a partial-stripe overwrite spends in each stage, from the
op-stage spans the program keeps in its rings (``ceph_tpu.common.
tracing``, 2048 a daemon, in a registry that outlives the cluster a
driver ran).

An overwrite of part of an object on an erasure pool is a tree:
``client.osd_op`` (root, at the client) -> ``osd.do_op`` (primary) ->
``ec.rmw_read`` (the touched stripes' old content: ExtentCache, else a
ranged gather of k shards), ``ec.rmw_parity`` (the fetch of the stripes'
stored parity chunks) and ``ec.encode`` (submit of the delta to the
batcher until the new parity is back: on this path the launch wait
alone).  ``op_stage.py`` cuts a whole-object write, which has only the
last of the three.  ``read`` keeps the writes whose root ended within
``run.window_s`` seconds before the last root ended (the writers drain
right after the window closes) and that have exactly one ``osd.do_op``
and one of each of the three still in the rings; a write the client
sent again has more than one ``osd.do_op`` and is left out, counted on
a printed line, as are those a ring has dropped part of.
``spec["stage"]`` is one of

  to_osd       client.osd_op start -> osd.do_op start
  read_old     the ec.rmw_read span
  read_parity  the ec.rmw_parity span
  launch       the ec.encode span
  commit       what is left of osd.do_op: before the read, the PG's
               lock, the object's earlier commit and the old size;
               after the launch, the sub-writes' fan-out, the local
               apply with its re-stamp, and the wait for every
               shard's commit (the printed line splits the two)
  reply        osd.do_op end -> client.osd_op end

in milliseconds, averaged over the kept writes: the six add up to their
mean latency.  ``None`` without ``run.window_s`` and where no write has
the spans (a program without them).
"""

from __future__ import annotations

from benchmark import harness

ROOT, PRIMARY = "client.osd_op", "osd.do_op"
READ, PARITY, LAUNCH = "ec.rmw_read", "ec.rmw_parity", "ec.encode"
TREE = (ROOT, PRIMARY, READ, PARITY, LAUNCH)

_reported = False


def _length(span: dict) -> float:
    return span["end"] - span["start"]


STAGES = {
    "to_osd": lambda o: o[PRIMARY]["start"] - o[ROOT]["start"],
    "read_old": lambda o: _length(o[READ]),
    "read_parity": lambda o: _length(o[PARITY]),
    "launch": lambda o: _length(o[LAUNCH]),
    "commit": lambda o: _length(o[PRIMARY]) - _length(o[READ])
    - _length(o[PARITY]) - _length(o[LAUNCH]),
    "reply": lambda o: o[ROOT]["end"] - o[PRIMARY]["end"],
}


def whole_writes(dumps: list[dict], window_s: float) -> tuple[list, dict]:
    """Overwrites with a whole tree ({span name: span}), and counts of
    what was left out."""
    trees: dict[str, list] = {}
    for s in dumps:
        if s.get("end") is not None:
            trees.setdefault(s["trace_id"], []).append(s)
    ops, left = [], {"resent": 0, "partial": 0}
    for spans in trees.values():
        by: dict[str, list] = {}
        for s in spans:
            by.setdefault(s["name"], []).append(s)
        if READ not in by:
            continue                     # not a partial-stripe overwrite
        if len(by.get(PRIMARY, ())) > 1:
            left["resent"] += 1
        elif any(len(by.get(n, ())) != 1 for n in TREE):
            left["partial"] += 1
        else:
            ops.append({n: by[n][0] for n in TREE})
    if ops:
        last = max(o[ROOT]["end"] for o in ops)
        ops = [o for o in ops if o[ROOT]["end"] >= last - window_s]
    return ops, left


def mean_ms(ops: list, stage) -> float:
    fn = STAGES[stage] if isinstance(stage, str) else stage
    return 1e3 * sum(fn(o) for o in ops) / len(ops)


def read(spec: dict, facts: dict) -> float | None:
    window_s = facts.get("run.window_s")
    if window_s is None:
        return None
    from ceph_tpu.common import tracing
    tracers = list(getattr(tracing, "_TRACERS", {}).values())
    ops, left = whole_writes([s for t in tracers for s in t.dump()],
                             window_s)
    if not ops:
        return None
    global _reported
    if not _reported:
        _reported = True
        total = sum(mean_ms(ops, stage) for stage in STAGES)
        before = mean_ms(ops, lambda o: o[READ]["start"]
                         - o[PRIMARY]["start"])
        after = mean_ms(ops, lambda o: o[PRIMARY]["end"]
                        - o[LAUNCH]["end"])
        asked = sum(o[READ]["tags"].get("asked", 0) for o in ops)
        cached = sum(o[READ]["tags"].get("cached", 0) for o in ops)
        harness.say(
            f"rmw stages: {len(ops)} overwrites with a whole span tree in "
            f"the rings, mean latency {total:.1f} ms; of commit, "
            f"{before:.1f} ms before {READ} (PG lock, the object's "
            f"earlier commit, old size) and {after:.1f} ms after {LAUNCH} "
            f"(sub-writes, local apply and re-stamp, the shards' commits); "
            f"stripes asked {asked}, of which the ExtentCache served "
            f"{cached}; left out: {left['resent']} sent again, "
            f"{left['partial']} partly dropped by a ring")
    return mean_ms(ops, spec["stage"])
