"""Mean time a backfill push spends in each stage, from the spans the
program keeps in its rings (``ceph_tpu.common.tracing``, 2048 a
daemon).  The repair goes on after the window and would push the
window's spans out of the rings, so the driver takes them at the
window's close and hands them over as the fact ``spans.backfill``.

One object's push to a backfill target is a tree: ``pg.backfill_push``
(root, at the primary; tags ``pgid``, ``oid``, ``shard``, ``dirty``)
-> ``ec.recover_gather`` (the gather of k shards that excludes the
one being rebuilt), ``ec.recover_decode`` (the survivors' submission to
the batcher until the rebuilt shard is back; absent where the wanted
shard was found whole on a survivor that moved, counted 0) and
``pg.push`` (``pg_push`` sent until the target's ack).  ``read`` keeps
the pushes whose root ended between ``run.wall_open`` and
``run.wall_close`` and that have exactly one gather and one push still
in the rings.  ``spec["stage"]`` is one of

  gather  the ec.recover_gather span
  decode  the ec.recover_decode span, 0 without one
  push    the pg.push span
  rest    what is left of pg.backfill_push: the PG's lock to mark the
          interlock, the payload's bytes and checksums, the reply's
          bookkeeping

in milliseconds, averaged over the kept pushes: the four add up to
their mean length.  ``None`` without the fact or the window's bounds
and where no push has the spans (a program without them).
"""

from __future__ import annotations

from benchmark import harness

ROOT, GATHER, DECODE, SEND = ("pg.backfill_push", "ec.recover_gather",
                              "ec.recover_decode", "pg.push")

_reported = False


def _length(span: dict | None) -> float:
    return 0.0 if span is None else span["end"] - span["start"]


STAGES = {
    "gather": lambda p: _length(p[GATHER]),
    "decode": lambda p: _length(p.get(DECODE)),
    "push": lambda p: _length(p[SEND]),
    "rest": lambda p: _length(p[ROOT]) - _length(p[GATHER])
    - _length(p.get(DECODE)) - _length(p[SEND]),
}


def whole_pushes(spans: list[dict], lo: float, hi: float
                 ) -> tuple[list, int]:
    """Pushes that ended in [lo, hi] with a whole tree ({span name:
    span}), and how many were left out for a missing part."""
    children: dict[str, dict[str, list]] = {}
    for s in spans:
        if s["name"] != ROOT and s.get("end") is not None:
            children.setdefault(s["parent_id"], {}).setdefault(
                s["name"], []).append(s)
    pushes, partial = [], 0
    for s in spans:
        if s["name"] != ROOT or s.get("end") is None \
                or not lo <= s["end"] <= hi:
            continue
        by = children.get(s["span_id"], {})
        if len(by.get(GATHER, ())) != 1 or len(by.get(SEND, ())) != 1 \
                or len(by.get(DECODE, ())) > 1:
            partial += 1
            continue
        tree = {ROOT: s, GATHER: by[GATHER][0], SEND: by[SEND][0]}
        if DECODE in by:
            tree[DECODE] = by[DECODE][0]
        pushes.append(tree)
    return pushes, partial


def mean_ms(pushes: list, stage) -> float:
    fn = STAGES[stage] if isinstance(stage, str) else stage
    return 1e3 * sum(fn(p) for p in pushes) / len(pushes)


def read(spec: dict, facts: dict) -> float | None:
    spans = facts.get("spans.backfill")
    lo, hi = facts.get("run.wall_open"), facts.get("run.wall_close")
    if not spans or lo is None or hi is None:
        return None
    pushes, partial = whole_pushes(spans, lo, hi)
    if not pushes:
        return None
    global _reported
    if not _reported:
        _reported = True
        total = sum(mean_ms(pushes, stage) for stage in STAGES)
        decoded = sum(DECODE in p for p in pushes)
        dirty = sum(bool(p[ROOT]["tags"].get("dirty")) for p in pushes)
        asked = sum(p[GATHER]["tags"].get("asked", 0) for p in pushes)
        harness.say(
            f"backfill stages: {len(pushes)} pushes with a whole span "
            f"tree ended in the window, mean length {total:.1f} ms; "
            f"{decoded} decoded, {len(pushes) - decoded} copied a shard "
            f"found whole, {dirty} dirty; sub-reads asked {asked}; left "
            f"out: {partial} partly dropped by a ring")
    return mean_ms(pushes, spec["stage"])
