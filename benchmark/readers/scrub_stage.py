"""Mean time a scrub chunk spends in each stage, from the spans the
program keeps in its rings (``ceph_tpu.common.tracing``, 2048 a
daemon).  The scrubs go on after the window and would push the
window's spans out of the rings, so the driver takes them at the
window's close and hands them over as the fact ``spans.scrub``.

One chunk of a PG's deep scrub is a tree at the primary:
``scrub.chunk`` (tags ``objects``, ``bytes``, ``blocked_writes``) ->
``scrub.maps`` (the map requests sent until every acting member's map
is in; ``scrub.digest``, the primary's own map built meanwhile, nests
in it), ``scrub.compare`` and, where something was found,
``scrub.repair``.  ``read`` keeps the chunks that ended between
``run.wall_open`` and ``run.wall_close`` and have exactly one maps,
one digest and one compare span still in the rings.  ``spec["stage"]``
is one of

  digest   the scrub.digest span: the primary digesting its own shards
  maps     scrub.maps outside scrub.digest: waiting for the maps that
           are still out once the primary's own is built
  compare  the scrub.compare span
  rest     what is left of scrub.chunk: the PG's lock and the listing,
           the wait for the writes in flight inside the range, a
           repair, bookkeeping

in milliseconds, averaged over the kept chunks: the four add up to
their mean length.  ``None`` without the fact or the window's bounds
and where no chunk has the spans (a program without them).
"""

from __future__ import annotations

from benchmark import harness

CHUNK, MAPS, DIGEST, COMPARE, REPAIR = (
    "scrub.chunk", "scrub.maps", "scrub.digest", "scrub.compare",
    "scrub.repair")

_reported = False


def _length(span: dict | None) -> float:
    return 0.0 if span is None else span["end"] - span["start"]


STAGES = {
    "digest": lambda c: _length(c[DIGEST]),
    "maps": lambda c: _length(c[MAPS]) - _length(c[DIGEST]),
    "compare": lambda c: _length(c[COMPARE]),
    "rest": lambda c: _length(c[CHUNK]) - _length(c[MAPS])
    - _length(c[COMPARE]),
}


def whole_chunks(spans: list[dict], lo: float, hi: float
                 ) -> tuple[list, int]:
    """Chunks that ended in [lo, hi] with a whole tree ({span name:
    span}), and how many were left out for a missing part."""
    kids: dict[str, dict[str, list]] = {}
    for s in spans:
        if s["name"] != CHUNK and s.get("end") is not None:
            kids.setdefault(s["parent_id"], {}).setdefault(
                s["name"], []).append(s)
    chunks, partial = [], 0
    for s in spans:
        if s["name"] != CHUNK or s.get("end") is None \
                or not lo <= s["end"] <= hi:
            continue
        under = kids.get(s["span_id"], {})
        maps = under.get(MAPS, [])
        digest = kids.get(maps[0]["span_id"], {}).get(DIGEST, []) \
            if len(maps) == 1 else []
        if len(maps) != 1 or len(digest) != 1 \
                or len(under.get(COMPARE, [])) != 1:
            partial += 1
            continue
        tree = {CHUNK: s, MAPS: maps[0], DIGEST: digest[0],
                COMPARE: under[COMPARE][0]}
        if under.get(REPAIR):
            tree[REPAIR] = under[REPAIR][0]
        chunks.append(tree)
    return chunks, partial


def report(chunks: list, partial: int) -> None:
    n = len(chunks)
    tags = [c[CHUNK]["tags"] for c in chunks]
    harness.say(
        f"scrub chunks: {n} with a whole span tree ended in the window "
        f"({partial} left out for a part the rings dropped), mean "
        f"{1e3 * sum(_length(c[CHUNK]) for c in chunks) / n:.1f} ms; "
        + ", ".join(f"{stage} {1e3 * sum(fn(c) for c in chunks) / n:.1f}"
                    for stage, fn in STAGES.items())
        + f" ms; {sum(t.get('objects', 0) for t in tags)} objects, "
        f"{sum(t.get('bytes', 0) for t in tags)} bytes digested, "
        f"{sum(t.get('blocked_writes', 0) for t in tags)} writes waited "
        f"for a chunk, {sum(REPAIR in c for c in chunks)} chunks repaired")


def read(spec: dict, facts: dict) -> float | None:
    global _reported
    spans = facts.get("spans.scrub")
    lo, hi = facts.get("run.wall_open"), facts.get("run.wall_close")
    if not spans or lo is None or hi is None:
        return None
    chunks, partial = whole_chunks(spans, lo, hi)
    if not chunks:
        return None
    if not _reported:
        _reported = True
        report(chunks, partial)
    return 1e3 * sum(STAGES[spec["stage"]](c) for c in chunks) / len(chunks)
