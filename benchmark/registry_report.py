"""What one traced slice of a registry cell says beside its metrics:
printed once a trace through ``harness.say``, read by no metric.

``readers/registry_idle.py`` pairs the slice's ``registry.launch``
sections with the device's ``jit_registry_gf*`` modules and hands its
result here (``sl``: ``split`` = {``operand``, ``inside``,
``undispatched`` by section}, ``slabs`` = dispatched / start / end /
off_idle a slab, ``uploads``, ``links`` (``registry.drain.link``),
``closes`` (``registry.copy_out``), ``gather_self``, ``gather_wait``).
The lines:

- the device's idle time an op by cause (the two metrics, with the
  not-dispatched part by the caller's section);
- per slab, dispatch to module start and ``registry.upload``'s start to
  module start, over the slabs whose module started off an idle device
  behind its dispatch (only there is the start not the device's own
  queue), with the rate a slab's input bytes would make of the median
  were all of it the upload: dispatch latency is in it, so the rate is
  a floor;
- the share of the ``registry.drain.link`` wait during which some slab
  was between its upload's start and its module's start (are both
  directions of the link asked for at once);
- per slab landed under ``registry.drain`` (all but each call's last,
  which lands under ``registry.copy_out``), module end to the end of its
  ``.link`` section where that waited ``LINK_WAITED_S`` or more, with
  the rate of its output bytes;
- from the host lines that do NOT hold the mark, the worker's
  ``registry.gather`` self time and ``registry.gather.wait`` time an op.

A program without the ``.link`` or ``.gather.wait`` sections prints
what is there.
"""

from __future__ import annotations

import bisect
import statistics

from benchmark import harness
from benchmark.xplane import _union

LAYER = "registry."
LINK_WAITED_S = 50e-6       # a .link section this long waited for the link
GIB = float(1 << 30)


def slab_bytes(facts: dict) -> tuple[float, float] | None:
    """Bytes a slab moves up and down the link, from the window's
    ``ec_registry`` counters (a call over pieces launches, and moves,
    its last slab's spare lanes too)."""
    w = "window.ec_registry."
    slabs = facts.get(w + "slabs", 0)
    if not slabs or w + "bytes_in" not in facts:
        return None
    lanes = facts.get(w + "lanes", 0)
    scale = facts.get(w + "lanes_launched", 0) / lanes if lanes else 1.0
    return (scale * facts[w + "bytes_in"] / slabs,
            scale * facts.get(w + "bytes_out", 0) / slabs)


def overlap_share(links: list[tuple], windows: list[tuple]) -> float | None:
    """Share of the ``.link`` sections' time inside the union of
    ``windows`` (a slab's upload start to its module's start)."""
    total = sum(e - s for s, e in links)
    if not total:
        return None
    merged = _union(sorted((s, e, "") for s, e in windows if e > s))
    inside = sum(max(0.0, min(e, me) - max(s, ms))
                 for s, e in links for ms, me, _ in merged)
    return inside / total


def drained(slabs: list[dict], closes: list[tuple]) -> list[dict]:
    """The slabs a ``registry.drain`` landed: all but each call's last,
    the one dispatched last before a ``registry.copy_out`` opened."""
    opened = [start for start, _ in closes]
    out = []
    for j, slab in enumerate(slabs):
        k = bisect.bisect_left(opened, slab["dispatched"])
        following = slabs[j + 1]["dispatched"] if j + 1 < len(slabs) \
            else float("inf")
        if k == len(opened) or opened[k] >= following:
            out.append(slab)
    return out


def _three(values: list[float]) -> str:
    return (f"{1e3 * min(values):.3f} / {1e3 * statistics.median(values):.3f}"
            f" / {1e3 * max(values):.3f} ms (min / median / max)")


def report(sl: dict, ops: float, facts: dict) -> None:
    split, slabs = sl["split"], sl["slabs"]
    by_section = sorted(split["undispatched"].items(), key=lambda kv: -kv[1])
    undispatched = sum(split["undispatched"].values())
    after = split["operand"] + split["inside"]
    harness.say(
        f"registry idle: slice {1e3 * (sl['hi'] - sl['lo']):.1f} ms, "
        f"{sl['launches']} launches paired with {len(slabs)} "
        f"jit_registry_gf* modules; device idle an op: "
        f"{1e3 * (undispatched + after) / ops:.3f} ms = dispatched and not "
        f"started (the operand's upload and the dispatch latency) "
        f"{1e3 * split['operand'] / ops:.3f} + between a module's own "
        f"operations {1e3 * split['inside'] / ops:.3f} + not dispatched "
        f"{1e3 * undispatched / ops:.3f} (" + ", ".join(
            f"{name.removeprefix(LAYER)} {1e3 * secs / ops:.3f}"
            for name, secs in by_section) + ")")
    moved = slab_bytes(facts)
    waited = [s["off_idle"] and s["start"] > s["dispatched"] for s in slabs]
    if any(waited):
        late = [s["start"] - s["dispatched"]
                for s, w in zip(slabs, waited) if w]
        harness.say(
            f"  dispatch (end of registry.launch) -> module start, over "
            f"the {len(late)} of {len(slabs)} slabs whose module started "
            f"off an idle device behind its dispatch: {_three(late)}; the "
            f"least is the runtime's dispatch latency at most")
    if len(sl["uploads"]) == len(slabs):
        up = [(u[0], s["start"]) for u, s in zip(sl["uploads"], slabs)]
        h2d = [e - s for (s, e), w in zip(up, waited) if w]
        if h2d:
            rate = (f", {moved[0] / statistics.median(h2d) / GIB:.2f} GiB/s "
                    f"of a slab's {moved[0] / (1 << 20):.1f} MiB up at the "
                    f"median, at least" if moved else "")
            harness.say(f"  registry.upload start -> module start, the "
                        f"same slabs: {_three(h2d)}{rate}")
        share = overlap_share(sl["links"], up)
        if share is not None:
            harness.say(
                f"  registry.drain.link sections: {len(sl['links'])}, "
                f"{1e3 * sum(e - s for s, e in sl['links']) / ops:.3f} ms "
                f"an op, {100 * share:.1f} % of it while some slab was "
                f"between its upload's start and its module's start (both "
                f"directions of the link asked for at once)")
    landed = drained(slabs, sl["closes"])
    if sl["links"] and len(sl["links"]) == len(landed):
        d2h = [e - slab["end"] for (s, e), slab in zip(sl["links"], landed)
               if e - s >= LINK_WAITED_S]
        if d2h:
            rate = (f", {moved[1] / statistics.median(d2h) / GIB:.2f} GiB/s "
                    f"of a slab's {moved[1] / (1 << 20):.1f} MiB down (the "
                    f"window's mean) at the median" if moved else "")
            harness.say(
                f"  module end -> end of the slab's registry.drain.link, "
                f"over the {len(d2h)} of {len(landed)} drained slabs whose "
                f".link waited {1e6 * LINK_WAITED_S:.0f} us or more: "
                f"{_three(d2h)}{rate}")
    harness.say(
        f"  the worker's lines (no mark on them): registry.gather self "
        f"{1e3 * sl['gather_self'] / ops:.3f} ms an op, "
        f"registry.gather.wait {1e3 * sl['gather_wait'] / ops:.3f} ms an op")
