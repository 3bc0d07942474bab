"""PG scrubbing: shard-local digests, chunk by chunk, compared at the
primary; repair through recovery's own path.

src/osd/scrubber analog (pg_scrubber.cc / scrub_backend.cc /
ECBackend::be_deep_scrub).  A PG is scrubbed in CHUNKS of at most
``osd_scrub_chunk_max`` names in the store's listing order.  For each
chunk the primary

  1. takes the PG's lock for an instant, lists the chunk, and marks
     its name range on the PG (``PG.scrub_chunk_begin``): from then on
     a write whose name falls inside the range waits for the chunk,
     every other write to the PG proceeds;
  2. waits, without the lock, until the writes already in flight to
     names inside the range have committed on every shard, so that
     the maps of one chunk describe the same set of committed writes;
  3. asks every up member of the acting set for its scrub map of the
     range (``pg_scrub_map_req``) and builds its own meanwhile.  Each
     OSD digests ITS OWN copy and answers with a map, never with
     bytes: for a replicated pool size and data / attr / omap digests
     (``build_scrub_map``); for an erasure pool, per shard object, its
     length, version, shard label, stored ``_crc`` and the CRC32C
     recomputed over the bytes (``build_shard_map``);
  4. compares the maps (``compare_shard_maps``, ``compare_replicas``),
     repairs what it found where asked to, and opens the range again.

Where a shard's digest runs: a shard resident in the OSD's shard cache
goes through the device CRC kernel, all resident shards of a chunk in
ONE launch submitted through the OSD's CodecBatcher (kind ``digest``,
program ``jit_crc32c_shards``, scope ``crc32c``); a shard that is not
resident is read through the store and digested by ``crc32c_batch`` on
the host.  The ``scrub`` perf set counts each route's bytes.

What makes an erasure shard bad: it is missing; its label is not its
position; its version or length differs from the authoritative one
(what most shards agree on, the primary's where they tie); or the
recomputed CRC32C and the stored ``_crc`` differ.  The tags were made
in the encode launch that made the parity, so an object whose every
shard verifies needs no reconstruction: the tags attest the parity.
Only where a tag and its bytes disagree can tags alone not say which
side is wrong: the shard is then rebuilt from k verified others
(``read_recovery_payload``: gather, ``jit_ec_decode_rows``) and the
rebuilt bytes decide between ``bytes`` and ``tag``.  Repair pushes
that rebuilt shard to its OSD through ``pg_push``, which verifies the
payload's CRC and label before applying it, as in backfill.
"""

from __future__ import annotations

import asyncio
import hashlib
import json
import time
import zlib
from typing import Any

from ..common import tracing
from ..common.tracing import section
from ..ops.crc32c_batch import crc32c_batch, digest_rows
from .backend import (CRC_ALG, CRC_ALG_XATTR, CRC_XATTR, META_OID,
                      ECBackend, SHARD_XATTR, VER_XATTR, ver_decode)

# objects digested per batched CRC call: bounds the payload bytes held
# in RAM at once while keeping the per-call amortization (a collection
# of any size still makes O(n/256) library calls, not O(n))
_DIGEST_BATCH = 256
# names a listing page asks the store for
_LIST_PAGE = 64
MAP_TIMEOUT = 15.0


def _scrubbed(oid: str) -> bool:
    """Internal objects (the PG's meta object, snap bookkeeping) are
    no part of a scrub."""
    from .snaps import INTERNAL_OIDS
    return oid != META_OID and oid not in INTERNAL_OIDS


def names_in_range(store, coll: str, begin: str,
                   end: str | None) -> list[str]:
    """Object names of a collection in (begin, end], in listing order;
    ``end`` None is the end of the collection."""
    out: list[str] = []
    cursor = begin
    while True:
        page = store.list_objects_range(coll, cursor, _LIST_PAGE)
        for oid in page:
            if end is not None and oid > end:
                return out
            if _scrubbed(oid):
                out.append(oid)
        if len(page) < _LIST_PAGE:
            return out
        cursor = page[-1]


def next_chunk(store, coll: str, begin: str,
               limit: int) -> tuple[list[str], str | None]:
    """The next chunk of a scrub: up to ``limit`` names after
    ``begin`` in listing order, and the chunk's last name, or None
    where the listing ends with it (the last chunk's range runs to the
    end of the collection, so a name created behind it waits too)."""
    names: list[str] = []
    cursor = begin
    while len(names) <= limit:
        page = store.list_objects_range(coll, cursor, _LIST_PAGE)
        names += [o for o in page if _scrubbed(o)]
        if len(page) < _LIST_PAGE:
            break
        cursor = page[-1]
    if len(names) <= limit:
        return names, None
    return names[:limit], names[limit - 1]


async def build_scrub_map(store, coll: str, deep: bool = True,
                          begin: str = "",
                          end: str | None = None) -> dict[str, dict]:
    """Digest the objects of a replicated PG's collection whose names
    lie in (begin, end] (replica side; the whole collection by
    default).

    Async with periodic yields: digesting a whole PG synchronously
    would stall the event loop past the heartbeat grace and get the
    daemon falsely reported down.  Deep-scrub data digests gather the
    object payloads and go through ONE batched ``crc32c_batch`` call
    per chunk of the collection instead of a scalar host call per
    object.  Objects resident in the store's shard cache digest
    WITHOUT a store read: the write-time CRC tag (when carried) IS the
    digest, else the resident buffer joins the batched pass directly."""
    cache = getattr(store, "shard_cache", None)
    out: dict[str, dict] = {}
    pending: list[tuple[str, bytes]] = []   # (oid, payload) awaiting CRC

    def flush_digests() -> None:
        if not pending:
            return
        crcs = crc32c_batch([p for _, p in pending])
        for (oid2, _), crc in zip(pending, crcs):
            out[oid2]["data_digest"] = int(crc)
        pending.clear()

    for i, oid in enumerate(names_in_range(store, coll, begin, end)):
        if i % 16 == 15:
            await asyncio.sleep(0)
        st = store.stat(coll, oid)
        if st is None:
            continue
        entry: dict[str, Any] = {"size": st["size"]}
        attrs = {k: v for k, v in store.getattrs(coll, oid).items()}
        omap = store.omap_get(coll, oid)
        entry["attrs_digest"] = hashlib.sha1(
            json.dumps({k: v.hex() for k, v in sorted(attrs.items())})
            .encode()).hexdigest()
        entry["omap_digest"] = hashlib.sha1(
            json.dumps({k: v.hex() for k, v in sorted(omap.items())})
            .encode()).hexdigest()
        out[oid] = entry
        if deep:
            resident = cache.get(coll, oid) \
                if cache is not None and (coll, oid) in cache else None
            if resident is not None and resident.crc is not None:
                entry["data_digest"] = resident.crc
                from ..os.device_cache import PERF as DATAPATH_PERF
                DATAPATH_PERF.inc("scrub_cached_digests")
                continue
            if resident is not None:
                payload = resident.buf          # no store round trip
            else:
                payload = bytes(store.read(coll, oid, 0, None))
                if cache is not None:
                    cache.note_host_read(len(payload))
            pending.append((oid, payload))
            if len(pending) >= _DIGEST_BATCH:
                flush_digests()
    flush_digests()
    return out


def _legacy_tag_ok(payload, crc: int | None, attrs: dict) -> bool:
    """A ``_crc`` stamped before the integrity pipeline unified on
    CRC32C (no ``_crc_alg`` beside it) may be a zlib.crc32 of the same
    bytes: still a tag that vouches for them."""
    if crc is None or attrs.get(CRC_ALG_XATTR) == CRC_ALG:
        return False
    if zlib.crc32(bytes(payload)) & 0xFFFFFFFF != crc:
        return False
    from ..ops.crc32c_batch import PERF
    PERF.inc("legacy_crc_tags")
    return True


async def build_shard_map(store, coll: str, begin: str = "",
                          end: str | None = None, batcher=None,
                          perf=None) -> dict[str, dict]:
    """This OSD's deep scrub map of an erasure PG's shard objects in
    (begin, end]: per object its shard's length, version, label,
    stored ``_crc`` and ``digest``, the CRC32C recomputed over the
    bytes this OSD holds.  No shard byte leaves the OSD.

    Resident shards (the store's shard cache) take the device route,
    all of them in one ``batcher.digest`` launch; the others are read
    through the store and digested in one ``crc32c_batch`` pass on
    the host (a scrub does not fill the cache with what it reads).
    ``perf`` (the OSD's ``scrub`` set) counts each route's bytes.  A
    chunk is at most ``osd_scrub_chunk_max`` objects, so nothing here
    yields except the launch itself."""
    cache = getattr(store, "shard_cache", None)
    out: dict[str, dict] = {}
    cold: list[tuple[str, dict]] = []
    resident: list[tuple[str, dict, Any]] = []
    with section("scrub.list"):
        for oid in names_in_range(store, coll, begin, end):
            st = store.stat(coll, oid)
            if st is None:
                continue
            attrs = store.getattrs(coll, oid)
            label, crc = attrs.get(SHARD_XATTR), attrs.get(CRC_XATTR)
            out[oid] = {
                "size": st["size"],
                "ver": list(ver_decode(attrs.get(VER_XATTR))),
                "shard": None if label is None else int(label),
                "crc": None if crc is None else int(crc)}
            entry = cache.get(coll, oid) if batcher is not None \
                and cache is not None and (coll, oid) in cache else None
            if entry is None:
                cold.append((oid, attrs))
            else:
                resident.append((oid, attrs, entry.buf))

    def settle(oid: str, attrs: dict, payload, digest: int) -> None:
        e = out[oid]
        e["digest"] = e["crc"] if digest != e["crc"] and _legacy_tag_ok(
            payload, e["crc"], attrs) else int(digest)

    if cold:
        with section("scrub.digest_host"):
            payloads = [bytes(store.read(coll, oid, 0, None))
                        for oid, _ in cold]
            n = sum(len(p) for p in payloads)
            if cache is not None:
                cache.note_host_read(n)
            for (oid, attrs), p, crc in zip(cold, payloads,
                                            crc32c_batch(payloads)):
                settle(oid, attrs, p, int(crc))
        if perf is not None:
            perf.inc("bytes_digested_host", n)
    if resident:
        with section("scrub.digest_device"):
            bufs = [buf for _, _, buf in resident]
            lengths = [len(b) for b in bufs]
            rows = digest_rows(bufs)
        crcs = await batcher.digest(rows, lengths)
        with section("scrub.digest_device"):
            for (oid, attrs, buf), crc in zip(resident, crcs):
                settle(oid, attrs, buf, int(crc))
        if perf is not None:
            perf.inc("bytes_digested_device", sum(lengths))
    return out


class ScrubResult:
    """What one scrub of a PG found.  ``errors`` is the flat list of
    ``(oid, shard, kind)`` (for a replicated pool ``shard`` is the
    OSD's id and the kind ``digest``); ``inconsistent`` groups it by
    object."""

    def __init__(self, pgid: str) -> None:
        self.pgid = pgid
        self.started = time.time()
        self.stamp: float | None = None     # set when it ran to the end
        self.chunks = 0
        self.objects_scrubbed = 0
        self.errors: list[tuple[str, int, str]] = []
        self.inconsistent: dict[str, dict] = {}   # oid -> detail
        self.repaired: list[str] = []
        self.shards_repaired: list[tuple[str, int]] = []
        self.unrepaired: list[tuple[str, int]] = []

    @property
    def clean(self) -> bool:
        return not self.inconsistent

    def to_dict(self) -> dict:
        return {"pgid": self.pgid, "started": self.started,
                "stamp": self.stamp, "deep": True,
                "chunks": self.chunks,
                "objects_scrubbed": self.objects_scrubbed,
                "errors": [list(e) for e in self.errors],
                "inconsistent": self.inconsistent,
                "repaired": self.repaired,
                "shards_repaired": [list(e)
                                    for e in self.shards_repaired],
                "unrepaired": [list(e) for e in self.unrepaired],
                "clean": self.clean}


class ScrubAborted(RuntimeError):
    """The PG changed under the scrub (not primary, not active, a map
    did not arrive): nothing is concluded from a partial comparison."""


# -- comparison ---------------------------------------------------------------

def compare_replicas(maps: dict[int, dict[str, dict]]) -> dict[str, dict]:
    """Replicated pool: majority of (size, digests) is authoritative.
    {oid: {"auth_osds", "bad": [{"osds", "digests"}]}} for every object
    the replicas disagree on."""
    found: dict[str, dict] = {}
    for oid in sorted(set().union(*[set(m) for m in maps.values()])):
        versions: dict[str, list[int]] = {}
        for osd_id, m in maps.items():
            key = json.dumps(m.get(oid), sort_keys=True)
            versions.setdefault(key, []).append(osd_id)
        if len(versions) <= 1:
            continue
        # majority vote picks the authoritative digest set
        auth_key = max(versions, key=lambda k: len(versions[k]))
        found[oid] = {
            "auth_osds": versions[auth_key],
            "bad": [{"osds": osds, "digests": json.loads(k)}
                    for k, osds in versions.items() if k != auth_key]}
    return found


def compare_shard_maps(maps: dict[int, dict[str, dict]], primary: int
                       ) -> tuple[list[tuple], list[tuple], int]:
    """Erasure pool: ``maps`` is {shard position: that OSD's
    ``build_shard_map``} for every up member of the acting set.
    Returns (bad, suspect, verified): ``bad`` the ``(oid, shard,
    kind)`` the maps alone decide (``missing``, ``label``, ``version``,
    ``size``), ``suspect`` the ``(oid, shard, digest)`` whose bytes and
    tag disagree or that carry bytes and no tag, which only a rebuilt
    shard can judge, and ``verified`` the objects whose every shard
    matched its write-time identity."""
    bad: list[tuple] = []
    suspect: list[tuple] = []
    verified = 0
    for oid in sorted(set().union(*[set(m) for m in maps.values()])):
        have = {s: m[oid] for s, m in maps.items() if oid in m}
        n_bad = len(bad) + len(suspect)
        # what most shards that sit where they were written agree on
        votes: dict[tuple, int] = {}
        for s, e in have.items():
            if e["shard"] is None or e["shard"] == s:
                key = (tuple(e["ver"]), e["size"])
                votes[key] = votes.get(key, 0) + 1
        mine = have.get(primary)
        auth = max(votes, key=lambda k: (
            votes[k], mine is not None
            and k == (tuple(mine["ver"]), mine["size"]))) \
            if votes else None
        for s in sorted(maps):
            e = have.get(s)
            if e is None:
                bad.append((oid, s, "missing"))
            elif e["shard"] is not None and e["shard"] != s:
                bad.append((oid, s, "label"))
            elif tuple(e["ver"]) != auth[0]:
                bad.append((oid, s, "version"))
            elif e["size"] != auth[1]:
                bad.append((oid, s, "size"))
            elif e["crc"] != e["digest"] and (e["crc"] is not None
                                              or e["size"]):
                suspect.append((oid, s, e["digest"]))
        verified += n_bad == len(bad) + len(suspect)
    return bad, suspect, verified


# -- the primary's side -------------------------------------------------------

async def _chunk_maps(pg, ec: bool, begin: str, end: str | None,
                      perf) -> dict[int, dict[str, dict]]:
    """Every up acting member's map of the range, the primary's own
    among them, keyed by OSD id.  The requests are staged first (on
    the per-peer pipe the sub-writes take, so behind them), the
    primary digests its own copy while the peers digest theirs (span
    ``scrub.digest``), then waits for the maps still out.  A peer
    whose map does not come aborts the scrub: a silent peer's shards
    are not missing shards."""
    osd = pg.osd
    peers = [o for o in pg.acting_peers() if osd.osd_is_up(o)]
    pipe = getattr(osd, "subop_pipe", None)
    if pipe is None or pipe.closed:
        raise ScrubAborted(f"osd.{osd.whoami} is stopping")
    asked = osd.fanout_staged(
        [(o, "pg_scrub_map_req",
          {"pgid": pg.pgid, "begin": begin, "end": end}, [])
         for o in peers])
    span = tracing.child_span("scrub.digest")
    try:
        if ec:
            local = await build_shard_map(
                osd.store, pg.coll, begin, end,
                batcher=osd.codec_batcher, perf=perf)
        else:
            local = await build_scrub_map(osd.store, pg.coll,
                                          begin=begin, end=end)
    except BaseException:
        osd.drop_staged(asked)
        raise
    finally:
        tracing.finish(span)
    replies = await osd.await_staged(asked, collect=True,
                                     timeout=MAP_TIMEOUT)
    maps = {pg.whoami: local}
    for rep in replies:
        if rep.data.get("err"):
            continue
        blob = rep.segments[0] if rep.segments else b"{}"
        if perf is not None:
            perf.inc("map_bytes", len(blob))
        maps[rep.data["from_osd"]] = json.loads(bytes(blob))
    if set(maps) != set(peers) | {pg.whoami}:
        raise ScrubAborted(f"pg {pg.pgid}: no scrub map from "
                           f"{sorted(set(peers) - set(maps))}")
    return maps


async def _repair_replicated(pg, oid: str, auth_osds: list[int],
                             bad_osds: list[int]) -> None:
    """Push the authoritative copy over divergent replicas."""
    if pg.whoami in auth_osds:
        payload = await pg.backend.read_recovery_payload(oid, 0)
    else:
        replies = await pg.osd.fanout_and_wait(
            [(auth_osds[0], "pg_pull",
              {"pgid": pg.pgid, "oid": oid, "shard": 0}, [])],
            collect=True, timeout=10)
        if not replies or replies[0].data.get("err"):
            return
        rep = replies[0]
        payload = {"data": rep.segments[0] if rep.segments else b"",
                   "xattrs": {k: bytes.fromhex(v) for k, v in
                              rep.data.get("xattrs", {}).items()},
                   "omap": {k: bytes.fromhex(v) for k, v in
                            rep.data.get("omap", {}).items()},
                   "absent": rep.data.get("absent", False)}
    data, segs = pg._push_payload(oid, payload)
    data["pgid"] = pg.pgid
    for osd_id in bad_osds:
        if osd_id == pg.whoami:
            pg._apply_recovery_payload(oid, data, segs)
        else:
            await pg.osd.fanout_and_wait(
                [(osd_id, "pg_push", data, segs)],
                collect=True, timeout=10)


async def _rebuild_shard(pg, oid: str, shard: int) -> dict | None:
    """The shard as k verified others give it (recovery's payload:
    bytes, identity xattrs, CRC), or None where they cannot."""
    try:
        payload = await pg.backend.read_recovery_payload(oid, shard)
    except (ConnectionError, OSError, asyncio.TimeoutError, ValueError,
            RuntimeError):
        return None
    return None if payload.get("absent") else payload


async def _push_shard(pg, oid: str, shard: int, payload: dict) -> bool:
    """Install a rebuilt shard on the OSD that serves ``shard``; its
    ``_apply_recovery_payload`` verifies CRC and label first."""
    data, segs = pg._push_payload(oid, payload)
    data["pgid"] = pg.pgid
    osd_id = pg.acting[shard]
    if osd_id == pg.whoami:
        try:
            pg._apply_recovery_payload(oid, data, segs)
        except ValueError:
            return False
        return True
    replies = await pg.osd.fanout_and_wait(
        [(osd_id, "pg_push", data, segs)], collect=True, timeout=10)
    return bool(replies) and not replies[0].data.get("err")


async def _scrub_chunk_ec(pg, res: ScrubResult, maps: dict, repair: bool,
                          perf) -> None:
    from ..os.device_cache import PERF as DATAPATH_PERF
    from .backend import shard_crc
    by_shard = {pg.acting.index(o): m for o, m in maps.items()}
    span = tracing.child_span("scrub.compare")
    with section("scrub.compare"):
        bad, suspect, verified = compare_shard_maps(
            by_shard, pg.acting.index(pg.whoami))
    tracing.finish(span)
    DATAPATH_PERF.inc("scrub_fast_verifies", verified)
    if not bad and not suspect:
        return
    span = tracing.child_span("scrub.repair",
                              shards=len(bad) + len(suspect))
    try:
        rebuilt: dict[tuple, dict | None] = {}
        for oid, shard, digest in suspect:
            # tag and bytes disagree: the rebuilt shard says which
            payload = rebuilt[oid, shard] = await _rebuild_shard(
                pg, oid, shard)
            with section("scrub.repair"):
                if payload is None:
                    bad.append((oid, shard, "crc"))
                elif shard_crc(payload["data"]) != digest:
                    bad.append((oid, shard, "bytes"))
                elif by_shard[shard][oid]["crc"] is not None:
                    bad.append((oid, shard, "tag"))
        for oid, shard, kind in sorted(bad):
            res.errors.append((oid, shard, kind))
            detail = res.inconsistent.setdefault(
                oid, {"bad_shards": [], "bad_tags": [], "errors": []})
            detail["bad_tags" if kind == "tag"
                   else "bad_shards"].append(shard)
            detail["errors"].append(
                {"shard": shard, "osd": pg.acting[shard], "kind": kind})
            if perf is not None:
                perf.inc("errors_found")
            if not repair:
                continue
            payload = rebuilt[oid, shard] if (oid, shard) in rebuilt \
                else await _rebuild_shard(pg, oid, shard)
            if payload is not None \
                    and await _push_shard(pg, oid, shard, payload):
                res.shards_repaired.append((oid, shard))
                if oid not in res.repaired:
                    res.repaired.append(oid)
                if perf is not None:
                    perf.inc("shards_repaired")
            else:
                res.unrepaired.append((oid, shard))
    finally:
        tracing.finish(span)


async def _scrub_chunk_replicated(pg, res: ScrubResult, maps: dict,
                                  repair: bool, perf) -> None:
    span = tracing.child_span("scrub.compare")
    with section("scrub.compare"):
        found = compare_replicas(maps)
    tracing.finish(span)
    for oid, detail in found.items():
        res.inconsistent[oid] = detail
        bad_osds = [o for b in detail["bad"] for o in b["osds"]]
        res.errors += [(oid, o, "digest") for o in bad_osds]
        if perf is not None:
            perf.inc("errors_found", len(bad_osds))
        if repair:
            span = tracing.child_span("scrub.repair",
                                      shards=len(bad_osds))
            try:
                await _repair_replicated(pg, oid, detail["auth_osds"],
                                         bad_osds)
            finally:
                tracing.finish(span)
            res.repaired.append(oid)
            if perf is not None:
                perf.inc("shards_repaired", len(bad_osds))


async def scrub_pg(pg, repair: bool = False) -> ScrubResult:
    """Deep-scrub one PG at its primary, chunk by chunk (the module
    docstring has the steps).  The caller holds the scrub slots; the
    PG's lock is held only while a chunk is listed and its range
    marked.  Under an active span (``pg.scrub``, which the OSD opens)
    each chunk is a ``scrub.chunk`` span with ``scrub.maps`` (the
    requests sent until every map is in; ``scrub.digest``, the
    primary's own map, nests in it), ``scrub.compare`` and
    ``scrub.repair`` under it."""
    osd = pg.osd
    ec = isinstance(pg.backend, ECBackend)
    perf = getattr(osd, "perf_scrub", None)
    chunk_max = max(1, int(osd.config.get("osd_scrub_chunk_max", 25)))
    compare = _scrub_chunk_ec if ec else _scrub_chunk_replicated
    res = ScrubResult(pg.pgid)
    acting = list(pg.acting)
    cursor = ""
    while True:
        if not pg.is_primary() or pg.state != "active" \
                or list(pg.acting) != acting:
            raise ScrubAborted(f"pg {pg.pgid} changed under its scrub")
        chunk = tracing.child_span("scrub.chunk")
        if chunk is not None:
            chunk.activate()
        try:
            names, end = await pg.scrub_chunk_begin(cursor, chunk_max)
            try:
                span = tracing.child_span("scrub.maps")
                if span is not None:
                    span.activate()
                try:
                    maps = await _chunk_maps(pg, ec, cursor, end, perf)
                finally:
                    tracing.finish(span)
                await compare(pg, res, maps, repair, perf)
            finally:
                blocked = pg.scrub_chunk_end()
            # names of the range on any member: one that a write
            # created while the range's commits drained among them
            objects = len(set().union(*maps.values()))
            res.chunks += 1
            res.objects_scrubbed += objects
            if perf is not None:
                perf.inc("chunks")
                perf.inc("objects", objects)
            if chunk is not None:
                chunk.tags.update(
                    objects=len(names), blocked_writes=blocked,
                    bytes=sum(e.get("size", 0) for m in maps.values()
                              for e in m.values()))
        finally:
            tracing.finish(chunk)
        if end is None:
            break
        cursor = end
    res.stamp = time.time()
    return res
