"""Plain reference for a deep scrub of an erasure pool with planted
faults: which ``(oid, shard, kind)`` a scrub must report, and the bytes
a repair must leave.

Everything comes from what the run was given, and nothing from what
the program made: the population's generator (object ``i`` is
``default_rng([seed, i]).bytes(object_bytes)``), the profile, and the
list of planted faults, itself drawn from ``default_rng([seed, 2])``.
Shards, labels and checksums are ``reference/ec.py``'s.  Nothing here
imports the program.

A fault is one of (``osd-scrub-repair.sh``'s ways of corrupting an
erasure pool):

  data_rot       ROT_BYTES bytes of a data shard XORed with 0xff at a
                 drawn offset, the shard's ``_crc`` left as it was
  parity_rot     the same on a parity shard
  tag_rot        the ``_crc`` xattr replaced by a drawn value, the
                 bytes left as they were
  missing_shard  one shard object removed

and a deep scrub reports it as ``(oid, shard, REPORTED_AS[kind])``:
changed bytes as ``bytes``, a changed tag as ``tag`` (the bytes still
are what k other shards give), a removed shard as ``missing``.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference import ec

KINDS = ("data_rot", "parity_rot", "tag_rot", "missing_shard")
REPORTED_AS = {"data_rot": "bytes", "parity_rot": "bytes",
               "tag_rot": "tag", "missing_shard": "missing"}
ROT_BYTES = 16
FAULT_STREAM = 2          # default_rng([seed, FAULT_STREAM])


def object_bytes(seed: int, i: int, size: int) -> bytes:
    """Object ``obj-<i>`` of the population."""
    return np.random.default_rng([seed, i]).bytes(size)


def shard_bytes(profile: dict, object_size: int) -> int:
    """Length of every shard of an object: whole stripe rows."""
    width = profile["k"] * profile["stripe_unit"]
    return -(-object_size // width) * profile["stripe_unit"]


def plant(seed: int, profile: dict, objects: int, object_size: int,
          per_kind: int) -> list[dict]:
    """The faults of a run: ``per_kind`` of each kind, in ``KINDS``'
    order, on ``4 * per_kind`` distinct objects of the population.
    Each is {"oid", "index", "shard", "kind"} and, for a rot, the
    "offset" of its ROT_BYTES bytes in the shard, for a changed tag the
    value "crc" it was replaced with (drawn until it is not the right
    one)."""
    k, m = int(profile["k"]), int(profile["m"])
    if objects < len(KINDS) * per_kind:
        raise ValueError(f"{len(KINDS) * per_kind} faults need as many "
                         f"objects, not {objects}")
    rng = np.random.default_rng([seed, FAULT_STREAM])
    length = shard_bytes(profile, object_size)
    picks = rng.choice(objects, size=len(KINDS) * per_kind, replace=False)
    faults = []
    for n, i in enumerate(int(i) for i in picks):
        kind = KINDS[n // per_kind]
        if kind == "data_rot":
            shard = int(rng.integers(0, k))
        elif kind == "parity_rot":
            shard = k + int(rng.integers(0, m))
        else:
            shard = int(rng.integers(0, k + m))
        fault = {"oid": f"obj-{i}", "index": i, "shard": shard,
                 "kind": kind}
        if kind in ("data_rot", "parity_rot"):
            fault["offset"] = int(rng.integers(0, length - ROT_BYTES + 1))
        elif kind == "tag_rot":
            right = ec.ceph_crc32c(ec.shards_of(
                profile, object_bytes(seed, i, object_size))[shard])
            wrong = right
            while wrong == right:
                wrong = int(rng.integers(0, 1 << 32))
            fault["crc"] = wrong
        faults.append(fault)
    return faults


def rotted(shard: bytes, offset: int) -> bytes:
    """The ROT_BYTES bytes a rot leaves at ``offset`` of a shard."""
    return bytes(b ^ 0xFF for b in shard[offset:offset + ROT_BYTES])


def expected_reports(faults: list[dict]) -> set[tuple[str, int, str]]:
    """Exactly what the deep scrubs of all PGs together must report."""
    return {(f["oid"], f["shard"], REPORTED_AS[f["kind"]]) for f in faults}


def repaired_shard(seed: int, profile: dict, fault: dict,
                   object_size: int) -> tuple[bytes, int, int]:
    """(bytes, CRC32C, label) the faulted shard holds after repair:
    the profile's generator over the object's last acknowledged write,
    which for the population is the populate's."""
    raw = ec.shards_of(profile, object_bytes(
        seed, fault["index"], object_size))[fault["shard"]]
    return raw, ec.ceph_crc32c(raw), fault["shard"]


def check_reports(reported, faults: list[dict]) -> dict:
    """Faults of the scrubs' union of reports against the planted
    list: ``missed`` (planted, not reported, or under another kind) and
    ``false_reports`` (reported, not planted: a sound shard, an object
    written meanwhile)."""
    want = expected_reports(faults)
    got = {(oid, int(shard), kind) for oid, shard, kind in reported}
    return {"missed": len(want - got), "false_reports": len(got - want)}
