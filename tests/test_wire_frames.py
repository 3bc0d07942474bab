"""The frame codec without a socket: ``Message.encode_parts`` against
golden frames that the encoder of PR 27 produced (the bytes on the wire
did not change when frames stopped being joined; since PR 39 the
envelope is struct_v 2, which is those bytes with ``ack_seq`` and
``flags`` appended inside the envelope: ``_v2`` / ``_v1`` spell the
difference out, and the v1 frames still decode), and ``FrameReader``
fed the same stream in every way a socket can cut it.
"""

from __future__ import annotations

import hashlib
import struct

import pytest

from ceph_tpu.msg.message import (COMP_MAGIC, MAGIC, MAX_FRAME, RECV_BUF,
                                  SCATTER_MIN, SEC_MAGIC, FrameReader,
                                  Message)
from ceph_tpu.native import crc32c

BIG = bytes(range(256)) * 80          # 20 KiB: a part of its own
assert len(BIG) >= SCATTER_MIN


def _deep(n: int):
    out = "leaf"
    for _ in range(n):
        out = [out]
    return out


# name -> (type, data, segments); seq 77 from osd.3
CASES = {
    "typed_no_segments": ("osd_op", {
        "pgid": "1.2a", "oid": "obj-7", "tid": 43, "reqid": ["client.4", 9],
        "ops": [{"op": "write_full", "len": 5}]}, []),
    "typed_one_segment": ("rep_op", {
        "pgid": "1.2a", "tid": 5,
        "entry": {"oid": "obj-7", "version": [9, 140]},
        "muts": [{"op": "write", "off": 0}]}, [b"hello"]),
    "value_many_segments": ("paxos_begin", {
        "version": 7, "value": "v" * 20, "e": 2,
        "nested": {"a": [1, None], "b": -1.5}},
        [b"a", b"bc", b"def", b"\x00\xff" * 9]),
    "value_empty_segment": ("ping", {"n": 1}, [b"", b"x", b""]),
    # deeper than the value codec nests: these ride the json escape
    "json_escape": ("odd", {"deep": _deep(230), "s": "t"}, [b"seg"]),
    "json_escape_no_segments": ("odd", {"deep": _deep(201)}, []),
}
KIND = {"typed": 2, "value": 0, "json": 1}
# the frames PR 27's ``Message.encode`` gave for CASES, as hex
GOLDEN = {
    "typed_no_segments": (
        "4354763396000000010190000000060000006f73645f6f704d00000000000000050000006f73"
        "642e33026c0000000101660000000104000000312e326101050000006f626a2d37012b000000"
        "000000000108000000636c69656e742e34090000000000000001070100000008020000000200"
        "00006f70050a00000077726974655f66756c6c030000006c656e030500000000000000080000"
        "00000000000011053ed4"),
    "typed_one_segment": (
        "43547633af0000000101a9000000060000007265705f6f704d00000000000000050000006f73"
        "642e33028100000001017b0000000104000000312e3261010500000000000000010802000000"
        "030000006f696405050000006f626a2d370700000076657273696f6e07020000000309000000"
        "00000000038c000000000000000107010000000802000000020000006f700505000000777269"
        "7465030000006f66660300000000000000000800000000010000000500000068656c6c6fc97f"
        "1fbb"),
    "value_many_segments": (
        "43547633b90000000101b30000000b0000007061786f735f626567696e4d0000000000000005"
        "0000006f73642e33007a00000008040000000700000076657273696f6e030700000000000000"
        "0500000076616c75650514000000767676767676767676767676767676767676767601000000"
        "65030200000000000000060000006e6573746564080200000001000000610702000000030100"
        "00000000000000010000006204000000000000f8bf0400000001000000020000000300000012"
        "00000061626364656600ff00ff00ff00ff00ff00ff00ff00ff00ffbb03a201"),
    "value_empty_segment": (
        "43547633470000000101410000000400000070696e674d00000000000000050000006f73642e"
        "3300130000000801000000010000006e03010000000000000003000000000000000100000000"
        "00000078e96f050c"),
    "json_escape": (
        "435476331502000001010f020000030000006f64644d00000000000000050000006f73642e33"
        "01ea010000e60100007b2264656570223a205b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b"
        "5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b"
        "5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b"
        "5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b"
        "5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b"
        "5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b"
        "5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b226c656166225d5d5d5d5d5d5d5d5d5d5d5d"
        "5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d"
        "5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d"
        "5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d"
        "5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d"
        "5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d"
        "5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d2c202273223a20227422"
        "7d010000000300000073656712b23bed"),
    "json_escape_no_segments": (
        "43547633cd0100000101c7010000030000006f64644d00000000000000050000006f73642e33"
        "01a6010000a20100007b2264656570223a205b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b"
        "5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b"
        "5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b"
        "5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b"
        "5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b"
        "5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b5b226c656166225d5d5d"
        "5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d"
        "5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d"
        "5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d"
        "5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d"
        "5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d5d"
        "5d5d5d5d5d5d5d5d7d00000000488625b2"),
}
# name -> (type, data, segments); seq 78 from client.9
LONG_CASES = {
    "typed_long_segment": ("rep_op", {"pgid": "3.1", "tid": 6}, [BIG]),
    "long_among_short": ("sub", {"i": 1}, [
        b"head", BIG, b"", b"mid", BIG[:16384], b"tail"]),
    "long_first_and_last": ("sub", {"i": 2}, [BIG, b"x" * 100, BIG + b"!"]),
}
# length and sha256 of the frames PR 27's ``Message.encode`` gave for them
GOLDEN_LONG = {
    "typed_long_segment": (20571,
        "23e8ec815ed8e84485e4551d1906280f0dfa6ba97a2deff16d5f1c546f667604"),
    "long_among_short": (36972,
        "fd0302afb81ebafb0a21141e00301ffa5d2b89c990cdaa716060bc8fb432193a"),
    "long_first_and_last": (41146,
        "4b9afd3dad796b2daf5545c8d555ac2822cb0214b62d4972d8fc9444f8831277"),
}


def _case(name: str) -> Message:
    if name in CASES:
        mtype, data, segs = CASES[name]
        seq, sender = 77, "osd.3"
    else:
        mtype, data, segs = LONG_CASES[name]
        seq, sender = 78, "client.9"
    m = Message(mtype, data, list(segs))
    m.seq, m.from_name = seq, sender
    return m


def _frame(meta: bytes, payload: bytes) -> bytes:
    body = meta + payload
    return (MAGIC + struct.pack("<I", len(meta)) + body
            + struct.pack("<I", crc32c(body) & 0xFFFFFFFF))


def _v2(v1: bytes, ack_seq: int = 0, flags: int = 0) -> bytes:
    """The struct_v 2 frame of the message in the struct_v 1 frame
    ``v1``: version byte 2, compat still 1, ``u64 ack_seq | u8 flags``
    appended inside the envelope, the two lengths and the crc
    following suit."""
    (meta_len,) = struct.unpack_from("<I", v1, 4)
    meta = v1[8:8 + meta_len]
    assert meta[:2] == b"\x01\x01"
    assert struct.unpack_from("<I", meta, 2) == (meta_len - 6,)
    meta = (b"\x02\x01" + struct.pack("<I", meta_len - 6 + 9) + meta[6:]
            + struct.pack("<QB", ack_seq, flags))
    return _frame(meta, v1[8 + meta_len:-4])


def _v1(v2: bytes) -> bytes:
    """The other way: what PR 27's encoder gave for the same message."""
    (meta_len,) = struct.unpack_from("<I", v2, 4)
    meta = v2[8:8 + meta_len]
    assert meta[:2] == b"\x02\x01"
    meta = b"\x01\x01" + struct.pack("<I", meta_len - 6 - 9) + meta[6:-9]
    return _frame(meta, v2[8 + meta_len:-4])


def _by_hand(frame: bytes, segments: list[bytes]) -> bytes:
    """The layout spelled out, around the meta that ``frame`` carries:
    magic | u32 meta_len | meta | segments | u32 crc32c(meta + segments)."""
    (meta_len,) = struct.unpack_from("<I", frame, 4)
    body = frame[8:8 + meta_len] + b"".join(segments)
    return (MAGIC + struct.pack("<I", meta_len) + body
            + struct.pack("<I", crc32c(body) & 0xFFFFFFFF))


@pytest.mark.parametrize("name", sorted(CASES))
def test_parts_join_to_the_golden_frame(name):
    m = _case(name)
    golden = _v2(bytes.fromhex(GOLDEN[name]))
    parts = m.encode_parts()
    assert b"".join(parts) == golden == m.encode()
    assert _v1(golden) == bytes.fromhex(GOLDEN[name])
    assert len(parts) == 1           # no long segment: one small buffer
    assert golden == _by_hand(golden, m.segments)
    # the payload codec the case is named for
    (meta_len,) = struct.unpack_from("<I", golden, 4)
    at = golden.index(b"osd.3", 8, 8 + meta_len) + 5
    assert golden[at] == KIND[name.split("_")[0]]
    assert Message.decode(golden) == m


@pytest.mark.parametrize("name", sorted(CASES))
def test_a_v1_envelope_still_decodes(name):
    """A struct_v 1 frame (no ``ack_seq``, no ``flags``) is the same
    message confirming nothing, to ``decode`` and to the reader, which
    sizes the frame by the envelope's segment lengths."""
    m = _case(name)
    v1 = bytes.fromhex(GOLDEN[name])
    got = Message.decode(v1)
    assert got == m and got.ack_seq == 0 and got.flags == 0
    fed: list[Message] = []
    _feed(_reader(fed), v1 + m.encode() + v1, [3, len(v1) + 11])
    assert fed == [m, m, m]


@pytest.mark.parametrize("name", ["typed_one_segment", "json_escape"])
def test_the_envelope_carries_ack_seq_and_flags(name):
    m = _case(name)
    m.ack_seq, m.flags = (1 << 40) + 5, 1
    frame = m.encode()
    assert frame == _v2(bytes.fromhex(GOLDEN[name]), (1 << 40) + 5, 1)
    got = Message.decode(frame)
    assert got == m and got.ack_seq == (1 << 40) + 5 and got.flags == 1
    assert got != _case(name)


@pytest.mark.parametrize("name", sorted(LONG_CASES))
def test_a_long_segment_is_sent_as_the_object_it_is(name):
    m = _case(name)
    parts = m.encode_parts()
    frame = b"".join(parts)
    v1 = _v1(frame)
    assert (len(v1), hashlib.sha256(v1).hexdigest()) == GOLDEN_LONG[name]
    assert frame == _v2(v1) and len(frame) == len(v1) + 9
    assert frame == m.encode() == _by_hand(frame, m.segments)
    long_segs = [s for s in m.segments if len(s) >= SCATTER_MIN]
    sent_as_is = [p for p in parts if any(p is s for s in long_segs)]
    assert len(sent_as_is) == len(long_segs)
    assert all(type(p) is bytes for p in parts)
    assert Message.decode(frame) == m


def _stream() -> tuple[bytes, list[Message]]:
    msgs = [_case(n) for n in (*sorted(CASES), *sorted(LONG_CASES))]
    return b"".join(m.encode() for m in msgs), msgs


def _feed(reader: FrameReader, data: bytes, cuts) -> None:
    """``data`` into the reader as a socket would put it: never more
    than the buffer on offer takes, and never across a cut."""
    pos = 0
    for cut in (*cuts, len(data)):
        while pos < cut:
            buf = reader.get_buffer()
            assert len(buf) > 0
            n = min(len(buf), cut - pos)
            buf[:n] = data[pos:pos + n]
            del buf
            reader.buffer_updated(n)
            pos += n


def _reader(got: list, spare=None) -> FrameReader:
    reader = FrameReader(
        lambda head, rest: got.append(Message.decode_parts(head, rest)),
        [] if spare is None else spare)
    reader.start_frames(secure=False)
    return reader


def test_one_byte_at_a_time():
    data, msgs = _stream()
    got: list[Message] = []
    _feed(_reader(got), data, range(1, len(data)))
    assert got == msgs


def test_every_split_point_of_two_frames():
    a, b = _case("value_many_segments"), _case("typed_one_segment")
    data = a.encode() + b.encode()
    for cut in range(len(data) + 1):
        got: list[Message] = []
        _feed(_reader(got), data, [cut])
        assert got == [a, b], cut


@pytest.mark.parametrize("first", [RECV_BUF - 700, RECV_BUF - 9,
                                   RECV_BUF - 3, RECV_BUF])
def test_frames_that_straddle_the_end_of_the_receive_buffer(first):
    """A frame whose header, meta or segments reach past the receive
    buffer's end is finished in a buffer of its own and the next frame
    starts clean."""
    pad = Message("pad", {}, [b"p" * (first - 200)])
    data, msgs = _stream()
    data = pad.encode() + data
    got: list[Message] = []
    _feed(_reader(got), data, [])
    assert got == [pad, *msgs]


def test_fifty_small_frames_in_one_chunk_are_delivered_in_one_call():
    msgs = [Message("n", {"i": i}, [bytes([i]) * i]) for i in range(50)]
    data = b"".join(m.encode() for m in msgs)
    assert len(data) < RECV_BUF
    got: list[Message] = []
    reader = _reader(got)
    buf = reader.get_buffer()
    buf[:len(data)] = data
    del buf
    reader.buffer_updated(len(data))
    assert got == msgs


def test_a_tail_buffer_is_used_again_and_the_idle_reader_holds_none():
    spare: list[bytearray] = []
    big = Message("big", {}, [b"z" * (3 * RECV_BUF)])
    got: list[Message] = []
    reader = _reader(got, spare)
    _feed(reader, big.encode(), [])
    assert len(spare) == 1 and reader._buf is None and reader._rest is None
    kept = spare[0]
    _feed(reader, big.encode(), [])
    assert got == [big, big] and spare == [kept]


def _offsets(frame: bytes, m: Message) -> dict[str, int]:
    """One offset in each region of ``frame``."""
    (meta_len,) = struct.unpack_from("<I", frame, 4)
    at = {"magic": 1, "meta_len": 5, "meta": 8 + meta_len // 2,
          "crc": len(frame) - 2}
    off = 8 + meta_len
    for i, seg in enumerate(m.segments):
        if seg:
            at[f"segment {i} first"] = off
            at[f"segment {i} last"] = off + len(seg) - 1
        off += len(seg)
    return at


@pytest.mark.parametrize("name", ["typed_one_segment",
                                  "value_many_segments",
                                  "long_among_short"])
def test_a_flipped_bit_anywhere_is_refused_and_nothing_is_delivered(name):
    m = _case(name)
    frame = m.encode()
    ok = _case("value_empty_segment").encode()
    for where, at in _offsets(frame, m).items():
        for bit in (0, 7):
            bad = bytearray(frame)
            bad[at] ^= 1 << bit
            got: list[Message] = []
            try:
                _feed(_reader(got), bytes(bad) + ok * 40, [])
                refused = False
            except ValueError:
                refused = True
            # a flipped length may instead leave the reader waiting
            # for a frame that never ends: nothing is delivered either
            # way, not even the sound frames behind it
            assert got == [], (where, bit)
            assert refused or where == "meta_len", (where, bit)
            with pytest.raises(ValueError):
                Message.decode(bytes(bad))


class _NoAlloc(FrameReader):
    def _tail_buffer(self, n):
        raise AssertionError(f"{n} bytes allocated for a refused frame")

    def _make_room(self, need):
        raise AssertionError(f"room made for {need} bytes of a refused "
                             f"frame")


@pytest.mark.parametrize("frame,secure,why", [
    (MAGIC + struct.pack("<I", MAX_FRAME + 1), False, "oversized meta"),
    (Message("x", {}, [b"s"]).encode().replace(
        struct.pack("<II", 1, 1), struct.pack("<II", 1, MAX_FRAME + 1)),
     False, "oversized frame"),
    (MAGIC + b"\0" * 8, True, "plaintext frame on a secure connection"),
    (SEC_MAGIC + struct.pack("<I", 16), False,
     "encrypted frame on a plain connection"),
    (SEC_MAGIC + struct.pack("<I", 1 << 31), True,
     "oversized encrypted frame"),
    (COMP_MAGIC + struct.pack("<II", 1 << 31, 8), False,
     "oversized compressed frame"),
    (b"HTTP/1.1 200", False, "bad magic"),
])
def test_lengths_are_refused_before_anything_is_allocated(frame, secure,
                                                          why):
    got: list = []
    reader = _NoAlloc(lambda head, rest: got.append(1), [])
    reader.start_frames(secure=secure)
    with pytest.raises(ValueError, match=why):
        _feed(reader, frame, [])
    assert got == []


def test_the_handshake_takes_its_bytes_and_leaves_the_frames_behind():
    m = _case("typed_one_segment")
    got: list[Message] = []
    reader = FrameReader(
        lambda head, rest: got.append(Message.decode_parts(head, rest)), [])
    _feed(reader, b"HELO", [])
    assert reader.take(8) is None
    _feed(reader, b"1234" + m.encode() + m.encode()[:20], [])
    assert reader.take(8) == b"HELO1234" and got == []
    reader.start_frames(secure=False)
    assert got == [m]
    _feed(reader, m.encode()[20:], [])
    assert got == [m, m]
    with pytest.raises(ValueError, match="oversized handshake"):
        reader.take(RECV_BUF + 1)
