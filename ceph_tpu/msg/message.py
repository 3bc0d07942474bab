"""Wire message model and v2-lite frame codec.

Frame = magic | u32 meta_len | meta(denc) | segments | u32 crc32c.
The meta envelope is the repo's own versioned denc encoding
(common/denc.py), NOT json: hot-path types (osd_op, rep_op, ping --
msg/wire_types.py) get explicit MOSDOp-style field layouts, everything
else rides the generic tagged-value encoding, and a json escape hatch
remains only for payloads denc cannot express.  Raw binary segments
ride beside the meta, not inside it -- the same meta/payload segment
split ProtocolV2 frames use (4 segments + epilogue crcs,
src/msg/async/frames_v2.cc) -- so a frame's payload bytes are touched
once a side by the crc and otherwise moved by the kernel: the sender
hands meta and segments to the socket as a list (``encode_parts``),
the receiver reads into the frame's own buffers (``FrameReader``) and
copies each segment out once (``decode_parts``).

meta envelope (denc, struct_v 2, compat 1):
  string t | u64 seq | string from | u8 kind | blob payload |
  list<u32> seg_lens | u64 ack_seq | u8 flags
where kind selects the payload codec: 0 generic value, 1 json
(escape hatch), 2 typed (wire_types.WIRE_CODECS[t]).  ``ack_seq`` and
``flags`` came with struct_v 2 (ceph_msg_header2's ``ack_seq``): the
highest seq the sender has received on this connection, and
``FLAG_ACK_NOW``, set by a sender that is short of flow-control
window.  They stand last, so a v1 reader skips them and a v1
envelope reads as 0 / 0.
"""

from __future__ import annotations

import json
import struct
from dataclasses import dataclass, field
from typing import Any

from ..common.denc import Decoder, DencError, Encoder
from ..common.tracing import section
from ..native import crc32c
from .wire_types import WIRE_CODECS

MAGIC = b"CTv3"
MAX_FRAME = 256 << 20
# a segment this long goes to the socket as it is (an iovec entry and
# a CRC call of its own); shorter ones are joined with their
# neighbours first
SCATTER_MIN = 16 << 10

KIND_VALUE = 0
KIND_JSON = 1
KIND_TYPED = 2

FLAG_ACK_NOW = 0x01      # the sender wants this frame confirmed at once


@dataclass
class Message:
    type: str
    data: dict[str, Any] = field(default_factory=dict)
    segments: list[bytes] = field(default_factory=list)
    seq: int = 0
    from_name: str = ""
    # stamped by the connection a frame leaves on, each time it leaves
    ack_seq: int = 0
    flags: int = 0

    def encode(self) -> bytes:
        """The frame as one buffer: for a connection that compresses
        or encrypts (``wrap_frame`` takes the whole frame) and for
        tools; a plain connection sends ``encode_parts`` as it is."""
        with section("wire.encode"):
            return b"".join(self._encode_parts())

    def encode_parts(self) -> list[bytes]:
        """The frame as the buffers a scatter-gather send takes, in
        wire order: header, meta, segments, crc.  A segment of
        ``SCATTER_MIN`` bytes or more is handed on as the object it
        is; shorter neighbours are joined, since below that size a
        memcpy costs less than one more CRC call and iovec entry (a
        frame without a long segment is one buffer)."""
        with section("wire.encode"):
            return self._encode_parts()

    def _encode_parts(self) -> list[bytes]:
        payload = Encoder()
        codec = WIRE_CODECS.get(self.type)
        try:
            if codec is not None:
                kind = KIND_TYPED
                codec[0](payload, self.data)
            else:
                kind = KIND_VALUE
                payload.value(self.data)
        except (DencError, TypeError, OverflowError) as denc_err:
            # escape hatch: a payload the denc codecs (typed OR
            # generic) cannot express falls back to json -- best
            # effort, since json's data model is a subset; if json
            # can't carry it either, the original error surfaces
            try:
                blob = json.dumps(self.data).encode()
            except (TypeError, ValueError):
                raise denc_err
            kind = KIND_JSON
            payload = Encoder()
            payload.blob(blob)
        enc = Encoder()
        enc.start(2, 1)
        enc.string(self.type)
        enc.u64(self.seq)
        enc.string(self.from_name)
        enc.u8(kind)
        enc.blob(payload.bytes())
        enc.list([len(s) for s in self.segments], Encoder.u32)
        enc.u64(self.ack_seq)
        enc.u8(self.flags)
        enc.finish()
        mb = enc.bytes()
        parts = [MAGIC + struct.pack("<I", len(mb))]
        # the crc register carries on from part to part: the word the
        # receiver gets from one pass over meta + segments
        crc = 0xFFFFFFFF
        run = [mb]
        with section("wire.crc"):
            for seg in self.segments:
                if len(seg) < SCATTER_MIN:
                    run.append(seg)
                    continue
                if run:
                    parts.append(b"".join(run))
                    crc = crc32c(parts[-1], crc)
                    run = []
                if type(seg) is not bytes:
                    # the transport may hold a view of a part after
                    # the send returns: only of what cannot change
                    seg = bytes(seg)
                parts.append(seg)
                crc = crc32c(seg, crc)
            if run:
                parts.append(b"".join(run))
                crc = crc32c(parts[-1], crc)
        parts.append(struct.pack("<I", crc & 0xFFFFFFFF))
        if len(parts) == 3:
            # header, one joined run, crc: no segment worth an iovec
            # entry, so the frame leaves as one small buffer
            return [b"".join(parts)]
        return parts

    @classmethod
    def decode(cls, buf: bytes) -> "Message":
        """One whole plain frame in one buffer."""
        return cls.decode_parts(memoryview(buf), _NO_BYTES)

    @classmethod
    def decode_parts(cls, head: memoryview,
                     rest: memoryview) -> "Message":
        """One whole plain frame whose bytes are ``head`` then
        ``rest`` (``FrameReader`` hands them over so; the meta lies
        in ``head``).  The crc is checked over the views where they
        lie and each segment leaves as ``bytes`` with one copy."""
        with section("wire.decode"):
            return cls._decode_parts(head, rest)

    @classmethod
    def _decode_parts(cls, head: memoryview,
                      rest: memoryview) -> "Message":
        total = len(head) + len(rest)
        if total < 12 or head[:4] != MAGIC:
            raise ValueError("bad magic")
        (meta_len,) = struct.unpack_from("<I", head, 4)
        off = 8 + meta_len
        if off > len(head) or off + 4 > total:
            raise ValueError("truncated frame")
        (crc,) = struct.unpack(
            "<I", b"".join(_span(head, rest, total - 4, total)))
        with section("wire.crc"):
            got = 0xFFFFFFFF
            for view in _span(head, rest, 8, total - 4):
                got = crc32c(view, got)
            if (got & 0xFFFFFFFF) != crc:
                raise ValueError("frame crc mismatch")
        mtype, seq, from_name, data, seg_lens, ack_seq, flags = \
            _decode_meta(head[8:off])
        if off + sum(seg_lens) + 4 != total:
            raise ValueError("segment lengths do not fill the frame")
        segments = []
        for ln in seg_lens:
            segments.append(b"".join(_span(head, rest, off, off + ln)))
            off += ln
        return cls(type=mtype, data=data, segments=segments,
                   seq=seq, from_name=from_name, ack_seq=ack_seq,
                   flags=flags)


_NO_BYTES = memoryview(b"")


def _span(head: memoryview, rest: memoryview, a: int,
          b: int) -> tuple:
    """Views of bytes [a, b) of a frame stored as ``head`` then
    ``rest``."""
    n = len(head)
    if b <= n:
        return (head[a:b],)
    if a >= n:
        return (rest[a - n:b - n],)
    return (head[a:], rest[:b - n])


def _decode_meta(mb) -> tuple:
    dec = Decoder(mb)
    struct_v = dec.start(2)
    mtype = dec.string()
    seq = dec.u64()
    from_name = dec.string()
    kind = dec.u8()
    payload = dec.blob()
    seg_lens = dec.list(Decoder.u32)
    ack_seq = flags = 0
    if struct_v >= 2:
        ack_seq = dec.u64()
        flags = dec.u8()
    dec.finish()
    if kind == KIND_TYPED:
        codec = WIRE_CODECS.get(mtype)
        if codec is None:
            raise ValueError(f"typed payload for unknown type {mtype}")
        data = codec[1](Decoder(payload))
    elif kind == KIND_VALUE:
        data = Decoder(payload).value()
    elif kind == KIND_JSON:
        data = json.loads(Decoder(payload).blob())
    else:
        raise ValueError(f"bad meta kind {kind}")
    return mtype, seq, from_name, data, seg_lens, ack_seq, flags


COMP_MAGIC = b"CTvC"     # on-wire compressed frame (compression_onwire)
SEC_MAGIC = b"CTvE"      # AES-GCM encrypted frame (crypto_onwire secure mode)
COMPRESS_THRESHOLD = 1024
# a plain frame may carry meta and segments EACH up to MAX_FRAME; the
# wrapped paths must accept at least that (a tighter cap would reject
# on receive a frame the sender legally built -> teardown/replay loop)
MAX_WRAPPED = 2 * MAX_FRAME + 65536
OFFLOAD_THRESHOLD = 1 << 20     # executor offload for >1 MiB transforms


def _parse_plain(buf: bytes) -> bytes:
    if buf[:4] != MAGIC:
        raise ValueError("bad magic")
    return buf


def wrap_frame(buf: bytes, compressor=None, aead=None) -> bytes:
    """Apply the connection's negotiated on-wire transforms.

    compress-then-encrypt, as ProtocolV2 layers compression inside the
    secure session (compression_onwire.cc / crypto_onwire.cc); the
    compressed form is only used when it actually shrinks the frame.
    """
    if compressor is not None and len(buf) > COMPRESS_THRESHOLD:
        comp = compressor.compress(buf)
        if len(comp) < len(buf):
            buf = (COMP_MAGIC + struct.pack("<II", len(buf), len(comp))
                   + comp)
    if aead is not None:
        import os as _os
        nonce = _os.urandom(12)
        ct = aead.encrypt(nonce, buf, b"")
        buf = SEC_MAGIC + struct.pack("<I", len(ct)) + nonce + ct
    return buf


def unwrap_frame(buf: bytes, compressor=None) -> bytes:
    """Undo COMP wrapping of an in-memory frame (post-decryption)."""
    if buf[:4] == COMP_MAGIC:
        raw_len, comp_len = struct.unpack_from("<II", buf, 4)
        if raw_len > MAX_WRAPPED:
            raise ValueError("oversized compressed frame")
        if compressor is None:
            raise ValueError("compressed frame on a plain connection")
        try:
            # bounded: output capped at the declared raw_len so a
            # bomb frame fails before materializing, not after
            out = compressor.decompress(buf[12:12 + comp_len],
                                        max_length=raw_len)
        except Exception as e:
            # corrupt input must look like any other framing error so
            # the protocol's abort -> reconnect/teardown path handles it
            raise ValueError(f"frame decompress failed: {e}") from e
        if len(out) != raw_len:
            raise ValueError("compressed frame length mismatch")
        return _parse_plain(out)
    return _parse_plain(buf)


def decrypt_frame(buf: bytes, aead) -> bytes:
    """The frame inside one whole ``SEC_MAGIC`` frame (which may in
    turn be a compressed one: ``unwrap_frame`` is next)."""
    nonce, ct = buf[8:20], buf[20:]
    try:
        return aead.decrypt(nonce, ct, b"")
    except ValueError:
        raise
    except Exception as e:
        raise ValueError(f"frame decrypt failed: {e}") from e


def frame_need(head: memoryview, secure: bool) -> tuple[int, bool]:
    """How many bytes the frame that starts at ``head[0]`` needs
    before more can be said about it, and whether that count is the
    whole frame.  ``head`` holds 8 bytes or more.  Every length is
    checked here, before anything is allocated for the frame."""
    magic = head[:4]
    if secure and magic != SEC_MAGIC:
        # a secure connection must never accept plaintext: an injected
        # cleartext frame would bypass the channel's authentication
        raise ValueError("plaintext frame on a secure connection")
    (n,) = struct.unpack_from("<I", head, 4)
    if magic == SEC_MAGIC:
        if not secure:
            raise ValueError("encrypted frame on a plain connection")
        if n > MAX_WRAPPED:
            raise ValueError("oversized encrypted frame")
        return 20 + n, True
    if magic == COMP_MAGIC:
        if len(head) < 12:
            return 12, False
        (comp_len,) = struct.unpack_from("<I", head, 8)
        if max(n, comp_len) > MAX_WRAPPED:
            raise ValueError("oversized compressed frame")
        return 12 + comp_len, True
    if magic != MAGIC:
        raise ValueError("bad magic")
    if n > MAX_FRAME:
        raise ValueError("oversized meta")
    if len(head) < 8 + n:
        return 8 + n, False
    total_segs = sum(_meta_seg_lens(head[8:8 + n]))
    if total_segs > MAX_FRAME:
        raise ValueError("oversized frame")
    return 8 + n + total_segs + 4, True


RECV_BUF = 64 << 10
# which tail buffers a messenger keeps for its next long frames, and
# how many: at most SPARE_COUNT * SPARE_MAX bytes lie idle
SPARE_MIN, SPARE_MAX, SPARE_COUNT = 64 << 10, 8 << 20, 4
# a frame big enough for the executor never lies whole in the receive
# buffer, so nothing is parsed behind it while it is being decrypted
assert RECV_BUF < OFFLOAD_THRESHOLD


class FrameReader:
    """The receive side of a socket, without the socket: hands out
    the buffer the next bytes go into and finds the frames in what
    has arrived.

    Bytes land in a receive buffer of ``RECV_BUF`` bytes, and every
    frame that lies whole in it is handed to ``on_frame(head, rest)``
    in the same call, ``rest`` empty.  A frame that reaches past what
    has arrived gets a tail buffer (a spare one, or a new one) of
    which exactly its missing bytes are offered, so the socket fills
    it without reading into the next frame; it is handed over as the
    two views.  Payload bytes are therefore never
    moved between buffers here: the one copy is ``decode_parts``
    making ``bytes`` of each segment.

    Before ``start_frames`` the bytes are the handshake's, taken with
    ``take``."""

    def __init__(self, on_frame, spare: list[bytearray]) -> None:
        self.on_frame = on_frame
        # tail buffers that are free again, shared by the readers of
        # one messenger: a fresh multi-megabyte bytearray costs a
        # zero-fill and its page faults, a used one nothing
        self.spare = spare
        self.secure = False
        self._framed = False
        # the receive buffer exists while it holds bytes or a read is
        # under way: an idle connection keeps none
        self._buf: bytearray | None = None
        self._lo = self._hi = 0          # unparsed bytes of _buf
        self._rest: bytearray | None = None
        self._rest_len = 0               # bytes of _rest the frame needs
        self._rest_n = 0                 # bytes of them that arrived

    def get_buffer(self) -> memoryview:
        if self._rest is not None:
            return memoryview(self._rest)[self._rest_n:self._rest_len]
        if self._buf is None:
            self._buf = bytearray(RECV_BUF)
        return memoryview(self._buf)[self._hi:]

    def buffer_updated(self, nbytes: int) -> None:
        if self._rest is None:
            self._hi += nbytes
            if self._framed:
                self._parse()
            return
        self._rest_n += nbytes
        if self._rest_n == self._rest_len:
            head = memoryview(self._buf)[self._lo:self._hi]
            tail, self._rest = self._rest, None
            self._buf = None
            self._lo = self._hi = 0
            with memoryview(tail) as whole, whole[:self._rest_len] as rest:
                self.on_frame(head, rest)
            if SPARE_MIN <= len(tail) <= SPARE_MAX \
                    and len(self.spare) < SPARE_COUNT:
                self.spare.append(tail)

    def take(self, n: int) -> bytes | None:
        """``n`` handshake bytes, or None until they have arrived."""
        if n > RECV_BUF:
            raise ValueError("oversized handshake")
        if self._hi - self._lo < n:
            return None
        if not n:
            return b""
        out = bytes(self._buf[self._lo:self._lo + n])
        self._lo += n
        if self._lo == self._hi:
            self._buf = None
            self._lo = self._hi = 0
        return out

    def start_frames(self, secure: bool) -> None:
        """The handshake is over: what has arrived since, and all that
        follows, is frames."""
        self.secure = secure
        self._framed = True
        self._parse()

    def _parse(self) -> None:
        while self._hi > self._lo:
            avail = self._hi - self._lo
            view = memoryview(self._buf)[self._lo:self._hi]
            need, whole = (8, False) if avail < 8 \
                else frame_need(view, self.secure)
            if avail >= need:
                self._lo += need
                self.on_frame(view[:need], _NO_BYTES)
            elif whole:
                self._rest_len = need - avail
                self._rest_n = 0
                self._rest = self._tail_buffer(self._rest_len)
                return
            else:
                self._make_room(need)
                return
        self._buf = None
        self._lo = self._hi = 0

    def _tail_buffer(self, n: int) -> bytearray:
        for i, buf in enumerate(self.spare):
            if len(buf) >= n:
                return self.spare.pop(i)
        return bytearray(n)

    def _make_room(self, need: int) -> None:
        """A header or a meta envelope that is still short moves to
        the front of a buffer that can hold all of it."""
        if self._lo + need <= len(self._buf):
            return
        short = self._buf[self._lo:self._hi]
        self._buf = bytearray(max(need, RECV_BUF))
        self._buf[:len(short)] = short
        self._lo, self._hi = 0, len(short)


def _meta_seg_lens(mb: memoryview) -> list[int]:
    """Just the segment lengths from a meta envelope (what
    ``frame_need`` sizes the rest of the frame by)."""
    dec = Decoder(mb)
    dec.start(2)
    dec.string()        # t
    dec.u64()           # seq
    dec.string()        # from
    dec.u8()            # kind
    dec._take(dec.u32())    # skip payload without materializing it
    lens = dec.list(Decoder.u32)
    dec.finish()
    return lens
