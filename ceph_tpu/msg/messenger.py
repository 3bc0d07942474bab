"""Asyncio messenger with lossless-client reconnect semantics.

Responsibilities mirrored from the reference's AsyncMessenger
(src/msg/async/AsyncMessenger.h:74): bind/accept, connect-by-address with
connection caching, ordered per-connection delivery with sequence numbers,
resend of unacked messages after reconnect (lossless policy,
src/msg/Policy.h), dispatcher fan-out, and an HMAC-SHA256 session
handshake standing in for cephx (src/auth/cephx) in crc mode.
"""

from __future__ import annotations

import asyncio
import hashlib
import hmac
import json
import os
import struct
import time
from collections import deque
from typing import Awaitable, Callable

from ..common.perf import PerfCounters
from ..common.throttle import injector as _fault
from ..common.tracing import install_loop_probe, section
from .message import (COMP_MAGIC, FLAG_ACK_NOW, MAGIC, OFFLOAD_THRESHOLD,
                      SEC_MAGIC, FrameReader, Message, decrypt_frame,
                      unwrap_frame, wrap_frame)

Dispatcher = Callable[["Connection", Message], Awaitable[None]]

HELLO_MAGIC = b"CTHL"
HELLO_ACCEPTS_TICKETS = 0x01     # server can validate cephx tickets
HELLO_REQUIRES_TICKET = 0x02     # server will NACK ticketless peers

# flow-control policy (src/msg/Policy.h throttler analog): senders block
# in send() once the unacked window exceeds the messenger's
# max_unacked_msgs/max_unacked_bytes instead of growing without bound,
# and receivers confirm what was delivered.  The confirmation rides in
# the envelope of every frame that leaves the connection anyway
# (``ack_seq``: a reply, a ping, the next request; msgr2's
# ceph_msg_header2.ack_seq); a frame of its own (ACK_TYPE) leaves only
# when nothing else will: after ack_every frames or ack_bytes bytes
# unconfirmed, for a frame whose sender says it is short of window
# (FLAG_ACK_NOW), or when the connection has sent nothing for
# ACK_IDLE_S.
ACK_EVERY = 64
ACK_BYTES = 8 << 20
# What the idle deadline bounds is memory and replay, not a round trip
# (nobody waits for an ack but a sender out of window, and it says so):
# the frames a sender keeps referenced in ``unacked`` and would replay
# after a reconnect, per connection at most ack_bytes.  It outlasts a
# reply that is on its way and the gap between two heartbeats, so that
# those carry the confirmation.
ACK_IDLE_S = 1.0
ACK_TYPE = "__ack"

# per-peer sub-op coalescing (the PR-12 write pipeline): concurrent
# ops' sub-writes bound for the same peer inside one flush window ride
# ONE framed message instead of one send per shard -- one seq, one
# frame header, one syscall, one receive callback.  The receiver
# unpacks and dispatches the sub-messages in staging order, so
# per-peer FIFO (what keeps replica logs in version order) is exactly
# as strong as the unbatched path.
SUBOP_BATCH_TYPE = "__subop_batch"


class FrameProtocol(asyncio.BufferedProtocol):
    """One socket of the messenger, both directions.

    Receive: the transport reads straight into the ``FrameReader``'s
    buffers (``recv_into``); every frame that has arrived whole is
    checked, decoded and delivered inside that one callback, under
    ``wire.recv``.  Send: a frame's buffers go to the transport as a
    list (``writelines``: the selector transport keeps them as views
    and sends them with ``sendmsg``), and ``drain`` holds a sender back
    while the transport's buffer is over its high-water mark.  Until
    ``start_frames`` the socket is the handshake's: ``read_exactly``
    and ``write``."""

    def __init__(self, messenger: "Messenger", on_accept=None) -> None:
        self.messenger = messenger
        self._on_accept = on_accept      # server side: the handshake
        self.conn: Connection | None = None
        self.transport: asyncio.Transport | None = None
        self.reader = FrameReader(self._on_frame, messenger._rx_spare)
        self._lost = False
        self._write_paused = False
        # one future each: the handshake waiting for bytes, senders
        # waiting for the transport's buffer to drain
        self._readable: asyncio.Future | None = None
        self._drained: asyncio.Future | None = None

    # -- transport callbacks ------------------------------------------------
    def connection_made(self, transport) -> None:
        self.transport = transport
        if self._on_accept is not None:
            self.messenger._spawn(self._on_accept(self))

    def get_buffer(self, sizehint: int) -> memoryview:
        return self.reader.get_buffer()

    def buffer_updated(self, nbytes: int) -> None:
        with section("wire.recv"):
            try:
                self.reader.buffer_updated(nbytes)
            except ValueError:
                # bad magic, length, crc or envelope: the stream cannot
                # be trusted from here on
                self.transport.abort()
        self._wake(self._readable)

    def connection_lost(self, exc) -> None:
        self._lost = True
        self._wake(self._readable)
        self._wake(self._drained)
        if self.conn is not None:
            self.messenger._socket_lost(self.conn, self)

    def pause_writing(self) -> None:
        self._write_paused = True

    def resume_writing(self) -> None:
        self._write_paused = False
        self._wake(self._drained)

    @staticmethod
    def _wake(fut: asyncio.Future | None) -> None:
        if fut is not None and not fut.done():
            fut.set_result(None)

    # -- the handshake's reads and every write ------------------------------
    async def read_exactly(self, n: int) -> bytes:
        while True:
            data = self.reader.take(n)
            if data is not None:
                return data
            if self._lost:
                raise ConnectionResetError("closed during the handshake")
            self._readable = asyncio.get_event_loop().create_future()
            await self._readable

    def write(self, parts: list[bytes]) -> None:
        """Hand one frame's (or the handshake's) buffers to the
        transport.  On a socket that is closing they are dropped, as
        ``transport.write`` drops them; ``drain`` then raises."""
        if self.transport.is_closing():
            return
        with section("wire.write"):
            if len(parts) == 1:
                self.transport.write(parts[0])
            else:
                self.transport.writelines(parts)

    async def drain(self) -> None:
        if self.transport.is_closing():
            await asyncio.sleep(0)       # let connection_lost run
        while not self._lost and self._write_paused:
            if self._drained is None or self._drained.done():
                self._drained = asyncio.get_event_loop().create_future()
            await self._drained
        if self._lost:
            raise ConnectionResetError("connection lost")

    def start_frames(self, conn: "Connection") -> None:
        """The handshake is done and ``conn`` rides this socket: frames
        from here on (and whatever arrived behind the handshake)."""
        self.conn = conn
        try:
            self.reader.start_frames(secure=conn.aead_rx is not None)
        except ValueError:
            self.transport.abort()

    # -- frames in ------------------------------------------------------------
    def _on_frame(self, head: memoryview, rest: memoryview) -> None:
        nbytes = len(head) + len(rest)
        magic = head[:4]
        if magic == MAGIC:
            self._deliver(Message.decode_parts(head, rest), nbytes, 0)
            return
        buf = b"".join((head, rest))
        if magic == SEC_MAGIC and nbytes > OFFLOAD_THRESHOLD:
            # big decrypts off the event loop: heartbeats must not
            # stall behind a multi-MB AES pass.  Nothing is read
            # meanwhile, so frames still arrive in order.
            self.transport.pause_reading()
            self.messenger._spawn(self._decrypt_off_loop(buf))
            return
        copied = nbytes
        if magic == SEC_MAGIC:
            buf = decrypt_frame(buf, self.conn.aead_rx)
            copied += len(buf)
        self._deliver_wrapped(buf, nbytes, copied)

    async def _decrypt_off_loop(self, buf: bytes) -> None:
        try:
            inner = await asyncio.get_event_loop().run_in_executor(
                None, decrypt_frame, buf, self.conn.aead_rx)
            if self.transport.is_closing():
                return           # the socket went meanwhile: the replay
            self._deliver_wrapped(inner, len(buf), len(buf) + len(inner))
        except ValueError:
            self.transport.abort()
            return
        self.transport.resume_reading()

    def _deliver_wrapped(self, buf: bytes, nbytes: int,
                         copied: int) -> None:
        if buf[:4] == COMP_MAGIC:
            buf = unwrap_frame(buf, self.conn.compressor)
            copied += len(buf)
        self._deliver(Message.decode(buf), nbytes, copied)

    def _deliver(self, msg: Message, nbytes: int, copied: int) -> None:
        perf = self.messenger.perf
        perf.inc("rx_frames")
        perf.inc("rx_bytes", nbytes)
        perf.inc("rx_copied_bytes",
                 copied + sum(map(len, msg.segments)))
        with section("wire.deliver"):
            self.messenger._frame_in(self.conn, msg, nbytes)


class Connection:
    def __init__(self, messenger: "Messenger", peer_name: str,
                 proto: "FrameProtocol", *, outgoing: bool,
                 peer_addr: tuple[str, int] | None = None) -> None:
        self.messenger = messenger
        self.peer_name = peer_name
        # the socket this connection rides now; a reconnect swaps in
        # another
        self.proto = proto
        self.outgoing = outgoing
        self.peer_addr = peer_addr
        self.out_seq = 0
        self.in_seq = 0
        self.unacked: deque[tuple[Message, int]] = deque()  # (msg, nbytes)
        self.unacked_bytes = 0
        self.acked_seq = 0           # peer-confirmed delivery watermark
        # receive side: delivered since a frame last left with in_seq
        self._ack_pending_msgs = 0
        self._ack_pending_bytes = 0
        self.closed = False
        self.generation = 0          # bumped per successful reconnect
        # negotiated on-wire transforms (ProtocolV2 compression_onwire
        # / crypto_onwire secure mode); set right after the handshake.
        # PER-DIRECTION AEAD keys: one shared key would let a recorded
        # client frame be reflected back to it as "authentic"
        self.compressor = None
        self.aead_tx = None
        self.aead_rx = None
        self._send_lock = asyncio.Lock()
        self._reconnect_lock = asyncio.Lock()
        self._window_open = asyncio.Event()
        self._window_open.set()
        # the idle deadline: when the oldest delivery no frame has
        # confirmed is due one of its own (loop time), and the one
        # handle that looks; a frame that leaves resets the count and
        # touches neither
        self._ack_due = 0.0
        self._ack_timer: asyncio.TimerHandle | None = None

    @property
    def writer(self) -> asyncio.Transport:
        """The write half of the current socket."""
        return self.proto.transport

    def _window_full(self) -> bool:
        m = self.messenger
        return (len(self.unacked) >= m.max_unacked_msgs
                or self.unacked_bytes >= m.max_unacked_bytes)

    def _trim_acked(self, seq: int) -> bool:
        """The peer has everything up to ``seq`` (cumulative and
        monotonic: a confirmation that was lost is covered by the
        next).  True if that was news."""
        if seq <= self.acked_seq:
            return False
        self.acked_seq = seq
        while self.unacked and self.unacked[0][0].seq <= seq:
            _, nbytes = self.unacked.popleft()
            self.unacked_bytes -= nbytes
        if not self._window_full():
            self._window_open.set()
        return True

    async def send(self, msg: Message) -> None:
        if msg.type == ACK_TYPE:
            raise ValueError(f"{ACK_TYPE} is a reserved control frame type")
        faults = self.messenger.faults
        if faults is not None:
            fd = faults.on_send(self.messenger.name, self.peer_name,
                                msg.type)
            if fd.drop:
                return           # vanished on the wire (chaos drop)
            if fd.delay > 0:
                await asyncio.sleep(fd.delay)
            for _ in range(fd.copies - 1):
                # duplicates take fresh seqs so the receiver's replay
                # dedup does NOT absorb them -- handler idempotency is
                # exactly what the duplication fault probes
                await self._send_one(Message(msg.type, dict(msg.data),
                                             segments=list(msg.segments)))
        await self._send_one(msg)

    async def _send_one(self, msg: Message) -> None:
        while True:
            # window wait OUTSIDE the lock: _reconnect needs _send_lock
            # for the writer swap+replay, and the acks that reopen the
            # window need the reconnected stream -- a sender parked
            # here while holding the lock would deadlock the pair.
            st = await self._send_locked(msg)
            if st == "sent":
                return
            if st == "reconnect":
                # outside the send lock: _reconnect takes it for the
                # writer swap + replay, so the replayed frames cannot
                # interleave with other senders' writes
                await self.messenger._reconnect(self)
                return          # msg is in unacked; the replay sent it
            self._window_open.clear()
            await self._window_open.wait()
            if self.closed:
                raise ConnectionError(f"{self.peer_name} closed")

    async def _send_locked(self, msg: Message) -> str:
        """One locked send attempt: "sent" | "reconnect" | "window"
        ("window" = flow-control window full, caller waits UNLOCKED
        and retries -- K queued senders re-check here so they cannot
        overshoot the window by K-1)."""
        async with self._send_lock:
            if self.closed:
                raise ConnectionError(f"{self.peer_name} closed")
            if self._window_full():
                return "window"
            self.out_seq += 1
            msg.seq = self.out_seq
            msg.from_name = self.messenger.name
            self._stamp(msg, 1, sum(map(len, msg.segments)))
            wraps = self._wraps()
            if wraps:
                buf = msg.encode()
                nbytes = len(buf)
            else:
                parts = msg.encode_parts()
                nbytes = sum(map(len, parts))
            self.unacked.append((msg, nbytes))
            self.unacked_bytes += nbytes
            if wraps:
                if nbytes > OFFLOAD_THRESHOLD:
                    # multi-MB compress/encrypt off the event loop so
                    # heartbeat handling doesn't stall behind it;
                    # ordering is preserved -- we still hold the send
                    # lock, and a reconnect cannot swap the socket or
                    # renegotiate keys under us because its
                    # swap+replay also requires the send lock.
                    wire = await asyncio.get_event_loop().run_in_executor(
                        None, wrap_frame, buf, self.compressor,
                        self.aead_tx)
                    if self.closed:
                        raise ConnectionError(f"{self.peer_name} closed")
                else:
                    wire = wrap_frame(buf, self.compressor, self.aead_tx)
                parts = [wire]
                self.messenger.perf.inc("tx_frames_joined")
            if _fault.check("ms_inject_socket_failures"):
                # chaos: drop the transport mid-send; the lossless
                # reconnect+replay machinery must absorb it
                # (ms_inject_socket_failures, qa msgr-failures suites)
                self.writer.close()
            try:
                self._write_frame(parts)
                await self.proto.drain()
                return "sent"
            except (ConnectionError, OSError):
                if not self.outgoing:
                    await self.close()
                    raise
                return "reconnect"

    def _wraps(self) -> bool:
        """This connection compresses or encrypts, so a frame has to be
        one buffer before it leaves."""
        return self.compressor is not None or self.aead_tx is not None

    def _stamp(self, msg: Message, more_msgs: int = 0,
               more_bytes: int = 0) -> None:
        """``msg`` is about to be encoded for this connection: it
        carries what we have received, so nothing is left for a frame
        of its own to confirm; and it asks to be confirmed at once
        where ``unacked`` (with what is about to join it) is at or
        past half of the flow-control window, since no cadence of the
        peer's knows our window."""
        m = self.messenger
        short = (2 * (len(self.unacked) + more_msgs) >= m.max_unacked_msgs
                 or 2 * (self.unacked_bytes + more_bytes)
                 >= m.max_unacked_bytes)
        msg.flags = FLAG_ACK_NOW if short else 0
        msg.ack_seq = self.in_seq
        self._ack_pending_msgs = 0
        self._ack_pending_bytes = 0

    def _disarm_ack(self) -> None:
        if self._ack_timer is not None:
            self._ack_timer.cancel()
            self._ack_timer = None

    def _frame_parts(self, msg: Message) -> list[bytes]:
        """The buffers of one frame as this connection sends it now."""
        self._stamp(msg)
        if not self._wraps():
            return msg.encode_parts()
        self.messenger.perf.inc("tx_frames_joined")
        return [wrap_frame(msg.encode(), self.compressor, self.aead_tx)]

    def _write_frame(self, parts: list[bytes]) -> None:
        perf = self.messenger.perf
        perf.inc("tx_frames")
        perf.inc("tx_bytes", sum(map(len, parts)))
        self.proto.write(parts)

    def _note_delivered(self, nbytes: int, now: bool) -> None:
        """Receive side: one more frame the next frame out will
        confirm.  A frame of its own confirms it only at the cadence
        that bounds what the sender retains, for a sender short of
        window (``now``), or once nothing has left for ACK_IDLE_S (a
        lost confirmation is covered by the next one or by the
        reconnect handshake)."""
        self._ack_pending_msgs += 1
        self._ack_pending_bytes += nbytes
        if (self._ack_pending_msgs >= self.messenger.ack_every
                or self._ack_pending_bytes >= self.messenger.ack_bytes):
            self._flush_ack("cadence")
        elif now:
            self._flush_ack("window")
        elif self._ack_pending_msgs == 1:
            loop = asyncio.get_running_loop()
            self._ack_due = loop.time() + ACK_IDLE_S
            if self._ack_timer is None:
                self._ack_timer = loop.call_at(self._ack_due,
                                               self._ack_idle)

    def _ack_idle(self) -> None:
        """The handle fired: confirm what has waited out the deadline,
        or look again when the oldest delivery still unconfirmed will
        have (a frame that left meanwhile moved the deadline without
        touching the handle: one timer a quiet second, not one a
        frame)."""
        self._ack_timer = None
        if self.closed or not self._ack_pending_msgs:
            return
        loop = asyncio.get_running_loop()
        if loop.time() >= self._ack_due:
            self._flush_ack("idle")
        else:
            self._ack_timer = loop.call_at(self._ack_due, self._ack_idle)

    def _flush_ack(self, why: str) -> None:
        perf = self.messenger.perf
        perf.inc("tx_acks")
        perf.inc("tx_acks_" + why)
        ack = Message(ACK_TYPE, {"seq": self.in_seq})
        ack.from_name = self.messenger.name
        self._write_frame(self._frame_parts(ack))

    async def _resend_unacked(self) -> None:
        """A reconnect's replay: each frame leaves with the watermark
        and the window as they are now."""
        for msg, _ in list(self.unacked):
            self._write_frame(self._frame_parts(msg))
        await self.proto.drain()

    async def close(self) -> None:
        self.closed = True
        self._window_open.set()      # wake throttled senders to error out
        self._disarm_ack()
        self.writer.close()


def pack_subop_batch(msgs: list[Message]) -> Message:
    """Fold staged sub-op messages into ONE framed flush: metas carry
    each sub-message's (type, data, segment count); the segment lists
    concatenate in order.  Seq/ack/replay accounting all happen on the
    outer frame -- a reconnect replays the whole flush, the receiver
    dedups it as one unit, and unpacking restores staging order."""
    metas = [{"t": m.type, "d": m.data, "n": len(m.segments)}
             for m in msgs]
    segments: list[bytes] = []
    for m in msgs:
        segments.extend(m.segments)
    return Message(SUBOP_BATCH_TYPE, {"metas": metas},
                   segments=segments)


def unpack_subop_batch(msg: Message) -> list[Message]:
    out: list[Message] = []
    off = 0
    for meta in msg.data.get("metas", []):
        n = int(meta.get("n", 0))
        sub = Message(meta["t"], meta["d"],
                      segments=list(msg.segments[off:off + n]))
        off += n
        sub.seq = msg.seq            # dedup identity is the frame's
        sub.from_name = msg.from_name
        out.append(sub)
    return out


class SubOpPipe:
    """Per-peer sub-op coalescing with a flush window.

    ``stage()`` parks an outbound message (synchronously -- staging
    order IS the wire order, which is what keeps replica logs applied
    in version order) on its peer's queue; ONE ship worker per peer
    drains that queue, coalescing everything staged since its last
    cycle into one ``pack_subop_batch`` frame.  The flush window is
    an event-loop pass (the codec batcher's Nagle-off discipline) --
    and under backpressure it widens NATURALLY: while a ship is in
    flight the queue keeps growing, and the next cycle carries the
    whole backlog in one frame.

    Per-peer workers are a liveness requirement, not an optimization:
    a single drain loop awaiting sends inline lets one dead peer's
    reconnect backoff head-of-line-block every other peer's commits
    -- observed at 64 OSDs as cluster-wide wedged ops the moment one
    OSD died.  A slow peer now stalls only its own queue, and a send
    failure fails exactly that peer's staged ``on_error`` hooks (the
    op layer sees the same per-send errors as the unbatched path).

    With a fault injector attached, messages ship INDIVIDUALLY: the
    injector's drop/delay/dup rules key on the logical message type,
    and hiding sub-ops inside a batch frame would blind the chaos
    harness to them (the kill-mid-pipeline tests depend on per-subop
    fault fidelity).
    """

    def __init__(self, messenger: Messenger, *,
                 flush_window: float = 0.002, perf=None) -> None:
        self.messenger = messenger
        self.flush_window = float(flush_window)
        self.perf = perf
        # peer -> deque of (addr, msg, on_error)
        self._peer_q: dict[str, deque] = {}
        self._peer_tasks: dict[str, asyncio.Task] = {}
        # peer -> a ship cycle is running (inline flush_now or the
        # worker task); the flag is the one-shipper-per-peer mutex
        # that keeps frames in staging order
        self._busy: dict[str, bool] = {}
        self._n_staged = 0
        self.closed = False

    def stage(self, addr: tuple[str, int], peer_name: str,
              msg: Message, on_error=None) -> None:
        """Park one sub-op send; the peer's ship worker flushes it.

        Shipping ALWAYS happens on the worker task, never inline in
        the staging caller: the op path stages while holding its PG
        lock, and an inline send to a dead peer would hold that lock
        across the reconnect backoff (the degraded-phase collapse the
        pipeline exists to prevent)."""
        if self.closed:
            raise ConnectionError("subop pipe closed")
        q = self._peer_q.setdefault(peer_name, deque())
        q.append((tuple(addr), msg, on_error))
        self._n_staged += 1
        if self._busy.get(peer_name):
            return               # the live ship cycle carries it
        t = self._peer_tasks.get(peer_name)
        if t is None or t.done():
            self._peer_tasks[peer_name] = asyncio.ensure_future(
                self.arm_flush_window(peer_name))

    async def arm_flush_window(self, peer: str) -> None:
        """The ship worker (one per peer, retires when the queue
        drains; ``stage`` re-arms).  One coalescing pass first:
        every already-runnable co-submitter stages during it."""
        if self._busy.get(peer):
            return
        try:
            if self.flush_window > 0:
                await asyncio.sleep(0)   # co-submitters stage here
            await self._ship_loop(peer)
        except asyncio.CancelledError:
            if not self._busy.get(peer):
                await self._ship_loop(peer)   # shutdown: ship now

    async def _ship_loop(self, peer: str) -> None:
        """Ship until the peer's queue drains.  Sole shipper: the
        _busy flag serializes cycles, so frames leave in staging
        order even when flush_now and the worker race."""
        q = self._peer_q.get(peer)
        if q is None:
            return
        self._busy[peer] = True
        try:
            while q:
                await self._ship_queued(peer, q)
        finally:
            self._busy[peer] = False

    async def _ship_queued(self, peer: str, q: deque) -> None:
        if not q:
            return
        entries = list(q)
        q.clear()
        self._n_staged -= len(entries)
        if self.perf is not None:
            self.perf.inc("flush_windows")
        addr = entries[0][0]
        msgs = [m for _, m, _ in entries]
        try:
            if len(msgs) == 1 or self.messenger.faults is not None:
                for a, m, _ in entries:
                    await self.messenger.send(a, peer, m)
            else:
                await self.messenger.send(addr, peer,
                                          pack_subop_batch(msgs))
                if self.perf is not None:
                    self.perf.inc("coalesced_subops", len(msgs))
        except (ConnectionError, OSError) as e:
            for _, _, on_error in entries:
                if on_error is not None:
                    on_error(e)

    async def close(self) -> None:
        """Ship anything parked, then refuse further staging -- a
        staged sub-op may never outlive the pipe (it would wedge the
        op awaiting its reply)."""
        self.closed = True
        for t in self._peer_tasks.values():
            t.cancel()
        self._peer_tasks.clear()
        for peer, q in list(self._peer_q.items()):
            if not self._busy.get(peer):
                await self._ship_queued(peer, q)


class Messenger:
    def __init__(self, name: str, secret: bytes | None = None, *,
                 max_unacked_msgs: int = 4096,
                 max_unacked_bytes: int = 64 << 20,
                 ack_every: int = ACK_EVERY,
                 ack_bytes: int = ACK_BYTES,
                 compression: str | None = None,
                 secure: bool = False,
                 faults=None) -> None:
        self.name = name
        self.secret = secret
        # deterministic message mangling (common/faults.py): consulted
        # on every app-level send and every delivered message; None in
        # production paths
        self.faults = faults
        # on-wire transforms this endpoint OFFERS/accepts; the server
        # picks during the handshake (ProtocolV2 negotiation)
        self.compression = compression
        self.secure = secure
        # secure mode needs a key source, but that can be the PSK OR a
        # cephx ticket/validator installed after construction; a
        # keyless endpoint that insists on secure simply refuses every
        # connection at negotiation time
        self.max_unacked_msgs = max_unacked_msgs
        self.max_unacked_bytes = max_unacked_bytes
        self.ack_every = ack_every
        self.ack_bytes = ack_bytes
        # incarnation distinguishes a restarted peer from a reconnecting
        # one (ProtocolV2's global_seq/connect_seq split): a new
        # incarnation resets the replay-dedup session, a reconnect of
        # the same incarnation resumes it
        self.incarnation = os.urandom(8).hex()
        # cephx ticket auth (composes with/replaces the static PSK,
        # src/auth/cephx/CephxProtocol.h): a CLIENT stores tickets per
        # target service in `tickets` ({"gen", "ticket", "session_key"
        # hex, "expires"}); connect() picks by the peer name's prefix
        # ("osd.3" -> tickets["osd"]) and proves the session key in
        # the handshake instead of the PSK.  A SERVER sets
        # `ticket_validator(gen, blob_hex) -> session_key bytes`
        # (raises to reject); the validated session key becomes the
        # connection secret for the proof, negotiation MAC, and
        # secure-mode AEAD keys, so a leaked PSK stops being forever
        # (round-3 review).  `require_ticket` makes the server NACK
        # peers that present no (or a bad) ticket.
        self.tickets: dict[str, dict] = {}
        self.ticket_validator = None
        self.require_ticket = False
        self.dispatchers: list[Dispatcher] = []
        # ms_fast_dispatch analog: a SYNCHRONOUS handler consulted
        # before the task-per-message dispatch path.  Returning True
        # consumes the message without spawning a task -- reply
        # messages that only resolve a tid waiter (the bulk of sub-op
        # traffic) skip a whole scheduling quantum each.  Fault
        # delays/duplicates still take the task path so chaos timing
        # semantics are unchanged.
        self.fast_dispatch = None
        # one connection per peer per DIRECTION: simultaneous cross-
        # connects between two daemons are legal and never race over a
        # shared slot (the reference arbitrates the same race with
        # ProtocolV2 global_seq; separate directions sidestep it)
        self.conns: dict[str, Connection] = {}       # outgoing, by peer
        self.conns_in: dict[str, Connection] = {}    # accepted, by peer
        # per-peer last delivered seq; survives reconnects so replayed
        # messages dedup (the lossless policy's session state)
        self._sessions: dict[str, int] = {}
        self._session_inst: dict[str, str] = {}      # peer -> incarnation
        self._connect_locks: dict[str, asyncio.Lock] = {}
        self._shutting_down = False
        self._server: asyncio.base_events.Server | None = None
        self.addr: tuple[str, int] | None = None
        self._accept_tasks: set[asyncio.Task] = set()
        # tx_frames, tx_frames_joined (sent as one buffer because the
        # connection compresses or encrypts), tx_bytes, rx_frames,
        # rx_bytes, rx_copied_bytes (bytes the receive path copied in
        # user space: on a plain connection each segment once), tx_acks
        # (ACK_TYPE frames sent: tx_acks_cadence + tx_acks_window +
        # tx_acks_idle, by what made each leave), rx_acks_carried
        # (frames other than those whose ack_seq advanced the peer's
        # watermark); a daemon adopts the set into its own collection
        self.perf = PerfCounters("msgr")
        self._rx_spare: list[bytearray] = []     # see FrameReader.spare

    # -- server -------------------------------------------------------------
    def _loop(self) -> asyncio.AbstractEventLoop:
        """The running loop, with the tracing module's probe on it
        before this messenger opens a socket there (the first
        messenger of a loop installs it; see ``install_loop_probe``)."""
        loop = asyncio.get_running_loop()
        install_loop_probe(loop)
        return loop

    async def bind(self, host: str = "127.0.0.1", port: int = 0) -> tuple[str, int]:
        self._server = await self._loop().create_server(
            lambda: FrameProtocol(self, self._on_accept), host, port)
        self.addr = self._server.sockets[0].getsockname()[:2]
        return self.addr

    def _spawn(self, coro) -> None:
        """A task this messenger owns until it ends or shuts down."""
        t = asyncio.ensure_future(coro)
        self._accept_tasks.add(t)
        t.add_done_callback(self._accept_tasks.discard)

    def add_dispatcher(self, fn: Dispatcher) -> None:
        self.dispatchers.append(fn)

    async def _on_accept(self, proto: FrameProtocol) -> None:
        """An accepted socket's handshake; the socket is closed again
        unless a connection came of it."""
        try:
            await self._accept(proto)
        finally:
            if proto.conn is None:
                proto.transport.close()

    async def _accept(self, proto: FrameProtocol) -> None:
        if self._shutting_down:
            return
        try:
            peer_name, inst, nego, hs_nonce, hs_cnonce, hs_secret = \
                await self._handshake_server_read(proto)
        except (ValueError, ConnectionError):
            return
        if self._shutting_down:      # raced shutdown during handshake
            return
        # close any stale conn from this peer BEFORE touching session
        # state: its socket must not repopulate _sessions with an
        # old seq between our reset and the in_seq snapshot below
        old = self.conns_in.get(peer_name)
        if old is not None:
            await old.close()
        if self._session_inst.get(peer_name) != inst:
            # restarted peer: fresh session, no replay dedup state
            self._session_inst[peer_name] = inst
            self._sessions.pop(peer_name, None)
        last_seq = self._sessions.get(peer_name, 0)
        try:
            nego_blob = json.dumps(nego).encode()
            proto.write([b"ACK!" + struct.pack("<Q", last_seq)
                         + struct.pack("<I", len(nego_blob)) + nego_blob])
            await proto.drain()
        except (ConnectionError, OSError):
            return
        conn = Connection(self, peer_name, proto, outgoing=False)
        self._apply_negotiation(conn, nego, hs_nonce, hs_cnonce,
                                is_server=True, secret=hs_secret)
        conn.in_seq = last_seq
        self.conns_in[peer_name] = conn
        proto.start_frames(conn)

    # -- handshake (HMAC challenge, cephx-lite) ------------------------------
    def _ticket_for(self, peer_name: str) -> dict | None:
        """The live ticket for the peer's service class, if any
        (expired tickets are dropped -- the owner refreshes)."""
        service = peer_name.split(".", 1)[0]
        t = self.tickets.get(service)
        if t is not None and t.get("expires", 0) < time.time():
            del self.tickets[service]
            return None
        return t

    def _session_keys(self, nonce: bytes, cnonce: bytes, salt: bytes,
                      secret: bytes | None = None):
        """Per-direction session keys from the full transcript: server
        nonce + CLIENT nonce + salt (a replayed server hello cannot
        force key reuse -- the client's nonce is fresh), with a
        direction label (c2s/s2c) so the two streams never share a key
        (cephx-style session key into AES-GCM, crypto_onwire.cc).
        The AEAD comes from cephx._aes: real AES-GCM when the
        optional `cryptography` wheel is present, the stdlib fallback
        otherwise (both ends of a connection share the environment in
        tests, so the negotiated mode always matches)."""
        from ..common.cephx import _aes
        secret = secret if secret is not None else self.secret
        base = nonce + cnonce + salt

        def key(label: bytes):
            return _aes(hmac.new(secret,
                                 b"ctv2-secure-" + label + base,
                                 hashlib.sha256).digest())
        return key(b"c2s"), key(b"s2c")

    def _nego_mac(self, nego: dict, nonce: bytes,
                  cnonce: bytes, secret: bytes | None = None) -> str:
        """Bind the negotiation to the shared secret: a MITM rewriting
        the plaintext nego blob (encryption downgrade) fails the MAC."""
        secret = secret if secret is not None else self.secret
        if secret is None:
            return ""
        blob = json.dumps({k: nego[k] for k in
                           ("compression", "secure", "salt")},
                          sort_keys=True).encode()
        return hmac.new(secret, b"nego" + nonce + cnonce + blob,
                        hashlib.sha256).hexdigest()

    def _negotiate(self, offered: dict,
                   secret: bytes | None = None) -> dict:
        """Server side: pick the on-wire transforms."""
        comp = ""
        if self.compression and self.compression in offered.get(
                "compress", []):
            comp = self.compression
        secure = bool(offered.get("secure")) and self.secure \
            and (secret if secret is not None
                 else self.secret) is not None
        return {"compression": comp, "secure": secure,
                "salt": os.urandom(16).hex()}

    async def _handshake_server_read(self, proto: FrameProtocol):
        """Server side up to (not including) the ACK: returns
        (peer name, peer incarnation, negotiated transforms, nonce,
        cnonce, connection secret)."""
        nonce = os.urandom(16)
        # hello flags advertise ticket support so a ticket-holding
        # client talking to a PSK-only server falls back to the PSK
        # instead of proving a key the server can't derive
        flags = (HELLO_ACCEPTS_TICKETS
                 if self.ticket_validator is not None else 0) \
            | (HELLO_REQUIRES_TICKET if self.require_ticket else 0)
        proto.write([HELLO_MAGIC + struct.pack("<16sB", nonce, flags)])
        await proto.drain()
        hdr = await proto.read_exactly(4)
        if hdr != HELLO_MAGIC:
            raise ValueError("bad hello")
        (nlen,) = struct.unpack("<I", await proto.read_exactly(4))
        payload = json.loads(await proto.read_exactly(nlen))

        async def reject(why: str):
            proto.write([b"NACK"])
            await proto.drain()
            raise ValueError(why)

        # cephx: a presented ticket, once validated against the
        # rotating service keys, carries the session key that becomes
        # THIS connection's secret (proof, nego MAC, AEAD) -- and its
        # sealed entity must MATCH the claimed peer name, or any
        # service-class ticket holder could impersonate any daemon
        secret = self.secret
        cephx = payload.get("cephx")
        if cephx is not None and self.ticket_validator is not None:
            try:
                info = self.ticket_validator(cephx["gen"],
                                             cephx["ticket"])
            except Exception as e:
                await reject(f"cephx ticket rejected: {e}")
            if info["entity"] != payload.get("name"):
                await reject(
                    f"ticket entity {info['entity']!r} does not match "
                    f"claimed name {payload.get('name')!r}")
            secret = info["session_key"]
        elif self.require_ticket:
            await reject("cephx ticket required")

        proof = bytes.fromhex(payload.get("proof", ""))
        if secret is not None:
            want = hmac.new(secret, nonce, hashlib.sha256).digest()
            if not hmac.compare_digest(proof, want):
                await reject("auth failure")
        nego = self._negotiate(payload, secret)
        if self.secure and not nego["secure"]:
            # the server's secure requirement binds BOTH directions: a
            # peer that won't (or can't) encrypt gets no session at all
            await reject("peer did not offer secure mode")
        cnonce = bytes.fromhex(payload.get("cnonce", "")) or b"\0" * 16
        nego["mac"] = self._nego_mac(nego, nonce, cnonce, secret)
        return payload["name"], payload.get("inst", ""), nego, \
            nonce, cnonce, secret

    def _apply_negotiation(self, conn: Connection, nego: dict,
                           nonce: bytes, cnonce: bytes,
                           is_server: bool,
                           secret: bytes | None = None) -> None:
        if conn.outgoing is is_server:
            raise ValueError("negotiation direction mismatch")
        secret = secret if secret is not None else self.secret
        # a RE-negotiation (reconnect) replaces the transforms wholesale:
        # keeping a stale compressor after the peer stopped offering it
        # would emit frames the peer can no longer parse
        conn.compressor = None
        conn.aead_tx = None
        conn.aead_rx = None
        if not is_server:
            # client: verify the server's pick against the transcript
            # MAC and refuse a downgrade of our secure requirement
            want = self._nego_mac(nego, nonce, cnonce, secret)
            if want and not hmac.compare_digest(
                    want, nego.get("mac", "")):
                raise ValueError("negotiation MAC mismatch (tampered?)")
            if self.secure and not nego.get("secure"):
                raise ValueError(
                    "peer refused secure mode (downgrade rejected)")
        if nego.get("compression"):
            from ..compressor import Compressor, CompressorError
            try:
                conn.compressor = Compressor.create(nego["compression"])
            except CompressorError as e:
                # normalize to the error type every negotiation-failure
                # path already handles (close, don't retry)
                raise ValueError(str(e)) from e
        if nego.get("secure"):
            c2s, s2c = self._session_keys(nonce, cnonce,
                                          bytes.fromhex(nego["salt"]),
                                          secret)
            if is_server:
                conn.aead_rx, conn.aead_tx = c2s, s2c
            else:
                conn.aead_tx, conn.aead_rx = c2s, s2c

    async def _handshake_client(self, proto: FrameProtocol,
                                peer_name: str = ""):
        hdr = await proto.read_exactly(21)
        if hdr[:4] != HELLO_MAGIC:
            raise ValueError("bad hello")
        nonce = hdr[4:20]
        flags = hdr[20]
        # a live ticket for the peer's service replaces the PSK: we
        # prove the ticket's session key, and the server recovers the
        # same key from the sealed ticket blob.  Only presented when
        # the server's hello says it can validate tickets (a PSK-only
        # server would otherwise fail our proof)
        secret = self.secret
        fields = {}
        ticket = (self._ticket_for(peer_name)
                  if peer_name and flags & HELLO_ACCEPTS_TICKETS
                  else None)
        if ticket is not None:
            secret = bytes.fromhex(ticket["session_key"])
            fields["cephx"] = {"gen": ticket["gen"],
                               "ticket": ticket["ticket"]}
        proof = b""
        if secret is not None:
            proof = hmac.new(secret, nonce, hashlib.sha256).digest()
        cnonce = os.urandom(16)
        payload = json.dumps({
            "name": self.name, "inst": self.incarnation,
            "proof": proof.hex(), "cnonce": cnonce.hex(),
            "compress": [self.compression] if self.compression else [],
            "secure": self.secure, **fields}).encode()
        proto.write([HELLO_MAGIC + struct.pack("<I", len(payload)) + payload])
        await proto.drain()
        ack = await proto.read_exactly(4)
        if ack != b"ACK!":
            raise ConnectionError("auth rejected")
        (last_seq,) = struct.unpack("<Q", await proto.read_exactly(8))
        (nego_len,) = struct.unpack("<I", await proto.read_exactly(4))
        nego = json.loads(await proto.read_exactly(nego_len))
        return last_seq, nego, nonce, cnonce, secret

    async def _open(self, addr: tuple[str, int], peer_name: str):
        """A new socket to ``addr`` with the client's handshake done:
        its protocol and what the handshake returned.  The socket is
        closed again if the handshake fails."""
        _, proto = await self._loop().create_connection(
            lambda: FrameProtocol(self), addr[0], addr[1])
        try:
            return proto, await self._handshake_client(proto, peer_name)
        except BaseException:
            proto.transport.close()
            raise

    # -- client -------------------------------------------------------------
    async def connect(self, addr: tuple[str, int],
                      peer_name: str) -> Connection:
        # serialize per peer: N concurrent sends must share ONE
        # connection, not race N handshakes (the acceptor keeps a single
        # incoming conn per peer and would drop the losers mid-flight)
        lock = self._connect_locks.setdefault(peer_name, asyncio.Lock())
        async with lock:
            replay: list[Message] = []   # unacked msgs carried over
            conn = self.conns.get(peer_name)
            if conn is not None and not conn.closed:
                if conn.outgoing and conn.peer_addr is not None \
                        and tuple(conn.peer_addr) != tuple(addr):
                    # peer rebound to a new address: the cached conn
                    # points at a dead endpoint; carry its unacked
                    # messages over (lossless policy)
                    replay = [m for m, _ in conn.unacked]
                    await conn.close()
                else:
                    return conn
            elif conn is not None and conn.closed:
                replay = [m for m, _ in conn.unacked]
            proto, (last_seq, nego, hs_nonce, hs_cnonce, hs_secret) = \
                await self._open(addr, peer_name)
            conn = Connection(self, peer_name, proto,
                              outgoing=True, peer_addr=addr)
            try:
                self._apply_negotiation(conn, nego, hs_nonce, hs_cnonce,
                                        is_server=False, secret=hs_secret)
            except ValueError:
                proto.transport.close()
                raise
            # continue the server's seq space: a same-incarnation
            # session survives connection churn, and starting below
            # last_seq would get every message deduped as a replay
            conn.out_seq = last_seq
            self.conns[peer_name] = conn
            proto.start_frames(conn)
            for msg in replay:
                if msg.seq > last_seq:
                    await conn.send(msg)     # re-stamps seq past last_seq
            return conn

    async def _reconnect(self, conn: Connection) -> None:
        """Lossless policy: reopen and replay unacked in order.

        Serialized per connection — the send error path and the
        socket's ``connection_lost`` can both request a reconnect
        concurrently; the second requester finds the generation
        already advanced and returns without racing the socket swap.
        """
        if conn.peer_addr is None:
            await conn.close()
            raise ConnectionError("incoming connection lost")
        gen = conn.generation
        async with conn._reconnect_lock:
            if conn.closed:
                raise ConnectionError(f"{conn.peer_name} closed")
            if conn.generation != gen:
                return               # someone else already reconnected
            for attempt in range(5):
                proto = None
                try:
                    proto, (last_seq, nego, hs_nonce, hs_cnonce,
                            hs_secret) = await self._open(
                        conn.peer_addr, conn.peer_name)
                    # swap + replay under the SEND lock: a sender mid-
                    # flight must not write a newer seq onto the fresh
                    # stream before the replay of older unacked frames
                    # (the receiver's dedup would then drop the older
                    # seq as a replay -> silent loss)
                    async with conn._send_lock:
                        self._apply_negotiation(conn, nego, hs_nonce,
                                                hs_cnonce,
                                                is_server=False,
                                                secret=hs_secret)
                        conn._trim_acked(last_seq)
                        old, conn.proto = conn.proto, proto
                        old.transport.close()
                        # server->client stream restarts on new accept
                        conn.in_seq = 0
                        conn.generation += 1
                        proto.start_frames(conn)
                        await conn._resend_unacked()
                    return
                except (ConnectionError, OSError):
                    await asyncio.sleep(0.05 * (2 ** attempt))
                except ValueError:
                    # negotiation failure (MAC mismatch, downgrade,
                    # unknown compressor): retrying cannot help; close
                    # so connect() replaces the conn instead of
                    # returning a zombie forever
                    if proto is not None:
                        proto.transport.close()
                    break
            await conn.close()
            raise ConnectionError(f"reconnect to {conn.peer_name} failed")

    async def send(self, addr: tuple[str, int], peer_name: str,
                   msg: Message) -> None:
        conn = await self.connect(addr, peer_name)
        await conn.send(msg)

    # -- dispatch -----------------------------------------------------------
    def _socket_lost(self, conn: Connection, proto: FrameProtocol) -> None:
        """``conn``'s socket closed, was aborted over a bad frame, or
        failed."""
        if conn.proto is not proto or conn.closed:
            return               # a reconnect already replaced it
        if conn.outgoing:
            # lossless policy: try to re-establish and replay
            # unacked; on failure the conn is closed so connect()
            # replaces it instead of returning a cached corpse
            try:
                self._spawn(self._try_reconnect(conn))
                return
            except RuntimeError:      # event loop shutting down
                pass
        conn.closed = True
        conn._disarm_ack()
        # wake any sender blocked on the flow-control window so
        # it raises instead of hanging on a dead connection
        conn._window_open.set()

    def _frame_in(self, conn: Connection, msg: Message,
                  nbytes: int) -> None:
        """One received frame of ``nbytes`` on the wire, synchronously:
        seq/ack accounting, delivery of the message(s) it carries."""
        # every frame confirms, a replayed duplicate too: it left with
        # the watermark of its replay
        carried = conn._trim_acked(msg.ack_seq)
        if msg.type == ACK_TYPE:   # control frame, outside seq space
            # (a v1 peer's says it in the payload alone)
            conn._trim_acked(int(msg.data.get("seq", 0)))
            return
        if carried:
            self.perf.inc("rx_acks_carried")
        if msg.seq <= conn.in_seq:
            return  # duplicate after resend
        conn.in_seq = msg.seq
        if not conn.outgoing:
            self._sessions[conn.peer_name] = msg.seq
        conn._note_delivered(nbytes, bool(msg.flags & FLAG_ACK_NOW))
        if msg.type == SUBOP_BATCH_TYPE:
            # one framed flush -> the staged sub-ops, delivered
            # in staging order (per-peer FIFO preserved)
            for sub in unpack_subop_batch(msg):
                self._deliver(conn, sub)
        else:
            self._deliver(conn, msg)

    def _deliver(self, conn: Connection, msg: Message) -> None:
        """Fault-inject and dispatch ONE logical message (seq/ack
        accounting already ran on its frame)."""
        copies, delay = 1, 0.0
        if self.faults is not None:
            # recv-side injection happens ABOVE the transport:
            # seq/ack accounting already ran, so a dropped
            # message is "lost in the daemon", not a wire error
            # the lossless replay would transparently heal
            fd = self.faults.on_recv(
                self.name, conn.peer_name or msg.from_name,
                msg.type)
            if fd.drop:
                return
            copies, delay = fd.copies, fd.delay
        if (self.fast_dispatch is not None and copies == 1
                and delay == 0.0 and self.fast_dispatch(conn, msg)):
            return
        # dispatch in a task: a handler that itself RPCs back to
        # this peer must not block the receive callback its reply rides
        # on (the reference's DispatchQueue decoupling).  Task
        # creation order preserves ordering for handlers'
        # synchronous prefixes.
        for _ in range(copies):
            self._spawn(self._dispatch_one(conn, msg, delay))

    async def _try_reconnect(self, conn: Connection) -> None:
        try:
            await self._reconnect(conn)
        except (ConnectionError, OSError):
            pass

    async def _dispatch_one(self, conn: Connection, msg: Message,
                            delay: float = 0.0) -> None:
        if delay > 0:
            await asyncio.sleep(delay)
        for d in list(self.dispatchers):
            try:
                await d(conn, msg)
            except (ConnectionError, OSError):
                pass

    async def shutdown(self) -> None:
        # stop accepting BEFORE closing connections: closing a conn
        # triggers the peer's instant reconnect, and a still-open
        # listener would accept it -- a ghost connection that survives
        # shutdown and keeps this daemon answering (e.g. heartbeats
        # from a "dead" OSD, defeating failure detection)
        self._shutting_down = True
        if self._server is not None:
            self._server.close()
        for t in list(self._accept_tasks):
            t.cancel()
        for conn in (list(self.conns.values())
                     + list(self.conns_in.values())):
            await conn.close()
        self.conns.clear()
        self.conns_in.clear()
        if self._server is not None:
            # 3.12 wait_closed blocks until every peer transport is
            # gone; peers shutting down concurrently make that a
            # deadlock, so bound it
            try:
                await asyncio.wait_for(self._server.wait_closed(), 1.0)
            except asyncio.TimeoutError:
                pass
