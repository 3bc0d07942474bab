import asyncio
import gc
import random

import pytest

from ceph_tpu.common.throttle import injector
from ceph_tpu.msg import Message, Messenger
from ceph_tpu.msg.message import OFFLOAD_THRESHOLD
from ceph_tpu.msg.messenger import FrameProtocol
from ceph_tpu.native import crc32c


def run(coro):
    return asyncio.get_event_loop_policy().new_event_loop().run_until_complete(coro)


def test_message_codec_roundtrip():
    m = Message("osd_op", {"op": "write", "oid": "foo"},
                segments=[b"payload", b"\x00bin\xff"])
    m.seq = 7
    m.from_name = "client.1"
    buf = m.encode()
    m2 = Message.decode(buf)
    assert m2.type == "osd_op"
    assert m2.data == {"op": "write", "oid": "foo"}
    assert m2.segments == [b"payload", b"\x00bin\xff"]
    assert m2.seq == 7 and m2.from_name == "client.1"


def test_message_crc_detects_corruption():
    buf = bytearray(Message("x", {"a": 1}, [b"data"]).encode())
    buf[-6] ^= 0xFF  # flip a payload byte
    with pytest.raises(ValueError):
        Message.decode(bytes(buf))


def test_basic_send_dispatch():
    async def main():
        server = Messenger("osd.0")
        client = Messenger("client.a")
        got = []
        done = asyncio.Event()

        async def dispatch(conn, msg):
            got.append(msg)
            done.set()

        server.add_dispatcher(dispatch)
        addr = await server.bind()
        await client.send(addr, "osd.0", Message("ping", {"n": 1}, [b"hi"]))
        await asyncio.wait_for(done.wait(), 5)
        await client.shutdown()
        await server.shutdown()
        return got

    got = run(main())
    assert got[0].type == "ping"
    assert got[0].from_name == "client.a"
    assert got[0].segments == [b"hi"]


def test_bidirectional_reply():
    async def main():
        server = Messenger("mon.0")
        client = Messenger("client.b")
        reply = asyncio.Event()
        replies = []

        async def server_dispatch(conn, msg):
            await conn.send(Message("pong", {"echo": msg.data["n"]}))

        async def client_dispatch(conn, msg):
            replies.append(msg)
            reply.set()

        server.add_dispatcher(server_dispatch)
        client.add_dispatcher(client_dispatch)
        addr = await server.bind()
        await client.send(addr, "mon.0", Message("ping", {"n": 42}))
        await asyncio.wait_for(reply.wait(), 5)
        await client.shutdown()
        await server.shutdown()
        return replies

    replies = run(main())
    assert replies[0].type == "pong"
    assert replies[0].data["echo"] == 42


def test_auth_secret_rejects_wrong_key():
    async def main():
        server = Messenger("mon.0", secret=b"sekret")
        good = Messenger("client.good", secret=b"sekret")
        bad = Messenger("client.bad", secret=b"wrong")
        seen = []

        async def dispatch(conn, msg):
            seen.append(msg.from_name)

        server.add_dispatcher(dispatch)
        addr = await server.bind()
        await good.send(addr, "mon.0", Message("hello"))
        with pytest.raises((ConnectionError, OSError)):
            await bad.send(addr, "mon.0", Message("hello"))
        await asyncio.sleep(0.1)
        await good.shutdown()
        await bad.shutdown()
        await server.shutdown()
        return seen

    seen = run(main())
    assert seen == ["client.good"]


def test_ordered_delivery_many():
    async def main():
        server = Messenger("osd.1")
        client = Messenger("client.c")
        got = []
        done = asyncio.Event()

        async def dispatch(conn, msg):
            got.append(msg.data["i"])
            if len(got) == 100:
                done.set()

        server.add_dispatcher(dispatch)
        addr = await server.bind()
        conn = await client.connect(addr, "osd.1")
        for i in range(100):
            await conn.send(Message("n", {"i": i}))
        await asyncio.wait_for(done.wait(), 10)
        await client.shutdown()
        await server.shutdown()
        return got

    got = run(main())
    assert got == list(range(100))


def test_reconnect_resends_unacked():
    async def main():
        server = Messenger("osd.2")
        client = Messenger("client.d")
        got = []

        async def dispatch(conn, msg):
            got.append(msg.data["i"])

        server.add_dispatcher(dispatch)
        addr = await server.bind()
        conn = await client.connect(addr, "osd.2")
        await conn.send(Message("n", {"i": 0}))
        await asyncio.sleep(0.1)
        # sever the TCP connection under the client
        conn.writer.close()
        await asyncio.sleep(0.05)
        await conn.send(Message("n", {"i": 1}))
        await asyncio.sleep(0.2)
        await client.shutdown()
        await server.shutdown()
        return got

    got = run(main())
    # resend after reconnect may duplicate already-seen seqs; the receiver
    # dedups, so the result is exactly [0, 1]
    assert got == [0, 1]


def test_flow_control_window_blocks_and_drains():
    """Sender window fills, acks from the receiver reopen it, and every
    message is delivered exactly once (Policy.h throttle semantics)."""
    async def main():
        server = Messenger("osd.3", ack_every=8)
        client = Messenger("client.f", max_unacked_msgs=16)
        got = []

        async def dispatch(conn, msg):
            got.append(msg.data["i"])

        server.add_dispatcher(dispatch)
        addr = await server.bind()
        conn = await client.connect(addr, "osd.3")
        n = 200
        await asyncio.wait_for(_send_all(conn, n), 10)
        # drain: every message delivered, and acks trimmed the window
        for _ in range(100):
            if len(got) == n:
                break
            await asyncio.sleep(0.02)
        trimmed = len(conn.unacked)
        await client.shutdown()
        await server.shutdown()
        return got, trimmed

    async def _send_all(conn, n):
        for i in range(n):
            await conn.send(Message("n", {"i": i}))

    got, trimmed = run(main())
    assert got == list(range(200))
    # the window was trimmed by acks, not grown unbounded (<= window +
    # one ack cadence of slack)
    assert trimmed <= 16 + 8


def test_flow_control_send_raises_on_closed_conn():
    async def main():
        server = Messenger("osd.4")
        client = Messenger("client.g", max_unacked_msgs=2, ack_every=1000)
        server.add_dispatcher(lambda c, m: asyncio.sleep(0))
        addr = await server.bind()
        conn = await client.connect(addr, "osd.4")
        # fill the window (no acks: the cadence is huge, and a receiver
        # that reads nothing cannot be asked for one either), then
        # close the conn under a blocked sender: it must raise, not hang
        assert await _until(lambda: "client.g" in server.conns_in)
        server.conns_in["client.g"].writer.pause_reading()
        await conn.send(Message("n", {"i": 0}))
        await conn.send(Message("n", {"i": 1}))
        blocked = asyncio.ensure_future(conn.send(Message("n", {"i": 2})))
        await asyncio.sleep(0.1)
        assert not blocked.done()
        await conn.close()
        with pytest.raises(ConnectionError):
            await asyncio.wait_for(blocked, 5)
        await client.shutdown()
        await server.shutdown()

    run(main())


# -- the frame protocol (PR 28): the same guarantees on sockets that are
# -- read into the frame's own buffer and written as a list of parts

def _pair(server_name, client_name, *, server_opts=None, client_opts=None):
    """A bound server that records (type, i, segment lengths, segment
    crcs) of what it is handed, and a client."""
    server = Messenger(server_name, **(server_opts or {}))
    client = Messenger(client_name, **(client_opts or {}))
    got = []

    async def dispatch(conn, msg):
        got.append((msg.type, msg.data.get("i"),
                    [len(s) for s in msg.segments],
                    [crc32c(s) for s in msg.segments]))

    server.add_dispatcher(dispatch)
    return server, client, got


def _want(mtype, i, segments):
    return (mtype, i, [len(s) for s in segments],
            [crc32c(s) for s in segments])


async def _until(cond, seconds=20.0):
    for _ in range(int(seconds / 0.01)):
        if cond():
            return True
        await asyncio.sleep(0.01)
    return cond()


def test_a_thousand_frames_of_mixed_sizes_arrive_in_order():
    sizes = [0, 1, 100, 4096, (16 << 10) - 1, 16 << 10, (64 << 10) - 20,
             64 << 10, (64 << 10) + 1, 200_000]
    rng = random.Random(28)
    blob = rng.randbytes(4 << 20)

    async def main():
        server, client, got = _pair("osd.5", "client.h")
        addr = await server.bind()
        conn = await client.connect(addr, "osd.5")
        want = []
        for i in range(1000):
            if i in (7, 400, 999):
                segs = [blob]                        # 4 MiB
            elif i % 97 == 0:
                segs = [blob[:512 << 10], b"", blob[5:9]]
            else:
                segs = [blob[o:o + rng.choice(sizes)]
                        for o in (rng.randrange(1 << 20),) * (i % 4)]
            want.append(_want("n", i, segs))
            await asyncio.wait_for(conn.send(Message("n", {"i": i}, segs)),
                                   20)
        assert await _until(lambda: len(got) == 1000)
        await client.shutdown()
        await server.shutdown()
        return got, want

    got, want = run(main())
    assert got == want


@pytest.mark.parametrize("how", ["inject", "abort"])
def test_a_socket_lost_inside_a_frame_delivers_it_once_by_replay(how):
    """The sender's transport dropped under a 4 MiB send
    (ms_inject_socket_failures), and the receiver's transport aborted
    while the frame is half read: nothing of the partial frame is
    delivered, and the reconnect's replay delivers it exactly once."""
    big = random.Random(4).randbytes(4 << 20)

    async def main():
        server, client, got = _pair("osd.6", "client.i")
        addr = await server.bind()
        conn = await client.connect(addr, "osd.6")
        await conn.send(Message("n", {"i": 0}, [b"first"]))
        assert await _until(lambda: len(got) == 1)
        half_read = False
        if how == "inject":
            injector.arm("ms_inject_socket_failures", countdown=1)
            await asyncio.wait_for(
                conn.send(Message("n", {"i": 1}, [big])), 20)
            assert injector.fired["ms_inject_socket_failures"] >= 1
        else:
            proto = server.conns_in["client.i"].proto
            sending = asyncio.ensure_future(
                conn.send(Message("n", {"i": 1}, [big])))
            while not sending.done() or proto.reader._rest is not None:
                if proto.reader._rest is not None:
                    half_read = 0 <= proto.reader._rest_n \
                        < proto.reader._rest_len
                    proto.transport.abort()
                    break
                await asyncio.sleep(0)
            assert half_read and len(got) == 1
            await asyncio.wait_for(sending, 20)
        await conn.send(Message("n", {"i": 2}, [b"last"]))
        assert await _until(lambda: len(got) >= 3)
        await asyncio.sleep(0.2)         # a second copy would land now
        generation = conn.generation
        await client.shutdown()
        await server.shutdown()
        return got, generation

    try:
        got, generation = run(main())
    finally:
        injector.disarm("ms_inject_socket_failures")
    assert generation >= 1               # it did reconnect
    assert got == [_want("n", 0, [b"first"]), _want("n", 1, [big]),
                   _want("n", 2, [b"last"])]


def test_a_sender_waits_while_the_transport_is_over_its_high_water_mark():
    chunk = bytes(1 << 20)

    async def main():
        server, client, got = _pair("osd.7", "client.j")
        addr = await server.bind()
        conn = await client.connect(addr, "osd.7")
        await conn.send(Message("n", {"i": 0}))
        assert await _until(lambda: len(got) == 1)
        # the receiver stops reading: the kernel's buffers fill, then
        # the transport's, and the sender has to wait
        server.conns_in["client.j"].writer.pause_reading()

        async def send_all():
            for i in range(1, 33):
                await conn.send(Message("n", {"i": i}, [chunk]))

        sending = asyncio.ensure_future(send_all())
        assert await _until(lambda: conn.proto._write_paused, 10)
        await asyncio.sleep(0.2)
        high = conn.writer.get_write_buffer_limits()[1]
        assert not sending.done()
        assert conn.writer.get_write_buffer_size() > high
        delivered_while_blocked = len(got)
        server.conns_in["client.j"].writer.resume_reading()
        await asyncio.wait_for(sending, 20)
        assert await _until(lambda: len(got) == 33)
        assert not conn.proto._write_paused
        await client.shutdown()
        await server.shutdown()
        return got, delivered_while_blocked

    got, delivered_while_blocked = run(main())
    assert delivered_while_blocked < 33
    assert [g[1] for g in got] == list(range(33))


@pytest.mark.parametrize("opts,payload", [
    ({"compression": "zlib"}, bytes(range(256)) * (3 << 12)),   # 3 MiB
    ({"secret": b"k", "secure": True},
     random.Random(9).randbytes((2 << 20) + 17)),
], ids=["compressed", "secure"])
def test_a_wrapped_connection_carries_a_frame_over_the_offload_threshold(
        opts, payload):
    """Compressed and encrypted frames are read whole into the same
    buffers and unwrapped; a decrypt over OFFLOAD_THRESHOLD leaves the
    loop and the frames behind it still arrive after it."""
    assert len(payload) > OFFLOAD_THRESHOLD

    async def main():
        server, client, got = _pair("osd.8", "client.k", server_opts=opts,
                                    client_opts=opts)
        addr = await server.bind()
        conn = await client.connect(addr, "osd.8")
        assert (conn.compressor is not None) == ("compression" in opts)
        assert (conn.aead_tx is not None) == ("secure" in opts)
        await conn.send(Message("n", {"i": 0}, [payload, b"tail"]))
        await conn.send(Message("n", {"i": 1}, [b"behind"]))
        await conn.send(Message("n", {"i": 2}, [payload[:70000]]))
        assert await _until(lambda: len(got) == 3)
        joined = client.perf.get("tx_frames_joined")
        await client.shutdown()
        await server.shutdown()
        return got, joined

    got, joined = run(main())
    assert got == [_want("n", 0, [payload, b"tail"]),
                   _want("n", 1, [b"behind"]),
                   _want("n", 2, [payload[:70000]])]
    assert joined == 3


def test_shutdown_with_frames_in_flight_leaves_no_task_and_no_socket():
    chunk = bytes(1 << 20)

    async def main():
        server, client, got = _pair("osd.9", "client.l")
        addr = await server.bind()
        conn = await client.connect(addr, "osd.9")
        await conn.send(Message("n", {"i": 0}))
        sends = [asyncio.ensure_future(conn.send(
            Message("n", {"i": i}, [chunk]))) for i in range(1, 17)]
        await asyncio.sleep(0)           # some written, some waiting
        await client.shutdown()
        await server.shutdown()
        await asyncio.gather(*sends, return_exceptions=True)

        def sockets_left():
            gc.collect()
            return [p for p in gc.get_objects()
                    if isinstance(p, FrameProtocol)
                    and p.messenger in (server, client)
                    and p.transport is not None and not p._lost]

        assert await _until(lambda: not sockets_left(), 5)
        others = asyncio.all_tasks() - {asyncio.current_task()}
        assert await _until(lambda: all(t.done() for t in others), 5)
        assert not client._accept_tasks and not server._accept_tasks

    run(main())


@pytest.mark.parametrize("opts,joined", [
    ({}, 0), ({"compression": "zlib"}, 1)], ids=["plain", "compressed"])
def test_msgr_counters_say_how_frames_left_and_what_was_copied(opts,
                                                              joined):
    """On a plain connection no frame is joined to be sent and the
    receive path copies each segment byte once, and nothing else; a
    connection that compresses sends every frame as one buffer."""
    sizes = [0, 5, 3000, 16 << 10, 100_000, 1 << 20]
    segs = [bytes([i]) * n for i, n in enumerate(sizes)]

    async def main():
        server, client, got = _pair("osd.10", "client.m", server_opts=opts,
                                    client_opts=opts)
        addr = await server.bind()
        conn = await client.connect(addr, "osd.10")
        for i, seg in enumerate(segs):
            await conn.send(Message("n", {"i": i}, [seg, seg[:7]]))
        assert await _until(lambda: len(got) == len(segs))
        tx, rx = client.perf.dump(), server.perf.dump()
        await client.shutdown()
        await server.shutdown()
        return tx, rx

    tx, rx = run(main())
    n = len(segs)
    seg_bytes = sum(len(s) + len(s[:7]) for s in segs)
    assert tx["tx_frames"] == rx["rx_frames"] == n
    assert tx.get("tx_frames_joined", 0) == joined * n
    assert tx["tx_bytes"] == rx["rx_bytes"] > 0
    if not joined:
        assert rx["rx_copied_bytes"] == seg_bytes
        assert rx["rx_bytes"] > seg_bytes
    else:
        # the frame joined out of its two buffers, the frame out of the
        # decompressor, then the segments
        assert rx["rx_copied_bytes"] > seg_bytes


# -- acks ride on frames that leave anyway (PR 39): a frame of its own
# -- only at the cadence, for a sender short of window, or after silence

def _echo_pair(monkeypatch, idle_s, *, server_opts=None, client_opts=None):
    """A server that answers every ``ping`` with a ``pong`` on the
    connection it came in on, a client that records the pongs, and the
    idle deadline both run with."""
    monkeypatch.setattr("ceph_tpu.msg.messenger.ACK_IDLE_S", idle_s)
    server = Messenger("osd.20", **(server_opts or {}))
    client = Messenger("client.q", **(client_opts or {}))
    pongs = []

    async def serve(conn, msg):
        await conn.send(Message("pong", {"i": msg.data["i"]}))

    async def collect(conn, msg):
        pongs.append(msg.data["i"])

    server.add_dispatcher(serve)
    client.add_dispatcher(collect)
    return server, client, pongs


def _acks(m):
    d = m.perf.dump()
    return {k: d.get(k, 0) for k in ("tx_acks", "tx_acks_cadence",
                                     "tx_acks_window", "tx_acks_idle",
                                     "rx_acks_carried")}


@pytest.mark.parametrize("opts", [
    {}, {"compression": "zlib"}, {"secret": b"k", "secure": True}],
    ids=["plain", "compressed", "secure"])
def test_requests_and_replies_confirm_each_other_without_an_ack_frame(
        monkeypatch, opts):
    """A reply carries the request's confirmation and the next request
    the reply's: both ``unacked`` queues are trimmed and no ``__ack``
    leaves, on a plain and on a wrapped connection."""
    async def main():
        server, client, pongs = _echo_pair(monkeypatch, 60.0,
                                           server_opts=opts,
                                           client_opts=opts)
        addr = await server.bind()
        conn = await client.connect(addr, "osd.20")
        pad = [bytes(3000)]              # over COMPRESS_THRESHOLD
        for i in range(20):
            await conn.send(Message("ping", {"i": i}, pad))
            assert await _until(lambda: len(pongs) == i + 1)
        back = server.conns_in["client.q"]
        # the 20th pong confirmed the 20th ping; the pong itself waits
        # for the next frame out (or the idle deadline, far off here)
        state = (len(conn.unacked), conn.acked_seq, len(back.unacked),
                 back.acked_seq, back._ack_pending_msgs,
                 conn._ack_pending_msgs)
        await conn.send(Message("ping", {"i": 20}, pad))
        assert await _until(lambda: len(pongs) == 21)
        state += (len(back.unacked), back.acked_seq)
        acks = _acks(client), _acks(server)
        frames = (client.perf.get("tx_frames"), server.perf.get("tx_frames"))
        await client.shutdown()
        await server.shutdown()
        return state, acks, frames

    state, (tx, rx), frames = run(main())
    assert state == (0, 20, 1, 19, 0, 1, 1, 20)
    assert tx["tx_acks"] == rx["tx_acks"] == 0
    assert tx["rx_acks_carried"] == 21 and rx["rx_acks_carried"] == 20
    assert frames == (21, 21)            # nothing but pings and pongs


def test_a_one_way_stream_into_a_small_window_drains_by_the_window_flag(
        monkeypatch):
    """No reply ever leaves the receiver and its cadence (64) is four
    windows long: a sender at half its window says so in the envelope
    and is confirmed at once, long before the idle deadline."""
    monkeypatch.setattr("ceph_tpu.msg.messenger.ACK_IDLE_S", 60.0)

    async def main():
        server, client, got = _pair("osd.21", "client.r",
                                    client_opts={"max_unacked_msgs": 16})
        addr = await server.bind()
        conn = await client.connect(addr, "osd.21")
        for i in range(200):
            await asyncio.wait_for(conn.send(Message("n", {"i": i})), 10)
            assert len(conn.unacked) <= 16
        assert await _until(lambda: len(got) == 200)
        # what is left unconfirmed is under half a window
        assert await _until(lambda: len(conn.unacked) < 8, 5)
        acks = _acks(server)
        await client.shutdown()
        await server.shutdown()
        return [g[1] for g in got], acks

    got, acks = run(main())
    assert got == list(range(200))
    assert acks["tx_acks_window"] > 0 and acks["tx_acks_idle"] == 0
    assert acks["tx_acks"] == acks["tx_acks_window"] + acks["tx_acks_cadence"]


def test_a_quiet_connection_is_confirmed_once_by_a_timer_and_no_task(
        monkeypatch):
    """Three frames, then silence: one ``__ack`` after the idle
    deadline confirms all three; the deadline is a timer handle, never
    a Task, and ``close`` cancels it."""
    idle = 0.15
    monkeypatch.setattr("ceph_tpu.msg.messenger.ACK_IDLE_S", idle)

    async def main():
        server, client, got = _pair("osd.22", "client.s")
        addr = await server.bind()
        conn = await client.connect(addr, "osd.22")
        before = asyncio.all_tasks()
        for i in range(3):
            await conn.send(Message("n", {"i": i}))
        assert await _until(lambda: len(got) == 3)
        back = server.conns_in["client.s"]
        assert isinstance(back._ack_timer, asyncio.TimerHandle)
        assert len(conn.unacked) == 3 and server.perf.get("tx_acks") == 0
        # only the three dispatch tasks came, and they are done
        assert await _until(lambda: all(
            t.done() for t in asyncio.all_tasks() - before), 5)
        assert await _until(lambda: not conn.unacked, 5)
        await asyncio.sleep(3 * idle)            # a second one would leave
        acks = _acks(server)
        assert back._ack_timer is None
        # armed again by the next frame, cancelled by close()
        await conn.send(Message("n", {"i": 3}))
        assert await _until(lambda: len(got) == 4)
        timer = back._ack_timer
        await back.close()
        assert timer.cancelled() and back._ack_timer is None
        await asyncio.sleep(2 * idle)
        acks_after = _acks(server)
        await client.shutdown()
        await server.shutdown()
        others = asyncio.all_tasks() - {asyncio.current_task()}
        assert await _until(lambda: all(t.done() for t in others), 5)
        return acks, acks_after

    acks, acks_after = run(main())
    assert acks == acks_after
    assert acks["tx_acks"] == acks["tx_acks_idle"] == 1


def test_frames_that_keep_leaving_keep_the_deadline_away(monkeypatch):
    """While pings and pongs follow each other faster than the idle
    deadline nobody sends an ``__ack``, however long it goes on, and the
    one handle of a connection is re-used, not re-made per frame; the
    silence after it costs the one ack for the last pong."""
    idle = 0.2

    async def main():
        server, client, pongs = _echo_pair(monkeypatch, idle)
        addr = await server.bind()
        conn = await client.connect(addr, "osd.20")
        handles = set()
        for i in range(12):                      # 0.6 s: three deadlines
            await conn.send(Message("ping", {"i": i}))
            assert await _until(lambda: len(pongs) == i + 1)
            handles.add(conn._ack_timer)
            await asyncio.sleep(0.05)
        during = _acks(client), _acks(server)
        assert await _until(lambda: client.perf.get("tx_acks") == 1, 5)
        await asyncio.sleep(2 * idle)
        after = _acks(client), _acks(server)
        back = server.conns_in["client.q"]
        state = (len(back.unacked), conn._ack_timer, back._ack_timer)
        await client.shutdown()
        await server.shutdown()
        return during, after, len(handles), state

    during, after, handles, state = run(main())
    assert during[0]["tx_acks"] == during[1]["tx_acks"] == 0
    assert handles <= 4                  # one a deadline, not one a frame
    assert after[0]["tx_acks"] == after[0]["tx_acks_idle"] == 1
    assert after[1]["tx_acks"] == 0
    assert state == (0, None, None)


def test_a_v1_peer_is_understood():
    """An envelope of struct_v 1 has no ``ack_seq``: its frames confirm
    nothing by themselves and its ``__ack`` says the seq in its
    payload, which still trims."""
    from ceph_tpu.common.denc import Encoder
    from ceph_tpu.msg.messenger import ACK_TYPE

    def v1_frame(mtype, seq, data):
        payload = Encoder()
        payload.value(data)
        enc = Encoder()
        enc.start(1, 1)
        enc.string(mtype).u64(seq).string("osd.23").u8(0)
        enc.blob(payload.bytes())
        enc.list([], Encoder.u32)
        enc.finish()
        body = enc.bytes()
        return (b"CTv3" + len(body).to_bytes(4, "little") + body
                + (crc32c(body) & 0xFFFFFFFF).to_bytes(4, "little"))

    old = Message.decode(v1_frame("n", 1, {"i": 0}))
    assert (old.type, old.seq, old.data, old.ack_seq, old.flags) \
        == ("n", 1, {"i": 0}, 0, 0)

    async def main():
        server, client, got = _pair("osd.23", "client.t")
        addr = await server.bind()
        conn = await client.connect(addr, "osd.23")
        for i in range(3):
            await conn.send(Message("n", {"i": i}))
        assert await _until(lambda: len(got) == 3)
        assert len(conn.unacked) == 3
        # what a v1 osd.23 would send back on this socket
        client._frame_in(conn, old, 64)
        assert len(conn.unacked) == 3 and conn.in_seq == 1
        client._frame_in(conn, Message.decode(
            v1_frame(ACK_TYPE, 0, {"seq": 2})), 64)
        left = [m.seq for m, _ in conn.unacked]
        await client.shutdown()
        await server.shutdown()
        return left

    assert run(main()) == [3]


def test_a_replay_carries_the_watermark_of_now_and_a_duplicate_trims(
        monkeypatch):
    """A frame that is replayed after a reconnect is stamped again:
    the receiver dedups it by seq and still takes its ``ack_seq``."""
    async def main():
        server, client, pongs = _echo_pair(monkeypatch, 60.0)
        addr = await server.bind()
        conn = await client.connect(addr, "osd.20")
        for i in range(3):
            await conn.send(Message("ping", {"i": i}))
            assert await _until(lambda: len(pongs) == i + 1)
        back = server.conns_in["client.q"]
        # pongs 1-2 are confirmed by pings 1-2; pong 3 is not yet
        assert [m.seq for m, _ in back.unacked] == [3]
        first = Message("ping", {"i": 0})
        first.seq, first.from_name = 1, "client.q"
        stamped = []
        for in_seq in (2, 3):
            # what the client's replay of ping 1 would be: the same
            # seq, the watermark of the moment it leaves again
            conn.in_seq = in_seq
            frame = b"".join(conn._frame_parts(first))
            stamped.append(Message.decode(frame).ack_seq)
        dup = Message.decode(frame)
        delivered = len(pongs)
        server._frame_in(back, dup, len(frame))
        await asyncio.sleep(0.05)
        state = (len(back.unacked), back.in_seq, len(pongs) - delivered)
        await client.shutdown()
        await server.shutdown()
        return stamped, state

    stamped, state = run(main())
    assert stamped == [2, 3]
    assert state == (0, 3, 0)            # trimmed, and not delivered again


def test_a_reconnect_replays_with_the_flag_and_the_watermark(monkeypatch):
    """The socket dies under a sender whose window is half full: the
    replay on the new socket asks for its confirmation at once, and
    everything arrives exactly once."""
    monkeypatch.setattr("ceph_tpu.msg.messenger.ACK_IDLE_S", 60.0)

    async def main():
        server, client, got = _pair("osd.24", "client.u",
                                    client_opts={"max_unacked_msgs": 32})
        addr = await server.bind()
        conn = await client.connect(addr, "osd.24")
        for i in range(10):              # under half the window: no flag
            await conn.send(Message("n", {"i": i}))
        assert await _until(lambda: len(got) == 10)
        assert len(conn.unacked) == 10 and server.perf.get("tx_acks") == 0
        conn.writer.abort()
        assert await _until(lambda: conn.generation == 1)
        # the handshake's last_seq trimmed all ten: nothing to replay
        assert not conn.unacked
        server.conns_in["client.u"].writer.pause_reading()
        for i in range(10, 26):
            await conn.send(Message("n", {"i": i}))
        conn.writer.abort()              # 16 of 32 in flight, unread
        assert await _until(lambda: conn.generation == 2)
        assert await _until(lambda: len(got) == 26)
        assert await _until(lambda: not conn.unacked, 5)
        acks = _acks(server)
        await client.shutdown()
        await server.shutdown()
        return [g[1] for g in got], acks

    got, acks = run(main())
    assert got == list(range(26))
    assert acks["tx_acks_window"] >= 1 and acks["tx_acks_idle"] == 0


def test_a_lost_carrier_is_covered_by_the_next(monkeypatch):
    """Two pongs never leave the server (a send fault) and the client
    throws a third away after its frame was accounted (a receive
    fault): the next pong's ``ack_seq`` says more than all of them
    would have, and no ``__ack`` was needed."""
    from ceph_tpu.common.faults import RECV, SEND, MessageFaultInjector

    async def main():
        drop_tx, drop_rx = MessageFaultInjector(1), MessageFaultInjector(2)
        drop_tx.drop(mtype="pong", direction=SEND, count=2)
        server, client, pongs = _echo_pair(
            monkeypatch, 60.0, server_opts={"faults": drop_tx},
            client_opts={"faults": drop_rx})
        addr = await server.bind()
        conn = await client.connect(addr, "osd.20")
        for i in range(2):
            await conn.send(Message("ping", {"i": i}))
        await asyncio.sleep(0.1)
        assert pongs == [] and len(conn.unacked) == 2
        drop_rx.drop(mtype="pong", direction=RECV, count=1)
        await conn.send(Message("ping", {"i": 2}))
        assert await _until(lambda: not conn.unacked)
        await conn.send(Message("ping", {"i": 3}))
        assert await _until(lambda: pongs == [3])
        acks = _acks(client), _acks(server)
        stats = drop_tx.stats.get("dropped", 0), drop_rx.stats.get(
            "dropped", 0)
        await client.shutdown()
        await server.shutdown()
        return acks, stats

    (tx, rx), stats = run(main())
    assert stats == (2, 1)
    assert tx["tx_acks"] == rx["tx_acks"] == 0
    assert tx["rx_acks_carried"] == 2    # pong 2 (dropped above) and pong 3
