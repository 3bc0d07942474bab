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
        # fill the window (no acks: cadence is huge), then close the
        # conn under a blocked sender: it must raise, not hang
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
