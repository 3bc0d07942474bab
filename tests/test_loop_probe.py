"""The event loop as a layer (``ceph_tpu.common.tracing``'s probe): the
first ``Messenger`` started on a loop brackets the selector's ``select``
and the transports' read-ready and write-ready callbacks on the loop
instance, and keeps the phase record: a bucket a second and a record
per phase of 100 ms or more, in ``perf dump`` and ``dump_loop``."""

from __future__ import annotations

import asyncio
import gc
import selectors
import time
from collections import deque

import pytest

from ceph_tpu.common import tracing
from ceph_tpu.msg.message import Message
from ceph_tpu.msg.messenger import Messenger

MS = 1_000_000


def records_since(t0: float, kind: str) -> list[dict]:
    return [r for r in tracing.loop_records()
            if r["start"] >= t0 and r["kind"] == kind]


async def pair(on_msg):
    """Two messengers on the running loop, ``b`` listening and handing
    what it receives to ``on_msg``."""
    a, b = Messenger("probe.a"), Messenger("probe.b")

    async def dispatch(conn, msg):
        on_msg(msg)
    b.add_dispatcher(dispatch)
    return a, b, await b.bind()


def test_the_probe_installs_once_per_loop():
    """The first messenger to open a socket installs it before the
    socket exists; a second messenger, and a second call, add nothing;
    no class of asyncio or of the selectors changes."""
    class_attrs = (asyncio.SelectorEventLoop._add_reader,
                   asyncio.SelectorEventLoop._add_writer,
                   selectors.DefaultSelector.select)

    async def main():
        loop = asyncio.get_running_loop()
        assert "select" not in vars(loop._selector)
        a, b, addr = await pair(lambda msg: None)
        first = (loop._selector.select, loop._add_reader, loop._add_writer)
        assert all(getattr(f, "__self__", None) is None for f in first)
        assert "select" in vars(loop._selector)
        # the listening socket's accept callback already runs inside
        key = loop._selector.get_key(b._server.sockets[0].fileno())
        assert key.data[0]._callback.__name__ == "read_ready"
        await a.connect(addr, "probe.b")
        c = Messenger("probe.c")
        await c.connect(addr, "probe.b")
        assert tracing.install_loop_probe(loop) is False
        assert first == (loop._selector.select, loop._add_reader,
                         loop._add_writer)
        for m in (a, b, c):
            await m.shutdown()

    asyncio.run(main())
    assert class_attrs == (asyncio.SelectorEventLoop._add_reader,
                           asyncio.SelectorEventLoop._add_writer,
                           selectors.DefaultSelector.select)
    assert gc.callbacks.count(tracing._gc_phase) == 1

    class NoSelector:
        pass
    assert tracing.install_loop_probe(NoSelector()) is False


def test_a_4m_frame_counts_its_recvs_and_deferred_sends():
    """A 4 MiB frame over loopback: the kernel does not take it in one
    ``sendmsg``, so the rest leaves from write-ready callbacks; the
    receiver's read-ready callbacks are counted with their time."""
    async def main():
        got = []
        a, b, addr = await pair(got.append)
        await a.connect(addr, "probe.b")
        before = tracing.LOOP_PERF.dump()
        await a.send(addr, "probe.b",
                     Message("blob", {}, segments=[bytes(4 << 20)]))
        while not got:
            await asyncio.sleep(0.005)
        await asyncio.sleep(0)
        after = tracing.LOOP_PERF.dump()
        for m in (a, b):
            await m.shutdown()
        return got[0], before, after

    msg, before, after = asyncio.run(main())
    assert len(msg.segments[0]) == 4 << 20
    reads = after["recv_calls"] - before["recv_calls"]
    writes = after["send_calls"] - before["send_calls"]
    assert reads >= 2 and writes >= 1
    assert after["recv_ns"] > before["recv_ns"]
    assert after["send_ns"] > before["send_ns"]
    assert after["iterations"] - before["iterations"] >= 2


def test_wire_recv_nests_in_loop_read_ready(tmp_path):
    """In a profiler session the three loop sections are host events of
    the loop's thread, and every ``wire.recv`` lies inside a
    ``loop.read_ready``."""
    import jax

    async def main():
        got = []
        a, b, addr = await pair(got.append)
        await a.connect(addr, "probe.b")
        jax.profiler.start_trace(str(tmp_path))
        try:
            await a.send(addr, "probe.b",
                         Message("blob", {}, segments=[bytes(4 << 20)]))
            while not got:
                await asyncio.sleep(0.005)
        finally:
            jax.profiler.stop_trace()
        for m in (a, b):
            await m.shutdown()

    asyncio.run(main())
    (path,) = tmp_path.rglob("*.xplane.pb")
    events = [
        (line, e.name, e.start_ns, e.start_ns + e.duration_ns)
        for plane in jax.profiler.ProfileData.from_file(str(path)).planes
        for line in plane.lines for e in line.events
        if e.name.startswith(("loop.", "wire.recv"))]
    names = {name for _, name, _, _ in events}
    assert {"loop.select", "loop.read_ready", "loop.write_ready",
            "wire.recv"} <= names
    assert len({id(line) for line, *_ in events}) == 1     # one thread
    outer = [(lo, hi) for _, n, lo, hi in events if n == "loop.read_ready"]
    inner = [(lo, hi) for _, n, lo, hi in events if n == "wire.recv"]
    assert inner and all(any(lo <= a and b <= hi for lo, hi in outer)
                         for a, b in inner)


def test_buckets_add_up_to_the_wall():
    """Over a second and more of a loop that mostly sleeps and
    sometimes works, ``select_ns`` + ``run_ns`` of the seconds it
    spans is the wall time, and a phase that crosses a second's edge
    is spread over both."""
    async def main():
        tracing.install_loop_probe(asyncio.get_running_loop())
        await asyncio.sleep(0)
        t0 = time.time_ns()
        while time.time_ns() - t0 < 1_300 * MS:
            await asyncio.sleep(0.03)
            t = time.perf_counter()
            while time.perf_counter() - t < 0.005:
                pass
        await asyncio.sleep(0)
        return t0, time.time_ns()

    t0, t1 = asyncio.run(main())
    kept = [b for b in tracing.loop_buckets()
            if t0 // 10**9 <= b["sec"] <= t1 // 10**9]
    assert [b["sec"] for b in kept] == list(range(t0 // 10**9,
                                                  t1 // 10**9 + 1))
    whole = [b for b in kept if t0 // 10**9 < b["sec"] < t1 // 10**9]
    for b in whole:
        assert b["select_ns"] + b["run_ns"] == pytest.approx(10**9, rel=0.02)
    total = sum(b["select_ns"] + b["run_ns"] for b in kept)
    assert t1 - t0 <= total * 1.02
    assert total <= (len(kept)) * 10**9
    assert all(b["run_cpu_ns"] <= b["run_ns"] * 1.02 + MS for b in kept)
    assert sum(b["iterations"] for b in kept) >= 30
    assert max(b["max_run_ns"] for b in kept) >= 4 * MS


def test_the_cpu_clocks_are_read_once_in_ten_milliseconds_at_most(
        monkeypatch):
    """The two CPU clocks are system calls: a loop that spins through
    thousands of short passes reads them once per ``CPU_PHASE_NS``, not
    four times a pass, and the thread's cpu between two readings goes
    to the run phases between them; a phase that long still ends with
    a reading of its own (a 60 ms sleep shows as off the cpu, less the
    stretch before it)."""
    calls = {"thread": 0, "proc": 0}
    thread_time, process_time = time.thread_time_ns, time.process_time_ns

    def thread():
        calls["thread"] += 1
        return thread_time()

    def proc():
        calls["proc"] += 1
        return process_time()

    monkeypatch.setattr(time, "thread_time_ns", thread)
    monkeypatch.setattr(time, "process_time_ns", proc)

    def gained(before: dict) -> dict:
        after = tracing.LOOP_PERF.dump()
        return {k: after[k] - before[k] for k in ("run_ns", "run_cpu_ns")}

    async def main():
        tracing.install_loop_probe(asyncio.get_running_loop())
        await asyncio.sleep(0.02)        # a reading: the count starts here
        calls.update(thread=0, proc=0)
        before = tracing.LOOP_PERF.dump()
        t0 = time.perf_counter_ns()
        while time.perf_counter_ns() - t0 < 50 * MS:
            await asyncio.sleep(0)
        took = time.perf_counter_ns() - t0
        spun, reads = gained(before), dict(calls)
        before = tracing.LOOP_PERF.dump()
        time.sleep(0.06)                 # one run phase, off the cpu
        await asyncio.sleep(0)
        return took, reads, spun, gained(before)

    took, reads, spun, slept = asyncio.run(main())
    assert reads["thread"] == reads["proc"]
    assert 3 <= reads["thread"] <= took // tracing.CPU_PHASE_NS + 1
    # the spinning thread was on the cpu; a stretch is counted when it
    # is read, so the ends of the 50 ms lie up to a stretch off
    assert spun["run_cpu_ns"] >= 0.5 * spun["run_ns"] - tracing.CPU_PHASE_NS
    assert spun["run_cpu_ns"] <= spun["run_ns"] + tracing.CPU_PHASE_NS
    assert slept["run_ns"] >= 60 * MS
    assert slept["run_ns"] - slept["run_cpu_ns"] >= 40 * MS


@pytest.mark.parametrize("what,kind,cpu", [
    ("spin", "run", "all"),
    ("sleep", "run", "none"),
    ("idle", "select", "none"),
])
def test_a_long_phase_leaves_one_record(what, kind, cpu):
    """150 ms of the thread's CPU in one callback, 150 ms in one
    callback blocked in ``time.sleep``, 150 ms with nothing to do: one
    record each, told apart by kind and by the thread's CPU time, which
    agrees with what the callback read of the thread's clock itself
    (on a loaded host a spinning thread is not on the CPU all the
    while; a record may hold up to ``CPU_PHASE_NS`` from before)."""
    own = {}

    async def main():
        tracing.install_loop_probe(asyncio.get_running_loop())
        await asyncio.sleep(0)
        t0, c0 = time.time(), time.thread_time()
        if what == "spin":
            while time.thread_time() - c0 < 0.15:
                pass
        elif what == "sleep":
            time.sleep(0.15)
        else:
            await asyncio.sleep(0.15)
        own["cpu_ms"] = 1e3 * (time.thread_time() - c0)
        await asyncio.sleep(0)
        await asyncio.sleep(0)
        return t0

    before = tracing.LOOP_PERF.dump()["long_phases"]
    t0 = asyncio.run(main())
    (rec,) = records_since(t0 - 0.01, kind)
    other = "select" if kind == "run" else "run"
    assert records_since(t0 - 0.01, other) == []
    assert tracing.LOOP_PERF.dump()["long_phases"] == before + 1
    assert 149 <= rec["wall_ms"] < 1000
    slack = tracing.CPU_PHASE_NS / 1e6 + 5
    assert own["cpu_ms"] - 5 <= rec["cpu_ms"] <= own["cpu_ms"] + slack
    if cpu == "all":
        assert rec["cpu_ms"] >= 149
        assert rec["proc_cpu_ms"] >= rec["cpu_ms"] - 5
    else:
        assert rec["cpu_ms"] < 0.2 * rec["wall_ms"]
    assert set(rec) == {"kind", "start", "wall_ms", "cpu_ms", "proc_cpu_ms",
                        "gc_ms", "recv_ms", "send_ms", "reads", "writes"}
    assert rec["reads"] == rec["writes"] == 0


def test_a_collection_inside_a_phase_shows_in_gc_ms():
    async def main():
        tracing.install_loop_probe(asyncio.get_running_loop())
        junk = [[i] for i in range(200_000)]      # something to walk
        await asyncio.sleep(0)
        t0 = time.time()
        t = time.perf_counter()
        gc.collect()
        took = time.perf_counter() - t
        del junk
        time.sleep(0.11)                  # long enough for a record
        await asyncio.sleep(0)
        return t0, took

    before = tracing.LOOP_PERF.dump()["gc_ns"]
    t0, took = asyncio.run(main())
    (rec,) = records_since(t0 - 0.01, "run")
    assert rec["gc_ms"] == pytest.approx(1e3 * took, rel=0.25, abs=0.5)
    assert rec["gc_ms"] > 0
    assert tracing.LOOP_PERF.dump()["gc_ns"] - before >= 0.7e9 * took


def test_dump_loop_and_perf_dump_carry_the_set(tmp_path, monkeypatch):
    """Every daemon's collection holds the one process-wide ``loop``
    set, so a sum across the OSDs of one loop counts it once; the admin
    socket answers ``dump_loop`` with the buckets and the records, and
    ``dump_tracing`` as before."""
    from ceph_tpu.common.admin_socket import admin_command
    from ceph_tpu.loadgen.cluster import SimCluster

    async def main():
        cluster = await SimCluster.create(2)
        osd = cluster.osds[0]
        try:
            assert osd.perf.get("loop") is tracing.LOOP_PERF
            assert cluster.osds[1].perf.get("loop") is tracing.LOOP_PERF
            assert cluster.mon.perf.get("loop") is tracing.LOOP_PERF
            await asyncio.sleep(0.05)
            one = tracing.LOOP_PERF.dump()
            summed = cluster.perf_counters("loop")
            assert set(summed) == set(one) == set(
                tracing.BUCKET_FIELDS[1:]) | {"long_phases"}
            assert one["iterations"] <= summed["iterations"] \
                < 2 * one["iterations"]
            assert osd.perf.dump()["loop"]["iterations"] > 0
            # a private socket: the cluster's daemons have none
            from ceph_tpu.common import AdminSocket
            # a ring as full as a long-lived daemon's: the answer is
            # one line well over a stream reader's default limit
            monkeypatch.setattr(tracing, "_loop_seconds", deque(
                ([sec] + [0] * (len(tracing.BUCKET_FIELDS) - 1)
                 for sec in range(tracing.LOOP_SECONDS)),
                maxlen=tracing.LOOP_SECONDS))
            osd.admin_socket = AdminSocket(str(tmp_path / "osd.asok"))
            osd._register_admin_commands()
            await osd.admin_socket.start()
            answer = await admin_command(str(tmp_path / "osd.asok"),
                                         "dump_loop")
            spans = await admin_command(str(tmp_path / "osd.asok"),
                                        "dump_tracing")
            perf = await admin_command(str(tmp_path / "osd.asok"),
                                       "perf dump")
        finally:
            await cluster.stop()
        return answer, spans, perf

    answer, spans, perf = asyncio.run(main())
    assert set(answer) == {"buckets", "records"}
    assert len(answer["buckets"]) == tracing.LOOP_SECONDS
    assert set(answer["buckets"][-1]) == set(tracing.BUCKET_FIELDS)
    assert isinstance(answer["records"], list)
    assert isinstance(spans, list)
    assert perf["loop"]["run_ns"] > 0 and perf["loop"]["select_ns"] > 0
