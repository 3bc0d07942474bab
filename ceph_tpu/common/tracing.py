"""Cross-daemon trace spans (src/common/tracer.h:10-27 role).

A trace id is minted at the CLIENT when an op is submitted; every hop
-- client -> primary OSD -> replica OSDs -> object store -- opens a
child span carrying (trace_id, parent span id) and records its own
timing.  Span contexts ride the wire inside message data ("trace"
field on osd_op / rep_op), and within a daemon they propagate through
the asyncio task via a ContextVar, so deep call chains (pg -> backend
-> store) pick up their parent without threading arguments.

Each daemon keeps its finished spans in a bounded ring, dumpable via
the admin socket ("dump_tracing"); assembling the rings from every
daemon yields the full hop tree for any op (the tracepoint + jaeger
span story, compressed to what this framework can verify in-process).

Spans cross ``await``s, so a span's duration is queueing plus work.
What the loop's thread is DOING is a ``section``: a plain ``with``
block that never contains an ``await`` or ``yield``, named
``<layer>.<what>`` with the layer one of ``SECTION_LAYERS``.  Sections
land in the jax profiler's trace as host events, on the same clock as
the device's own events, so a device idle gap can be laid against the
section the thread was in.  Outside a profiler session a section
records nothing.

The event loop itself is a layer (``loop``): every daemon of a process
shares one asyncio selector loop, and what that loop does between the
daemons' callbacks -- asleep in ``select``, ``recv_into`` ahead of the
protocol, the ``sendmsg`` a transport deferred -- is in nobody's code.
``install_loop_probe`` brackets those three on the loop INSTANCE (no
asyncio class is touched) as the sections ``loop.select``,
``loop.read_ready`` and ``loop.write_ready``, and keeps, all the time
and on the spans' clock, a phase record: one bucket a second and one
record per phase of ``LONG_PHASE_NS`` or more.  This is the
reference's ``AsyncMessenger::Worker`` set (``msgr_running_total_time``,
``msgr_running_send_time``, ``msgr_running_recv_time``) for a loop that
all daemons share: the process-wide perf set ``loop``, the admin
socket's ``dump_loop``.
"""

from __future__ import annotations

import contextvars
import gc
import itertools
import os
import sys
import time
from collections import deque

from .perf import PerfCounters

# the host layers a section name starts with ("wire.decode"); the
# benchmark's per-layer metrics read these prefixes letter for letter
SECTION_LAYERS = ("client", "wire", "osd_op", "osd_read", "store",
                  "batcher", "device_wait", "recovery", "scrub", "loop",
                  "placement", "registry")


class _NoSection:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NO_SECTION = _NoSection()
_annotation = None      # jax.profiler.TraceAnnotation, once jax is loaded


def section(name: str):
    """Context manager around synchronous work on the calling thread.

    A ``jax.profiler.TraceAnnotation`` once ``jax`` is in
    ``sys.modules`` (host and device planes of one profiler session
    share a clock); the shared no-op while it is not: a
    replicated-only OSD never imports jax, and no section makes it."""
    global _annotation
    if _annotation is None:
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        if profiler is None:
            return _NO_SECTION
        _annotation = profiler.TraceAnnotation
    return _annotation(name)

# the active span of THIS asyncio task (or sync call chain under it)
current_span: contextvars.ContextVar = contextvars.ContextVar(
    "ceph_tpu_span", default=None)

RING = 2048


class Span:
    __slots__ = ("trace_id", "span_id", "parent_id", "name", "daemon",
                 "start", "end", "tags", "_tracer", "_token")

    def __init__(self, tracer: "Tracer", name: str, trace_id: str,
                 parent_id: str | None, tags: dict) -> None:
        self._tracer = tracer
        self.name = name
        self.daemon = tracer.daemon
        self.trace_id = trace_id
        self.span_id = tracer.next_id()
        self.parent_id = parent_id
        self.tags = tags
        self.start = time.time_ns()
        self.end: int | None = None
        self._token = None

    def ctx(self) -> dict:
        """The wire context a child hop embeds in its message."""
        return {"id": self.trace_id, "parent": self.span_id}

    def finish(self) -> None:
        if self.end is None:
            self.end = time.time_ns()
            self._tracer._done(self)
        if self._token is not None:
            current_span.reset(self._token)
            self._token = None

    def activate(self) -> "Span":
        """Make this the task's current span (children attach to it)."""
        self._token = current_span.set(self)
        return self

    def to_dict(self) -> dict:
        """Times are integer nanoseconds inside; the dump keeps its
        seconds and milliseconds."""
        return {"trace_id": self.trace_id, "span_id": self.span_id,
                "parent_id": self.parent_id, "name": self.name,
                "daemon": self.daemon, "start": self.start * 1e-9,
                "end": None if self.end is None else self.end * 1e-9,
                "duration_ms": None if self.end is None
                else (self.end - self.start) / 1e6,
                "tags": self.tags}


class Tracer:
    def __init__(self, daemon: str) -> None:
        self.daemon = daemon
        self.finished: deque[Span] = deque(maxlen=RING)
        # ids: daemon name, one random tag per tracer (two processes
        # may run a client of the same name), then a counter -- no
        # syscall per span
        self._id_prefix = f"{daemon}.{os.urandom(3).hex()}-"
        self._serial = itertools.count(1)

    def next_id(self) -> str:
        return f"{self._id_prefix}{next(self._serial):x}"

    def start(self, name: str, parent: dict | None = None,
              **tags) -> Span:
        """Open a span.  ``parent`` is a wire context ({"id",
        "parent"}) from an incoming message; absent that, the task's
        current span is the parent; absent both, this is a ROOT span
        with a fresh trace id."""
        if parent and parent.get("id"):
            return Span(self, name, parent["id"],
                        parent.get("parent"), tags)
        cur = current_span.get()
        if cur is not None:
            return Span(self, name, cur.trace_id, cur.span_id, tags)
        return Span(self, name, self.next_id(), None, tags)

    def root(self, name: str, **tags) -> Span:
        """A span with a trace id of its own, whatever span the task
        has inherited: background work (a backfill push) is no part of
        the client op whose task happened to start it."""
        return Span(self, name, self.next_id(), None, tags)

    def _done(self, span: Span) -> None:
        self.finished.append(span)

    def dump(self, trace_id: str | None = None) -> list[dict]:
        return [s.to_dict() for s in self.finished
                if trace_id is None or s.trace_id == trace_id]


def child_span(name: str, **tags) -> Span | None:
    """A child of the task's current span, on that span's tracer; None
    when no op trace is active on this task."""
    cur = current_span.get()
    if cur is None:
        return None
    return Span(cur._tracer, name, cur.trace_id, cur.span_id, tags)


def finish(span: Span | None) -> None:
    if span is not None:
        span.finish()


# per-process registry (daemon name -> tracer): tests and admin
# sockets look tracers up here
_TRACERS: dict[str, Tracer] = {}


def get_tracer(daemon: str) -> Tracer:
    t = _TRACERS.get(daemon)
    if t is None:
        t = _TRACERS[daemon] = Tracer(daemon)
    return t


def all_spans(trace_id: str) -> list[dict]:
    """Every span of a trace across every tracer IN THIS PROCESS
    (tests run whole clusters in-process; multi-process deployments
    dump per-daemon over the admin socket instead)."""
    out = []
    for t in _TRACERS.values():
        out.extend(t.dump(trace_id))
    return sorted(out, key=lambda s: s["start"])


# -- the event loop's phase record ---------------------------------------------
#
# A pass of the loop is a ``select`` and then a run phase: the ready
# callbacks, from select's exit to its next entry.  Both are timed on
# ``perf_counter_ns`` and laid on the spans' clock (``time.time_ns``)
# by one offset taken at install.

LONG_PHASE_NS = 100_000_000      # a phase this long leaves a record
CPU_PHASE_NS = 10_000_000        # the CPU clocks are read this often at most
LOOP_SECONDS = 600               # buckets kept, one a second
LOOP_RECORDS = 256

# a bucket is a list in this order, ``sec`` (time.time_ns() // 1e9)
# first; the counters after it are also the ``loop`` perf set's keys
BUCKET_FIELDS = ("sec", "select_ns", "run_ns", "run_cpu_ns", "iterations",
                 "max_run_ns", "recv_ns", "recv_calls", "send_ns",
                 "send_calls", "gc_ns")
(_SEC, _SELECT, _RUN, _RUN_CPU, _ITER, _MAX_RUN, _RECV, _RECV_N, _SEND,
 _SEND_N, _GC) = range(len(BUCKET_FIELDS))
_SECOND = 1_000_000_000

_loop_seconds: deque[list] = deque(maxlen=LOOP_SECONDS)
_loop_records: deque[dict] = deque(maxlen=LOOP_RECORDS)
_gc_clock = [0, 0]               # [start of the collection running, ns in all]
_loop_closed = [0] * len(BUCKET_FIELDS)     # the seconds that are over, summed
_NO_SECOND = (0,) * len(BUCKET_FIELDS)


class _LoopPerf(PerfCounters):
    """The ``loop`` set: the buckets' counters summed since the process
    started (the seconds that are over and the one still filling),
    ``max_run_ns`` the longest run phase so far, and ``long_phases``,
    the records made."""

    def __init__(self) -> None:
        super().__init__("loop")
        self.inc("long_phases", 0)

    def dump(self) -> dict:
        out = super().dump()
        filling = _loop_seconds[-1] if _loop_seconds else _NO_SECOND
        for i in range(_SELECT, len(BUCKET_FIELDS)):
            out[BUCKET_FIELDS[i]] = (
                max(_loop_closed[i], filling[i]) if i == _MAX_RUN
                else _loop_closed[i] + filling[i])
        return out


LOOP_PERF = _LoopPerf()


def _gc_phase(phase: str, info: dict) -> None:
    if phase == "start":
        _gc_clock[0] = time.perf_counter_ns()
    elif _gc_clock[0]:
        _gc_clock[1] += time.perf_counter_ns() - _gc_clock[0]
        _gc_clock[0] = 0


def _bucket(sec: int) -> list:
    """The bucket of second ``sec``: the ring's newest, or a new one
    behind it (the newest then is over and joins the sums)."""
    if _loop_seconds:
        newest = _loop_seconds[-1]
        if newest[_SEC] >= sec:
            return newest
        for i in range(_SELECT, len(BUCKET_FIELDS)):
            _loop_closed[i] = (max(_loop_closed[i], newest[i])
                               if i == _MAX_RUN
                               else _loop_closed[i] + newest[i])
    bucket = [0] * len(BUCKET_FIELDS)
    bucket[_SEC] = sec
    _loop_seconds.append(bucket)
    return bucket


def _spread(field: int, start: int, end: int, cpu: int) -> list:
    """Add the wall time of a phase [start, end) (spans' clock, ns) to
    ``field`` of each second it lies in, and ``cpu`` to ``run_cpu_ns``
    in the same shares; returns the bucket it ends in.  A stall of five
    seconds fills five buckets."""
    wall = max(1, end - start)
    bucket = _bucket(start // _SECOND)
    while True:
        edge = (bucket[_SEC] + 1) * _SECOND
        part = max(0, min(end, edge) - max(start, bucket[_SEC] * _SECOND))
        bucket[field] += part
        bucket[_RUN_CPU] += cpu * part // wall
        if end <= edge:
            return bucket
        bucket = _bucket(bucket[_SEC] + 1)


def install_loop_probe(loop) -> bool:
    """Bracket ``loop``'s select, read-ready and write-ready callbacks
    and start its phase record; once per loop, by attributes of the
    loop and of its selector alone (``BaseEventLoop._run_once`` and the
    selector transports look all three up on the instance at each
    call).  False for a loop without a selector (nothing to bracket)
    and for one that has its probe."""
    selector = getattr(loop, "_selector", None)
    if selector is None or getattr(loop, "_ceph_tpu_probe", False):
        return False
    loop._ceph_tpu_probe = True
    if _gc_phase not in gc.callbacks:
        gc.callbacks.append(_gc_phase)

    clock, thread_cpu, proc_cpu = (time.perf_counter_ns, time.thread_time_ns,
                                   time.process_time_ns)
    to_wall = time.time_ns() - clock()
    io = [0, 0, 0, 0]            # recv ns, calls, send ns, calls this phase
    # the open phase: where it started (spans' clock) and the gc ns so
    # far then; the CPU clocks' last reading: when, thread, process, and
    # the ns of run phases that ended since
    now = clock() + to_wall
    mark = [now, _gc_clock[1], now, thread_cpu(), proc_cpu(), 0]

    def phase_ends(kind: str, field: int) -> tuple[list, int]:
        """The open phase (``kind``, its wall time counted under
        ``field``) ends here and the other begins: the bucket it ends
        in and its wall ns.  Wall time is spread over the seconds the
        phase spans; what else is counted goes to the second it ends
        in.  The CPU clocks are system calls (6 us each on the chip's
        host): they are read at the first edge ``CPU_PHASE_NS`` after
        their last reading, so at the end of every phase that long, and
        the thread's cpu since then is the run phases' (``select``'s
        own is a few us of system time a pass)."""
        now = clock() + to_wall
        start = mark[0]
        wall = now - start
        if field == _RUN:
            mark[5] += wall
        cpu_ns = 0
        if now - mark[2] >= CPU_PHASE_NS:
            thread, proc = thread_cpu(), proc_cpu()
            cpu_ns = min(mark[5], thread - mark[3])
            proc_ns = proc - mark[4]
            mark[2:] = (now, thread, proc, 0)
        bucket = _loop_seconds[-1] if _loop_seconds else _NO_SECOND
        second = bucket[_SEC] * _SECOND
        if second <= start and now <= second + _SECOND:
            bucket[field] += wall
            bucket[_RUN_CPU] += cpu_ns
        else:
            bucket = _spread(field, start, now, cpu_ns)
        gc_ns = _gc_clock[1] - mark[1]
        if gc_ns:
            bucket[_GC] += gc_ns
        if wall >= LONG_PHASE_NS:
            # thread off the cpu while the process is on it: another
            # thread held the GIL; both off: blocked or descheduled.
            # (Both cpu times are counted from the clocks' last
            # reading, under CPU_PHASE_NS before the phase began.)
            _loop_records.append({
                "kind": kind, "start": start, "wall_ms": wall / 1e6,
                "cpu_ms": cpu_ns / 1e6, "proc_cpu_ms": proc_ns / 1e6,
                "gc_ms": gc_ns / 1e6, "recv_ms": io[0] / 1e6,
                "send_ms": io[2] / 1e6, "reads": io[1], "writes": io[3]})
            LOOP_PERF.inc("long_phases")
        mark[0], mark[1] = now, _gc_clock[1]
        return bucket, wall

    selector_select = selector.select

    def select(timeout=None):
        bucket, wall = phase_ends("run", _RUN)
        bucket[_ITER] += 1
        if wall > bucket[_MAX_RUN]:
            bucket[_MAX_RUN] = wall
        if io[1] or io[3]:
            bucket[_RECV] += io[0]
            bucket[_RECV_N] += io[1]
            bucket[_SEND] += io[2]
            bucket[_SEND_N] += io[3]
            io[:] = (0, 0, 0, 0)
        try:
            with section("loop.select"):
                return selector_select(timeout)
        finally:
            phase_ends("select", _SELECT)

    add_reader, add_writer = loop._add_reader, loop._add_writer

    def _add_reader(fd, callback, *args):
        def read_ready(*a):
            t0 = clock()
            try:
                with section("loop.read_ready"):
                    callback(*a)
            finally:
                io[0] += clock() - t0
                io[1] += 1
        return add_reader(fd, read_ready, *args)

    def _add_writer(fd, callback, *args):
        def write_ready(*a):
            t0 = clock()
            try:
                with section("loop.write_ready"):
                    callback(*a)
            finally:
                io[2] += clock() - t0
                io[3] += 1
        return add_writer(fd, write_ready, *args)

    selector.select = select
    loop._add_reader, loop._add_writer = _add_reader, _add_writer
    return True


def loop_buckets() -> list[dict]:
    """The ring of seconds, oldest first (the newest still filling)."""
    return [dict(zip(BUCKET_FIELDS, b)) for b in _loop_seconds]


def loop_records() -> list[dict]:
    """The ring of phases of ``LONG_PHASE_NS`` or more, oldest first;
    ``start`` in seconds like a dumped span's."""
    return [dict(r, start=r["start"] * 1e-9) for r in _loop_records]


def dump_loop() -> dict:
    """What the admin socket's ``dump_loop`` answers."""
    return {"buckets": loop_buckets(), "records": loop_records()}
