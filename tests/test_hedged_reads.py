"""Straggler-tolerant hedged coded reads (osd/hedged_gather.py).

Pins the ISSUE-11 contract: byte-parity of first-k decode vs the
full-set oracle (including a late-set switch mid-gather), the hedge
timer firing only after the EWMA quantile, cancellation accounting (no
orphan sub-read tasks), LRC locality preference under hedging, the
hedge x retry interplay bound, heavy-tail fault determinism, and the
slow-marked kill+delay drive with zero failed ops.
"""

import asyncio
import itertools
import math
import random

import pytest

from ceph_tpu.common.faults import (RECV, FaultRule,
                                    MessageFaultInjector)
from ceph_tpu.msg import Message, Messenger
from ceph_tpu.osd.hedged_gather import HedgedGather, PeerLatencyEWMA

from test_osd_cluster import Cluster, read_result, run


# -- per-peer EWMA / adaptive quantile ---------------------------------------

def test_ewma_estimate_tracks_peer_latency():
    t = PeerLatencyEWMA(alpha=0.3, quantile=0.9, min_samples=4)
    assert t.estimate(1) is None            # cold
    for _ in range(20):
        t.observe(1, 0.010)
        t.observe(2, 0.200)
    e1, e2 = t.estimate(1), t.estimate(2)
    # steady input converges near the mean; q>0.5 keeps it above it
    assert 0.010 <= e1 < 0.030
    assert 0.200 <= e2 < 0.600
    # the cohort delay is the MEDIAN of the warm estimates: one slow
    # peer must not drag the whole cohort's hedge timer up to its pace
    for _ in range(20):
        t.observe(3, 0.012)
    cohort = t.cohort_delay([1, 2, 3])
    assert cohort < 0.050


def test_ewma_min_samples_gate_and_cost():
    t = PeerLatencyEWMA(alpha=0.2, quantile=0.9, min_samples=5)
    for _ in range(4):
        t.observe(7, 0.01)
    assert t.estimate(7) is None            # below the sample gate
    assert t.cohort_delay([7]) is None
    assert t.cost_us(7, default_s=0.5) == 500000   # cold -> default
    t.observe(7, 0.01)
    assert t.estimate(7) is not None
    assert t.cost_us(7, default_s=0.5) < 500000


def test_hedge_delay_clamps_and_cold_default():
    t = PeerLatencyEWMA(min_samples=1, quantile=0.9)
    eng = HedgedGather(None, t, enabled=True, delay_min=0.005,
                       delay_max=0.250)
    assert eng.hedge_delay([99]) == 0.250   # cold cohort -> ceiling
    t.observe(1, 0.0001)
    assert eng.hedge_delay([1]) == 0.005    # fast cohort -> floor
    t.observe(2, 5.0)
    t.observe(2, 5.0)
    assert eng.hedge_delay([2]) == 0.250    # slow cohort -> ceiling


# -- engine-level behavior over a stub OSD -----------------------------------

class StubOSD:
    """start_request stand-in with scripted per-peer reply delays
    (None = never replies)."""

    def __init__(self, delays, nbytes=64):
        self.delays = dict(delays)
        self.nbytes = nbytes
        self.whoami = -1
        self.tasks = []
        self.sent = []                       # (peer, mtype, payload)
        self._tid = itertools.count(1)

    def start_request(self, peer, mtype, data, segments=()):
        tid = next(self._tid)
        self.sent.append((peer, mtype, dict(data)))

        async def _run():
            d = self.delays[peer]
            if d is None:
                await asyncio.Event().wait()     # a true straggler
            await asyncio.sleep(d)
            return Message("ec_subop_read_reply",
                           {"tid": tid, "req_shard": data.get("shard")},
                           segments=[b"x" * self.nbytes])

        task = asyncio.ensure_future(_run())
        self.tasks.append(task)
        return tid, task


def _warm(tracker, peers, lat=0.005, n=10):
    for p in peers:
        for _ in range(n):
            tracker.observe(p, lat)


def _mk_engine(osd, perf=None, **kw):
    from ceph_tpu.common.perf import PerfCounters
    t = PeerLatencyEWMA(alpha=0.2, quantile=0.9, min_samples=3)
    kw.setdefault("delay_min", 0.02)
    kw.setdefault("delay_max", 0.5)
    eng = HedgedGather(osd, t, perf=perf or PerfCounters("ec_hedge"),
                       **kw)
    return eng


def test_first_sufficient_set_cancels_and_reaps_straggler():
    """The gather completes on the first sufficient set; the straggler
    sub-read is cancelled AND awaited (no orphan task), and counted."""
    async def main():
        osd = StubOSD({1: 0.002, 2: None, 3: 0.002})
        eng = _mk_engine(osd)
        _warm(eng.tracker, [1, 2, 3])
        got = {}

        def on_reply(s, msg):
            if msg is not None:
                got[s] = msg

        def sufficient():
            return set(got) if len(got) >= 2 else False

        out = await eng.gather_shards(
            {0: (1, "ec_subop_read", {"shard": 0}),
             1: (2, "ec_subop_read", {"shard": 1})},
            on_reply=on_reply, sufficient=sufficient,
            hedge_pool={2: (3, "ec_subop_read", {"shard": 2})},
            choose_extras=lambda h: {2: (3, "ec_subop_read",
                                         {"shard": 2})},
            timeout=5.0)
        assert out.completed
        assert out.accepted == {0, 2}
        assert out.hedge_fired and out.hedged == {2}
        assert out.cancelled == {1}
        # cancellation hygiene: every task the engine spawned is DONE
        # (the straggler was cancelled and reaped, not orphaned)
        await asyncio.sleep(0)
        assert all(t.done() for t in osd.tasks)
        pc = eng.perf
        assert pc.get("hedges_fired") == 1
        assert pc.get("hedges_won") == 1
        assert pc.get("cancelled_subreads") == 1
        assert pc.get("first_set_completions") == 1
        assert pc.get("hedge_bytes") == 64
    run(main())


def test_hedge_fires_only_after_ewma_quantile():
    """Fast replies beat the armed quantile delay: no hedge fires.  A
    straggler outliving it does fire one -- and only after the cohort
    delay elapsed."""
    async def main():
        # all replies well under the armed delay (~20ms floor)
        osd = StubOSD({1: 0.001, 2: 0.001})
        eng = _mk_engine(osd)
        _warm(eng.tracker, [1, 2, 3])
        got = {}

        def mk(shards_needed):
            def sufficient():
                return set(got) if len(got) >= shards_needed else False
            return sufficient

        out = await eng.gather_shards(
            {0: (1, "ec_subop_read", {"shard": 0}),
             1: (2, "ec_subop_read", {"shard": 1})},
            on_reply=lambda s, m: got.__setitem__(s, m),
            sufficient=mk(2),
            hedge_pool={2: (3, "ec_subop_read", {"shard": 2})},
            choose_extras=lambda h: {2: (3, "ec_subop_read",
                                         {"shard": 2})},
            timeout=5.0)
        assert out.completed and not out.hedge_fired
        assert eng.perf.get("hedges_armed") == 1
        assert eng.perf.get("hedges_fired") == 0

        # now a straggler: the hedge must not fire before the armed
        # delay (the EWMA quantile, clamped to the 20ms floor)
        osd2 = StubOSD({1: 0.001, 2: None, 3: 0.001})
        eng2 = _mk_engine(osd2)
        _warm(eng2.tracker, [1, 2, 3])
        got.clear()
        loop = asyncio.get_event_loop()
        t0 = loop.time()
        fire_times = []

        def choose(h):
            fire_times.append(loop.time() - t0)
            return {2: (3, "ec_subop_read", {"shard": 2})}

        out = await eng2.gather_shards(
            {0: (1, "ec_subop_read", {"shard": 0}),
             1: (2, "ec_subop_read", {"shard": 1})},
            on_reply=lambda s, m: got.__setitem__(s, m),
            sufficient=mk(2),
            hedge_pool={2: (3, "ec_subop_read", {"shard": 2})},
            choose_extras=choose, timeout=5.0)
        assert out.completed and out.hedge_fired
        assert fire_times and fire_times[0] >= 0.02   # not before
    run(main())


def test_collect_all_mode_reaps_on_deadline():
    """sufficient=None (scrub collection): completes when everything
    arrived; a straggler is bounded by the deadline and reaped."""
    async def main():
        osd = StubOSD({1: 0.001, 2: None})
        eng = _mk_engine(osd)
        got = {}
        out = await eng.gather_shards(
            {0: (1, "ec_subop_read", {"shard": 0}),
             1: (2, "ec_subop_read", {"shard": 1})},
            on_reply=lambda s, m: got.__setitem__(s, m),
            timeout=0.1)
        assert not out.completed
        assert out.timed_out == {1}
        assert set(got) == {0}
        assert all(t.done() for t in osd.tasks)
    run(main())


def test_first_reply_hedges_across_sources():
    """Recovery-pull shape: source 0 straggles, the hedge escalates to
    source 1 and its reply wins; the loser is cancelled and reaped."""
    async def main():
        osd = StubOSD({5: None, 6: 0.002})
        eng = _mk_engine(osd)
        _warm(eng.tracker, [5, 6])
        rep = await eng.first_reply([5, 6], "pg_pull", {"oid": "o"},
                                    timeout=5.0)
        assert rep is not None
        assert all(t.done() for t in osd.tasks)
        assert eng.perf.get("hedges_fired") == 1
        assert eng.perf.get("hedges_won") == 1
        assert eng.perf.get("cancelled_subreads") == 1
        # rejected replies escalate immediately (no timer wait)
        osd2 = StubOSD({5: 0.001, 6: 0.001})
        eng2 = _mk_engine(osd2)
        _warm(eng2.tracker, [5, 6])
        seen = []
        rep = await eng2.first_reply(
            [5, 6], "pg_pull", {"oid": "o"}, timeout=5.0,
            accept=lambda m: (seen.append(1), len(seen) > 1)[-1])
        assert rep is not None and len(seen) == 2
    run(main())


# -- cost-aware minimum_to_decode_with_cost ----------------------------------

@pytest.fixture
def registry():
    from ceph_tpu.ec import registry as reg
    return reg()


def test_with_cost_prefers_cheap_tier(registry):
    codec = registry.factory("tpu", {"k": "2", "m": "1",
                                     "technique": "reed_sol_van"})
    # shard 1 (a data shard) is exorbitant; 0 + parity 2 are cheap:
    # the plan must decode around shard 1
    plan = codec.minimum_to_decode_with_cost({0, 1},
                                             {0: 0, 1: 10_000, 2: 1})
    assert plan == {0, 2}
    # uniform costs degrade to the old direct-read behavior
    plan = codec.minimum_to_decode_with_cost({0, 1},
                                             {0: 1, 1: 1, 2: 1})
    assert plan == {0, 1}


def test_lrc_locality_preference_under_costs(registry):
    """The cost-tier growth composes with (not overrides) the LRC
    plugin's locality preference: with uniform costs a single missing
    chunk repairs inside its local group; pricing a local source out
    pushes the plan to the cheaper tier instead."""
    codec = registry.factory("lrc", {"k": "4", "m": "2", "l": "3"})
    n = codec.get_chunk_count()
    # pick a data chunk and find its local (smallest) layer
    local_layers = sorted(codec.layers,
                          key=lambda la: len(la.positions))[:-1]
    lost = local_layers[0].data_pos[0]
    group = set(local_layers[0].positions)
    avail = {i: 1 for i in range(n) if i != lost}
    plan = codec.minimum_to_decode_with_cost({lost}, avail)
    assert plan <= group - {lost}            # locality held
    assert len(plan) == local_layers[0].k
    # a straggling group member prices the local repair out: the
    # cheaper tier (feasible via the global layer) wins and the plan
    # routes around the expensive source entirely
    expensive = local_layers[0].data_pos[1]
    avail = {i: (10_000 if i == expensive else 1)
             for i in range(n) if i != lost}
    plan2 = codec.minimum_to_decode_with_cost({lost}, avail)
    assert expensive not in plan2
    assert len(plan2) > local_layers[0].k    # paid reads, not latency


# -- heavy-tail fault injector -----------------------------------------------

def test_straggler_delays_deterministic_per_peer():
    """Same seed -> same per-peer delay sequence, independent of how
    traffic to OTHER peers interleaves (the per-(seed, peer) RNG
    stream contract)."""
    def drain(inj, n, interleave=False):
        out = []
        for _ in range(n):
            if interleave:
                inj.decide(RECV, "osd.0", "osd.9", "noise")
            out.append(inj.decide(RECV, "osd.0", "osd.3",
                                  "ec_subop_read_reply").delay)
        return out

    a = MessageFaultInjector(seed=42)
    a.straggler("osd.3", dist="lognormal", mu=-3.0, sigma=1.5, cap=4.0)
    b = MessageFaultInjector(seed=42)
    b.straggler("osd.3", dist="lognormal", mu=-3.0, sigma=1.5, cap=4.0)
    b.straggler("osd.9", dist="pareto", scale=0.01, alpha=1.1)
    assert drain(a, 16) == drain(b, 16, interleave=True)
    # a different seed IS a different schedule
    c = MessageFaultInjector(seed=43)
    c.straggler("osd.3", dist="lognormal", mu=-3.0, sigma=1.5, cap=4.0)
    assert drain(a, 16) != drain(c, 16)
    assert a.stats.get("straggler_delays", 0) >= 16


def test_straggler_distributions_and_cap():
    rng = random.Random(1)
    ln = FaultRule("delay", dist="lognormal",
                   dist_params={"mu": -2.0, "sigma": 1.0, "cap": 0.5})
    samples = [ln.sample_delay(rng) for _ in range(200)]
    assert all(0.0 < s <= 0.5 for s in samples)
    assert len(set(samples)) > 100           # actually a distribution
    pa = FaultRule("delay", dist="pareto",
                   dist_params={"scale": 0.01, "alpha": 1.2})
    samples = [pa.sample_delay(rng) for _ in range(200)]
    assert all(s >= 0.01 for s in samples)
    assert max(samples) > 0.05               # the heavy tail is there
    with pytest.raises(ValueError):
        FaultRule("delay", dist="zipfian")


# -- cluster-level: parity, interplay, counters ------------------------------

HEDGE_FAST = {
    "osd_heartbeat_interval": 0.2, "osd_heartbeat_grace": 3.0,
    "osd_ec_hedge_delay_min": 0.01, "osd_ec_hedge_delay_max": 0.15,
    "osd_ec_hedge_min_samples": 2, "osd_ec_read_timeout": 3.0,
}


async def make_hedged_cluster(n_osds=3, pg_num=8, faults=None,
                              osd_config=None):
    from ceph_tpu.mon import Monitor
    from ceph_tpu.osd import OSD
    mon = Monitor(rank=0, config={"mon_osd_min_down_reporters": 1,
                                  "mon_osd_down_out_interval": 3600.0})
    addr = await mon.start()
    mon.peer_addrs = [addr]
    osds = []
    for i in range(n_osds):
        osd = OSD(host=f"host{i}",
                  config={**HEDGE_FAST, **(osd_config or {})},
                  fault_injector=faults)
        await osd.start(addr)
        osds.append(osd)
    client = Messenger("client.test")
    await client.bind()
    c = Cluster(mon, osds, client)
    await c.command("osd erasure-code-profile set",
                    {"name": "p21",
                     "profile": {"plugin": "tpu", "k": "2", "m": "1",
                                 "technique": "reed_sol_van"}})
    await c.command("osd pool create",
                    {"name": "ecpool", "type": "erasure",
                     "pg_num": pg_num, "erasure_code_profile": "p21"})
    return c


def _hedge_counters(c, key):
    return sum(o.perf.get("ec_hedge").get(key) for o in c.osds
               if o.perf.get("ec_hedge") is not None and not o._stopped)


def test_hedged_reads_byte_parity_and_no_retry_coupling():
    """Under an induced per-peer straggler, every read returns bytes
    identical to the unhedged full-set oracle (first-k decode == full
    decode, including late-set switches where the hedged parity beats
    a straggling data shard), hedges fire and win, and the retry
    ladder NEVER engages (a hedged op holding a sufficient set must
    not also schedule a retry)."""
    async def main():
        inj = MessageFaultInjector(seed=11)
        c = await make_hedged_cluster(faults=inj)
        try:
            rng = random.Random(3)
            objs = {}
            for i in range(8):
                size = rng.randrange(4 << 10, 16 << 10)
                data = rng.getrandbits(8 * size).to_bytes(size,
                                                          "little")
                objs[f"h-{i}"] = data
                await c.osd_op("ecpool", f"h-{i}",
                               [{"op": "write", "off": 0,
                                 "data": data}])
            # warm the per-peer EWMAs with healthy reads
            for oid in objs:
                await c.osd_op("ecpool", oid,
                               [{"op": "read", "off": 0, "len": None}])
            # induce a heavy-tail straggler on ONE peer's read replies
            # -- the peer that serves h-0's REMOTE data shard, so at
            # least that read must gather through the straggler
            _, primary, up = c.target_for("ecpool", "h-0")
            victim = next(o for o in up[:2] if o != primary)
            inj.straggler(f"osd.{victim}", dist="lognormal",
                          mu=math.log(0.5), sigma=0.3, cap=1.5,
                          mtype="ec_subop_read_reply", direction=RECV)
            retries0 = sum(
                o.perf.get("ec_degraded").get("gather_retries")
                for o in c.osds)
            # hedged pass: reads decode around the straggler
            for oid, want in objs.items():
                reply = await c.osd_op(
                    "ecpool", oid,
                    [{"op": "read", "off": 0, "len": None}])
                r, data = read_result(reply)
                assert r.get("ok") and data == want, oid
            fired = _hedge_counters(c, "hedges_fired")
            assert fired > 0, "straggler never triggered a hedge"
            assert _hedge_counters(c, "hedges_won") > 0
            # the hedge must not have multiplied into the retry ladder
            retries1 = sum(
                o.perf.get("ec_degraded").get("gather_retries")
                for o in c.osds)
            assert retries1 == retries0, "hedged ops scheduled retries"
            # nor left a sub-read behind: once the pass has settled no
            # ``OSD.start_request`` task is pending (a live one means a
            # gather returned without reaping its stragglers)
            await asyncio.sleep(0.05)
            leaked = [t for t in asyncio.all_tasks() if not t.done()
                      and getattr(t.get_coro(), "__name__", "")
                      == "_issue"]
            assert not leaked, leaked
            # unhedged oracle: same bytes through the full-set gather
            inj.clear()
            for o in c.osds:
                o.hedger.enabled = False
            for oid, want in objs.items():
                reply = await c.osd_op(
                    "ecpool", oid,
                    [{"op": "read", "off": 0, "len": None}])
                r, data = read_result(reply)
                assert r.get("ok") and data == want, oid
        finally:
            await c.stop()
    run(main())


def test_exhaustion_surfaces_eio_with_bounded_subreads():
    """All remote sources dead-silent: the read surfaces EIO exactly
    as before hedging, and the combined hedge x retry sub-read count
    stays inside the pinned bound."""
    async def main():
        inj = MessageFaultInjector(seed=5)
        c = await make_hedged_cluster(
            faults=inj,
            osd_config={"osd_ec_read_timeout": 0.3,
                        "osd_ec_read_retries": 1,
                        "osd_ec_read_backoff": 0.01,
                        "osd_ec_hedge_delay_max": 0.05})
        try:
            await c.osd_op("ecpool", "dead", [
                {"op": "write", "off": 0, "data": b"z" * 8192}])
            sub0 = _hedge_counters(c, "subreads")
            inj.drop(mtype="ec_subop_read", direction=RECV)
            reply = await c.osd_op(
                "ecpool", "dead",
                [{"op": "read", "off": 0, "len": None}],
                timeout=20, retries=1)
            assert reply.data.get("err") == "EIO" or \
                not reply.data["results"][0].get("ok")
            # bound: rounds x (plan + h) -- retries(1) + acting(3) + 1
            # rounds, <= 2 remote plan shards + 2 hedge extras each
            width, h, rounds = 3, 2, 1 + 3 + 1
            assert 0 < _hedge_counters(c, "subreads") - sub0 \
                <= rounds * (width - 1 + h)
        finally:
            await c.stop()
    run(main())


def test_scrub_collects_maps_not_shards_and_stays_clean():
    """An erasure scrub is shard-local: every OSD digests its own
    shard and only the maps travel, so a healthy PG's scrub makes no
    sub-read at all (it used to pull every remote shard to the
    primary through the hedged sub-read machinery)."""
    async def main():
        c = await make_hedged_cluster()
        try:
            from ceph_tpu.osd.scrub import scrub_pg
            data = bytes(range(256)) * 24
            await c.osd_op("ecpool", "sc", [
                {"op": "write", "off": 0, "data": data}])
            pgid, primary, _ = c.target_for("ecpool", "sc")
            prim = next(o for o in c.osds if o.whoami == primary)
            sub0 = _hedge_counters(c, "subreads")
            res = await scrub_pg(prim.pgs[pgid], repair=False)
            assert res.clean and res.objects_scrubbed == 1
            assert _hedge_counters(c, "subreads") == sub0, \
                "a healthy PG's scrub read shards from its peers"
            assert prim.perf_scrub.get("map_bytes") > 0
        finally:
            await c.stop()
    run(main())


@pytest.mark.slow
def test_kill_plus_delay_drive_zero_failed_ops():
    """The ISSUE acceptance drive: one OSD killed AND a heavy-tail
    straggler armed on a survivor's replies; every read completes
    byte-identical (zero failed/wedged ops) with hedges_fired > 0."""
    async def main():
        inj = MessageFaultInjector(seed=23)
        c = await make_hedged_cluster(n_osds=4, pg_num=16, faults=inj)
        try:
            rng = random.Random(9)
            objs = {}
            for i in range(16):
                size = rng.randrange(4 << 10, 24 << 10)
                data = rng.getrandbits(8 * size).to_bytes(size,
                                                          "little")
                objs[f"kd-{i}"] = data
                await c.osd_op("ecpool", f"kd-{i}",
                               [{"op": "write", "off": 0,
                                 "data": data}])
            for oid in objs:        # warm EWMAs
                await c.osd_op("ecpool", oid,
                               [{"op": "read", "off": 0, "len": None}])
            victim = c.osds[-1]
            vid = victim.whoami
            await victim.stop()
            for _ in range(100):
                if not c.mon.osdmap.is_up(vid):
                    break
                await asyncio.sleep(0.2)
            assert not c.mon.osdmap.is_up(vid)
            # every surviving peer's read replies go heavy-tail: every
            # degraded gather now races stragglers on ALL sources
            inj.straggler("osd.", dist="pareto", scale=0.08,
                          alpha=1.2, cap=1.5,
                          mtype="ec_subop_read_reply", direction=RECV)
            bad, wedged = [], []
            for oid, want in objs.items():
                try:
                    reply = await asyncio.wait_for(
                        c.osd_op("ecpool", oid,
                                 [{"op": "read", "off": 0,
                                   "len": None}],
                                 timeout=10, retries=8),
                        timeout=60)
                except (TimeoutError, asyncio.TimeoutError):
                    wedged.append(oid)
                    continue
                r, data = read_result(reply)
                if not r.get("ok") or data != want:
                    bad.append(oid)
            assert not wedged, f"wedged reads: {wedged}"
            assert not bad, f"corrupted reads: {bad}"
            assert _hedge_counters(c, "hedges_fired") > 0
        finally:
            await c.stop()
    run(main())
