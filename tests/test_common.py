"""Common runtime: config registry, perf counters, admin socket, log."""

import asyncio
import functools
import io
import json
import os
import pathlib
import re

import pytest

from ceph_tpu.common import (
    AdminSocket, ConfigProxy, Logger, Option, OPT_BOOL, OPT_INT,
    PerfCounters, PerfCountersCollection,
)
from ceph_tpu.common.admin_socket import admin_command
from ceph_tpu.common.config import DEFAULT_SCHEMA


def run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


# -- config ------------------------------------------------------------------

def test_config_defaults_and_types():
    conf = ConfigProxy(read_env=False)
    assert conf["osd_max_backfills"] == 2
    conf.set("osd_max_backfills", "5")          # cast from string
    assert conf["osd_max_backfills"] == 5
    with pytest.raises(ValueError):
        conf.set("osd_max_backfills", "not-a-number")
    with pytest.raises(ValueError):
        conf.set("osd_heartbeat_grace", -1)      # below min
    with pytest.raises(KeyError):
        conf.get("no_such_option")


def test_config_observers():
    conf = ConfigProxy(read_env=False)
    seen = []
    conf.add_observer("osd_recovery_max_active",
                      lambda k, v: seen.append((k, v)))
    conf.set("osd_recovery_max_active", 7)
    assert seen == [("osd_recovery_max_active", 7)]


def test_config_env_and_file_layering(tmp_path, monkeypatch):
    f = tmp_path / "ceph.json"
    f.write_text(json.dumps({"osd_ec_batch_max": 32,
                             "mon_lease": 9.0}))
    monkeypatch.setenv("CEPH_TPU_MON_LEASE", "11.5")
    conf = ConfigProxy(conf_file=str(f))
    assert conf["osd_ec_batch_max"] == 32         # from file
    assert conf["mon_lease"] == 11.5               # env overrides file
    d = conf.describe("mon_lease")
    assert d["current"] == 11.5 and d["default"] == 5.0


def test_config_custom_schema():
    conf = ConfigProxy(schema=[
        Option("my_flag", OPT_BOOL, False),
        Option("my_level", OPT_INT, 1, enum_values=[1, 2, 3]),
    ], read_env=False)
    conf.set("my_flag", "yes")
    assert conf["my_flag"] is True
    with pytest.raises(ValueError):
        conf.set("my_level", 9)


# an option is a promise to an operator; each is kept by some code
# but one: the backfill cell's configuration sets it through
# ``osd_config`` (benchmark/configs/rs_k8m3_12osd_1out.json) and
# ``ConfigProxy.set`` raises on a name the schema lacks, so it stays
# until ROADMAP B-i 3 decides whether recovery honours it
_UNREAD = {"osd_recovery_max_active"}


@functools.cache
def _program_text() -> str:
    root = pathlib.Path(__file__).resolve().parents[1] / "ceph_tpu"
    return "\n".join(
        p.read_text() for p in sorted(root.rglob("*.py"))
        if p.relative_to(root).as_posix() != "common/config.py")


@pytest.mark.parametrize("name", [o.name for o in DEFAULT_SCHEMA])
def test_every_option_has_a_reader(name):
    """The option's name occurs, quoted, in some file of the program
    beside the schema itself."""
    read = re.search(rf"""["']{name}["']""", _program_text())
    assert (read is None) == (name in _UNREAD), name


# -- perf counters -----------------------------------------------------------

def test_perf_counters():
    pc = PerfCounters("osd")
    pc.inc("op")
    pc.inc("op", 4)
    pc.set_gauge("load", 0.5)
    pc.tinc("op_latency", 0.1)
    pc.tinc("op_latency", 0.3)
    pc.hist_register("op_size", [100, 1000])
    pc.hist_sample("op_size", 50)
    pc.hist_sample("op_size", 500)
    pc.hist_sample("op_size", 5000)
    d = pc.dump()
    assert d["op"] == 5
    assert d["load"] == 0.5
    assert d["op_latency"]["avgcount"] == 2
    assert abs(d["op_latency"]["avg"] - 0.2) < 1e-9
    assert d["op_size"]["counts"] == [1, 1, 1]


def test_perf_collection_and_timer():
    coll = PerfCountersCollection()
    pc = coll.create("paxos")
    with pc.time("commit_latency"):
        pass
    assert coll.dump()["paxos"]["commit_latency"]["avgcount"] == 1
    assert coll.create("paxos") is pc     # idempotent


# -- admin socket ------------------------------------------------------------

def test_admin_socket_roundtrip(tmp_path):
    async def main():
        sock = AdminSocket(str(tmp_path / "test.asok"))

        async def hello(req):
            return {"who": req.get("name", "world")}

        sock.register("hello", "greet", hello)
        path = await sock.start()
        try:
            result = await admin_command(path, "hello", name="ceph")
            assert result == {"who": "ceph"}
            helps = await admin_command(path, "help")
            assert "hello" in helps and "version" in helps
            with pytest.raises(RuntimeError, match="unknown command"):
                await admin_command(path, "frobnicate")
        finally:
            await sock.stop()
        assert not os.path.exists(path)
    run(main())


# -- logger ------------------------------------------------------------------

def test_logger_levels_and_ring():
    sink = io.StringIO()
    log = Logger(max_recent=3, sink=sink)
    log.set_level("osd", 5)
    log.info("osd", "visible")           # level 1 <= 5 -> emitted
    log.debug("osd", "hidden", level=10)  # 10 > 5 -> ring only
    out = sink.getvalue()
    assert "visible" in out and "hidden" not in out
    # ring keeps everything (bounded)
    log.info("osd", "a")
    log.info("osd", "b")
    msgs = [m for _, _, _, m in log.recent()]
    assert msgs == ["hidden", "a", "b"]      # maxlen 3 evicted "visible"
    dump = io.StringIO()
    log.dump_recent(sink=dump)
    assert "hidden" in dump.getvalue()


# -- daemon integration ------------------------------------------------------

def test_osd_admin_socket_live(tmp_path):
    from ceph_tpu.mon import Monitor
    from ceph_tpu.osd import OSD
    from ceph_tpu.client import Rados

    async def main():
        mon = Monitor(rank=0,
                      config={"mon_osd_min_down_reporters": 1},
                      admin_socket_path=str(tmp_path / "mon.asok"))
        addr = await mon.start()
        mon.peer_addrs = [addr]
        osds = []
        for i in range(3):
            osd = OSD(host=f"host{i}",
                      admin_socket_path=str(tmp_path / f"osd{i}.asok"))
            await osd.start(addr)
            osds.append(osd)
        rados = None
        try:
            rados = await Rados(addr).connect()
            await rados.pool_create("p", pg_num=4)
            io_ = await rados.open_ioctx("p")
            await io_.write_full("o1", b"x" * 1000)
            await io_.read("o1")
            # per-daemon introspection over the unix socket
            st = await admin_command(str(tmp_path / "osd0.asok"),
                                     "status")
            assert st["whoami"] == 0 and st["num_pgs"] >= 1
            found_op = False
            for i in range(3):
                perf = await admin_command(
                    str(tmp_path / f"osd{i}.asok"), "perf dump")
                if perf["osd"].get("op", 0) >= 2:
                    assert perf["osd"]["op_w"] >= 1
                    assert perf["osd"]["op_latency"]["avgcount"] >= 2
                    found_op = True
            assert found_op
            ops = await admin_command(str(tmp_path / "osd0.asok"),
                                      "dump_ops_in_flight")
            assert isinstance(ops["ops"], list)
            assert ops["num_ops"] == len(ops["ops"])
            mst = await admin_command(str(tmp_path / "mon.asok"),
                                      "mon_status")
            assert mst["leader"] is True
            mperf = await admin_command(str(tmp_path / "mon.asok"),
                                        "perf dump")
            assert mperf["paxos"]["commit"] >= 4   # boots + pool
        finally:
            if rados:
                await rados.shutdown()
            for o in osds:
                await o.stop()
            await mon.stop()
    run(main())
