"""Offline tools: osdmaptool, ceph-objectstore-tool, ceph-monstore-tool
(src/tools/{osdmaptool,ceph_objectstore_tool,ceph-monstore-tool}).

Artifacts come from a REAL durable cluster: boot, write, stop, then
operate on the files the daemons left behind."""

import asyncio
import json
import os
import sys

import pytest

from ceph_tpu.client import Rados
from ceph_tpu.mon import Monitor
from ceph_tpu.os.store import DBStore
from ceph_tpu.osd import OSD
from ceph_tpu.tools import monstore_tool, objectstore_tool, osdmaptool

from test_client import run, teardown


async def durable_cluster(tmp_path, n=3):
    mon = Monitor(rank=0,
                  store_path=os.path.join(tmp_path, "mon.db"),
                  config={"mon_osd_min_down_reporters": 1})
    addr = await mon.start()
    mon.peer_addrs = [addr]
    osds = []
    for i in range(n):
        store = DBStore(os.path.join(tmp_path, f"osd{i}.db"))
        o = OSD(host=f"host{i}", store=store)
        await o.start(addr)
        osds.append(o)
    return mon, osds


def test_offline_tools_roundtrip(tmp_path, capsys):
    async def main():
        mon, osds = await durable_cluster(str(tmp_path))
        rados = await Rados(mon.msgr.addr).connect()
        await rados.pool_create("p", pg_num=4, size=3)
        io = await rados.open_ioctx("p")
        for i in range(12):
            await io.write_full(f"obj{i}", f"payload-{i}".encode())
        mapdump = await rados.mon_command("osd dump", {})
        await teardown(mon, osds, rados)
        return mapdump

    mapdump = run(main())
    map_path = os.path.join(tmp_path, "map.json")
    with open(map_path, "w") as f:
        json.dump(mapdump, f)

    # -- osdmaptool ------------------------------------------------------
    assert osdmaptool.main([map_path, "--print"]) == 0
    out = capsys.readouterr().out
    assert "pool 1 'p'" in out and "osd.0" in out
    assert osdmaptool.main([map_path, "--test-map-pgs"]) == 0
    out = capsys.readouterr().out
    assert "pool pg count: 4" in out and "size 3\t4" in out
    upmap_path = os.path.join(tmp_path, "upmap.txt")
    assert osdmaptool.main([map_path, "--upmap", upmap_path]) == 0

    # -- objectstore-tool ------------------------------------------------
    db0 = os.path.join(tmp_path, "osd0.db")
    assert objectstore_tool.main(
        ["--data-path", db0, "--op", "list"]) == 0
    listing = [json.loads(line)
               for line in capsys.readouterr().out.splitlines()]
    pg_objs = [(pg, oid) for pg, oid in listing
               if oid.startswith("obj")]
    assert pg_objs, "osd.0 holds no client objects?"
    pgid, oid = pg_objs[0]
    assert objectstore_tool.main(
        ["--data-path", db0, "--op", "dump", "--pgid", pgid,
         "--oid", oid]) == 0
    rec = json.loads(capsys.readouterr().out)
    assert bytes.fromhex(rec["data"]).startswith(b"payload-")
    # PG meta decodes (denc path)
    assert objectstore_tool.main(
        ["--data-path", db0, "--op", "meta", "--pgid", pgid]) == 0
    meta = json.loads(capsys.readouterr().out)
    assert meta["info"]["pgid"] == pgid
    assert meta["log"]["entries"] > 0
    # export -> remove -> import restores the object byte-exact
    export_path = os.path.join(tmp_path, "pg.export")
    assert objectstore_tool.main(
        ["--data-path", db0, "--op", "export", "--pgid", pgid,
         "--file", export_path]) == 0
    capsys.readouterr()
    assert objectstore_tool.main(
        ["--data-path", db0, "--op", "remove", "--pgid", pgid,
         "--oid", oid]) == 0
    st = DBStore(db0)
    st.mount()
    assert oid not in st.list_objects(f"pg_{pgid}")
    del st
    assert objectstore_tool.main(
        ["--data-path", db0, "--op", "import",
         "--file", export_path]) == 0
    st = DBStore(db0)
    st.mount()
    assert st.read(f"pg_{pgid}", oid) == bytes.fromhex(rec["data"])
    capsys.readouterr()

    # -- monstore-tool ---------------------------------------------------
    mon_db = os.path.join(tmp_path, "mon.db")
    assert monstore_tool.main([mon_db, "dump-versions"]) == 0
    out = capsys.readouterr().out
    assert "last_committed:" in out and "version 1" in out
    assert monstore_tool.main([mon_db, "get-version", "1"]) == 0
    json.loads(capsys.readouterr().out)       # valid incremental json
    assert monstore_tool.main([mon_db, "get-osdmap"]) == 0
    final_map = json.loads(capsys.readouterr().out)
    # the replayed offline map matches what the live mon reported
    assert final_map["epoch"] == mapdump["epoch"]
    assert [s["name"] for s in final_map["pools"].values()] == ["p"]
    # ...and feeds straight back into osdmaptool
    replay_path = os.path.join(tmp_path, "replayed.json")
    with open(replay_path, "w") as f:
        json.dump(final_map, f)
    assert osdmaptool.main([replay_path, "--test-map-pgs"]) == 0
    assert "pool pg count: 4" in capsys.readouterr().out


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_crush_bench_cli_times_verified_launches():
    """python -m ceph_tpu.tools.crush_bench (BASELINE config 5's own
    entry point) runs end to end: exit 0, a mapping rate, and the
    verified lanes sampled from the timed launches."""
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-m", "ceph_tpu.tools.crush_bench",
         "--pgs", "20000", "--batch", "10000", "--verify", "16"],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=240)
    assert r.returncode == 0, r.stderr[-2000:]
    res = json.loads(r.stdout.strip().splitlines()[-1])
    assert res["metric"] == "crush_bulk_mappings_per_s"
    assert res["value"] > 0
    assert res["n_mappings"] == 20000 and res["launches"] == 2
    assert res["verified_lanes"] == 16
    assert res["lane_exact_vs_scalar"] is True
