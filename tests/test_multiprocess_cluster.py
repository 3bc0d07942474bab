"""Tier-3 standalone test: a REAL multi-process cluster (one OS
process per daemon, TCP between them), driven end-to-end with a
SIGKILL'd OSD process recovering on its durable BlockStore -- the
qa/standalone/ceph-helpers.sh shape the single-process integration
tests cannot cover."""

import asyncio
import os
import signal
import socket
import subprocess
import sys
import time

import pytest

from test_client import run

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _spawn(args: list[str]) -> subprocess.Popen:
    # one daemon per process: on the CPU so that no two of them reach
    # for one accelerator (a chip belongs to one process at a time)
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO}
    return subprocess.Popen(
        [sys.executable, "-m", "ceph_tpu.tools.vstart", *args],
        cwd=REPO, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)


def _wait_line(proc: subprocess.Popen, needle: str,
               timeout: float = 60.0) -> str:
    t0 = time.time()
    while time.time() - t0 < timeout:
        line = proc.stdout.readline()
        if not line:
            raise AssertionError(f"daemon exited: rc={proc.poll()}")
        if needle in line:
            return line
    raise AssertionError(f"timed out waiting for {needle!r}")



def test_multiprocess_cluster_io_and_osd_process_crash(tmp_path):
    mon_port = _free_port()
    procs: list[subprocess.Popen] = []
    store = str(tmp_path)
    try:
        mon = _spawn(["--role", "mon", "--mon-port", str(mon_port),
                      "--store-dir", store,
                      "--min-down-reporters", "1"])
        procs.append(mon)
        _wait_line(mon, "mon.0 at")

        osds = []
        for i in range(3):
            p = _spawn(["--role", "osd", "--mon-addr",
                        f"127.0.0.1:{mon_port}", "--osd-index", str(i),
                        "--store", "block", "--store-dir", store])
            procs.append(p)
            osds.append(p)
            _wait_line(p, "up (block store)")

        async def client_io():
            from ceph_tpu.client import Rados
            rados = await Rados(("127.0.0.1", mon_port)).connect()
            try:
                await rados.pool_create("p", pg_num=4, size=3,
                                        min_size=2)
                io = await rados.open_ioctx("p")
                for i in range(20):
                    await io.write_full(f"obj-{i}",
                                        f"payload-{i}".encode() * 50)
                # SIGKILL a daemon PROCESS mid-flight
                victim = osds[1]
                victim.send_signal(signal.SIGKILL)
                victim.wait()
                # writes continue against the surviving replicas
                for i in range(20, 35):
                    await io.write_full(f"obj-{i}",
                                        f"payload-{i}".encode() * 50)
                # restart the SAME daemon on its durable store: it
                # must reclaim its id and recover the missed writes
                p = _spawn(["--role", "osd", "--mon-addr",
                            f"127.0.0.1:{mon_port}", "--osd-index",
                            "1", "--store", "block", "--store-dir",
                            store])
                procs.append(p)
                osds[1] = p
                _wait_line(p, "up (block store)")
                # every byte still readable through the cluster
                for i in range(35):
                    got = await io.read(f"obj-{i}")
                    assert got == f"payload-{i}".encode() * 50, i
                out = await rados.mon_command("status")
                assert out["num_osds"] >= 3 if "num_osds" in out \
                    else True
            finally:
                await rados.shutdown()

        run(asyncio.wait_for(client_io(), 120))
    finally:
        for p in procs:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
