"""``tracing.section``: what the loop's thread is doing, as host events
of a profiler session.  A section is synchronous work: it never holds
an ``await`` or a ``yield``, it is a shared no-op until jax is loaded,
and its name starts with one of the layers the benchmark's metrics
read.  Also the names the device programs carry in a trace, and the
batcher's three wait counters.
"""

from __future__ import annotations

import ast
import asyncio
import re
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from ceph_tpu.common import tracing

ROOT = Path(__file__).resolve().parents[1]
# tracing.py among them: the event loop's own three sections are there
SECTIONED = sorted(
    str(p.relative_to(ROOT)) for p in (ROOT / "ceph_tpu").rglob("*.py")
    if "section(" in p.read_text())


def _is_section(item: ast.withitem) -> bool:
    call = item.context_expr
    if not isinstance(call, ast.Call):
        return False
    fn = call.func
    return (isinstance(fn, ast.Name) and fn.id == "section") or (
        isinstance(fn, ast.Attribute) and fn.attr == "section")


def section_faults(source: str) -> list[str]:
    """Lines where a ``with section(...)`` holds an await or a yield,
    is itself ``async with``, or carries a name outside the layers: a
    name is ``<layer>.<what>``, or ``<layer>.<what>.<part>`` for a part
    of ``<layer>.<what>``, which the same module opens around it (a
    reader that sums by name prefix then reads the whole as before)."""
    faults = []
    names: dict[str, int] = {}
    for node in ast.walk(ast.parse(source)):
        if not isinstance(node, (ast.With, ast.AsyncWith)):
            continue
        items = [i for i in node.items if _is_section(i)]
        if not items:
            continue
        if isinstance(node, ast.AsyncWith):
            faults.append(f"{node.lineno}: async with section")
        for item in items:
            arg = item.context_expr.args[0]
            if not (isinstance(arg, ast.Constant)
                    and isinstance(arg.value, str)
                    and arg.value.split(".")[0] in tracing.SECTION_LAYERS
                    and len(arg.value.split(".")) in (2, 3)
                    and all(arg.value.split("."))):
                faults.append(f"{node.lineno}: section name "
                              f"{ast.unparse(arg)}")
            else:
                names.setdefault(arg.value, node.lineno)
        for stmt in node.body:
            for inner in ast.walk(stmt):
                if isinstance(inner, (ast.FunctionDef, ast.AsyncFunctionDef,
                                      ast.Lambda)):
                    faults.append(f"{inner.lineno}: a function defined "
                                  f"inside a section")
                if isinstance(inner, (ast.Await, ast.Yield, ast.YieldFrom,
                                      ast.AsyncWith, ast.AsyncFor)):
                    faults.append(f"{inner.lineno}: "
                                  f"{type(inner).__name__} inside a section")
    for name, lineno in names.items():
        if name.count(".") == 2 and name.rpartition(".")[0] not in names:
            faults.append(f"{lineno}: section {name} is a part of no "
                          f"section this module opens")
    return faults


def test_sections_are_placed():
    assert len(SECTIONED) >= 9, SECTIONED
    assert "ceph_tpu/common/tracing.py" in SECTIONED


def test_the_loop_layer_has_its_three_sections_and_no_others():
    """``loop`` is a layer like the others, and its sections are the
    probe's: nothing else in the program opens a ``loop.*`` section."""
    assert "loop" in tracing.SECTION_LAYERS
    found = {}
    for path in SECTIONED:
        for node in ast.walk(ast.parse((ROOT / path).read_text())):
            if isinstance(node, ast.With):
                for i in node.items:
                    if _is_section(i) and i.context_expr.args[0].value \
                            .startswith("loop."):
                        found[i.context_expr.args[0].value] = path
    assert found == {name: "ceph_tpu/common/tracing.py" for name in (
        "loop.select", "loop.read_ready", "loop.write_ready")}


@pytest.mark.parametrize("path", SECTIONED)
def test_no_await_or_yield_inside_a_section(path):
    assert section_faults((ROOT / path).read_text()) == []


def test_the_wire_layer_has_a_section_for_each_way_through_it():
    """``wire.recv`` is the protocol's ``buffer_updated``: parse, crc,
    decode and deliver of every frame that arrived, so what asyncio's
    stream reader did outside any section is the wire layer's now;
    ``wire.write`` holds the hand-over to the transport."""
    names = set()
    for path in ("ceph_tpu/msg/message.py", "ceph_tpu/msg/messenger.py"):
        for node in ast.walk(ast.parse((ROOT / path).read_text())):
            if isinstance(node, ast.With):
                names.update(i.context_expr.args[0].value
                             for i in node.items if _is_section(i))
    assert names == {"wire.encode", "wire.crc", "wire.decode",
                     "wire.deliver", "wire.write", "wire.recv"}
    from ceph_tpu.msg.messenger import FrameProtocol
    src = ast.parse((ROOT / "ceph_tpu/msg/messenger.py").read_text())
    (cls,) = [n for n in src.body if isinstance(n, ast.ClassDef)
              and n.name == FrameProtocol.__name__]
    inside = {fn.name: {i.context_expr.args[0].value
                        for w in ast.walk(fn) if isinstance(w, ast.With)
                        for i in w.items if _is_section(i)}
              for fn in cls.body if isinstance(fn, ast.FunctionDef)}
    assert inside["buffer_updated"] == {"wire.recv"}
    assert inside["write"] == {"wire.write"}


@pytest.mark.parametrize("body,fault", [
    ("async def f():\n  with section('wire.x'):\n    await g()\n", "Await"),
    ("def f():\n  with tracing.section('wire.x'):\n    yield 1\n", "Yield"),
    ("async def f():\n  async with section('wire.x'):\n    pass\n",
     "async with"),
    ("def f():\n  with section('nolayer.x'):\n    pass\n", "section name"),
    ("def f():\n  with section(name):\n    pass\n", "section name"),
    ("def f():\n  with section('wire.x.y.z'):\n    pass\n", "section name"),
    ("def f():\n  with section('wire..y'):\n    pass\n", "section name"),
    ("def f():\n  with section('wire.x.y'):\n    pass\n", "a part of no"),
])
def test_the_section_check_finds_what_it_looks_for(body, fault):
    assert any(fault in f for f in section_faults(body))


def test_a_part_of_a_section_is_named_under_it():
    ok = ("def f():\n  with section('wire.x'):\n    g()\n"
          "def g():\n  with section('wire.x.y'):\n    pass\n")
    assert section_faults(ok) == []


def test_the_registry_layer_has_these_sections_and_no_others():
    """The caller's thread, the worker's, and the parts of a landing:
    ``registry.drain.kernel`` / ``.link`` / ``.land`` inside
    ``registry.drain``, ``registry.gather.wait`` inside the worker's
    ``registry.gather``.  PERF.md section 3 says who reads each."""
    assert "registry" in tracing.SECTION_LAYERS
    found: dict[str, set] = {}
    for path in SECTIONED:
        for node in ast.walk(ast.parse((ROOT / path).read_text())):
            if isinstance(node, ast.With):
                for i in node.items:
                    if _is_section(i) and i.context_expr.args[0].value \
                            .startswith("registry."):
                        found.setdefault(i.context_expr.args[0].value,
                                         set()).add(path)
    whole = ["copy_out", "device_wait", "drain", "gather", "launch",
             "marshal", "matrix", "prepare", "upload"]
    parts = ["drain.kernel", "drain.land", "drain.link", "gather.wait"]
    assert sorted(found) == sorted(f"registry.{name}"
                                   for name in whole + parts)
    # the slab loop is one function's: every part and its whole are there
    for name in parts + ["drain", "copy_out", "gather", "marshal", "upload",
                         "launch", "device_wait"]:
        assert found[f"registry.{name}"] == {"ceph_tpu/ops/gf2kernels.py"}
    assert found["registry.prepare"] == {"ceph_tpu/ec/plugins/tpu.py"}


def test_section_is_a_shared_noop_until_jax_is_loaded():
    """In a fresh interpreter: the same no-op object while ``jax`` is
    absent from ``sys.modules`` (and importing the tracing module does
    not load it), a profiler annotation after."""
    code = (
        "import sys\n"
        "from ceph_tpu.common import tracing\n"
        "assert 'jax' not in sys.modules\n"
        "a, b = tracing.section('wire.x'), tracing.section('client.y')\n"
        "assert a is b is tracing._NO_SECTION\n"
        "with a:\n    pass\n"
        "assert 'jax' not in sys.modules\n"
        "import jax\n"
        "c = tracing.section('wire.x')\n"
        "assert isinstance(c, jax.profiler.TraceAnnotation), c\n"
        "with c:\n    pass\n"
        "print('ok')\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                         capture_output=True, text=True, timeout=120,
                         env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
                              "PYTHONPATH": str(ROOT)})
    assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr


def test_spans_keep_their_dump_and_take_ids_from_a_counter():
    t = tracing.Tracer("osd.77")
    root = t.start("client.osd_op", oid="o").activate()
    kid = tracing.child_span("ec.encode")
    assert tracing.child_span("x") is not None
    kid.finish()
    root.finish()
    assert tracing.child_span("x") is None          # no op trace active
    tracing.finish(None)
    d_root, d_kid = root.to_dict(), kid.to_dict()
    assert set(d_kid) == {"trace_id", "span_id", "parent_id", "name",
                          "daemon", "start", "end", "duration_ms", "tags"}
    assert isinstance(root.start, int) and isinstance(root.end, int)
    assert d_kid["parent_id"] == d_root["span_id"]
    assert d_kid["trace_id"] == d_root["trace_id"]
    assert d_kid["span_id"].startswith("osd.77.")
    assert len({d_root["span_id"], d_kid["span_id"], d_root["trace_id"]}) == 3
    assert d_root["duration_ms"] == (root.end - root.start) / 1e6 >= 0
    assert d_root["end"] - d_root["start"] == pytest.approx(
        d_root["duration_ms"] / 1e3, abs=1e-5)
    assert [s["name"] for s in t.dump()] == ["ec.encode", "client.osd_op"]
    # two tracers of one name (two processes) do not share ids
    assert tracing.Tracer("osd.77").next_id() != tracing.Tracer(
        "osd.77").next_id()


def test_batcher_counts_queue_overlap_and_materialize_microseconds():
    from ceph_tpu.common.perf import PerfCounters
    from ceph_tpu.ec import registry
    from ceph_tpu.osd.codec_batcher import CodecBatcher

    codec = registry().factory("tpu", {"k": "2", "m": "1",
                                       "technique": "reed_sol_van"})
    perf = PerfCounters("ec_batch")
    batcher = CodecBatcher(perf=perf)
    rng = np.random.default_rng(5)

    async def main():
        await asyncio.gather(*(
            batcher.encode(codec, rng.integers(0, 256, (2, 2, 256),
                                               np.uint8), with_crc=True)
            for _ in range(3)))
        batcher.close()

    asyncio.run(main())
    dump = perf.dump()
    assert dump["batches"] >= 1
    for key in ("queue_wait_us", "overlap_us", "materialize_us"):
        assert isinstance(dump[key], int) and dump[key] >= 0, key
    assert dump["materialize_us"] > 0


@pytest.mark.parametrize("call,name", [
    ("encode_crc", "jit_ec_encode_crc"),
    ("encode", "jit_ec_encode"),
    ("decode_rows", "jit_ec_decode_rows"),
    ("rmw", "jit_ec_rmw"),
])
def test_mesh_programs_carry_their_own_names(call, name):
    import jax
    import jax.numpy as jnp
    from ceph_tpu.parallel import mesh_codec as mc

    mesh = mc._shared_mesh(1)
    w = jnp.zeros((8, 16), jnp.int8)
    data = jnp.zeros((2, 2, 64), jnp.uint8)
    if call == "rmw":
        fn = mc._compiled_rmw(mesh, 2, 1, 2, 64)
        text = fn.lower(w, jnp.zeros((2, 1, 64), jnp.uint8),
                        data).as_text(debug_info=True)
    else:
        fn = mc._compiled_apply(mesh, "ec_" + call.removesuffix("_crc"), 2,
                                2, 64, call.endswith("_crc"))
        text = fn.lower(w, data).as_text(debug_info=True)
    assert f"module @{name} " in text
    # a decode's matmul stands under its own scope, the others' under
    # gf_encode
    assert ("gf_decode" in text) == (name == "jit_" + mc.DECODE_PROGRAM)
    assert ("gf_encode" in text) == (name != "jit_" + mc.DECODE_PROGRAM)
    assert ("crc32c" in text) == call.endswith("_crc")
    del jax


def test_digest_program_carries_its_own_name_under_the_crc_scope():
    """A deep scrub's launch is ``jit_crc32c_shards`` in a trace, not
    the ``crc32c_chunks`` an encode inlines, and its operations stand
    under the ``crc32c`` scope."""
    import jax.numpy as jnp
    from ceph_tpu.ops.crc32c_batch import DIGEST_PROGRAM
    from ceph_tpu.parallel import mesh_codec as mc

    fn = mc._compiled_digest(mc._shared_mesh(1), 2, 4096)
    text = fn.lower(jnp.zeros((2, 4096), jnp.uint8)).as_text(
        debug_info=True)
    assert DIGEST_PROGRAM == "crc32c_shards"
    assert f"module @jit_{DIGEST_PROGRAM} " in text
    assert "crc32c" in text.replace(DIGEST_PROGRAM, "")
    assert "gf_encode" not in text and "gf_decode" not in text


def test_the_scrub_layer_has_its_five_sections():
    """``scrub.list``, ``.digest_host``, ``.digest_device``,
    ``.compare`` and ``.repair``, in the scrubber and where the PG
    lists a chunk under its lock; nothing else opens a ``scrub.*``."""
    assert "scrub" in tracing.SECTION_LAYERS
    found: dict[str, set] = {}
    for path in SECTIONED:
        for node in ast.walk(ast.parse((ROOT / path).read_text())):
            if isinstance(node, ast.With):
                for i in node.items:
                    if _is_section(i) and i.context_expr.args[0].value \
                            .startswith("scrub."):
                        found.setdefault(i.context_expr.args[0].value,
                                         set()).add(path)
    assert sorted(found) == ["scrub.compare", "scrub.digest_device",
                             "scrub.digest_host", "scrub.list",
                             "scrub.repair"]
    assert set().union(*found.values()) == {"ceph_tpu/osd/scrub.py",
                                            "ceph_tpu/osd/pg.py"}


@pytest.mark.parametrize("lanes", [8, 64], ids=["short", "long"])
def test_crush_program_carries_its_scopes(lanes, monkeypatch):
    """``straw2_draw`` in every launch; ``crush_retry`` around the narrow
    stage (the count of the lanes left, their compaction, the narrow
    loop with its draws, the scatter back) of a launch long enough to
    have one."""
    import jax
    import jax.numpy as jnp
    import ceph_tpu.crush.vectorized as V
    from ceph_tpu.crush.builder import build_two_level_map

    monkeypatch.setattr(V, "RETRY_MIN_LANES", 64)
    vc = V.VectorCrush(build_two_level_map(4, 2), 0)
    text = vc.crush_firstn.lower(
        vc, jnp.arange(lanes, dtype=jnp.int32), 2,
        jnp.full((8,), 0x10000, jnp.int32)).as_text(debug_info=True)
    assert "module @jit_crush_firstn " in text
    assert "straw2_draw" in text
    # the narrow loop's draws nest inside it
    assert bool(re.search(r'crush_retry/[^"]*straw2_draw', text)) \
        == (lanes >= 64)
