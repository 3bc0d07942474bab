"""The picture of the system in a few boxes, held by a test.

The served path (client -> mon / osd -> msg, os, parallel, ec, crush ->
ops, gf -> common) imports nothing of what drives or measures it: the
operator tools, the in-process cluster, the static analysis, the
benchmark, the chip smoke.  And the bottom three packages know nothing
of the daemons above them.

Imports are read with ``ast`` from every file of a package, the ones
inside functions included, and relative ones resolved, so a lazy import
is an arrow like any other.
"""

import ast
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1] / "ceph_tpu"

SERVED = ("common", "gf", "ops", "ec", "crush", "parallel", "os", "msg",
          "osd", "mon", "client")

# what drives or measures the served path, as module prefixes
ABOVE = ("ceph_tpu.tools", "ceph_tpu.loadgen", "ceph_tpu.analysis",
         "benchmark", "bench", "chip_smoke")
DAEMONS = ("ceph_tpu.osd", "ceph_tpu.mon", "ceph_tpu.client")
BOTTOM = ("common", "gf", "ops")


def _imports(path: Path):
    """Absolute dotted names of every module ``path`` imports."""
    pkg = ("ceph_tpu",) + path.relative_to(ROOT).parts[:-1]
    for node in ast.walk(ast.parse(path.read_text(), str(path))):
        if isinstance(node, ast.Import):
            for alias in node.names:
                yield alias.name
        elif isinstance(node, ast.ImportFrom):
            base = node.module or ""
            if node.level:
                up = pkg[:len(pkg) - node.level + 1]
                base = ".".join(up + ((base,) if base else ()))
            yield base
            # ``from .. import loadgen`` names the module in the names
            for alias in node.names:
                yield f"{base}.{alias.name}"


def _under(name: str, prefixes) -> bool:
    return any(name == p or name.startswith(p + ".") for p in prefixes)


@pytest.mark.parametrize("package", SERVED)
def test_lower_layers_import_nothing_above_them(package):
    forbidden = ABOVE + (DAEMONS if package in BOTTOM else ())
    files = sorted((ROOT / package).rglob("*.py"))
    assert files, package
    arrows = set()
    for path in files:
        rel = path.relative_to(ROOT).as_posix()
        for name in _imports(path):
            if _under(name, forbidden):
                arrows.add((rel, name))
    assert not arrows, sorted(arrows)

