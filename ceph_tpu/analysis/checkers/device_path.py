"""device-path-host-sync: no host syncs reachable from batch launches.

The batched data plane only pays off if a launch stays on device from
submission to fan-out: one stray ``np.asarray`` / ``.item()`` /
``.block_until_ready()`` / ``bytes()`` inside the launch closure
re-serializes the whole batch through the host and silently turns the
amortized round trip back into a per-op one.  PR 5's
``scalar_calls_on_batched_paths=0`` perf-counter gate proves this
dynamically -- but only on the paths the bench happens to drive.  This
rule is the static closure of the same invariant: starting from the
launch entry points (the submit API of ``CodecBatcher``, the batched
``StripeInfo`` drivers riding it, the bulk ``VectorCrush`` mapper, and
the ``crc32c_batch`` engines), every function reachable through call
edges of fan-out <= 4 is "on the batched device path", and host-sync
operations there are findings.

Two precision fences keep the closure on the data plane it guards:

* the traversal never leaves *device-plane modules* (modules that
  import numpy or jax at the top level) -- a call that escapes into
  the transaction/messaging layers has already crossed the one
  intended host boundary, and everything past it is host code by
  construction;
* ``bytes()`` only counts in jax-importing modules -- it forces a
  transfer only when its argument can be a device array, and device
  arrays do not flow through modules that never touch jax.

Deliberate host hops (the single post-launch materialization, the
host fallback for non-batch codecs, the host CRC engine) carry a
``# lint: disable=device-path-host-sync`` with a one-line
justification -- the suppression is the documentation that the hop
was a decision, not an accident.
"""

from __future__ import annotations

import ast
from typing import Iterable

from .. import astutil
from ..callgraph import CallGraph, own_nodes
from ..core import Finding
from ..registry import ProjectChecker, register

# the launch entry points of the batched data plane, by Class.method
# (or bare function) name; the dynamic scalar-call gate exercises
# exactly these (tests/test_crc_batch.py)
ROOTS = (
    "CodecBatcher.encode",
    "CodecBatcher.decode",
    "MeshCodec.encode",
    "MeshCodec.decode",
    "MeshCodec.rmw",
    "StripeInfo.encode_async",
    "StripeInfo.decode_async",
    "StripeInfo.reconstruct_logical_async",
    "VectorCrush.map_pgs",
    "crc32c_batch",
    "crc32c_rows",
    "crc32c_device_chunks",
    # the XOR-schedule compiler's launch entry points
    # (ops/xor_schedule.py): the batched scheduled kernel family and
    # the host scheduled executor the BitMatrixCodec data path rides
    "sched_matmul_batch_device",
    "scheduled_xor_matmul",
    # the hedged gather spine (osd/hedged_gather.py): reply buffers
    # flow straight into decode launches, so a stray host sync in the
    # engine re-serializes every gather.  (The ECBackend fetch shims
    # around it are NOT rooted: they call into minimum_to_decode
    # PLANNING code, whose host-side GF algebra is legitimate.)
    "HedgedGather.gather_shards",
    "HedgedGather.first_reply",
    # the pipelined launch spine (PR 12): the staged launch driver
    # owns the dispatch/materialize split -- a stray host sync inside
    # it would close the overlap window the double-buffering opens
    "CodecBatcher._drive",
    "CodecBatcher._dispatch",
    "CodecBatcher._complete",
    # the flat linear codec family (ec/linear_codec.py): lrc/pmsr
    # encode/decode ride the batched scheduled/dense kernels through
    # these -- a host hop inside any of them re-serializes every
    # layered/regenerating launch
    "LinearSubchunkCodec.encode_batch",
    "LinearSubchunkCodec.decode_batch",
    "LinearSubchunkCodec._batch_matmul",
)

# ambiguity budget: a fuzzy call edge that could hit more than this
# many same-named functions is noise, not the device path
MAX_FANOUT = 4

_NUMPY_SYNCS = {"asarray", "array", "copyto"}


def _imports_top(tree: ast.AST, *tops: str) -> bool:
    """True if the module imports any of the given top-level packages
    (``import jax.numpy`` and ``from jax import numpy`` both count as
    ``jax``)."""
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            heads = [a.name.split(".", 1)[0] for a in node.names]
        elif isinstance(node, ast.ImportFrom) and not node.level:
            heads = [(node.module or "").split(".", 1)[0]]
        else:
            continue
        if any(h in tops for h in heads):
            return True
    return False


@register
class DevicePathHostSync(ProjectChecker):
    name = "device-path-host-sync"
    description = ("np.asarray/.item()/.block_until_ready()/bytes() "
                   "transitively reachable from batched launch entry "
                   "points (static form of the "
                   "scalar_calls_on_batched_paths=0 gate)")

    def check_project(self, graph: CallGraph) -> Iterable[Finding]:
        # device plane: where arrays flow
        in_scope = {
            path for path, syms in graph.symbols.items()
            if _imports_top(syms.module.tree, "numpy", "jax")}
        jax_scope = {
            path for path in in_scope
            if _imports_top(graph.symbols[path].module.tree, "jax")}
        roots: list[str] = []
        root_of: dict[str, str] = {}
        for spec in ROOTS:
            for qual in graph.lookup(spec):
                if graph.functions[qual].path in in_scope:
                    roots.append(qual)
                    root_of[qual] = spec
        if not roots:
            return
        # BFS with origin tracking so the finding can say WHICH entry
        # point makes the sync reachable
        seen: dict[str, str] = {}
        stack = [(q, root_of[q]) for q in roots]
        while stack:
            cur, origin = stack.pop()
            if cur in seen:
                continue
            seen[cur] = origin
            for dst, fo in graph.calls.get(cur, {}).items():
                fi = graph.functions.get(dst)
                if (fo <= MAX_FANOUT and dst not in seen
                        and fi is not None and fi.path in in_scope):
                    stack.append((dst, origin))
        for qual, origin in sorted(seen.items()):
            fi = graph.functions.get(qual)
            if fi is None:
                continue
            syms = graph.symbols[fi.path]
            allow_bytes = fi.path in jax_scope
            for node in own_nodes(fi.node):
                if isinstance(node, ast.Call):
                    msg = self._sync_kind(node, syms, allow_bytes)
                    if msg:
                        yield Finding(
                            fi.path, node.lineno, self.name,
                            f"{msg} on the batched device path "
                            f"(reachable from {origin}): forces a "
                            f"device->host sync per call -- keep the "
                            f"batch on device, hoist the hop to the "
                            f"single post-launch materialization, or "
                            f"justify with a disable comment")

    @staticmethod
    def _sync_kind(node: ast.Call, syms,
                   allow_bytes: bool) -> str | None:
        func = node.func
        if isinstance(func, ast.Attribute):
            attr = func.attr
            if attr == "block_until_ready":
                return ".block_until_ready()"
            if attr == "item" and not node.args:
                return ".item()"
            if attr in _NUMPY_SYNCS:
                base = astutil.dotted(func.value)
                if base and syms.expand_alias(
                        base.split(".", 1)[0]) == "numpy":
                    return f"np.{attr}"
            return None
        if isinstance(func, ast.Name):
            if (allow_bytes and func.id == "bytes"
                    and len(node.args) == 1
                    and not isinstance(node.args[0], ast.Constant)):
                return "bytes()"
            if (func.id in _NUMPY_SYNCS
                    and syms.expand_alias(func.id).startswith(
                        "numpy.")):
                return f"np.{func.id}"
        return None
