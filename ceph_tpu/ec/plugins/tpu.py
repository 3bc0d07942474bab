"""The `tpu` erasure-code plugin: ISA-semantics RS/Cauchy on the MXU.

Registers behind the same registry/interface boundary as every other
plugin, so the benchmark harness and the OSD EC backend pick it up by
profile name alone (the reference selects plugins the same way:
src/test/erasure-code/ceph_erasure_code_benchmark.cc:170).  Parity bytes
are identical to the `isa` plugin (same generator matrices, same GF(2^8)
field); only the execution engine differs: stripes are batched into one
MXU bit-matmul launch (see ceph_tpu/ops/gf2kernels.py).

What a caller of the batch entry points paid is counted in the
plugin's ``ec_registry`` set (``codec.perf``): ``launches``, ``stripes``,
``bytes_in``, ``bytes_out``, ``engine_<name>`` (the engine that served,
per call), ``slabs`` / ``pipelined`` / ``staging_waits`` (how a call from
host memory to host memory streamed through the device),
``arena_hits`` / ``arena_misses`` (the result and the two staging
buffers of such a call, each borrowed from the process's host arena: a
buffer an earlier call's caller dropped, or a fresh allocation; a
result stays its caller's own until no array views it),
``parity_gates`` (first launches of a matrix held to the host oracle),
``table_hits`` / ``table_misses`` (decode matrices taken from, or built
into, the DecodeTableCache); where its thread was is in the
``registry.*`` sections (a table miss's ``matrix`` here, the rest in
``gf_matmul_batch_device``).
"""

from __future__ import annotations

import numpy as np

from .isa import ErasureCodeIsa, K_VANDERMONDE
from ..registry import ErasureCodePlugin
from ...common.perf import PerfCounters
from ...common.tracing import section
from ...gf import build_decode_matrix, erasure_signature
from ...gf.matrices import decode_index_for
from ...ops.jax_backend import JaxBackend


class ErasureCodeTpu(ErasureCodeIsa):
    def __init__(self, technique: str = K_VANDERMONDE) -> None:
        self.perf = PerfCounters("ec_registry")
        super().__init__(technique=technique, backend=JaxBackend(self.perf))

    # -- batched entry points (OSD CodecBatcher / bench fast path) ----------
    def encode_batch(self, data: np.ndarray, out_np: bool = False):
        """(B, k, L) data chunks -> (B, m, L) parity chunks: one launch,
        or from host memory to host memory (``out_np``) a pipeline of
        slabs (``gf_matmul_batch_device``)."""
        return self.backend.matmul_batch(
            self.encode_matrix[self.k:], data, out_np=out_np)

    def decode_signature(self, erasures) -> str:
        """DecodeTableCache key for an erasure pattern.  Also the
        grouping key the per-OSD CodecBatcher uses to decide which
        reconstruction submissions may share a decode_batch launch
        (same signature = same decode matrix = same math)."""
        return erasure_signature(
            decode_index_for(self.k, set(erasures)), list(erasures))

    def decode_batch(self, erasures: list[int], chunks: np.ndarray,
                     out_np: bool = False):
        """Recover ``erasures`` for a batch.

        ``chunks`` is (B, k, L): for every stripe, the k surviving chunks in
        decode_index order (first k surviving shard ids ascending).
        """
        matrix = self.decode_matrix_for(erasures)
        return self.backend.matmul_batch(matrix, chunks, out_np=out_np)

    def decode_stripes(self, erasures: list[int], stripes: np.ndarray,
                       out_np: bool = False):
        """Recover ``erasures`` from whole stripes in host memory.

        ``stripes`` is (B, k+m, L) with chunk i of every stripe at
        ``[:, i]`` (the chunk map a caller of ``decode`` hands over,
        for B stripes at once); what lies at an erased position is
        never read.  The k survivors are gathered in decode_index
        order on the way to the device (``gf_matmul_batch_device``'s
        ``rows``: slab by slab when the result is asked back to host
        memory, never the whole batch at once); the result is
        (B, len(erasures), L), row p the chunk ``erasures[p]``."""
        return self.backend.matmul_batch(
            self.decode_matrix_for(erasures), stripes,
            rows=decode_index_for(self.k, set(erasures)), out_np=out_np)

    def decode_matrix_for(self, erasures) -> np.ndarray:
        """The decode matrix an erasure pattern selects, through the
        DecodeTableCache.  Shared by ``decode_batch`` and the sharded
        MeshCodec decode path, so both launch engines compute with the
        identical matrix (byte parity by construction)."""
        signature = self.decode_signature(erasures)
        entry = self.tcache.get(signature)
        if entry is None:
            self.perf.inc("table_misses")
            with section("registry.matrix"):
                matrix, decode_index = build_decode_matrix(
                    self.encode_matrix, self.k, list(erasures))
            self.tcache.put(signature, matrix, decode_index)
        else:
            self.perf.inc("table_hits")
            matrix, decode_index = entry
        return matrix


def _factory(profile):
    return ErasureCodeTpu(profile.get("technique", K_VANDERMONDE))


def __erasure_code_init__(registry, name: str) -> None:
    registry.add(name, ErasureCodePlugin(_factory))
