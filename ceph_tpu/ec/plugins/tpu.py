"""The `tpu` erasure-code plugin: ISA-semantics RS/Cauchy on the MXU.

Registers behind the same registry/interface boundary as every other
plugin, so the benchmark harness and the OSD EC backend pick it up by
profile name alone (the reference selects plugins the same way:
src/test/erasure-code/ceph_erasure_code_benchmark.cc:170).  Parity bytes
are identical to the `isa` plugin (same generator matrices, same GF(2^8)
field); only the execution engine differs: stripes are batched into one
MXU bit-matmul launch (see ceph_tpu/ops/gf2kernels.py).
"""

from __future__ import annotations

import numpy as np

from .isa import ErasureCodeIsa, K_VANDERMONDE
from ..registry import ErasureCodePlugin
from ...ops.jax_backend import JaxBackend


class ErasureCodeTpu(ErasureCodeIsa):
    def __init__(self, technique: str = K_VANDERMONDE) -> None:
        super().__init__(technique=technique, backend=JaxBackend())

    # -- batched entry points (OSD CodecBatcher / bench fast path) ----------
    def encode_batch(self, data: np.ndarray, out_np: bool = False):
        """(B, k, L) data chunks -> (B, m, L) parity chunks, one launch."""
        return self.backend.matmul_batch(
            self.encode_matrix[self.k:], data, out_np=out_np)

    def decode_signature(self, erasures) -> str:
        """DecodeTableCache key for an erasure pattern.  Also the
        grouping key the per-OSD CodecBatcher uses to decide which
        reconstruction submissions may share a decode_batch launch
        (same signature = same decode matrix = same math)."""
        from ...gf import erasure_signature
        from ...gf.matrices import decode_index_for
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

    def decode_matrix_for(self, erasures) -> np.ndarray:
        """The decode matrix an erasure pattern selects, through the
        DecodeTableCache.  Shared by ``decode_batch`` and the sharded
        MeshCodec decode path, so both launch engines compute with the
        identical matrix (byte parity by construction)."""
        from ...gf import build_decode_matrix
        signature = self.decode_signature(erasures)
        entry = self.tcache.get(signature)
        if entry is None:
            matrix, decode_index = build_decode_matrix(
                self.encode_matrix, self.k, list(erasures))
            self.tcache.put(signature, matrix, decode_index)
        else:
            matrix, decode_index = entry
        return matrix


def _factory(profile):
    return ErasureCodeTpu(profile.get("technique", K_VANDERMONDE))


def __erasure_code_init__(registry, name: str) -> None:
    registry.add(name, ErasureCodePlugin(_factory))
