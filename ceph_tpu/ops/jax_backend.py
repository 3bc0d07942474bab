"""RSMatrixCodec backend that executes on the TPU via the MXU bit-matmul."""

from __future__ import annotations

import numpy as np

from .gf2kernels import gf_matmul_device, gf_matmul_batch_device

# below this many bytes per chunk the host round-trip dominates: do it on CPU
HOST_FALLBACK_BYTES = 0  # parity-critical: keep everything on one code path


class JaxBackend:
    name = "jax"

    def __init__(self, perf=None) -> None:
        self.perf = perf        # the plugin's ``ec_registry`` set, or none

    def matmul(self, matrix: np.ndarray, data: np.ndarray) -> np.ndarray:
        return gf_matmul_device(matrix, data, out_np=True)

    def matmul_batch(self, matrix: np.ndarray, data: np.ndarray,
                     out_np: bool = False, rows=None):
        return gf_matmul_batch_device(matrix, data, rows=rows,
                                      out_np=out_np, perf=self.perf)
