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
host memory to host memory streamed through the device, at most
``gf2kernels.SLABS_IN_FLIGHT + 1`` slabs between ``device_put`` and
landing), ``uploads_beside`` (summed over such a call's slabs, the
earlier slabs in flight whose launch was not done when the slab's
``device_put`` went out: over ``slabs``, how many slabs the link and
the device carry beside a new upload),
``gathers`` / ``gathers_ahead`` (slabs of such a call whose survivors or
pieces its worker thread gathered into staging, and those of them that
were ready when the caller's thread came to upload them: how often the
gather stage ran ahead of the uploads),
``arena_hits`` / ``arena_misses`` (the result and the staging buffers
of such a call, ``SLABS_IN_FLIGHT + 1`` at most, each borrowed from the process's host arena: a
buffer an earlier call's caller dropped, or a fresh allocation; a
result stays its caller's own until no array views it),
``parity_gates`` (first launches of a matrix held to the host oracle),
``table_hits`` / ``table_misses`` (decode matrices taken from, or built
into, the DecodeTableCache); where its thread was is in the
``registry.*`` sections (a table miss's ``matrix`` and the per-object
``prepare`` here, the rest in ``gf_matmul_batch_device``: a landing's
three waits are ``registry.drain.kernel`` / ``.link`` / ``.land`` inside
``registry.drain``; ``registry.gather``, with ``registry.gather.wait``
inside it, is the worker's, on its own thread).
"""

from __future__ import annotations

import numpy as np

from .isa import ErasureCodeIsa, K_VANDERMONDE
from ..registry import ErasureCodePlugin
from ...common.perf import PerfCounters
from ...common.tracing import section
from ...gf import build_decode_matrix, erasure_signature
from ...gf.matrices import decode_index_for
from ...ops.gf2kernels import LanePieces
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

    # -- objects of unequal size in one call (host memory in and out) -------
    def encode_objects(self, objects) -> list[np.ndarray]:
        """Per-object byte arrays of any sizes -> per object its
        ``(m, L_i)`` parity chunks, ``L_i = get_chunk_size(size_i)``.

        An object is chunked as ``encode_prepare`` chunks it (k chunks
        of ``L_i`` bytes, the tail of the last data chunks zero) on its
        way into the slab, never in a copy of its own: the call's
        chunks lie end to end on the lane axis and stream through
        ``gf_matmul_batch_device`` in slabs of one width.  The results
        are views of the call's one leased buffer (row p of object i's
        is its parity chunk p, contiguous): the buffer is the caller's
        until the last of them is gone."""
        k = self.k
        with section("registry.prepare"):
            lengths, blocks, tails = [], [], []
            for obj in objects:
                obj = obj if isinstance(obj, np.ndarray) \
                    else np.frombuffer(obj, np.uint8)
                size = obj.size
                length = self.get_chunk_size(size)
                full = size // length if length else 0
                whole = full * length
                mine = [(0, obj[:whole].reshape(full, length))] if full else []
                if whole < size:
                    mine.append((full, obj[whole:].reshape(1, size - whole)))
                lengths.append(length)
                blocks.append(mine)
                tails.append((full, size - whole) if full < k else None)
            pieces = LanePieces(lengths, blocks, tails)
        return self._matmul_objects(self.encode_matrix[k:], pieces)

    def decode_objects(self, erasures: list[int],
                       chunk_maps) -> list[np.ndarray]:
        """Recover ``erasures`` (one pattern a call) for objects of any
        sizes: ``chunk_maps[i]`` is object i's ``(k+m, L_i)`` chunk map
        in host memory, chunk j at ``[j]``; what lies at an erased
        position is never read.  The survivors are the first k ids not
        erased, ascending, taken run by run of neighbouring ids on the
        way into the slab.  Per object the ``(len(erasures), L_i)``
        erased chunks, row p the chunk ``erasures[p]``, as views of the
        call's one leased buffer (``encode_objects``)."""
        matrix = self.decode_matrix_for(erasures)
        with section("registry.prepare"):
            index = decode_index_for(self.k, set(erasures))
            # runs of neighbouring survivor ids: (operand row, id, count)
            runs: list[list[int]] = []
            for row, chunk in enumerate(index):
                if runs and runs[-1][1] + runs[-1][2] == chunk:
                    runs[-1][2] += 1
                else:
                    runs.append([row, chunk, 1])
            pieces = LanePieces(
                [chunks.shape[1] for chunks in chunk_maps],
                [[(row, chunks[first:first + count])
                  for row, first, count in runs] for chunks in chunk_maps],
                [None] * len(chunk_maps))
        return self._matmul_objects(matrix, pieces)

    def _matmul_objects(self, matrix: np.ndarray,
                        pieces: LanePieces) -> list[np.ndarray]:
        if not pieces.lanes:
            return [np.empty((len(matrix), 0), np.uint8)
                    for _ in pieces.lengths]
        out = self.backend.matmul_batch(matrix, pieces, out_np=True)
        with section("registry.prepare"):
            return [out[:, start:end] for start, end in
                    zip(pieces.starts.tolist(), pieces.ends.tolist())]

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
