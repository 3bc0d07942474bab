"""MeshCodec: the multichip mesh as a live OSD codec engine.

The sharded dry runs (parallel/sharded_ec.py) do sharded encode, LRC
local repair and delta-encoded partial-stripe RMW byte-exact over a
device mesh -- but nothing in the OSD path calls them.  This module is
the promotion: a shard_map-compiled launch
family the per-OSD CodecBatcher feeds its coalesced stripe batches,
so one launch encodes the batches of many PGs across every chip in
the slice ("a rack of OSDs per TPU slice").

Shape of the thing:

  * the stripe-batch axis partitions across all visible devices via a
    1-D ('stripe',) Mesh + NamedSharding -- stripes are independent,
    so the per-device block needs NO collective (unlike the dry-run's
    (stripe, shard) mesh, whose all_gather pays an ICI hop the data
    plane does not need);
  * launches compile ONCE per (matrix, B, k, L, crc) family and the
    compiled executables are cached PROCESS-WIDE keyed by the mesh --
    every OSD of an in-process cluster shares one compile (the same
    lesson as the VectorCrush digest cache);
  * the fused CRC32C side-path (ops/crc32c_batch.crc32c_chunks_traced)
    rides inside the same jitted program, so chunk checksums come back
    from the one device round trip that produced the parity; it is a
    bit-matmul like the encode itself (segment registers, then a
    log-depth GF(2) fold), a small part of the launch, not a serial
    walk over the chunk's bytes;
  * stripe buffers are DONATED (``donate_argnums``): the launch owns
    the device copy of the input batch -- callers must never read it
    again (the donated-buffer-aliasing lint rule), XLA may free or
    reuse it instead of keeping it alive for a defensive copy, and the
    RMW delta path genuinely ALIASES the old-parity buffer in place
    (shapes match, pinned by test_mesh_codec) -- writes stop paying
    the keep-both-copies host<->device discipline;
  * single-device is just a 1-device mesh: the CPU tier-1 suite runs
    the identical partitioned program, and
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` runs the
    real 8-way SPMD program on CPU (tests/test_mesh_codec.py).

There is one program family: the dense bit-matmul with the coefficient
matrix as an operand (encode, decode, rmw), and beside it the digest
program a deep scrub launches: whole resident shards in, one CRC32C
register a row out, no codec (``digest``).  What the tests byte-check on the CPU is the
program the chip runs.  The mesh holds no config object and reads no
environment variable.
"""

from __future__ import annotations

import functools
import warnings

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .sharded_ec import _gf_matmul_bits, make_data_mesh
from ..common.tracing import section
from ..ops.gf2kernels import bitmatrix_i8, bucket_batch

# encode (B,k,L)->(B,m,L) and decode (B,k,L)->(B,r,L) donate a buffer
# whose shape matches no output; XLA then frees it early instead of
# aliasing and jax warns that the donation "was not usable".  The early
# free is exactly what we want (no defensive copy, no double-residency
# of the batch), so the advisory warning is noise on this path.
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable")


@functools.lru_cache(maxsize=8)
def _shared_mesh(n_devices: int) -> Mesh:
    """One Mesh instance per device count, shared process-wide so the
    compiled-executable caches below hit across every MeshCodec (and
    therefore every OSD) in the process."""
    return make_data_mesh(n_devices or None)


@functools.lru_cache(maxsize=256)
def _w_device(mesh: Mesh, mat_bytes: bytes, r: int, k: int):
    """Replicated device-resident bit-matrix: one upload per
    (mesh, coefficient matrix), ever."""
    mat = np.frombuffer(mat_bytes, np.uint8).reshape(r, k)
    return jax.device_put(bitmatrix_i8(mat),
                          NamedSharding(mesh, P(None, None)))


# The decode's program name.  Not ``ec_decode``: compile caches hold an
# executable of that name built before the ``gf_decode`` scope existed,
# and their keys ignore scope metadata, so the old name would load it.
DECODE_PROGRAM = "ec_decode_rows"


def _jit_as(name: str, fn, donate_argnums: tuple, **jit_kw):
    """``jax.jit`` under a program name of its own: the profiler's
    trace and the compile cache call the program ``jit_<name>``, so
    encode, decode, rmw and digest launches stand apart in a trace.
    The stripe buffers at ``donate_argnums`` are donated -- consumed
    by the launch, never read again."""
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn, donate_argnums=donate_argnums, **jit_kw)


def _host(out):
    """The synchronous return of a launch: the calling thread waits
    here until the device is done."""
    with section("device_wait.materialize"):
        # lint: disable=device-path-host-sync -- the single post-launch materialization
        return np.asarray(out)


def _stripe_block(w_local, chunks, scope: str = "gf_encode"):
    """Per-device block: my slice of the stripe batch through the GF
    bit-matmul.  No collective -- stripes are independent."""
    bl, kk, ll = chunks.shape
    flat = chunks.transpose(1, 0, 2).reshape(kk, bl * ll)
    rows = _gf_matmul_bits(w_local, flat, scope)
    return rows.reshape(-1, bl, ll).transpose(1, 0, 2)


@functools.lru_cache(maxsize=512)
def _compiled_apply(mesh: Mesh, name: str, b: int, k: int, lane: int,
                    with_crc: bool):
    """One launch: (8r,8k) W x (B,k,L) stripes -> (B,r,L) [+ chunk
    CRCs].  The batch axis shards over 'stripe'; W replicates.  The
    stripe buffer (arg 1) is donated -- consumed by the launch, never
    read again (the donated-buffer-aliasing lint rule guards callers).
    ``name`` (``ec_encode``, ``DECODE_PROGRAM``) is the program's name:
    the same matrix product compiles once per caller's kind, and a
    decode's matmul sits under the ``gf_decode`` scope.
    """
    scope = "gf_decode" if name == DECODE_PROGRAM else "gf_encode"
    sharded = shard_map(
        functools.partial(_stripe_block, scope=scope), mesh=mesh,
        in_specs=(P(None, None), P("stripe", None, None)),
        out_specs=P("stripe", None, None))
    if not with_crc:
        return _jit_as(name, sharded, (1,))

    def fn(w, data):
        from ..ops.crc32c_batch import crc32c_chunks_traced
        parity = sharded(w, data)
        crcs = jnp.concatenate([crc32c_chunks_traced(data),
                                crc32c_chunks_traced(parity)], axis=1)
        return parity, crcs

    return _jit_as(name + "_crc", fn, (1,))


@functools.lru_cache(maxsize=256)
def _compiled_rmw(mesh: Mesh, b: int, m: int, k: int, lane: int):
    """Delta-encoded partial-stripe RMW in one launch: new_parity =
    old_parity XOR encode(delta) (GF linearity; the sharded rendering
    of ECCommon.cc:704's pipeline).  old_parity (arg 1) is donated and
    ALIASES the output buffer -- shapes match, so the update is truly
    in place on device."""
    def block(w_local, oldp, delta):
        return jnp.bitwise_xor(oldp, _stripe_block(w_local, delta))

    sharded = shard_map(
        block, mesh=mesh,
        in_specs=(P(None, None), P("stripe", None, None),
                  P("stripe", None, None)),
        out_specs=P("stripe", None, None))
    return _jit_as("ec_rmw", sharded, (1, 2))


@functools.lru_cache(maxsize=64)
def _compiled_digest(mesh: Mesh, b: int, lane: int):
    """The digest program: (B, lane) whole-shard rows -> (B,) uint32
    zero-seed CRC32C registers (ops/crc32c_batch.crc32c_shards_traced).
    Rows are independent, so the batch axis shards over 'stripe' like
    every other launch and no collective runs.  Nothing is donated: no
    output has the rows' shape, and the caller keeps no reference."""
    from ..ops.crc32c_batch import DIGEST_PROGRAM, crc32c_shards_traced
    def fn(x):
        return crc32c_shards_traced(x)

    return _jit_as(DIGEST_PROGRAM, fn, (),
                   in_shardings=NamedSharding(mesh, P("stripe", None)),
                   out_shardings=NamedSharding(mesh, P("stripe")))


@functools.lru_cache(maxsize=256)
def _decode_matrix_cached(mat_bytes: bytes, rows: int, k_total: int,
                          k: int, erasures: tuple) -> np.ndarray:
    """build_decode_matrix product for codecs without their own
    DecodeTableCache; same construction as the tpu plugin's, so the
    mesh decode is byte-identical to decode_batch."""
    from ..gf import build_decode_matrix
    enc = np.frombuffer(mat_bytes, np.uint8).reshape(rows, k_total)
    matrix, _ = build_decode_matrix(enc, k, list(erasures))
    return matrix


def clear_mesh_cache() -> None:
    for fn in (_shared_mesh, _w_device, _compiled_apply, _compiled_rmw,
               _compiled_digest, _decode_matrix_cached):
        fn.cache_clear()


class MeshCodec:
    """A multi-chip slice presented as one giant erasure codec.

    ``encode``/``decode``/``rmw`` each run EXACTLY ONE device launch
    for a whole (B, k, L) stripe batch, partitioned over every mesh
    device, byte-identical to the per-stripe host codec.  B must be a
    multiple of the device count -- ``pad_batch`` gives the bucketed
    size the CodecBatcher pads to.
    """

    def __init__(self, n_devices: int = 0, perf=None) -> None:
        self.mesh = _shared_mesh(int(n_devices))
        self.n_devices = self.mesh.devices.size
        self.perf = perf
        self._data_sharding = NamedSharding(self.mesh,
                                            P("stripe", None, None))
        if perf is not None:
            perf.set_gauge("mesh_devices", self.n_devices)

    # -- capability gate ----------------------------------------------------
    @staticmethod
    def supports(codec) -> bool:
        """Whether this codec's stripes launch through the mesh; one
        that does not takes the per-op path.  The mesh speaks two
        coefficient-matrix dialects: the positional matrix family
        (batch entry points, an ``encode_matrix`` whose rows are the
        chunk positions and no chunk remapping -- the encode matrix
        drives the launch directly and the decode matrix is the same
        build_decode_matrix product ``decode_batch`` uses) and the
        flat sub-chunk family (the ``mesh_flat_ok`` marker,
        ec/linear_codec.py -- chunks reshape to alpha sub-chunk rows
        around the same launches, matrices come from
        ``parity_matrix``/``decode_flat_matrix``, keyed by position
        so mapped layouts (lrc) launch too; fused CRC stays with the
        first dialect, whose CRCs are chunk-granular)."""
        if getattr(codec, "mesh_flat_ok", False):
            return True
        return (hasattr(codec, "encode_batch")
                and hasattr(codec, "decode_batch")
                and getattr(codec, "encode_matrix", None) is not None
                and not codec.get_chunk_mapping())

    @staticmethod
    def _flat(codec) -> bool:
        return getattr(codec, "mesh_flat_ok", False)

    def pad_batch(self, total: int) -> int:
        """Bucketed launch batch: power-of-two (bounded jit cache) AND
        a multiple of the device count (the 'stripe' axis must divide
        evenly).  Zero rows are byte-exact padding, as ever."""
        b = max(bucket_batch(total), 1)
        n = self.n_devices
        return b if b % n == 0 else ((b + n - 1) // n) * n

    # -- launches ------------------------------------------------------------
    def _count(self, b: int, total: int | None = None) -> None:
        if self.perf is not None:
            self.perf.inc("mesh_launches")
            self.perf.inc("mesh_padded_stripes", b)

    def _put(self, arr: np.ndarray):
        """Host batch -> device, already laid out stripe-sharded, so
        the launch consumes it without a resharding copy.  The device
        buffer is DONATED to the launch: do not read it afterwards."""
        return jax.device_put(np.ascontiguousarray(arr, np.uint8),
                              self._data_sharding)

    def _apply(self, name: str, matrix: np.ndarray, batch: np.ndarray,
               with_crc: bool):
        b, k, lane = batch.shape
        assert b % self.n_devices == 0, (b, self.n_devices)
        matrix = np.ascontiguousarray(matrix, np.uint8)
        w = _w_device(self.mesh, matrix.tobytes(), *matrix.shape)
        fn = _compiled_apply(self.mesh, name, b, k, lane, with_crc)
        out = fn(w, self._put(batch))
        self._count(b)
        return out

    def encode(self, codec, batch: np.ndarray, with_crc: bool = False,
               out_np: bool = True):
        """(B, k, L) data chunks -> (B, m, L) parity in one sharded
        launch; ``with_crc`` returns ``(parity, crcs)`` with the
        (B, k+m) chunk CRCs computed inside the SAME launch (no second
        round trip, no host re-scan), or ``(parity, None)`` for a flat
        codec, whose caller hashes on the host.  ``out_np=False``
        leaves the result on device (the batcher defers the
        materialization past its overlap window)."""
        if self._flat(codec):
            # sub-chunk dialect: (B, k, L) -> (B, k*alpha, L/alpha)
            # rows around the same sharded launch; fused CRC is the
            # other dialect's contract (its CRCs are chunk-granular)
            a = codec.alpha
            b, kc, lane = batch.shape
            out = self._apply("ec_encode", codec.parity_matrix,
                              batch.reshape(b, kc * a, lane // a),
                              False)
            out = out.reshape(b, -1, lane)
            out = _host(out) if out_np else out
            return (out, None) if with_crc else out
        mat = codec.encode_matrix[codec.k:]
        if not with_crc:
            out = self._apply("ec_encode", mat, batch, False)
            return _host(out) if out_np else out
        out, crcs = self._apply("ec_encode", mat, batch, True)
        from ..ops.crc32c_batch import PERF
        PERF.inc("fused_launches")
        PERF.inc("fused_crcs", int(batch.shape[0])
                 * (batch.shape[1] + out.shape[1]))
        if not out_np:
            return out, crcs
        return _host(out), _host(crcs)

    def decode(self, codec, erasures, batch: np.ndarray,
               out_np: bool = True):
        """(B, k, L) survivors (decode-index order, the decode_batch
        contract) -> (B, len(erasures), L) recovered chunks."""
        erasures = tuple(int(e) for e in erasures)
        if self._flat(codec):
            # the packed (sources, lost) extra selects the SAME cached
            # repair matrix decode_batch uses; survivors reshape to
            # sub-chunk rows around the launch
            matrix = codec.decode_flat_matrix(list(erasures))
            a = codec.alpha
            b, s, lane = batch.shape
            out = self._apply(DECODE_PROGRAM, matrix,
                              batch.reshape(b, s * a, lane // a),
                              False)
            out = out.reshape(b, -1, lane)
            return _host(out) if out_np else out
        if hasattr(codec, "decode_matrix_for"):
            # the plugin's DecodeTableCache: the SAME matrix object
            # decode_batch would use
            matrix = codec.decode_matrix_for(list(erasures))
        else:
            enc = np.ascontiguousarray(codec.encode_matrix, np.uint8)
            matrix = _decode_matrix_cached(enc.tobytes(), *enc.shape,
                                           codec.k, erasures)
        out = self._apply(DECODE_PROGRAM, matrix, batch, False)
        return _host(out) if out_np else out

    def digest(self, rows: np.ndarray, out_np: bool = True):
        """(B, lane) whole buffers, each at the end of its row
        (ops/crc32c_batch.digest_rows) -> (B,) uint32 zero-seed
        CRC32C registers in one launch of the digest program; the
        caller folds each buffer's length in (``digest_finish``).  No
        codec: a checksum has no coefficients."""
        b, lane = rows.shape
        assert b % self.n_devices == 0, (b, self.n_devices)
        fn = _compiled_digest(self.mesh, b, lane)
        out = fn(jax.device_put(
            np.ascontiguousarray(rows, np.uint8),
            NamedSharding(self.mesh, P("stripe", None))))
        self._count(b)
        return _host(out) if out_np else out

    def rmw(self, codec, old_parity: np.ndarray,
            delta: np.ndarray, out_np: bool = True):
        """Partial-stripe RMW: (B, m, L) old parity + (B, k, L) delta
        (zeros outside the written range) -> (B, m, L) new parity.
        One launch; the old-parity device buffer is donated and
        aliased in place."""
        b, k, lane = delta.shape
        m = old_parity.shape[1]
        assert b % self.n_devices == 0, (b, self.n_devices)
        if self._flat(codec):
            # GF linearity holds per sub-chunk row identically: both
            # operands reshape to sub-chunk rows around the launch
            a = codec.alpha
            mat = codec.parity_matrix
            old_parity = old_parity.reshape(b, m * a, lane // a)
            delta = delta.reshape(b, k * a, lane // a)
        else:
            mat = np.ascontiguousarray(codec.encode_matrix[codec.k:],
                                       np.uint8)
        w = _w_device(self.mesh, mat.tobytes(), *mat.shape)
        fn = _compiled_rmw(self.mesh, b, old_parity.shape[1],
                           *delta.shape[1:])
        out = fn(w, self._put(old_parity), self._put(delta))
        out = out.reshape(b, m, lane)
        self._count(b)
        if self.perf is not None:
            self.perf.inc("mesh_rmw_launches")
            self.perf.inc("mesh_rmw_padded_stripes", b)
        return _host(out) if out_np else out
