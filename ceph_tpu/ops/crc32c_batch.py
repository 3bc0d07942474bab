"""Batched CRC32C: whole-batch checksums for the integrity pipeline.

PR 2 made integrity the default path -- a CRC rides every EC shard
write and is verified on every shard read, recovery payload and scrub
-- but each of those digests was a per-buffer host call (ctypes into
``native.ceph_crc32c``, or a per-byte Python loop without the lib).
This module makes the checksum side-path batch-shaped like the codec
itself (the same observation as arXiv:2108.02692: once the GF math is
amortized, the XOR/CRC side-path dominates):

* ``crc32c_batch`` / ``crc32c_rows``: checksum a whole (possibly
  ragged) batch of buffers in one pass.  Backend ladder: one call into
  ``native.ceph_crc32c_batch`` (amortizes the ~7 us/buffer ctypes
  marshaling that dominates small buffers), falling back to a numpy
  table-driven slice-by-8 engine that is always available (and is also
  what ``native._crc32c_py`` now delegates to).

* GF(2) register algebra (``crc32c_zeros`` / ``crc32c_combine`` /
  ``crc32c_strip_zeros`` / ``fold_chunk_crcs`` / ``crc32c_patch``):
  advancing a CRC over n zero bytes is multiplication by the 32x32
  bit-matrix M^n (the same x^(8n) mod P math Ceph's crc32c combine
  uses), which makes CRC embarrassingly batch-parallel: ragged
  buffers are zero-padded, checksummed in lockstep, and un-padded by
  the INVERSE matrix; chunk
  CRCs from a device launch fold into whole-shard CRCs without
  re-reading a byte, and a ranged overwrite updates a shard's CRC
  from the bytes it changes.

* ``crc32c_device_chunks``: the JAX kernel variant, the same algebra
  on the device: every 512-byte segment's register is one GF(2)
  bit-matmul of the codec's own family, a log-depth fold of M^width
  matmuls combines the segments, the seed enters as one constant --
  no loop over bytes, no table.  The codec batcher feeds it the same
  (B, k, L) tensors the encode/decode launch just touched, so shard
  CRCs come back from the device round trip that produced the parity
  -- no host re-scan.

Observability: the module-global ``PERF`` ("integrity") counts batched
vs scalar calls, bytes hashed and fused-launch hits; ``native.crc32c``
reports every remaining per-buffer call into the same set, so a test
can prove the codec-batcher and deep-scrub paths ride the batched API
(scalar-call delta 0: ``tests/test_crc_batch.py``).

This module must stay importable without jax (blockstore/scrub/native
fallback are jax-free); the device kernel imports lazily.
"""

from __future__ import annotations

import functools

import numpy as np

from .. import native
from ..common.perf import PerfCounters

SEED = 0xFFFFFFFF
_POLY = 0x82F63B78                  # reversed Castagnoli

# process-wide integrity counter set; OSDs adopt it into their perf
# dumps (PerfCountersCollection.adopt), native.crc32c counts scalar
# calls against it
PERF = PerfCounters("integrity")


# -- slice tables -----------------------------------------------------------

@functools.lru_cache(maxsize=1)
def _tables() -> np.ndarray:
    """(8, 256) uint32 slice-by-8 tables (t[0] = plain byte table)."""
    t = np.zeros((8, 256), np.uint32)
    for i in range(256):
        c = i
        for _ in range(8):
            c = (c >> 1) ^ _POLY if c & 1 else c >> 1
        t[0, i] = c
    for s in range(1, 8):
        t[s] = t[0][t[s - 1] & 0xFF] ^ (t[s - 1] >> 8)
    return t


# -- GF(2) register algebra -------------------------------------------------
# A 32x32 GF(2) matrix is a (32,) uint32 array of COLUMNS: applying it
# to a register XORs together the columns selected by the register's
# set bits.  The CRC update over data is affine in (register, data), so
# advancing over n zero bytes is purely linear: reg' = M^n . reg.

def _mat_apply(mat: np.ndarray, v) -> np.ndarray:
    """Apply a (32,) column-matrix to a scalar/array of registers."""
    # lint: disable=device-path-host-sync -- GF(2) register algebra on (n,) uint32 CRCs, not batch payload
    v = np.asarray(v, np.uint32)
    bits = ((v[..., None] >> np.arange(32, dtype=np.uint32)) & 1) != 0
    return np.bitwise_xor.reduce(
        np.where(bits, mat, np.uint32(0)), axis=-1)


def _mat_mul(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """(a . b): column i of the product is a applied to b's column i."""
    return _mat_apply(a, b)


@functools.lru_cache(maxsize=1)
def _zero_byte_matrix() -> np.ndarray:
    """M: one zero-byte register update, reg' = (reg >> 8) ^ T0[reg & 0xff]."""
    t0 = _tables()[0]
    cols = np.zeros(32, np.uint32)
    for i in range(32):
        v = np.uint32(1 << i)
        cols[i] = (v >> np.uint32(8)) ^ t0[v & 0xFF]
    return cols


def _mat_inv(mat: np.ndarray) -> np.ndarray:
    """GF(2) inverse by Gauss-Jordan on 64-bit augmented rows."""
    rows = []
    for r in range(32):
        row = 0
        for c in range(32):
            row |= ((int(mat[c]) >> r) & 1) << c
        rows.append(row | (1 << (32 + r)))
    for col in range(32):
        piv = next(r for r in range(col, 32) if (rows[r] >> col) & 1)
        rows[col], rows[piv] = rows[piv], rows[col]
        for r in range(32):
            if r != col and (rows[r] >> col) & 1:
                rows[r] ^= rows[col]
    inv = np.zeros(32, np.uint32)
    for c in range(32):
        col = 0
        for r in range(32):
            col |= ((rows[r] >> (32 + c)) & 1) << r
        inv[c] = col
    return inv


@functools.lru_cache(maxsize=64)
def _zeros_pow2(b: int) -> np.ndarray:
    """M^(2^b): advance over 2^b zero bytes."""
    if b == 0:
        return _zero_byte_matrix()
    m = _zeros_pow2(b - 1)
    return _mat_mul(m, m)


@functools.lru_cache(maxsize=64)
def _inv_zeros_pow2(b: int) -> np.ndarray:
    """(M^-1)^(2^b): strip 2^b trailing zero bytes."""
    if b == 0:
        return _mat_inv(_zero_byte_matrix())
    m = _inv_zeros_pow2(b - 1)
    return _mat_mul(m, m)


@functools.lru_cache(maxsize=1024)
def _zeros_matrix(n: int) -> np.ndarray:
    """M^n via the binary ladder (few distinct n recur: segment and
    chunk lengths, and the chunk-aligned tails of ``crc32c_patch``)."""
    assert n >= 0
    out = None
    b = 0
    while n:
        if n & 1:
            sq = _zeros_pow2(b)
            out = sq if out is None else _mat_mul(sq, out)
        n >>= 1
        b += 1
    if out is None:                  # n == 0: identity
        return (np.uint32(1) << np.arange(32, dtype=np.uint32))
    return out


def crc32c_zeros(crc, n: int):
    """Advance CRC register(s) over ``n`` zero bytes (raw register
    semantics: equivalent to ``native.crc32c(b"\\x00" * n, crc)``)."""
    mat = _zeros_matrix(int(n))
    if np.ndim(crc) != 0:
        return _mat_apply(mat, crc)
    # one register: a pass over its set bits costs less than
    # marshaling it through _mat_apply's arrays
    reg, out = int(crc), 0
    for bit, col in enumerate(mat.tolist()):
        if reg >> bit & 1:
            out ^= col
    return out


def crc32c_combine(crc_a, crc_b, len_b: int):
    """``crc32c(a + b)`` from ``crc32c(a)`` and ``crc32c(b)`` (both
    with the default seed) without touching the bytes:
    M^len_b . (crc_a ^ seed) ^ crc_b."""
    a = np.asarray(crc_a, np.uint32) ^ np.uint32(SEED)
    out = _mat_apply(_zeros_matrix(int(len_b)), a) \
        ^ np.asarray(crc_b, np.uint32)
    return int(out) if np.ndim(crc_a) == 0 and np.ndim(crc_b) == 0 \
        else out


def crc32c_patch(crc: int, patches) -> int:
    """CRC of a buffer after disjoint ranges of it were replaced in
    place, from its CRC before (any seed, raw register) without
    touching the rest: the register is affine in the data, so
    replacing ``old`` by ``new`` with ``tail`` bytes after the range
    XORs in the register run from 0 over ``old ^ new`` and advanced
    over ``tail`` zero bytes.  ``patches``: (old, new, tail) with
    ``old`` and ``new`` bytes-like of one length.  One range is the
    rule (a sub-write's chunk), so each is one scalar library call and
    one scalar ``crc32c_zeros``: the batched entry costs more in
    marshaling than 4 KiB cost to hash."""
    crc = int(crc)
    for old, new, tail in patches:
        reg = native.crc32c(np.bitwise_xor(
            np.frombuffer(old, np.uint8),
            np.frombuffer(new, np.uint8)).tobytes(), 0)
        crc ^= crc32c_zeros(reg, tail)
    return crc


def crc32c_strip_zeros(crcs, nzeros):
    """Undo a zero suffix: given crc(buf + zeros), recover crc(buf).

    Zero-extension is the invertible linear map M^z, so the batched
    engines can pad ragged buffers to a common length, run in lockstep,
    and un-pad here; the codec batcher uses it to fix up fused CRCs
    computed at the padded lane width.  ``nzeros`` is a scalar or an
    array broadcastable to ``crcs``.
    """
    # lint: disable=device-path-host-sync -- GF(2) register algebra on (n,) uint32 CRCs, not batch payload
    crcs = np.asarray(crcs, np.uint32)
    # lint: disable=device-path-host-sync -- GF(2) register algebra on (n,) uint32 CRCs, not batch payload
    z = np.broadcast_to(np.asarray(nzeros, np.int64), crcs.shape)
    out = crcs.copy()
    maxz = int(z.max()) if z.size else 0
    b = 0
    while (1 << b) <= maxz:
        mask = ((z >> b) & 1) != 0
        if mask.any():
            out = np.where(mask, _mat_apply(_inv_zeros_pow2(b), out),
                           out)
        b += 1
    return out


def fold_chunk_crcs(chunk_crcs, chunk_len: int):
    """CRC of the concatenation along axis 0 of equal-length chunks,
    from their individual CRCs (default seed each): the host-side fold
    that turns a launch's per-stripe chunk CRCs into whole-shard CRCs
    without re-reading the bytes."""
    # lint: disable=device-path-host-sync -- host-side fold of per-chunk uint32 CRCs, not batch payload
    cc = np.asarray(chunk_crcs, np.uint32)
    if cc.shape[0] == 0:
        return np.full(cc.shape[1:], SEED, np.uint32)
    mat = _zeros_matrix(int(chunk_len))
    f = np.uint32(SEED)
    acc = cc[0]
    for s in range(1, cc.shape[0]):
        acc = _mat_apply(mat, acc ^ f) ^ cc[s]
    PERF.inc("combine_folds", max(0, cc.shape[0] - 1))
    return acc


# -- numpy lockstep engine --------------------------------------------------

def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def _pick_seg(n_rows: int, lp: int) -> int:
    """Segment length for the chunk-split: shorter segments mean more
    parallel lanes (good for few rows) but more combine levels."""
    seg = 512
    while seg > 16 and n_rows * ((lp + seg - 1) // seg) < 1024:
        seg //= 2
    return seg


def _lockstep(lanes: np.ndarray, crc: np.ndarray) -> np.ndarray:
    """Slice-by-8 over (N, L) lanes in lockstep; L % 8 == 0.  ``crc``
    carries per-lane seeds and returns the raw registers."""
    t0, t1, t2, t3, t4, t5, t6, t7 = _tables()
    u64 = lanes.view("<u8")
    for j in range(lanes.shape[1] // 8):
        v = u64[:, j]
        x = crc.astype(np.uint64) ^ v
        crc = (t7[(x & 0xFF).astype(np.intp)]
               ^ t6[((x >> 8) & 0xFF).astype(np.intp)]
               ^ t5[((x >> 16) & 0xFF).astype(np.intp)]
               ^ t4[((x >> 24) & 0xFF).astype(np.intp)]
               ^ t3[((v >> 32) & 0xFF).astype(np.intp)]
               ^ t2[((v >> 40) & 0xFF).astype(np.intp)]
               ^ t1[((v >> 48) & 0xFF).astype(np.intp)]
               ^ t0[(v >> 56).astype(np.intp)])
    return crc


def _crc_rows_numpy(arr: np.ndarray, lengths: np.ndarray,
                    seed: int) -> np.ndarray:
    """Rows of a zero-padded (N, L) array -> (N,) uint32, pure numpy.

    Chunk-split + combine: each row splits into S power-of-two
    segments checksummed in lockstep across N*S lanes, a log2(S)-level
    tree of M^len combines folds them back, and the per-row zero
    padding is stripped by the inverse matrix.
    """
    n, l = arr.shape
    if n == 0:
        return np.zeros(0, np.uint32)
    seg = _pick_seg(n, max(l, 8))
    s = _next_pow2(max(1, -(-max(l, 1) // seg)))
    lp = s * seg
    if lp != l:
        padded = np.zeros((n, lp), np.uint8)
        padded[:, :l] = arr
        arr = padded
    lanes = np.ascontiguousarray(arr).reshape(n * s, seg)
    crc0 = np.zeros(n * s, np.uint32)
    crc0[::s] = np.uint32(seed)     # leftmost segment carries the seed
    crcs = _lockstep(lanes, crc0).reshape(n, s)
    width = seg
    while crcs.shape[1] > 1:        # combine pairs, doubling coverage
        mat = _zeros_matrix(width)
        crcs = _mat_apply(mat, crcs[:, 0::2]) ^ crcs[:, 1::2]
        width *= 2
    return crc32c_strip_zeros(
        crcs[:, 0],
        # lint: disable=device-path-host-sync -- (n,) length vector for the un-pad, not batch payload
        lp - np.asarray(lengths, np.int64))


def crc32c_numpy_one(data, crc: int = SEED) -> int:
    """Single-buffer numpy engine (``native._crc32c_py`` delegate)."""
    buf = np.frombuffer(data, np.uint8) if not isinstance(
        data, np.ndarray) else np.ascontiguousarray(data, np.uint8)
    if buf.size == 0:
        return crc & 0xFFFFFFFF
    return int(_crc_rows_numpy(buf.reshape(1, -1),
                               np.array([buf.size], np.int64), crc)[0])


# -- batched entry points ---------------------------------------------------

def crc32c_rows(arr, lengths=None, seed: int = SEED,
                backend: str | None = None) -> np.ndarray:
    """CRCs of the rows of a (N, L) uint8 array in one pass.

    ``lengths`` (optional, per-row) truncates row i to its first
    ``lengths[i]`` bytes -- the bytes beyond may be anything on the
    native path but are zeroed for the numpy engine.  ``backend``
    forces "native" or "numpy" (parity tests); default is the ladder.
    """
    arr = np.ascontiguousarray(arr, np.uint8)
    assert arr.ndim == 2, arr.shape
    n, l = arr.shape
    lens = (np.full(n, l, np.int64) if lengths is None
            # lint: disable=device-path-host-sync -- (n,) length vector of a host-engine call, not batch payload
            else np.asarray(lengths, np.int64))
    PERF.inc("batched_calls")
    PERF.inc("batched_bufs", n)
    PERF.inc("batched_bytes", int(lens.sum()))
    if backend != "numpy" and n:
        crcs = np.full(n, seed, np.uint32)
        offs = np.arange(n, dtype=np.uint64) * np.uint64(l)
        if native.crc32c_batch_native(crcs, arr.reshape(-1), offs,
                                      lens.astype(np.uint64)):
            PERF.inc("native_batches")
            return crcs
        if backend == "native":
            raise RuntimeError("native crc32c batch unavailable")
    PERF.inc("numpy_batches")
    if lengths is not None and bool((lens < l).any()):
        arr = arr.copy()
        arr[np.arange(l) >= lens[:, None]] = 0
    return _crc_rows_numpy(arr, lens, seed)


def crc32c_batch(bufs, seed: int = SEED,
                 backend: str | None = None) -> np.ndarray:
    """CRCs of a ragged sequence of buffers (bytes-like or uint8
    arrays) in one pass; empty buffers come back as the seed, exactly
    like the scalar call."""
    bufs = bufs if isinstance(bufs, (list, tuple)) else list(bufs)
    n = len(bufs)
    # fast marshal: one C-level join instead of a numpy view per
    # buffer (the per-buffer frombuffer was itself ~0.5 us -- most of
    # a scalar call's overhead smuggled back in)
    if all(type(b) is bytes for b in bufs):
        lens = np.fromiter((len(b) for b in bufs), np.int64, count=n)
        views = None
    else:
        views = []
        for b in bufs:
            if isinstance(b, np.ndarray):
                views.append(
                    np.ascontiguousarray(b, np.uint8).reshape(-1))
            else:
                views.append(np.frombuffer(b, np.uint8))
        lens = np.fromiter((v.size for v in views), np.int64, count=n)
    PERF.inc("batched_calls")
    PERF.inc("batched_bufs", n)
    PERF.inc("batched_bytes", int(lens.sum()))
    if n == 0:
        return np.zeros(0, np.uint32)
    if backend != "numpy":
        crcs = np.full(n, seed, np.uint32)
        # marshaling strategy: big buffers go by pointer table (zero
        # copy, per-buffer cost only), small ones by one C-level join
        # (per-byte memcpy beats 393k pointer-object conversions)
        if views is None and int(lens.sum()) >= 768 * n:
            if native.crc32c_batch_native_ptrs(crcs, bufs, lens):
                PERF.inc("native_batches")
                return crcs
        if views is None:
            flat = np.frombuffer(b"".join(bufs), np.uint8)
        else:
            flat = views[0] if n == 1 else np.concatenate(views)
        offs = np.zeros(n + 1, np.uint64)
        np.cumsum(lens, out=offs[1:])
        if native.crc32c_batch_native(crcs, flat, offs[:-1],
                                      offs[1:] - offs[:-1]):
            PERF.inc("native_batches")
            return crcs
        if backend == "native":
            raise RuntimeError("native crc32c batch unavailable")
    PERF.inc("numpy_batches")
    if views is None:
        views = [np.frombuffer(b, np.uint8) for b in bufs]
    # bucket by power-of-two padded length so one huge buffer cannot
    # blow the padded matrix up to N x max(L)
    out = np.empty(n, np.uint32)
    classes: dict[int, list[int]] = {}
    for i, ln in enumerate(lens):
        classes.setdefault(_next_pow2(max(int(ln), 64)), []).append(i)
    for cap, idx in sorted(classes.items()):
        rows = np.zeros((len(idx), cap), np.uint8)
        for r, i in enumerate(idx):
            rows[r, :lens[i]] = views[i]
        out[idx] = _crc_rows_numpy(rows, lens[idx], seed)
    return out


# -- JAX device kernel ------------------------------------------------------

# Bytes a row's segment holds: the segment matrix is (8 * _SEG, 32),
# 128 KiB as int8, and a contraction of 4096 fills the MXU's depth.
_SEG = 512
# Segment rows the program unpacks at a time: 8 bit planes of 65536 x
# 512 bytes are 256 MiB, so a launch's temporary is bounded whatever
# the buffer (the store's 1408 x 4096 launch is 11264 segment rows:
# one block, no loop).
_ROW_BLOCK = 65536


@functools.lru_cache(maxsize=1)
def _segment_matrix() -> np.ndarray:
    """(8, _SEG, 32) 0/1 int8: entry [b, p] holds the bits of the
    zero-seed register of a _SEG-byte segment in which only bit b of
    byte p is set.  That register depends only on the byte's distance
    from the segment's end, so a shorter segment's matrix is the tail
    ``[:, _SEG - seg:]`` of this one."""
    t0 = _tables()[0]
    regs = np.zeros((8, _SEG), np.uint32)
    r = t0[1 << np.arange(8)]            # the byte itself, then zeros
    for p in range(_SEG - 1, -1, -1):
        regs[:, p] = r
        r = (r >> np.uint32(8)) ^ t0[r & 0xFF]
    return _reg_bits(regs)


def _reg_bits(regs: np.ndarray) -> np.ndarray:
    """(...,) uint32 registers -> (..., 32) 0/1 int8, bit j at [j]."""
    return ((regs[..., None] >> np.arange(32, dtype=np.uint32))
            & 1).astype(np.int8)


@functools.lru_cache(maxsize=64)
def _crc_chunks_compiled(l: int):
    """Jitted (N, l) uint8 -> (N,) uint32 chunk CRCs (default seed),
    data-parallel over bytes: CRC32C is linear over GF(2), so

    * a row front-pads with zero bytes to S segments of ``seg`` bytes,
      S a power of two (leading zeros leave a zero-seed register as
      it is);
    * every segment's zero-seed register is ONE bit-matmul, (rows * S,
      8 * seg) bit planes x the (8 * seg, 32) segment matrix, summed
      exactly in int32 and reduced mod 2 afterwards;
    * log2(S) fold levels combine halves, ``M^width . left ^ right``,
      each a (.., 32) x (32, 32) bit-matmul;
    * the seed enters once, as the constant ``M^l . SEED``.

    Every ``l`` and every platform runs this one formulation; S and
    the row block derive from the shape."""
    import jax
    import jax.numpy as jnp
    seg = min(_SEG, _next_pow2(l))
    s = _next_pow2(-(-l // seg))
    # host constants staged per trace: device-caching them here would
    # capture a tracer when the first call happens inside an outer
    # trace (the MeshCodec fused launch) and poison the cache
    wnp = _segment_matrix()[:, _SEG - seg:].reshape(8 * seg, 32)
    # level i folds halves of (s >> i) segments each
    folds = [_reg_bits(_zeros_matrix((s >> i) * seg))
             for i in range(1, s.bit_length())]
    seed_term = np.uint32(crc32c_zeros(SEED, l))
    shifts = np.arange(8, dtype=np.uint8).reshape(8, 1)

    def fn(x):
        n = x.shape[0]

        def segment_bits(row):
            """(seg,) uint8 -> (32,) int32 0/1 register bits."""
            planes = ((row[None, :] >> shifts) & 1).astype(jnp.int8)
            return jnp.dot(planes.reshape(8 * seg), wnp,
                           preferred_element_type=jnp.int32) & 1

        rows = jnp.pad(x, ((0, 0), (s * seg - l, 0))).reshape(
            n * s, seg)
        # one matmul over all rows up to _ROW_BLOCK of them, a loop
        # over such blocks beyond
        bits = jax.lax.map(segment_bits, rows, batch_size=_ROW_BLOCK)
        bits = bits.reshape(n, s, 32)
        for mat in folds:
            left, right = jnp.split(bits, 2, axis=1)
            bits = (jnp.dot(left.astype(jnp.int8), mat,
                            preferred_element_type=jnp.int32)
                    ^ right) & 1
        crc = (bits[:, 0].astype(jnp.uint32)
               << jnp.arange(32, dtype=jnp.uint32)).sum(
                   axis=1, dtype=jnp.uint32)
        return crc ^ seed_term

    # a name of its own in a device trace, alone or inlined
    fn.__name__ = fn.__qualname__ = "crc32c_chunks"
    return jax.jit(fn)


def crc32c_chunks_traced(x):
    """Trace-safe core of ``crc32c_device_chunks``: same math, no perf
    side effects, safe to INLINE inside a larger jitted program -- the
    MeshCodec fused path calls this so the chunk CRCs are part of the
    one sharded launch that produces the parity (the CRC math is
    row-independent, so GSPMD partitions it over the stripe axis with
    no collective)."""
    import jax
    import jax.numpy as jnp
    xd = jnp.asarray(x, jnp.uint8)
    lead, l = xd.shape[:-1], xd.shape[-1]
    if l == 0:                      # zero-length chunks: seed, no kernel
        return jnp.full(lead, SEED, jnp.uint32)
    # the scope a device trace finds the checksum's operations under
    with jax.named_scope("crc32c"):
        flat = xd.reshape((-1, l))
        return _crc_chunks_compiled(l)(flat).reshape(lead)


def crc32c_device_chunks(x):
    """(..., L) uint8 (host or device array) -> (...,) uint32 chunk
    CRCs computed on the accelerator.  Returns a DEVICE array so the
    caller fetches it together with the parity of the same launch
    window -- the fused path of the codec batcher."""
    out = crc32c_chunks_traced(x)
    PERF.inc("fused_launches")
    PERF.inc("fused_crcs", int(np.prod(out.shape, dtype=np.int64)))
    return out


# The digest program's name.  A trace finds a deep scrub's launches
# under it (``jit_crc32c_shards``), apart from the ``crc32c_chunks``
# inlined in the encode that made the parity; its operations stay
# under the ``crc32c`` scope.
DIGEST_PROGRAM = "crc32c_shards"
# Shortest lane a digest row is padded to: lanes are powers of two, so
# a launch's shape is one of a few whatever lengths a PG's shards have.
DIGEST_MIN_LANE = 4096


def digest_lane(n: int) -> int:
    """The power-of-two lane a digest launch gives a buffer of ``n``
    bytes."""
    return max(DIGEST_MIN_LANE, _next_pow2(n))


def digest_rows(bufs, lane: int | None = None) -> np.ndarray:
    """Whole buffers as the rows of one digest launch: (len(bufs),
    lane) uint8, each buffer at the END of its row.  Zero bytes in
    front of a message leave its zero-seed register as it is, so
    ragged buffers share a launch and ``digest_finish`` needs only
    their lengths."""
    views = [np.frombuffer(b, np.uint8) if isinstance(
        b, (bytes, bytearray, memoryview))
        else np.asarray(b, np.uint8).reshape(-1) for b in bufs]
    if lane is None:
        lane = digest_lane(max((v.size for v in views), default=0))
    rows = np.zeros((len(views), lane), np.uint8)
    for row, v in zip(rows, views):
        if v.size:
            row[lane - v.size:] = v
    return rows


def crc32c_shards_traced(x):
    """(B, lane) uint8 rows -> (B,) uint32 ZERO-SEED registers, the
    body of the digest program: the chunk kernel over whole rows with
    its seed constant taken out again, so a row's front padding costs
    nothing and the true length enters on the host
    (``digest_finish``)."""
    import jax
    import jax.numpy as jnp
    lane = x.shape[-1]
    with jax.named_scope("crc32c"):
        return _crc_chunks_compiled(lane)(x) ^ jnp.uint32(
            crc32c_zeros(SEED, lane))


def digest_finish(regs, lengths) -> np.ndarray:
    """Zero-seed registers of front-padded rows -> the buffers'
    CRC32C (default seed): the seed advanced over each buffer's own
    length, XORed in."""
    # lint: disable=device-path-host-sync -- (n,) uint32 registers the batcher has already materialized, not batch payload
    regs = np.asarray(regs, np.uint32).reshape(-1)
    # lint: disable=device-path-host-sync -- (n,) length vector for the seed fold, not batch payload
    lengths = np.asarray(lengths, np.int64).reshape(-1)
    out = regs.copy()
    for n in np.unique(lengths):
        out[lengths == n] ^= np.uint32(crc32c_zeros(SEED, int(n)))
    return out
