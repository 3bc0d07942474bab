"""GF(2^8) matrix multiply as GF(2) bit-matmul on the TPU MXU.

The reference's hot loop is ISA-L's ``ec_encode_data`` -- an (r,k) GF(2^8)
coefficient matrix applied to k data chunks (src/erasure-code/isa/
ErasureCodeIsa.cc:128, called from the OSD write path via ECUtil::encode,
src/osd/ECUtil.cc:134).  On TPU we reformulate: multiplication by a GF(2^8)
constant is linear over GF(2), so the whole stripe encode is

    parity_bits(8r, N) = W(8r, 8k) @ data_bits(8k, N)  (mod 2)

with W the bit-expanded coefficient matrix.  That is a plain int8 matmul --
exactly what the MXU does -- plus cheap VPU unpack/pack around it.  Batching
thousands of stripes makes N huge, which is the regime the systolic array
wants.  Byte-identical to the host/numpy path by construction.

Two executions are provided:
  * XLA path (`_gf_matmul_xla`): portable; serves on CPU and for lane
    widths the Pallas tiling cannot take.
  * Pallas path (`_make_pallas_*`): fuses unpack+dot+pack per VMEM tile
    so HBM traffic is just bytes in / parity out.

Which one launches is decided from the backend and the shape alone
(``batch_engine``).  A selected kernel that fails to compile, or whose
first launch misses the byte-parity gate, is an ERROR that reaches the
caller -- never a quiet drop to a slower engine.
"""

from __future__ import annotations

import collections
import functools
import json
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

import jax
import jax.numpy as jnp

from ..common.tracing import section
from ..gf.gf8 import matrix_to_bitmatrix
from .host_arena import HostArena

# column-tile width for the pallas kernel; also the padding bucket for the
# XLA path so recompiles stay bounded
LANE_TILE = 8192


def bucket_batch(b: int) -> int:
    """Round a batch dimension up to a power of two.

    The batch kernels compile per (B, k, L); a coalescing caller (the
    OSD CodecBatcher) produces near-arbitrary B values, which would
    churn the jit cache with single-use executables.  Zero-padding the
    batch axis to the bucket is byte-exact (GF matmul rows are
    independent) and bounds distinct shapes to log2(max_batch).
    """
    if b <= 1:
        return 1
    n = 1
    while n < b:
        n *= 2
    return n


# what the batch programs carry in a device trace, whichever engine
# serves: the module is ``jit_registry_gf_<engine>``, its operations
# sit under the scope
REGISTRY_SCOPE = "registry_gf"


def registry_program(engine: str, fn):
    """``fn`` jitted as ``registry_gf_<engine>`` under ``REGISTRY_SCOPE``:
    a trace tells the registry's launches from anything else a process
    runs (parallel/sharded_ec.py names the mesh's the same way)."""
    def program(*args):
        with jax.named_scope(REGISTRY_SCOPE):
            return fn(*args)
    program.__name__ = program.__qualname__ = f"{REGISTRY_SCOPE}_{engine}"
    return jax.jit(program)


@functools.lru_cache(maxsize=256)
def _bitmatrix_cached(mat_bytes: bytes, r: int, k: int) -> np.ndarray:
    mat = np.frombuffer(mat_bytes, dtype=np.uint8).reshape(r, k)
    return matrix_to_bitmatrix(mat).astype(np.int8)


def bitmatrix_i8(matrix: np.ndarray) -> np.ndarray:
    """(r,k) GF coefficient matrix -> (8r,8k) int8 GF(2) matrix (cached)."""
    matrix = np.ascontiguousarray(matrix, dtype=np.uint8)
    return _bitmatrix_cached(matrix.tobytes(), *matrix.shape)


@functools.lru_cache(maxsize=256)
def _bitmatrix_device(mat_bytes: bytes, r: int, k: int):
    """Device-resident W: one upload per coefficient matrix, ever."""
    return jax.device_put(_bitmatrix_cached(mat_bytes, r, k))


def bitmatrix_device(matrix: np.ndarray):
    matrix = np.ascontiguousarray(matrix, dtype=np.uint8)
    return _bitmatrix_device(matrix.tobytes(), *matrix.shape)


def _unpack_bits(data: jnp.ndarray) -> jnp.ndarray:
    """(k, N) uint8 -> (8k, N) int8 bit planes.

    Plane order matches matrix_to_bitmatrix: row 8j+s is bit s of chunk j.
    (bit 0 of an arithmetic right shift by s == bit s, for any sign.)
    """
    k = data.shape[0]
    planes = [((data >> s) & 1) for s in range(8)]
    # interleave to (k, 8, N) then flatten; stacking then reshape keeps the
    # 8j+s row order
    stacked = jnp.stack(planes, axis=1)  # (k, 8, N)
    return stacked.reshape(8 * k, data.shape[1]).astype(jnp.int8)


def _pack_bits(bits: jnp.ndarray) -> jnp.ndarray:
    """(8r, N) int32 bit rows (already mod 2) -> (r, N) uint8."""
    r8, n = bits.shape
    r = r8 // 8
    b = bits.reshape(r, 8, n)
    shifts = jnp.arange(8, dtype=jnp.int32).reshape(1, 8, 1)
    return (b << shifts).sum(axis=1).astype(jnp.uint8)


def _gf_matmul_math(w: jnp.ndarray, data_u8: jnp.ndarray) -> jnp.ndarray:
    bits = _unpack_bits(data_u8.astype(jnp.uint8))
    acc = jax.lax.dot_general(
        w, bits,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    return _pack_bits(acc & 1)


@functools.partial(jax.jit, static_argnames=())
def _gf_matmul_xla(w: jnp.ndarray, data_u8: jnp.ndarray) -> jnp.ndarray:
    return _gf_matmul_math(w, data_u8)


# ---------------------------------------------------------------------------
# Pallas fused kernel
# ---------------------------------------------------------------------------

def _pallas_kernel_body(r8: int, k: int, tile: int):
    def kernel(w_ref, data_ref, out_ref):
        # Mosaic has no i8 shrui; widen to i32 for the bit extraction
        data = data_ref[...].reshape(k, tile).astype(jnp.int32)
        planes = [((data >> s) & 1) for s in range(8)]
        stacked = jnp.stack(planes, axis=1).reshape(8 * k, tile).astype(jnp.int8)
        acc = jax.lax.dot_general(
            w_ref[:], stacked,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        ) & 1
        r = r8 // 8
        b = acc.reshape(r, 8, tile)
        shifts = jnp.arange(8, dtype=jnp.int32).reshape(1, 8, 1)
        out_ref[...] = ((b << shifts).sum(axis=1).astype(jnp.uint8)
                        .reshape(out_ref.shape))
    return kernel


def _interpret() -> bool:
    """Pallas kernels target the TPU; a CPU backend (the tier-1 suite)
    can only run them through the interpreter.  Derived from the
    backend, so nothing in the environment can put a chip run into
    interpret mode."""
    return jax.default_backend() == "cpu"


def _make_pallas_fn(r8: int, k: int, n: int, tile: int):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    grid = (n // tile,)
    fn = pl.pallas_call(
        _pallas_kernel_body(r8, k, tile),
        out_shape=jax.ShapeDtypeStruct((r8 // 8, n), jnp.uint8),
        grid=grid,
        in_specs=[
            pl.BlockSpec((r8, 8 * k), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((k, tile), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((r8 // 8, tile), lambda i: (0, i),
                               memory_space=pltpu.VMEM),
        interpret=_interpret(),
    )
    return jax.jit(fn)


def _make_pallas_batch_fn(r8: int, k: int, b: int, l: int, tile: int):
    """Batched stripes without the (B,k,L)->(k,B*L) transpose copy: the
    grid walks (stripe, tile) and each step reads a (1,k,tile) block.
    One dispatch, HBM traffic = bytes in + parity out."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    grid = (b, l // tile)
    fn = pl.pallas_call(
        _pallas_kernel_body(r8, k, tile),
        out_shape=jax.ShapeDtypeStruct((b, r8 // 8, l), jnp.uint8),
        grid=grid,
        in_specs=[
            pl.BlockSpec((r8, 8 * k), lambda i, j: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, k, tile), lambda i, j: (i, 0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, r8 // 8, tile), lambda i, j: (i, 0, j),
                               memory_space=pltpu.VMEM),
        interpret=_interpret(),
    )
    return registry_program("v1", fn)


# ---------------------------------------------------------------------------
# MXU-packed kernel family (v2/v3): the v1 kernel keeps the systolic
# array ~9% utilized -- the bit-matmul contraction is only 8k<=64 of the
# MXU's 128 rows, and the int32-widened unpack plus the sublane-strided
# pack burn VPU cycles on relayouts.  This family is parameterized so
# the best point can be AUTOTUNED on real hardware (tools/ec_autotune.py
# writes ceph_tpu/ops/gf2_tuned.json):
#   * group g: stripes packed per grid step; contraction is 8*k*g (=128
#     for the headline k=8 at g=2) so every MXU column-cycle carries g
#     byte columns of work;
#   * unpack "concat" (8 mask-compares concatenated plane-major, no
#     int32 widening) or "bcast" (one broadcast compare + reshape);
#   * matmul dtype int8 (MXU int path) or bf16 (MXU native path; bit
#     counts <=128 are exact in bf16);
#   * pack "vpu" (shift+sum over an (r,8,T) view) or "mxu" (a second
#     tiny matmul against a power-of-two matrix, keeping the relayout
#     on the systolic array);
#   * lane tile T.
# Byte-identical to the host path; selected from the shape at runtime
# with a one-time parity self-check that RAISES on a miss.

G2_DEFAULT = {"unpack": "concat", "mm": "int8", "pack": "vpu",
              "tile": LANE_TILE}
_TUNED_PATH = os.path.join(os.path.dirname(__file__), "gf2_tuned.json")


@functools.lru_cache(maxsize=1)
def _tuned_cfgs() -> dict:
    """{str(k): cfg} autotuned on hardware; absent file = defaults
    (a malformed file is an error, not defaults)."""
    try:
        with open(_TUNED_PATH) as f:
            return json.load(f)
    except FileNotFoundError:
        return {}


def _g2_cfg(k: int) -> dict:
    return {**G2_DEFAULT, **_tuned_cfgs().get(str(k), {})}


def pick_group(k: int, b: int) -> int:
    """Largest g with contraction 8*k*g <= 128 that divides the batch."""
    g = max(1, 16 // k)
    while g > 1 and (b % g or 8 * k * g > 128):
        g //= 2
    return g


@functools.lru_cache(maxsize=64)
def _w_gN_planemajor(mat_bytes: bytes, r: int, k: int,
                     g: int) -> np.ndarray:
    """(g*8r, 8*g*k): block-diagonal-by-stripe W whose columns match the
    plane-major layout of the unpacked concat of g stripes' chunks:
    RHS row s*(g*k) + j  <->  bit s of chunk j (stripe = j // k)."""
    w = _bitmatrix_cached(mat_bytes, r, k)      # (8r, 8k), col 8j+s
    r8 = 8 * r
    gk = g * k
    out = np.zeros((g * r8, 8 * gk), np.int8)
    for s in range(8):
        for j in range(gk):
            stripe, jj = divmod(j, k)
            out[stripe * r8:(stripe + 1) * r8, s * gk + j] = \
                w[:, 8 * jj + s]
    return out


def _kernel_body_gN(r8: int, k: int, g: int, tile: int, unpack: str,
                    mm: str, pack: str):
    r = r8 // 8
    gk = g * k

    def _pack_mat_iota():
        # (g*r, g*8r) with P[i, 8i+s] = 2**s, built in-kernel (pallas
        # cannot capture array constants) from iotas
        rows = jax.lax.broadcasted_iota(jnp.int32, (g * r, g * r8), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (g * r, g * r8), 1)
        pow2 = (1 << (cols % 8))
        return jnp.where(cols // 8 == rows, pow2, 0).astype(jnp.bfloat16)

    def kernel(w_ref, d_ref, o_ref):
        x = d_ref[...].reshape(gk, tile)             # g stripes' chunks
        if unpack == "bcast":
            masks = (1 << jax.lax.broadcasted_iota(
                jnp.int32, (8, 1, 1), 0)).astype(jnp.uint8)
            bits = (x[None] & masks) != 0            # (8, gk, T)
            bits = bits.reshape(8 * gk, tile)
        else:
            ps = [(x & np.uint8(1 << s)).astype(jnp.bool_)
                  for s in range(8)]
            bits = jnp.concatenate(ps, axis=0)       # (8gk, T) plane-major
        if mm == "bf16":
            # 0/1 entries, contraction <=128: sums are exact in bf16
            acc = jax.lax.dot_general(
                w_ref[:].astype(jnp.bfloat16), bits.astype(jnp.bfloat16),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32).astype(jnp.int32) & 1
        else:
            acc = jax.lax.dot_general(
                w_ref[:], bits.astype(jnp.int8),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32) & 1     # (g*8r, T)
        if pack == "mxu":
            out = jax.lax.dot_general(
                _pack_mat_iota(), acc.astype(jnp.bfloat16),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)       # (g*r, T) exact
            # Mosaic has no f32 -> u8 cast; go through i32
            o_ref[...] = (out.astype(jnp.int32).astype(jnp.uint8)
                          .reshape(g, r, tile))
        else:
            # global row stripe*8r + 8i + t == ((stripe*r + i)*8) + t,
            # so one reshape groups each output byte's 8 bit rows
            b = acc.reshape(g * r, 8, tile)
            shifts = jnp.arange(8, dtype=jnp.int32).reshape(1, 8, 1)
            o_ref[...] = ((b << shifts).sum(axis=1).astype(jnp.uint8)
                          .reshape(g, r, tile))
    return kernel


def _make_pallas_batch_fn_gN(r8: int, k: int, b: int, l: int, g: int,
                             tile: int, unpack: str, mm: str, pack: str):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    r = r8 // 8
    fn = pl.pallas_call(
        _kernel_body_gN(r8, k, g, tile, unpack, mm, pack),
        out_shape=jax.ShapeDtypeStruct((b, r, l), jnp.uint8),
        grid=(b // g, l // tile),
        in_specs=[
            pl.BlockSpec((g * r8, 8 * g * k), lambda i, j: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((g, k, tile), lambda i, j: (i, 0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((g, r, tile), lambda i, j: (i, 0, j),
                               memory_space=pltpu.VMEM),
        interpret=_interpret(),
    )
    return registry_program("gN", fn)


@functools.lru_cache(maxsize=512)
def _compiled(r8: int, k: int, n_padded: int, use_pallas: bool):
    if use_pallas:
        return _make_pallas_fn(r8, k, n_padded, min(LANE_TILE, n_padded))
    return _gf_matmul_xla


def clear_kernel_cache() -> None:
    for fn in (_compiled, _compiled_batch, _compiled_batch_gN,
               _w_gN_device, _w_gN_planemajor, _bitmatrix_cached,
               _bitmatrix_device, _tuned_cfgs):
        getattr(fn, "cache_clear", lambda: None)()
    _gN_verified.clear()
    _arena.clear()
    from .xor_schedule import clear_schedule_cache
    clear_schedule_cache()


def _want_pallas() -> bool:
    return jax.default_backend() != "cpu"


def _pad_n(n: int) -> int:
    # bucket N so the jit cache stays small: pad to LANE_TILE multiples,
    # with a small-size bucket ladder below one tile
    if n >= LANE_TILE:
        return ((n + LANE_TILE - 1) // LANE_TILE) * LANE_TILE
    b = 512
    while b < n:
        b *= 2
    return b


def gf_matmul_device(matrix: np.ndarray, data, *, out_np: bool = True):
    """(r,k) GF(2^8) coeff matrix x (k,N) bytes -> (r,N) bytes, on device.

    ``data`` may be a numpy array or a device array; the result is returned
    as numpy when out_np (plugin path) or left on device (bench path).
    """
    w = bitmatrix_device(matrix)
    r8, k8 = w.shape
    k = k8 // 8
    n = data.shape[1]
    n_pad = _pad_n(n)
    use_pallas = _want_pallas() and n_pad % 128 == 0
    fn = _compiled(r8, k, n_pad, use_pallas)
    xd = jnp.asarray(data, dtype=jnp.uint8)
    if n_pad != n:
        xd = jnp.pad(xd, ((0, 0), (0, n_pad - n)))
    out = fn(w, xd)
    if n_pad != n:
        out = out[:, :n]
    return np.asarray(out) if out_np else out


@functools.lru_cache(maxsize=256)
def _w_gN_device(mat_bytes: bytes, r: int, k: int, g: int, mm: str):
    w = _w_gN_planemajor(mat_bytes, r, k, g)
    if mm == "bf16":
        w = w.astype(jnp.bfloat16)
    return jax.device_put(w)


def _pick_tile(l: int, want: int = LANE_TILE) -> int:
    """Lane-tile ladder shared by the batch kernels; 0 = ineligible."""
    if l % want == 0:
        return want
    if l % LANE_TILE == 0:
        return LANE_TILE
    if l <= LANE_TILE and l % 128 == 0:
        return l
    return 0


@functools.lru_cache(maxsize=512)
def _compiled_batch_gN(r8: int, k: int, b: int, l: int, g: int,
                       unpack: str, mm: str, pack: str, tile: int):
    return _make_pallas_batch_fn_gN(r8, k, b, l, g, tile, unpack, mm,
                                    pack)


def _gN_plan(k: int, b: int, l: int, cfg: dict) -> tuple[int, int] | None:
    """(group, tile) when the MXU-packed kernel can take this shape,
    else None (the v1 kernel / XLA path serves: a choice made from the
    shape, before anything runs)."""
    g = int(cfg.get("g") or pick_group(k, b))
    if 8 * k * g > 128 or b % g:
        # a tuned g incompatible with THIS batch (odd tail batch)
        # clamps to a compatible group instead of losing the packed
        # kernel entirely
        g = pick_group(k, b)
    if 8 * k * g > 128 or b % g or b < g:
        return None
    if (g * k) % 8:
        # Mosaic (jax 0.9, v5e) refuses both unpacks when the g*k chunk
        # rows do not fill whole 8-sublane tiles (k=10): the concat of
        # i1 planes needs an "invalid vector register cast" and the
        # bcast reshape is an "unsupported shape cast"
        return None
    tile = _pick_tile(l, int(cfg.get("tile", LANE_TILE)))
    return (g, tile) if tile else None


# (matrix, shape, cfg or engine) keys whose kernel passed its one-time
# byte-parity gate vs the host oracle
_gN_verified: set[tuple] = set()


def _run_gN(matrix: np.ndarray, xd, b: int, k: int, l: int, cfg: dict,
            g: int, tile: int, perf=None):
    """Launch the MXU-packed kernel.  A compile failure propagates; a
    first-launch parity miss raises ``KernelParityError``."""
    mat_bytes = matrix.tobytes()
    r = matrix.shape[0]
    fn = _compiled_batch_gN(8 * r, k, b, l, g, cfg["unpack"], cfg["mm"],
                            cfg["pack"], tile)
    with section("registry.matrix"):
        w = _w_gN_device(mat_bytes, r, k, g, cfg["mm"])
    out = fn(w, xd)
    key = (mat_bytes, b, l, tuple(sorted(cfg.items())), g)
    if key not in _gN_verified:
        check_batch_parity("gN pallas kernel", matrix, xd, out, min(g, 2),
                           perf)
        _gN_verified.add(key)
    return out


class KernelParityError(RuntimeError):
    """A device kernel's first launch disagreed with the host GF
    oracle.  Raised to the caller: a silently-wrong kernel must never
    serve, and another engine must never quietly serve in its place."""


def check_batch_parity(what: str, matrix: np.ndarray, xd, out,
                       nb: int, perf=None) -> None:
    """One-time byte-parity gate vs the host oracle on a small slice of
    a (B, k, L) launch; raises ``KernelParityError`` on a miss.  It
    waits for the launch it checks: ``perf`` counts it
    (``parity_gates``) and the section times it."""
    from ..gf import gf_matmul
    ncheck = min(256, xd.shape[2])
    if perf is not None:
        perf.inc("parity_gates")
    with section("registry.matrix"):
        # lint: disable=device-path-host-sync -- one-time parity gate vs the host oracle, bounded slice
        got = np.asarray(out[:nb, :, :ncheck])
        # lint: disable=device-path-host-sync -- one-time parity gate vs the host oracle, bounded slice
        sample = np.asarray(xd[:nb, :, :ncheck])
        for i in range(nb):
            if not np.array_equal(got[i], gf_matmul(matrix, sample[i])):
                raise KernelParityError(
                    f"{what} disagrees with the host GF oracle "
                    f"(matrix {matrix.shape}, batch shape "
                    f"{tuple(xd.shape)}, stripe {i})")


@functools.lru_cache(maxsize=512)
def _compiled_batch(r8: int, k: int, b: int, l: int, use_pallas: bool):
    if use_pallas:
        return _make_pallas_batch_fn(r8, k, b, l, _pick_tile(l))

    def fn(w, xd):  # whole path under one jit: one dispatch per call
        flat = xd.transpose(1, 0, 2).reshape(k, b * l)
        out = _gf_matmul_math(w, flat)
        return out.reshape(r8 // 8, b, l).transpose(1, 0, 2)
    return registry_program("xla", fn)


def _select_batch_engine(matrix: np.ndarray, b: int, k: int, l: int):
    """(engine name, plan) for a (B, k, L) launch of ``matrix``, from
    the backend and the shape alone.  Engines: "sched" (the
    CSE-minimized XOR schedule as an XLA program, ops/xor_schedule.py,
    when its cost model picks it; plan = the schedule), "gN" (the
    MXU-packed pallas kernel; plan = (cfg, group, tile)), "v1" (the
    per-stripe pallas kernel), "xla"."""
    from .xor_schedule import want_scheduled
    pallas = _want_pallas()
    sched = want_scheduled(bitmatrix_i8(matrix), l, jax.default_backend(),
                           have_packed=pallas)
    if sched is not None:
        return "sched", sched
    if pallas:
        cfg = _g2_cfg(k)
        plan = _gN_plan(k, b, l, cfg)
        if plan:
            return "gN", (cfg, *plan)
        if _pick_tile(l):
            return "v1", None
    return "xla", None


def batch_engine(matrix: np.ndarray, b: int, k: int, l: int) -> str:
    """Name of the engine ``gf_matmul_batch_device`` launches for this
    matrix and (B, k, L) shape (observability: benches and the chip
    smoke state which kernel served)."""
    matrix = np.ascontiguousarray(matrix, dtype=np.uint8)
    return _select_batch_engine(matrix, b, k, l)[0]


# input bytes of one slab of a host-to-host call (``_slab_stripes``)
SLAB_BYTES = 32 << 20
# slabs such a call keeps between ``device_put`` and landing before its
# thread blocks for the oldest; with the one it then lands,
# ``SLABS_IN_FLIGHT + 1`` are on their way up, in the kernel or on their
# way down, and as many staging buffers serve a call that gathers.  One
# upload alone gets 4.5 GiB/s of the host's link and four or more in
# flight 11.7-11.9; the smallest depth of a sweep of 2, 3, 4, 6, 8
# within 2 % of the best at (32, 8, 131072) slabs under which the
# (1, 10, 3350528) slabs lose nothing (PERF.md section 6, PR 53)
SLABS_IN_FLIGHT = 6
# host bytes the process keeps at rest for the next such call's result
# and staging (``host_arena.HostArena``); a buffer past it is dropped.
# What a caller's loop leaves there while it keeps one result, as both
# registry cells' drivers do (MiB; PERF.md section 7): at k=8, m=3 over
# 1024 x 1 MiB a second encode's 384, a decode's 128 and
# ``SLABS_IN_FLIGHT + 1`` staging buffers of 32 = 736; at k=10, m=4 over
# 4608 objects of 4 KiB to 1 MiB 409 + 205 + 224 = 838
ARENA_BYTES = 1 << 30
_arena = HostArena(ARENA_BYTES)


def _slab_stripes(b: int, k: int, l: int) -> int:
    """Stripes a slab of a (B, k, L) host-to-host call holds:
    ``SLAB_BYTES`` of input in whole groups of the packed engine, one
    group at least, the batch at most."""
    g = pick_group(k, b)
    return min(b, max(g, SLAB_BYTES // (k * l) // g * g))


def _slab_lanes(k: int) -> int:
    """Lanes a slab of a call over pieces of unequal length holds:
    ``SLAB_BYTES`` of input across the k operand rows, in whole lane
    tiles.  One width whatever the call's mix of lengths, so one
    program a count of output rows."""
    lanes = SLAB_BYTES // k
    if lanes >= LANE_TILE:
        return lanes // LANE_TILE * LANE_TILE
    return max(128, lanes // 128 * 128)


class LanePieces:
    """The host operands of one call over pieces of unequal length (an
    object's chunks, ``L_i`` bytes each), laid end to end on the lane
    axis: the GF product acts on every byte column alone, so the k
    operand rows of all pieces are one ``(k, lanes)`` row of columns,
    which ``gf_matmul_batch_device`` cuts into slabs of
    ``_slab_lanes`` columns wherever a piece begins or ends (a piece
    may lie across two slabs).

    ``lengths[i]`` is piece i's ``L_i``; ``blocks[i]`` its operand
    bytes as ``(first operand row, 2-D host array)`` pairs, row j of a
    block being columns ``0:block.shape[1]`` of operand row
    ``first + j``; ``tails[i]`` is None or ``(row, col)``: from column
    ``col`` of operand row ``row`` to the piece's end no block has
    bytes and the operand is zero.  Nothing is copied until ``fill``,
    and nothing outside the blocks is ever read."""

    def __init__(self, lengths, blocks, tails) -> None:
        self.lengths = np.asarray(lengths, dtype=np.int64)
        self.ends = np.cumsum(self.lengths)
        self.starts = self.ends - self.lengths
        self.lanes = int(self.ends[-1]) if len(self.ends) else 0
        self.blocks = blocks
        self.tails = tails

    def within(self, lo: int, hi: int):
        """The pieces that have columns among ``lo:hi`` of the call's
        lanes: ``(piece, a, b, off)``, its columns ``a:b`` lying at
        column ``off`` of the slab that starts at ``lo``."""
        first = int(np.searchsorted(self.ends, lo, side="right"))
        last = int(np.searchsorted(self.starts, hi, side="left"))
        for i, start, length in zip(range(first, last),
                                    self.starts[first:last].tolist(),
                                    self.lengths[first:last].tolist()):
            a, b = max(lo - start, 0), min(hi - start, length)
            yield i, a, b, start + a - lo

    def fill(self, lo: int, hi: int, into: np.ndarray) -> np.ndarray:
        """Columns ``lo:hi`` of the call's lanes into ``into[0, :,
        :hi - lo]`` (``into`` is a staging buffer ``(1, k, slab
        lanes)``; what lies past ``hi - lo`` is left as it was: those
        lanes' results are dropped)."""
        rows = into[0]
        for i, a, b, off in self.within(lo, hi):
            for row, block in self.blocks[i]:
                part = block[:, a:b]
                rows[row:row + part.shape[0],
                     off:off + part.shape[1]] = part
            tail = self.tails[i]
            if tail is not None:
                row, col = tail
                rows[row, off + min(max(col - a, 0), b - a):off + b - a] = 0
                rows[row + 1:, off:off + b - a] = 0
        return into


def _gather_rows(data, rows, lo: int, hi: int, into: np.ndarray):
    """Chunks ``rows`` of stripes ``lo:hi`` of a (B, n, L) host array
    into the C-ordered ``into``, chunk by chunk (``data[lo:hi, rows]``
    comes back with the chunk axis outermost in memory, and an upload
    of it copies the slab a second time); no other chunk is read."""
    for row, chunk in enumerate(rows):
        into[:hi - lo, row] = data[lo:hi, chunk]
    return into[:hi - lo]


def _launch_batch(matrix: np.ndarray, xd, perf=None):
    """One (B, k, L) device array through ``batch_engine``'s choice:
    (engine name, the (B, r, L) device result, not waited for)."""
    b, k, l = xd.shape
    engine, plan = _select_batch_engine(matrix, b, k, l)
    if engine == "sched":
        from .xor_schedule import sched_matmul_batch_device
        out = sched_matmul_batch_device(plan, matrix, xd, b, k, l, perf)
    elif engine == "gN":
        out = _run_gN(matrix, xd, b, k, l, *plan, perf)
    else:
        with section("registry.matrix"):
            w = bitmatrix_device(matrix)
        fn = _compiled_batch(w.shape[0], k, b, l, engine == "v1")
        out = fn(w, xd)
        key = (matrix.tobytes(), b, l, engine)
        if key not in _gN_verified:
            check_batch_parity(f"{engine} kernel", matrix, xd, out, 1, perf)
            _gN_verified.add(key)
    return engine, out


def gf_matmul_batch_device(matrix: np.ndarray, data, *, rows=None,
                           out_np: bool = False, perf=None):
    """Batched stripes: (B, k, L) -> (B, r, L) (layout changes
    included: a launch lives under one jit).  With ``rows``, ``data`` is
    (B, n, L) and stripe s's operands are ``data[s, rows]``, k of its n
    chunks in that order; no other chunk is read.  The engine is
    ``batch_engine``'s choice; whatever it picks either serves or raises.

    A device array in, or ``out_np=False``, is ONE dispatch, and
    nothing is waited for.  ``data`` in host memory with the result
    asked back to it (``out_np``) streams through the device in slabs
    of ``_slab_stripes`` stripes, each through the same program at the
    slab's shape; a batch no larger than a slab is one slab.  Per slab
    and under its own section on the caller's thread:
    ``registry.marshal`` (with ``rows``: the slab's survivors in a
    staging buffer; a one-slab call gathers them there and then, a call
    of several waits for its worker, below), ``registry.upload``
    (``device_put``; a one-slab call waits until the bytes are on the
    device, a slab of many does not), ``registry.launch`` (engine choice
    and dispatch; the matrix's device copy and a first launch's parity
    gate are ``registry.matrix`` inside it), then the result's copy to
    the host is started and, once more than ``SLABS_IN_FLIGHT`` slabs
    are between their ``device_put`` and their landing, the oldest of
    them is landed in its rows of the call's one result array
    (``registry.drain``, its three waits nested in it under names of
    their own: ``registry.drain.kernel``
    until the slab's launch is done, whether the device or the upload
    of its operand was late, opened only where the launch is not done
    when the landing comes to it, so that a landing blocks once where
    it need not block twice; ``registry.drain.link`` until the copy-out
    started at the launch has brought its bytes to the host;
    ``registry.drain.land`` the host copy into the result).  The call
    closes with the last slab's ``registry.device_wait`` and
    ``registry.copy_out`` (until the last byte is readable on the
    host, and into the result); the result is a C-ordered array of the
    caller's own.

    The gather of a call of several slabs is a stage of its own, on a
    worker thread the call starts and ends (``registry-gather``; one a
    call in flight, so two callers never wait for each other's): the
    caller hands it slab 0 at once and slab i+1 before it uploads slab
    i, over ``SLABS_IN_FLIGHT + 1`` staging buffers (one being filled,
    and one for every slab in flight whose launch may still read its
    upload), and ``registry.marshal`` is only the wait for the slab
    about to go up.  The worker's time is ``registry.gather`` on its own
    thread: the wait for the launch that read the buffer's last upload
    (slab ``i + 1 - staging``'s, looked up by its index among the slabs
    in flight; ``registry.gather.wait`` nested in it, only where there
    is something to wait for), then ``_gather_rows`` or
    ``LanePieces.fill``, whose numpy copies release the GIL.  So slab
    i+1's gather and the uploads, kernels and copy-outs of slabs i down
    to i - ``SLABS_IN_FLIGHT`` are in flight together, on two threads,
    the link and the device, and ``SLABS_IN_FLIGHT + 1`` slabs at most
    live on the device; a call of fewer slabs never reaches the depth.
    A call without a gather (no ``rows``, no pieces: the caller's array
    is uploaded as it is), of one slab, or from a device array starts no
    worker.

    Where the host memory of a call of several slabs comes from: the
    process's ``HostArena`` (``host_arena.py``; ``ARENA_BYTES`` at rest
    at most), so that a caller's loop writes into pages it has touched
    before and not into 448 MiB of fresh ones a call.  The result is a
    ``lease``: exactly ``(B, r, L)`` over the smallest kept buffer that
    fits, else over a fresh one; it, and every slice cut from it, is
    the caller's alone until the last array over its memory is gone,
    and only then does the buffer go back for a later call.  The
    staging buffers (a slab each, ``SLABS_IN_FLIGHT + 1`` at most) are
    taken at the call's start and given back at its end, also when the
    call raises, on either thread (the worker's exception is the call's,
    raised where the caller asks for that slab): a staging buffer is
    refilled, or given back, only behind the launch that read its upload, and
    before it returns either way the call has waited for its worker
    and for every launch it made; no thread outlives it.  A uniform
    call of one slab borrows nothing: its result is ``np.asarray`` of
    the launch's.

    ``data`` a ``LanePieces`` (pieces of unequal length, always from
    host memory to host memory) goes through the same loop with a slab
    cut over the call's LANES: every slab is one ``(1, k,
    _slab_lanes(k))`` launch whatever lengths the call mixes (one
    program a count of output rows; the last slab's spare lanes are
    launched and dropped), the gather is ``LanePieces.fill`` into the
    staging buffer, and a slab lands in its columns of the call's
    ``(r, lanes)`` result, which is leased, as the staging is, at any
    number of slabs: the caller cuts each piece's ``(r, L_i)`` out of
    it as a view.

    ``perf`` (the plugin's ``ec_registry`` set) counts a call once,
    however many slabs: ``launches``, ``stripes``, ``bytes_in``,
    ``bytes_out``, ``engine_<name>``; and ``slabs`` (device launches),
    ``pipelined`` (calls of more than one slab), ``gathers`` (slabs
    whose staging the worker filled), ``gathers_ahead`` (those already
    filled when the caller's thread came for them), ``staging_waits``
    (refills that had to wait for a launch, counted where the worker
    waits), ``uploads_beside`` (over the call's slabs, the earlier slabs
    in flight whose launch was not done when the slab's ``device_put``
    went out: over ``slabs`` it is the mean number of slabs on their way
    up or in the kernel beside a new upload, 0 for a call of one slab
    and ``SLABS_IN_FLIGHT`` at most), ``parity_gates``, ``arena_hits`` /
    ``arena_misses`` (one a buffer borrowed, result or staging: a kept
    one, or a fresh allocation); for a ``LanePieces`` call ``objects``
    (its pieces), ``lanes`` (the columns the caller asked for),
    ``lanes_launched`` (slabs x slab width) and ``lanes_padded`` (their
    difference) in the place of ``stripes``."""
    matrix = np.ascontiguousarray(matrix, dtype=np.uint8)
    r, k = matrix.shape
    ragged = isinstance(data, LanePieces)
    on_host = not isinstance(data, jax.Array)
    if ragged:
        # one virtual stripe a slab: the call's lanes, a slab's width each
        l, step, out_np = _slab_lanes(k), 1, True
        spans = [(lo, min(lo + l, data.lanes))
                 for lo in range(0, data.lanes, l)]
        result = _arena.lease((r, data.lanes), perf)
    else:
        b, _, l = data.shape
        step = _slab_stripes(b, k, l) if on_host and out_np else b
        spans = [(lo, min(lo + step, b)) for lo in range(0, b, step)]
        result = _arena.lease((b, r, l), perf) if len(spans) > 1 else None
    borrowed: list[np.ndarray] = []     # staging to give back at the end
    worker = None           # fills a slab's staging ahead of its upload
    if on_host and (ragged or rows is not None):
        if result is not None:
            # a slab being filled, and one for every slab in flight
            # whose launch may still read its upload
            borrowed = [_arena.take(step * k * l, perf)
                        for _ in range(min(SLABS_IN_FLIGHT + 1, len(spans)))]
            staging = [buf[:step * k * l].reshape(step, k, l)
                       for buf in borrowed]
        else:
            staging = [np.empty((step, k, l), np.uint8)]
        if len(spans) > 1:
            worker = ThreadPoolExecutor(1, "registry-gather")
    flying: collections.deque = collections.deque()   # (slab, lo, hi, out)
    waits = ahead = beside = 0
    xd = filling = None

    def fill(i: int) -> np.ndarray:
        lo, hi = spans[i]
        into = staging[i % len(staging)]
        return (data.fill(lo, hi, into) if ragged
                else _gather_rows(data, rows, lo, hi, into))

    def fill_ahead(i: int, reader) -> np.ndarray:
        """Slab i's staging, on the worker, behind ``reader``: the
        launch that read the buffer's last upload (slab
        ``i - len(staging)``'s; None where that slab has landed, or
        there is none)."""
        nonlocal waits
        with section("registry.gather"):
            if reader is not None and not reader.is_ready():
                waits += 1
                with section("registry.gather.wait"):
                    # lint: disable=device-path-host-sync -- a staging buffer is refilled only after the launch that read it
                    reader.block_until_ready()
            return fill(i)

    def put(lo: int, hi: int, host: np.ndarray) -> None:
        if ragged:
            result[:, lo:hi] = host[0, :, :hi - lo]
        else:
            result[lo:hi] = host

    def drain() -> None:
        """The oldest slab in flight into its place in the result, each
        of its waits under a name of its own.  A launch that is done is
        not waited for: the landing then blocks once, as it would
        without the names."""
        with section("registry.drain"):
            _, lo, hi, out = flying.popleft()
            if not out.is_ready():
                with section("registry.drain.kernel"):
                    # lint: disable=device-path-host-sync -- the wait for the launch, timed apart from the copy-out that follows it
                    out.block_until_ready()
            with section("registry.drain.link"):
                # lint: disable=device-path-host-sync -- the caller asked for host bytes (out_np); the copy was started at the launch
                host = np.asarray(out)
            with section("registry.drain.land"):
                put(lo, hi, host)

    try:
        if worker is not None:
            filling = worker.submit(fill_ahead, 0, None)
        for i, (lo, hi) in enumerate(spans):
            if not on_host:
                xd = jnp.asarray(data, dtype=jnp.uint8)
                if rows is not None:
                    xd = jnp.take(xd, jnp.asarray(rows), axis=1)
            else:
                if rows is None and not ragged:
                    slab = np.ascontiguousarray(data[lo:hi], dtype=np.uint8)
                else:
                    with section("registry.marshal"):
                        if worker is None:
                            slab = fill(i)
                        else:
                            ahead += filling.done()
                            slab = filling.result()
                            if i + 1 < len(spans):
                                # the buffer slab i + 1 goes into was
                                # last uploaded for the slab this many back
                                last = i + 1 - len(staging)
                                filling = worker.submit(
                                    fill_ahead, i + 1, next(
                                        (out for j, _, _, out in flying
                                         if j == last), None))
                with section("registry.upload"):
                    beside += sum(not out.is_ready()
                                  for _, _, _, out in flying)
                    xd = jax.device_put(slab)
                    if result is None:
                        # lint: disable=device-path-host-sync -- a one-slab upload is timed apart from the kernel it feeds; the launch needs its last byte either way
                        xd.block_until_ready()
            with section("registry.launch"):
                engine, out = _launch_batch(matrix, xd, perf)
                if i == 0:
                    served = engine
                if result is not None:
                    out.copy_to_host_async()
            flying.append((i, lo, hi, out))
            if len(flying) > SLABS_IN_FLIGHT:
                drain()
        if perf is not None:
            perf.inc("launches")
            perf.inc(f"engine_{served}")
            if ragged:
                launched = len(spans) * l
                perf.inc("objects", len(data.lengths))
                perf.inc("lanes", data.lanes)
                perf.inc("lanes_launched", launched)
                perf.inc("lanes_padded", launched - data.lanes)
                perf.inc("bytes_in", k * data.lanes)
                perf.inc("bytes_out", r * data.lanes)
            else:
                perf.inc("stripes", b)
                perf.inc("bytes_in", b * k * l)
                perf.inc("bytes_out", b * r * l)
            perf.inc("slabs", len(spans))
            if len(spans) > 1:
                perf.inc("pipelined")
            if worker is not None:
                perf.inc("gathers", len(spans))
                perf.inc("gathers_ahead", ahead)
            if waits:
                perf.inc("staging_waits", waits)
            if beside:
                perf.inc("uploads_beside", beside)
        if not out_np:
            return out
        while len(flying) > 1:
            drain()
        with section("registry.device_wait"):
            # lint: disable=device-path-host-sync -- the caller asked for host bytes (out_np): the wait is timed apart from the copy
            out.block_until_ready()
        with section("registry.copy_out"):
            if result is None:
                # lint: disable=device-path-host-sync -- the single post-launch materialization (caller opts in via out_np)
                return np.asarray(out)
            _, lo, hi, out = flying.popleft()
            # lint: disable=device-path-host-sync -- the caller asked for host bytes (out_np); the copy was started at the launch
            put(lo, hi, np.asarray(out))
            return result
    finally:
        if worker is not None:
            # a call that raised may leave a fill running: it ends first
            worker.shutdown(wait=True, cancel_futures=True)
        if borrowed:
            # a call that raised may leave a slab on its way to the device
            for held in (xd, *(out for _, _, _, out in flying)):
                if held is not None:
                    # lint: disable=device-path-host-sync -- staging goes back only behind the last launch that read it
                    held.block_until_ready()
            for buf in borrowed:
                _arena.give(buf)
