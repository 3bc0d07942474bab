"""XOR-schedule compiler: CSE-minimized GF(2) bit-matrix kernels.

The dense bit-matmul family (ops/gf2kernels.py) multiplies by a matrix
that is mostly zeros: a Cauchy k=8,m=3 bitmatrix is ~half ones, and a
liberation RAID-6 matrix is minimal-density by construction.  The MXU
does not care (the systolic array runs the full contraction either
way), but every OTHER engine does -- the XLA:CPU fallback and the host
numpy path pay for every zero.  "Accelerating XOR-based Erasure Coding
using Program Optimization Techniques" (PAPERS.md) shows the fix: the
bit-matrix IS a set of XOR equations, and common-subexpression
elimination over those equations plus a good evaluation order cuts the
XOR count severalfold.

This module is that compiler:

  * ``compile_schedule`` lowers any (R, C) GF(2) 0/1 matrix to an
    ``XorSchedule``: greedy pairwise CSE (repeatedly extract the
    operand pair shared by the most equations into a temporary -- the
    paper's normalization+scheduling passes), then a just-in-time
    topological lowering into SSA XOR ops with temporaries scheduled
    immediately before first use and freed after last use, so the live
    register set stays small and REPORTED (``peak_registers``); a
    schedule whose peak exceeds ``max_registers`` is re-compiled with
    a smaller temp budget until the bound holds;
  * schedules are cached PROCESS-WIDE keyed by matrix digest (the
    VectorCrush one-compile-serves-all lesson): every OSD of an
    in-process cluster shares one compile;
  * two executors, byte-identical by construction: ``apply_host``
    (numpy rows -- the BitMatrixCodec data path) and
    ``apply_bits_traced`` (the jax-traceable (k, N) bytes -> (r, N)
    bytes block of the jitted XLA family).  There is no Pallas variant: on the v5e the per-tile
    kernel ran no faster than the XLA program for k=8,m=3 and
    exhausted scoped VMEM at k=10,m=4 (CHANGES.md, PR 21), and the
    MXU-bearing backends default to the dense family anyway;
  * ``sched_matmul_batch_device`` is the batched kernel family itself:
    the same (B, k, L) signature, padding buckets and one-launch
    contract as the dense ``gN`` family, parity-gated on first use per
    (matrix, shape) against the host oracle (a miss raises);
  * ``want_scheduled`` is the per-(matrix, shape) cost model: env
    override, then the autotuned winner recorded in ``gf2_tuned.json``
    (``tools/ec_autotune.py`` sweeps dense-vs-scheduled per
    (k, m, chunk)), then a backend heuristic comparing scheduled XOR
    terms against the dense contraction length.

Jax is imported lazily: the host executor serves jax-free paths (the
jerasure bitmatrix plugins) and must not pull the device stack in.
"""

from __future__ import annotations

import functools
import hashlib
import os
import threading
from collections import Counter
from dataclasses import dataclass

import numpy as np

# default register-file bound for compiled schedules: peak concurrently
# live temporaries.  64 matches a comfortable vector-register budget on
# every target; the compiler PROVES the bound (re-compiling with fewer
# temps if the first schedule exceeds it) rather than assuming it.
DEFAULT_MAX_REGISTERS = 64

# cost-model default: on CPU engines the dense contraction runs R*C
# multiply-accumulates per byte column while the schedule runs n_terms
# XORs; the MAC is not 1:1 with an XOR (XLA vectorizes both), so the
# schedule must beat the dense length by this factor to be picked.
CPU_DENSE_DISCOUNT = 0.35

# matrices beyond this many cells are not worth a Python-side CSE pass
# (nothing on the codec path is remotely this large)
MAX_COMPILE_CELLS = 1 << 18

# the SPECULATIVE compile bound: the backend heuristic and the
# build-time warms compile on the chance the schedule wins, and the
# greedy-CSE pass is quadratic in pair count -- a dense 20k-cell
# matrix (the pmsr k=5 parity bitmatrix) costs ~15s of pure Python,
# which would stall codec init / the first launch's event loop.
# Above this bound only an EXPLICIT opt-in compiles: a measured
# gf2_tuned.json entry or CEPH_TPU_XOR_SCHED=1 (both accept the
# one-time cost knowingly).
SPECULATIVE_MAX_CELLS = 1 << 14

# below this many bytes per plane row the naive xor_matmul's C-level
# gather+reduce beats the schedule's one-numpy-call-per-XOR dispatch
# overhead (measured crossover ~10 KiB; CEPH_TPU_XOR_SCHED=1 forces
# the scheduled engine anyway, e.g. for parity tests)
HOST_MIN_LANE = 16384


@dataclass(frozen=True)
class XorSchedule:
    """A compiled XOR evaluation plan for one GF(2) bit-matrix.

    Value ids are SSA: ids ``0..n_in-1`` are the input rows, id
    ``n_in + i`` is the value produced by ``ops[i] = (a, b)`` (the XOR
    of values ``a`` and ``b``).  ``outputs[j]`` names the value holding
    output row j -- possibly an input id (a single-one matrix row is a
    copy) or -1 (an all-zero row).
    """

    digest: str
    n_in: int
    n_out: int
    ops: tuple[tuple[int, int], ...]
    outputs: tuple[int, ...]
    naive_terms: int
    peak_registers: int
    max_registers: int

    @property
    def n_terms(self) -> int:
        return len(self.ops)

    @property
    def terms_saved(self) -> int:
        return self.naive_terms - self.n_terms

    @property
    def reduction(self) -> float:
        if not self.naive_terms:
            return 0.0
        return 1.0 - self.n_terms / self.naive_terms

    def last_uses(self) -> list[int]:
        """For each op value, the last OP index that reads it (its own
        definition index when no later op does).  Output stores happen
        eagerly at definition time (the executors write the output row
        the moment its value exists), so they do not extend a value's
        lifetime."""
        last = list(range(len(self.ops)))
        n_in = self.n_in
        for i, (a, b) in enumerate(self.ops):
            if a >= n_in:
                last[a - n_in] = i
            if b >= n_in:
                last[b - n_in] = i
        return last

    def outputs_by_value(self) -> dict[int, list[int]]:
        """value id -> output rows it serves (eager-store map)."""
        by_val: dict[int, list[int]] = {}
        for j, o in enumerate(self.outputs):
            by_val.setdefault(o, []).append(j)
        return by_val


def matrix_digest(matrix: np.ndarray) -> str:
    """Content digest of a 0/1 matrix; the process-wide schedule key."""
    m = np.ascontiguousarray(matrix, dtype=np.uint8)
    h = hashlib.sha256()
    h.update(b"%d,%d;" % m.shape)
    h.update(m.tobytes())
    return h.hexdigest()[:16]


def naive_xor_terms(matrix: np.ndarray) -> int:
    """XOR count of the row-by-row evaluation (ones - 1 per nonzero
    row): the baseline the schedule is measured against."""
    ones = (np.ascontiguousarray(matrix, np.uint8) != 0).sum(axis=1)
    return int(np.maximum(ones - 1, 0).sum())


# ---------------------------------------------------------------------------
# CSE + lowering
# ---------------------------------------------------------------------------

def _greedy_cse(rows: list[set[int]], n_in: int,
                max_temps: int) -> list[tuple[int, int]]:
    """Extract the most-shared operand pair into a temporary until no
    pair is shared by two equations (or the temp budget is spent).
    Deterministic: ties break to the smallest (a, b) pair.  Returns the
    temp definitions; ``rows`` is rewritten in place to reference them.
    """
    counts: Counter[tuple[int, int]] = Counter()
    for row in rows:
        ordered = sorted(row)
        for i, a in enumerate(ordered):
            for b in ordered[i + 1:]:
                counts[(a, b)] += 1
    temps: list[tuple[int, int]] = []
    while len(temps) < max_temps and counts:
        (a, b), n = min(counts.items(), key=lambda kv: (-kv[1], kv[0]))
        if n < 2:
            break
        t = n_in + len(temps)
        temps.append((a, b))
        for row in rows:
            if a in row and b in row:
                # incremental pair-count maintenance: pairs that lose a
                # member leave, pairs gaining the temp enter
                row.discard(a)
                row.discard(b)
                dropped = [(a, b)]
                for x in row:
                    for m in (a, b):
                        dropped.append((x, m) if x < m else (m, x))
                for pair in dropped:
                    counts[pair] -= 1
                    if not counts[pair]:
                        del counts[pair]
                for x in row:
                    counts[(x, t) if x < t else (t, x)] += 1
                row.add(t)
    return temps


def _lower(n_in: int, temps: list[tuple[int, int]],
           rows: list[set[int]]) -> tuple[tuple, tuple]:
    """Just-in-time topological lowering: a temporary's op is emitted
    immediately before its first use, outputs are left-to-right XOR
    chains.  Returns (ops, outputs) in SSA ids."""
    ops: list[tuple[int, int]] = []
    emitted: dict[int, int] = {}

    def resolve(x: int) -> int:
        if x < n_in:
            return x
        sid = emitted.get(x)
        if sid is None:
            a, b = temps[x - n_in]
            ia, ib = resolve(a), resolve(b)
            ops.append((ia, ib))
            sid = emitted[x] = n_in + len(ops) - 1
        return sid

    outputs: list[int] = []
    for row in rows:
        operands = sorted(row)
        if not operands:
            outputs.append(-1)
            continue
        acc = resolve(operands[0])
        for x in operands[1:]:
            ops.append((acc, resolve(x)))
            acc = n_in + len(ops) - 1
        outputs.append(acc)
    return tuple(ops), tuple(outputs)


def _peak_registers(n_in: int, ops: tuple, outputs: tuple) -> int:
    """Max concurrently-live computed values over the schedule.
    Inputs are free (they are the resident input array) and output
    stores happen at definition time, so a value lives from its op to
    its last OP use."""
    last = list(range(len(ops)))
    for i, (a, b) in enumerate(ops):
        for v in (a, b):
            if v >= n_in:
                last[v - n_in] = i
    deaths = Counter(last)
    live = peak = 0
    for i in range(len(ops)):
        live += 1
        peak = max(peak, live)
        live -= deaths.get(i, 0)
    return peak


def compile_schedule(matrix: np.ndarray, *,
                     max_registers: int = DEFAULT_MAX_REGISTERS,
                     max_temps: int | None = None) -> XorSchedule:
    """Lower a GF(2) 0/1 matrix to a CSE-minimized XOR schedule.

    Deterministic: the same matrix bytes always produce the identical
    schedule (pinned by tests/test_xor_schedule.py), so the digest is a
    complete cache key across processes and rounds.
    """
    m = np.ascontiguousarray(matrix, dtype=np.uint8)
    if m.ndim != 2:
        raise ValueError(f"bit-matrix must be 2-D, got {m.shape}")
    if m.size > MAX_COMPILE_CELLS:
        raise ValueError(f"matrix {m.shape} too large to schedule")
    n_out, n_in = m.shape
    digest = matrix_digest(m)
    naive = naive_xor_terms(m)
    budget = max_temps if max_temps is not None else m.size
    while True:
        rows = [set(np.flatnonzero(r).tolist()) for r in m]
        temps = _greedy_cse(rows, n_in, budget)
        ops, outputs = _lower(n_in, temps, rows)
        peak = _peak_registers(n_in, ops, outputs)
        if peak <= max_registers or budget == 0:
            break
        # too much sharing to hold in the register file: shrink the
        # temp budget (halving terminates at the naive schedule, whose
        # only live value is the running accumulator)
        budget = min(budget, len(temps)) // 2
    return XorSchedule(digest=digest, n_in=n_in, n_out=n_out, ops=ops,
                       outputs=outputs, naive_terms=naive,
                       peak_registers=peak, max_registers=max_registers)


# ---------------------------------------------------------------------------
# process-wide schedule cache + launch stats
# ---------------------------------------------------------------------------

_LOCK = threading.Lock()
_SCHEDULES: dict[str, XorSchedule] = {}


class _Stats:
    """Process-wide scheduled-launch counters: every launch of the
    scheduled engine, host executor or XLA family, notes itself
    here."""

    __slots__ = ("launches", "terms_saved")

    def __init__(self) -> None:
        self.launches = 0
        self.terms_saved = 0

    def snapshot(self) -> tuple[int, int]:
        with _LOCK:
            return (self.launches, self.terms_saved)

    def note_launch(self, sched: XorSchedule) -> None:
        with _LOCK:
            self.launches += 1
            self.terms_saved += sched.terms_saved


STATS = _Stats()


def schedule_for(matrix: np.ndarray, *,
                 compile_missing: bool = True) -> XorSchedule | None:
    """The cached schedule for a bit-matrix, compiling (and caching it
    process-wide) on miss unless ``compile_missing`` is False."""
    digest = matrix_digest(matrix)
    with _LOCK:
        sched = _SCHEDULES.get(digest)
    if sched is not None or not compile_missing:
        return sched
    sched = compile_schedule(matrix)
    with _LOCK:
        return _SCHEDULES.setdefault(digest, sched)


def cached_schedule(matrix: np.ndarray) -> XorSchedule | None:
    return schedule_for(matrix, compile_missing=False)


def registered(digest: str) -> XorSchedule:
    with _LOCK:
        return _SCHEDULES[digest]


def clear_schedule_cache() -> None:
    with _LOCK:
        _SCHEDULES.clear()
    _sched_verified.clear()
    _compiled_sched_batch.cache_clear()


# ---------------------------------------------------------------------------
# executors
# ---------------------------------------------------------------------------

def apply_host(sched: XorSchedule, planes: np.ndarray) -> np.ndarray:
    """(n_in, N) byte rows -> (n_out, N) byte rows on the host.

    Output rows are stored the moment their value exists and
    temporaries are freed at last use, so the working set matches the
    schedule's ``peak_registers`` bound."""
    planes = np.ascontiguousarray(planes, dtype=np.uint8)
    assert planes.shape[0] == sched.n_in, (planes.shape, sched.n_in)
    n_in = sched.n_in
    last = sched.last_uses()
    by_val = sched.outputs_by_value()
    out = np.zeros((sched.n_out, planes.shape[1]), dtype=np.uint8)
    for o, js in by_val.items():
        if 0 <= o < n_in:                  # single-one rows: copies
            for j in js:
                out[j] = planes[o]
    vals: dict[int, np.ndarray] = {}
    for i, (a, b) in enumerate(sched.ops):
        v = np.bitwise_xor(planes[a] if a < n_in else vals[a - n_in],
                           planes[b] if b < n_in else vals[b - n_in])
        for j in by_val.get(n_in + i, ()):
            out[j] = v                     # eager store at definition
        if last[i] > i:                    # a later op still needs it
            vals[i] = v
        for x in (a, b):
            if x >= n_in and last[x - n_in] == i:
                vals.pop(x - n_in, None)
    return out


def scheduled_xor_matmul(matrix: np.ndarray, planes: np.ndarray, *,
                         allow_compile: bool = True) -> np.ndarray:
    """Drop-in ``gf.gf2w.xor_matmul`` with the scheduled engine: uses
    the cached (or, when allowed and profitable, freshly compiled)
    schedule, else the naive row-by-row XOR.  The BitMatrixCodec
    encode path compiles (the matrix is hot for the codec's lifetime);
    the repair path passes ``allow_compile=False`` and rides a
    schedule only when one is already cached (warmed at decode-matrix
    build time)."""
    env = os.environ.get("CEPH_TPU_XOR_SCHED")
    sched = cached_schedule(matrix)
    if sched is None and allow_compile \
            and matrix.size <= MAX_COMPILE_CELLS \
            and env != "0":
        sched = schedule_for(matrix)
    if sched is None or env == "0" \
            or sched.n_terms >= sched.naive_terms \
            or (env != "1" and planes.shape[1] < HOST_MIN_LANE):
        from ..gf.gf2w import xor_matmul
        return xor_matmul(np.ascontiguousarray(matrix, np.uint8),
                          planes)
    out = apply_host(sched, planes)
    STATS.note_launch(sched)
    return out


def warm_schedule(matrix: np.ndarray) -> XorSchedule | None:
    """Compile-and-cache when the matrix qualifies (called at decode-
    matrix build time, so subsequent repairs find a schedule cached and
    never pay the compile on the read path)."""
    if _env_off() or matrix.size > MAX_COMPILE_CELLS:
        return None
    sched = schedule_for(matrix)
    return sched if sched.n_terms < sched.naive_terms else None


def warm_gf8_schedule(matrix: np.ndarray) -> XorSchedule | None:
    """``warm_schedule`` for a GF(2^8) coefficient matrix: expand to
    the GF(2) bit-matrix the batched kernel family keys on
    (``gf2kernels.bitmatrix_i8``) and compile-and-cache its schedule.
    Called when a codec builds a repair/local-parity matrix, so the
    first batched launch with it finds the schedule cached and the
    read/recovery path never pays the CSE compile.  Matrices above
    the speculative bound are skipped -- codec init (which the
    monitor runs per profile validation) must never stall on a
    multi-second CSE pass for a matrix the cost model would not pick
    speculatively anyway."""
    if _env_off():
        return None
    from .gf2kernels import bitmatrix_i8
    bm = bitmatrix_i8(np.ascontiguousarray(matrix, np.uint8))
    if bm.size > SPECULATIVE_MAX_CELLS:
        return None
    return warm_schedule(bm)


def apply_bits_traced(sched: XorSchedule, data_u8):
    """(k, N) bytes -> (n_out//8, N) bytes under trace: unpack to bit
    planes, run the schedule, pack.  The jax-traceable core of the
    jitted XLA family -- same plane order as the dense family (plane
    8j+s = bit s of chunk j, matching ``bitmatrix_i8`` columns)."""
    import jax.numpy as jnp
    k = data_u8.shape[0]
    assert sched.n_in == 8 * k, (sched.n_in, k)
    assert sched.n_out % 8 == 0, sched.n_out
    d = data_u8.astype(jnp.int32)
    planes = [(d[j] >> s) & 1 for j in range(k) for s in range(8)]
    n_in = sched.n_in
    last = sched.last_uses()
    by_val = sched.outputs_by_value()
    outvals: list = [None] * sched.n_out
    for o, js in by_val.items():
        if 0 <= o < n_in:                  # single-one rows: copies
            for j in js:
                outvals[j] = planes[o]
    vals: dict[int, object] = {}
    for i, (a, b) in enumerate(sched.ops):
        v = (planes[a] if a < n_in else vals[a - n_in]) \
            ^ (planes[b] if b < n_in else vals[b - n_in])
        for j in by_val.get(n_in + i, ()):
            outvals[j] = v                 # eager store at definition
        if last[i] > i:
            vals[i] = v
        for x in (a, b):
            # free dead tracers so the unrolled graph's live set
            # matches the schedule's register bound
            if x >= n_in and last[x - n_in] == i:
                vals.pop(x - n_in, None)
    zero = jnp.zeros_like(planes[0])
    out_rows = []
    for r in range(sched.n_out // 8):
        acc = None
        for s in range(8):
            o = outvals[8 * r + s]
            if o is None:
                continue
            term = o << s if s else o
            acc = term if acc is None else acc | term
        out_rows.append(zero if acc is None else acc)
    return jnp.stack(out_rows).astype(jnp.uint8)


# ---------------------------------------------------------------------------
# the batched (B, k, L) kernel family
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=512)
def _compiled_sched_batch(digest: str, b: int, k: int, l: int):
    from .gf2kernels import registry_program
    sched = registered(digest)

    def fn(xd):  # (B, k, L) -> (B, r, L), whole path under one jit
        flat = xd.transpose(1, 0, 2).reshape(k, b * l)
        out = apply_bits_traced(sched, flat)
        return out.reshape(-1, b, l).transpose(1, 0, 2)

    return registry_program("sched", fn)


# (digest, shape) keys whose scheduled launch passed its one-time
# byte-parity gate vs the host oracle
_sched_verified: set[tuple] = set()


def _env_off() -> bool:
    return os.environ.get("CEPH_TPU_XOR_SCHED") == "0"


def _tuned_engine(k: int, m: int, lane: int) -> str | None:
    """The autotuned dense-vs-scheduled winner for this (k, m) family
    from gf2_tuned.json (``tools/ec_autotune.py`` writes it), exact
    chunk first, family default second."""
    from .gf2kernels import _tuned_cfgs
    table = _tuned_cfgs().get("xor_sched")
    if not isinstance(table, dict):
        return None
    hit = table.get(f"{k},{m},{lane}") or table.get(f"{k},{m}")
    if isinstance(hit, dict):
        hit = hit.get("engine")
    return hit if hit in ("dense", "scheduled") else None


def want_scheduled(bitmatrix: np.ndarray, lane: int, backend: str,
                   have_packed: bool = False) -> XorSchedule | None:
    """The per-(matrix, shape) cost model: the schedule to launch with,
    or None (dense wins).  Precedence: CEPH_TPU_XOR_SCHED env override,
    the autotuned winner recorded in gf2_tuned.json, then the backend
    heuristic -- scheduled XOR terms vs the dense contraction length
    (R*C MACs per byte column), discounted because a vectorized MAC
    and a vectorized XOR are not 1:1.  MXU-bearing backends (and any
    caller whose packed pallas family is live, ``have_packed``)
    default dense: the systolic array runs the zeros for free, so
    only a measured tuned entry may override it there."""
    env = os.environ.get("CEPH_TPU_XOR_SCHED")
    if env == "0":
        return None
    if bitmatrix.size > MAX_COMPILE_CELLS \
            or bitmatrix.shape[0] % 8 or bitmatrix.shape[1] % 8:
        return None
    if env == "1":
        return schedule_for(bitmatrix)
    tuned = _tuned_engine(bitmatrix.shape[1] // 8,    # k data chunks
                          bitmatrix.shape[0] // 8,    # m parity rows
                          lane)
    if tuned == "scheduled":
        return schedule_for(bitmatrix)
    if tuned == "dense":
        return None
    if backend != "cpu" or have_packed:
        return None
    if bitmatrix.size > SPECULATIVE_MAX_CELLS:
        return None            # dense family serves; tune to opt in
    sched = schedule_for(bitmatrix)
    dense_macs = bitmatrix.shape[0] * bitmatrix.shape[1]
    if sched.n_terms <= CPU_DENSE_DISCOUNT * dense_macs:
        return sched
    return None


def sched_matmul_batch_device(sched: XorSchedule, matrix: np.ndarray,
                              xd, b: int, k: int, l: int, perf=None):
    """Launch the scheduled kernel family for a (B, k, L) device batch
    of the (r, k) GF(2^8) coefficient ``matrix``; returns the (B, r, L)
    device output.  Same padding buckets and one-launch contract as the
    dense path.  A compile failure propagates and a first-launch parity
    miss raises ``KernelParityError``: the cost model picked this
    engine, so it serves or the caller hears why not."""
    from .gf2kernels import check_batch_parity
    out = _compiled_sched_batch(sched.digest, b, k, l)(xd)
    key = (sched.digest, b, k, l)
    if key not in _sched_verified:
        check_batch_parity("scheduled XOR kernel", matrix, xd, out,
                           min(b, 2), perf)
        _sched_verified.add(key)
    STATS.note_launch(sched)
    return out
