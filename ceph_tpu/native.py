"""ctypes bindings for the native C++ runtime library.

Builds lazily with make on first use if the .so is absent; every entry
point has a numpy fallback so the framework stays functional without a
toolchain.  The native GF path (the ISA-L-technique stand-in) is the
oracle ``chip_smoke.py`` holds the device codec to.
"""

from __future__ import annotations

import ctypes
import subprocess
import threading
from pathlib import Path

import numpy as np

_NATIVE_DIR = Path(__file__).resolve().parent.parent / "native"
_LIB_PATH = _NATIVE_DIR / "libceph_tpu_native.so"

_lib = None
_lib_lock = threading.Lock()
_build_attempted = False


def _load():
    global _lib, _build_attempted
    if _lib is not None:
        return _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        if not _LIB_PATH.exists() and not _build_attempted:
            _build_attempted = True
            try:
                subprocess.run(["make", "-C", str(_NATIVE_DIR), "-j4"],
                               check=True, capture_output=True, timeout=120)
            except Exception:
                return None
        if not _LIB_PATH.exists():
            return None
        lib = ctypes.CDLL(str(_LIB_PATH))
        if not hasattr(lib, "crush_oracle_select") \
                or not hasattr(lib, "ceph_crc32c_batch_ptrs"):
            # stale .so from before the oracle / batched crc landed:
            # rebuild once; if that fails, keep serving the symbols it
            # DOES have
            try:
                subprocess.run(["make", "-C", str(_NATIVE_DIR), "clean"],
                               check=True, capture_output=True, timeout=60)
                subprocess.run(["make", "-C", str(_NATIVE_DIR), "-j4"],
                               check=True, capture_output=True, timeout=120)
                lib = ctypes.CDLL(str(_LIB_PATH))
            except Exception:
                pass
        lib.gf8_matmul.argtypes = [
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_int, ctypes.c_int,
            ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_uint8),
            ctypes.c_size_t,
        ]
        lib.ceph_crc32c.restype = ctypes.c_uint32
        lib.ceph_crc32c.argtypes = [
            ctypes.c_uint32, ctypes.c_void_p, ctypes.c_size_t]
        lib.rjenkins_hash3.restype = ctypes.c_uint32
        lib.rjenkins_hash3.argtypes = [ctypes.c_uint32] * 3
        if hasattr(lib, "ceph_crc32c_batch"):
            u64p = ctypes.POINTER(ctypes.c_uint64)
            lib.ceph_crc32c_batch.restype = None
            lib.ceph_crc32c_batch.argtypes = [
                ctypes.POINTER(ctypes.c_uint32),
                ctypes.POINTER(ctypes.c_uint8), u64p, u64p,
                ctypes.c_int]
        if hasattr(lib, "ceph_crc32c_batch_ptrs"):
            u64p = ctypes.POINTER(ctypes.c_uint64)
            lib.ceph_crc32c_batch_ptrs.restype = None
            lib.ceph_crc32c_batch_ptrs.argtypes = [
                ctypes.POINTER(ctypes.c_uint32),
                ctypes.POINTER(ctypes.c_char_p), u64p, ctypes.c_int]
        if hasattr(lib, "crush_oracle_select"):
            i32p = ctypes.POINTER(ctypes.c_int32)
            lib.crush_oracle_select.restype = ctypes.c_int
            lib.crush_oracle_select.argtypes = [
                ctypes.POINTER(ctypes.c_int64),
                ctypes.POINTER(ctypes.c_int64),
                ctypes.c_int, i32p, i32p, i32p, i32p, i32p, i32p,
                ctypes.c_int, ctypes.c_int, ctypes.c_int32,
                ctypes.c_uint32, ctypes.c_int, ctypes.c_int,
                ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
                ctypes.c_int, i32p,
            ]
        _lib = lib
        return _lib


_dencfast = None
_dencfast_attempted = False


def get_dencfast():
    """The C denc tagged-value codec (native/denc_value.cc), built
    lazily; None when no toolchain -- callers keep the pure-Python
    reference implementation as fallback."""
    global _dencfast, _dencfast_attempted
    if _dencfast is not None or _dencfast_attempted:
        return _dencfast
    with _lib_lock:
        if _dencfast_attempted:
            return _dencfast
        _dencfast_attempted = True
        so = _NATIVE_DIR / "ceph_tpu_dencfast.so"
        if not so.exists():
            try:
                subprocess.run(
                    ["make", "-C", str(_NATIVE_DIR),
                     "ceph_tpu_dencfast.so"],
                    check=True, capture_output=True, timeout=120)
            except Exception:
                return None
        if not so.exists():
            return None
        try:
            import importlib.util
            spec = importlib.util.spec_from_file_location(
                "ceph_tpu_dencfast", so)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
        except Exception:
            return None
        _dencfast = mod
    return _dencfast


def available() -> bool:
    return _load() is not None


def gf8_matmul(matrix: np.ndarray, data: np.ndarray) -> np.ndarray:
    """(r,k) GF(2^8) coeff matrix x (k,n) bytes -> (r,n), native path."""
    lib = _load()
    matrix = np.ascontiguousarray(matrix, dtype=np.uint8)
    data = np.ascontiguousarray(data, dtype=np.uint8)
    r, k = matrix.shape
    n = data.shape[1]
    if lib is None:
        from .gf import gf_matmul
        return gf_matmul(matrix, data)
    out = np.empty((r, n), dtype=np.uint8)
    lib.gf8_matmul(
        matrix.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), r, k,
        data.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), n)
    return out


# scalar-call accounting: the batched integrity pipeline
# (ops/crc32c_batch.py) owns the "integrity" perf counter set; every
# per-buffer call through here is counted against it so perf dumps and
# tests/test_crc_batch.py can prove the hot paths ride the batched API.
# Resolved lazily: processes that never checksum never import ops.
_integrity_perf = None


def _count_scalar(nbytes: int) -> None:
    global _integrity_perf
    perf = _integrity_perf
    if perf is None:
        try:
            from .ops.crc32c_batch import PERF as perf
        except Exception:
            return
        _integrity_perf = perf
    perf.inc("scalar_calls")
    perf.inc("scalar_bytes", nbytes)


def crc32c(data, crc: int = 0xFFFFFFFF) -> int:
    """CRC32-C of any bytes-like ``data``, continuing the raw register
    ``crc`` (no final xor, so ``crc32c(b, crc32c(a))`` is the register
    of ``a + b``); the default matches the common -1 seed.

    ``bytes`` and a writable ``memoryview`` (the messenger's frames)
    go to the library by address; anything else through numpy, which
    costs several microseconds more a call."""
    _count_scalar(len(data))
    lib = _load()
    if lib is None:
        return _crc32c_py(data, crc)
    if type(data) is bytes:
        ptr, n = data, len(data)
    elif (type(data) is memoryview and data.nbytes
          and not data.readonly and data.c_contiguous):
        ptr, n = ctypes.byref(ctypes.c_char.from_buffer(data)), data.nbytes
    else:
        buf = np.frombuffer(data, dtype=np.uint8)
        ptr, n = buf.ctypes.data, len(buf)
    if n == 0:
        return crc
    return lib.ceph_crc32c(crc, ptr, n)


def crc32c_batch_native(crcs: np.ndarray, flat: np.ndarray,
                        offsets: np.ndarray,
                        lens: np.ndarray) -> bool:
    """One library call checksumming ``len(crcs)`` buffers laid out in
    ``flat`` (buffer i at ``offsets[i]``, ``lens[i]`` bytes); ``crcs``
    carries seeds in and results out, in place.  Returns False when the
    native lib (or a pre-batch stale .so) is unavailable -- the caller
    (ops/crc32c_batch.py) falls back to the numpy engine."""
    lib = _load()
    if lib is None or not hasattr(lib, "ceph_crc32c_batch"):
        return False
    assert crcs.dtype == np.uint32 and crcs.flags.c_contiguous
    assert flat.dtype == np.uint8 and flat.flags.c_contiguous
    u64p = ctypes.POINTER(ctypes.c_uint64)
    lib.ceph_crc32c_batch(
        crcs.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
        flat.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        np.ascontiguousarray(offsets, np.uint64).ctypes.data_as(u64p),
        np.ascontiguousarray(lens, np.uint64).ctypes.data_as(u64p),
        len(crcs))
    return True


def crc32c_batch_native_ptrs(crcs: np.ndarray, bufs: list,
                             lens: np.ndarray) -> bool:
    """Scattered-buffer variant of :func:`crc32c_batch_native`: one
    library call over a pointer table built straight from the bytes
    objects -- no concatenation memcpy at all.  ``bufs`` must be a
    list of ``bytes`` (the pointer table borrows their storage for the
    duration of the call)."""
    lib = _load()
    if lib is None or not hasattr(lib, "ceph_crc32c_batch_ptrs"):
        return False
    assert crcs.dtype == np.uint32 and crcs.flags.c_contiguous
    ptrs = (ctypes.c_char_p * len(bufs))(*bufs)
    lib.ceph_crc32c_batch_ptrs(
        crcs.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)), ptrs,
        np.ascontiguousarray(lens, np.uint64).ctypes.data_as(
            ctypes.POINTER(ctypes.c_uint64)),
        len(bufs))
    return True


def _crc32c_py(data: bytes, crc: int) -> int:
    """No-toolchain fallback: numpy table-driven slice-by-8 via the
    batched engine (the seed's per-byte Python loop made EVERY
    frame/block/scrub digest a ~10 MB/s interpreter walk whenever
    libceph_native was absent)."""
    from .ops.crc32c_batch import crc32c_numpy_one
    return crc32c_numpy_one(data, crc)


class NativeBackend:
    """RSMatrixCodec backend over the C++ library (CPU baseline)."""

    name = "native"

    def matmul(self, matrix: np.ndarray, data: np.ndarray) -> np.ndarray:
        return gf8_matmul(matrix, data)


def crush_oracle_do_rule(crush_map, ruleno: int, x: int, numrep: int,
                         osd_weights) -> list[int] | None:
    """Independent C oracle for straw2 TAKE->CHOOSE(LEAF)->EMIT rules
    (native/crush_oracle.cc); None when the native lib is unavailable
    or the rule shape is outside the oracle's scope."""
    lib = _load()
    if lib is None or not hasattr(lib, "crush_oracle_select"):
        return None
    from .crush.ln import RH_LH_TBL, LL_TBL
    from .crush.types import (
        CRUSH_BUCKET_STRAW2, CRUSH_RULE_TAKE, CRUSH_RULE_CHOOSE_FIRSTN,
        CRUSH_RULE_CHOOSE_INDEP, CRUSH_RULE_CHOOSELEAF_FIRSTN,
        CRUSH_RULE_CHOOSELEAF_INDEP, CRUSH_RULE_EMIT,
        CRUSH_RULE_SET_CHOOSE_TRIES, CRUSH_RULE_SET_CHOOSELEAF_TRIES,
    )
    rule = crush_map.rules.get(ruleno)
    if rule is None or not (1 <= numrep <= 64):
        return None
    choose_tries_override = None
    leaf_tries_override = None
    steps = []
    for s in rule.steps:
        if s.op == CRUSH_RULE_SET_CHOOSE_TRIES:
            choose_tries_override = s.arg1
        elif s.op == CRUSH_RULE_SET_CHOOSELEAF_TRIES:
            leaf_tries_override = s.arg1
        else:
            steps.append(s)
    if len(steps) != 3:
        return None
    take, choose, emit = steps
    if take.op != CRUSH_RULE_TAKE or emit.op != CRUSH_RULE_EMIT:
        return None
    shapes = {
        CRUSH_RULE_CHOOSE_FIRSTN: (1, 0),
        CRUSH_RULE_CHOOSELEAF_FIRSTN: (1, 1),
        CRUSH_RULE_CHOOSE_INDEP: (0, 0),
        CRUSH_RULE_CHOOSELEAF_INDEP: (0, 1),
    }
    if choose.op not in shapes:
        return None
    if choose.arg1 != 0:
        return None   # rule-capped numrep: outside the oracle's scope
    if (choose_tries_override or 0) < 0 or (leaf_tries_override or 0) < 0:
        return None
    firstn, leaf = shapes[choose.op]
    t = crush_map.tunables
    if t.chooseleaf_vary_r != 1 or not t.chooseleaf_stable \
            or t.choose_local_tries or t.choose_local_fallback_tries:
        return None                   # oracle implements jewel profile
    buckets = list(crush_map.buckets.values())
    if any(b.alg != CRUSH_BUCKET_STRAW2 for b in buckets):
        return None
    ids = np.array([b.id for b in buckets], np.int32)
    types = np.array([b.type for b in buckets], np.int32)
    off = np.zeros(len(buckets) + 1, np.int32)
    items, weights = [], []
    for i, b in enumerate(buckets):
        items.extend(b.items)
        weights.extend(b.item_weights)
        off[i + 1] = len(items)
    items = np.array(items, np.int32)
    weights = np.array(weights, np.int32)
    osd_w = np.asarray(osd_weights, np.int32)
    out = np.full(max(numrep, 1), 0x7FFFFFFF, np.int32)
    rh = np.ascontiguousarray(RH_LH_TBL, np.int64)
    ll = np.ascontiguousarray(LL_TBL, np.int64)
    i64p = ctypes.POINTER(ctypes.c_int64)
    i32p = ctypes.POINTER(ctypes.c_int32)
    # default counts tries (total_tries + 1); an explicit SET step is
    # used as-is (crush_do_rule's compatibility quirk)
    choose_tries = choose_tries_override or (t.choose_total_tries + 1)
    if leaf_tries_override:
        recurse_tries = leaf_tries_override
    elif firstn:
        recurse_tries = 1 if t.chooseleaf_descend_once else choose_tries
    else:
        recurse_tries = 1
    n = lib.crush_oracle_select(
        rh.ctypes.data_as(i64p), ll.ctypes.data_as(i64p),
        len(buckets), ids.ctypes.data_as(i32p),
        types.ctypes.data_as(i32p), off.ctypes.data_as(i32p),
        items.ctypes.data_as(i32p), weights.ctypes.data_as(i32p),
        osd_w.ctypes.data_as(i32p), len(osd_w),
        crush_map.max_devices, take.arg1, ctypes.c_uint32(x & 0xFFFFFFFF),
        numrep, choose.arg2, firstn, leaf,
        choose_tries, recurse_tries, 1,
        out.ctypes.data_as(i32p))
    return [int(v) for v in out[:n]] if firstn else \
        [int(v) for v in out[:numrep]]
