"""Loader for the native host-runtime kernels (hashing.cpp).

Compiles the committed C++ on first use with g++ (cached as a .so keyed
by source hash and host CPU under <repo>/.native_cache, listed in
.gitignore) and binds it via ctypes — no pybind11 dependency. Every caller falls back to the numpy implementation
when the toolchain or the build is unavailable, so this module is a pure
accelerator: `available()` reports which path is active.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import platform
import subprocess
from pathlib import Path

import numpy as np

from hyperspace_tpu.exceptions import HyperspaceError

_SRC = Path(__file__).with_name("hashing.cpp")

_lib: ctypes.CDLL | None = None
_tried = False


def _cache_dir() -> Path:
    root = os.environ.get("HYPERSPACE_TPU_NATIVE_CACHE")
    return Path(root) if root else _SRC.parents[2] / ".native_cache"


def _cpu_identity(cpuinfo: Path = Path("/proc/cpuinfo")) -> str:
    """The machine and instruction-set flags of the host CPU. The library
    is built with -march=native, so a copy built on another CPU can die
    with SIGILL here. With cpuinfo unreadable the machine alone keys it."""
    try:
        text = cpuinfo.read_text()
    except OSError:
        text = ""
    flags = next((ln for ln in text.splitlines() if ln.startswith("flags")), "")
    return f"{platform.machine()}-{hashlib.sha256(flags.encode()).hexdigest()[:16]}"


def _library_path(cpu: str) -> Path:
    tag = hashlib.sha256(_SRC.read_bytes() + cpu.encode()).hexdigest()[:16]
    return _cache_dir() / f"libhs_native_{tag}.so"


def _build() -> Path | None:
    out = _library_path(_cpu_identity())
    if out.exists():
        return out
    out.parent.mkdir(parents=True, exist_ok=True)
    # Per-process temp name: concurrent builders must not interleave writes
    # into one file, or os.replace could publish a corrupted .so.
    tmp = out.parent / f"{out.name}.{os.getpid()}.tmp"
    cmd = [
        "g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread",
        "-march=native", str(_SRC), "-o", str(tmp),
    ]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
    except Exception:
        try:  # retry without -march=native (portability)
            cmd.remove("-march=native")
            subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        except Exception:
            tmp.unlink(missing_ok=True)
            return None
    os.replace(tmp, out)  # atomic publish; concurrent builders converge
    return out


def _load() -> ctypes.CDLL | None:
    global _lib, _tried
    if _tried:
        return _lib
    _tried = True
    if os.environ.get("HYPERSPACE_TPU_DISABLE_NATIVE"):
        return None
    path = _build()
    if path is None:
        return None
    try:
        lib = ctypes.CDLL(str(path))
    except OSError:
        return None
    u32p = np.ctypeslib.ndpointer(np.uint32, flags="C_CONTIGUOUS")
    i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
    i32p = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
    u8p = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")
    lib.hs_hash_i64.argtypes = [i64p, u32p, ctypes.c_int64]
    lib.hs_hash_i32.argtypes = [i32p, u32p, ctypes.c_int64]
    lib.hs_md5_prefix.argtypes = [u8p, i64p, u32p, ctypes.c_int64]
    lib.hs_take_rows.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, i64p, ctypes.c_int64, ctypes.c_int64,
    ]
    lib.hs_combine.argtypes = [u32p, u32p, ctypes.c_int64]
    lib.hs_mj_count.argtypes = [i32p, i64p, i32p, i64p, ctypes.c_int64, i64p]
    lib.hs_mj_fill.argtypes = [i32p, i64p, i32p, i64p, i64p, ctypes.c_int64, i64p, i64p]
    f64p = np.ctypeslib.ndpointer(np.float64, flags="C_CONTIGUOUS")
    lib.hs_mj_accum.argtypes = [
        i32p, i64p, i32p, i64p, ctypes.c_int64,
        f64p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, f64p, f64p,
    ]
    lib.hs_bucket_perm.argtypes = [i32p, ctypes.c_int64, ctypes.c_int64, i64p, i64p]
    lib.hs_sort_range.argtypes = [i64p, ctypes.c_int64, u32p, ctypes.c_int64, ctypes.c_int64]
    _lib = lib
    return _lib


def available() -> bool:
    return _load() is not None


def require(what: str) -> None:
    """Raise unless the library is loaded: `what`=host names a host
    kernel that exists only here."""
    if not available():
        raise HyperspaceError(
            f"{what}=host requires the native library (g++ build failed "
            "or unavailable); use device"
        )


# ---- typed wrappers (None ⇒ caller uses the numpy path) --------------------

def hash_i64(arr: np.ndarray) -> np.ndarray | None:
    lib = _load()
    if lib is None:
        return None
    arr = np.ascontiguousarray(arr, dtype=np.int64)
    out = np.empty(len(arr), dtype=np.uint32)
    lib.hs_hash_i64(arr, out, len(arr))
    return out


def hash_i32(arr: np.ndarray) -> np.ndarray | None:
    lib = _load()
    if lib is None:
        return None
    arr = np.ascontiguousarray(arr, dtype=np.int32)
    out = np.empty(len(arr), dtype=np.uint32)
    lib.hs_hash_i32(arr, out, len(arr))
    return out


def md5_prefix(strings: np.ndarray) -> np.ndarray | None:
    """uint32 md5-prefix per entry of an object array of strings."""
    lib = _load()
    if lib is None:
        return None
    encoded = [str(s).encode("utf-8") for s in strings]
    offsets = np.zeros(len(encoded) + 1, dtype=np.int64)
    np.cumsum([len(b) for b in encoded], out=offsets[1:])
    blob = np.frombuffer(b"".join(encoded), dtype=np.uint8) if encoded else np.zeros(0, np.uint8)
    blob = np.ascontiguousarray(blob)
    out = np.empty(len(encoded), dtype=np.uint32)
    lib.hs_md5_prefix(blob if len(blob) else np.zeros(1, np.uint8), offsets, out, len(encoded))
    return out


def take_rows(arr: np.ndarray, idx: np.ndarray) -> np.ndarray | None:
    """arr[idx] for 1-D/2-D contiguous arrays, threaded."""
    lib = _load()
    if lib is None:
        return None
    arr = np.ascontiguousarray(arr)
    idx = np.ascontiguousarray(idx, dtype=np.int64)
    row_bytes = arr.dtype.itemsize * (arr.shape[1] if arr.ndim == 2 else 1)
    out = np.empty((len(idx),) + arr.shape[1:], dtype=arr.dtype)
    lib.hs_take_rows(
        arr.ctypes.data_as(ctypes.c_void_p),
        out.ctypes.data_as(ctypes.c_void_p),
        idx, len(idx), row_bytes,
    )
    return out


def bucket_perm(
    bucket: np.ndarray, num_buckets: int
) -> tuple[np.ndarray, np.ndarray] | None:
    """Stable counting sort of row ids by bucket. Returns (perm int64,
    per-bucket counts int64), or None when the library is unavailable."""
    lib = _load()
    if lib is None:
        return None
    bucket = np.ascontiguousarray(bucket, dtype=np.int32)
    perm = np.empty(len(bucket), dtype=np.int64)
    counts = np.zeros(num_buckets, dtype=np.int64)
    lib.hs_bucket_perm(bucket, len(bucket), num_buckets, perm, counts)
    return perm, counts


def sort_range(perm_slice: np.ndarray, lanes_u32: np.ndarray) -> bool:
    """In-place key sort of one bucket's contiguous permutation slice by
    the [L, n] unsigned lanes (GIL released — pipelines with encode)."""
    lib = _load()
    if lib is None:
        return False
    assert perm_slice.flags.c_contiguous and perm_slice.dtype == np.int64
    num_lanes = lanes_u32.shape[0] if lanes_u32.ndim == 2 else 0
    lib.hs_sort_range(
        perm_slice,
        len(perm_slice),
        lanes_u32 if num_lanes else np.zeros((1, 1), np.uint32),
        lanes_u32.shape[1] if num_lanes else 0,
        num_lanes,
    )
    return True


def merge_join_accumulate(
    lk: np.ndarray, lofs: np.ndarray, rk: np.ndarray, rofs: np.ndarray,
    rvals: np.ndarray,
) -> tuple[np.ndarray, np.ndarray] | None:
    """Fused merge + accumulate over within-bucket-sorted int32 codes:
    per SORTED-primary-row channel sums of the matching secondary rows
    plus the per-row match count — Aggregate(Join) without materializing
    pairs. rvals is [A, n_r] float64; returns (out [A, n_l], counts
    [n_l]); None when the library is unavailable."""
    lib = _load()
    if lib is None:
        return None
    lk = np.ascontiguousarray(lk, dtype=np.int32)
    rk = np.ascontiguousarray(rk, dtype=np.int32)
    lofs = np.ascontiguousarray(lofs, dtype=np.int64)
    rofs = np.ascontiguousarray(rofs, dtype=np.int64)
    rvals = np.ascontiguousarray(rvals, dtype=np.float64)
    a_r = rvals.shape[0]
    n_r, n_l = len(rk), len(lk)
    out = np.zeros((a_r, n_l), dtype=np.float64)
    counts = np.zeros(n_l, dtype=np.float64)
    lib.hs_mj_accum(
        lk, lofs, rk, rofs, len(lofs) - 1,
        rvals if a_r else np.zeros((1, 1)), a_r, n_r, n_l, out, counts,
    )
    return out, counts


def merge_join_sorted(
    lk: np.ndarray, lofs: np.ndarray, rk: np.ndarray, rofs: np.ndarray
) -> tuple[np.ndarray, np.ndarray, np.ndarray] | None:
    """Exact bucket-parallel merge join over within-bucket-sorted int32
    codes. Returns (li, ri, totals): GLOBAL row indices (int64) in
    bucket-major match order, and per-bucket match counts. None when the
    library is unavailable (caller uses the device path)."""
    lib = _load()
    if lib is None:
        return None
    lk = np.ascontiguousarray(lk, dtype=np.int32)
    rk = np.ascontiguousarray(rk, dtype=np.int32)
    lofs = np.ascontiguousarray(lofs, dtype=np.int64)
    rofs = np.ascontiguousarray(rofs, dtype=np.int64)
    nb = len(lofs) - 1
    counts = np.zeros(nb, dtype=np.int64)
    lib.hs_mj_count(lk, lofs, rk, rofs, nb, counts)
    oofs = np.zeros(nb + 1, dtype=np.int64)
    np.cumsum(counts, out=oofs[1:])
    total = int(oofs[-1])
    li = np.empty(total, dtype=np.int64)
    ri = np.empty(total, dtype=np.int64)
    lib.hs_mj_fill(lk, lofs, rk, rofs, oofs, nb, li, ri)
    return li, ri, counts


def combine(acc: np.ndarray, h: np.ndarray) -> np.ndarray | None:
    lib = _load()
    if lib is None:
        return None
    acc = np.ascontiguousarray(acc, dtype=np.uint32).copy()
    lib.hs_combine(acc, np.ascontiguousarray(h, dtype=np.uint32), len(acc))
    return acc
