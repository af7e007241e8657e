"""Native host-kernel parity tests.

The C++ kernels (hyperspace_tpu/native) must be BIT-IDENTICAL to the numpy
reference implementations: bucket pruning recomputes hashes at query time
and on-disk indexes embed them, so any divergence silently corrupts
results. These tests pin the contract on every dtype the hash path takes.
The suite must pass whether or not the toolchain built the library
(available() False just exercises the fallbacks).
"""

import hashlib
import platform

import numpy as np
import pytest

from hyperspace_tpu import native
from hyperspace_tpu.ops.hashing import _mix32, combine_hashes, hash_int_column, string_dict_hashes


def _reference_mix_i64(arr):
    lo = (arr & 0xFFFFFFFF).astype(np.uint32)
    hi = ((arr >> 32) & 0xFFFFFFFF).astype(np.uint32)
    return _mix32(lo ^ (_mix32(hi, np) * np.uint32(0x9E3779B1)), np)


@pytest.fixture(scope="module")
def rng():
    return np.random.default_rng(7)


def test_native_builds():
    import shutil

    if shutil.which("g++") is None:
        pytest.skip("no g++ toolchain on this host — numpy fallbacks cover it")
    assert native.available()


def test_library_cache_is_keyed_by_host_cpu(tmp_path):
    """A library built with -march=native on one CPU must not be loaded
    on another: two instruction-set identities get two cached paths, the
    same identity the same path, and an unreadable cpuinfo still names
    the machine."""
    a, b = tmp_path / "a", tmp_path / "b"
    a.write_text("processor\t: 0\nflags\t\t: fpu sse2 avx2\n")
    b.write_text("processor\t: 0\nflags\t\t: fpu sse2 avx2 avx512f\n")
    cpu_a, cpu_b = native._cpu_identity(a), native._cpu_identity(b)
    assert cpu_a != cpu_b
    assert native._library_path(cpu_a) != native._library_path(cpu_b)
    assert native._library_path(cpu_a) == native._library_path(native._cpu_identity(a))
    missing = native._cpu_identity(tmp_path / "missing")
    assert missing.startswith(platform.machine()) and missing not in (cpu_a, cpu_b)


def test_hash_i64_parity(rng):
    arr = rng.integers(-(2**62), 2**62, 100_000).astype(np.int64)
    arr[:4] = [0, -1, np.iinfo(np.int64).min, np.iinfo(np.int64).max]
    assert np.array_equal(hash_int_column(arr, np), _reference_mix_i64(arr))


def test_hash_i32_and_float_parity(rng):
    i32 = rng.integers(-(2**31), 2**31 - 1, 50_000).astype(np.int32)
    assert np.array_equal(hash_int_column(i32, np), _mix32(i32.astype(np.uint32), np))
    f32 = rng.standard_normal(50_000).astype(np.float32)
    assert np.array_equal(
        hash_int_column(f32, np), _mix32(f32.view(np.int32).astype(np.uint32), np)
    )
    f64 = rng.standard_normal(50_000)
    assert np.array_equal(hash_int_column(f64, np), _reference_mix_i64(f64.view(np.int64)))


def test_md5_prefix_parity():
    strs = np.array(
        ["", "a", "hello world", "x" * 55, "y" * 56, "z" * 64, "w" * 120, "ü–😀"],
        dtype=object,
    )
    expected = np.array(
        [
            int.from_bytes(hashlib.md5(str(s).encode("utf-8")).digest()[:4], "little")
            for s in strs
        ],
        dtype=np.uint32,
    )
    assert np.array_equal(string_dict_hashes(strs), expected)


def test_combine_parity(rng):
    a = rng.integers(0, 2**32, 10_000).astype(np.uint32)
    b = rng.integers(0, 2**32, 10_000).astype(np.uint32)
    c = rng.integers(0, 2**32, 10_000).astype(np.uint32)
    expected = _mix32(_mix32(a * np.uint32(31) + b, np) * np.uint32(31) + c, np)
    assert np.array_equal(combine_hashes([a, b, c], np), expected)


def test_take_rows_parity(rng):
    for arr in (
        rng.standard_normal((5_000, 3)),
        rng.integers(0, 100, 5_000).astype(np.int64),
        rng.standard_normal(5_000).astype(np.float32),
    ):
        idx = rng.permutation(len(arr))[:2_000]
        out = native.take_rows(arr, idx)
        if out is not None:
            assert np.array_equal(out, arr[idx])
