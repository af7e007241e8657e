"""Test harness configuration.

The analog of the reference's `local[4]` SparkSession
(SparkInvolvedSuite.scala:99-119): multi-device is simulated with 8 virtual
CPU devices via XLA_FLAGS, set before jax is first imported. Tests must not
assume real TPU hardware.
"""

import os

# XLA:CPU compiles on the calling thread; LLVM's recursive passes can
# overflow the default 8 MB main-thread stack on the largest fused
# programs (observed as a SIGSEGV inside backend_compile deep into the
# suite). The hard limit is unlimited here — raise the soft limit so the
# main thread's stack can grow past 8 MB.
try:
    import resource

    _soft, _hard = resource.getrlimit(resource.RLIMIT_STACK)
    if _hard in (resource.RLIM_INFINITY, -1) or (_hard > _soft >= 0):
        resource.setrlimit(resource.RLIMIT_STACK, (_hard, _hard))
except (ImportError, ValueError, OSError):
    pass

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

# Tests run on the CPU backend with the virtual devices above; the chip
# path is exercised by chip_smoke.py on a TPU. The config update also
# covers an interpreter that imported jax before this file ran.
import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np
import pytest


def _map_count() -> int:
    """Memory mappings of this process (Linux); 0 where unreadable."""
    try:
        with open("/proc/self/maps", "rb") as f:
            return sum(1 for _ in f)
    except OSError:
        return 0


@pytest.fixture(autouse=True)
def _jit_map_guard():
    """Keep the process under vm.max_map_count (default 65530).

    Every XLA:CPU executable pins LLVM-JIT'd code/rodata/data mappings
    for the life of the jit cache; a full-suite run compiles enough
    programs (~18k live sections near the end) to exhaust the kernel's
    mapping limit, after which mmap fails inside LLVM and the compiler
    SIGSEGVs. Dropping jax's caches releases the executables; the
    occasional recompile is far cheaper than a dead process."""
    yield
    if _map_count() > 40_000:
        jax.clear_caches()


@pytest.fixture(autouse=True)
def _obs_reset():
    """Observability isolation: counters/metrics and the tracer's
    process-global state (last trace, sink path) are zeroed before each
    test, so cross-test counter drift can't leak into assertions and a
    test that configures a sink can't make a later test write to it."""
    from hyperspace_tpu import stats
    from hyperspace_tpu.obs import events, journal, metrics, runtime, slo, trace

    stats.reset()
    metrics.REGISTRY.reset()
    trace.reset()
    trace.set_enabled(True)
    events.reset()
    slo.reset()
    runtime.reset()
    journal.reset()
    yield
    journal.reset()


@pytest.fixture
def tmp_system_path(tmp_path):
    """Per-test index system path isolation (analog of HyperspaceSuite's
    systemPath handling, HyperspaceSuite.scala:25-75)."""
    p = tmp_path / "indexes"
    p.mkdir(parents=True, exist_ok=True)
    return str(p)


@pytest.fixture
def sample_parquet(tmp_path):
    """Small deterministic sample dataset (analog of SampleData.scala:141-153)."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    rng = np.random.default_rng(42)
    n = 1000
    table = pa.table(
        {
            "id": pa.array(np.arange(n, dtype=np.int64)),
            "key": pa.array(rng.integers(0, 100, size=n, dtype=np.int64)),
            "value": pa.array(rng.standard_normal(n).astype(np.float64)),
            "name": pa.array([f"name_{i % 37}" for i in range(n)]),
        }
    )
    root = tmp_path / "sample_data"
    root.mkdir()
    # Two files so signatures cover multi-file listing.
    pq.write_table(table.slice(0, n // 2), root / "part-0.parquet")
    pq.write_table(table.slice(n // 2), root / "part-1.parquet")
    return str(root)
