"""Scoped 64-bit device compute without global flag flips.

Aggregation sums need 53-bit accumulation, but `jax_enable_x64` is
process-wide poison (round 1 weakness #8) and re-entering the
`jax.enable_x64(True)` context around every call invalidates the jit
executable cache — each query would re-lower a multi-second program.

JAX config contexts are THREAD-LOCAL, so all f64 device work runs on one
dedicated worker thread that enters the context once and never leaves it.
Every other thread keeps 32-bit-native semantics; the executable cache
stays warm across queries.
"""

from __future__ import annotations

import threading
from concurrent.futures import ThreadPoolExecutor

_pool: ThreadPoolExecutor | None = None
_pool_lock = threading.Lock()
# The entered context object stays referenced for the worker's lifetime:
# it is entered once and never exited.
_x64_ctx = None


def _enter_x64() -> None:
    global _x64_ctx
    from hyperspace_tpu.compat import enable_x64

    _x64_ctx = enable_x64(True)
    _x64_ctx.__enter__()  # intentionally never exited: thread-local scope


def run_x64(fn, /, *args, **kwargs):
    """Run `fn` on the persistent x64 worker thread and return its result."""
    global _pool
    # Double-checked init (HSL013-allowlisted): the unguarded read is
    # the lock-free hot path; a stale None only sends the loser into the
    # locked block, where the re-check under _pool_lock decides. Once
    # published, _pool is never reassigned.
    if _pool is None:
        with _pool_lock:
            if _pool is None:
                # XLA:CPU compiles on the calling thread, and LLVM's
                # recursive passes can exhaust the default 8 MB pthread
                # stack on very large fused programs (observed as a
                # SIGSEGV inside backend_compile) — give the worker a
                # deep stack before it is spawned.
                prev = threading.stack_size()
                try:
                    threading.stack_size(256 << 20)
                except (ValueError, RuntimeError):
                    prev = None
                try:
                    _pool = ThreadPoolExecutor(max_workers=1, initializer=_enter_x64)
                    # Spawn the worker NOW, while the stack size is set
                    # (threads are created lazily on first submit).
                    _pool.submit(lambda: None).result()
                finally:
                    if prev is not None:
                        threading.stack_size(prev)
    # The worker thread starts with an empty contextvar context —
    # re-plant the caller's active trace span so f64 device work
    # attributes to the operator that requested it.
    from hyperspace_tpu.obs import trace as obs_trace

    return _pool.submit(obs_trace.wrap(fn), *args, **kwargs).result()
