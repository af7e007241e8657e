"""HSL026 kernel eligibility-ladder completeness: a complete (clean)
ladder, a ladder that swallows its lowering errors in a broad except,
an undeclared engagement with an empty ladder, a stale registry entry,
and a counter missing from KNOWN_COUNTERS."""

import functools

import jax.numpy as jnp

from hyperspace_tpu import stats
from hyperspace_tpu.compat import jit, resolve_pallas

KNOWN_KERNELS = (  # expect: HSL026
    "corpus.reduce",
    "corpus.rowmax",
    "corpus.ghost",
)
# "device.kernel.fallbacks" is deliberately missing: both fallback
# increments below are flagged against this registry.
KNOWN_COUNTERS = ("device.kernel.fused",)

_TILE = 128
_MAX_LANES = 1024


@functools.lru_cache(maxsize=8)
def _make_reduce(n):
    pl = resolve_pallas()

    def kernel(x_ref, o_ref):
        o_ref[...] = jnp.sum(x_ref[...], axis=1)

    def run(x):
        return pl.pallas_call(kernel, grid=(n // _TILE,))(x)

    return jit(run, key="corpus.reduce")


def reduce_rows(x):
    n = x.shape[1]
    if n > _MAX_LANES:
        stats.increment("device.kernel.fallbacks")  # expect: HSL026
        return jnp.sum(x, axis=1)
    out = _make_reduce(n)(x)
    stats.increment("device.kernel.fused")
    return out


@functools.lru_cache(maxsize=8)
def _make_rowmax(n):
    pl = resolve_pallas()

    def kernel(x_ref, o_ref):
        o_ref[...] = jnp.max(x_ref[...], axis=1)

    def run(x):
        # Ladder has a gate and both counters, but its broad except
        # turns a lowering failure into a silent lax-path reroute.
        return pl.pallas_call(kernel, grid=(n // _TILE,))(x)  # expect: HSL026

    return jit(run, key="corpus.rowmax")


def rowmax(x):
    n = x.shape[1]
    if n <= _MAX_LANES:
        try:
            run = _make_rowmax(n)
            out = run(x)
            stats.increment("device.kernel.fused")
            return out
        except Exception:
            stats.increment("device.kernel.fallbacks")  # expect: HSL026
    return jnp.max(x, axis=1)


@functools.lru_cache(maxsize=4)
def _make_stray(n):
    pl = resolve_pallas()

    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2

    def run(x):
        # Undeclared engagement AND an empty ladder: both findings
        # land on this pallas_call line.
        return pl.pallas_call(kernel, grid=(1,))(x)  # expect: HSL026

    return jit(run, key="corpus.stray")
