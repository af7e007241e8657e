"""Two Pallas engagements with eligibility ladders: ``tile_reduce`` is
complete (explicit shape rule + both counters, lowering errors raise —
the proven ladder), ``rowmax`` swallows its lowering errors in a broad
``except`` and reroutes to the lax path unseen (planted HSL026)."""

import functools

import jax.numpy as jnp

from jitdemo.shims import jit, resolve_pallas, stats

# Both engagements declared, both counters declared — the registries
# the HSL026 checks read (AST-extracted, like the real ops/stats ones).
KNOWN_KERNELS = (
    "jitdemo.tile_reduce",
    "jitdemo.rowmax",
)
KNOWN_COUNTERS = (
    "device.kernel.fused",
    "device.kernel.fallbacks",
)

_TILE = 128
_MAX_TILE = 4096


def _next_mult(n, m):
    return ((n + m - 1) // m) * m


@functools.lru_cache(maxsize=8)
def _make_tile_reduce(n):
    pl = resolve_pallas()

    def kernel(x_ref, o_ref):
        o_ref[...] = jnp.sum(x_ref[...], axis=1)

    def run(x):
        return pl.pallas_call(kernel, grid=(n // _TILE,))(x)

    return jit(run, key="jitdemo.tile_reduce")


def tile_reduce(x):
    n = x.shape[1]
    m = _next_mult(n, _TILE)
    if n > _MAX_TILE:
        stats.increment("device.kernel.fallbacks")
        return jnp.sum(x, axis=1)
    out = _make_tile_reduce(m)(jnp.pad(x, ((0, 0), (0, m - n))))
    stats.increment("device.kernel.fused")
    return out


@functools.lru_cache(maxsize=8)
def _make_rowmax(n):
    pl = resolve_pallas()

    def kernel(x_ref, o_ref):
        o_ref[...] = jnp.max(x_ref[...], axis=1)

    def run(x):
        return pl.pallas_call(kernel, grid=(n // _TILE,))(x)

    return jit(run, key="jitdemo.rowmax")


def rowmax(x):
    n = x.shape[1]
    if n <= _MAX_TILE:
        try:
            run = _make_rowmax(n)
            out = run(x)
            stats.increment("device.kernel.fused")
            return out
        except Exception:
            stats.increment("device.kernel.fallbacks")
    return jnp.max(x, axis=1)
