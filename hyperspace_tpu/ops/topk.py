"""Top-k selection on TPU: tiled Pallas kernel + lax.top_k fallback.

XLA lowers `lax.top_k` on TPU to a full sort — O(n log² n) bitonic passes
for a k of 10. The Pallas kernel instead streams score tiles through VMEM
once: each (query, tile) program unrolls k max/argmax/mask rounds on its
tile (k · 3 vector ops over data already in VMEM), emitting per-tile
partial top-k lists; one tiny `lax.top_k` over the [tiles·k] partials
merges the result. Work: O(n·k/T + tiles·k·log) ≈ one HBM pass.

This is the ANN/vector-index hot path (BASELINE config 5). CPU tests run
the same kernel in interpret mode. Eligibility is an explicit shape rule
(k <= `_MAX_PALLAS_K`, n >= `_MIN_PALLAS_N`); ineligible calls take
lax.top_k (`topk(..., impl="xla")` forces it), and a lowering failure of
an eligible call raises.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from hyperspace_tpu import stats
from hyperspace_tpu.compat import to_host

_TILE = 2048
_MAX_PALLAS_K = 64
_MIN_PALLAS_N = 512


def _next_mult(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


_QBLOCK = 8  # queries per program (TPU sublane granularity)


@functools.lru_cache(maxsize=32)
def _make_tile_kernel(k: int, tile: int, interpret: bool):
    from hyperspace_tpu.compat import resolve_pallas

    pl = resolve_pallas()

    out_lanes = _next_mult(k, 128)

    def kernel(x_ref, vals_ref, idx_ref):
        x = x_ref[...].astype(jnp.float32)  # (QBLOCK, tile)
        base = pl.program_id(1) * tile
        lanes = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
        for r in range(k):  # static unroll: k max/argmax/mask rounds
            m = jnp.max(x, axis=1)  # [QBLOCK]
            am = jnp.min(jnp.where(x == m[:, None], lanes, tile), axis=1)
            vals_ref[:, r] = m
            idx_ref[:, r] = am + base
            x = jnp.where(lanes == am[:, None], -jnp.inf, x)

    def run(scores):  # [q_pad, n_pad], q_pad % QBLOCK == n_pad % tile == 0
        q, n_pad = scores.shape
        tiles = n_pad // tile
        with jax.named_scope("topk"):
            return pl.pallas_call(
                kernel,
                grid=(q // _QBLOCK, tiles),
                in_specs=[pl.BlockSpec((_QBLOCK, tile), lambda i, j: (i, j))],
                out_specs=[
                    pl.BlockSpec((_QBLOCK, out_lanes), lambda i, j: (i, j)),
                    pl.BlockSpec((_QBLOCK, out_lanes), lambda i, j: (i, j)),
                ],
                out_shape=[
                    jax.ShapeDtypeStruct((q, tiles * out_lanes), jnp.float32),
                    jax.ShapeDtypeStruct((q, tiles * out_lanes), jnp.int32),
                ],
                interpret=interpret,
            )(scores)

    # jit so repeated calls with the same shape hit the executable cache
    # instead of re-lowering the pallas_call every invocation.
    from hyperspace_tpu.compat import jit

    return jit(run, key="ops.topk.pallas_tile"), out_lanes


def _pallas_topk(scores: jnp.ndarray, k: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    q, n = scores.shape
    tile = min(_TILE, _next_mult(max(n, 128), 128))
    n_pad = _next_mult(n, tile)
    q_pad = _next_mult(q, _QBLOCK)
    if n_pad != n or q_pad != q:
        scores = jnp.pad(
            scores, ((0, q_pad - q), (0, n_pad - n)), constant_values=-np.inf
        )
    interpret = jax.default_backend() == "cpu"
    run, out_lanes = _make_tile_kernel(k, tile, interpret)
    vals, idx = run(scores)
    tiles = vals.shape[1] // out_lanes
    # Keep the k real lanes of each tile's 128-lane padded block.
    vals = vals.reshape(q_pad, tiles, out_lanes)[:q, :, :k].reshape(q, tiles * k)
    idx = idx.reshape(q_pad, tiles, out_lanes)[:q, :, :k].reshape(q, tiles * k)
    # Merge partials (tiny: tiles*k elements).
    mvals, mpos = jax.lax.top_k(vals, min(k, vals.shape[1]))
    midx = jnp.take_along_axis(idx, mpos, axis=1)
    return mvals, midx


def topk(scores, k: int, impl: str = "auto") -> tuple[np.ndarray, np.ndarray]:
    """Top-k (largest) per row of `scores` [q, n] → (values, indices)
    [q, k]. impl: "auto" (Pallas when eligible, else XLA), "pallas", "xla".
    """
    scores = jnp.asarray(scores)
    if scores.ndim == 1:
        v, i = topk(scores[None, :], k, impl)
        return v[0], i[0]
    # NaN scores are treated as -inf in BOTH paths: the Pallas kernel's
    # max/argmax rounds would otherwise never mask a NaN (x == NaN is
    # false) and emit an out-of-range index, and lax.top_k would rank NaN
    # first. -inf gives one deterministic, sane semantic for corrupt rows.
    scores = jnp.where(jnp.isnan(scores), -jnp.inf, scores)
    q, n = scores.shape
    k = min(k, n)
    if impl == "pallas" or (impl == "auto" and k <= _MAX_PALLAS_K and n >= _MIN_PALLAS_N):
        v, i = _pallas_topk(scores, k)
        stats.increment("device.kernel.fused")
        v, i = to_host((v, i))
        return np.asarray(v), np.asarray(i)
    if impl == "auto":
        stats.increment("device.kernel.fallbacks")
    v, i = to_host(jax.lax.top_k(scores, k))
    return np.asarray(v), np.asarray(i)
