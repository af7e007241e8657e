"""Device k-means: the coarse quantizer for the vector index.

The analog of the covering index's hash-bucketize step for embedding
columns (BASELINE config 5): rows are partitioned by nearest centroid so a
query probes only its closest partitions. Everything is MXU work — the
distance matrix is one [n, d] @ [d, C] matmul per Lloyd iteration, and the
centroid update is the one-hot-assignment matmul [C, n] @ [n, d] — so the
whole trainer is a handful of big batched matmuls, exactly what the
systolic array wants.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from hyperspace_tpu.compat import jit, to_host

_TRAIN_SAMPLE = 131_072
_ASSIGN_CHUNK = 262_144


@functools.partial(jit, static_argnames=("iters",))
def _lloyd(x: jnp.ndarray, init: jnp.ndarray, iters: int) -> jnp.ndarray:
    """x [n, d] f32, init [C, d] f32 → trained centroids [C, d]."""
    xsq = jnp.sum(x * x, axis=1, keepdims=True)  # [n, 1]

    def step(c, _):
        d2 = xsq - 2.0 * (x @ c.T) + jnp.sum(c * c, axis=1)[None, :]  # [n, C]
        assign = jnp.argmin(d2, axis=1)  # [n]
        onehot = jax.nn.one_hot(assign, c.shape[0], dtype=x.dtype)  # [n, C]
        sums = onehot.T @ x  # [C, d] — MXU
        counts = jnp.sum(onehot, axis=0)[:, None]  # [C, 1]
        new_c = jnp.where(counts > 0, sums / jnp.maximum(counts, 1.0), c)
        return new_c, None

    out, _ = jax.lax.scan(step, init, None, length=iters)
    return out


def train_centroids(
    x: np.ndarray, num_partitions: int, iters: int = 8, seed: int = 0
) -> np.ndarray:
    """Train `num_partitions` centroids on (a sample of) x [n, d]."""
    n = len(x)
    rng = np.random.default_rng(seed)
    if n > _TRAIN_SAMPLE:
        sample = x[rng.choice(n, _TRAIN_SAMPLE, replace=False)]
    else:
        sample = x
    init_idx = rng.choice(len(sample), min(num_partitions, len(sample)), replace=False)
    init = sample[init_idx].astype(np.float32)
    if len(init) < num_partitions:  # degenerate tiny input: repeat rows
        reps = -(-num_partitions // len(init))
        init = np.tile(init, (reps, 1))[:num_partitions]
    out = _lloyd(jnp.asarray(sample, dtype=jnp.float32), jnp.asarray(init), iters)
    return np.asarray(to_host(out))


@jit
def _assign(x: jnp.ndarray, c: jnp.ndarray) -> jnp.ndarray:
    d2 = (
        jnp.sum(x * x, axis=1, keepdims=True)
        - 2.0 * (x @ c.T)
        + jnp.sum(c * c, axis=1)[None, :]
    )
    return jnp.argmin(d2, axis=1).astype(jnp.int32)


def assign_partitions(x: np.ndarray, centroids: np.ndarray) -> np.ndarray:
    """Nearest-centroid partition id per row, chunked to bound HBM."""
    c = jnp.asarray(centroids, dtype=jnp.float32)
    out = []
    for lo in range(0, len(x), _ASSIGN_CHUNK):
        chunk = jnp.asarray(x[lo : lo + _ASSIGN_CHUNK], dtype=jnp.float32)
        out.append(np.asarray(to_host(_assign(chunk, c))))
    return np.concatenate(out) if out else np.zeros(0, np.int32)
