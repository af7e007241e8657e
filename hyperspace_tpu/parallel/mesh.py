"""Device mesh/topology layer.

SURVEY.md §2.3 names this a first-class component for the TPU build: the
analog of "N shuffle partitions over a Spark cluster" is "N buckets sharded
over a device mesh". The inner axis ("x") spans the chips of one slice —
build-time bucketize rides ICI via all_to_all over it; query-time
bucket-aligned ops need no collective at all. Multi-slice deployments add
an outer "dcn" axis (make_multislice_mesh): the exchange then runs over
the combined (dcn, x) axes and XLA routes the inter-slice portion over
DCN. Bucket ownership stays contiguous in flattened mesh order either way,
so the carve/query planes are mesh-shape agnostic.
"""

from __future__ import annotations

import os
from pathlib import Path

import numpy as np

import jax
from jax.sharding import Mesh

AXIS = "x"
DCN_AXIS = "dcn"


def mesh_axes(mesh: Mesh) -> tuple:
    """The mesh's data axes, innermost last ((x,) or (dcn, x))."""
    return tuple(mesh.axis_names)


def mesh_size(mesh: Mesh) -> int:
    out = 1
    for name in mesh.axis_names:
        out *= mesh.shape[name]
    return out

# The checkout root: hyperspace_tpu/parallel/mesh.py -> <repo>. For an
# installed package this is site-packages, which holds no cache.
_REPO_ROOT = Path(__file__).resolve().parents[2]
_cache_enabled = False


def enable_compile_cache() -> None:
    """Turn on JAX's persistent compilation cache, once per process.

    The build's exchange+sort program takes minutes to compile for TPU;
    the cache makes every later process with the same programs start
    hot. Where ``JAX_COMPILATION_CACHE_DIR`` is set, or the caller set
    ``jax_compilation_cache_dir`` through ``jax.config``, JAX uses it and
    nothing is set here. Otherwise, run from a checkout, the cache sits
    at one fixed path inside it, ``<repo>/.jax_cache`` (listed in
    .gitignore), so it never moves between runs. An installed package
    sets no cache: its users set ``JAX_COMPILATION_CACHE_DIR``."""
    global _cache_enabled
    if _cache_enabled:
        return
    _cache_enabled = True
    if os.environ.get("JAX_COMPILATION_CACHE_DIR") or jax.config.jax_compilation_cache_dir:
        return
    if _REPO_ROOT.name in ("site-packages", "dist-packages"):
        return
    jax.config.update("jax_compilation_cache_dir", str(_REPO_ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)


def make_mesh(devices=None, n: int | None = None) -> Mesh:
    devices = list(jax.devices()) if devices is None else list(devices)
    if n is not None:
        devices = devices[:n]
    return Mesh(np.array(devices), (AXIS,))


def make_multislice_mesh(num_slices: int, devices=None) -> Mesh:
    """2-D (dcn, x) mesh: outer axis spans slices (DCN), inner axis the
    chips within a slice (ICI)."""
    devices = list(jax.devices()) if devices is None else list(devices)
    if num_slices < 1 or len(devices) % num_slices != 0:
        raise ValueError(
            f"{len(devices)} devices do not split into {num_slices} equal slices"
        )
    per = len(devices) // num_slices
    return Mesh(np.array(devices).reshape(num_slices, per), (DCN_AXIS, AXIS))


def default_mesh() -> Mesh:
    return make_mesh()


def mesh_for_parallelism(mesh: Mesh | None, n_units: int) -> Mesh:
    """The largest prefix of `mesh` (flattened order) whose size divides
    `n_units`, so contiguous ownership of units (buckets) is exact. Used by
    both the build and the distributed query plane."""
    mesh = mesh if mesh is not None else make_mesh()
    d = mesh_size(mesh)
    if n_units % d == 0:
        return mesh
    while n_units % d != 0:
        d -= 1
    return make_mesh(list(mesh.devices.flat), n=d)
