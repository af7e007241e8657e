"""Parallel execution utilities: device meshes, x64 worker pools, and
the spawn-context worker-process lifecycle.

This ``__init__`` must stay **jax-free at module load**: the pooled
build's spawned workers import ``hyperspace_tpu.parallel.procpool``,
which executes THIS file first — an eager ``from .mesh import ...``
re-export here made every worker pay the full jax import before its
task ran (caught by the HSL019 runtime-mirror test; the static proof is
analysis rule HSL019, docs/static_analysis.md). The mesh re-exports are
therefore lazy.
"""

__all__ = ["default_mesh", "make_mesh"]


def __getattr__(name):
    if name in ("default_mesh", "make_mesh"):
        from hyperspace_tpu.parallel import mesh

        return getattr(mesh, name)
    raise AttributeError(name)
