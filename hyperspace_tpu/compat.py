"""Version-compatibility shims for jax's fragile import surface.

jax renames and relocates public symbols across minor versions; the cost
of importing them directly is not a graceful degradation but a module
that fails to IMPORT — the seed shipped a bare ``from jax import
shard_map`` that produced 66 collection errors and ~200 cascading test
failures on jax 0.4.37. Every symbol jax has moved (or is likely to
move) is resolved HERE and nowhere else:

- ``shard_map``: ``jax.shard_map`` (jax 0.9; callers spell its
  ``check_vma=`` kwarg).
- Pallas: ``resolve_pallas()`` returns the ``pallas`` module from its
  current home (``jax.experimental.pallas`` today).
- ``jit``: the package's one jit entry point. Same surface as
  ``jax.jit``, plus a ``key=`` call-site identity used by the runtime
  health plane (``obs/runtime.py``) to count compiles per call site and
  detect recompile storms while the process runs — the dynamic mirror
  of lint rule HSL015, and the observable form of the XLA:CPU
  map-count segfault ``utils/jit_memory.py`` guards against.
- ``to_host``: the package's one blocking device→host fetch, timed as
  the ``device.sync`` span (the host's wait on the device).
- The tracer's profiler bridge: importing this module installs
  ``jax.profiler.TraceAnnotation`` into ``obs/trace.py``, which stays
  stdlib-only, so recorded spans show in a profile beside the device.

The trace-safety linter (``analysis/lint.py``, rule HSL001) makes this
arrangement permanent: any ``from jax import shard_map`` or
``jax.experimental`` use outside this module is a lint error, and the CI
gate runs the linter over the package — so the seed's breakage class
cannot be reintroduced by a future PR.
"""

from __future__ import annotations

import functools

#: The bounded signature-space registry (static-analysis rule HSL024,
#: analysis/tracedomain.py). Every value that reaches a jit static
#: argument must range over a declared bounded domain, or each new value
#: mints a fresh compile — the static dual of the runtime
#: ``jit.recompile_storm`` detector in obs/runtime.py. Keys are static
#: argument / enum parameter names; values describe the domain (a tuple
#: enumerates it exactly). AST-extracted by the analyzer like
#: ``faults.KNOWN_POINTS`` — keep it a plain literal of constants.
KNOWN_STATIC_DOMAINS = {
    # jit static argument names (bounded by construction at their sites)
    "cap": "pow2-rounded expansion capacity (join_expand)",
    "m_pad": "pow2-rounded pair-buffer length (join _compact_pairs)",
    "shift": "bit width from pack_shift — at most 64",
    "num_segments": "tile-rounded group count (aggregate/join_agg)",
    "channels": "per-spec channel count — bounded by the plan",
    "fns": "reduction-kind tuple drawn from the AggSpec vocabulary",
    "iters": "Lloyd iteration count — a config-bounded small int",
    # enum parameters that select a compiled variant
    "venue": ("device", "host"),
    "fused": ("auto", "off"),
    "impl": ("auto", "pallas", "lax"),
    "reduce": ("dense", "scatter", "bucket_dense"),
}


def shard_map(f=None, **kwargs):
    """``jax.shard_map``, usable directly or through
    ``functools.partial(shard_map, mesh=..., ...)`` as a decorator (the
    call style ops/* use); calling with the keyword arguments alone
    returns a decorator, matching jax's own behavior."""
    import jax

    if f is None:
        return functools.partial(shard_map, **kwargs)
    return jax.shard_map(f, **kwargs)


def jit(fn=None, *, key: "str | None" = None, **jit_kwargs):
    """``jax.jit`` with per-call-site compile accounting (obs/runtime.py).

    Usable exactly like ``jax.jit``: as a decorator, through
    ``functools.partial(jit, static_argnames=...)``, or called directly
    on a function. ``key`` names the call site in the runtime jit
    report and in recompile-storm events; it defaults to the wrapped
    function's module-qualified name — pass it explicitly when the
    function is a lambda or a local closure (whose qualnames collide).
    """
    if fn is None:
        return functools.partial(jit, key=key, **jit_kwargs)
    import jax

    from hyperspace_tpu.obs import runtime as obs_runtime

    if key is None:
        module = getattr(fn, "__module__", None) or "<unknown>"
        qual = getattr(fn, "__qualname__", None) or getattr(fn, "__name__", "<fn>")
        key = f"{module}.{qual}"
    return obs_runtime.instrument(jax.jit(fn, **jit_kwargs), key)


def to_host(x):
    """``jax.device_get(x)`` inside a ``device.sync`` span: its wall is the
    time the host waited for the device to finish ``x``, plus the
    transfer. Every blocking fetch of a device result goes through here."""
    import jax

    from hyperspace_tpu.obs import trace as obs_trace

    with obs_trace.span("device.sync"):
        return jax.device_get(x)


def _bridge_spans() -> None:
    import jax

    from hyperspace_tpu.obs import trace as obs_trace

    obs_trace.bridge(jax.profiler.TraceAnnotation)


_bridge_spans()


def enable_x64(new_val: bool = True):
    """Scoped-x64 context manager (``jax.enable_x64``)."""
    import jax

    return jax.enable_x64(new_val)


def resolve_pallas():
    """The Pallas module, wherever this jax puts it. Kernel factories
    import it lazily through here."""
    from jax.experimental import pallas  # noqa: HSL001

    return pallas
