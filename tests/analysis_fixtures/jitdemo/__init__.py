"""jitdemo: trace-domain fixture package (tracedomain.py, HSL023-026).

A miniature device plane exercising every shape the trace-domain
inference handles — decorator-form jit (bare and
``functools.partial(jit, static_argnames=...)``), call-form jit inside
lru_cache factories, a shard_map body, Pallas kernel bodies, zero-copy
staging, and two kernel eligibility ladders — with exactly four planted
violations, one per rule:

- HSL023: ``traced._total`` (reached from ``@jit leaky_norm``) bumps a
  stats counter inside the trace domain; ``norm``/``engage`` is the
  clean hoisted counterpart.
- HSL024: ``traced.poly`` declares static argument ``order`` which is
  not in the fixture's KNOWN_STATIC_DOMAINS; ``scale`` uses the
  declared ``reps`` domain.
- HSL025: ``staging.read_aliased`` mutates a zero-copy staged view in
  place; ``read_owned`` goes through ``own_arrays()`` first.
- HSL026: ``device.rowmax`` swallows its lowering errors in a broad
  ``except``; ``tile_reduce``'s ladder is complete (the proven one).

Like every analysis fixture, this package is parsed by the engine and
never imported — ``shims.py`` stands in for compat/stats so the code
reads like the real device plane without needing jax.
"""
