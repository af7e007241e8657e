"""Decoded-table cache hits over hits and misses in the window
(``io.table_cache_stats()`` deltas), in %."""

from perfbench.spans import queries


def read(run):
    n = run.cache["hits"] + run.cache["misses"]
    if not queries(run) or n == 0:
        return None
    return run.cache["hits"] / n * 100.0
