"""ANN query path: probe the vector index (or brute-force the source).

Query flow for `ann_search`:

1. find an ACTIVE VectorIndex over the scanned dataset whose stored
   signature matches the live data (same contract as the rewrite rules —
   a stale index silently falls back to brute force, mirroring how the
   covering-index rules downgrade to the raw scan);
2. score queries against the centroids and pick each query's `nprobe`
   nearest partitions (matmul + top-k);
3. load the union of probed partitions, score candidates in one batched
   MXU matmul, select top-k per query with the Pallas kernel (ops/topk.py);
4. per query, mask candidates from partitions it did not probe.

With nprobe == num_partitions the result is EXACTLY brute force — the
equality gate the tests pin.
"""

from __future__ import annotations

import dataclasses
from pathlib import Path

import numpy as np

from hyperspace_tpu.exceptions import HyperspaceError
from hyperspace_tpu.execution import io as hio
from hyperspace_tpu.execution.table import ColumnTable
from hyperspace_tpu.metadata.log_entry import IndexLogEntry
from hyperspace_tpu.ops.topk import topk
from hyperspace_tpu.plan.nodes import LogicalPlan, Scan
from hyperspace_tpu.schema import Schema


@dataclasses.dataclass
class AnnResult:
    """Top-k matches for one query batch. Row-major: query i's matches are
    `indices[i]`/`scores[i]`; `rows` holds the matched payload rows as a
    ColumnTable with a leading `__query__` column."""

    scores: np.ndarray  # [q, k] (higher is better; l2 scores are negated distances)
    rows: ColumnTable


def _device_scores(metric: str, queries, cand):
    """[q, m] score matrix, higher = better, computed AND LEFT on device.

    The [q, m] score matrix is never materialized on host — only the
    [q, k] top-k result crosses the device→host link."""
    import jax.numpy as jnp

    q = jnp.asarray(queries, dtype=jnp.float32)
    x = jnp.asarray(cand, dtype=jnp.float32)
    if metric == "cos":
        q = q / jnp.maximum(jnp.linalg.norm(q, axis=1, keepdims=True), 1e-12)
        x = x / jnp.maximum(jnp.linalg.norm(x, axis=1, keepdims=True), 1e-12)
    dots = q @ x.T  # [q, m] — the MXU hot op
    if metric == "l2":
        qsq = jnp.sum(q * q, axis=1, keepdims=True)
        xsq = jnp.sum(x * x, axis=1)[None, :]
        return -(qsq - 2.0 * dots + xsq)  # negated squared distance
    return dots


def brute_force_search(
    table: ColumnTable, embedding_column: str, queries: np.ndarray, k: int, metric: str = "l2"
) -> AnnResult:
    """Exact search over a materialized table (the no-index fallback)."""
    emb_name = table.schema.field(embedding_column).name
    scores = _device_scores(metric, queries, table.columns[emb_name])
    vals, idx = topk(scores, k)
    return _gather_result(table, vals, idx)


def _result_with_query_ids(rows: ColumnTable, vals: np.ndarray) -> AnnResult:
    """Attach the leading __query__ column; `rows` is query-major [q*k].
    Slots whose score is -inf (query matched fewer than k candidates) are
    dropped from `rows`; `scores` keeps the -inf markers."""
    from hyperspace_tpu.schema import Field

    q, k = vals.shape
    qcol = np.repeat(np.arange(q, dtype=np.int64), k)
    schema = Schema((Field("__query__", "int64"),) + rows.schema.fields)
    cols = {"__query__": qcol, **rows.columns}
    out = ColumnTable(schema, cols, dict(rows.dictionaries), dict(rows.validity))
    valid = np.isfinite(vals.reshape(-1))
    if not valid.all():
        out = out.filter_mask(valid)
    return AnnResult(scores=vals, rows=out)


def _gather_result(table: ColumnTable, vals: np.ndarray, idx: np.ndarray) -> AnnResult:
    return _result_with_query_ids(table.take(idx.reshape(-1)), vals)


def find_vector_index(
    session, plan: Scan, embedding_column: str | None = None
) -> IndexLogEntry | None:
    """ACTIVE VectorIndex over this scan with a live signature match."""
    from hyperspace_tpu.rules.base import SignatureMatcher

    matcher = SignatureMatcher()
    for entry in session.manager.get_indexes():
        if entry.derived_dataset.kind != "VectorIndex":
            continue
        if (
            embedding_column is not None
            and entry.derived_dataset.embedding_column.lower() != embedding_column.lower()
        ):
            continue
        m = matcher.match(entry, plan)
        if m is not None and m.is_exact:
            return entry
    return None


def ann_search(
    session,
    plan: LogicalPlan,
    queries,
    k: int,
    nprobe: int | None = None,
    embedding_column: str | None = None,
    metric: str | None = None,
) -> AnnResult:
    """Approximate nearest neighbours of `queries` [q, d] over the scanned
    dataset. Uses a matching vector index when hyperspace is enabled and
    one exists (scoring with the INDEX's metric; an explicitly different
    `metric` raises instead of being silently ignored); otherwise
    brute-forces the source exactly, scoring with `metric` (default l2)."""
    queries = np.asarray(queries, dtype=np.float32)
    if queries.ndim == 1:
        queries = queries[None, :]
    if not isinstance(plan, Scan):
        raise HyperspaceError("ann_search operates on a scanned dataset (Scan plan)")

    entry = None
    if session.is_hyperspace_enabled():
        entry = find_vector_index(session, plan, embedding_column)

    if entry is None:
        # Exact fallback over the raw source.
        if embedding_column is None:
            vec_fields = [f for f in plan.schema.fields if f.is_vector]
            if len(vec_fields) != 1:
                raise HyperspaceError(
                    "embedding_column is required when the schema does not have "
                    "exactly one vector column"
                )
            embedding_column = vec_fields[0].name
        from hyperspace_tpu.execution.executor import Executor

        table = Executor().execute(plan)
        return brute_force_search(table, embedding_column, queries, k, metric or "l2")

    dd = entry.derived_dataset
    if metric is not None and metric != dd.metric:
        raise HyperspaceError(
            f"metric {metric!r} conflicts with index {entry.name!r} built with "
            f"metric {dd.metric!r}; omit metric or disable hyperspace for an "
            "exact search with the requested metric"
        )
    # Incremental refresh keeps several version dirs live: partition p is
    # the union of p's files across dirs (the covering index's hybrid
    # layout). Centroids come from the newest dir carrying a copy.
    from hyperspace_tpu.vector.lifecycle import load_centroids

    dirs = [Path(entry.content.root) / d for d in entry.content.directories]
    centroids = load_centroids(entry)
    num_partitions = dd.num_partitions
    nprobe = num_partitions if nprobe is None else min(nprobe, num_partitions)

    qv = queries
    if dd.metric == "cos":
        qv = qv / np.maximum(np.linalg.norm(qv, axis=1, keepdims=True), 1e-12)

    # Stage 1: route queries to their nprobe nearest partitions.
    cscores = _device_scores(dd.metric, qv, centroids)
    _, probe = topk(cscores, nprobe)  # [q, nprobe]

    # Stage 2: candidate geometry from the manifests — no payload IO yet.
    # One rows[(dir, p)] map per query batch; stages 3 and 4 reuse it so
    # the stat/manifest lookups run once per (dir, partition).
    needed = sorted(set(int(p) for p in probe.reshape(-1)))
    schema = Schema.from_json(dd.schema)
    rows_map = {(d, p): _partition_rows(d, p) for p in needed for d in dirs}
    sizes = np.array(
        [sum(rows_map[(d, p)] for d in dirs) for p in needed], dtype=np.int64
    )
    offsets = np.concatenate([[0], np.cumsum(sizes)])
    cand_part = np.repeat(np.array(needed, dtype=np.int32), sizes)

    # Stage 3: one batched scoring matmul + top-k, all on device. Each
    # partition's embedding matrix is cached device-resident (only the
    # embedding column is read from parquet for it), so a query batch
    # uploads just the queries and the probed-partition mask; no score
    # matrix is ever downloaded.
    import jax.numpy as jnp

    emb_name = schema.field(dd.embedding_column).name
    emb_parts = [
        _partition_device_emb(d, p, schema, emb_name)
        for p in needed
        for d in dirs
        if rows_map[(d, p)] > 0
    ]
    emb_dev = jnp.concatenate(emb_parts) if emb_parts else jnp.zeros((0, dd.dim), jnp.float32)
    scores = _device_scores(dd.metric, qv, emb_dev)  # [q, m] on device
    probed_mask = np.zeros((len(qv), num_partitions), dtype=bool)
    probed_mask[np.arange(len(qv))[:, None], probe] = True
    scores = jnp.where(jnp.asarray(probed_mask[:, cand_part]), scores, -np.inf)
    m = int(offsets[-1])
    vals, idx = topk(scores, min(k, m))

    # Stage 4: payload gather — read ONLY the partitions owning winning
    # rows, one batched take per owner, reassembled into slot order.
    flat = np.asarray(idx).reshape(-1)
    # Pad-lane top-k partials can carry indices >= m with -inf scores; if
    # one survives the merge, its index would fall past the last offset.
    # Point those slots at row 0 — callers drop them via the -inf score.
    flat = np.where(np.isfinite(np.asarray(vals).reshape(-1)), flat, 0)
    owner = np.searchsorted(offsets, flat, side="right") - 1
    local = flat - offsets[owner]
    group_order = np.argsort(owner, kind="stable")
    grouped: list[ColumnTable] = []
    for o in np.unique(owner):
        part_table = _read_partition_multi(dirs, needed[int(o)], schema, rows_map)
        grouped.append(part_table.take(local[owner == o]))
    regrouped = ColumnTable.concat(grouped)
    inverse = np.empty(len(flat), dtype=np.int64)
    inverse[group_order] = np.arange(len(flat))
    rows = regrouped.take(inverse)
    return _result_with_query_ids(rows, vals)


def _partition_rows(version_dir: Path, p: int) -> int:
    """Row count of partition p in one version dir (0 when the dir has no
    file for it), from the dir's manifest or the parquet footer."""
    path = version_dir / hio.bucket_file_name(p)
    if not path.exists():
        return 0
    manifest = hio.read_manifest_cached(version_dir)
    if manifest is not None and p < len(manifest.get("bucketRows", [])):
        return int(manifest["bucketRows"][p])
    import pyarrow.parquet as pq

    return int(pq.read_metadata(path).num_rows)


def _read_partition_multi(dirs: list[Path], p: int, schema: Schema, rows_map: dict) -> ColumnTable:
    """Partition p's payload rows concatenated across version dirs, in the
    SAME dir order the embedding concat uses (offsets stay aligned)."""
    parts = [
        _read_partition(d, p, schema) for d in dirs if rows_map[(d, p)] > 0
    ]
    if not parts:
        return ColumnTable.empty(schema)
    return ColumnTable.concat(parts) if len(parts) > 1 else parts[0]


# Per-process partition read cache: (path, mtime_ns) → ColumnTable. The
# probed working set is re-read on every query batch otherwise; bounded by
# total cached bytes with FIFO eviction. One lock covers both caches —
# the byte-budget eviction is a read-modify-write that concurrent serve
# workers must not interleave.
import threading

_VEC_CACHE_LOCK = threading.Lock()
_PARTITION_CACHE: dict = {}
_PARTITION_CACHE_BYTES = 2 * 1024**3


def _table_bytes(t: ColumnTable) -> int:
    return sum(v.nbytes for v in t.columns.values())


# Device-resident embedding matrices per partition file, so repeated query
# batches skip the host→device upload of candidate embeddings entirely.
_DEVICE_EMB_CACHE: dict = {}
_DEVICE_EMB_CACHE_BYTES = 4 * 1024**3


def _partition_device_emb(version_dir: Path, p: int, schema: Schema, emb_name: str):
    import os

    import jax.numpy as jnp

    path = str(version_dir / hio.bucket_file_name(p))
    key = (path, os.stat(path).st_mtime_ns, emb_name)
    with _VEC_CACHE_LOCK:
        hit = _DEVICE_EMB_CACHE.get(key)
    if hit is not None:
        return hit
    # Read ONLY the embedding column — payload columns are read lazily by
    # _read_partition when a winning row actually lands in this partition.
    t = hio.read_parquet([path], columns=[emb_name], schema=schema)
    arr = jnp.asarray(t.columns[emb_name], dtype=jnp.float32)
    with _VEC_CACHE_LOCK:
        _DEVICE_EMB_CACHE[key] = arr
        total = sum(a.nbytes for a in _DEVICE_EMB_CACHE.values())
        while total > _DEVICE_EMB_CACHE_BYTES and len(_DEVICE_EMB_CACHE) > 1:
            oldest = next(iter(_DEVICE_EMB_CACHE))
            total -= _DEVICE_EMB_CACHE.pop(oldest).nbytes
    return arr


def _read_partition(version_dir: Path, p: int, schema: Schema) -> ColumnTable:
    import os

    path = str(version_dir / hio.bucket_file_name(p))
    key = (path, os.stat(path).st_mtime_ns)
    with _VEC_CACHE_LOCK:
        hit = _PARTITION_CACHE.get(key)
    if hit is not None:
        return hit
    t = hio.read_parquet([path], columns=schema.names, schema=schema)
    with _VEC_CACHE_LOCK:
        _PARTITION_CACHE[key] = t
        # FIFO-evict oldest entries past the byte budget (dict preserves
        # insertion order).
        total = sum(_table_bytes(tab) for tab in _PARTITION_CACHE.values())
        while total > _PARTITION_CACHE_BYTES and len(_PARTITION_CACHE) > 1:
            oldest = next(iter(_PARTITION_CACHE))
            total -= _table_bytes(_PARTITION_CACHE.pop(oldest))
    return t
