"""Parquet IO: host staging between disk and the device plane.

The analog of Spark's FileSourceScanExec + vectorized Parquet read
(SURVEY.md §2.2). Reads go through pyarrow into ColumnTable (strings
dictionary-encoded); writes emit one sorted parquet file per bucket plus a
`_index_manifest.json` with per-bucket row counts — the manifest is what
enables query-time bucket pruning and hybrid-scan planning without opening
every footer.
"""

from __future__ import annotations

import collections
import dataclasses
import json
import os
import stat
import time
from collections.abc import Mapping
from pathlib import Path
from types import MappingProxyType

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

from hyperspace_tpu import stats
from hyperspace_tpu.dataset import list_data_files
from hyperspace_tpu.exceptions import HyperspaceError, IndexCorruptionError
from hyperspace_tpu.execution.table import ColumnTable
from hyperspace_tpu.faults import fault_point
from hyperspace_tpu.metadata.log_entry import FileInfo
from hyperspace_tpu.obs import metrics as obs_metrics
from hyperspace_tpu.obs import trace as obs_trace
from hyperspace_tpu.schema import Schema
from hyperspace_tpu.utils import retry
from hyperspace_tpu.utils.file_utils import write_json

MANIFEST_NAME = "_index_manifest.json"

# -- decoded-table cache ------------------------------------------------------
# Index bucket files are read on every query; decoding them once and
# revalidating by mtime removes the host IO floor from the read hot path
# (round 1 weakness #4/#5). Entries are treated as immutable by callers.
# Callers read concurrently from thread pools — all cache state is guarded
# by one lock (reads/decodes themselves run unlocked).
import threading

_CACHE_BUDGET = 512 << 20
_cache: "dict[tuple, tuple[tuple, int, ColumnTable]]" = {}
_cache_bytes = 0
_cache_lock = threading.Lock()
_cache_stats = {"hits": 0, "misses": 0, "miss_files": 0, "miss_bytes": 0}

# Process-lifetime mirrors of the per-process cache dict above, in the
# exportable registry (obs/export.py renders them).
_MET_HITS = obs_metrics.counter("table_cache.hits", "decoded-table cache hits")
_MET_MISSES = obs_metrics.counter("table_cache.misses", "decoded-table cache misses")
_MET_BYTES = obs_metrics.counter("io.bytes_scanned", "bytes physically read (cache misses)")
_MET_FILES = obs_metrics.counter("io.files_read", "files physically read (cache misses)")


def set_table_cache_budget(nbytes: int) -> None:
    global _CACHE_BUDGET
    with _cache_lock:
        _CACHE_BUDGET = int(nbytes)
        _evict_locked()


def clear_table_cache() -> None:
    global _cache_bytes
    with _cache_lock:
        _cache.clear()
        _cache_bytes = 0
    # Device/derived caches key on the identity of (now-released) host
    # arrays; drop them too so the pinned references don't linger.
    from hyperspace_tpu.execution import device_cache

    device_cache.clear_all()


def table_cache_stats() -> dict:
    with _cache_lock:
        return dict(_cache_stats)


def _evict_locked() -> None:
    global _cache_bytes
    while _cache_bytes > _CACHE_BUDGET and _cache:
        k = next(iter(_cache))
        # Caller holds _cache_lock (the _locked suffix is the contract).
        _, nb, _ = _cache.pop(k)  # noqa: HSL008
        _cache_bytes -= nb


def _table_nbytes(t: ColumnTable) -> int:
    from hyperspace_tpu.execution import device_cache

    return device_cache.table_footprint_bytes(t)


def _freeze_table(t: ColumnTable) -> None:
    """Mark a table's arrays read-only before it enters the cache: the
    SAME object is returned to every caller, so an accidental in-place
    write must raise instead of corrupting every later query."""
    for arr in (*t.columns.values(), *t.validity.values(), *t.dictionaries.values()):
        arr.flags.writeable = False


def read_parquet_cached(files: list[str], columns: list[str] | None = None, schema: Schema | None = None) -> ColumnTable:
    """read_parquet through the mtime-validated decoded-table cache."""
    import os

    key = (tuple(files), tuple(columns) if columns is not None else None)
    try:
        stats_ = [os.stat(f) for f in files]
    except OSError:
        return read_parquet(files, columns=columns, schema=schema)
    mtimes = tuple(s.st_mtime_ns for s in stats_)
    with _cache_lock:
        hit = _cache.get(key)
        if hit is not None and hit[0] == mtimes:
            # Re-insert for LRU recency (dict preserves insertion order).
            _cache[key] = _cache.pop(key)
            _cache_stats["hits"] += 1
            _MET_HITS.inc()
            return hit[2]
        _cache_stats["misses"] += 1
        _cache_stats["miss_files"] += len(files)
        disk_bytes = sum(s.st_size for s in stats_)
        _cache_stats["miss_bytes"] += disk_bytes
    _MET_MISSES.inc()
    _MET_FILES.inc(len(files))
    _MET_BYTES.inc(disk_bytes)
    # Cache-destined decode: the one sanctioned caller of the zero-copy
    # staging path (execution/staging.py) — eligible columns stay
    # read-only views over the Arrow buffers, frozen into the cache
    # below (or downgraded to owned copies when the table turns out too
    # large to cache, restoring writable per-query semantics exactly).
    table = read_table_files(
        files, "parquet", columns=columns, schema=schema, zero_copy_ok=True
    )
    nb = _table_nbytes(table)
    global _cache_bytes
    cached = False
    with _cache_lock:
        if nb <= _CACHE_BUDGET // 4:
            # Freeze ONLY what actually enters the cache: frozen ⟺
            # identity-stable. A table too large to cache is re-decoded
            # per query with fresh ids — freezing it would make the
            # device/derived caches accumulate dead never-hit entries.
            _freeze_table(table)
            if key in _cache:
                _cache_bytes -= _cache.pop(key)[1]
            _cache[key] = (mtimes, nb, table)
            _cache_bytes += nb
            _evict_locked()
            cached = True
    if not cached:
        table.own_arrays()
    return table


def read_parquet(files: list[str], columns: list[str] | None = None, schema: Schema | None = None) -> ColumnTable:
    return read_table_files(files, "parquet", columns=columns, schema=schema)


_ARROW_TYPES = {
    "int32": pa.int32(),
    "int64": pa.int64(),
    "float32": pa.float32(),
    "float64": pa.float64(),
    "bool": pa.bool_(),
    "string": pa.string(),
    "date": pa.date32(),
    "timestamp": pa.timestamp("us"),
}


def _arrow_types_for(schema: Schema | None) -> dict | None:
    """name → arrow type for the registered schema's scalar fields —
    pins CSV/JSON decode to the PLANNED types instead of re-inferring
    per file (per-file inference can diverge across files and from the
    registration-time schema)."""
    if schema is None:
        return None
    out = {}
    for f in schema.fields:
        t = _ARROW_TYPES.get(f.dtype)
        if t is not None:
            out[f.name] = t
    return out or None


def _read_one_file(path: str, fmt: str, columns: list[str] | None, schema: Schema | None):
    """One file of any supported source format → pyarrow Table, with
    transient-IO retry (pyarrow's IO errors subclass OSError; only
    retryable errnos re-attempt — a missing or truncated file surfaces
    immediately). The reference gates sources to the same four formats
    (index/serde/LogicalPlanSerDeUtils.scala:225-245)."""
    return retry.retry_call(_read_one_file_once, path, fmt, columns, schema)


def _read_one_file_once(path: str, fmt: str, columns: list[str] | None, schema: Schema | None):
    fault_point("bucket.read", path)
    if fmt == "parquet":
        # ParquetFile (never the dataset API): index files live under
        # hive-looking `v__=N` version dirs, and inferring a `v__`
        # partition column would bake it into compacted files. Decoding
        # as ONE whole-file batch (instead of pq.read_table's ~128Ki-row
        # internal batches) keeps every column SINGLE-CHUNK, which is
        # what lets the zero-copy staging layer keep it as an Arrow
        # buffer view — multi-chunk columns must copy to become
        # contiguous. Measures at parity or faster than read_table.
        pf = pq.ParquetFile(path)
        n = pf.metadata.num_rows
        if columns is not None:
            # iter_batches silently IGNORES unknown columns where
            # read_table raised — keep the strict contract (an index
            # file missing a declared column is corruption, not a
            # narrower read).
            names = set(pf.schema_arrow.names)
            missing = [c for c in columns if c not in names]
            if missing:
                raise pa.lib.ArrowInvalid(
                    f"no match for column(s) {missing} in {path}"
                )
        batches = list(
            pf.iter_batches(batch_size=max(n, 1), columns=columns, use_threads=True)
        )
        if not batches:
            sch = pf.schema_arrow
            if columns is not None:
                sch = pa.schema([sch.field(c) for c in columns])
            return sch.empty_table()
        return pa.Table.from_batches(batches)
    if fmt == "orc":
        from pyarrow import orc

        return orc.ORCFile(path).read(columns=columns)
    if fmt == "csv":
        from pyarrow import csv as pcsv

        opts = pcsv.ConvertOptions(
            include_columns=columns if columns is not None else None,
            column_types=_arrow_types_for(schema),
        )
        return pcsv.read_csv(path, convert_options=opts)
    if fmt == "json":
        from pyarrow import json as pjson

        types = _arrow_types_for(schema)
        parse = None
        if types is not None and schema is not None and len(types) == len(schema.fields):
            parse = pjson.ParseOptions(
                explicit_schema=pa.schema([(f.name, types[f.name]) for f in schema.fields])
            )
        t = pjson.read_json(path, parse_options=parse)
        return t.select(columns) if columns is not None else t
    raise HyperspaceError(f"unsupported source format {fmt!r} (parquet|orc|csv|json)")


# Cold reads at or above this many on-disk bytes decode as parallel
# row-group chunks instead of one serial pq.read_table per file (only
# engaged when the file count alone cannot saturate the pool).
_CHUNKED_READ_MIN_BYTES = 32 << 20


def _read_parquet_chunked(files: list[str], columns: list[str] | None):
    """Row-group-parallel decode of a small file set, or None when the
    footer plan yields no parallelism (single row group, tiny estimate,
    unreadable footers — every fallback lands on the per-file path)."""
    from concurrent.futures import ThreadPoolExecutor

    try:
        footers = read_footers(files)
    except (OSError, pa.ArrowException):
        return None
    est = estimate_uncompressed_bytes(files, columns, footers=footers)
    if est <= 0:
        return None
    units = plan_row_group_chunks(files, max(4 << 20, est // 16), columns, footers=footers)
    if len(units) < 2:
        return None
    read = obs_trace.wrap(lambda c: read_chunk(c, columns))
    with ThreadPoolExecutor(max_workers=min(8, len(units))) as ex:
        parts = list(ex.map(read, units))
    # Units are planned in file order with row groups in order, so the
    # ordered concat reproduces the serial read's row order exactly.
    return pa.concat_tables(parts, promote_options="default")


def read_table_files(
    files: list[str],
    fmt: str = "parquet",
    columns: list[str] | None = None,
    schema: Schema | None = None,
    zero_copy_ok: bool = False,
) -> ColumnTable:
    """Format-aware multi-file read into a ColumnTable (decode released
    from the GIL and overlapped across files). `schema` is the registered
    dataset schema; CSV/JSON decode is pinned to it. `zero_copy_ok`
    opts the decode into the device-staging path — ONLY the
    cache-destined read (read_parquet_cached) may pass it (see
    ColumnTable.from_arrow)."""
    if not files:
        raise HyperspaceError("no files to read")
    import os

    try:
        nbytes = sum(os.path.getsize(f) for f in files)
    except OSError:
        nbytes = 0
    with obs_trace.span("io.read", files=len(files), fmt=fmt, bytes=nbytes):
        table = None
        if fmt == "parquet" and len(files) <= 4 and nbytes >= _CHUNKED_READ_MIN_BYTES:
            # A cold read of one (or few) big bucket files used to decode
            # serially — one pq.read_table per pool worker with most of
            # the pool idle. Split it into footer-planned row-group
            # chunks instead so the decode parallelizes within the file.
            table = _read_parquet_chunked(files, columns)
        if table is None:
            if len(files) == 1:
                tables = [_read_one_file(files[0], fmt, columns, schema)]
            else:
                from concurrent.futures import ThreadPoolExecutor

                # wrap(): pool workers start with an empty contextvar
                # context — re-plant the caller's active span so per-file
                # retry/fault events attribute to this read.
                read = obs_trace.wrap(lambda f: _read_one_file(f, fmt, columns, schema))
                with ThreadPoolExecutor(max_workers=min(8, len(files))) as ex:
                    tables = list(ex.map(read, files))
            table = pa.concat_tables(tables, promote_options="default") if len(tables) > 1 else tables[0]
    if schema is not None and columns is not None:
        schema = schema.select(columns)
    with obs_trace.span("device.stage", files=len(files), zero_copy=zero_copy_ok):
        return ColumnTable.from_arrow(table, schema, zero_copy_ok=zero_copy_ok)


def _read_footer(path: str) -> "pq.FileMetaData":
    fault_point("footer.read", path)
    return pq.ParquetFile(path).metadata


# -- footer cache -------------------------------------------------------------
# Every size estimate, chunk plan, spill batch, and stats lookup used to
# re-open footers already parsed moments earlier (the build opened each
# source footer up to three times). One mtime-validated map dedupes them;
# the prefetcher warms it so the executor's footer reads are hits.
_FOOTER_CACHE_MAX = 4096
_footer_cache: "dict[str, tuple[int, pq.FileMetaData]]" = {}
_footer_lock = threading.Lock()


def clear_footer_cache() -> None:
    with _footer_lock:
        _footer_cache.clear()


def read_footers(files: list[str]) -> dict[str, "pq.FileMetaData"]:
    """One footer parse per file, reused by the size estimate, the chunk
    planner, the spill batcher, and the query-tail prefetcher (footers
    can be remote round-trips — hence the transient-IO retry and the
    mtime-validated cache; `io.footer_cache.*` counts the dedup)."""
    import os

    from concurrent.futures import ThreadPoolExecutor

    if not files:
        return {}
    out: dict[str, "pq.FileMetaData"] = {}
    todo: list[tuple[str, int | None]] = []
    for f in files:
        try:
            mt = os.stat(f).st_mtime_ns
        except OSError:
            mt = None
        hit = None
        if mt is not None:
            with _footer_lock:
                cached = _footer_cache.get(f)
            if cached is not None and cached[0] == mt:
                hit = cached[1]
        if hit is not None:
            out[f] = hit
        else:
            todo.append((f, mt))
    if len(out):
        stats.increment("io.footer_cache.hits", len(out))
    if not todo:
        return {f: out[f] for f in files}
    stats.increment("io.footer_cache.misses", len(todo))
    if len(todo) == 1:
        mds = [retry.retry_call(_read_footer, todo[0][0])]
    else:
        with obs_trace.span("io.footers", files=len(todo)):
            read = obs_trace.wrap(lambda f: retry.retry_call(_read_footer, f))
            with ThreadPoolExecutor(max_workers=min(8, len(todo))) as ex:
                mds = list(ex.map(read, (f for f, _ in todo)))
    with _footer_lock:
        for (f, mt), md in zip(todo, mds):
            out[f] = md
            if mt is not None:
                _footer_cache[f] = (mt, md)
        while len(_footer_cache) > _FOOTER_CACHE_MAX:
            _footer_cache.pop(next(iter(_footer_cache)))
    return {f: out[f] for f in files}


def _row_group_bytes(md, rg: int, want: set | None) -> int:
    g = md.row_group(rg)
    total = 0
    for ci in range(g.num_columns):
        col = g.column(ci)
        name = col.path_in_schema.split(".")[0]
        if want is None or name.lower() in want:
            total += col.total_uncompressed_size
    return total


def estimate_uncompressed_bytes(
    files: list[str], columns: list[str] | None = None, footers=None
) -> int:
    """Uncompressed in-memory size estimate from parquet footers (no data
    read) — drives the in-memory vs streaming build decision."""
    footers = footers if footers is not None else read_footers(files)
    want = {c.lower() for c in columns} if columns is not None else None
    return sum(
        _row_group_bytes(md, rg, want)
        for f, md in footers.items()
        for rg in range(md.num_row_groups)
    )


def plan_row_group_chunks(
    files: list[str], chunk_bytes: int, columns: list[str] | None = None, footers=None
) -> list[list[tuple[str, int]]]:
    """Split (file, row-group) units into chunks of ≤ chunk_bytes
    uncompressed (each chunk holds at least one row group). The streaming
    build's host-memory unit."""
    footers = footers if footers is not None else read_footers(files)
    want = {c.lower() for c in columns} if columns is not None else None
    chunks: list[list[tuple[str, int]]] = []
    cur: list[tuple[str, int]] = []
    cur_bytes = 0
    for f in files:
        md = footers[f]
        for rg in range(md.num_row_groups):
            sz = _row_group_bytes(md, rg, want)
            if cur and cur_bytes + sz > chunk_bytes:
                chunks.append(cur)
                cur, cur_bytes = [], 0
            cur.append((f, rg))
            cur_bytes += sz
    if cur:
        chunks.append(cur)
    return chunks


def _read_chunk_file(f: str, rgs: list[int], columns: list[str] | None):
    fault_point("bucket.read", f)
    pf = pq.ParquetFile(f)
    if columns is not None:
        # Tolerate per-file schema skew: a column absent from THIS file is
        # skipped here and null-filled by the caller's promoting concat —
        # the same union semantics read_table_files gets from
        # concat_tables, and what lets the prefetcher probe any file.
        names = set(pf.schema_arrow.names)
        columns = [c for c in columns if c in names]
    return pf.read_row_groups(rgs, columns=columns)


def read_chunk(chunk: list[tuple[str, int]], columns: list[str] | None = None):
    """Decode one planned chunk to a pyarrow Table (transient-IO retried
    per file; columns missing from a file are null-filled)."""
    by_file: dict[str, list[int]] = {}
    for f, rg in chunk:
        by_file.setdefault(f, []).append(rg)
    parts = [
        retry.retry_call(_read_chunk_file, f, rgs, columns)
        for f, rgs in by_file.items()
    ]
    return pa.concat_tables(parts, promote_options="default") if len(parts) > 1 else parts[0]


def bucket_file_name(bucket: int) -> str:
    return f"bucket-{bucket:05d}.parquet"


def bucket_of_file_name(name: str) -> int | None:
    """Inverse of bucket_file_name (None for non-bucket files)."""
    if name.startswith("bucket-") and name.endswith(".parquet"):
        try:
            return int(name[len("bucket-") : -len(".parquet")])
        except ValueError:
            return None
    return None


def _json_scalar(v):
    """numpy scalar → plain JSON-serializable Python value."""
    return v.item() if hasattr(v, "item") else v


def bucket_key_stats(table: ColumnTable, key: str, sel: np.ndarray | None = None):
    """JSON-serializable [min, max] of `table[key]` over rows `sel` (all
    rows when None), ignoring nulls; None for empty/all-null/vector. The
    analog of parquet column-chunk statistics the reference gets from
    FileSourceScanExec min/max pruning (SURVEY.md §2.2) — persisted in the
    index manifest so range predicates can skip whole bucket files."""
    try:
        f = table.schema.field(key)
    except Exception:
        return None
    if f.is_vector:
        return None
    vals = table.columns[f.name]
    valid = table.valid_mask(f.name)
    if sel is not None:
        vals = vals[sel]
        valid = valid[sel] if valid is not None else None
    if valid is not None:
        vals = vals[valid]
    if len(vals) == 0:
        return None
    if f.name in table.dictionaries:
        # np.min has no ufunc loop for unicode; reduce over the (small)
        # set of used dictionary values in Python instead.
        used = np.asarray(table.dictionaries[f.name])[np.unique(vals)].tolist()
        return [min(used), max(used)]
    return [_json_scalar(vals.min()), _json_scalar(vals.max())]


def bucket_column_stats(
    table: ColumnTable, columns: list[str], sel: np.ndarray | None = None
) -> dict:
    """Per-column [min, max] stats over rows `sel` for every named scalar
    column — the included-column analog of bucket_key_stats (Spark's
    parquet reader gives the reference min/max on EVERY column; the
    manifest carries ours so non-leading predicates prune files too)."""
    out = {}
    for c in columns:
        s = bucket_key_stats(table, c, sel)
        out[c] = s
    return out


# Parquet codec for INDEX bucket files (read only by this engine; the
# source data keeps whatever codec it arrived with). lz4 encodes ~2x
# faster than the parquet default (snappy is close, zstd far slower) on
# the single-core hosts where encode IS the build's carve phase, and
# decodes at least as fast. Overridable per call for experiments.
INDEX_WRITE_COMPRESSION = "lz4"


def write_bucket(
    dest_dir: Path, bucket: int, table: ColumnTable, compression: str | None = None
) -> None:
    dest_dir.mkdir(parents=True, exist_ok=True)
    dest = dest_dir / bucket_file_name(bucket)
    fault_point("bucket.write", dest)
    # Dictionary-encode ONLY string columns: for numeric index data,
    # parquet dictionary encoding costs ~6x encode time AND grows the
    # files (high-cardinality keys, float payloads); for low-cardinality
    # strings it still wins.
    dict_cols = [f.name for f in table.schema.fields if f.is_string]
    pq.write_table(
        table.to_arrow(),
        dest,
        use_dictionary=dict_cols,
        compression=compression or INDEX_WRITE_COMPRESSION,
        # Pruning reads the MANIFEST's key/column stats (computed over the
        # gathered bucket in carve_and_write), never parquet footer
        # statistics — skipping them is ~2x on the encode of numeric
        # buckets.
        write_statistics=False,
    )
    fault_point("bucket.written", dest)


def write_manifest(
    dest_dir: Path,
    num_buckets: int,
    indexed_columns: list[str],
    bucket_rows: list[int],
    key_stats: list | None = None,
    column_stats: list | None = None,
) -> None:
    dest_dir.mkdir(parents=True, exist_ok=True)
    manifest = {
        "numBuckets": num_buckets,
        "indexedColumns": indexed_columns,
        "bucketRows": bucket_rows,
    }
    if key_stats is not None:
        # Per-bucket [min, max] of the first indexed column (None when the
        # bucket is empty or all-null) — enables file-level range pruning.
        manifest["keyStats"] = key_stats
    if column_stats is not None:
        # Per-bucket {column: [min, max] | None} for the remaining scalar
        # columns — file pruning on included-column predicates.
        manifest["columnStats"] = column_stats
    mp = dest_dir / MANIFEST_NAME
    fault_point("manifest.write", mp)
    # Atomic temp-file + os.replace (+ fsync) via write_json: a crash
    # mid-write leaves either the previous manifest or none — never a
    # torn `_index_manifest.json` that poisons every later read.
    write_json(mp, manifest)
    fault_point("manifest.written", mp)


def read_manifest(version_dir: Path) -> dict | None:
    """Version dir's manifest, or None when absent (pre-stats builds —
    planning degrades to footer counts). Garbage raises a typed
    IndexCorruptionError so callers can distinguish "no manifest" from
    "index data is damaged" and degrade/fall back deliberately."""
    p = Path(version_dir) / MANIFEST_NAME
    if not p.exists():
        return None
    fault_point("manifest.read", p)
    try:
        return json.loads(p.read_text())
    except (OSError, ValueError) as e:
        stats.increment("index.corruption")
        raise IndexCorruptionError(
            f"corrupt index manifest {p}: {e}",
            index_root=str(Path(version_dir).parent),
            path=str(p),
        ) from e


_manifest_cache: "dict[str, tuple[tuple[int, int, int], dict | None]]" = {}
_manifest_lock = threading.Lock()


def read_manifest_cached(version_dir: Path) -> dict | None:
    """read_manifest through a stat-validated cache (manifests are
    immutable per version, but refresh can rewrite a dir's manifest)."""
    mp = Path(version_dir) / MANIFEST_NAME
    try:
        st = os.stat(mp)
    except OSError:
        return None
    stamp = (st.st_ino, st.st_size, st.st_mtime_ns)
    with _manifest_lock:
        cached = _manifest_cache.get(str(mp))
    if cached is not None and cached[0] == stamp:
        return cached[1]
    m = read_manifest(version_dir)
    with _manifest_lock:
        _manifest_cache[str(mp)] = (stamp, m)
    return m


# -- version-directory listing cache ------------------------------------------
# A committed index version directory never changes: refresh, optimize
# and ingest write a new v__=N, builds write flat bucket files before
# the commit. So each rewrite's listing of it is served from here, the
# entry checked by one stat of the directory: a file added, removed or
# renamed moves the directory's mtime and ctime. Keyed by the
# directory's absolute path; guarded by _listing_lock (HSL008/HSL013),
# the listing itself runs unlocked.
_listing_cache: "collections.OrderedDict[str, _Listing]" = collections.OrderedDict()
_listing_lock = threading.Lock()
_LISTING_MAX = 256
# A listing made within this long of its directory's mtime is not
# cached: a change in the same timestamp tick would leave the times as
# they were (git's racy-index rule).
_RACY_NS = 1_000_000_000


@dataclasses.dataclass(frozen=True)
class _Listing:
    stamp: tuple[int, int, int]  # the directory's (ino, mtime_ns, ctime_ns)
    dir: str  # the path as the caller spelt it; each row's path starts with it
    rows: tuple[tuple[str, int, int], ...]  # list_data_files' (path, size, mtime_ns)
    mtimes: Mapping[str, int]


def _stamp(st: os.stat_result) -> tuple[int, int, int]:
    return (st.st_ino, st.st_mtime_ns, st.st_ctime_ns)


def _valid_listing(d: Path, st: os.stat_result) -> _Listing | None:
    """The cached listing of `d` if the directory's stat `st` still
    validates it."""
    key = os.path.abspath(d)
    with _listing_lock:
        ent = _listing_cache.get(key)
        if ent is not None:
            _listing_cache.move_to_end(key)
    if ent is None or ent.stamp != _stamp(st) or ent.dir != str(d):
        return None
    return ent


def _list_flat(d: Path) -> list[tuple[str, int, int]] | None:
    """list_data_files(d) rows for a directory with no subdirectory;
    None for any other layout (rglob would recurse, and one stat of `d`
    cannot see changes below it)."""
    rows = []
    with os.scandir(d) as it:
        for e in it:
            if e.is_dir():
                return None
            if not e.name.endswith(".parquet") or e.name.startswith((".", "_")):
                continue
            st = e.stat()
            rows.append((str(d / e.name), st.st_size, st.st_mtime_ns))
    rows.sort()
    return rows


def list_version_dir(version_dir: Path) -> tuple[list[FileInfo], bool]:
    """``list_data_files(version_dir)`` through the listing cache, and
    whether the cache served it. A missing or unreadable directory, or
    one with subdirectories, is listed as list_data_files lists it and
    never cached."""
    d = Path(version_dir)
    try:
        st = os.stat(d)
    except OSError:
        return list_data_files(d), False
    ent = _valid_listing(d, st)
    if ent is not None:
        return [FileInfo(*r) for r in ent.rows], True
    listed_at = time.time_ns()
    try:
        rows = _list_flat(d) if stat.S_ISDIR(st.st_mode) else None
    except OSError:
        rows = None
    if rows is None:
        return list_data_files(d), False
    if st.st_mtime_ns <= listed_at - _RACY_NS:
        key = os.path.abspath(d)
        ent = _Listing(_stamp(st), str(d), tuple(rows), MappingProxyType({p: m for p, _, m in rows}))
        with _listing_lock:
            _listing_cache[key] = ent
            _listing_cache.move_to_end(key)
            while len(_listing_cache) > _LISTING_MAX:
                _listing_cache.popitem(last=False)
    return [FileInfo(*r) for r in rows], False


def cached_file_mtimes(version_dir: Path) -> Mapping[str, int] | None:
    """{path: mtime_ns} of the cached listing of `version_dir` when one
    stat of the directory still validates it; None otherwise. Never
    lists the directory."""
    d = Path(version_dir)
    try:
        st = os.stat(d)
    except OSError:
        return None
    ent = _valid_listing(d, st)
    return None if ent is None else ent.mtimes


def file_key_stats(files: list[str]) -> dict[str, list | None]:
    """Per-file [min, max] of the leading indexed column, looked up in each
    file's version-dir manifest (cached, mtime-validated). Files whose dir
    has no manifest or whose manifest has no keyStats are absent from the
    result; a present-but-None value means the bucket is empty/all-null."""
    out: dict[str, list | None] = {}
    by_dir: dict[Path, list[str]] = {}
    for f in files:
        by_dir.setdefault(Path(f).parent, []).append(f)
    for d, fs in by_dir.items():
        m = read_manifest_cached(d)
        if not m or "keyStats" not in m:
            continue
        ks = m["keyStats"]
        for f in fs:
            b = bucket_of_file_name(Path(f).name)
            if b is not None and b < len(ks):
                out[f] = ks[b]
    return out


def file_column_stats(files: list[str], column: str) -> dict[str, list | None]:
    """Per-file [min, max] of a NON-leading column from the manifests'
    columnStats (case-insensitive name match). Same present/None contract
    as file_key_stats."""
    out: dict[str, list | None] = {}
    by_dir: dict[Path, list[str]] = {}
    low = column.lower()
    for f in files:
        by_dir.setdefault(Path(f).parent, []).append(f)
    for d, fs in by_dir.items():
        m = read_manifest_cached(d)
        cs = (m or {}).get("columnStats")
        if not cs:
            continue
        for f in fs:
            b = bucket_of_file_name(Path(f).name)
            if b is None or b >= len(cs) or cs[b] is None:
                continue
            for name, s in cs[b].items():
                if name.lower() == low:
                    out[f] = s
                    break
    return out


def carve_and_write(
    dest: Path,
    table: "ColumnTable",
    sorted_partition: "np.ndarray",
    num_partitions: int,
    indexed_columns: list[str],
    order: "np.ndarray | None" = None,
    sort_fn=None,
) -> list[int]:
    """Carve `table` into one parquet file per partition + manifest.

    `sorted_partition` is the non-decreasing partition id per carved row;
    `order` (optional) maps carved row i to `table` row order[i] (identity
    when the table is already in carved order). `sort_fn(p, sel)` (optional)
    finalizes partition p's selection inside its write task — the host
    build venue passes the per-bucket native key sort here so sorting
    PIPELINES with the parquet encode of other buckets. Encode and sort
    both release the GIL, so buckets run concurrently. Returns
    per-partition row counts (also persisted in the manifest)."""
    from concurrent.futures import ThreadPoolExecutor

    dest = Path(dest)
    dest.mkdir(parents=True, exist_ok=True)
    starts = np.searchsorted(sorted_partition, np.arange(num_partitions + 1))
    rows = [int(starts[p + 1] - starts[p]) for p in range(num_partitions)]
    key_stats: list = [None] * num_partitions

    col_stats: list = [None] * num_partitions
    other_cols = [
        f.name
        for f in table.schema.fields
        if not f.is_vector and (not indexed_columns or f.name != table.schema.field(indexed_columns[0]).name)
    ]

    def write_one(p: int) -> None:
        lo, hi = int(starts[p]), int(starts[p + 1])
        sel = np.arange(lo, hi) if order is None else order[lo:hi]
        if sort_fn is not None:
            sel = sort_fn(p, sel)
        # Gather ONCE; stats read the gathered bucket (a second full
        # per-column fancy-index here measurably slows the carve phase).
        sub = table.take(sel)
        if indexed_columns:
            key_stats[p] = bucket_key_stats(sub, indexed_columns[0])
        if other_cols:
            col_stats[p] = bucket_column_stats(sub, other_cols)
        write_bucket(dest, p, sub)

    with obs_trace.span("io.carve", partitions=num_partitions):
        with ThreadPoolExecutor(max_workers=min(16, max(1, num_partitions))) as ex:
            list(ex.map(obs_trace.wrap(write_one), range(num_partitions)))
    has_stats = any(s is not None for s in key_stats)
    write_manifest(
        dest, num_partitions, indexed_columns, rows,
        key_stats if has_stats else None,
        col_stats if any(s is not None for s in col_stats) else None,
    )
    return rows
