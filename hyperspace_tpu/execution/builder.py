"""The device build pipeline: scan → hash-bucketize → per-shard sort → persist.

This is the framework's write hot path — the TPU-native re-design of the
reference's `df.repartition(numBuckets, indexedCols)` + bucketed sorted
Parquet write (actions/CreateActionBase.scala:99-120 and
index/DataFrameWriterExtensions.scala:49-78):

  host:   parquet → ColumnTable (strings dict-encoded) → row hashes (the
          same uint32 function the query plane uses for bucket pruning)
  device: all_to_all bucketize over the mesh (ops/bucketize.py — the
          Spark-shuffle analog, riding ICI) then ONE fused lexicographic
          lax.sort by (bucket, indexed columns) per shard
  host:   carve the bucket-grouped, key-sorted shards into one parquet
          file per bucket + a manifest of per-bucket row counts

`DeviceIndexBuilder` implements the `IndexWriter` seam consumed by
CreateAction/RefreshAction, and `compact` implements OptimizeAction's
compactor seam.
"""

from __future__ import annotations

import math
import time
from pathlib import Path

import numpy as np

from jax.sharding import Mesh

from hyperspace_tpu import native
from hyperspace_tpu.config import DEFAULT_BUILD_MEMORY_BUDGET, DEFAULT_VENUE
from hyperspace_tpu.dataset import format_suffix, list_data_files
from hyperspace_tpu.exceptions import HyperspaceError
from hyperspace_tpu.execution import io as hio

# Host hash/sort helpers live in build_exchange (the jax-free module the
# pooled build's worker processes import); re-exported here for the
# query plane's historical import path (executor/exec_side/exec_scan).
from hyperspace_tpu.execution.build_exchange import (  # noqa: F401 — re-exports
    NULL_HASH,
    compute_row_hashes,
    hash_scalar_key,
)
from hyperspace_tpu.execution.table import ColumnTable
from hyperspace_tpu.faults import fault_point
from hyperspace_tpu.obs import metrics as obs_metrics
from hyperspace_tpu.obs import trace as obs_trace
from hyperspace_tpu.ops.hashing import bucket_ids
from hyperspace_tpu.parallel.mesh import enable_compile_cache, mesh_size
from hyperspace_tpu.plan.nodes import LogicalPlan, Scan

# Pipeline telemetry (docs/observability.md): occupancy is the mean busy
# fraction of the three p2 stages over the pipeline wall (1.0 = every
# stage saturated — a longer queue window cannot help; ≪1.0 = one stage
# starves the others); queue depth is observed at each reader put.
_MET_OCCUPANCY = obs_metrics.gauge(
    "build.pipeline.occupancy",
    "mean busy fraction of the p2 read/sort/write stages over the pipeline wall",
)
_MET_QDEPTH = obs_metrics.histogram(
    "build.pipeline.queue_depth",
    "bucket-completion queue depth at each reader put",
    buckets=obs_metrics.COUNT_BUCKETS,
)
_MET_POOL_WORKERS = obs_metrics.gauge(
    "build.workers.active",
    "worker processes the pooled build currently has spawned (0 between builds)",
)


def _pad_to(arr: np.ndarray, n: int, fill=0) -> np.ndarray:
    if len(arr) == n:
        return arr
    pad = np.full((n - len(arr),) + arr.shape[1:], fill, dtype=arr.dtype)
    return np.concatenate([arr, pad])


def _host_sort_perms(tables, indexed_columns: list[str]) -> list[np.ndarray]:
    """Per-table stable key-sort permutations via the native kernel (the
    streaming build's host sort venue; same order as device_sort_perms)."""
    from hyperspace_tpu import native
    from hyperspace_tpu.ops.sortkeys import key_lanes, lanes_as_unsigned

    perms = []
    for t in tables:
        perm = np.arange(t.num_rows, dtype=np.int64)
        native.sort_range(perm, lanes_as_unsigned(key_lanes(t, indexed_columns)))
        perms.append(perm)
    return perms


def _prefetched(it):
    """One-ahead prefetch over an iterator: the next item decodes on a
    worker thread while the caller processes the current one. Each step
    runs under a `build.p1.decode` span re-planted from the caller
    (pool workers start with an empty contextvar context)."""
    from concurrent.futures import ThreadPoolExecutor

    sentinel = object()
    it = iter(it)

    def step():
        with obs_trace.span("build.p1.decode"):
            return next(it, sentinel)

    step = obs_trace.wrap(step)
    with ThreadPoolExecutor(max_workers=1) as ex:
        fut = ex.submit(step)
        while True:
            cur = fut.result()
            if cur is sentinel:
                return
            fut = ex.submit(step)
            yield cur


class DeviceIndexBuilder:
    """IndexWriter over a device mesh (defaults to all local devices).

    Two build paths, chosen by the parquet footers' uncompressed-size
    estimate against `memory_budget_bytes`:

    - **in-memory** (fits): one host decode, one fused device
      exchange+sort returning just the row permutation, one host gather,
      threaded per-bucket write;
    - **streaming** (doesn't fit): the out-of-core pipeline the reference
      gets from Spark's pipelined scan (actions/CreateActionBase.scala:
      99-120 scans sources of any size) — chunked row-group decode
      (prefetch-overlapped) → per-chunk host bucket partition → per-bucket
      spill row groups → batched device key-sort per bucket → final files.
      Host memory is bounded by `chunk_bytes`, never the source size.
    """

    def __init__(
        self,
        mesh: Mesh | None = None,
        capacity_factor: float = 2.0,
        memory_budget_bytes: int | None = None,
        chunk_bytes: int | None = None,
        venue: str = DEFAULT_VENUE,
        pipeline_enabled: bool = True,
        pipeline_max_inflight_bytes: int = 0,
        workers: int = 0,
        exchange_dir: str | None = None,
    ):
        self._mesh = mesh
        self.capacity_factor = capacity_factor
        if memory_budget_bytes is None:
            memory_budget_bytes = DEFAULT_BUILD_MEMORY_BUDGET
        self.memory_budget_bytes = memory_budget_bytes
        self.chunk_bytes = chunk_bytes or max(16 << 20, memory_budget_bytes // 8)
        self.venue = venue
        # Streaming-build pipeline (hyperspace.build.pipeline.*): False
        # restores the serial two-phase build — the byte-for-byte
        # reference the pipeline is verified against (bench.py --smoke).
        self.pipeline_enabled = pipeline_enabled
        self.pipeline_max_inflight_bytes = pipeline_max_inflight_bytes
        # Scale-out pooled build (hyperspace.build.workers, docs/
        # architecture.md "scale-out build"): 0 = in-process (the paths
        # above, unchanged); N > 0 splits the build across N spawned
        # worker processes exchanging rows through per-owner spill files
        # — byte-identical to the serial streaming reference.
        self.workers = int(workers)
        self.exchange_dir = str(exchange_dir) if exchange_dir else None
        self.last_build_stats: dict = {}
        self._last_phases: dict = {}
        enable_compile_cache()

    def _sort_venue(self) -> str:
        """Where the bucketize+sort permutation is computed: `device`
        (the all_to_all exchange and device sort) or `host` (the threaded
        C++ counting sort + per-bucket key sort, which needs the native
        library)."""
        if self.venue == "host":
            native.require("hyperspace.build.venue")
        return self.venue

    def _mesh_for(self, num_buckets: int) -> Mesh:
        # Shrink to the largest device count dividing num_buckets
        # (dropping any multi-slice structure — correctness first).
        from hyperspace_tpu.parallel.mesh import mesh_for_parallelism

        return mesh_for_parallelism(self._mesh, num_buckets)

    # -- IndexWriter -----------------------------------------------------
    def write(
        self,
        plan: LogicalPlan,
        columns: list[str],
        indexed_columns: list[str],
        num_buckets: int,
        dest_path: Path,
    ) -> None:
        if not isinstance(plan, Scan):
            raise HyperspaceError("index builds materialize scan-only plans")
        if plan.files is not None:
            files = list(plan.files)
        else:
            files = [fi.path for fi in list_data_files(plan.root, suffix=format_suffix(plan.format))]
        if self.workers > 0 and files:
            # Scale-out path: the pooled build IS a streaming build (it
            # exchanges through spill files), so it runs regardless of
            # the memory estimate and stays byte-identical to the serial
            # streaming reference at every source size.
            self._write_pooled(
                files, plan.scan_schema, columns, indexed_columns, num_buckets,
                dest_path, fmt=plan.format,
            )
            return
        if plan.format == "parquet":
            footers = hio.read_footers(files)
            est = hio.estimate_uncompressed_bytes(files, columns, footers=footers)
            if est > self.memory_budget_bytes:
                self._write_streaming(
                    files, plan.scan_schema, columns, indexed_columns, num_buckets,
                    dest_path, est, footers=footers,
                )
                return
        else:
            # Non-parquet sources: a rough on-disk-size inflate picks the
            # path; above the budget they stream too — CSV by record
            # batches, ORC by stripes, JSON at file granularity (pyarrow
            # has no incremental JSON reader, so the memory bound holds
            # per file there).
            import os

            est = sum(os.stat(f).st_size for f in files) * 4
            if est > self.memory_budget_bytes:
                self._write_streaming(
                    files, plan.scan_schema, columns, indexed_columns, num_buckets,
                    dest_path, est, fmt=plan.format,
                )
                return
        t0 = time.perf_counter()
        table = hio.read_table_files(files, plan.format, columns=columns, schema=plan.schema)
        t_decode = time.perf_counter() - t0
        self.write_table(table, indexed_columns, num_buckets, dest_path)
        phases = dict(self._last_phases)
        phases["decode"] = round(t_decode, 4)
        self.last_build_stats = {
            "path": "in-memory",
            "bytes_estimate": est,
            "rows": table.num_rows,
            "phases_s": phases,
        }

    def write_table(
        self,
        table: ColumnTable,
        indexed_columns: list[str],
        num_buckets: int,
        dest_path: Path,
    ) -> None:
        from hyperspace_tpu.ops.bucketize import bucketize_perm
        from hyperspace_tpu.ops.sortkeys import key_lanes

        mesh = self._mesh_for(num_buckets)
        d = mesh_size(mesh)
        n = table.num_rows
        t0 = time.perf_counter()

        # Host: bucket assignment from the canonical row hash.
        row_hash = compute_row_hashes(table, indexed_columns)
        bucket = bucket_ids(row_hash, num_buckets, np)

        # Host: decompose key columns into order-preserving 32-bit lanes
        # (ops/sortkeys.py — no np.unique rank pass; streaming-safe).
        # Payload bytes never touch the device: the exchange+sort emits a
        # row-id permutation and the host gathers columns by it.
        key_names = [table.schema.field(c).name for c in indexed_columns]
        lanes = key_lanes(table, indexed_columns)
        t_hash = time.perf_counter()

        sort_fn = None
        if self._sort_venue() == "host":
            # Host venue: C++ counting-sort by bucket now; each bucket's
            # key sort runs INSIDE its carve task (sort_fn) so sorting
            # pipelines with the parquet encode of other buckets — no
            # device round-trip (the permutation is the sort's only
            # output and it must land on host).
            from hyperspace_tpu.ops.sortkeys import lanes_as_unsigned

            order, bucket_rows = native.bucket_perm(bucket, num_buckets)
            lanes_u = lanes_as_unsigned(lanes)

            def sort_fn(p: int, sel: np.ndarray) -> np.ndarray:
                native.sort_range(sel, lanes_u)
                return sel
        else:
            # Pad rows to a multiple of the mesh size; rows past n are pads
            # (the device derives validity from the global row id).
            n_pad = max(d, math.ceil(max(n, 1) / d) * d)
            bucket_p = _pad_to(bucket, n_pad)
            lanes_p = [_pad_to(l, n_pad) for l in lanes]

            # Device: the exchange (Spark-shuffle analog, single all_to_all)
            # fused with the per-shard lex sort by (bucket, key lanes); ONE
            # int32-per-row readback (the permutation).
            order, bucket_rows = bucketize_perm(
                mesh, lanes_p, bucket_p, n, num_buckets, self.capacity_factor
            )
        if len(order) != n:
            raise HyperspaceError(
                f"row count changed through exchange: {n} → {len(order)}"
            )
        t_exchange = time.perf_counter()
        compact_bucket = np.repeat(
            np.arange(num_buckets, dtype=np.int32), bucket_rows
        )

        # Host: carve into per-bucket files, gathering each bucket's rows
        # by its slice of the permutation INSIDE the write threads (the
        # gather overlaps the parquet encode — and, host venue, the key
        # sort — of other buckets). Devices own contiguous bucket ranges
        # in mesh order and each shard is bucket-sorted, so the compacted
        # global bucket array is sorted.
        field_names = [f.name for f in table.schema.fields]
        payload_names = [c for c in field_names if c not in key_names]
        hio.carve_and_write(
            Path(dest_path), table.select(key_names + payload_names),
            compact_bucket, num_buckets, indexed_columns,
            order=order, sort_fn=sort_fn,
        )
        t_done = time.perf_counter()
        # Phase wall times. On the host venue the per-bucket KEY sort
        # runs inside the carve tasks (pipelined with parquet encode), so
        # it lands in carve_encode_write by design.
        self._last_phases = {
            "hash_lanes": round(t_hash - t0, 4),
            "partition_exchange": round(t_exchange - t_hash, 4),
            "carve_encode_write": round(t_done - t_exchange, 4),
        }

    # -- streaming out-of-core build -------------------------------------
    def _write_streaming(
        self,
        files: list[str],
        schema,
        columns: list[str],
        indexed_columns: list[str],
        num_buckets: int,
        dest_path: Path,
        est_bytes: int,
        footers=None,
        fmt: str = "parquet",
    ) -> None:
        import shutil
        from concurrent.futures import ThreadPoolExecutor

        import pyarrow.parquet as pq

        from hyperspace_tpu.ops.sortkeys import device_sort_perms

        dest = Path(dest_path)
        spill = dest.parent / (dest.name + ".spill")
        if spill.exists():
            shutil.rmtree(spill)
        spill.mkdir(parents=True, exist_ok=True)
        sub_schema = schema.select(columns)
        key_names = [sub_schema.field(c).name for c in indexed_columns]
        payload_names = [f.name for f in sub_schema.fields if f.name not in key_names]
        ordered = key_names + payload_names

        pipelined = self.pipeline_enabled
        writers: dict[int, pq.ParquetWriter] = {}
        spill_bytes: dict[int, int] = {}
        total_rows = 0
        n_chunks = 0
        pipe_info: dict | None = None
        try:
            # Phase 1: stream decoded chunks (format-aware iterator);
            # decode of chunk i+1 overlaps the hash/partition/spill of
            # chunk i via the one-ahead prefetcher. Pipelined mode also
            # fans the per-bucket spill encodes of chunk i out to pool
            # workers (waiting out chunk i−1's first, so per-bucket write
            # order stays chunk order and host memory stays ≤ two
            # chunks) — decode ‖ hash ‖ encode instead of decode ‖ rest.
            t_p1 = time.perf_counter()
            decode_wait = 0.0
            gen = _prefetched(
                self._decoded_chunks(files, fmt, columns, schema, footers=footers)
            )
            _SENTINEL = object()
            def _encode_chunk(parts: list) -> None:
                # One pool task per CHUNK (not per bucket): per-bucket
                # futures cost more churn than the encodes they cover.
                with obs_trace.span("build.p1.spill", parts=len(parts)):
                    for w, part in parts:
                        w.write_table(part)

            _encode_chunk_w = obs_trace.wrap(_encode_chunk)
            with ThreadPoolExecutor(max_workers=2) as p1_pool:
                spill_fut = None
                while True:
                    tw = time.perf_counter()
                    at = next(gen, _SENTINEL)
                    decode_wait += time.perf_counter() - tw
                    if at is _SENTINEL:
                        break
                    n_chunks += 1
                    ct = ColumnTable.from_arrow(at, sub_schema).select(ordered)
                    total_rows += ct.num_rows
                    bucket = bucket_ids(
                        compute_row_hashes(ct, indexed_columns), num_buckets, np
                    )
                    order = np.argsort(bucket, kind="stable")
                    sb = bucket[order]
                    starts = np.searchsorted(sb, np.arange(num_buckets + 1))
                    arrow_sorted = ct.take(order).to_arrow()
                    parts: list = []
                    for b in range(num_buckets):
                        lo, hi = int(starts[b]), int(starts[b + 1])
                        if hi <= lo:
                            continue
                        w = writers.get(b)
                        if w is None:
                            # Spill is engine-private scratch: the cheap codec
                            # (see io.INDEX_WRITE_COMPRESSION) beats snappy on
                            # encode CPU, which bounds phase 1 on small hosts,
                            # and dictionary encoding stays strings-only for
                            # the same reason write_bucket's does.
                            w = pq.ParquetWriter(
                                spill / hio.bucket_file_name(b),
                                arrow_sorted.schema,
                                compression=hio.INDEX_WRITE_COMPRESSION,
                                # Stats skipped like write_bucket's: spill
                                # footers are only read for sizes.
                                write_statistics=False,
                                use_dictionary=[
                                    f.name for f in sub_schema.select(ordered).fields if f.is_string
                                ],
                            )
                            writers[b] = w
                        part = arrow_sorted.slice(lo, hi - lo)
                        # Decoded-size ledger: the pipeline's p2 window
                        # admits buckets by these bytes, so no spill
                        # footer is ever re-opened (io.footer_cache
                        # dedupes the rest).
                        spill_bytes[b] = spill_bytes.get(b, 0) + part.nbytes
                        parts.append((w, part))
                    if pipelined:
                        # Waiting out chunk i−1 HERE (after chunk i's
                        # hash/partition) keeps per-writer chunk order —
                        # the spill bytes stay identical to the serial
                        # path's — while chunk i−1's encode overlapped
                        # this chunk's decode and hash.
                        if spill_fut is not None:
                            spill_fut.result()
                        spill_fut = p1_pool.submit(_encode_chunk_w, parts)
                    else:
                        for w, part in parts:
                            w.write_table(part)
                if spill_fut is not None:
                    spill_fut.result()
            if not pipelined:
                for w in writers.values():
                    w.close()
            t_p2 = time.perf_counter()

            # Phase 2. Pipelined: writer closes feed a bounded
            # bucket-completion queue; spill-read of bucket b+1 overlaps
            # the key sort of b overlaps the final write of b−1 (see
            # _p2_pipelined). Serial: the original batched two-step.
            dest.mkdir(parents=True, exist_ok=True)
            bucket_rows = [0] * num_buckets
            key_stats: list = [None] * num_buckets
            col_stats: list = [None] * num_buckets
            stat_cols = [
                f.name
                for f in sub_schema.select(ordered).fields
                if not f.is_vector and f.name != sub_schema.field(indexed_columns[0]).name
            ]
            sort_venue = self._sort_venue()
            if pipelined:
                pipe_info = self._p2_pipelined(
                    writers, spill, spill_bytes, dest, sub_schema, ordered,
                    indexed_columns, num_buckets, stat_cols, sort_venue,
                    bucket_rows, key_stats, col_stats,
                )
            else:
                # Batches are planned from the SPILL FOOTERS (uncompressed
                # bytes per bucket), so at most ~chunk_bytes of bucket data
                # is resident at once — the memory bound holds end to end,
                # not just in phase 1. Within a batch, reads and writes are
                # threaded; the sort is one device call.
                spill_files = {
                    b: str(spill / hio.bucket_file_name(b))
                    for b in range(num_buckets)
                    if (spill / hio.bucket_file_name(b)).exists()
                }
                spill_footers = hio.read_footers(list(spill_files.values()))
                bucket_bytes = {
                    b: hio.estimate_uncompressed_bytes([p], footers={p: spill_footers[p]})
                    for b, p in spill_files.items()
                }
                batches: list[list[int]] = []
                cur: list[int] = []
                cur_bytes = 0
                for b in sorted(spill_files):
                    if cur and cur_bytes + bucket_bytes[b] > self.chunk_bytes:
                        batches.append(cur)
                        cur, cur_bytes = [], 0
                    cur.append(b)
                    cur_bytes += bucket_bytes[b]
                if cur:
                    batches.append(cur)

                with ThreadPoolExecutor(max_workers=8) as pool:
                    empty = ColumnTable.empty(sub_schema.select(ordered))
                    for b in range(num_buckets):
                        if b not in spill_files:
                            hio.write_bucket(dest, b, empty)
                    for ids in batches:
                        tables = list(pool.map(lambda b: hio.read_parquet([spill_files[b]]), ids))
                        if sort_venue == "host":
                            perms = _host_sort_perms(tables, indexed_columns)
                        else:
                            perms = device_sort_perms(tables, indexed_columns)
                        futs = [
                            pool.submit(hio.write_bucket, dest, b, t.take(p))
                            for b, t, p in zip(ids, tables, perms)
                        ]
                        for b, t in zip(ids, tables):
                            bucket_rows[b] = t.num_rows
                            key_stats[b] = hio.bucket_key_stats(t, indexed_columns[0])
                            if stat_cols:
                                col_stats[b] = hio.bucket_column_stats(t, stat_cols)
                        for f in futs:
                            f.result()
            hio.write_manifest(
                dest, num_buckets, indexed_columns, bucket_rows,
                key_stats if any(s is not None for s in key_stats) else None,
                col_stats if any(s is not None for s in col_stats) else None,
            )
        finally:
            shutil.rmtree(spill, ignore_errors=True)
        t_end = time.perf_counter()
        self.last_build_stats = {
            "path": "streaming",
            "format": fmt,
            "bytes_estimate": est_bytes,
            "chunks": n_chunks,
            "rows": total_rows,
            # Phase walls: p1 = decode→hash→partition→spill (decode_wait
            # is the NON-overlapped decode stall inside it — the prefetch
            # hides the rest); p2 = spill read→key sort→final write
            # (pipelined mode overlaps its stages AND the writer closes,
            # so p2 here is the OVERLAPPED wall, not a sum of stages).
            "phases_s": {
                "p1_decode_hash_spill": round(t_p2 - t_p1, 4),
                "p1_decode_wait": round(decode_wait, 4),
                "p2_sort_encode_write": round(t_end - t_p2, 4),
            },
        }
        if pipe_info is not None:
            self.last_build_stats["pipeline"] = pipe_info

    # -- scale-out pooled build ------------------------------------------
    def _exchange_root(self, dest: Path) -> Path:
        """Where this build's cross-process spill exchange lives:
        `hyperspace.build.exchange.dir` (suffixed with the dest name so
        concurrent builds never collide), or `<dest>.exchange` next to
        the version dir (same filesystem as the output)."""
        if self.exchange_dir:
            return Path(self.exchange_dir) / f"{dest.parent.name}-{dest.name}.exchange"
        return dest.parent / (dest.name + ".exchange")

    def _write_pooled(
        self,
        files: list[str],
        schema,
        columns: list[str],
        indexed_columns: list[str],
        num_buckets: int,
        dest_path: Path,
        fmt: str = "parquet",
    ) -> None:
        """The scale-out build (docs/architecture.md "scale-out build"):
        bucket id → owner is the shard key, spill files are the
        cross-process exchange format, and the only things crossing the
        process boundary are paths plus the decoded-byte ledger.

        - **p1** — ≤ `workers` shard processes, each decoding a disjoint
          *contiguous* slice of the input files, hashing/partitioning
          rows, and appending per-bucket spill parquet into the
          destination owners' exchange dirs (build_exchange.p1_shard);
        - **p2** — ≤ min(workers, num_buckets) owner processes, each
          reading its buckets' spill in shard order (reproducing the
          global row order), key-sorting, and writing the final bucket
          files + stats in parallel (build_exchange.p2_owner);
        - **coordinator** — slices files, babysits the pools (a dead
          worker is a typed WorkerCrashed abort, never a hang), merges
          the per-owner manifest stats, and writes the manifest. The
          surrounding Action 2-phase protocol is untouched, so commit
          semantics — and the output bytes — match the in-process
          streaming build exactly.

        The exchange dir is swept in `finally`, success or abort."""
        import os
        import shutil

        from hyperspace_tpu import stats
        from hyperspace_tpu.execution import build_exchange as bx
        from hyperspace_tpu.parallel.procpool import TaskPool

        dest = Path(dest_path)
        exchange = self._exchange_root(dest)
        if exchange.exists():
            shutil.rmtree(exchange)
        exchange.mkdir(parents=True, exist_ok=True)
        try:
            sizes = [os.stat(f).st_size for f in files]
        except OSError:
            sizes = [1] * len(files)
        slices = bx.slice_files(files, sizes, self.workers)
        n_shards = len(slices)
        num_owners = max(1, min(self.workers, num_buckets))
        # Per-owner one-ahead read window: the same maxInflightBytes
        # budget the p2 pipeline uses, fed from p1's decoded-byte ledger.
        window = self.pipeline_max_inflight_bytes or max(1, 4 * self.chunk_bytes)
        total_rows = 0
        n_chunks = 0
        spill_bytes: dict[int, int] = {}
        try:
            t0 = time.perf_counter()
            with obs_trace.span("build.pool.p1", shards=n_shards):
                with TaskPool("hs-build-p1") as pool:
                    for w, slc in enumerate(slices):
                        fault_point("build.worker.spawn", str(exchange))
                        pool.submit(w, bx.p1_shard, bx.P1Task(
                            worker=w, files=slc, fmt=fmt, columns=list(columns),
                            schema=schema, indexed_columns=list(indexed_columns),
                            num_buckets=num_buckets, num_owners=num_owners,
                            chunk_bytes=self.chunk_bytes,
                            memory_budget_bytes=self.memory_budget_bytes,
                            exchange_dir=str(exchange),
                        ))
                        _MET_POOL_WORKERS.set(w + 1)
                    p1 = pool.join()
            _MET_POOL_WORKERS.set(0)
            for _, res in sorted(p1.items()):
                total_rows += res["rows"]
                n_chunks += res["chunks"]
                for b, nb in res["spill_bytes"].items():
                    spill_bytes[b] = spill_bytes.get(b, 0) + nb
            exchange_bytes = sum(spill_bytes.values())
            stats.increment("build.exchange.bytes", exchange_bytes)
            t_p2 = time.perf_counter()

            dest.mkdir(parents=True, exist_ok=True)
            with obs_trace.span("build.pool.p2", owners=num_owners):
                with TaskPool("hs-build-p2") as pool:
                    for o in range(num_owners):
                        fault_point("build.worker.spawn", str(exchange))
                        pool.submit(o, bx.p2_owner, bx.P2Task(
                            owner=o, num_owners=num_owners, n_shards=n_shards,
                            num_buckets=num_buckets, exchange_dir=str(exchange),
                            dest_dir=str(dest), columns=list(columns),
                            schema=schema, indexed_columns=list(indexed_columns),
                            spill_bytes={
                                b: nb for b, nb in spill_bytes.items()
                                if bx.owner_of(b, num_owners) == o
                            },
                            window_bytes=window,
                        ))
                        _MET_POOL_WORKERS.set(o + 1)
                    p2 = pool.join()
            _MET_POOL_WORKERS.set(0)

            fault_point("build.manifest.merge", str(dest))
            bucket_rows = [0] * num_buckets
            key_stats: list = [None] * num_buckets
            col_stats: list = [None] * num_buckets
            for _, res in sorted(p2.items()):
                for b, r in res["bucket_rows"].items():
                    bucket_rows[b] = r
                for b, s in res["key_stats"].items():
                    key_stats[b] = s
                for b, s in res["col_stats"].items():
                    col_stats[b] = s
            hio.write_manifest(
                dest, num_buckets, indexed_columns, bucket_rows,
                key_stats if any(s is not None for s in key_stats) else None,
                col_stats if any(s is not None for s in col_stats) else None,
            )
            t_end = time.perf_counter()
        finally:
            _MET_POOL_WORKERS.set(0)
            shutil.rmtree(exchange, ignore_errors=True)
        self.last_build_stats = {
            "path": "pooled",
            "format": fmt,
            "workers": self.workers,
            "p1_shards": n_shards,
            "p2_owners": num_owners,
            "rows": total_rows,
            "chunks": n_chunks,
            "exchange_bytes": exchange_bytes,
            "phases_s": {
                "p1_decode_hash_spill": round(t_p2 - t0, 4),
                "p2_sort_encode_write": round(t_end - t_p2, 4),
            },
        }

    def _p2_pipelined(
        self,
        writers,
        spill: Path,
        spill_bytes: dict[int, int],
        dest: Path,
        sub_schema,
        ordered: list[str],
        indexed_columns: list[str],
        num_buckets: int,
        stat_cols: list[str],
        sort_venue: str,
        bucket_rows: list,
        key_stats: list,
        col_stats: list,
    ) -> dict:
        """The 3-stage phase-2 pipeline behind a bounded bucket-completion
        queue: writer CLOSES fan out to the pool and feed the queue as
        they land, the reader admits buckets under a byte-budgeted
        in-flight window and decodes them (`spill.read`), the sort stage
        (this thread) computes each bucket's key permutation, and write
        tasks gather+encode the final file — so the spill read of bucket
        b+1 overlaps the key sort of b overlaps the parquet write of b−1,
        and the first reads overlap the remaining closes (the only
        p1→p2 order that hash partitioning permits: every bucket needs
        every chunk). Crash-safe: reader failures re-raise on this
        thread via the error sentinel, writers release their window bytes
        in `finally`, and the stop flag unblocks a parked reader, so the
        spill dir's cleanup (caller's `finally`) always runs.

        Mutates bucket_rows/key_stats/col_stats in place (distinct slots
        per bucket) and returns the pipeline telemetry dict."""
        import queue as _queue
        import threading
        from concurrent.futures import ThreadPoolExecutor

        from hyperspace_tpu.ops.sortkeys import device_sort_perms

        # The window covers buckets across ALL THREE stages (a bucket's
        # bytes release only when its final write lands), so it needs
        # headroom beyond one sort batch or the reader starves.
        window = self.pipeline_max_inflight_bytes or max(1, 4 * self.chunk_bytes)
        cv = threading.Condition()
        inflight = {"bytes": 0}
        stop = [False]
        ready: "_queue.Queue" = _queue.Queue()  # bucket ids whose spill writer closed
        sortq: "_queue.Queue" = _queue.Queue()  # (bucket, table, nbytes) | _DONE | _ERR
        _DONE, _ERR = object(), object()
        busy = {"read": 0.0, "sort": 0.0, "write": 0.0}
        busy_lock = threading.Lock()
        max_depth = [0]
        spill_ids = sorted(writers)
        n_spilled = len(spill_ids)

        def close_one(b: int) -> None:
            try:
                writers[b].close()
            finally:
                # Enqueue even on a failed close: the reader's decode of
                # the torn spill file surfaces the error (never a hang).
                ready.put(b)

        def read_loop() -> None:
            try:
                for _ in range(n_spilled):
                    b = ready.get()
                    if stop[0]:
                        return
                    nb = max(1, spill_bytes.get(b, 1))
                    with cv:
                        while not stop[0] and inflight["bytes"] > 0 and inflight["bytes"] + nb > window:
                            cv.wait()
                        if stop[0]:
                            return
                        inflight["bytes"] += nb
                    path = str(spill / hio.bucket_file_name(b))
                    fault_point("spill.read", path)
                    t0 = time.perf_counter()
                    with obs_trace.span("build.p2.read", bucket=b, bytes=nb):
                        t = hio.read_parquet([path])
                    with busy_lock:
                        busy["read"] += time.perf_counter() - t0
                    fault_point("pipeline.put", path)
                    sortq.put((b, t, nb))
                    d = sortq.qsize()
                    _MET_QDEPTH.observe(d)
                    if d > max_depth[0]:
                        max_depth[0] = d
            except BaseException:
                sortq.put(_ERR)
                raise
            sortq.put(_DONE)

        def write_one(b: int, t: ColumnTable, perm: np.ndarray, nb: int) -> None:
            try:
                t0 = time.perf_counter()
                with obs_trace.span("build.p2.write", bucket=b):
                    # Manifest stats ride the write stage (min/max is
                    # permutation-invariant, so computing them pre-gather
                    # matches the serial path exactly) — they parallelize
                    # across write workers instead of serializing the
                    # sort stage.
                    bucket_rows[b] = t.num_rows
                    key_stats[b] = hio.bucket_key_stats(t, indexed_columns[0])
                    if stat_cols:
                        col_stats[b] = hio.bucket_column_stats(t, stat_cols)
                    hio.write_bucket(dest, b, t.take(perm))
                with busy_lock:
                    busy["write"] += time.perf_counter() - t0
            finally:
                with cv:
                    inflight["bytes"] -= nb
                    cv.notify_all()

        t_start = time.perf_counter()
        wfuts: list = []
        with ThreadPoolExecutor(max_workers=8) as pool:
            empty = ColumnTable.empty(sub_schema.select(ordered))
            for b in range(num_buckets):
                if b not in writers:
                    wfuts.append(pool.submit(obs_trace.wrap(hio.write_bucket), dest, b, empty))
            for b in spill_ids:
                pool.submit(obs_trace.wrap(close_one), b)
            rfut = pool.submit(obs_trace.wrap(read_loop))
            try:
                sentinel = None
                while sentinel is None:
                    fault_point("pipeline.get")
                    item = sortq.get()
                    if item is _DONE or item is _ERR:
                        break
                    # Micro-batch: drain whatever the reader has already
                    # staged (≤8 buckets) into ONE device sort call. Each
                    # table pads and sorts independently inside the batch
                    # (ops/sortkeys.device_sort_perms), so every bucket's
                    # permutation is identical whatever batch it lands in
                    # — batching amortizes dispatch, never changes bytes.
                    batch = [item]
                    while len(batch) < 8:
                        try:
                            nxt = sortq.get_nowait()
                        except _queue.Empty:
                            break
                        if nxt is _DONE or nxt is _ERR:
                            sentinel = nxt
                            break
                        batch.append(nxt)
                    ts = [t for _, t, _ in batch]
                    t0 = time.perf_counter()
                    with obs_trace.span(
                        "build.p2.sort", buckets=len(batch), rows=sum(t.num_rows for t in ts)
                    ):
                        if sort_venue == "host":
                            perms = _host_sort_perms(ts, indexed_columns)
                        else:
                            perms = device_sort_perms(ts, indexed_columns)
                    busy["sort"] += time.perf_counter() - t0
                    for (b, t, nb), perm in zip(batch, perms):
                        wfuts.append(pool.submit(obs_trace.wrap(write_one), b, t, perm, nb))
                item = sentinel if sentinel is not None else item
                if item is _ERR:
                    rfut.result()  # re-raises the reader's failure here
            finally:
                with cv:
                    stop[0] = True
                    cv.notify_all()
            for f in wfuts:
                f.result()
        wall = time.perf_counter() - t_start
        occ = 0.0
        if wall > 0:
            occ = sum(min(v, wall) for v in busy.values()) / (3 * wall)
        _MET_OCCUPANCY.set(round(occ, 4))
        return {
            "occupancy": round(occ, 4),
            "max_queue_depth": max_depth[0],
            "window_bytes": window,
            "stage_busy_s": {k: round(v, 4) for k, v in busy.items()},
        }

    def _decoded_chunks(self, files, fmt: str, columns, schema, footers=None):
        """Yield pyarrow Tables of ≤ ~chunk_bytes decoded source data —
        the shared format-aware chunked decode in build_exchange.py (the
        pooled build's p1 shard workers drive the same generator over
        their own file slices)."""
        from hyperspace_tpu.execution.build_exchange import decoded_chunks

        yield from decoded_chunks(
            files, fmt, columns, schema,
            self.chunk_bytes, self.memory_budget_bytes, footers=footers,
        )

    # -- OptimizeAction's compactor seam ---------------------------------
    def compact(self, entry, src_paths: list[Path] | Path, dest_path: Path) -> None:
        """Merge all files of each bucket across every live version dir
        (base + incremental-refresh deltas) into one sorted file per bucket
        in the new version dir. Indexes too large for the in-memory path
        compact through the same streaming pipeline that built them."""
        from hyperspace_tpu.schema import Schema

        num_buckets = entry.derived_dataset.num_buckets
        indexed = entry.derived_dataset.indexed_columns
        if isinstance(src_paths, (str, Path)):
            src_paths = [src_paths]
        files = [fi.path for src in src_paths for fi in list_data_files(src)]
        footers = hio.read_footers(files)
        est = hio.estimate_uncompressed_bytes(files, footers=footers)
        if est > self.memory_budget_bytes:
            import pyarrow.parquet as pq

            schema = Schema.from_arrow(pq.ParquetFile(files[0]).schema_arrow)
            self._write_streaming(
                files, schema, list(schema.names), indexed, num_buckets,
                dest_path, est, footers=footers,
            )
            return
        table = hio.read_parquet(files)
        self.write_table(table, indexed, num_buckets, dest_path)
