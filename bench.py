"""Benchmark: TPC-H SF1 lineitem point-lookup, indexed vs un-indexed.

The BASELINE.json config 1 analog ("TPC-H SF1 lineitem single-column
CoveringIndex + FilterIndexRule point-lookup") on the REAL SF1 scale:
6,001,215-row lineitem with the full 16-column TPC-H schema (strings,
dates, decimals), generated deterministically and cached under the system
tmp dir. Builds a covering index on l_orderkey, then times point-lookup
queries with hyperspace enabled (bucket-pruned sorted index scan) vs
disabled (full scan + device filter). Prints ONE JSON line:

    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

vs_baseline normalizes against the driver's ≥5× query-speedup target
(BASELINE.md). Auxiliary numbers (build GB/s/chip at two scales — the
throughput curve) go to stderr.
"""

from __future__ import annotations

import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np


def log(*args):
    print(*args, file=sys.stderr, flush=True)


INDEXED = ["l_orderkey"]
INCLUDED = ["l_partkey", "l_quantity", "l_extendedprice", "l_discount"]


def build_once(session_path: Path, data_root: Path, num_buckets: int):
    from hyperspace_tpu import Hyperspace, HyperspaceSession, IndexConfig
    from hyperspace_tpu.execution import io as hio
    from hyperspace_tpu.dataset import list_data_files

    session = HyperspaceSession(system_path=str(session_path), num_buckets=num_buckets)
    hs = Hyperspace(session)
    df = session.parquet(data_root)
    files = [fi.path for fi in list_data_files(data_root)]
    sel_bytes = hio.estimate_uncompressed_bytes(files, INDEXED + INCLUDED)
    t0 = time.perf_counter()
    hs.create_index(df, IndexConfig("lineitem_orderkey", INDEXED, INCLUDED))
    build_s = time.perf_counter() - t0
    phases = session.last_build_stats.get("phases_s")
    if phases:
        log(f"  build phases (s): {phases}")
    return session, hs, df, sel_bytes, build_s


def main():
    import jax

    from hyperspace_tpu import col
    from benchmarks.datagen import cached_tpch, gen_tpch_lineitem, TPCH_SF1_ORDERS_ROWS

    devices = jax.devices()
    log(f"devices: {devices}")

    li_root, _orders_root = cached_tpch(sf=1.0)
    tmp = Path(tempfile.mkdtemp(prefix="hs_bench_"))
    try:
        # ---- GB/s curve point at SF0.1 (amortization evidence) ---------
        small = tmp / "li_small"
        gen_tpch_lineitem(small, sf=0.1)
        _, _, _, sb, bs = build_once(tmp / "idx_small", small, 64)
        log(f"build sf=0.1: {bs:.2f}s -> {sb/1e9/bs:.3f} GB/s/chip (selected cols)")

        # ---- SF1 build --------------------------------------------------
        session, hs, df, sel_bytes, build_s = build_once(tmp / "indexes", li_root, 200)
        gbps = sel_bytes / 1e9 / build_s
        log(f"build sf=1:   {build_s:.2f}s -> {gbps:.3f} GB/s/chip (selected cols, ~6.0M rows)")

        # ---- point lookups ---------------------------------------------
        rng = np.random.default_rng(7)
        keys = rng.integers(0, TPCH_SF1_ORDERS_ROWS, 12).astype(np.int64)

        def run_lookups():
            total = 0
            for k in keys:
                q = df.filter(col("l_orderkey") == int(k)).select(
                    "l_orderkey", "l_partkey", "l_extendedprice"
                )
                total += len(session.run(q).columns["l_orderkey"])
            return total

        session.enable_hyperspace()
        run_lookups()  # warmup (compile)
        t0 = time.perf_counter()
        rows_idx = run_lookups()
        t_indexed = time.perf_counter() - t0

        session.disable_hyperspace()
        run_lookups()  # warmup
        t0 = time.perf_counter()
        rows_no = run_lookups()
        t_noindex = time.perf_counter() - t0

        assert rows_idx == rows_no, f"result mismatch: {rows_idx} vs {rows_no}"
        assert rows_idx > 0, "lookups matched nothing"
        speedup = t_noindex / t_indexed
        log(f"indexed: {t_indexed:.3f}s  no-index: {t_noindex:.3f}s  speedup: {speedup:.2f}x")

        # Real per-query profiles (docs/observability.md): one
        # representative lookup per mode, written alongside the headline
        # metric so the perf trajectory carries measured operator
        # evidence (wall per operator, files/bytes, cache outcomes)
        # rather than a single number.
        q = df.filter(col("l_orderkey") == int(keys[0])).select(
            "l_orderkey", "l_partkey", "l_extendedprice"
        )
        session.enable_hyperspace()
        session.run(q)
        profile_indexed = session.last_profile().to_json()
        session.disable_hyperspace()
        session.run(q)
        profile_noindex = session.last_profile().to_json()

        headline = {
            "metric": "tpch_sf1_point_lookup_speedup",
            "value": round(speedup, 3),
            "unit": "x",
            "vs_baseline": round(speedup / 5.0, 3),
        }
        Path("BENCH_PROFILES.json").write_text(
            json.dumps(
                {
                    **headline,
                    "indexed_s": round(t_indexed, 4),
                    "no_index_s": round(t_noindex, 4),
                    "profiles": {
                        "point_lookup_indexed": profile_indexed,
                        "point_lookup_no_index": profile_noindex,
                    },
                },
                indent=1,
                default=str,
            )
        )
        log("wrote BENCH_PROFILES.json (per-operator profiles, both modes)")

        print(json.dumps(headline))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def smoke(out_path: str = "BENCH_PIPELINE.json") -> int:
    """Build-pipeline smoke (the CI `build-pipeline` job): build a small
    synthetic table through the streaming path twice — serial
    (`pipeline_enabled=False`, the phase-accounting reference) and
    pipelined — assert the index is byte-for-byte identical, and gate
    the pipelined wall against 0.9 x (p1 + p2) of the serial run.

    The wall gate only binds on hosts with >= 2 schedulable CPUs: on a
    single CPU every stage timeshares one core, both paths saturate it,
    and wall ratios measure the box, not the pipeline — there the
    overlap evidence is the recorded per-stage busy sum vs the p2 wall
    (overlap_factor > 1 means stages genuinely ran concurrently)."""
    import os

    from hyperspace_tpu import native
    from hyperspace_tpu.dataset import Dataset
    from hyperspace_tpu.execution import io as hio
    from hyperspace_tpu.execution.builder import DeviceIndexBuilder
    from hyperspace_tpu.parallel.mesh import make_mesh

    rng = np.random.default_rng(11)
    num_buckets = 32
    n, files = 600_000, 3
    tmp = Path(tempfile.mkdtemp(prefix="hs_pipe_"))
    try:
        import pyarrow as pa
        import pyarrow.parquet as pq

        root = tmp / "src"
        root.mkdir()
        per = n // files
        for i in range(files):
            k = rng.integers(0, 10**9, per).astype(np.int64)
            pq.write_table(
                pa.table(
                    {
                        "k": k,
                        "s": pa.array([f"s{j % 37:02d}" for j in range(per)]),
                        "v": rng.standard_normal(per),
                    }
                ),
                root / f"p{i}.parquet",
                row_group_size=20_000,
            )
        ds = Dataset.parquet(root)
        mesh = make_mesh()
        # The host sort venue when the native kernel is available
        # (identical permutations either venue — the comparison is
        # venue-neutral).
        venue = "host" if native.available() else "device"
        kw = dict(
            mesh=mesh, memory_budget_bytes=400_000, chunk_bytes=600_000, venue=venue
        )

        # Best-of-2 per path: shared-runner noise easily exceeds the
        # margin under test; the min is the honest "what the code costs"
        # number for both sides of the ratio.
        serial = DeviceIndexBuilder(pipeline_enabled=False, **kw)
        d_serial = tmp / "idx_serial" / "v__=0"
        serial_wall, phases = None, None
        for _ in range(2):
            t0 = time.perf_counter()
            serial.write(ds.scan(), ["k", "s", "v"], ["k"], num_buckets, d_serial)
            w = time.perf_counter() - t0
            if serial_wall is None or w < serial_wall:
                serial_wall, phases = w, serial.last_build_stats["phases_s"]
        p1, p2 = phases["p1_decode_hash_spill"], phases["p2_sort_encode_write"]
        assert serial.last_build_stats["path"] == "streaming"

        pipe = DeviceIndexBuilder(pipeline_enabled=True, **kw)
        d_pipe = tmp / "idx_pipe" / "v__=0"
        pipe_wall, pipe_stats = None, None
        for _ in range(2):
            t0 = time.perf_counter()
            pipe.write(ds.scan(), ["k", "s", "v"], ["k"], num_buckets, d_pipe)
            w = time.perf_counter() - t0
            if pipe_wall is None or w < pipe_wall:
                pipe_wall, pipe_stats = w, dict(pipe.last_build_stats)
        pinfo = pipe_stats.get("pipeline", {})

        identical = hio.read_manifest(d_serial) == hio.read_manifest(d_pipe) and all(
            (d_serial / hio.bucket_file_name(b)).read_bytes()
            == (d_pipe / hio.bucket_file_name(b)).read_bytes()
            for b in range(num_buckets)
        )
        assert identical, "pipelined index differs from the serial reference"

        busy = pinfo.get("stage_busy_s", {})
        p2_pipe = pipe_stats["phases_s"]["p2_sort_encode_write"]
        overlap_factor = round(sum(busy.values()) / p2_pipe, 3) if p2_pipe else None
        cpus = len(os.sched_getaffinity(0))
        ratio = round(pipe_wall / (p1 + p2), 3)
        gate = "enforced" if cpus >= 2 else "skipped-single-cpu"
        result = {
            "metric": "build_pipeline_overlap_ratio",
            "value": ratio,
            "unit": "x (pipelined wall / serial p1+p2; < 1 is overlap)",
            "serial": {"wall_s": round(serial_wall, 4), "p1_s": p1, "p2_s": p2},
            "pipelined": {
                "wall_s": round(pipe_wall, 4),
                "phases_s": pipe_stats["phases_s"],
                "pipeline": pinfo,
                "overlap_factor": overlap_factor,
            },
            "identical_index_bytes": identical,
            "rows": n,
            "num_buckets": num_buckets,
            "venue": venue,
            "cpus": cpus,
            "gate": gate,
        }
        Path(out_path).write_text(json.dumps(result, indent=1) + "\n")
        log(f"wrote {out_path}: ratio={ratio} (p1={p1}s p2={p2}s pipe={pipe_wall:.3f}s "
            f"overlap_factor={overlap_factor} cpus={cpus} gate={gate})")
        print(json.dumps({k: result[k] for k in ("metric", "value", "unit", "gate")}))
        if gate == "enforced" and ratio >= 0.9:
            log(f"FAIL: pipelined wall {pipe_wall:.3f}s >= 0.9 x (p1+p2) = {0.9*(p1+p2):.3f}s")
            return 1
        return 0
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def scaleout_smoke(out_path: str = "BENCH_SCALEOUT.json", workers: int = 2) -> int:
    """Scale-out build smoke (the CI `build-scaleout` job): build a small
    synthetic table three ways — serial streaming reference
    (`pipeline_enabled=False`), pooled with ONE worker process, pooled
    with `workers` processes — assert all three indexes are byte-for-byte
    identical, and gate the N-worker wall against the 1-worker wall.

    Like BENCH_PIPELINE, the wall/GB/s scaling gate only binds on hosts
    with >= 2 schedulable CPUs: on one CPU, N worker processes timeshare
    one core and the wall ratio measures the box, not the sharding —
    there the run is recorded informational (`cpus` field) while the
    identical-bytes gate is ALWAYS enforced."""
    import os

    from hyperspace_tpu.dataset import Dataset
    from hyperspace_tpu.execution import io as hio
    from hyperspace_tpu.execution.builder import DeviceIndexBuilder

    rng = np.random.default_rng(11)
    num_buckets = 32
    n, files = 600_000, 4
    tmp = Path(tempfile.mkdtemp(prefix="hs_scaleout_"))
    try:
        import pyarrow as pa
        import pyarrow.parquet as pq

        root = tmp / "src"
        root.mkdir()
        per = n // files
        for i in range(files):
            k = rng.integers(0, 10**9, per).astype(np.int64)
            pq.write_table(
                pa.table(
                    {
                        "k": k,
                        "s": pa.array([f"s{j % 37:02d}" for j in range(per)]),
                        "v": rng.standard_normal(per),
                    }
                ),
                root / f"p{i}.parquet",
                row_group_size=20_000,
            )
        ds = Dataset.parquet(root)
        sel_bytes = hio.estimate_uncompressed_bytes(
            sorted(str(p) for p in root.glob("*.parquet")), ["k", "s", "v"]
        )
        kw = dict(memory_budget_bytes=400_000, chunk_bytes=600_000)

        serial = DeviceIndexBuilder(pipeline_enabled=False, **kw)
        d_serial = tmp / "idx_serial" / "v__=0"
        serial.write(ds.scan(), ["k", "s", "v"], ["k"], num_buckets, d_serial)
        assert serial.last_build_stats["path"] == "streaming"

        def pooled_build(w: int, dest: Path):
            """Best-of-2 wall (shared-runner noise exceeds the margin)."""
            wall, stats_ = None, None
            b = DeviceIndexBuilder(workers=w, **kw)
            for _ in range(2):
                t0 = time.perf_counter()
                b.write(ds.scan(), ["k", "s", "v"], ["k"], num_buckets, dest)
                e = time.perf_counter() - t0
                if wall is None or e < wall:
                    wall, stats_ = e, dict(b.last_build_stats)
            return wall, stats_

        d_one = tmp / "idx_w1" / "v__=0"
        wall_one, stats_one = pooled_build(1, d_one)
        d_n = tmp / f"idx_w{workers}" / "v__=0"
        wall_n, stats_n = pooled_build(workers, d_n)

        def identical(d_got):
            return hio.read_manifest(d_serial) == hio.read_manifest(d_got) and all(
                (d_serial / hio.bucket_file_name(b)).read_bytes()
                == (d_got / hio.bucket_file_name(b)).read_bytes()
                for b in range(num_buckets)
            )

        same = identical(d_one) and identical(d_n)
        assert same, "pooled index differs from the serial reference"

        cpus = len(os.sched_getaffinity(0))
        speedup = round(wall_one / wall_n, 3)
        gate = "enforced" if cpus >= 2 else "skipped-single-cpu"
        result = {
            "metric": "build_scaleout_speedup",
            "value": speedup,
            "unit": f"x (1-worker wall / {workers}-worker wall; > 1 is scaling)",
            "workers": workers,
            "serial": {
                "wall_phases_s": serial.last_build_stats["phases_s"],
            },
            "one_worker": {
                "wall_s": round(wall_one, 4),
                "gbps": round(sel_bytes / 1e9 / wall_one, 4),
                "phases_s": stats_one["phases_s"],
            },
            "n_workers": {
                "wall_s": round(wall_n, 4),
                "gbps": round(sel_bytes / 1e9 / wall_n, 4),
                "phases_s": stats_n["phases_s"],
                "p1_shards": stats_n["p1_shards"],
                "p2_owners": stats_n["p2_owners"],
                "exchange_bytes": stats_n["exchange_bytes"],
            },
            "identical_index_bytes": same,
            "rows": n,
            "num_buckets": num_buckets,
            "cpus": cpus,
            "gate": gate,
        }
        Path(out_path).write_text(json.dumps(result, indent=1) + "\n")
        log(f"wrote {out_path}: speedup={speedup}x (w1={wall_one:.3f}s "
            f"w{workers}={wall_n:.3f}s cpus={cpus} gate={gate})")
        print(json.dumps({k: result[k] for k in ("metric", "value", "unit", "gate")}))
        if gate == "enforced" and speedup < 1.1:
            log(f"FAIL: {workers}-worker wall {wall_n:.3f}s shows no scaling over "
                f"1-worker {wall_one:.3f}s on a {cpus}-CPU host")
            return 1
        return 0
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="build-pipeline smoke: serial vs pipelined streaming build "
                         "(with --workers: serial vs pooled scale-out build)")
    ap.add_argument("--workers", type=int, default=0,
                    help="with --smoke: run the scale-out smoke comparing a "
                         "1-worker pool against this many worker processes")
    ap.add_argument("--out", default=None,
                    help="artifact path for --smoke (default BENCH_PIPELINE.json, "
                         "or BENCH_SCALEOUT.json with --workers)")
    args = ap.parse_args()
    if args.smoke and args.workers > 0:
        sys.exit(scaleout_smoke(args.out or "BENCH_SCALEOUT.json", args.workers))
    if args.smoke:
        sys.exit(smoke(args.out or "BENCH_PIPELINE.json"))
    main()
