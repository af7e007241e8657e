"""Serving-plane benchmark: throughput + tail latency under concurrency.

Measures the QueryServer (docs/serving.md) at 1/4/16 concurrent clients
over a point-lookup workload of distinct plans, cold vs warm plan cache:

- **cold**: fresh PlanCache — every distinct query pays `optimized_plan`
  (rule matching, index-log reads, pushdown/prune) before execution;
- **warm**: same submission pattern again — every plan is a versioned-key
  hit and goes straight to the executor.

XLA compilation and the decoded-table/device caches are warmed before
measurement, so the cold-vs-warm delta isolates exactly the work the
plan cache amortizes. Writes BENCH_SERVE.json; `--smoke` runs a quick
4-client correctness pass (the CI `serving` job) and additionally boots
the runtime health plane (`hyperspace.obs.http.enabled`), scrapes
/metrics + /healthz over the real socket mid-load, and asserts the
serve gauges and a computed SLO burn rate are present — the CI
`observability` job's live-endpoint gate (docs/observability.md).

**Fleet mode** (`--fleet N [--clients M] [--smoke]`, docs/serving.md
"fleet topology"): N REAL worker processes over one index store, each
running its own session + QueryServer wired through the shared
disk-backed plan/result caches (serve/fleet/). Four regimes, written to
BENCH_FLEET.json with hard gates:

1. *throughput* — the same work through 1 process and through N;
   results must be digest-identical to serial execution, and on >=2-CPU
   hosts aggregate fleet qps must beat the single process;
2. *refresh churn* — workers serve a point query while this process
   appends rows and runs `refresh()` repeatedly: every returned result
   must match ONE legitimate version, and any query beginning after a
   refresh commit must reflect it (zero stale serves — the
   multi-process staleness proof at load);
3. *overload* — more clients than capacity against a small queue with
   shedding + tenant quotas: every refusal must be a typed
   AdmissionRejected/QuotaExceeded (zero untyped errors) and completed
   p99 must stay bounded — graceful saturation, never collapse;
4. *takeover* — a SIGKILLed single-flight lease holder must be
   recovered by lease takeover.
"""

from __future__ import annotations

import json
import shutil
import sys
import tempfile
import threading
import time
from pathlib import Path


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def _gen_data(root: Path, rows: int, files: int) -> None:
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    rng = np.random.default_rng(7)
    per = rows // files
    root.mkdir(parents=True)
    for f in range(files):
        t = pa.table(
            {
                "id": pa.array(np.arange(f * per, (f + 1) * per, dtype=np.int64)),
                "key": pa.array(rng.integers(0, 1024, per, dtype=np.int64)),
                "value": pa.array(rng.standard_normal(per)),
                "amount": pa.array(rng.integers(0, 10_000, per, dtype=np.int64)),
            }
        )
        pq.write_table(t, root / f"part-{f}.parquet")


def _stats(lat_s: list[float], wall_s: float) -> dict:
    import numpy as np

    arr = np.sort(np.asarray(lat_s))
    return {
        "queries": len(arr),
        "throughput_qps": round(len(arr) / wall_s, 2),
        "p50_ms": round(float(np.percentile(arr, 50)) * 1e3, 3),
        "p95_ms": round(float(np.percentile(arr, 95)) * 1e3, 3),
        "p99_ms": round(float(np.percentile(arr, 99)) * 1e3, 3),
        "mean_ms": round(float(arr.mean()) * 1e3, 3),
    }


def _scrape(endpoint, expect_burn: bool) -> dict:
    """Scrape /metrics and /healthz over the real socket and assert the
    health plane is live: serve gauges in the Prometheus text, scheduler
    saturation in the healthz document, and — once traffic has flowed
    between two scrapes — a computed (non-sentinel) SLO burn rate."""
    import json as _json
    import urllib.request

    with urllib.request.urlopen(endpoint.url("/metrics"), timeout=10) as r:
        metrics_text = r.read().decode()
    for needle in (
        "hyperspace_serve_inflight",
        "hyperspace_serve_queue_depth",
        "hyperspace_serve_latency_seconds_bucket",
        "hyperspace_slo_serve_availability_burn_rate",
        "hyperspace_proc_map_count",
        "hyperspace_jit_live_executables",
    ):
        assert needle in metrics_text, f"{needle} missing from /metrics"
    with urllib.request.urlopen(endpoint.url("/healthz"), timeout=10) as r:
        doc = _json.loads(r.read().decode())
    assert doc["status"] in ("ok", "degraded"), doc["status"]
    assert doc["scheduler"] and doc["scheduler"][0]["workers"] == 4, doc["scheduler"]
    burn = [
        ln.rsplit(" ", 1)[1]
        for ln in metrics_text.splitlines()
        if ln.startswith("hyperspace_slo_serve_availability_burn_rate ")
    ][0]
    if expect_burn:
        assert float(burn) >= 0.0, f"burn rate not computed: {burn}"
    return {"status": doc["status"], "availability_burn": float(burn)}


def _run_phase(server, queries, n_clients: int, reps: int) -> dict:
    """Each client submits its share of `queries` x reps; per-query
    latency is submit→result as a client sees it."""
    lat: list[float] = []
    lock = threading.Lock()
    errors: list[BaseException] = []

    def client(cid: int):
        mine = [q for i, q in enumerate(queries) if i % n_clients == cid]
        out: list[float] = []
        try:
            for _ in range(reps):
                for q in mine:
                    t0 = time.perf_counter()
                    server.submit(q).result(timeout=600)
                    out.append(time.perf_counter() - t0)
        except BaseException as e:
            errors.append(e)
        with lock:
            lat.extend(out)

    t0 = time.perf_counter()
    threads = [threading.Thread(target=client, args=(i,)) for i in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    if errors:
        raise errors[0]
    return _stats(lat, wall)


# -- fleet mode (docs/serving.md "fleet topology") ----------------------------

def _digest(table) -> str:
    """Order-insensitive content digest of a ColumnTable: the
    byte-identical-results gate compares these across processes."""
    import hashlib

    import numpy as np

    d = table.decode()
    cols = sorted(d)
    rows = sorted(zip(*[np.asarray(d[c]).tolist() for c in cols])) if cols else []
    payload = json.dumps([cols, rows], default=str)
    return hashlib.md5(payload.encode()).hexdigest()


def _fleet_worker(ctx, data_root, system_path, n_keys, opts, work_q, res_q):
    """One fleet member: session + QueryServer over the shared store,
    wired through the shared disk caches; client threads pull work items
    `(query_id, tenant)` and report `(kind, worker, qid, begin_ts, lat,
    payload)` tuples. A `None` work item stops one client thread."""
    import queue as _queue
    import threading as _threading

    from hyperspace_tpu import HyperspaceSession
    from hyperspace_tpu import col as _col
    from hyperspace_tpu.exceptions import AdmissionRejected
    from hyperspace_tpu.serve import fleet as _fleet

    session = HyperspaceSession(system_path=system_path, num_buckets=16)
    session.conf.set("hyperspace.obs.http.enabled", "true")  # port=0: ephemeral
    session.enable_hyperspace()
    df = session.parquet(data_root)
    queries = [
        df.filter(_col("key") == int(k)).select("key", "value", "amount")
        for k in range(n_keys)
    ]
    plans, results = _fleet.shared_caches(session)
    quotas = None
    if opts.get("quota_rate"):
        from hyperspace_tpu.serve.fleet.quota import TenantQuotas

        quotas = TenantQuotas(rate=opts["quota_rate"], burst=opts.get("quota_burst", 4))
    server_kwargs = dict(
        workers=opts.get("workers", 2),
        max_queue_depth=opts.get("max_queue_depth", 256),
        plan_cache=plans,
        result_cache=results if opts.get("result_cache", True) else False,
        quotas=quotas,
        shed_depth_ratio=opts.get("shed_ratio", 1.0),
    )
    with session.serve(**server_kwargs) as server:
        _fleet.register_worker(ctx.fleet_dir, ctx.worker_id, server.health_endpoint.port)

        def client_loop():
            while True:
                try:
                    item = work_q.get(timeout=1.0)
                except _queue.Empty:
                    if ctx.stop_event.is_set():
                        return
                    continue
                if item is None:
                    return
                qid, tenant = item
                begin_ts = time.time()  # cross-process ordering vs refresh commits
                t0 = time.perf_counter()
                try:
                    out = server.submit(queries[qid], tenant=tenant).result(timeout=600)
                    res_q.put(("ok", ctx.worker_id, qid, begin_ts,
                               time.perf_counter() - t0, _digest(out)))
                except AdmissionRejected as e:
                    # The typed saturation surface (QuotaExceeded included).
                    res_q.put(("rejected", ctx.worker_id, qid, begin_ts,
                               time.perf_counter() - t0, type(e).__name__))
                except BaseException as e:
                    res_q.put(("error", ctx.worker_id, qid, begin_ts, 0.0,
                               f"{type(e).__name__}: {e}"))

        threads = [
            _threading.Thread(target=client_loop, daemon=True)
            for _ in range(opts.get("clients_per_worker", 2))
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()


def _bench_lease_holder(sf_dir, name, ready_q):
    """Child for the takeover gate: claim the lease, report, hang until
    SIGKILLed (a crashed holder gets no cleanup)."""
    from pathlib import Path as _Path

    from hyperspace_tpu.serve.fleet.lease import FileLease
    from hyperspace_tpu.serve.fleet.singleflight import key_name

    lease = FileLease(_Path(sf_dir) / f"{key_name(name)}.lease", ttl_s=300)
    ready_q.put("held" if lease.try_acquire() is not None else "failed")
    time.sleep(300)


def _collect(res_q, expect: int, timeout_s: float = 600.0) -> list[tuple]:
    import queue as _queue

    out: list[tuple] = []
    deadline = time.monotonic() + timeout_s
    while len(out) < expect and time.monotonic() < deadline:
        try:
            out.append(res_q.get(timeout=1.0))
        except _queue.Empty:
            continue
    if len(out) < expect:
        raise RuntimeError(f"fleet phase collected {len(out)}/{expect} results")
    return out


def _run_fleet_phase(
    fleet_dir, data_root, system_path, n_keys, n_workers, opts, work_items,
    phase_timeout_s: float = 600.0,
):
    """Spawn `n_workers` fleet members, feed `work_items`, and collect
    every result. Returns (records, wall_s) with the warmup pass (one
    item per key per worker, XLA + shared-cache fill) excluded from the
    measured wall."""
    from hyperspace_tpu.serve import fleet as _fleet

    ctx_mp = __import__("multiprocessing").get_context("spawn")
    work_q, res_q = ctx_mp.Queue(), ctx_mp.Queue()
    clients = opts.get("clients_per_worker", 2)
    sup = _fleet.FleetSupervisor(
        _fleet_worker, fleet_dir=str(fleet_dir), n=n_workers,
        args=(str(data_root), str(system_path), n_keys, opts, work_q, res_q),
        max_restarts=0,
    )
    sup.start()
    try:
        warm = [(k, None) for k in range(n_keys)] * n_workers
        for item in warm:
            work_q.put(item)
        _collect(res_q, len(warm), timeout_s=phase_timeout_s)
        t0 = time.perf_counter()
        for item in work_items:
            work_q.put(item)
        records = _collect(res_q, len(work_items), timeout_s=phase_timeout_s)
        wall = time.perf_counter() - t0
        # Fleet-wide health right after the rated load drained: every
        # member's /healthz (scraped over its registered ephemeral port)
        # must not be paging — 503-on-page is the LB overload signal,
        # and rated traffic must not trip it.
        health = sup.fleet_health()
        for _ in range(n_workers * clients):
            work_q.put(None)
    finally:
        sup.stop(timeout=60)
    return records, wall, health


def fleet_main(n_fleet: int, n_clients: int, smoke: bool) -> int:
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
    import os
    import signal

    import jax
    import numpy as np

    # A chip serves one process at a time and the members need it: this
    # coordinator builds, refreshes and computes its serial reference on
    # the CPU backend (results are venue-independent), so it never holds
    # a chip while the supervisor spawns members.
    jax.config.update("jax_platforms", "cpu")

    from hyperspace_tpu import Hyperspace, HyperspaceSession, IndexConfig, col
    from hyperspace_tpu import stats as hs_stats
    from hyperspace_tpu.serve.fleet.singleflight import SingleFlight

    rows = 40_000 if smoke else 200_000
    n_keys = 8 if smoke else 32
    reps = 4 if smoke else 12
    cpus = os.cpu_count() or 1

    tmp = Path(tempfile.mkdtemp(prefix="hs_benchfleet_"))
    results_doc: dict = {
        "fleet": n_fleet, "clients": n_clients, "rows": rows,
        "distinct_queries": n_keys, "cpus": cpus, "gates": {},
    }
    try:
        data = tmp / "events"
        _gen_data(data, rows, 8)
        system_path = tmp / "indexes"
        session = HyperspaceSession(system_path=str(system_path), num_buckets=16)
        hs = Hyperspace(session)
        df = session.parquet(data)
        hs.create_index(df, IndexConfig("events_key", ["key"], ["value", "amount"]))
        session.enable_hyperspace()
        queries = [
            df.filter(col("key") == int(k)).select("key", "value", "amount")
            for k in range(n_keys)
        ]
        serial_digests = {k: _digest(session.run(queries[k])) for k in range(n_keys)}

        # -- regime 1: throughput, 1 process vs N --------------------------
        work = [(k, None) for k in range(n_keys)] * reps
        per_worker_clients = max(1, n_clients // max(1, n_fleet))
        base_opts = {"workers": 2, "clients_per_worker": per_worker_clients,
                     "max_queue_depth": 1024}
        rec1, wall1, _h1 = _run_fleet_phase(
            tmp / "fleet1", data, system_path, n_keys, 1,
            {**base_opts, "clients_per_worker": n_clients}, work)
        recN, wallN, healthN = _run_fleet_phase(
            tmp / "fleetN", data, system_path, n_keys, n_fleet, base_opts, work)
        ok1 = [r for r in rec1 if r[0] == "ok"]
        okN = [r for r in recN if r[0] == "ok"]
        identical = all(serial_digests[r[2]] == r[5] for r in ok1 + okN)
        qps_1 = round(len(ok1) / wall1, 2)
        qps_n = round(len(okN) / wallN, 2)
        results_doc["throughput"] = {
            "queries": len(work),
            "single_process_qps": qps_1,
            "fleet_qps": qps_n,
            "speedup": round(qps_n / qps_1, 3) if qps_1 else None,
            "errors": [r for r in rec1 + recN if r[0] == "error"][:5],
        }
        results_doc["gates"]["results_identical_to_serial"] = identical
        # The qps gate needs real parallel hardware: on a 1-CPU host N
        # processes time-slice one core (the same build-pipeline caveat
        # BENCH_PIPELINE records) — the gate is enforced on >=2 CPUs and
        # recorded as informational otherwise.
        qps_gate_enforced = cpus >= 2
        results_doc["throughput"]["qps_gate_enforced"] = qps_gate_enforced
        results_doc["gates"]["fleet_qps_beats_single"] = (
            qps_n > qps_1 if qps_gate_enforced else None
        )
        results_doc["throughput"]["fleet_health"] = {
            "status": healthN["status"],
            "alive": healthN["alive"],
            "saturation": healthN["saturation"],
        }
        results_doc["gates"]["slo_unpaged_at_rated_load"] = (
            healthN["status"] in ("ok", "degraded")
            and healthN["alive"] == n_fleet
        )
        log(f"throughput: 1-proc {qps_1} qps | fleet({n_fleet}) {qps_n} qps "
            f"| identical={identical} | health={healthN['status']} (cpus={cpus})")

        # -- regime 2: concurrent refresh churn ----------------------------
        import pyarrow as pa
        import pyarrow.parquet as pq

        churn_key = 7 % n_keys
        n_churn_q = 24 if smoke else 96
        n_refresh = 2 if smoke else 4
        ctx_mp = __import__("multiprocessing").get_context("spawn")
        work_q, res_q = ctx_mp.Queue(), ctx_mp.Queue()
        from hyperspace_tpu.serve import fleet as _fleet

        churn_workers = min(2, max(1, n_fleet))
        mid_batch = n_churn_q // 2
        feeder_stop = threading.Event()
        sup = _fleet.FleetSupervisor(
            _fleet_worker, fleet_dir=str(tmp / "fleet_churn"), n=churn_workers,
            args=(str(data), str(system_path), n_keys,
                  {"workers": 2, "clients_per_worker": 2}, work_q, res_q),
            max_restarts=0,
        )
        sup.start()
        try:
            work_q.put((churn_key, None))
            _collect(res_q, 1)  # workers up and serving
            legit = [serial_digests[churn_key]]
            commits: list[float] = []

            def feeder():
                # Queries racing the refreshes — interleaved with the
                # commits below.
                for _ in range(mid_batch):
                    if feeder_stop.is_set():
                        return
                    work_q.put((churn_key, None))
                    time.sleep(0.05)

            ft = threading.Thread(target=feeder, daemon=True)
            ft.start()
            next_id = 1_000_000
            for i in range(n_refresh):
                extra = pa.table({
                    "id": pa.array(np.arange(next_id, next_id + 16, dtype=np.int64)),
                    "key": pa.array(np.full(16, churn_key, dtype=np.int64)),
                    "value": pa.array(np.linspace(0.0, 1.0, 16)),
                    "amount": pa.array(np.arange(16, dtype=np.int64)),
                })
                pq.write_table(extra, data / f"churn-{i}.parquet")
                next_id += 16
                hs.refresh_index("events_key")
                commits.append(time.time())
                legit.append(_digest(session.run(queries[churn_key])))
                time.sleep(0.2)
            ft.join()
            # A guaranteed post-final-commit batch: every one of these
            # begins after the last refresh, so each MUST return the
            # final version — the stale-serve gate has teeth even when
            # the racing batch finished early.
            for _ in range(n_churn_q - mid_batch):
                work_q.put((churn_key, None))
            churn = _collect(res_q, n_churn_q)
            for _ in range(churn_workers * 2):
                work_q.put(None)
        finally:
            feeder_stop.set()
            sup.stop(timeout=60)
        ok_churn = [r for r in churn if r[0] == "ok"]
        version_of = {d: i for i, d in enumerate(legit)}
        wrong_version = [r for r in ok_churn if r[5] not in version_of]
        stale = []
        for r in ok_churn:
            begin = r[3]
            floor = sum(1 for c in commits if begin > c)  # versions committed first
            if r[5] in version_of and version_of[r[5]] < floor:
                stale.append((r[1], begin, version_of[r[5]], floor))
        results_doc["refresh_churn"] = {
            "queries": len(ok_churn), "refreshes": n_refresh,
            "errors": [r for r in churn if r[0] == "error"][:5],
            "wrong_version": len(wrong_version), "stale_serves": len(stale),
        }
        results_doc["gates"]["zero_wrong_version_results"] = not wrong_version
        results_doc["gates"]["zero_stale_serves"] = not stale
        log(f"refresh churn: {len(ok_churn)} queries over {n_refresh} refreshes, "
            f"wrong_version={len(wrong_version)}, stale={len(stale)}")

        # -- regime 3: overload (graceful saturation) ----------------------
        n_over = 160 if smoke else 600
        over_opts = {
            "workers": 2, "clients_per_worker": 8, "max_queue_depth": 8,
            "shed_ratio": 0.5, "result_cache": False,
            "quota_rate": 50.0, "quota_burst": 8,
        }
        tenants = [f"tenant-{i % 4}" for i in range(n_over)]
        over_work = [(i % n_keys, tenants[i]) for i in range(n_over)]
        rec_over, wall_over, _hover = _run_fleet_phase(
            tmp / "fleet_over", data, system_path, n_keys, 1, over_opts, over_work)
        ok_over = sorted(r[4] for r in rec_over if r[0] == "ok")
        rejected = [r for r in rec_over if r[0] == "rejected"]
        errors_over = [r for r in rec_over if r[0] == "error"]
        p99_over = ok_over[int(len(ok_over) * 0.99)] if ok_over else None
        warm_p95 = sorted(r[4] for r in okN if r[0] == "ok")
        warm_p95 = warm_p95[int(len(warm_p95) * 0.95)] if warm_p95 else 0.1
        p99_bound_s = max(10.0 * warm_p95, 5.0)
        results_doc["overload"] = {
            "offered": n_over, "completed": len(ok_over),
            "rejected": len(rejected),
            "rejection_types": sorted({r[5] for r in rejected}),
            "untyped_errors": errors_over[:5],
            "p99_s": round(p99_over, 4) if p99_over is not None else None,
            "p99_bound_s": round(p99_bound_s, 4),
            "wall_s": round(wall_over, 3),
        }
        results_doc["gates"]["overload_typed_rejections"] = len(rejected) > 0
        results_doc["gates"]["overload_zero_untyped_errors"] = not errors_over
        results_doc["gates"]["overload_p99_bounded"] = (
            p99_over is not None and p99_over <= p99_bound_s
        )
        log(f"overload: {len(ok_over)} ok, {len(rejected)} typed rejections "
            f"({results_doc['overload']['rejection_types']}), "
            f"{len(errors_over)} untyped, p99={p99_over and round(p99_over, 4)}s "
            f"(bound {round(p99_bound_s, 2)}s)")

        # -- regime 4: SIGKILLed single-flight holder ----------------------
        sf_dir = tmp / "fleet_sf"
        ready = ctx_mp.Queue()
        holder = ctx_mp.Process(
            target=_bench_lease_holder, args=(str(sf_dir), "hot", ready))
        holder.start()
        assert ready.get(timeout=120) == "held"
        os.kill(holder.pid, signal.SIGKILL)
        holder.join(timeout=30)
        time.sleep(0.7)
        sf = SingleFlight(sf_dir, lease_ttl_s=0.5, wait_s=10)
        t_before = hs_stats.get("fleet.singleflight.takeovers")
        recovered = sf.run("hot", build=lambda: "recovered", check=lambda: None)
        takeover_ok = (
            recovered == "recovered"
            and hs_stats.get("fleet.singleflight.takeovers") == t_before + 1
        )
        results_doc["takeover"] = {"recovered": takeover_ok}
        results_doc["gates"]["sigkill_holder_recovered_by_takeover"] = takeover_ok
        log(f"takeover: SIGKILLed holder recovered={takeover_ok}")

        out = Path(__file__).resolve().parent.parent / "BENCH_FLEET.json"
        out.write_text(json.dumps(results_doc, indent=2, default=str) + "\n")
        log(f"wrote {out}")
        failed = [k for k, v in results_doc["gates"].items() if v is False]
        if failed:
            log(f"FLEET GATES FAILED: {failed}")
            return 1
        log("fleet gates OK: " + ", ".join(
            f"{k}={v}" for k, v in results_doc["gates"].items()))
        return 0
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def main(smoke: bool = False) -> int:
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
    import numpy as np

    from hyperspace_tpu import Hyperspace, HyperspaceSession, IndexConfig, col
    from hyperspace_tpu.serve import PlanCache

    rows = 40_000 if smoke else 400_000
    n_keys = 16 if smoke else 96
    reps = 1 if smoke else 3
    client_counts = [4] if smoke else [1, 4, 16]

    tmp = Path(tempfile.mkdtemp(prefix="hs_benchserve_"))
    try:
        data = tmp / "events"
        _gen_data(data, rows, 8)
        session = HyperspaceSession(system_path=str(tmp / "indexes"), num_buckets=16)
        hs = Hyperspace(session)
        df = session.parquet(data)
        hs.create_index(df, IndexConfig("events_key", ["key"], ["value", "amount"]))
        session.enable_hyperspace()

        queries = [
            df.filter(col("key") == int(k)).select("key", "value", "amount")
            for k in range(n_keys)
        ]
        # Warm XLA + table/device caches so cold-vs-warm isolates the
        # planning cost (all point lookups share one jitted program).
        serial = [session.run(q) for q in queries[: min(4, n_keys)]]

        if smoke:
            session.conf.set("hyperspace.obs.http.enabled", "true")
            with session.serve(workers=4, max_queue_depth=256) as server:
                endpoint = server.health_endpoint
                _scrape(endpoint, expect_burn=False)  # first SLO sample
                for i, q in enumerate(queries[: len(serial)]):
                    out = server.submit(q).result(timeout=600).decode()
                    ref = serial[i].decode()
                    assert set(out) == set(ref)
                    for c in out:
                        assert np.array_equal(
                            np.asarray(out[c]), np.asarray(ref[c])
                        ), f"smoke mismatch in {c}"
                st = _run_phase(server, queries, n_clients=4, reps=2)
                scraped = _scrape(endpoint, expect_burn=True)
            log(f"smoke OK: 4 clients, {st['queries']} queries, "
                f"p95 {st['p95_ms']}ms, {st['throughput_qps']} qps; "
                f"health plane OK: {scraped}")
            return 0

        results: dict = {
            "rows": rows,
            "distinct_queries": n_keys,
            "workers": 4,
            "reps_per_phase": reps,
            "clients": {},
        }
        for nc in client_counts:
            cache = PlanCache(max_entries=256)
            with session.serve(workers=4, max_queue_depth=1024, plan_cache=cache) as server:
                cold = _run_phase(server, queries, n_clients=nc, reps=1)
                cold["plan_cache"] = dict(cache.stats())
                warm = _run_phase(server, queries, n_clients=nc, reps=reps)
                warm["plan_cache"] = dict(cache.stats())
            results["clients"][str(nc)] = {"cold": cold, "warm": warm}
            log(
                f"{nc:>2} client(s): cold p95 {cold['p95_ms']:8.3f}ms "
                f"{cold['throughput_qps']:8.2f} qps | warm p95 "
                f"{warm['p95_ms']:8.3f}ms {warm['throughput_qps']:8.2f} qps"
            )

        out = Path(__file__).resolve().parent.parent / "BENCH_SERVE.json"
        out.write_text(json.dumps(results, indent=2) + "\n")
        log(f"wrote {out}")
        for nc, r in results["clients"].items():
            if r["warm"]["p95_ms"] >= r["cold"]["p95_ms"]:
                log(f"WARNING: warm p95 not below cold at {nc} clients")
        return 0
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _arg(name: str, default: int | None = None) -> int | None:
    for i, a in enumerate(sys.argv):
        if a == name and i + 1 < len(sys.argv):
            return int(sys.argv[i + 1])
        if a.startswith(name + "="):
            return int(a.split("=", 1)[1])
    return default


if __name__ == "__main__":
    _fleet_n = _arg("--fleet")
    if _fleet_n:
        sys.exit(fleet_main(
            n_fleet=_fleet_n,
            n_clients=_arg("--clients", max(2, 2 * _fleet_n)),
            smoke="--smoke" in sys.argv,
        ))
    sys.exit(main(smoke="--smoke" in sys.argv))
