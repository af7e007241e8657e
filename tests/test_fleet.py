"""Multi-process serving fleet tests (docs/serving.md "fleet topology").

Covers the file-lease primitive (stale-holder reaping), cross-process
single-flight (leader/follower/local-fallback/takeover), the RefCache
single-flight wait timeout, per-tenant token-bucket quotas and
queue-depth shedding in the scheduler, the disk-backed shared
plan/result caches (round-trip, versioned invalidation, advisory
corruption handling, lease-held eviction), and — with REAL processes
over one store — the promoted staleness proof (process A refreshes,
process B must never serve a pre-refresh cached result), lease takeover
from a SIGKILLed holder, and supervisor crash-restart.
"""

from __future__ import annotations

import json
import os
import signal
import threading
import time

import numpy as np
import pytest

from hyperspace_tpu import Hyperspace, HyperspaceSession, IndexConfig, col, stats
from hyperspace_tpu.exceptions import AdmissionRejected, QuotaExceeded
from hyperspace_tpu.serve import QueryServer, fleet
from hyperspace_tpu.serve.fleet.lease import FileLease
from hyperspace_tpu.serve.fleet.quota import TenantQuotas, TokenBucket
from hyperspace_tpu.serve.fleet.shared_cache import SharedResultCache
from hyperspace_tpu.serve.fleet.singleflight import SingleFlight, key_name


def _session(tmp_system_path) -> HyperspaceSession:
    return HyperspaceSession(system_path=tmp_system_path)


def _assert_same(a, b, label=""):
    da, db = a.decode(), b.decode()
    assert set(da) == set(db), (label, set(da), set(db))
    for c in da:
        av, bv = np.asarray(da[c]), np.asarray(db[c])
        assert len(av) == len(bv), (label, c, len(av), len(bv))
        if av.dtype.kind in "fc" and bv.dtype.kind in "fc":
            np.testing.assert_allclose(av, bv, rtol=1e-9, err_msg=f"{label}.{c}")
        else:
            assert (av.astype(object) == bv.astype(object)).all(), (label, c)


# -- file lease ---------------------------------------------------------------

class TestFileLease:
    def test_acquire_release_roundtrip(self, tmp_path):
        lease = FileLease(tmp_path / "a.lease", ttl_s=30)
        claim = lease.try_acquire()
        assert claim is not None
        token, reaped = claim
        assert not reaped
        assert lease.try_acquire() is None  # held by a live contender
        lease.release(token)
        assert lease.try_acquire() is not None  # free again

    def test_stale_holder_is_reaped(self, tmp_path):
        path = tmp_path / "b.lease"
        # A lease whose creator epoch is long past the TTL: a crashed
        # holder's leftover.
        path.write_text(f"{time.time() - 120:.6f}:99999:dead")
        lease = FileLease(path, ttl_s=1.0)
        claim = lease.try_acquire()
        assert claim is not None and claim[1] is True  # reaped

    def test_release_of_stolen_lease_is_noop(self, tmp_path):
        path = tmp_path / "c.lease"
        lease = FileLease(path, ttl_s=30)
        token, _ = lease.try_acquire()
        path.write_text("other-holder-token")  # our lease was reaped/stolen
        lease.release(token)
        assert path.read_text() == "other-holder-token"  # not unlinked


# -- cross-process single-flight (driven in-process for determinism) ----------

def _walk(span):
    yield span
    for c in span.get("children", ()):
        yield from _walk(c)


class TestSingleFlight:
    def test_leader_builds_follower_observes(self, tmp_path):
        sf = SingleFlight(tmp_path, lease_ttl_s=30, wait_s=10)
        artifact = tmp_path / "artifact.json"
        built = []
        release = threading.Event()

        def leader_build():
            release.wait(30)
            artifact.write_text(json.dumps({"v": 42}))
            built.append("leader")
            return 42

        def check():
            if artifact.exists():
                return json.loads(artifact.read_text())["v"]
            return None

        def follower_build():
            built.append("follower")  # must never run
            return -1

        results = []
        t1 = threading.Thread(target=lambda: results.append(sf.run("k", leader_build, check)))
        t1.start()
        time.sleep(0.2)  # leader holds the lease now
        t2 = threading.Thread(target=lambda: results.append(sf.run("k", follower_build, check)))
        t2.start()
        time.sleep(0.2)
        release.set()
        t1.join(30)
        t2.join(30)
        assert sorted(results) == [42, 42]
        assert built == ["leader"]  # exactly one build across "processes"
        assert stats.get("fleet.singleflight.leader") == 1
        assert stats.get("fleet.singleflight.follower_hits") == 1

    def test_follower_wait_span_links_leader_trace_id(self, tmp_path):
        """Cross-process trace propagation (docs/observability.md): the
        leader stamps its root trace id into the lease token note; a
        follower that waited records a `fleet.singleflight.wait` span
        carrying that leader id — the fleet chrome trace can join the
        follower's stall to the trace that actually did the work."""
        from hyperspace_tpu.obs import trace as obs_trace

        sf = SingleFlight(tmp_path, lease_ttl_s=30, wait_s=10)
        artifact = tmp_path / "artifact.json"
        release = threading.Event()
        leader_trace = []

        def leader():
            with obs_trace.trace("leader.query"):
                leader_trace.append(obs_trace.current_trace_id())
                sf.run("k", build=lambda: (
                    release.wait(30),
                    artifact.write_text(json.dumps({"v": 1})),
                )[0], check=check)

        def check():
            return 1 if artifact.exists() else None

        follower_roots = []

        def follower():
            with obs_trace.trace("follower.query"):
                sf.run("k", build=lambda: -1, check=check)
            follower_roots.append(obs_trace.last_trace().to_json())

        t1 = threading.Thread(target=leader)
        t1.start()
        time.sleep(0.3)  # leader holds the lease, note = its trace id
        t2 = threading.Thread(target=follower)
        t2.start()
        time.sleep(0.3)  # follower is in the wait loop
        release.set()
        t1.join(30)
        t2.join(30)
        (root,) = follower_roots
        waits = [s for s in _walk(root) if s["name"] == "fleet.singleflight.wait"]
        assert waits, "follower never recorded its wait"
        (wait,) = waits
        assert wait["attrs"]["outcome"] == "follower_hit"
        assert wait["attrs"]["leader_trace_id"] == leader_trace[0]

    def test_wait_expiry_falls_back_to_local_build(self, tmp_path):
        sf = SingleFlight(tmp_path, lease_ttl_s=30, wait_s=0.1)
        # A live (non-stale) foreign lease, artifact never appears.
        FileLease(tmp_path / f"{key_name('k2')}.lease", ttl_s=30).try_acquire()
        out = sf.run("k2", build=lambda: "local", check=lambda: None)
        assert out == "local"
        assert stats.get("fleet.singleflight.local_fallbacks") == 1

    def test_stale_lease_takeover(self, tmp_path):
        sf = SingleFlight(tmp_path, lease_ttl_s=0.5, wait_s=10)
        stale = tmp_path / f"{key_name('k3')}.lease"
        stale.write_text(f"{time.time() - 60:.6f}:99999:dead")
        out = sf.run("k3", build=lambda: "rebuilt", check=lambda: None)
        assert out == "rebuilt"
        assert stats.get("fleet.singleflight.takeovers") == 1
        from hyperspace_tpu.obs import events as obs_events

        names = [e["name"] for e in obs_events.recent()]
        assert "fleet.singleflight.takeover" in names

    def test_build_error_releases_lease(self, tmp_path):
        sf = SingleFlight(tmp_path, lease_ttl_s=30, wait_s=0.1)
        with pytest.raises(ValueError):
            sf.run("k4", build=lambda: (_ for _ in ()).throw(ValueError("boom")))
        # The lease is free again: the next run leads immediately.
        assert sf.run("k4", build=lambda: "ok") == "ok"


# -- RefCache single-flight wait timeout (satellite fix) ----------------------

class TestRefCacheWaitTimeout:
    def test_abandoned_build_event_no_longer_blocks(self):
        from hyperspace_tpu.execution.device_cache import RefCache

        rc = RefCache(budget_bytes=1 << 20, name="t_refcache_timeout")
        key = ("k", 1)
        # Simulate an abandoned in-process build: the building slot is
        # claimed but its event will never be set (builder thread died
        # without unwinding through get_or_build).
        with rc._lock:
            rc._building[key] = threading.Event()
        t0 = time.monotonic()
        out = rc.get_or_build(key, (), lambda: ("value", 8), wait_timeout=0.05)
        assert out == "value"
        assert time.monotonic() - t0 < 5.0  # returned promptly, not wedged
        # The abandoned slot still belongs to the stuck builder.
        with rc._lock:
            assert key in rc._building

    def test_timeout_path_still_caches(self):
        from hyperspace_tpu.execution.device_cache import RefCache

        rc = RefCache(budget_bytes=1 << 20, name="t_refcache_timeout2")
        key = ("k", 2)
        with rc._lock:
            rc._building[key] = threading.Event()
        rc.get_or_build(key, (), lambda: ("v1", 8), wait_timeout=0.01)
        with rc._lock:
            del rc._building[key]  # stuck builder "finally" goes away
        calls = []
        out = rc.get_or_build(key, (), lambda: calls.append(1) or ("v2", 8))
        assert out == "v1" and not calls  # the local build was admitted


# -- tenant quotas ------------------------------------------------------------

class TestQuota:
    def test_token_bucket_math(self):
        b = TokenBucket(rate=2.0, burst=2.0, now=0.0)
        assert b.try_take(0.0) == 0.0
        assert b.try_take(0.0) == 0.0
        wait = b.try_take(0.0)
        assert wait == pytest.approx(0.5)  # 1 token / 2 per second
        assert b.try_take(0.6) == 0.0  # refilled

    def test_tenants_are_isolated(self):
        clk = [0.0]
        tq = TenantQuotas(rate=1.0, burst=1, clock=lambda: clk[0])
        tq.admit("a")
        with pytest.raises(QuotaExceeded) as ei:
            tq.admit("a")
        assert ei.value.tenant == "a" and ei.value.retry_after_s > 0
        tq.admit("b")  # b's bucket is untouched by a's exhaustion

    def test_per_tenant_limit_override(self):
        clk = [0.0]
        tq = TenantQuotas(rate=100.0, burst=100, clock=lambda: clk[0])
        tq.set_limit("starved", rate=1.0, burst=1)
        tq.admit("starved")
        with pytest.raises(QuotaExceeded):
            tq.admit("starved")

    def test_scheduler_integration(self, tmp_system_path):
        session = _session(tmp_system_path)
        clk = [0.0]
        quotas = TenantQuotas(rate=1.0, burst=2, clock=lambda: clk[0])
        server = QueryServer(session, workers=1, max_queue_depth=16,
                             plan_cache=False, run_fn=lambda p: p, quotas=quotas)
        try:
            assert server.submit("q1", tenant="t1").result(timeout=30) == "q1"
            assert server.submit("q2", tenant="t1").result(timeout=30) == "q2"
            with pytest.raises(QuotaExceeded):
                server.submit("q3", tenant="t1")
            # QuotaExceeded IS an AdmissionRejected (one typed surface).
            with pytest.raises(AdmissionRejected):
                server.submit("q4", tenant="t1")
            # Tenant-less submits are unmetered by contract.
            assert server.submit("q5").result(timeout=30) == "q5"
            # Another tenant is unaffected.
            assert server.submit("q6", tenant="t2").result(timeout=30) == "q6"
        finally:
            server.shutdown()


# -- queue-depth shedding (graceful saturation) -------------------------------

class TestShedding:
    def test_non_priority_sheds_at_threshold_priority_continues(self, tmp_system_path):
        session = _session(tmp_system_path)
        started, release = threading.Event(), threading.Event()

        def blocking_run(plan):
            started.set()
            assert release.wait(30)
            return plan

        server = QueryServer(session, workers=1, max_queue_depth=8,
                             plan_cache=False, run_fn=blocking_run,
                             shed_depth_ratio=0.5)
        try:
            assert server.shed_depth == 4
            server.submit("head")
            assert started.wait(10)  # worker busy; queue empty
            for i in range(4):
                server.submit(f"q{i}")  # depth reaches the shed threshold
            with pytest.raises(AdmissionRejected, match="load shed"):
                server.submit("ordinary")
            # The priority lane keeps admitting up to the hard limit —
            # saturation degrades ordinary traffic first, never collapses.
            h = server.submit("urgent", priority=True)
            sat = server.saturation()
            assert sat["queue_depth"] == 5 and sat["shed_depth"] == 4
            release.set()
            assert h.result(timeout=30) == "urgent"
        finally:
            release.set()
            server.shutdown()


# -- shared caches (single process) -------------------------------------------

class TestSharedCaches:
    def test_result_roundtrip_with_strings_and_nulls(self, tmp_path, tmp_system_path):
        import pyarrow as pa
        import pyarrow.parquet as pq

        root = tmp_path / "nulls"
        root.mkdir()
        pq.write_table(pa.table({
            "id": pa.array([1, 2, 3, 4], type=pa.int64()),
            "key": pa.array([7, 7, 7, 8], type=pa.int64()),
            "name": pa.array(["a", None, "c", "d"]),
            "value": pa.array([1.5, None, 3.5, 4.5], type=pa.float64()),
        }), root / "p0.parquet")
        session = _session(tmp_system_path)
        df = session.parquet(root)
        q = df.filter(col("key") == 7).select("id", "key", "name", "value")
        serial = session.run(q)
        rc = SharedResultCache(tmp_path / "cache", max_bytes=1 << 20)
        key = rc.key(session, q)
        assert rc.get(key) is None
        assert rc.put(key, serial)
        out = rc.get(key)
        assert out is not None
        _assert_same(serial, out, "roundtrip")

    def test_refresh_changes_key_old_entry_unreachable(
        self, sample_parquet, tmp_system_path, tmp_path
    ):
        import pyarrow as pa
        import pyarrow.parquet as pq

        session = _session(tmp_system_path)
        hs = Hyperspace(session)
        df = session.parquet(sample_parquet)
        hs.create_index(df, IndexConfig("fl_idx", ["key"], ["value", "id"]))
        session.enable_hyperspace()
        q = df.filter(col("key") == 77).select("id", "key", "value")
        rc = SharedResultCache(tmp_path / "cache", max_bytes=1 << 20)
        k1 = rc.key(session, q)
        rc.put(k1, session.run(q))
        assert rc.get(k1) is not None
        extra = pa.table({
            "id": np.arange(20_000, 20_004, dtype=np.int64),
            "key": np.full(4, 77, dtype=np.int64),
            "value": np.linspace(0.0, 1.0, 4),
            "name": [f"l{i}" for i in range(4)],
        })
        pq.write_table(extra, f"{sample_parquet}/part-9.parquet")
        hs.refresh_index("fl_idx")
        k2 = rc.key(session, q)
        assert k2 != k1  # the stamp moved: pre-refresh entry unreachable
        assert rc.get(k2) is None

    def test_corrupt_entry_is_advisory_miss(self, sample_parquet, tmp_system_path, tmp_path):
        session = _session(tmp_system_path)
        df = session.parquet(sample_parquet)
        q = df.filter(col("key") == 5).select("id", "key")
        rc = SharedResultCache(tmp_path / "cache", max_bytes=1 << 20)
        key = rc.key(session, q)
        rc.put(key, session.run(q))
        rc.entry_path(key).write_bytes(b"garbage not arrow")
        e0 = stats.get("fleet.shared_cache.errors")
        assert rc.get(key) is None  # miss, not a failed query
        assert stats.get("fleet.shared_cache.errors") == e0 + 1

    def test_oversized_result_never_admitted(self, sample_parquet, tmp_system_path, tmp_path):
        session = _session(tmp_system_path)
        df = session.parquet(sample_parquet)
        q = df.select("id", "key", "value", "name")
        rc = SharedResultCache(tmp_path / "cache", max_bytes=64)  # everything too big
        key = rc.key(session, q)
        assert rc.put(key, session.run(q)) is False
        assert rc.stats()["entries"] == 0

    def test_eviction_under_lease_respects_budget(self, tmp_path, tmp_system_path):
        session = _session(tmp_system_path)
        import pyarrow as pa
        import pyarrow.parquet as pq

        root = tmp_path / "d"
        root.mkdir()
        pq.write_table(pa.table({
            "id": pa.array(np.arange(64, dtype=np.int64)),
            "key": pa.array(np.arange(64, dtype=np.int64) % 8),
        }), root / "p0.parquet")
        df = session.parquet(root)
        serial = session.run(df.filter(col("key") == 1).select("id", "key"))
        entry_bytes = None
        rc = SharedResultCache(tmp_path / "cache", max_bytes=1 << 30)
        # Size one entry, then rebuild the cache with a budget of ~3 entries.
        rc.put(("probe",), serial)
        entry_bytes = rc.stats()["bytes"]
        rc.clear()
        rc = SharedResultCache(tmp_path / "cache", max_bytes=int(entry_bytes * 3.5))
        for i in range(6):
            assert rc.put(("k", i), serial)
            time.sleep(0.02)  # distinct mtimes for deterministic LRU order
        st = rc.stats()
        assert st["bytes"] <= rc.max_bytes
        assert st["entries"] < 6
        assert stats.get("fleet.shared_cache.evictions") > 0
        # The newest entries survive (oldest-mtime eviction).
        assert rc.get(("k", 5)) is not None

    def test_plan_cache_shared_across_servers(self, sample_parquet, tmp_system_path):
        session = _session(tmp_system_path)
        hs = Hyperspace(session)
        df = session.parquet(sample_parquet)
        hs.create_index(df, IndexConfig("fl_idx2", ["key"], ["value"]))
        session.enable_hyperspace()
        q = df.filter(col("key") == 3).select("key", "value")
        plans, results = fleet.shared_caches(session)
        with session.serve(workers=1, plan_cache=plans, result_cache=False) as server:
            server.submit(q).result(timeout=300)
        h0 = stats.get("fleet.shared_cache.hits")
        # A SECOND server (fresh process stand-in) hits the disk entry.
        with session.serve(workers=1, plan_cache=plans, result_cache=False) as server:
            server.submit(q).result(timeout=300)
        assert stats.get("fleet.shared_cache.hits") > h0


# -- real multi-process proofs ------------------------------------------------

def _mp_ctx():
    import multiprocessing as mp

    return mp.get_context("spawn")


def _cache_worker(ctx, data_root, system_path, cmd_q, out_q):
    """Fleet member: serve one point query over the shared store through
    the shared caches, reporting (ids, shared hit count, port)."""
    from hyperspace_tpu import HyperspaceSession
    from hyperspace_tpu import col as _col
    from hyperspace_tpu import stats as _stats
    from hyperspace_tpu.serve import fleet as _fleet

    session = HyperspaceSession(system_path=system_path)
    session.conf.set("hyperspace.obs.http.enabled", "true")  # port=0 default
    session.enable_hyperspace()
    df = session.parquet(data_root)
    q = df.filter(_col("key") == 7).select("id", "key", "value")
    plans, results = _fleet.shared_caches(session)
    with session.serve(workers=1, plan_cache=plans, result_cache=results) as server:
        endpoint = server.health_endpoint
        _fleet.register_worker(ctx.fleet_dir, ctx.worker_id, endpoint.port)
        import queue as _queue

        while not ctx.stop_event.is_set():
            try:
                cmd = cmd_q.get(timeout=0.2)
            except _queue.Empty:
                continue
            if cmd == "stop":
                break
            out = server.submit(q).result(timeout=300)
            import numpy as _np

            ids = sorted(_np.asarray(out.decode()["id"]).tolist())
            out_q.put({
                "ids": ids,
                "shared_hits": _stats.get("fleet.shared_cache.hits"),
                "port": endpoint.port,
            })


class TestMultiProcessFleet:
    def test_cross_process_invalidation_and_port_discovery(self, tmp_path):
        """The promoted staleness proof: process A (this one) runs
        refresh(); process B must never serve a pre-refresh cached
        result — the versioned key it computes AFTER the refresh commit
        embeds the new log id, so A's published entries are simply
        unreachable from B. Also proves ephemeral-port discovery: B
        binds port=0 and registers the real port in the fleet dir."""
        import pyarrow as pa
        import pyarrow.parquet as pq

        data = tmp_path / "data"
        data.mkdir()
        rng = np.random.default_rng(3)
        pq.write_table(pa.table({
            "id": pa.array(np.arange(400, dtype=np.int64)),
            "key": pa.array(rng.integers(0, 16, 400, dtype=np.int64)),
            "value": pa.array(rng.standard_normal(400)),
        }), data / "p0.parquet")
        system_path = str(tmp_path / "indexes")
        session = _session(system_path)
        hs = Hyperspace(session)
        df = session.parquet(data)
        hs.create_index(df, IndexConfig("mp_idx", ["key"], ["value", "id"]))
        session.enable_hyperspace()
        q = df.filter(col("key") == 7).select("id", "key", "value")

        # Process A warms the SHARED result cache with the pre-refresh rows.
        plans, results = fleet.shared_caches(session)
        with session.serve(workers=1, plan_cache=plans, result_cache=results) as server:
            pre = server.submit(q).result(timeout=300)
        pre_ids = sorted(np.asarray(pre.decode()["id"]).tolist())

        ctx = _mp_ctx()
        cmd_q, out_q = ctx.Queue(), ctx.Queue()
        sup = fleet.FleetSupervisor(
            _cache_worker, fleet_dir=str(tmp_path / "fleet"), n=1,
            args=(str(data), system_path, cmd_q, out_q), max_restarts=0,
        )
        sup.start()
        try:
            cmd_q.put("query")
            first = out_q.get(timeout=180)
            assert first["ids"] == pre_ids
            # B served A's published entry (shared cache crossed the
            # process boundary) — plan or result hit, either proves it.
            assert first["shared_hits"] >= 1
            assert first["port"] and first["port"] > 0

            # Port discovery + fleet aggregation over the real socket.
            health = sup.fleet_health()
            assert health["members"][0]["port"] == first["port"]
            assert health["members"][0]["status"] in ("ok", "degraded")
            assert health["saturation"]["workers"] >= 1

            # A's world change: append rows with key=7, refresh.
            extra = pa.table({
                "id": np.arange(10_000, 10_006, dtype=np.int64),
                "key": np.full(6, 7, dtype=np.int64),
                "value": np.linspace(0.0, 1.0, 6),
            })
            pq.write_table(extra, data / "p1.parquet")
            hs.refresh_index("mp_idx")
            post = session.run(q)
            post_ids = sorted(np.asarray(post.decode()["id"]).tolist())
            assert set(post_ids) >= set(pre_ids) | {10_000, 10_005}

            # B, queried AFTER the commit, must see the new world — its
            # key embeds the bumped log id; the stale entry cannot hit.
            cmd_q.put("query")
            second = out_q.get(timeout=180)
            assert second["ids"] == post_ids
            cmd_q.put("stop")
        finally:
            sup.stop(timeout=60)

    def test_sigkilled_singleflight_holder_is_taken_over(self, tmp_path):
        """A SIGKILLed lease holder gets no cleanup; the next claimant
        must reap its lease after the TTL and run the build — the
        crashed-holder-never-wedges-the-fleet guarantee."""
        ctx = _mp_ctx()
        ready = ctx.Queue()
        p = ctx.Process(
            target=_lease_holder, args=(str(tmp_path / "sf"), "hot-key", ready)
        )
        p.start()
        try:
            assert ready.get(timeout=120) == "held"
            os.kill(p.pid, signal.SIGKILL)
            p.join(timeout=30)
            time.sleep(0.7)  # let the dead holder's epoch go stale (ttl 0.5)
            sf = SingleFlight(tmp_path / "sf", lease_ttl_s=0.5, wait_s=10)
            t0 = stats.get("fleet.singleflight.takeovers")
            out = sf.run("hot-key", build=lambda: "recovered", check=lambda: None)
            assert out == "recovered"
            assert stats.get("fleet.singleflight.takeovers") == t0 + 1
        finally:
            if p.is_alive():
                p.terminate()

    def test_supervisor_restarts_crashed_worker(self, tmp_path):
        marker = tmp_path / "attempts"
        marker.mkdir()
        sup = fleet.FleetSupervisor(
            _crasher, fleet_dir=str(tmp_path / "fleet"), n=1,
            args=(str(marker),), max_restarts=1,
        )
        r0 = stats.get("fleet.supervisor.restarts")
        sup.start()
        try:
            deadline = time.monotonic() + 180
            while time.monotonic() < deadline:
                if sup.restarts().get(0, 0) >= 1 and sup.alive_count() == 0:
                    break
                time.sleep(0.2)
            assert sup.restarts().get(0, 0) == 1  # budget spent, slot left down
            assert len(list(marker.iterdir())) == 2  # original + one respawn
            assert stats.get("fleet.supervisor.restarts") == r0 + 1
        finally:
            sup.stop(timeout=30)

    @pytest.mark.parametrize("held,n,refused", [
        (False, 2, False), (False, 3, True), (True, 1, True),
    ])
    def test_supervisor_refuses_more_device_members_than_free_chips(
        self, tmp_path, monkeypatch, held, n, refused
    ):
        """A chip serves one process at a time: on a two-chip host the
        supervisor starts at most two device members, and none while it
        holds the chips itself — a typed error, never a hung member."""
        from hyperspace_tpu.exceptions import FleetCapacityError
        from hyperspace_tpu.serve.fleet import supervisor as sup_mod

        # A fake host: two v5e chips (VFIO groups 0 and 1), a NIC passed
        # through VFIO (group 5, Google's vendor id, not a TPU device id),
        # and this process's fds — holding both chips' nodes when `held`.
        root = tmp_path / "host"
        for group, device in (("0", "0x0063"), ("1", "0x0063"), ("5", "0x0042")):
            (root / "dev/vfio").mkdir(parents=True, exist_ok=True)
            (root / "dev/vfio" / group).touch()
            fn = root / "sys/kernel/iommu_groups" / group / "devices" / f"0000:00:0{group}.0"
            fn.mkdir(parents=True)
            (fn / "vendor").write_text("0x1ae0\n")
            (fn / "device").write_text(device + "\n")
        fds = root / "proc/self/fd"
        fds.mkdir(parents=True)
        (fds / "0").symlink_to(root / "dev/null")
        if held:
            (fds / "7").symlink_to(root / "dev/vfio/0")
            (fds / "8").symlink_to(root / "dev/vfio/1")
        monkeypatch.setattr(sup_mod, "_ROOT", str(root))
        monkeypatch.setenv("JAX_PLATFORMS", "tpu")
        assert sup_mod.tpu_chip_nodes() == [str(root / "dev/vfio/0"), str(root / "dev/vfio/1")]
        assert sup_mod.device_member_slots() == (0 if held else 2)
        sup = fleet.FleetSupervisor(_crasher, fleet_dir=str(tmp_path / "fleet"), n=n)
        if refused:
            with pytest.raises(FleetCapacityError) as ei:
                sup._check_capacity(n)
            assert ei.value.requested == n and ei.value.slots == (0 if held else 2)
        else:
            sup._check_capacity(n)
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")  # CPU members: unbounded
        assert sup_mod.device_member_slots() is None

    def test_crash_loop_backs_off_instead_of_burning_budget(self, tmp_path):
        """A crash-looping member must not spend its whole maxRestarts
        budget in milliseconds: the first respawn is immediate, repeat
        respawns of the SAME member wait out an exponential backoff
        (announced by a WARN fleet.worker.crash_loop event naming the
        member and its delay)."""
        from hyperspace_tpu.obs import events

        marker = tmp_path / "attempts"
        marker.mkdir()
        sup = fleet.FleetSupervisor(
            _crasher, fleet_dir=str(tmp_path / "fleet"), n=1,
            args=(str(marker),), max_restarts=3, restart_backoff=0.4,
        )
        sup.start()
        try:
            deadline = time.monotonic() + 180
            while time.monotonic() < deadline:
                if sup.restarts().get(0, 0) >= 3 and sup.alive_count() == 0:
                    break
                time.sleep(0.2)
            assert sup.restarts().get(0, 0) == 3
        finally:
            sup.stop(timeout=30)
        loops = [e for e in events.recent() if e["name"] == "fleet.worker.crash_loop"]
        restarted = [e for e in events.recent() if e["name"] == "fleet.worker.restarted"]
        # respawns 2 and 3 each engaged a backoff window first
        assert len(loops) == 2 and len(restarted) == 3
        assert all(e["severity"] == "warn" for e in loops)
        assert all(e["fields"]["worker_id"] == 0 for e in loops)
        delays = [e["fields"]["delay_s"] for e in loops]
        assert 0.4 <= delays[0] <= 0.5  # base x (1 + jitter<0.25)
        assert 0.8 <= delays[1] <= 1.0  # base x 2 x (1 + jitter)
        # the scheduled delay was actually waited out: the respawn event
        # lands no earlier than crash_loop + delay
        for loop in loops:
            after = min(
                (e for e in restarted if e["seq"] > loop["seq"]),
                key=lambda e: e["seq"],
            )
            assert after["ts"] - loop["ts"] >= loop["fields"]["delay_s"] - 0.05


def _lease_holder(sf_dir, name, ready_q):
    """Child: take the single-flight lease for `name` and hang until
    killed (the crashed-holder simulation)."""
    from pathlib import Path

    from hyperspace_tpu.serve.fleet.lease import FileLease
    from hyperspace_tpu.serve.fleet.singleflight import key_name as _kn

    lease = FileLease(Path(sf_dir) / f"{_kn(name)}.lease", ttl_s=300)
    claim = lease.try_acquire()
    ready_q.put("held" if claim is not None else "failed")
    time.sleep(300)


def _crasher(ctx, marker_dir):
    """Child: record the attempt, then die with a non-zero exit."""
    from pathlib import Path

    Path(marker_dir, f"pid-{os.getpid()}").write_text("x")
    raise SystemExit(3)


def _fault_probe(ctx, marker_dir):
    """Child: report whether the coordinator's registered fault rule
    fired INSIDE this spawned fleet worker (fresh module state — the
    rule can only be here if the supervisor shipped it)."""
    from pathlib import Path

    from hyperspace_tpu import faults

    try:
        faults.fault_point("fleet.lease.acquire", "probe")
        out = "no-fault"
    except faults.FaultError:
        out = "fault-fired"
    Path(marker_dir, f"{ctx.worker_id}.txt").write_text(out)


class TestSupervisorFaultContinuity:
    def test_fault_rules_ship_into_fleet_workers(self, tmp_path):
        """The HSL022 contract at runtime (the fleet half of procpool's
        cross-process injection test): a rule registered in the
        coordinator fires inside a spawned fleet worker because
        FleetSupervisor ships faults.export_state() through the worker
        shim."""
        from hyperspace_tpu import faults

        marker = tmp_path / "probe"
        marker.mkdir()
        faults.inject("fleet.lease.acquire", times=1)
        try:
            sup = fleet.FleetSupervisor(
                _fault_probe, fleet_dir=str(tmp_path / "fleet"), n=1,
                args=(str(marker),), max_restarts=0,
            )
            with sup:
                sup.start()
                deadline = time.monotonic() + 60
                out = marker / "0.txt"
                while not out.exists() and time.monotonic() < deadline:
                    time.sleep(0.05)
        finally:
            faults.reset()
        assert out.read_text() == "fault-fired"

    def test_export_state_carries_brownout_schedule(self):
        """The spawn-shipping contract covers the slow path too: a
        delay rule's full brownout schedule (delay, jitter, the
        configured clamp) survives export_state -> install_state, with
        fresh per-process call counters."""
        from hyperspace_tpu import faults

        faults.inject("bucket.read", delay_s=0.25, jitter_s=0.05, times=3)
        faults.set_max_delay(12.0)
        try:
            state = faults.export_state()
            (rule,) = state["rules"]
            assert rule.delay_s == 0.25 and rule.jitter_s == 0.05
            assert rule.calls == 0 and rule.fired == 0  # fresh schedule
            assert state["max_delay_s"] == 12.0
            # a "worker": install and verify the delay actually applies
            faults.reset()
            faults.install_state(state)
            slept = []
            faults.set_sleeper(slept.append)
            faults.fault_point("bucket.read")
            assert sum(slept) == pytest.approx(
                0.25 + 0.05 * ((1 * 2654435761) % 1000) / 1000.0
            )
        finally:
            faults.set_max_delay(30.0)
            faults.reset()


# -- obs/http port=0 satellite ------------------------------------------------

class TestEphemeralHealthPort:
    def test_healthz_reports_bound_port(self):
        from hyperspace_tpu.obs.http import HealthServer

        hs = HealthServer(port=0).start()
        try:
            assert hs.port and hs.port > 0  # kernel-picked ephemeral port
            doc = hs.healthz()
            assert doc["endpoint"] == {"host": "127.0.0.1", "port": hs.port}
        finally:
            hs.stop()

    def test_two_servers_two_ports_one_host(self):
        """The reason port=0 is the fleet default: two health planes on
        one host never fight over a configured port."""
        from hyperspace_tpu.obs.http import HealthServer

        a = HealthServer(port=0).start()
        b = HealthServer(port=0).start()
        try:
            assert a.port != b.port
        finally:
            a.stop()
            b.stop()


def _journaling_member(ctx):
    """Child: journal root spans forever (the supervisor shipped the
    parent's journal config in via env, so this member writes its own
    `<_obs>/<pid>/` segments) until stopped or killed."""
    from hyperspace_tpu.obs import trace as _trace

    i = 0
    while not ctx.stop_event.is_set():
        with _trace.trace("member.query") as _:
            i += 1
        time.sleep(0.005)


class TestFleetJournal:
    def test_sigkilled_member_journal_merges_into_fleet_chrome_trace(
        self, tmp_path
    ):
        """The flight-recorder promise end to end: a fleet member dies by
        a REAL SIGKILL mid-write, and its durable journal segments still
        merge into the fleet chrome trace on a pid-qualified lane —
        post-mortem observability does not require the process."""
        from hyperspace_tpu.obs import export as obs_export
        from hyperspace_tpu.obs import journal

        jroot = tmp_path / "_obs"
        # Small segments so the member seals quickly; the supervisor
        # ships this exact config into the spawned member.
        journal.configure(
            enabled=True, root=str(jroot), segment_bytes=4096
        )
        sup = fleet.FleetSupervisor(
            _journaling_member, fleet_dir=str(tmp_path / "fleet"), n=1,
            max_restarts=0,
        )
        sup.start()
        pid = None
        try:
            deadline = time.monotonic() + 180
            while time.monotonic() < deadline:
                p = sup._host.get(0)
                if p is not None and p.pid is not None:
                    pid = p.pid
                    if journal.segment_paths(jroot / str(pid)):
                        break  # at least one sealed segment on disk
                time.sleep(0.05)
            assert pid is not None and journal.segment_paths(jroot / str(pid))
            os.kill(pid, signal.SIGKILL)  # no cleanup handlers run
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline and sup.alive_count() > 0:
                time.sleep(0.05)
            assert sup.alive_count() == 0
        finally:
            sup.stop(timeout=30)
        # The dead member's sealed history survives and merges: a
        # `process` start marker (install_state) and its root spans.
        merged = journal.merge_dir(jroot)
        member_recs = [r for r in merged if r["pid"] == pid]
        assert any(r["kind"] == "process" for r in member_recs)
        spans = [r for r in member_recs if r["kind"] == "span"]
        assert spans and all(
            r["trace"]["name"] == "member.query" for r in spans
        )
        # Fleet chrome export lanes the dead member by pid.
        doc = obs_export.chrome_trace(obs_export.roots_from_fleet(str(jroot)))
        slices = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert {e["pid"] for e in slices} >= {pid}
        names = [
            e["args"]["name"] for e in doc["traceEvents"]
            if e["ph"] == "M" and e["name"] == "process_name"
        ]
        assert f"member pid {pid}" in names
        # The kill tore at most the active tmp tail; sweep reaps it
        # without touching sealed history.
        before = journal.merge_dir(jroot)
        journal.sweep(jroot)
        assert journal.merge_dir(jroot) == before
        assert not [
            p for p in (jroot / str(pid)).iterdir()
            if p.name.startswith(".tmp-seg-")
        ]
