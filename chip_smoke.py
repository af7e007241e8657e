#!/usr/bin/env python3
"""Chip smoke: the main path on a TPU, checked against a plain reference.

Builds two covering indexes over TPC-H SF1 ``lineitem`` and ``orders``
(generated from a seed by ``benchmarks/datagen.py``) through the public
``Hyperspace`` facade, then runs indexed point lookups, a range filter,
a filtered group-by, the ``orders`` x ``lineitem`` equi-join, an
``Aggregate(Join)`` and an ``ORDER BY ... LIMIT``. Every operator venue
is forced to the device, every answer is compared with pyarrow/numpy
over the same parquet files, and the last line of standard output is
the one JSON object the chip check reads.

    python chip_smoke.py                  # one chip, SF1
    python chip_smoke.py --chips 4        # 4-device mesh build + queries vs one device

A rehearsal on CPU needs ``JAX_PLATFORMS=cpu``, a smaller ``--sf`` and
``--allow-cpu-rehearsal``; the default run refuses any platform but TPU.
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent
WORK = REPO / ".smoke_work"  # listed in .gitignore; wiped at start and end
NUM_BUCKETS = 200  # as bench.py
QTY_MAX = 40  # the range filter's residual conjunct: l_quantity <= 40
LI_INDEXED = ["l_orderkey"]
LI_INCLUDED = ["l_partkey", "l_quantity", "l_extendedprice", "l_discount"]
O_INDEXED = ["o_orderkey"]
O_INCLUDED = ["o_custkey", "o_totalprice"]
VENUE_KEYS = (
    "hyperspace.build.venue",
    "hyperspace.join.venue",
    "hyperspace.filter.venue",
    "hyperspace.agg.venue",
    "hyperspace.sort.venue",
)
# Executor stats and operator fields that name a host kernel or venue. A
# query whose profile holds one of these did not run that operator on
# the chip.
HOST_MARKERS = ("host", "numpy", "native")
# Positive evidence: every operator must name a device kernel or venue
# holding one of these, unless it has no compute of its own.
DEVICE_MARKERS = ("device", "fused-xla-mask", "mesh-sharded")
# Column selection and the parquet decode below an ORDER BY: host work
# by design, listed per query in the output as `host_by_design`.
HOST_BY_DESIGN = ("Project", "TableScan")


def say(**kv) -> None:
    print(json.dumps(kv, default=str), flush=True)


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--sf", type=float, default=1.0, help="TPC-H scale factor")
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument(
        "--allow-cpu-rehearsal", action="store_true",
        help="run on CPU at --sf < 1 (never valid for the SF1 chip run)",
    )
    args = ap.parse_args(argv)
    if args.allow_cpu_rehearsal and args.sf >= 1.0:
        ap.error("--allow-cpu-rehearsal needs --sf < 1: the SF1 run is for the chip only")
    return args


def check_devices(args):
    import jax

    devices = jax.devices()
    dev = devices[0]
    say(phase="devices", platform=dev.platform, device_kind=dev.device_kind, count=len(devices))
    if dev.platform != "tpu" and not args.allow_cpu_rehearsal:
        raise SystemExit(f"chip_smoke: platform is {dev.platform!r}, not 'tpu' — refusing to run")
    if len(devices) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} but JAX sees {len(devices)} devices")
    from hyperspace_tpu.serve.fleet.supervisor import device_member_slots, tpu_chip_nodes

    # What the fleet supervisor's capacity check sees here: every chip
    # node this process can see, and the slots left while it holds them.
    say(phase="host_chips", nodes=tpu_chip_nodes(), member_slots=device_member_slots())
    return devices


class CompileClock:
    """Sums XLA backend compile seconds reported through jax.monitoring."""

    def __init__(self):
        import jax.monitoring

        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event.endswith("backend_compile_duration"):
            self.seconds += duration
            self.count += 1


def generate(sf: float, seed: int):
    import pyarrow.parquet as pq

    from benchmarks.datagen import gen_tpch_lineitem, gen_tpch_orders

    t0 = time.perf_counter()
    li_root, o_root = WORK / "lineitem", WORK / "orders"
    gen_tpch_lineitem(li_root, sf=sf, seed=seed)
    gen_tpch_orders(o_root, sf=sf, seed=seed + 1)
    li = pq.read_table(li_root, columns=LI_INDEXED + LI_INCLUDED)
    orders = pq.read_table(o_root, columns=O_INDEXED + O_INCLUDED)
    say(
        phase="datagen", sf=sf, seed=seed, seconds=time.perf_counter() - t0,
        lineitem_rows=li.num_rows, orders_rows=orders.num_rows,
    )
    ref = {
        "li": {c: li.column(c).to_numpy() for c in li.column_names},
        "o": {c: orders.column(c).to_numpy() for c in orders.column_names},
    }
    return li_root, o_root, ref


def make_session(system_path: Path, mesh=None):
    from hyperspace_tpu import Hyperspace, HyperspaceSession
    from hyperspace_tpu.config import DEVICE_FUSED_KERNELS

    session = HyperspaceSession(system_path=str(system_path), num_buckets=NUM_BUCKETS, mesh=mesh)
    for key in VENUE_KEYS:
        session.conf.set(key, "device")
    session.conf.set(DEVICE_FUSED_KERNELS, "auto")
    return session, Hyperspace(session)


def build_indexes(session, hs, li_root, o_root):
    from hyperspace_tpu import IndexConfig
    from hyperspace_tpu.parallel.mesh import mesh_for_parallelism, mesh_size

    devices = mesh_size(mesh_for_parallelism(session.mesh, NUM_BUCKETS))
    out = []
    for name, root, indexed, included in (
        ("li_orderkey", li_root, LI_INDEXED, LI_INCLUDED),
        ("o_orderkey", o_root, O_INDEXED, O_INCLUDED),
    ):
        ds = session.parquet(root)
        t0 = time.perf_counter()
        hs.create_index(ds, IndexConfig(name, indexed, included))
        say(phase="build", index=name, seconds=time.perf_counter() - t0,
            path=session.last_build_stats.get("path"), devices=devices)
        out.append(ds)
    return out


# -- answers ------------------------------------------------------------------

def rows_of(cols: dict, names: list[str]):
    """Row-order-free canonical form: the columns lexsorted together."""
    import numpy as np

    arrs = [np.asarray(cols[n]) for n in names]
    if not arrs or len(arrs[0]) == 0:
        return [a[:0] for a in arrs]
    order = np.lexsort(tuple(reversed(arrs)))
    return [a[order] for a in arrs]


def same_rows(got: dict, want: dict, names: list[str]) -> bool:
    import numpy as np

    g, w = rows_of(got, names), rows_of(want, names)
    return all(len(a) == len(b) and np.array_equal(a, b) for a, b in zip(g, w))


def result_columns(table, names: list[str]) -> dict:
    """Decoded host arrays of a ColumnTable result (the user-visible answer)."""
    out = table.to_arrow()
    return {n: out.column(n).to_numpy() for n in names}


def venues_of(session) -> dict:
    """Operator -> venue evidence from the last query's profile."""
    prof = session.last_profile().to_json()
    stats = prof["stats"]
    ev = {
        k: v for k, v in stats.items()
        if isinstance(v, str) and any(s in k for s in ("venue", "kernel", "path"))
    }
    ops = []

    def walk(node):
        if node is None:
            return
        ops.append({"op": node["op"], **{
            k: v for k, v in node.get("detail", {}).items()
            if isinstance(v, str) and any(s in k for s in ("venue", "kernel", "path"))
        }})
        for c in node.get("children", []):
            walk(c)

    walk(prof["operators"])
    return {"stats": ev, "operators": ops, "platform": prof["venue"].get("platform")}


def off_device(venues: dict) -> list[str]:
    """Operators and stats that name a host venue, and operators with
    compute that name no device kernel or venue."""
    bad = [
        f"{k}={v}" for k, v in venues["stats"].items()
        if any(m in v.lower() for m in HOST_MARKERS)
    ]
    for op in venues["operators"]:
        fields = [str(v).lower() for k, v in op.items() if k != "op"]
        if any(m in v for v in fields for m in HOST_MARKERS):
            bad.append(f"{op}")
        elif op["op"] not in HOST_BY_DESIGN and not any(
            m in v for v in fields for m in DEVICE_MARKERS
        ):
            bad.append(f"{op['op']}: no device kernel or venue")
    return bad


def run_query(session, name: str, plan, names: list[str], want):
    """Run `plan` cold then warm; check the warm answer and the venues.
    `want` is the reference columns, or a predicate over the answer."""
    from hyperspace_tpu import stats

    fused0 = stats.get("device.kernel.fused")
    t0 = time.perf_counter()
    session.run(plan)
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    result = session.run(plan)
    got = result_columns(result, names)  # host materialisation ends the clock
    warm = time.perf_counter() - t0
    fused = stats.get("device.kernel.fused") - fused0
    venues = venues_of(session)
    ok = bool(want(got) if callable(want) else same_rows(got, want, names))
    bad = off_device(venues)
    say(
        phase="query", name=name, rows=len(got[names[0]]), cold_seconds=cold,
        warm_seconds=warm, matches_reference=ok, fused_kernels=fused,
        off_device=bad, venues=venues,
        host_by_design=[o["op"] for o in venues["operators"] if o["op"] in HOST_BY_DESIGN],
    )
    if not ok:
        raise SystemExit(f"chip_smoke: {name} does not match the reference")
    if bad:
        raise SystemExit(f"chip_smoke: {name} ran operators off the device: {bad}")
    return got, fused


class Cases:
    """The smoke's queries over one session's (lineitem, orders) datasets,
    each with its reference answer computed from `ref` with numpy alone."""

    def __init__(self, li, od, ref, seed: int):
        import numpy as np

        self.li, self.od = li, od
        self.L, self.O = ref["li"], ref["o"]
        self.n_orders = len(self.O["o_orderkey"])
        self.keys = np.random.default_rng(seed).integers(0, self.n_orders, 12)
        lo = self.n_orders // 3
        self.lo, self.hi = lo, lo + max(self.n_orders // 500, 10)
        L, O = self.L, self.O
        self.in_range = (
            (L["l_orderkey"] >= self.lo) & (L["l_orderkey"] < self.hi)
            & (L["l_quantity"] <= QTY_MAX)
        )
        pos = np.searchsorted(O["o_orderkey"], L["l_orderkey"])
        hit = (pos < self.n_orders) & (
            O["o_orderkey"][np.minimum(pos, self.n_orders - 1)] == L["l_orderkey"]
        )
        pos = pos[hit]
        self.joined = {
            "o_orderkey": O["o_orderkey"][pos],
            "o_custkey": O["o_custkey"][pos],
            "l_partkey": L["l_partkey"][hit],
            "l_quantity": L["l_quantity"][hit],
        }

    def lookups(self):
        from hyperspace_tpu import col

        names = ["l_orderkey", "l_partkey", "l_extendedprice"]
        for i, k in enumerate(self.keys):
            m = self.L["l_orderkey"] == k
            plan = self.li.filter(col("l_orderkey") == int(k)).select(*names)
            yield f"lookup_{i}", plan, names, {n: self.L[n][m] for n in names}

    def range_pred(self):
        """A key range the index slices out, and a residual conjunct on
        an included column that the device mask evaluates."""
        from hyperspace_tpu import col, lit

        return (
            (col("l_orderkey") >= lit(self.lo)) & (col("l_orderkey") < lit(self.hi))
            & (col("l_quantity") <= lit(QTY_MAX))
        )

    def range_filter(self):
        names = ["l_orderkey", "l_partkey", "l_quantity"]
        want = {n: self.L[n][self.in_range] for n in names}
        return "range_filter", self.li.filter(self.range_pred()).select(*names), names, want

    def range_group_agg(self):
        """Integral sums small enough for the fused segment-reduce
        kernel's float32 exactness rule."""
        import numpy as np

        from hyperspace_tpu import AggSpec

        m = self.in_range
        q_vals, q_inv = np.unique(self.L["l_quantity"][m], return_inverse=True)
        part = self.L["l_partkey"][m]
        want = {
            "l_quantity": q_vals,
            "n": np.bincount(q_inv).astype(np.int64),
            "s_qty": np.bincount(q_inv, weights=self.L["l_quantity"][m]),
            "max_part": np.array([part[q_inv == g].max() for g in range(len(q_vals))]),
        }
        plan = self.li.filter(self.range_pred()).aggregate(["l_quantity"], [
            AggSpec.of("count", None, "n"),
            AggSpec.of("sum", "l_quantity", "s_qty"),
            AggSpec.of("max", "l_partkey", "max_part"),
        ])
        return "range_group_agg", plan, list(want), want

    def join_plan(self):
        return self.od.select("o_orderkey", "o_custkey", "o_totalprice").join(
            self.li.select("l_orderkey", "l_partkey", "l_quantity"),
            ["o_orderkey"], ["l_orderkey"],
        )

    def join(self):
        names = list(self.joined)
        return "join", self.join_plan().select(*names), names, self.joined

    def join_aggregate(self):
        """Integral sums grouped by a low-cardinality key: the fused
        Aggregate(Join) path with the Pallas run-bounds kernel."""
        import numpy as np

        from hyperspace_tpu import AggSpec

        j = self.joined
        g_vals, g_inv = np.unique(j["l_quantity"], return_inverse=True)
        want = {
            "l_quantity": g_vals,
            "n": np.bincount(g_inv).astype(np.int64),
            "s_cust": np.bincount(g_inv, weights=j["o_custkey"]).astype(np.int64),
            "s_part": np.bincount(g_inv, weights=j["l_partkey"]).astype(np.int64),
        }
        plan = self.join_plan().aggregate(["l_quantity"], [
            AggSpec.of("count", None, "n"),
            AggSpec.of("sum", "o_custkey", "s_cust"),
            AggSpec.of("sum", "l_partkey", "s_part"),
        ])
        return "join_aggregate", plan, list(want), want

    def order_by_limit(self):
        """ORDER BY price DESC, key LIMIT 10: the (price, key) sequence is
        defined; a tie on both may pick either row, which must exist."""
        import numpy as np

        L = self.L
        order = np.lexsort((L["l_orderkey"], -L["l_extendedprice"]))[:10]
        names = ["l_extendedprice", "l_orderkey", "l_partkey"]
        plan = self.li.select(*names).sort(
            [("l_extendedprice", False), ("l_orderkey", True)]
        ).limit(10)

        def want(got):
            if not (
                np.array_equal(got["l_extendedprice"], L["l_extendedprice"][order])
                and np.array_equal(got["l_orderkey"], L["l_orderkey"][order])
            ):
                return False
            return all(
                bool(np.any(
                    (L["l_orderkey"] == k) & (L["l_partkey"] == p) & (L["l_extendedprice"] == e)
                ))
                for e, k, p in zip(got["l_extendedprice"], got["l_orderkey"], got["l_partkey"])
            )

        return "order_by_limit", plan, names, want


def single_chip(args, devices, clock):
    li_root, o_root, ref = generate(args.sf, args.seed)
    session, hs = make_session(WORK / "indexes")
    compile0 = clock.seconds
    li, od = build_indexes(session, hs, li_root, o_root)
    session.enable_hyperspace()
    cases = Cases(li, od, ref, args.seed)
    for case in cases.lookups():
        run_query(session, *case)
    for make in (cases.range_filter, cases.range_group_agg, cases.join):
        run_query(session, *make())
    _, fused = run_query(session, *cases.join_aggregate())
    if fused <= 0:
        raise SystemExit("chip_smoke: no fused Pallas kernel ran in the Aggregate(Join) query")
    run_query(session, *cases.order_by_limit())
    say(phase="compile", seconds=clock.seconds - compile0, programs=clock.count)


def four_chips(args, devices, clock):
    """The lineitem build over a 4-device mesh and over one device, then
    the range filter and the join on both sessions: every answer against
    the reference and the mesh's against the one device's."""
    from hyperspace_tpu.parallel.mesh import make_mesh, mesh_for_parallelism

    li_root, o_root, ref = generate(args.sf, args.seed)
    mesh = make_mesh(devices[:4])
    placement = {
        "make_mesh": mesh_placement(mesh),
        "mesh_for_parallelism": mesh_placement(mesh_for_parallelism(mesh, NUM_BUCKETS)),
    }
    say(phase="mesh", devices=placement)
    if any(ids != [d.id for d in devices[:4]] for ids in placement.values()):
        raise SystemExit(f"chip_smoke: mesh shards are not on four devices: {placement}")
    answers = {}
    for label, m in (("one", make_mesh(devices[:1])), ("mesh4", mesh)):
        session, hs = make_session(WORK / f"indexes_{label}", mesh=m)
        li, od = build_indexes(session, hs, li_root, o_root)
        session.enable_hyperspace()
        cases = Cases(li, od, ref, args.seed)
        answers[label] = {}
        for make in (cases.range_filter, cases.join):
            name, plan, names, want = make()
            got, _ = run_query(session, f"{name}_{label}", plan, names, want)
            answers[label][name] = (got, names)
    same = same_index_files(
        WORK / "indexes_one" / "li_orderkey", WORK / "indexes_mesh4" / "li_orderkey"
    )
    say(phase="compare_builds", identical=same)
    if not same:
        raise SystemExit("chip_smoke: the 4-device build differs from the one-device build")
    agree = all(
        same_rows(got, answers["one"][name][0], names)
        for name, (got, names) in answers["mesh4"].items()
    )
    say(phase="compare_queries", identical=agree)
    if not agree:
        raise SystemExit("chip_smoke: 4-device answers differ from one-device answers")
    say(phase="compile", seconds=clock.seconds, programs=clock.count)


def mesh_placement(mesh) -> list[int]:
    """Device ids holding the shards of a mesh-sharded array."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    x = jax.device_put(jnp.arange(4 * 128), NamedSharding(mesh, P(mesh.axis_names)))
    return sorted(s.device.id for s in x.addressable_shards)


def same_index_files(a: Path, b: Path) -> bool:
    """Both builds hold the same bucket row sets and the same manifests."""
    import pyarrow.parquet as pq

    def version_dir(root: Path) -> Path:
        return sorted(p for p in root.iterdir() if p.name.startswith("v__="))[-1]

    da, db = version_dir(a), version_dir(b)
    fa = sorted(p.relative_to(da) for p in da.rglob("*") if p.is_file())
    fb = sorted(p.relative_to(db) for p in db.rglob("*") if p.is_file())
    if fa != fb:
        return False
    for rel in fa:
        pa_, pb_ = da / rel, db / rel
        if rel.suffix == ".parquet":
            ta, tb = pq.read_table(pa_), pq.read_table(pb_)
            if not ta.equals(tb):
                return False
        elif pa_.read_bytes() != pb_.read_bytes():
            return False
    return True


def main(argv=None) -> int:
    args = parse_args(argv)
    sys.path.insert(0, str(REPO))
    devices = check_devices(args)
    clock = CompileClock()
    shutil.rmtree(WORK, ignore_errors=True)
    WORK.mkdir(parents=True)
    try:
        if args.chips == 4:
            four_chips(args, devices, clock)
        else:
            single_chip(args, devices, clock)
        peak = (devices[0].memory_stats() or {}).get("peak_bytes_in_use")
        say(phase="memory", peak_bytes_in_use=peak)
    finally:
        shutil.rmtree(WORK, ignore_errors=True)
    dev = devices[0]
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
