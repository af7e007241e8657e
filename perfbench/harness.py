"""One run of one benchmark cell: set-up, a measured window, the check.

Everything a cell is made of is found by name, one file each:
``BENCHMARK.json`` names the cell's configuration, traffic mix and
metrics; ``configs/<config>.json`` holds the deployment,
``traffic/<mix>.json`` the mix, ``ops/<op>.py`` how each operation of a
mix drives the program, ``refs/<op>.py`` its plain reference, and
``metrics/<metric>.py`` the reader of each metric. A later cell or
metric is added by adding files and entries; this file does not change.

A run: check the chips; generate the tables from the seed; build the
indexes the mix needs through the ``Hyperspace`` facade; warm up every
shape with operations drawn from a stream of their own; then, for
``--seconds``, one closed-loop client issues the mix's operations one
after another. The last operation started in the window runs to its
end, and the window ends with it. Once it has closed, the device's
memory peak is read, the program's caches are dropped, and every
answer is compared with the reference.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
WORK = BENCH / ".work"
COMPILE_CACHE = BENCH / ".jax_cache"


def say(**kv) -> None:
    print(json.dumps(kv, default=str), flush=True)


# -- the cell, by name ---------------------------------------------------------

def load_module(kind: str, name: str):
    """``<kind>/<name>.py`` under the benchmark, as a module."""
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise SystemExit(f"perfbench: no {kind} file {path.relative_to(ROOT)} for {name!r}")
    mod_name = f"perfbench.{kind}.{name.replace('.', '_')}"
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list
    ops: dict  # op name -> ops/<op>.py module
    refs: dict  # op name -> refs/<op>.py module


def load_cell(name: str, spec_file: Path = ROOT / "BENCHMARK.json") -> Cell:
    spec = json.loads(spec_file.read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"perfbench: no workload {name!r} in {spec_file.name}")
    w = cells[name]
    cfg_entry = next(c for c in spec["configs"] if c["name"] == w["config"])
    config = json.loads((ROOT / cfg_entry["file"]).read_text())
    traffic = json.loads((BENCH / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in spec["end_to_end"] if name in m.get("workloads", [name])]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [
        m for m in spec["per_layer"]
        if (name in m["workloads"] if "workloads" in m else m["moves"] in e2e_names)
    ]
    op_names = sorted({e["op"] for e in traffic["block"]})
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        end_to_end=e2e, per_layer=per_layer,
        ops={o: load_module("ops", o) for o in op_names},
        refs={o: load_module("refs", o) for o in op_names},
    )


# -- chips, compiles -----------------------------------------------------------

def check_devices(chips: int, allow_cpu: bool):
    """The devices of the run. Refuses any platform but TPU, and fewer
    chips than the cell asks for: the run then prints no result."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not allow_cpu:
        raise SystemExit(f"perfbench: JAX finds no TPU (platform {dev.platform!r}); refusing to run")
    if len(devices) < chips:
        raise SystemExit(f"perfbench: the cell needs {chips} chips, JAX finds {len(devices)}")
    return devices


def enable_compile_cache(cache_dir: Path) -> None:
    """JAX's persistent compilation cache at one fixed path inside the
    checkout, for every program however short its compile."""
    import jax

    cache_dir.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(cache_dir))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileClock:
    """Counts XLA backend compiles reported through jax.monitoring."""

    def __init__(self):
        import jax.monitoring

        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event.endswith("backend_compile_duration"):
            self.count += 1
            self.seconds += duration


class GcClock:
    """Full (generation 2) garbage collections and their pauses: a long
    one inside the window shows as a latency outlier."""

    def __init__(self):
        self.count, self.seconds, self.longest = 0, 0.0, 0.0
        self._t0 = None
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: dict) -> None:
        if info["generation"] != 2:
            return
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            pause = time.perf_counter() - self._t0
            self.count, self.seconds = self.count + 1, self.seconds + pause
            self.longest = max(self.longest, pause)


# -- the program under test ----------------------------------------------------

class Ctx:
    """What an op needs to drive the program."""

    def __init__(self, session, hs, scans: dict, system_path: Path, trace: bool):
        self.session, self.hs, self.scans = session, hs, scans
        self.system_path = system_path
        self.trace = trace
        self.label = ""

    def annotate(self, kind: str):
        if not self.trace:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(f"{kind}:{self.label}")

    def annotate_window(self):
        if not self.trace:
            return contextlib.nullcontext()
        import jax

        from perfbench.xplane import WINDOW

        return jax.profiler.TraceAnnotation(WINDOW)

    def run(self, plan):
        """The user-visible answer of a query: run, then decode on the host."""
        with self.annotate("run"):
            result = self.session.run(plan)
        with self.annotate("decode"):
            return result.to_arrow()

    def index_root(self, name: str) -> Path:
        return self.system_path / name


def make_session(config: dict, system_path: Path, devices, chips: int):
    from hyperspace_tpu import Hyperspace, HyperspaceSession
    from hyperspace_tpu.parallel.mesh import make_mesh

    session = HyperspaceSession(
        system_path=str(system_path), num_buckets=config["num_buckets"],
        mesh=make_mesh(devices[:chips]),
    )
    for key, value in config["session"].items():
        session.conf.set(key, value)
    return session, Hyperspace(session)


def generate(config: dict, tables, work: Path, seed: int) -> tuple[dict, dict]:
    from perfbench import datagen

    roots, rows = {}, {}
    for t in sorted(tables):
        roots[t] = work / "data" / t
        rows[t] = datagen.GENERATORS[t](roots[t], config["scale_factor"], seed)
    return roots, rows


def build_indexes(ctx: Ctx, config: dict, names, roots: dict) -> list:
    from hyperspace_tpu import IndexConfig

    out = []
    for spec in config["indexes"]:
        if spec["name"] not in names:
            continue
        t0 = time.perf_counter()
        ctx.hs.create_index(
            ctx.scans[spec["table"]], IndexConfig(spec["name"], spec["indexed"], spec["included"])
        )
        out.append({"index": spec["name"], "seconds": time.perf_counter() - t0,
                    "path": ctx.session.last_build_stats.get("path")})
    return out


@dataclasses.dataclass
class Op:
    name: str
    kind: str
    params: dict
    latency_s: float
    answer: object = None
    error: str | None = None
    evidence: dict = dataclasses.field(default_factory=dict)


def evidence_of(ctx: Ctx, kind: str) -> dict:
    """What the program says it did for the op just finished."""
    if kind == "query":
        rec = ctx.session.workload.snapshot()[-1]
        return {"indexes": tuple(rec.index_names), "profile": rec.profile}
    return {"build": ctx.session.last_build_stats}


def execute(ctx: Ctx, cell: Cell, name: str, params: dict, i: int) -> Op:
    mod = cell.ops[name]
    ctx.label = f"{name}#{i}"
    start = time.perf_counter()
    with ctx.annotate("op"):
        try:
            answer, error = mod.execute(ctx, params), None
        except Exception as e:  # a failed op is counted, and the run goes on
            answer, error = None, f"{type(e).__name__}: {e}"
    end = time.perf_counter()
    op = Op(name, mod.KIND, params, end - start, answer, error)
    if error is None:
        op.evidence = evidence_of(ctx, mod.KIND)
    return op


def window(ctx: Ctx, cell: Cell, blocks, seconds: float) -> tuple[list, float]:
    """Closed loop over whole blocks of the mix, for `seconds`: the
    block started last runs to its end, and the window ends with it."""
    done = []
    t0 = time.perf_counter()
    with ctx.annotate_window():
        while time.perf_counter() - t0 < seconds:
            for name, params in next(blocks):
                done.append(execute(ctx, cell, name, params, len(done)))
    return done, time.perf_counter() - t0


def latency_summary(done: list) -> dict:
    """Per op class: count, median and max latency in ms."""
    out = {}
    for name in sorted({op.name for op in done}):
        lat = [op.latency_s * 1e3 for op in done if op.name == name and op.error is None]
        if lat:
            out[name] = [len(lat), float(np.median(lat)), max(lat)]
    return out


# -- the check -----------------------------------------------------------------

HOST_MARKERS = ("host", "numpy", "native")
DEVICE_MARKERS = ("device", "fused-xla-mask", "mesh-sharded")
HOST_BY_DESIGN = ("Project", "TableScan")


def off_device(profile_json: dict) -> list:
    """Operators and executor stats of one query that name a host venue,
    and operators with compute of their own that name no device kernel."""
    def fields(d):
        return [str(v).lower() for k, v in d.items()
                if isinstance(v, str) and any(s in k for s in ("venue", "kernel", "path"))]

    bad = [f for f in fields(profile_json["stats"]) if any(m in f for m in HOST_MARKERS)]
    stack = [profile_json["operators"]]
    while stack:
        node = stack.pop()
        if node is None:
            continue
        fs = fields(node.get("detail", {}))
        if any(m in f for f in fs for m in HOST_MARKERS):
            bad.append(f"{node['op']}: {fs}")
        elif node["op"] not in HOST_BY_DESIGN and not any(m in f for f in fs for m in DEVICE_MARKERS):
            bad.append(f"{node['op']}: no device kernel or venue")
        stack.extend(node.get("children", []))
    return bad


def answer_columns(answer):
    """A decoded Arrow answer as numpy columns; other answers as they are."""
    if hasattr(answer, "column_names"):
        return {n: answer.column(n).to_numpy() for n in answer.column_names}
    return answer


def check(cell: Cell, done: list, data, seed: int) -> dict:
    """The numbers compared, each {value, limit}; all limits are exact.
    Every query answer is compared; of the index builds, the last and
    one drawn from the seed (reading an index back takes seconds)."""
    counts: dict = {"failed_ops": sum(op.error is not None for op in done)}
    ok = [op for op in done if op.error is None]
    queries = [op for op in ok if op.kind == "query"]
    if queries:
        counts["not_from_index"] = sum(
            set(op.evidence["indexes"]) != set(cell.ops[op.name].INDEXES) for op in queries
        )
        counts["off_device_ops"] = sum(bool(op.evidence["off_device"]) for op in queries)
    builds = [op for op in ok if op.kind == "build"]
    checked = queries
    if builds:
        rng = np.random.default_rng([seed, 3])
        pick = {len(builds) - 1, int(rng.integers(0, len(builds)))}
        checked = queries + [builds[i] for i in sorted(pick)]
    wants: dict = {}
    for op in checked:
        key = (op.name, json.dumps(op.params, sort_keys=True))
        if key not in wants:
            wants[key] = cell.refs[op.name].answer(op.params, data)
        for k, v in cell.refs[op.name].compare(op.answer, wants[key]).items():
            counts[k] = counts.get(k, 0) + v
    for name in sorted({op.name for op in builds}):
        seq = [op.answer for op in builds if op.name == name]
        for k, v in cell.refs[name].compare_sequence(seq).items():
            counts[k] = counts.get(k, 0) + v
    return {k: {"value": v, "limit": 0} for k, v in counts.items()}


# -- metrics -------------------------------------------------------------------

@dataclasses.dataclass
class Run:
    """What the metric readers read (see metrics/*.py)."""

    cell: Cell
    ops: list  # the window's completed ops
    window_s: float
    setup_s: float
    rows: dict  # table -> rows generated
    cache: dict  # decoded-table cache hits and misses over the window
    device_kind: str
    trace: dict | None = None


def read_metrics(run: Run, metrics: list) -> dict:
    out = {}
    for m in metrics:
        value = load_module("metrics", m["name"]).read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# -- main ----------------------------------------------------------------------

def main(args, allow_cpu: bool = False, scale_factor: float | None = None,
         work: Path | None = None, cache_dir: Path = COMPILE_CACHE) -> int:
    t_start = time.perf_counter()
    cell = load_cell(args.workload)
    if scale_factor is not None:
        cell.config = {**cell.config, "scale_factor": scale_factor}
    devices = check_devices(cell.chips, allow_cpu)
    enable_compile_cache(cache_dir)
    clock = CompileClock()
    work = work or WORK / cell.name
    shutil.rmtree(work, ignore_errors=True)
    work.mkdir(parents=True)
    try:
        return _run(args, cell, devices, clock, work, t_start)
    finally:
        shutil.rmtree(work, ignore_errors=True)


def _run(args, cell: Cell, devices, clock: CompileClock, work: Path, t_start: float) -> int:
    import jax

    from hyperspace_tpu.execution import io as hio
    from perfbench import generator, xplane
    from perfbench.refs.data import Data

    seed = int(args.seed)
    config = cell.config
    tables = {t for m in cell.ops.values() for t in m.TABLES}
    t0 = time.perf_counter()
    roots, rows = generate(config, tables, work, seed)
    t_gen = time.perf_counter() - t0
    say(phase="generate", seconds=t_gen, rows=rows)

    system_path = work / "indexes"
    session, hs = make_session(config, system_path, devices, cell.chips)
    scans = {t: session.parquet(r) for t, r in roots.items()}
    ctx = Ctx(session, hs, scans, system_path, trace=bool(args.trace))
    needed = set()
    for e in cell.traffic["block"]:
        mod = cell.ops[e["op"]]
        needed |= set(getattr(mod, "setup_indexes", lambda spec: mod.INDEXES)(e))
    builds = build_indexes(ctx, config, needed, roots)
    say(phase="build", builds=builds, compiles=clock.count, compile_s=clock.seconds)
    session.enable_hyperspace()

    domain = int(config["key_domain"]["rows_per_scale_factor"] * config["scale_factor"])
    t0 = time.perf_counter()
    warm = generator.blocks(cell.traffic, cell.ops, domain, seed, "warmup")
    warm_ops = [
        execute(ctx, cell, name, params, i)
        for _ in range(cell.traffic["warmup_blocks"])
        for i, (name, params) in enumerate(next(warm))
    ]
    warm_errors = [op.error for op in warm_ops if op.error]
    if warm_errors:
        raise RuntimeError(f"perfbench: warm-up failed: {warm_errors[0]}")
    t_warm = time.perf_counter() - t0
    setup_s = time.perf_counter() - t_start
    say(phase="setup", seconds=setup_s, warmup_s=t_warm, compiles=clock.count,
        compile_s=clock.seconds)

    # The newest traced window of each cell stays for inspection.
    trace_dir = WORK / f"trace-{cell.name}"
    cache0 = hio.table_cache_stats()
    compiles0, compile_s0 = clock.count, clock.seconds
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    gc_clock = GcClock()
    # A compile inside the window is logged with its shapes (stderr).
    jax.config.update("jax_log_compiles", True)
    done, window_s = window(ctx, cell, generator.blocks(
        cell.traffic, cell.ops, domain, seed, "window"), args.seconds)
    jax.config.update("jax_log_compiles", False)
    if args.trace:
        jax.profiler.stop_trace()
    compiles = clock.count - compiles0
    cache1 = hio.table_cache_stats()
    say(phase="window", seconds=window_s, ops=len(done), compiles_in_window=compiles,
        compile_s_in_window=clock.seconds - compile_s0, latency_ms=latency_summary(done),
        full_gc=[gc_clock.count, gc_clock.seconds, gc_clock.longest])
    gc.callbacks.remove(gc_clock._on)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices[:cell.chips])

    for op in done:
        op.answer = answer_columns(op.answer)
        if op.kind == "query" and op.error is None:
            op.evidence["profile"] = op.evidence["profile"].to_json()
            op.evidence["off_device"] = off_device(op.evidence["profile"])
    trace = None
    if args.trace and devices[0].platform == "tpu":
        t0 = time.perf_counter()
        trace = xplane.reduce(xplane.read(xplane.find_trace(trace_dir)))
        say(phase="trace", read_s=time.perf_counter() - t0, busy_s=trace["busy_s"],
            window_s=trace["window_s"])

    # The program's state goes before the reference runs.
    del ctx, session, hs, scans
    hio.clear_table_cache()
    gc.collect()

    t0 = time.perf_counter()
    checks = check(cell, done, Data(roots, config), seed)
    say(phase="reference", seconds=time.perf_counter() - t0)

    run = Run(
        cell=cell, ops=[op for op in done if op.error is None], window_s=window_s,
        setup_s=setup_s, rows=rows,
        cache={k: cache1[k] - cache0[k] for k in ("hits", "misses")},
        device_kind=devices[0].device_kind, trace=trace,
    )
    metrics = read_metrics(run, cell.per_layer if args.trace else cell.end_to_end)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
              "memory_peak_bytes": int(peak)}
    correct = bool(done) and all(c["value"] <= c["limit"] for c in checks.values())
    result = {
        "correct": correct,
        "attempted": len(done),
        "failed": sum(op.error is not None for op in done),
        "metrics": metrics,
        "device": device,
    }
    if trace is not None:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        result["breakdown"] = {"device_ops": trace["device_ops"], "idle_gaps": trace["idle_gaps"]}
    result["checks"] = checks
    errors = [op.error for op in done if op.error]
    if errors:
        print(f"perfbench: {len(errors)} ops failed; first: {errors[0]}", file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name} {c['value']} limit {c['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
