"""Unified static-analysis driver: lint + whole-program rules + baseline.

    python -m hyperspace_tpu.analysis.check [paths...] [--format json]

One command runs everything the analysis subsystem knows how to check,
parsing every file exactly ONCE and feeding the same AST to the
per-file linter (HSL001-HSL008, analysis/lint.py) and the whole-program
engine (analysis/program.py → callgraph.py → locks.py):

- **HSL009 lock-order inversion** — the static lock-acquisition graph
  (lock held → locks reachable through the call graph inside the
  ``with`` body) must be cycle-free; findings carry a two-chain witness.
- **HSL010 config-key drift** — every ``hyperspace.*`` key that is
  get/set (or declared as a module constant) must be declared in
  ``config.KNOWN_KEYS`` (typo suggestions via edit distance); declared
  keys never read anywhere are dead and reported; the generated key
  table in docs/configuration.md must match the registry
  (``--write-config-docs`` regenerates it).
- **HSL011 resource/exception safety** — locks/spans/files acquired
  outside ``with``/``try-finally`` on a path that can raise.
- **HSL012 fault-point coverage** — ``faults.KNOWN_POINTS`` and the
  ``fault_point()``/``inject()`` call sites must agree in both
  directions.
- **HSL013 lockset data race** — shared state accessed under
  inconsistent locksets with a write in play, over the effect
  summaries (analysis/effects.py → races.py); two-path witness.
- **HSL014 atomicity violation** — torn check-then-act across released
  and re-acquired locks (memo-fill and re-check idioms exempt).
- **HSL015 jit-cache hygiene** — jit call sites manufacturing a fresh
  cache key per call (recompile storm / executable leak).
- **HSL016 error-contract drift** — every public entry point's
  statically observed escape set (analysis/raises.py) must be covered
  by its declared ``exceptions.ERROR_CONTRACTS`` entry, modulo the
  exception hierarchy; dead entries/types are findings and the
  generated docs/errors.md table is verified (``--write-error-docs``).
- **HSL017 swallowed crash/fault** — except clauses absorbing
  CrashPoint/FaultError/everything without re-raise or signal, and the
  retry-classification bypass.
- **HSL018 unwind safety** — every ``faults.KNOWN_POINTS`` entry must
  have a static propagation path to a recovery construct (witness
  chains land in the report's ``unwind_proof``), and ``+= 1``/``-= 1``
  pairs on shared state must be finally-balanced on raising paths.
- **HSL019-022 process domains** (analysis/procdomain.py) — the
  multi-process invariants over the inferred spawn domain
  (``SPAWN_ENTRY_POINTS``): spawn-import purity (no module a worker
  imports at start may import jax at module level), exchange-surface
  typing (only picklable plain data crosses TaskPool/ProcessHost/fleet
  boundaries), the shared-file protocol (atomic publish + TTL-reaped
  O_EXCL leases on exchange/fleet paths), and cross-boundary
  fault/telemetry continuity. The inferred domain graph lands in the
  report's ``process_domains``.
- **HSL023-026 trace domains** (analysis/tracedomain.py) — the
  device-plane invariants over the inferred trace domain (the closure
  of every function object handed to ``compat.jit``, ``shard_map``, or
  a Pallas ``pallas_call``): traced-effect purity (no host effect
  anywhere in a traced closure), signature-space boundedness (jit keys,
  static arguments and pad widths derive from declared bounded domains
  — ``compat.KNOWN_STATIC_DOMAINS``), donation/aliasing safety
  (zero-copy staged views are never mutated or donated; callers go
  through ``ColumnTable.own_arrays``), and kernel eligibility-ladder
  completeness (``ops.KNOWN_KERNELS``: every Pallas engagement proves
  an eligibility rule, its ``device.kernel.*`` counters, and no broad
  handler that would swallow a lowering error). The inferred trace graph, donation
  proof and per-kernel ladder proofs land in the report's
  ``trace_domains``.
- **HSL027-030 durability domains** (analysis/duradomain.py) — the
  crash-consistency invariants over the inferred durability domain (the
  call-graph closure writing under a declared ``DURABLE_ROOTS`` plane):
  atomic-publish completeness (every durable write reaches the
  mkstemp + fsync + ``os.replace`` idiom, generalizing HSL021 beyond
  lease/fleet paths — sites HSL027 claims are deduplicated out of
  HSL021), torn-window ordering (every ``TORN_WINDOWS`` exactly-once
  protocol statically orders its two writes AND arms a
  ``faults.KNOWN_POINTS`` entry inside the window, so the crash sweeps
  provably exercise each torn state), replay idempotence (durable file
  names reachable from ``REPLAY_ROOTS`` recovery/re-poll/takeover
  paths derive from cursor/log-id/generation values, never wall clock,
  pid or RNG), and snapshot-stamp discipline (pinned-snapshot contexts
  never read the live version vector). The inferred durability graph —
  roots, write sites, window proofs with their in-window fault-point
  witnesses, replay closures — lands in the report's
  ``durable_domains``.
- **Validator corpus** — a small set of known-good / known-bad logical
  plans is pushed through the plan validator (analysis/validator.py) as
  a self-test; skipped (with a note) when numpy isn't installed, so the
  dependency-free CI lint job still runs everything else.

Default paths: the package itself plus ``benchmarks/``, ``bench.py``
and ``tests/conftest.py`` (the satellite surfaces that feed CI), with a
narrow, justified allowlist for findings that are correct-but-benign in
single-threaded benchmark code (:data:`TEST_ALLOWLIST`).

**Baseline.** CI fails only on findings not present in the committed
``ANALYSIS_BASELINE.json`` (``--write-baseline`` refreshes it), so a
newly added rule with pre-existing findings can land without blocking
every unrelated PR, while any NEW finding fails immediately.

``--format sarif`` renders the same findings as SARIF 2.1.0 (the CI
code-scanning artifact); ``--changed`` restricts *reporting* to files
changed vs origin/main while the engine still indexes the whole program
(the fast local pre-push mode). Exit codes are format-independent:
0 = clean (no new findings), 1 = new findings, 2 = the analyzer itself
crashed.
"""

from __future__ import annotations

import argparse
import ast
import json
import pathlib
import sys

from hyperspace_tpu.analysis import lint as lint_mod
from hyperspace_tpu.analysis.callgraph import CallGraph
from hyperspace_tpu.analysis.effects import Effects
from hyperspace_tpu.analysis.lint import (
    EXIT_CLEAN,
    EXIT_FINDINGS,
    EXIT_INTERNAL_ERROR,
    Finding,
    RULES,
)
from hyperspace_tpu.analysis.duradomain import DurabilityDomains
from hyperspace_tpu.analysis.locks import LockGraph, resource_findings
from hyperspace_tpu.analysis.procdomain import ProcessDomains
from hyperspace_tpu.analysis.tracedomain import TraceDomains
from hyperspace_tpu.analysis.program import Program, _index_module, _module_name
from hyperspace_tpu.analysis.races import (
    atomicity_findings,
    jit_hygiene_findings,
    lockset_race_findings,
)
from hyperspace_tpu.analysis.raises import (
    DYNAMIC,
    Raises,
    declared_contracts,
    error_contract_findings,
    swallowed_findings,
    unwind_findings,
)

CONFIG_DRIFT = "HSL010"
FAULT_COVERAGE = "HSL012"
CONTRACT_DRIFT = "HSL016"

BASELINE_NAME = "ANALYSIS_BASELINE.json"
DOCS_BEGIN = "<!-- KNOWN_KEYS:begin (generated from config.KNOWN_KEYS — edit config.py, then run python -m hyperspace_tpu.analysis.check --write-config-docs) -->"
DOCS_END = "<!-- KNOWN_KEYS:end -->"
ERRORS_BEGIN = "<!-- ERROR_CONTRACTS:begin (generated from exceptions.ERROR_CONTRACTS + the HSL016 escape analysis — edit exceptions.py, then run python -m hyperspace_tpu.analysis.check --write-error-docs) -->"
ERRORS_END = "<!-- ERROR_CONTRACTS:end -->"

# (path suffix, rule) -> justification. The narrow test-only allowlist:
# entries must name code that is single-threaded by construction or
# otherwise exempt BY DESIGN — anything else gets fixed, not listed.
TEST_ALLOWLIST: dict[tuple[str, str], str] = {
    # TPC-DS datagen memoizes generated sales tables in a module dict.
    # Benchmarks are one process, one thread, by construction (the
    # harness forks fresh processes per scale) — the HSL008 race cannot
    # occur, and locking the datagen would suggest it is serve-safe when
    # it is not meant to be.
    ("benchmarks/tpcds.py", "HSL008"): "single-threaded benchmark datagen memo",
    # The load-harness client threads collect every error (BaseException
    # included — a CrashPoint must fail the bench) into a list the main
    # thread re-raises after join(); nothing is swallowed, the re-raise
    # just lives outside the handler.
    ("benchmarks/bench_serve.py", "HSL017"): "client threads store errors; main re-raises after join",
}


def _repo_root() -> pathlib.Path:
    return pathlib.Path(__file__).resolve().parent.parent.parent


def default_paths(root: pathlib.Path) -> list[pathlib.Path]:
    out = []
    for rel in ("hyperspace_tpu", "benchmarks", "bench.py", "tests/conftest.py"):
        p = root / rel
        if p.exists():
            out.append(p)
    return out


# -- shared-parse loading -----------------------------------------------------

def load_sources(paths: list[pathlib.Path]) -> tuple[list, list[Finding]]:
    """Parse every .py under `paths` once. Returns ([(name, path, source,
    tree)], findings-for-unparseable-files)."""
    sources, findings = [], []
    for p in paths:
        files = sorted(p.rglob("*.py")) if p.is_dir() else [p]
        for f in files:
            try:
                src = f.read_text()
            except OSError as e:
                findings.append(Finding(str(f), 0, 0, "HSL000", f"unreadable: {e}"))
                continue
            try:
                tree = ast.parse(src, filename=str(f))
            except SyntaxError as e:
                findings.append(Finding(str(f), e.lineno or 0, e.offset or 0,
                                        "HSL000", f"syntax error: {e.msg}"))
                continue
            sources.append((_module_name(f), str(f), src, tree))
    return sources, findings


def build_program(sources: list) -> Program:
    modules = {name: _index_module(name, path, src, tree)
               for name, path, src, tree in sources}
    return Program(modules)


# -- HSL010: config-key drift -------------------------------------------------

def config_key_findings(program: Program, usage_dirs: list[pathlib.Path]) -> list[Finding]:
    from hyperspace_tpu import config as config_mod

    declared = set(config_mod.KNOWN_KEYS)
    findings: list[Finding] = []
    config_module_names = {m.name for m in program.modules.values()
                           if m.path.endswith("hyperspace_tpu/config.py")}
    used: set[str] = set()
    # get/set call sites
    for fn in sorted(program.functions.values(), key=lambda f: (f.module, f.line)):
        mod = program.modules[fn.module]
        for acc in fn.config_accesses:
            used.add(acc.key)
            if acc.key in declared:
                continue
            if _suppressed(mod, acc.line, CONFIG_DRIFT):
                continue
            import difflib

            close = difflib.get_close_matches(acc.key, declared, n=1, cutoff=0.6)
            hint = f" — did you mean {close[0]!r}?" if close else ""
            findings.append(Finding(
                mod.path, acc.line, 0, CONFIG_DRIFT,
                f"config {'set' if acc.write else 'get'} of undeclared key "
                f"{acc.key!r}{hint} (declare it in config.KNOWN_KEYS; the "
                f"runtime rejects it too)",
            ))
    # hyperspace.* constants declared outside config.py
    for mod in program.modules.values():
        if mod.name in config_module_names:
            continue
        for name, val in sorted(mod.const_strings.items()):
            if val.startswith("hyperspace.") and val not in declared:
                findings.append(Finding(
                    mod.path, 0, 0, CONFIG_DRIFT,
                    f"module constant {name} declares key {val!r} outside "
                    f"config.KNOWN_KEYS — every hyperspace.* key lives in the "
                    f"one registry (move the declaration to config.py)",
                ))
    # dead keys: declared in KNOWN_KEYS but consumed by NOTHING — not
    # wired into the conf get/set dispatch, never get/set by key, never
    # referenced by constant name in another module, and never spelled
    # literally in the usage scan (tests). The registry-only key is the
    # drift this catches: documented, settable, and ignored. Only
    # meaningful when config.py itself is in the scanned set (a corpus
    # file scanned alone must not report the whole registry dead).
    if not config_module_names:
        return findings
    const_of_key = {}
    wired: set[str] = set()
    for mname in config_module_names:
        mod = program.modules[mname]
        for cname, val in mod.const_strings.items():
            const_of_key[val] = cname
        wired |= {const for const in _dispatch_references(mod.tree)}
    other_sources = [m.source for m in program.modules.values()
                     if m.name not in config_module_names]
    for d in usage_dirs:
        for f in sorted(d.rglob("*.py")) if d.is_dir() else [d]:
            try:
                other_sources.append(f.read_text())
            except OSError:
                continue
    config_paths = [program.modules[m].path for m in config_module_names]
    for key in sorted(declared - used):
        cname = const_of_key.get(key)
        if cname is not None and cname in wired:
            continue
        if any(
            (cname is not None and cname in src) or key in src
            for src in other_sources
        ):
            continue
        findings.append(Finding(
            config_paths[0] if config_paths else "hyperspace_tpu/config.py", 0, 0,
            CONFIG_DRIFT,
            f"declared key {key!r} is dead: not wired into the conf get/set "
            f"dispatch and never referenced anywhere — wire it up or delete "
            f"it from KNOWN_KEYS",
        ))
    return findings


def _dispatch_references(config_tree: ast.Module) -> set[str]:
    """Constant names config.py references OUTSIDE their own definition
    and the KNOWN_KEYS literal — i.e. names the get/set dispatch (or any
    other real code) actually consumes."""
    skip_ids: set[int] = set()
    for node in ast.walk(config_tree):
        if isinstance(node, ast.Assign):
            is_const_def = any(
                isinstance(t, ast.Name) and t.id.isupper() for t in node.targets
            )
            is_registry = any(
                isinstance(t, ast.Name) and t.id == "KNOWN_KEYS" for t in node.targets
            )
            if is_registry:
                for sub in ast.walk(node.value):
                    skip_ids.add(id(sub))
            elif is_const_def and isinstance(node.value, ast.Constant):
                for t in node.targets:
                    skip_ids.add(id(t))
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            if isinstance(node.target, ast.Name) and node.target.id == "KNOWN_KEYS":
                for sub in ast.walk(node.value):
                    skip_ids.add(id(sub))
    return {
        node.id
        for node in ast.walk(config_tree)
        if isinstance(node, ast.Name)
        and isinstance(node.ctx, ast.Load)
        and node.id.isupper()
        and id(node) not in skip_ids
    }


def docs_findings(root: pathlib.Path) -> list[Finding]:
    """The generated key table in docs/configuration.md must match
    config.KNOWN_KEYS exactly."""
    from hyperspace_tpu import config as config_mod

    doc = root / "docs" / "configuration.md"
    if not doc.exists():
        return []
    text = doc.read_text()
    if DOCS_BEGIN not in text or DOCS_END not in text:
        return [Finding(str(doc), 0, 0, CONFIG_DRIFT,
                        "docs/configuration.md has no KNOWN_KEYS generated-table "
                        "markers — run python -m hyperspace_tpu.analysis.check "
                        "--write-config-docs")]
    current = text.split(DOCS_BEGIN, 1)[1].split(DOCS_END, 1)[0].strip()
    if current != config_mod.docs_table().strip():
        return [Finding(str(doc), 0, 0, CONFIG_DRIFT,
                        "docs/configuration.md key table is stale relative to "
                        "config.KNOWN_KEYS — run python -m "
                        "hyperspace_tpu.analysis.check --write-config-docs")]
    return []


def write_config_docs(root: pathlib.Path) -> bool:
    from hyperspace_tpu import config as config_mod

    doc = root / "docs" / "configuration.md"
    text = doc.read_text()
    if DOCS_BEGIN not in text or DOCS_END not in text:
        return False
    head, rest = text.split(DOCS_BEGIN, 1)
    _, tail = rest.split(DOCS_END, 1)
    doc.write_text(f"{head}{DOCS_BEGIN}\n{config_mod.docs_table()}\n{DOCS_END}{tail}")
    return True


# -- HSL016: docs/errors.md error-contract table ------------------------------

def errors_table(program, raises_obj: Raises, contracts: dict) -> str:
    """The generated contract table: one row per entry point, declared
    surface next to the statically observed escape set (``(dynamic)``
    marks re-raises of stored/registered exception objects the static
    analysis cannot type)."""
    lines = [
        "| entry point | declared contract | statically observed escapes |",
        "|---|---|---|",
    ]
    for qname in sorted(contracts):
        types, _, _ = contracts[qname]
        esc = raises_obj.escapes.get(qname, {})
        observed = sorted(t for t in esc if t != DYNAMIC)
        if DYNAMIC in esc:
            observed.append("(dynamic)")
        lines.append(
            f"| `{qname}` | {', '.join(f'`{t}`' for t in types) or '—'} "
            f"| {', '.join(f'`{t}`' for t in observed) or '—'} |"
        )
    return "\n".join(lines)


_ERRORS_DOC_SKELETON = """# Error contracts

The typed error surface of every public entry point, declared in
`exceptions.ERROR_CONTRACTS` and statically verified on every push by
rule HSL016 (see docs/static_analysis.md): any exception type that can
escape an entry point without being covered by its declared contract —
modulo the exception hierarchy — fails the build, and so does a declared
program-local type that covers nothing. The table below is generated;
edit `exceptions.py`, then run
`python -m hyperspace_tpu.analysis.check --write-error-docs`.

{begin}
{table}
{end}

An entry covers its subclasses: `HyperspaceError` covers
`IndexCorruptionError`, `PlanValidationError`, `AdmissionRejected`,
`QueryTimeout`; `OSError` covers real disk failures and the injected
`FaultError`. `CrashPoint` (a `BaseException`) is the simulated hard
process death — it appears in the contracts because it must escape
these APIs untouched (docs/fault_tolerance.md). `(dynamic)` marks a
re-raise of a stored exception object the static analysis cannot type.
"""


def errors_docs_findings(root: pathlib.Path, program, raises_obj: Raises,
                         contracts: dict) -> list[Finding]:
    """docs/errors.md must exist and its generated table must match the
    registry + analysis exactly (the HSL010 config-docs pattern)."""
    if not any(q.startswith("hyperspace_tpu.") for q in contracts):
        return []  # scanning a corpus subset, not the package
    doc = root / "docs" / "errors.md"
    stale = Finding(str(doc), 0, 0, CONTRACT_DRIFT,
                    "docs/errors.md error-contract table is missing or stale "
                    "relative to exceptions.ERROR_CONTRACTS — run python -m "
                    "hyperspace_tpu.analysis.check --write-error-docs")
    if not doc.exists():
        return [stale]
    text = doc.read_text()
    if ERRORS_BEGIN not in text or ERRORS_END not in text:
        return [stale]
    current = text.split(ERRORS_BEGIN, 1)[1].split(ERRORS_END, 1)[0].strip()
    if current != errors_table(program, raises_obj, contracts).strip():
        return [stale]
    return []


def write_error_docs(root: pathlib.Path, program, raises_obj: Raises,
                     contracts: dict) -> bool:
    doc = root / "docs" / "errors.md"
    table = errors_table(program, raises_obj, contracts)
    if not doc.exists() or ERRORS_BEGIN not in doc.read_text():
        doc.write_text(_ERRORS_DOC_SKELETON.format(
            begin=ERRORS_BEGIN, table=table, end=ERRORS_END,
        ))
        return True
    text = doc.read_text()
    head, rest = text.split(ERRORS_BEGIN, 1)
    _, tail = rest.split(ERRORS_END, 1)
    doc.write_text(f"{head}{ERRORS_BEGIN}\n{table}\n{ERRORS_END}{tail}")
    return True


# -- dead-symbol report (informational) ---------------------------------------

def dead_symbol_report(program, callgraph, raises_obj: Raises, contracts: dict) -> dict:
    """Functions unreachable from any public entry point through the
    dispatch-augmented call graph. Informational ONLY — the resolver is
    deliberately under-approximate (dynamic dispatch through untyped
    locals, higher-order uses), so a listing here is a lead for a human,
    never a finding."""
    roots = {
        q for q, fn in program.functions.items() if not fn.name.startswith("_")
    }
    roots |= {q for q in contracts if q in program.functions}
    adj: dict[str, set[str]] = {}
    for e in callgraph.edges:
        slot = adj.setdefault(e.caller, set())
        for t in raises_obj.dispatch_targets(e.callee):
            slot.add(t)
    reach = set(roots)
    stack = list(roots)
    while stack:
        q = stack.pop()
        for nxt in adj.get(q, ()):
            if nxt not in reach:
                reach.add(nxt)
                stack.append(nxt)
    dead = sorted(
        q for q, fn in program.functions.items()
        if q not in reach and not fn.name.startswith("__")
    )
    return {"count": len(dead), "functions": dead}


# -- HSL012: fault-point coverage ---------------------------------------------

def fault_point_findings(program: Program) -> list[Finding]:
    from hyperspace_tpu import faults as faults_mod

    declared = set(faults_mod.KNOWN_POINTS)
    findings: list[Finding] = []
    threaded: set[str] = set()
    faults_path = None
    for fn in sorted(program.functions.values(), key=lambda f: (f.module, f.line)):
        mod = program.modules[fn.module]
        if mod.path.endswith("hyperspace_tpu/faults.py"):
            faults_path = mod.path
            continue  # the harness's own docstrings/validation, not call sites
        for name, line, kind in fn.fault_refs:
            if kind == "point" and fn.module.startswith("hyperspace_tpu."):
                threaded.add(name)
            if name not in declared and not _suppressed(mod, line, FAULT_COVERAGE):
                findings.append(Finding(
                    mod.path, line, 0, FAULT_COVERAGE,
                    f"fault point {name!r} is not declared in "
                    f"faults.KNOWN_POINTS — an undeclared name can never fire "
                    f"a registered rule (fix the typo or declare it)",
                ))
    for mod in program.modules.values():
        if mod.path.endswith("hyperspace_tpu/faults.py"):
            faults_path = mod.path
    if not any(m.startswith("hyperspace_tpu.") for m in program.modules):
        # Coverage direction needs the package in the scanned set; a
        # corpus file scanned alone must not report every point missing.
        return findings
    for point in sorted(declared - threaded):
        findings.append(Finding(
            faults_path or "hyperspace_tpu/faults.py", 0, 0, FAULT_COVERAGE,
            f"declared fault point {point!r} is never threaded through a "
            f"fault_point() call site — the crash sweep cannot exercise it; "
            f"thread it or remove it from KNOWN_POINTS",
        ))
    return findings


def _suppressed(mod, line: int, rule: str) -> bool:
    lines = mod.lines
    text = lines[line - 1] if 0 < line <= len(lines) else ""
    if "# noqa" not in text:
        return False
    tail = text.split("# noqa", 1)[1]
    return not tail.strip().startswith(":") or rule in tail


# -- validator corpus ---------------------------------------------------------

def validator_corpus() -> dict:
    """Self-test the plan validator over a tiny known-good/known-bad
    corpus. Returns a JSON-able status dict; `failures` non-empty means
    the validator regressed."""
    try:
        from hyperspace_tpu.analysis.validator import validate_plan
        from hyperspace_tpu.plan.expr import col
        from hyperspace_tpu.plan.nodes import Filter, Join, Scan, Sort
        from hyperspace_tpu.schema import Field, Schema
    except ImportError as e:
        return {"status": "skipped", "reason": f"dependencies unavailable: {e}"}
    schema = Schema.of(Field("k", "int32"), Field("v", "float64"),
                       Field("emb", "vector", dim=4))
    right = Scan("/corpus/u", "parquet", Schema.of(Field("k", "int32")))
    base = Scan("/corpus/t", "parquet", schema)
    corpus = [
        ("clean-filter", Filter(base, col("k") > 1), []),
        ("unresolved-column", Filter(base, col("zz") > 1), ["unresolved-column"]),
        ("dtype-predicate", Filter(base, col("emb") > 1), ["dtype-incompatible-predicate"]),
        ("unsortable-key", Sort(base, [("emb", True)]), ["unsortable-key"]),
        ("bucket-mismatch",
         Join(Scan("/corpus/t", "parquet", schema, bucket_spec=(8, ["k"])),
              Scan("/corpus/u", "parquet", Schema.of(Field("k", "int32")),
                   bucket_spec=(16, ["k"])),
              ["k"], ["k"]),
         ["join-bucket-mismatch"]),
        ("clean-join", Join(base, right, ["k"], ["k"]), []),
    ]
    failures = []
    for name, plan, expect in corpus:
        got = [d.rule for d in validate_plan(plan)]
        if got != expect:
            failures.append({"case": name, "expected": expect, "got": got})
    return {"status": "ok" if not failures else "failed",
            "cases": len(corpus), "failures": failures}


# -- SARIF --------------------------------------------------------------------

def to_sarif(findings: list[Finding], baseline: set[tuple], root: pathlib.Path) -> dict:
    """SARIF 2.1.0 form of the findings — the code-scanning artifact CI
    uploads next to the JSON report. Baseline-known findings carry
    ``baselineState: unchanged`` so scanners triage only what's new."""
    rules = [
        {
            "id": r.rule,
            "name": r.slug,
            "shortDescription": {"text": r.summary},
            "properties": {"scope": r.scope},
        }
        for r in sorted(RULES.values(), key=lambda r: r.rule)
    ]
    results = []
    for f in findings:
        path = _finding_key(f, root)[1]
        results.append({
            "ruleId": f.rule,
            "level": "error",
            "message": {"text": f.message},
            "locations": [{
                "physicalLocation": {
                    "artifactLocation": {"uri": path},
                    "region": {"startLine": max(1, f.line)},
                },
            }],
            "baselineState": (
                "unchanged" if tuple(_finding_key(f, root)) in baseline else "new"
            ),
        })
    return {
        "$schema": "https://json.schemastore.org/sarif-2.1.0.json",
        "version": "2.1.0",
        "runs": [{
            "tool": {
                "driver": {
                    "name": "hyperspace-analysis",
                    "informationUri": "docs/static_analysis.md",
                    "rules": rules,
                },
            },
            "results": results,
        }],
    }


# -- --changed: restrict findings to files changed vs origin/main -------------

def changed_files(root: pathlib.Path) -> tuple[str, set[str]] | None:
    """(base ref, changed .py paths relative to root) from git, trying
    ``origin/main`` then ``main`` then ``HEAD``; None when git (or the
    repo) is unavailable — the caller falls back to a full run."""
    import subprocess

    for base in ("origin/main", "main", "HEAD"):
        try:
            proc = subprocess.run(
                ["git", "diff", "--name-only", base, "--", "*.py"],
                cwd=root, capture_output=True, text=True, timeout=30,
            )
        except (OSError, subprocess.SubprocessError):
            return None
        if proc.returncode == 0:
            files = {line.strip() for line in proc.stdout.splitlines() if line.strip()}
            return base, files
    return None


def restrict_findings(findings: list[Finding], changed: set[str], root: pathlib.Path) -> list[Finding]:
    """Findings whose (root-relative) path — or ANY file on the witness
    chain — is in the changed set. The engine still indexed the WHOLE
    program; only the reporting surface narrows. Witness files count
    because a cross-module finding is often CAUSED by the edited callee
    while its report line sits in an unchanged caller: dropping those
    made --changed blind to exactly the regressions the whole-program
    rules exist for."""

    def _rel(path: str) -> str:
        try:
            return str(pathlib.Path(path).resolve().relative_to(root))
        except ValueError:
            return path

    return [
        f for f in findings
        if _finding_key(f, root)[1] in changed
        or any(_rel(w) in changed for w in f.witness_paths)
    ]


# -- baseline -----------------------------------------------------------------

def _finding_key(f: Finding, root: pathlib.Path) -> list:
    path = f.path
    try:
        path = str(pathlib.Path(f.path).resolve().relative_to(root))
    except ValueError:
        pass
    return [f.rule, path, f.message]


def load_baseline(path: pathlib.Path) -> set[tuple]:
    try:
        data = json.loads(path.read_text())
    except (OSError, ValueError):
        return set()
    return {tuple(entry) for entry in data.get("findings", [])}


# -- driver -------------------------------------------------------------------

def run_check(
    paths: list[pathlib.Path],
    root: pathlib.Path,
    usage_dirs: list[pathlib.Path],
    allowlist: dict | None = None,
) -> dict:
    """Everything except baseline comparison and rendering: returns the
    full report dict (findings as Finding objects under '_findings')."""
    allowlist = TEST_ALLOWLIST if allowlist is None else allowlist
    sources, findings = load_sources(paths)
    for name, path, src, tree in sources:
        findings.extend(lint_mod.lint_source(src, path, tree=tree))
    program = build_program(sources)
    callgraph = CallGraph(program)
    lockgraph = LockGraph(program, callgraph)
    effects = Effects(program, callgraph)
    raises_obj = Raises(program, callgraph)
    contracts = declared_contracts(program)
    findings.extend(lockgraph.inversions())
    findings.extend(resource_findings(program))
    findings.extend(config_key_findings(program, usage_dirs))
    findings.extend(docs_findings(root))
    findings.extend(fault_point_findings(program))
    findings.extend(lockset_race_findings(program, effects))
    findings.extend(atomicity_findings(program, effects))
    findings.extend(jit_hygiene_findings(program))
    findings.extend(error_contract_findings(program, raises_obj, contracts))
    findings.extend(errors_docs_findings(root, program, raises_obj, contracts))
    findings.extend(swallowed_findings(program, raises_obj))
    unwind, unwind_proof = unwind_findings(program, callgraph, raises_obj, contracts)
    findings.extend(unwind)
    domains = ProcessDomains(program, callgraph, raises_obj)
    tdomains = TraceDomains(program, callgraph, raises_obj)
    ddomains = DurabilityDomains(program, callgraph, raises_obj)
    # HSL021 vs HSL027 dedupe: a lease/fleet write site HSL027 now
    # checks reports ONCE, under the newer rule — otherwise every
    # --changed run would double-report the shared sites.
    findings.extend(
        f for f in domains.findings()
        if not (f.rule == "HSL021" and (f.path, f.line) in ddomains.claimed_sites)
    )
    findings.extend(tdomains.findings())
    findings.extend(ddomains.findings())
    allowed = []
    kept = []
    for f in findings:
        just = next(
            (why for (suffix, rule), why in allowlist.items()
             if f.rule == rule and f.path.endswith(suffix)),
            None,
        )
        (allowed if just is not None else kept).append(f)
    kept.sort(key=lambda f: (f.path, f.line, f.rule))
    corpus = validator_corpus()
    if corpus.get("failures"):
        for fail in corpus["failures"]:
            kept.append(Finding(
                "hyperspace_tpu/analysis/validator.py", 0, 0, "HSL000",
                f"validator corpus case {fail['case']!r} regressed: expected "
                f"{fail['expected']}, got {fail['got']}",
            ))
    total_calls = len(callgraph.edges) + len(callgraph.unresolved)
    dead = dead_symbol_report(program, callgraph, raises_obj, contracts)
    return {
        "_findings": kept,
        "_engine": (program, callgraph, raises_obj, contracts),
        "summary": {
            "files": len(sources),
            "findings": len(kept),
            "allowlisted": len(allowed),
            "functions": len(program.functions),
            "call_edges": len(callgraph.edges),
            # Resolution-quality accounting: the engine's blind spots.
            # A rising unresolved ratio silently weakens every
            # whole-program rule, so tests pin a regression bound on it.
            "calls_unresolved": len(callgraph.unresolved),
            "calls_unresolved_ratio": round(
                len(callgraph.unresolved) / total_calls, 4
            ) if total_calls else 0.0,
            "locks": len(program.locks),
            "lock_edges": len(lockgraph.order_edges()),
            "shared_states": len(effects.by_state),
            "entry_guaranteed_fns": len(effects.entry_locks),
            "contract_entry_points": len(contracts),
            "fault_points_proven": sum(
                1 for e in unwind_proof.values() if e["covered"]
            ),
            "dead_symbols": dead["count"],
            # Process-domain accounting (HSL019-022): CI asserts the
            # rules actually RAN — a zero entry-point count on the real
            # repo would mean the registry extraction silently broke.
            "spawn_entry_points": len(domains.entry_points),
            "spawn_domain_functions": len(domains.task_fns),
            "spawn_domain_modules": len(domains.domain_modules),
            "spawn_boundary_sites": len(domains.boundary_sites),
            "lease_acquire_sites": len(domains.lease_acquires),
            # Trace-domain accounting (HSL023-026): same CI contract —
            # a zero trace-entry count on the real repo would mean jit
            # site detection silently broke.
            "trace_entry_points": len({e.traced for e in tdomains.entries}),
            "trace_domain_functions": len(tdomains.trace_fns),
            "trace_kernels_proven": sum(
                1 for lad in tdomains._kernel_ladders if lad["proven"]
            ),
            # The trace closure's own blind-spot accounting: traced
            # bodies call mostly jnp/lax (external, unresolvable by
            # design), so this ratio runs high — the bound pins it from
            # drifting higher, like calls_unresolved_ratio above.
            "trace_domain_unresolved_ratio": tdomains.unresolved_ratio(),
            # Durability-domain accounting (HSL027-030): same CI
            # contract — zero roots/sites/windows on the real repo
            # would mean the registry extraction or write-site
            # detection silently broke.
            "durable_roots": len(ddomains.roots or {}),
            "durable_write_sites": len(ddomains.sites),
            "durable_domain_functions": len(ddomains.domain_fns),
            "torn_windows": len(ddomains.windows or {}),
            "torn_windows_proven": sum(
                1 for p in ddomains._window_proofs.values() if p["proven"]
            ),
            "replay_roots": len(ddomains.replay_roots or {}),
            "replay_closure_functions": len(ddomains.replay_fns),
            "durable_domain_unresolved_ratio": ddomains.unresolved_ratio(),
        },
        "validator_corpus": corpus,
        "lock_graph": lockgraph.to_json(),
        # The HSL018 witness chains: per fault point, the recovery
        # construct that statically reaches each threading site.
        "unwind_proof": unwind_proof,
        # The HSL019-022 substrate: the inferred process-domain graph
        # (entries, task closure, domain modules, boundary sites, lease
        # reap proofs) — procdemo pins its exact shape in a golden.
        "process_domains": domains.to_json(),
        # The HSL023-026 substrate: the inferred trace-domain graph
        # (entries, traced closure, donation proof, per-kernel fallback
        # ladders) — jitdemo pins its exact shape in a golden.
        "trace_domains": tdomains.to_json(),
        # The HSL027-030 substrate: the inferred durability-domain
        # graph (durable roots + write sites, torn-window proofs with
        # their in-window fault-point witnesses, replay closures,
        # snapshot carriers) — durademo pins its exact shape in a
        # golden.
        "durable_domains": ddomains.to_json(),
        # Informational (never gated): private functions no public entry
        # point reaches through the resolved call graph.
        "dead_symbols": dead,
        "allowlisted": [
            {"rule": f.rule, "path": f.path, "line": f.line} for f in allowed
        ],
    }


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m hyperspace_tpu.analysis.check",
        description="Unified static analysis: per-file lint (HSL001-HSL008), "
                    "whole-program rules (HSL009-HSL030), validator corpus, "
                    "findings baseline.",
    )
    ap.add_argument("paths", nargs="*", help="files/directories (default: the "
                    "package + benchmarks + bench.py + tests/conftest.py)")
    ap.add_argument("--format", choices=("text", "json", "sarif"), default="text")
    ap.add_argument("--output", help="also write the report to this file")
    ap.add_argument("--changed", action="store_true",
                    help="report findings only for files changed vs origin/main "
                         "(the engine still indexes the whole program) — the "
                         "fast local pre-push mode")
    ap.add_argument("--baseline", help=f"baseline file (default: {BASELINE_NAME} "
                    "at the repo root when present)")
    ap.add_argument("--write-baseline", action="store_true",
                    help="write the current findings as the new baseline")
    ap.add_argument("--write-config-docs", action="store_true",
                    help="regenerate the docs/configuration.md key table from "
                         "config.KNOWN_KEYS and exit")
    ap.add_argument("--write-error-docs", action="store_true",
                    help="regenerate the docs/errors.md contract table from "
                         "exceptions.ERROR_CONTRACTS + the escape analysis "
                         "and exit")
    ap.add_argument("--no-baseline", action="store_true",
                    help="fail on ALL findings, ignoring any baseline")
    args = ap.parse_args(argv)
    try:
        root = _repo_root()
        if args.write_config_docs:
            ok = write_config_docs(root)
            print("docs/configuration.md key table "
                  + ("regenerated" if ok else "markers missing — not rewritten"))
            return EXIT_CLEAN if ok else EXIT_INTERNAL_ERROR
        paths = [pathlib.Path(p) for p in args.paths] or default_paths(root)
        usage_dirs = [root / "tests"] if (root / "tests").exists() else []
        report = run_check(paths, root, usage_dirs)
        findings: list[Finding] = report.pop("_findings")
        program, _cg, raises_obj, contracts = report.pop("_engine")
        if args.write_error_docs:
            write_error_docs(root, program, raises_obj, contracts)
            print("docs/errors.md error-contract table regenerated")
            return EXIT_CLEAN
        if args.changed:
            got = changed_files(root)
            if got is None:
                print("--changed: git unavailable — running on everything",
                      file=sys.stderr)
            else:
                base, files = got
                findings = restrict_findings(findings, files, root)
                report["changed"] = {"base": base, "files": sorted(files)}
        baseline_path = pathlib.Path(args.baseline) if args.baseline else root / BASELINE_NAME
        if args.write_baseline:
            baseline_path.write_text(json.dumps(
                {"findings": sorted(_finding_key(f, root) for f in findings)},
                indent=2, sort_keys=True,
            ) + "\n")
            print(f"baseline written: {baseline_path} ({len(findings)} finding(s))")
            return EXIT_CLEAN
        baseline = set() if args.no_baseline else (
            load_baseline(baseline_path) if baseline_path.exists() else set()
        )
        new = [f for f in findings if tuple(_finding_key(f, root)) not in baseline]
        stale = len(baseline) - (len(findings) - len(new))
        report["findings"] = [
            {"rule": f.rule, "slug": RULES[f.rule].slug if f.rule in RULES else f.rule,
             "path": f.path, "line": f.line, "message": f.message,
             "new": tuple(_finding_key(f, root)) not in baseline}
            for f in findings
        ]
        report["baseline"] = {
            "path": str(baseline_path) if baseline_path.exists() else None,
            "known": len(baseline), "stale": max(0, stale), "new": len(new),
        }
        report["summary"]["new_findings"] = len(new)
        if args.format == "sarif":
            rendered = json.dumps(to_sarif(findings, baseline, root), indent=2)
        else:
            rendered = json.dumps(report, indent=2, sort_keys=True)
        if args.output:
            pathlib.Path(args.output).write_text(rendered + "\n")
        if args.format in ("json", "sarif"):
            print(rendered)
        else:
            for f in findings:
                marker = "" if tuple(_finding_key(f, root)) in baseline else " [new]"
                print(f"{f}{marker}")
            s = report["summary"]
            print(
                f"{s['files']} files, {s['functions']} functions, "
                f"{s['locks']} locks ({s['lock_edges']} order edges, cycle-free="
                f"{not any(f.rule == 'HSL009' for f in findings)}); "
                f"{s['findings']} finding(s), {len(new)} new, "
                f"{s['allowlisted']} allowlisted; validator corpus: "
                f"{report['validator_corpus']['status']}",
                file=sys.stderr,
            )
        return EXIT_FINDINGS if new else EXIT_CLEAN
    except SystemExit:
        raise
    except Exception as e:
        print(f"internal error: {type(e).__name__}: {e}", file=sys.stderr)
        return EXIT_INTERNAL_ERROR


if __name__ == "__main__":
    sys.exit(main())
