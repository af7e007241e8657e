"""Trace-safety / compat linter: AST rules for the jax bug classes that
actually bite this codebase.

Run over paths (files or directories) with::

    python -m hyperspace_tpu.analysis.lint hyperspace_tpu

Exit status is non-zero iff any finding is reported — the CI gate. Rules:

- **HSL001 fragile-jax-import** — importing jax symbols whose location
  changes across jax versions (`from jax import shard_map`, anything
  under `jax.experimental`) anywhere except the sanctioned
  ``hyperspace_tpu/compat.py``. The seed shipped exactly this bug: a
  bare ``from jax import shard_map`` produced 66 collection errors on
  jax 0.4.37. The compat module resolves such symbols once, with
  fallbacks; everything else imports from it.
- **HSL002 host-sync-in-jit** — forcing a traced value to a host Python
  value inside jitted/shard_mapped code: ``.item()``, ``.tolist()``,
  ``float()/int()/bool()`` on non-literals, ``np.asarray``/``np.array``,
  ``jax.device_get``. Under tracing these either fail
  (ConcretizationTypeError) or silently insert a blocking transfer.
- **HSL003 traced-control-flow** — Python ``if``/``while`` whose test
  reads a traced argument's VALUE inside jitted code. Shape/dtype
  attributes (``x.shape``, ``x.ndim``, ``x.dtype``, ``x.size``) are
  static and exempt; branching on the value itself needs ``lax.cond`` /
  ``jnp.where``.
- **HSL004 unhashable-static** — ``static_argnums``/``static_argnames``
  given a list/set/dict display. jit caches on static argument VALUES,
  which therefore must be hashable; the tuple spelling is required.
- **HSL005 unseeded-randomness** — module-level RNG calls
  (``np.random.rand`` etc., stdlib ``random.*``) and
  ``np.random.default_rng()`` with no seed. Unseeded randomness makes
  device results irreproducible across runs and shards; pass an explicit
  seed (``np.random.default_rng(0)``) or thread ``jax.random`` keys.
- **HSL007 wallclock-duration / undeclared-counter** — two observability
  hazards (docs/observability.md): (a) ``time.time()`` appearing in a
  subtraction — wall clock steps under NTP, so durations and TTL ages
  must use ``time.monotonic()``/``time.perf_counter()`` (persisted
  cross-process stamps are the legitimate exception; mark them
  ``# noqa: HSL007`` with a comment saying why); (b) ``stats.increment``
  with a constant counter name not declared in
  ``stats.KNOWN_COUNTERS`` — a typo'd name would raise at runtime (the
  declared-registry contract); the linter catches it before then. The
  declared set is read by parsing ``hyperspace_tpu/stats.py``'s AST, so
  the rule works in dependency-free CI.
- **HSL008 unlocked-global-mutation** — a module-level mutable container
  (dict/list/set/deque display or constructor) mutated from inside a
  function or method without a lock held (no enclosing ``with`` whose
  context expression names a lock). This is the bug class the serving
  plane's concurrency hardening removed (docs/serving.md): module
  globals that were safe under one caller become torn-eviction /
  lost-update races under N worker threads. Mutations at module level
  (import time, single-threaded) are exempt; so are the declared
  allowlist entries (:data:`HSL008_ALLOWED` — e.g. the obs no-op
  singleton plumbing, where a benign last-writer-wins is the design).
- **HSL006 metadata-write-bypass** — bare ``.write_text()`` /
  ``.write_bytes()`` / write-mode ``open()`` on metadata-plane paths
  (``_hyperspace_log`` entries, the ``latestStable`` pointer, the index
  manifest, ``v__=`` version dirs) anywhere except the sanctioned
  ``utils/file_utils.py``. A bare write is a torn write waiting for a
  crash: the metadata plane only stays crash-consistent because every
  commit goes through ``file_utils.write_json``/``atomic_write`` (temp
  file + fsync + atomic rename + dir fsync). The seed shipped exactly
  this bug in ``write_manifest`` (``Path.write_text``); this rule keeps
  it fixed.

Suppression: a finding on a line containing ``# noqa`` or
``# noqa: HSLxxx`` (matching rule id) is dropped.

This module is the *per-file* half of the analysis engine. The
whole-program rules — HSL009 lock-order inversion, HSL010 config-key
drift, HSL011 resource/exception safety, HSL012 fault-point coverage,
HSL013 lockset data races, HSL014 torn check-then-act, HSL015
jit-cache hygiene, HSL016 error-contract drift, HSL017 swallowed
crash/fault, HSL018 unwind safety, HSL019-022 the process-domain
invariants (spawn-import purity, exchange-surface typing, shared-file
protocol, cross-boundary continuity) — need the cross-module index
(analysis/program.py, callgraph.py, locks.py, effects.py, races.py,
raises.py, procdomain.py) and run from the unified
driver ``python -m hyperspace_tpu.analysis.check``, which parses each
file ONCE and feeds the same tree to this linter and to the program
index. All rules,
per-file and whole-program, are declared in :data:`RULES` — the one
registry the JSON report, the docs table, and the baseline key on.
"""

from __future__ import annotations

import argparse
import ast
import dataclasses
import pathlib
import sys

FRAGILE_IMPORT = "HSL001"
HOST_SYNC = "HSL002"
TRACED_FLOW = "HSL003"
UNHASHABLE_STATIC = "HSL004"
UNSEEDED_RNG = "HSL005"
METADATA_WRITE = "HSL006"
WALLCLOCK_OR_UNDECLARED = "HSL007"
UNLOCKED_GLOBAL = "HSL008"

# Exit codes (`main` and analysis/check.py): CI must be able to tell "the
# tree has findings" from "the analyzer crashed".
EXIT_CLEAN = 0
EXIT_FINDINGS = 1
EXIT_INTERNAL_ERROR = 2


@dataclasses.dataclass(frozen=True)
class RuleInfo:
    """One registered rule: id, short slug, one-line summary, and where
    it runs ('file' = per-file AST walk here, 'program' = whole-program
    engine in check.py)."""

    rule: str
    slug: str
    summary: str
    scope: str = "file"


RULES: dict[str, RuleInfo] = {
    r.rule: r
    for r in (
        RuleInfo("HSL000", "unparseable", "file cannot be read or parsed"),
        RuleInfo("HSL001", "fragile-jax-import",
                 "version-fragile jax import outside the sanctioned compat.py"),
        RuleInfo("HSL002", "host-sync-in-jit",
                 "device->host sync (.item()/float()/np.asarray/...) inside traced code"),
        RuleInfo("HSL003", "traced-control-flow",
                 "Python if/while on a traced value inside jitted code"),
        RuleInfo("HSL004", "unhashable-static",
                 "static_argnums/static_argnames given an unhashable display"),
        RuleInfo("HSL005", "unseeded-randomness",
                 "global/unseeded RNG use — irreproducible across runs and shards"),
        RuleInfo("HSL006", "metadata-write-bypass",
                 "bare write to a metadata-plane path outside file_utils.py"),
        RuleInfo("HSL007", "wallclock-or-undeclared-counter",
                 "time.time() in a duration subtraction; undeclared stats counter name"),
        RuleInfo("HSL008", "unlocked-global-mutation",
                 "module-level container mutated in a function without a lock held"),
        RuleInfo("HSL009", "lock-order-inversion",
                 "cycle in the whole-program lock-acquisition graph", scope="program"),
        RuleInfo("HSL010", "config-key-drift",
                 "hyperspace.* config key not declared in config.KNOWN_KEYS (or declared and dead)",
                 scope="program"),
        RuleInfo("HSL011", "resource-safety",
                 "lock/span/file acquired outside with/try-finally on a raising path",
                 scope="program"),
        RuleInfo("HSL012", "fault-point-coverage",
                 "faults.KNOWN_POINTS and fault_point()/inject() call sites out of sync",
                 scope="program"),
        RuleInfo("HSL013", "lockset-race",
                 "shared state accessed under inconsistent locksets with a write in play",
                 scope="program"),
        RuleInfo("HSL014", "atomicity-violation",
                 "torn check-then-act: read under a lock, released, stale write-back re-acquiring it",
                 scope="program"),
        RuleInfo("HSL015", "jit-cache-hygiene",
                 "jit call site manufacturing a fresh cache key per call (recompile storm / executable leak)",
                 scope="program"),
        RuleInfo("HSL016", "error-contract-drift",
                 "statically observed escape not covered by exceptions.ERROR_CONTRACTS (or dead contract entry)",
                 scope="program"),
        RuleInfo("HSL017", "swallowed-crash",
                 "except clause absorbing CrashPoint/FaultError/everything without re-raise or signal",
                 scope="program"),
        RuleInfo("HSL018", "unwind-safety",
                 "fault point with no static path to a recovery construct; +=/-= pair unbalanced on unwind",
                 scope="program"),
        RuleInfo("HSL019", "spawn-import-purity",
                 "module reachable at worker start from a spawn entry point imports jax/pallas at module level",
                 scope="program"),
        RuleInfo("HSL020", "exchange-surface-typing",
                 "non-picklable/device value (ColumnTable, Span, lock, open handle, jax array) crosses a process boundary",
                 scope="program"),
        RuleInfo("HSL021", "shared-file-protocol",
                 "bare write on an exchange/fleet/lease path outside the atomic publish protocol; O_EXCL acquire with no reachable TTL reap",
                 scope="program"),
        RuleInfo("HSL022", "cross-boundary-continuity",
                 "spawn entry point missing fault/trace continuity plumbing; undeclared spawn target or worker telemetry name",
                 scope="program"),
        RuleInfo("HSL023", "traced-effect-purity",
                 "host effect (config/stats/event/lock/file/clock/materialization) reachable inside the jit trace-domain closure",
                 scope="program"),
        RuleInfo("HSL024", "signature-space-boundedness",
                 "jit key/static argument/pad width not derived from a declared bounded domain — recompile-storm risk",
                 scope="program"),
        RuleInfo("HSL025", "donation-aliasing-safety",
                 "zero-copy staged view mutated or donated without own_arrays; donated buffer referenced after the jitted call",
                 scope="program"),
        RuleInfo("HSL026", "kernel-fallback-ladder",
                 "Pallas engagement undeclared in ops.KNOWN_KERNELS, missing its eligibility rule or device.kernel.* counters, or swallowing lowering errors in a broad except",
                 scope="program"),
        RuleInfo("HSL027", "durable-atomic-publish",
                 "durable write under a DURABLE_ROOTS plane does not reach the mkstemp + fsync + os.replace idiom — crash can surface a torn or zero-length file",
                 scope="program"),
        RuleInfo("HSL028", "torn-window-ordering",
                 "declared TORN_WINDOWS exactly-once protocol: two writes not statically ordered, or no KNOWN_POINTS fault point armed inside the window",
                 scope="program"),
        RuleInfo("HSL029", "replay-idempotence",
                 "durable file name on a REPLAY_ROOTS recovery/re-poll/takeover path derives from wall clock, pid, or RNG instead of cursor/log-id/generation values",
                 scope="program"),
        RuleInfo("HSL030", "snapshot-stamp-discipline",
                 "pinned-snapshot context reads the live version vector (get_latest_id/collection_log_versions/latest_log_id) instead of keying on the snapshot stamp",
                 scope="program"),
    )
}

# The one module allowed to touch version-fragile jax import paths.
SANCTIONED_COMPAT = "compat.py"
# The one module allowed to open metadata-plane paths for writing (it
# implements the atomic temp+fsync+rename primitives everything uses).
SANCTIONED_FILE_UTILS = "file_utils.py"

# Expression text that marks a write target as metadata-plane: the log
# dir and its pointer, version dirs, and the index manifest (both the
# literal names and the config/module constants they're spelled with).
_METADATA_PATH_MARKERS = (
    "_hyperspace_log",
    "lateststable",
    "hyperspace_log_dir",
    "latest_stable_log_name",
    "_index_manifest",
    "manifest_name",
    "data_version_prefix",
    "v__",
    "log_dir",
    "version_dir",
)

# HSL008 allowlist: (module basename, container name) pairs whose
# unlocked mutation is deliberate. The obs singletons' module state is
# written only through set_enabled/configure/reset — config-plane calls
# where last-writer-wins is the intended semantic, not a data race on
# the query path.
HSL008_ALLOWED = {
    ("trace.py", "NOOP"),
    ("trace.py", "_NOOP_TRACE"),
}

# Container constructors whose module-level result HSL008 tracks, and
# the method names that mutate such a container in place.
_HSL008_CTORS = {"dict", "list", "set", "deque", "defaultdict", "OrderedDict", "Counter"}
_HSL008_MUTATORS = {
    "append", "appendleft", "extend", "extendleft", "insert", "add",
    "update", "setdefault", "pop", "popleft", "popitem", "clear",
    "remove", "discard",
}


def _declared_counters() -> "frozenset[str] | None":
    """Counter names declared in hyperspace_tpu/stats.py's
    KNOWN_COUNTERS tuple, extracted by AST parse (no import — the lint
    CI job runs without the package's dependencies installed). None when
    the file can't be located/parsed, which disables the check."""
    global _DECLARED_CACHE
    if _DECLARED_CACHE is not ...:
        return _DECLARED_CACHE
    _DECLARED_CACHE = None
    stats_path = pathlib.Path(__file__).resolve().parent.parent / "stats.py"
    try:
        tree = ast.parse(stats_path.read_text())
    except (OSError, SyntaxError):
        return None
    for node in ast.walk(tree):
        if not isinstance(node, ast.Assign):
            continue
        for tgt in node.targets:
            if isinstance(tgt, ast.Name) and tgt.id == "KNOWN_COUNTERS":
                if isinstance(node.value, (ast.Tuple, ast.List)):
                    names = [
                        e.value
                        for e in node.value.elts
                        if isinstance(e, ast.Constant) and isinstance(e.value, str)
                    ]
                    _DECLARED_CACHE = frozenset(names)
                    return _DECLARED_CACHE
    return None


_DECLARED_CACHE: "frozenset[str] | None | object" = ...


_JIT_NAMES = {"jit", "shard_map", "pmap"}
_HOST_SYNC_ATTRS = {"item", "tolist"}
_HOST_SYNC_CASTS = {"float", "int", "bool"}
_NP_SYNC_FNS = {"asarray", "array"}
_STATIC_SHAPE_ATTRS = {"shape", "ndim", "dtype", "size"}
_GLOBAL_RNG_FNS = {
    "rand", "randn", "randint", "random", "random_sample", "choice",
    "shuffle", "permutation", "normal", "uniform", "standard_normal", "seed",
}


@dataclasses.dataclass(frozen=True)
class Finding:
    path: str
    line: int
    col: int
    rule: str
    message: str
    # Files (other than `path`) on the finding's witness chain — the
    # lock-order / escape / unwind / domain chains that PROVE the
    # finding. `--changed` mode keeps a finding when ANY witness file
    # changed, not just the primary location: editing a callee can
    # create a finding whose report line sits in an unchanged caller.
    # Not part of the baseline key (the message already pins the chain).
    witness_paths: tuple = ()

    def __str__(self) -> str:
        return f"{self.path}:{self.line}:{self.col}: {self.rule} {self.message}"


def _dotted(node: ast.AST) -> str:
    """Best-effort dotted name of a Name/Attribute chain ('' otherwise)."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _static_params(decl: ast.AST, ordered_params: list[str]) -> set[str]:
    """Parameter names a jit declaration (decorator or wrapping call)
    marks static via static_argnames (strings) / static_argnums
    (positions into `ordered_params`)."""
    out: set[str] = set()
    for sub in ast.walk(decl):
        if not isinstance(sub, ast.Call):
            continue
        for kw in sub.keywords:
            if kw.arg not in ("static_argnums", "static_argnames"):
                continue
            values = (
                kw.value.elts
                if isinstance(kw.value, (ast.Tuple, ast.List, ast.Set))
                else [kw.value]
            )
            for v in values:
                if not isinstance(v, ast.Constant):
                    continue
                if kw.arg == "static_argnames" and isinstance(v.value, str):
                    out.add(v.value)
                elif kw.arg == "static_argnums" and isinstance(v.value, int):
                    if 0 <= v.value < len(ordered_params):
                        out.add(ordered_params[v.value])
    return out


def _mentions_jit(node: ast.AST) -> bool:
    """True when the (decorator / callee) expression references a
    jit-family transform anywhere: `jax.jit`, `functools.partial(jax.jit,
    ...)`, bare `jit`, `shard_map`, ..."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name) and sub.id in _JIT_NAMES:
            return True
        if isinstance(sub, ast.Attribute) and sub.attr in _JIT_NAMES:
            return True
    return False


class _Linter(ast.NodeVisitor):
    def __init__(self, path: str, source: str, is_compat: bool, is_file_utils: bool = False):
        self.path = path
        self.source = source
        self.lines = source.splitlines()
        self.is_compat = is_compat
        self.is_file_utils = is_file_utils
        self.findings: list[Finding] = []
        # Names wrapped by a jit-family call somewhere in the module
        # (`return jax.jit(fn)` marks `fn` as traced code), and the call
        # nodes that wrapped them (their static_arg* declarations apply).
        self.jit_wrapped: set[str] = set()
        self.static_decls: dict[str, list[ast.AST]] = {}
        # Stack of (in_jit_context, param_names) per function scope.
        self._fn_stack: list[tuple[bool, frozenset]] = []
        # HSL008 state: module-level mutable container names, and how
        # many lock-holding `with` blocks enclose the current node.
        self.module_containers: set[str] = set()
        self._lock_depth = 0

    def collect_module_containers(self, tree: ast.Module) -> None:
        """Names assigned a mutable container display/constructor at
        module level (HSL008 candidates). Only simple top-level
        assignments count — a container built inside a function is local
        state, and attribute targets belong to lock-owning objects."""
        for node in tree.body:
            targets: list[ast.expr] = []
            value = None
            if isinstance(node, ast.Assign):
                targets, value = node.targets, node.value
            elif isinstance(node, ast.AnnAssign) and node.value is not None:
                targets, value = [node.target], node.value
            if value is None:
                continue
            is_container = isinstance(
                value, (ast.Dict, ast.List, ast.Set, ast.DictComp, ast.ListComp, ast.SetComp)
            ) or (
                isinstance(value, ast.Call)
                and _dotted(value.func).split(".")[-1] in _HSL008_CTORS
            )
            if not is_container:
                continue
            for tgt in targets:
                if isinstance(tgt, ast.Name):
                    basename = pathlib.PurePath(self.path).name
                    if (basename, tgt.id) not in HSL008_ALLOWED:
                        self.module_containers.add(tgt.id)

    # -- bookkeeping ---------------------------------------------------------

    def collect_jit_wrapped(self, tree: ast.AST) -> None:
        for node in ast.walk(tree):
            if (
                isinstance(node, ast.Call)
                and node.args
                and isinstance(node.args[0], ast.Name)
                and _mentions_jit(node.func)
            ):
                self.jit_wrapped.add(node.args[0].id)
                self.static_decls.setdefault(node.args[0].id, []).append(node)

    def _report(self, node: ast.AST, rule: str, message: str) -> None:
        line = getattr(node, "lineno", 0)
        text = self.lines[line - 1] if 0 < line <= len(self.lines) else ""
        if "# noqa" in text:
            tail = text.split("# noqa", 1)[1]
            if not tail.strip().startswith(":") or rule in tail:
                return
        self.findings.append(
            Finding(self.path, line, getattr(node, "col_offset", 0), rule, message)
        )

    def _in_jit(self) -> bool:
        return any(flag for flag, _ in self._fn_stack)

    def _jit_params(self) -> set[str]:
        out: set[str] = set()
        for flag, params in self._fn_stack:
            if flag:
                out |= params
        return out

    # -- HSL001: fragile imports ---------------------------------------------

    def visit_Import(self, node: ast.Import) -> None:
        if not self.is_compat:
            for alias in node.names:
                if alias.name == "jax.experimental" or alias.name.startswith("jax.experimental."):
                    self._report(
                        node, FRAGILE_IMPORT,
                        f"import of {alias.name!r} outside compat.py — jax moves "
                        f"experimental symbols between versions; resolve it in "
                        f"hyperspace_tpu/compat.py and import from there",
                    )
        self.generic_visit(node)

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        if not self.is_compat and node.module:
            if node.module == "jax":
                fragile = [a.name for a in node.names if a.name in ("shard_map", "enable_x64")]
                for name in fragile:
                    self._report(
                        node, FRAGILE_IMPORT,
                        f"'from jax import {name}' is version-fragile (moved "
                        f"between jax releases; broke collection on jax "
                        f"0.4.37) — import it from hyperspace_tpu.compat",
                    )
            elif node.module == "jax.experimental" or node.module.startswith("jax.experimental."):
                self._report(
                    node, FRAGILE_IMPORT,
                    f"import from {node.module!r} outside compat.py — resolve "
                    f"experimental symbols in hyperspace_tpu/compat.py",
                )
        self.generic_visit(node)

    # -- function scopes -----------------------------------------------------

    def _visit_fn(self, node) -> None:
        in_jit = (
            any(_mentions_jit(d) for d in node.decorator_list)
            or node.name in self.jit_wrapped
            or self._in_jit()  # nested defs inherit the traced context
        )
        ordered = [*node.args.posonlyargs, *node.args.args]
        params = {
            a.arg
            for a in [
                *ordered, *node.args.kwonlyargs,
                *( [node.args.vararg] if node.args.vararg else [] ),
                *( [node.args.kwarg] if node.args.kwarg else [] ),
            ]
        }
        # Parameters declared static (static_argnums/static_argnames on
        # the jit decorator or wrapping call) hold ordinary Python values
        # — control flow on them is fine.
        for decl in [*node.decorator_list, *self.static_decls.get(node.name, [])]:
            params -= _static_params(decl, [a.arg for a in ordered])
        self._fn_stack.append((in_jit, frozenset(params)))
        self.generic_visit(node)
        self._fn_stack.pop()

    visit_FunctionDef = _visit_fn
    visit_AsyncFunctionDef = _visit_fn

    # -- HSL002 / HSL004 / HSL005: calls -------------------------------------

    def visit_Call(self, node: ast.Call) -> None:
        dotted = _dotted(node.func)

        # HSL004: static_argnums/static_argnames must be hashable (tuple).
        for kw in node.keywords:
            if kw.arg in ("static_argnums", "static_argnames") and isinstance(
                kw.value, (ast.List, ast.Set, ast.Dict)
            ):
                self._report(
                    node, UNHASHABLE_STATIC,
                    f"{kw.arg} given a {type(kw.value).__name__.lower()} "
                    f"display; jit hashes static argument POSITIONS and "
                    f"values — use a tuple",
                )

        # HSL005: module-level RNG state.
        parts = dotted.split(".")
        if len(parts) >= 2 and parts[-2] == "random" and parts[0] in ("np", "numpy", "jax"):
            if parts[0] != "jax" and parts[-1] in _GLOBAL_RNG_FNS:
                self._report(
                    node, UNSEEDED_RNG,
                    f"{dotted}() uses numpy's global RNG — results are not "
                    f"reproducible across runs/shards; use "
                    f"np.random.default_rng(seed)",
                )
            elif parts[-1] == "default_rng" and not node.args and not node.keywords:
                self._report(
                    node, UNSEEDED_RNG,
                    "np.random.default_rng() without a seed is entropy-seeded "
                    "— pass an explicit seed for reproducible builds",
                )
        elif len(parts) == 2 and parts[0] == "random" and parts[1] in (
            _GLOBAL_RNG_FNS | {"gauss", "sample", "randrange"}
        ):
            self._report(
                node, UNSEEDED_RNG,
                f"stdlib {dotted}() draws from global, unseeded state — "
                f"use a seeded np.random.default_rng",
            )

        # HSL006: bare writes to metadata-plane paths.
        self._check_metadata_write(node)

        # HSL007(b): stats.increment with an undeclared constant name.
        if (
            isinstance(node.func, ast.Attribute)
            and node.func.attr == "increment"
            and "stats" in _dotted(node.func.value).lower()
            and node.args
            and isinstance(node.args[0], ast.Constant)
            and isinstance(node.args[0].value, str)
        ):
            declared = _declared_counters()
            if declared is not None and node.args[0].value not in declared:
                self._report(
                    node, WALLCLOCK_OR_UNDECLARED,
                    f"counter {node.args[0].value!r} is not declared in "
                    f"stats.KNOWN_COUNTERS — undeclared names raise at "
                    f"runtime (the declared-registry contract); fix the "
                    f"typo or declare it",
                )

        # HSL008: in-place mutation of a module-level container from a
        # function without a lock held.
        if (
            isinstance(node.func, ast.Attribute)
            and node.func.attr in _HSL008_MUTATORS
            and isinstance(node.func.value, ast.Name)
            and node.func.value.id in self.module_containers
        ):
            self._check_global_mutation(node, node.func.value.id, f".{node.func.attr}()")

        # HSL002: host sync inside traced code.
        if self._in_jit():
            if isinstance(node.func, ast.Attribute) and node.func.attr in _HOST_SYNC_ATTRS:
                self._report(
                    node, HOST_SYNC,
                    f".{node.func.attr}() forces a device->host transfer and "
                    f"fails under tracing — return the array and read it "
                    f"outside the jitted function",
                )
            elif (
                isinstance(node.func, ast.Name)
                and node.func.id in _HOST_SYNC_CASTS
                and node.args
                and not isinstance(node.args[0], ast.Constant)
            ):
                self._report(
                    node, HOST_SYNC,
                    f"{node.func.id}() on a traced value raises "
                    f"ConcretizationTypeError inside jit — keep it an array "
                    f"(jnp.float32(...) etc.) or hoist the cast to the host",
                )
            elif parts[-1] in _NP_SYNC_FNS and parts[0] in ("np", "numpy"):
                self._report(
                    node, HOST_SYNC,
                    f"{dotted}() materializes a traced value on host inside "
                    f"jit — use jnp equivalents",
                )
            elif dotted in ("jax.device_get",):
                self._report(
                    node, HOST_SYNC,
                    "jax.device_get inside jitted code blocks on a transfer "
                    "that tracing cannot represent",
                )
        self.generic_visit(node)

    # -- HSL006: bare metadata-plane writes ------------------------------------

    def _check_metadata_write(self, node: ast.Call) -> None:
        """Flag `<expr>.write_text/.write_bytes(...)` and write-mode
        `open(...)` whose expression text names a metadata-plane path
        (operation-log entries, latestStable, the index manifest,
        version dirs) outside file_utils.py — such writes are torn on
        crash; the atomic primitives exist precisely so they can't be."""
        if self.is_file_utils:
            return
        is_write = (
            isinstance(node.func, ast.Attribute)
            and node.func.attr in ("write_text", "write_bytes")
        )
        if not is_write and isinstance(node.func, ast.Name) and node.func.id == "open":
            mode = None
            if (
                len(node.args) >= 2
                and isinstance(node.args[1], ast.Constant)
                and isinstance(node.args[1].value, str)
            ):
                mode = node.args[1].value
            for kw in node.keywords:
                if (
                    kw.arg == "mode"
                    and isinstance(kw.value, ast.Constant)
                    and isinstance(kw.value.value, str)
                ):
                    mode = kw.value.value
            is_write = mode is not None and any(c in mode for c in "wax+")
        if not is_write:
            return
        seg = (ast.get_source_segment(self.source, node) or "").lower()
        if any(m in seg for m in _METADATA_PATH_MARKERS):
            self._report(
                node, METADATA_WRITE,
                "bare write to a metadata-plane path (operation log / "
                "latestStable / manifest / version dir) — a crash mid-write "
                "tears it; route through file_utils.write_json/atomic_write "
                "(temp file + fsync + atomic rename + dir fsync)",
            )

    # -- HSL008: unlocked module-global container mutation ---------------------

    def _check_global_mutation(self, node: ast.AST, name: str, how: str) -> None:
        if not self._fn_stack:
            return  # module level runs once at import, single-threaded
        if self._lock_depth > 0:
            return
        self._report(
            node, UNLOCKED_GLOBAL,
            f"module-level container {name!r} mutated ({how}) outside a "
            f"lock — safe single-threaded, a lost-update/torn-eviction "
            f"race under the concurrent serving plane (docs/serving.md); "
            f"guard it with a module lock (`with _lock:`) or move it into "
            f"a lock-guarded class",
        )

    def _subscript_base(self, tgt: ast.expr) -> str | None:
        """The bare module-container name a Subscript target indexes, if
        any (`NAME[k] = v` / `del NAME[k]` / `NAME[k] += v`)."""
        if isinstance(tgt, ast.Subscript) and isinstance(tgt.value, ast.Name):
            if tgt.value.id in self.module_containers:
                return tgt.value.id
        return None

    def visit_With(self, node: ast.With) -> None:
        held = any(
            "lock" in (ast.get_source_segment(self.source, item.context_expr) or "").lower()
            for item in node.items
        )
        if held:
            self._lock_depth += 1
        try:
            self.generic_visit(node)
        finally:
            if held:
                self._lock_depth -= 1

    def visit_Assign(self, node: ast.Assign) -> None:
        for tgt in node.targets:
            base = self._subscript_base(tgt)
            if base is not None:
                self._check_global_mutation(node, base, "[...] = ")
        self.generic_visit(node)

    def visit_AugAssign(self, node: ast.AugAssign) -> None:
        base = self._subscript_base(node.target)
        if base is not None:
            self._check_global_mutation(node, base, "[...] op= ")
        self.generic_visit(node)

    def visit_Delete(self, node: ast.Delete) -> None:
        for tgt in node.targets:
            base = self._subscript_base(tgt)
            if base is not None:
                self._check_global_mutation(node, base, "del [...]")
        self.generic_visit(node)

    # -- HSL007(a): wall-clock duration measurement ----------------------------

    def visit_BinOp(self, node: ast.BinOp) -> None:
        """`time.time() - x` / `x - time.time()` measures a duration (or
        a TTL age) with a steppable clock: an NTP adjustment makes it
        negative or wildly large. Durations want time.monotonic() /
        time.perf_counter(); persisted cross-process stamps are the one
        legitimate wall-clock use — annotate those `# noqa: HSL007`."""
        if isinstance(node.op, ast.Sub):
            for side in (node.left, node.right):
                if isinstance(side, ast.Call) and _dotted(side.func) == "time.time":
                    self._report(
                        node, WALLCLOCK_OR_UNDECLARED,
                        "time.time() in a subtraction — wall clock steps "
                        "under NTP; measure durations/TTL ages with "
                        "time.monotonic() or time.perf_counter() (persisted "
                        "cross-process stamps may stay wall-clock with a "
                        "negative-age guard and `# noqa: HSL007`)",
                    )
                    break
        self.generic_visit(node)

    # -- HSL003: traced-value control flow ------------------------------------

    def _check_branch(self, node, kind: str) -> None:
        if self._in_jit():
            tainted = self._traced_value_names(node.test)
            if tainted:
                self._report(
                    node, TRACED_FLOW,
                    f"Python {kind} on traced value(s) {sorted(tainted)} "
                    f"inside jitted code — branch decisions must use "
                    f"lax.cond/lax.while_loop/jnp.where (shape/dtype "
                    f"attributes are static and fine)",
                )
        self.generic_visit(node)

    def visit_If(self, node: ast.If) -> None:
        self._check_branch(node, "if")

    def visit_While(self, node: ast.While) -> None:
        self._check_branch(node, "while")

    def _traced_value_names(self, test: ast.AST) -> set[str]:
        """Parameter names whose runtime VALUE the test reads. A name
        consumed only through static attributes (x.shape, x.ndim, ...)
        or len() does not count."""
        params = self._jit_params()
        if not params:
            return set()
        static_ids: set[int] = set()
        for sub in ast.walk(test):
            if isinstance(sub, ast.Attribute) and sub.attr in _STATIC_SHAPE_ATTRS:
                for inner in ast.walk(sub.value):
                    if isinstance(inner, ast.Name):
                        static_ids.add(id(inner))
            if (
                isinstance(sub, ast.Call)
                and isinstance(sub.func, ast.Name)
                and sub.func.id in ("len", "isinstance", "getattr", "hasattr")
            ):
                for inner in ast.walk(sub):
                    if isinstance(inner, ast.Name):
                        static_ids.add(id(inner))
        return {
            sub.id
            for sub in ast.walk(test)
            if isinstance(sub, ast.Name)
            and sub.id in params
            and id(sub) not in static_ids
        }


# -- driver ------------------------------------------------------------------

def lint_source(source: str, path: str = "<string>", tree: ast.Module | None = None) -> list[Finding]:
    """Lint one source text; `path` only labels findings (a basename of
    compat.py marks the sanctioned module). Pass `tree` to reuse an
    existing parse — the unified check driver parses each file exactly
    once and feeds the same AST to this linter and the program index."""
    if tree is None:
        tree = ast.parse(source, filename=path)
    name = pathlib.PurePath(path).name
    linter = _Linter(
        path, source, name == SANCTIONED_COMPAT, is_file_utils=name == SANCTIONED_FILE_UTILS
    )
    linter.collect_jit_wrapped(tree)
    linter.collect_module_containers(tree)
    linter.visit(tree)
    return sorted(linter.findings, key=lambda f: (f.path, f.line, f.col))


def lint_paths(paths: list[str]) -> list[Finding]:
    findings: list[Finding] = []
    for p in paths:
        root = pathlib.Path(p)
        files = sorted(root.rglob("*.py")) if root.is_dir() else [root]
        for f in files:
            try:
                src = f.read_text()
            except OSError as e:
                findings.append(Finding(str(f), 0, 0, "HSL000", f"unreadable: {e}"))
                continue
            try:
                findings.extend(lint_source(src, str(f)))
            except SyntaxError as e:
                findings.append(
                    Finding(str(f), e.lineno or 0, e.offset or 0, "HSL000",
                            f"syntax error: {e.msg}")
                )
    return findings


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m hyperspace_tpu.analysis.lint",
        description="Trace-safety / jax-compat / observability linter (rules HSL001-HSL007).",
    )
    ap.add_argument("paths", nargs="+", help="files or directories to lint")
    ap.add_argument(
        "-q", "--quiet", action="store_true", help="suppress the summary line"
    )
    args = ap.parse_args(argv)
    # Unambiguous exit codes: 0 = clean, 1 = findings, 2 = the linter
    # itself crashed (an unreadable/unparseable TARGET is a finding —
    # HSL000 — not a crash).
    try:
        findings = lint_paths(args.paths)
        for f in findings:
            print(f)
        if not args.quiet:
            print(f"{len(findings)} finding(s)", file=sys.stderr)
    except Exception as e:  # pragma: no cover - exercised via unit test stub
        print(f"internal error: {type(e).__name__}: {e}", file=sys.stderr)
        return EXIT_INTERNAL_ERROR
    return EXIT_FINDINGS if findings else EXIT_CLEAN


if __name__ == "__main__":
    sys.exit(main())
