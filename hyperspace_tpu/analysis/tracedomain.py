"""Trace-domain analysis: which code runs inside a TRACE (HSL023-026).

The device plane stacks everything on conventions that, until this
layer, were enforced only by review and by runtime observation: jitted
bodies are host-effect-free, jit cache keys come from a bounded
signature space (obs/runtime.py's ``jit.recompile_storm`` event merely
*observes* violations after the fact), zero-copy staged arrays
(execution/staging.py's writeable=False => identity-stable contract)
are never mutated or donated, and every Pallas engagement sits behind an
explicit eligibility rule whose lowering errors raise. This
module is the device-plane dual of :mod:`procdomain`: instead of
inferring which code runs in which *process*, it infers which code runs
inside a *trace*, then turns each convention into a checked rule.

- **The trace-domain inference.** A *trace entry* is any function
  object handed to a tracing transform: ``compat.jit`` (call form
  ``jit(fn, key=...)`` inside a factory, decorator form ``@jit`` /
  ``@functools.partial(jit, static_argnames=...)``), ``shard_map``
  bodies (same two forms), and Pallas kernel bodies (the first argument
  of a ``pallas_call``). Entries come in two shapes the engine treats
  uniformly: *program functions* (module-level / method defs with their
  own FunctionInfo summaries) and *nested defs* (the ``run``/``kernel``
  closures manufactured inside lru_cache factories — program.py folds
  their call sites into the enclosing function's summary, so the
  nested body is re-walked at AST level and its calls resolved with the
  enclosing function as import/type context). The *trace domain* is the
  dispatch-augmented call-graph closure of every entry, with witness
  chains recorded exactly like procdomain's task closure.

- **HSL023 traced-effect purity.** Nothing in the trace domain may
  touch the host: no ``conf.get``/``conf.set``, no ``stats.increment``,
  no event ``emit``, no lock acquire, no file IO, no ``fault_point``,
  no wall clock, and no host materialization (``.item()``/``.tolist()``,
  ``float()``/``int()``/``bool()`` on non-literals, ``np.asarray``,
  ``jax.device_get``). This is the whole-program upgrade of the
  per-file HSL002/HSL003 checks: those only see a lexically-jitted
  body; this rule follows the closure, so an effect buried two calls
  deep inside a traced helper is found with an entry -> callee witness
  chain.

- **HSL024 signature-space boundedness.** The static proof of
  recompile-storm freedom that HSL015 and the runtime storm event only
  approximate. Three legs: (1) every ``key=`` at a jit site must be a
  string literal (per-call keys defeat the storm detector's grouping);
  (2) every call-form jit must be manufactured inside a bounded cache —
  an ``lru_cache`` factory with a real ``maxsize`` or the HSL015
  memo-container idiom — so the set of live jit callables is finite;
  (3) every static argument name must be declared in
  ``compat.KNOWN_STATIC_DOMAINS`` (or be a parameter of a bounded
  factory, whose memo key already bounds it), and every
  shape-determining pad width must derive from a tile-rounding helper
  (a function returning ``//``/``<<`` arithmetic) rather than a raw
  data-dependent shape. The registry is AST-extracted like
  ``SPAWN_ENTRY_POINTS`` — fixture packages declare their own.

- **HSL025 donation/aliasing safety.** The exact precondition the
  ROADMAP's donated-buffer plans need. A writeable=False staged view
  (a ``stage_column(...)`` result or a ``from_arrow(...,
  zero_copy_ok=True)`` table) may never be mutated in place — callers
  must go through ``ColumnTable.own_arrays`` first — and may never be
  donated to a jitted call; a donated buffer must not be referenced on
  any path after the call that donated it. The report carries a
  donation proof: every staged-view producer, every donation site
  (empty today — that IS the proof), and the ``own_arrays`` ownership
  gateways with call-chain witnesses.

- **HSL026 kernel eligibility-ladder completeness.** Every Pallas
  engagement must be declared in ``ops.KNOWN_KERNELS`` (mirroring
  ``faults.KNOWN_POINTS``, both directions: undeclared engagements and
  stale registry entries are findings), and its *engagement closure*
  (the kernel factory plus its same-module transitive callers) must
  statically contain the full ladder: an explicit eligibility rule (a
  comparison against an uppercase module constant), both a success and
  a fallback ``device.kernel.*`` counter, each declared in
  ``stats.KNOWN_COUNTERS`` — and no broad handler (bare ``except``,
  ``except Exception``/``BaseException``) anywhere in the closure: a
  lowering error of an eligible call must raise, never reroute the
  call to the lax path unseen. The report carries a per-kernel ladder
  proof with the caller-chain witness from the public op down to the
  factory.

Everything here is stdlib-``ast`` only and never imports analyzed code,
same as the rest of the engine (docs/static_analysis.md).
"""

from __future__ import annotations

import ast
import dataclasses

from hyperspace_tpu.analysis.callgraph import CallGraph
from hyperspace_tpu.analysis.lint import (
    _HOST_SYNC_ATTRS,
    _HOST_SYNC_CASTS,
    _NP_SYNC_FNS,
    Finding,
    _dotted,
)
from hyperspace_tpu.analysis.procdomain import _string_tuple_registry, _suppressed
from hyperspace_tpu.analysis.program import FunctionInfo, ModuleInfo, Program

TRACED_EFFECT = "HSL023"
SIGNATURE_SPACE = "HSL024"
DONATION_SAFETY = "HSL025"
KERNEL_LADDER = "HSL026"

#: Call tails that enter a trace when handed a function object.
_TRANSFORMS = ("jit", "shard_map")

#: Wall-clock reads: meaningless inside a trace (they run once, at
#: trace time, and bake a constant into the compiled program).
_WALLCLOCK = {
    "time.time",
    "time.time_ns",
    "time.monotonic",
    "time.monotonic_ns",
    "time.perf_counter",
    "time.perf_counter_ns",
    "datetime.now",
    "datetime.utcnow",
    "datetime.datetime.now",
    "datetime.datetime.utcnow",
}

#: File-IO call tails (HSL023): a traced body must never touch a file.
_FILE_IO_TAILS = {"write_text", "write_bytes", "read_text", "read_bytes"}


def _uppercase_const(name: str) -> bool:
    """Module-constant naming convention: _MAX_PALLAS_K, _RB_TILE, ..."""
    body = name.lstrip("_")
    return bool(body) and body == body.upper() and any(c.isalpha() for c in body)


def declared_static_domains(program: Program) -> set[str] | None:
    """Keys of every scanned module's top-level ``KNOWN_STATIC_DOMAINS``
    dict literal (the real registry lives in compat.py; fixture packages
    and corpus files declare their own), or None when no module declares
    one — the checks that read it disarm, so a corpus file scanned alone
    does not report every static argument undeclared."""
    out: set[str] | None = None
    for mod in program.modules.values():
        for node in mod.tree.body:
            target = value = None
            if isinstance(node, ast.Assign) and len(node.targets) == 1:
                target, value = node.targets[0], node.value
            elif isinstance(node, ast.AnnAssign) and node.value is not None:
                target, value = node.target, node.value
            if not (isinstance(target, ast.Name) and target.id == "KNOWN_STATIC_DOMAINS"):
                continue
            if isinstance(value, ast.Dict):
                out = out or set()
                out.update(
                    k.value for k in value.keys
                    if isinstance(k, ast.Constant) and isinstance(k.value, str)
                )
    return out


def _broad_handler(handler: ast.ExceptHandler) -> bool:
    """A handler that catches every error: bare, Exception or BaseException
    (alone or inside a tuple)."""
    t = handler.type
    if t is None:
        return True
    names = t.elts if isinstance(t, ast.Tuple) else [t]
    return any(_dotted(n) in ("Exception", "BaseException") for n in names)


def _registry_site(program: Program, name: str) -> tuple[ModuleInfo, int] | None:
    """(module, line) of the first top-level assignment declaring `name`."""
    for _, mod in sorted(program.modules.items()):
        for node in mod.tree.body:
            target = None
            if isinstance(node, ast.Assign) and len(node.targets) == 1:
                target = node.targets[0]
            elif isinstance(node, ast.AnnAssign):
                target = node.target
            if isinstance(target, ast.Name) and target.id == name:
                return mod, node.lineno
    return None


@dataclasses.dataclass
class TraceEntry:
    """One function object handed to a tracing transform."""

    traced: str                  # qname, or `<host>.<locals>.<name>` for nested defs
    kind: str                    # "jit" | "shard_map" | "pallas_kernel"
    form: str                    # "call" | "decorator"
    host: str                    # enclosing program function qname (the site)
    line: int                    # site line
    key: str | None = None      # constant key= when present
    key_literal: bool = True    # False when key= is a non-constant expression
    static_names: tuple[str, ...] = ()
    donate_nums: tuple[int, ...] = ()
    donate_names: tuple[str, ...] = ()
    node: ast.AST | None = None  # nested def body when not a program function

    @property
    def donates(self) -> bool:
        return bool(self.donate_nums or self.donate_names)


def _transform_kind(dec: ast.AST) -> tuple[str, ast.Call | None] | None:
    """Classify a decorator (or decorator-shaped expression): returns
    (kind, kwargs-bearing Call or None) for jit/shard_map decorators in
    any of their three spellings: bare ``@jit``, ``@jit(...)``, and
    ``@functools.partial(jit, ...)``."""
    if isinstance(dec, (ast.Name, ast.Attribute)):
        tail = _dotted(dec).rsplit(".", 1)[-1]
        if tail in _TRANSFORMS:
            return tail, None
        return None
    if isinstance(dec, ast.Call):
        ftail = _dotted(dec.func).rsplit(".", 1)[-1]
        if ftail in _TRANSFORMS:
            return ftail, dec
        if ftail == "partial" and dec.args:
            atail = _dotted(dec.args[0]).rsplit(".", 1)[-1]
            if atail in _TRANSFORMS:
                return atail, dec
    return None


def _jit_kwargs(call: ast.Call | None) -> dict:
    """Extract the signature-shaping kwargs of a jit/shard_map call:
    key=, static_argnames/argnums, donate_argnums/argnames."""
    out = {
        "key": None, "key_literal": True, "static_names": (),
        "donate_nums": (), "donate_names": (),
    }
    if call is None:
        return out
    statics: list[str] = []
    dnums: list[int] = []
    dnames: list[str] = []
    for kw in call.keywords:
        values = (
            kw.value.elts
            if isinstance(kw.value, (ast.Tuple, ast.List, ast.Set))
            else [kw.value]
        )
        if kw.arg == "key":
            if isinstance(kw.value, ast.Constant) and isinstance(kw.value.value, str):
                out["key"] = kw.value.value
            else:
                out["key_literal"] = False
        elif kw.arg == "static_argnames":
            statics += [v.value for v in values
                        if isinstance(v, ast.Constant) and isinstance(v.value, str)]
        elif kw.arg == "static_argnums":
            statics += [str(v.value) for v in values
                        if isinstance(v, ast.Constant) and isinstance(v.value, int)]
        elif kw.arg == "donate_argnums":
            dnums += [v.value for v in values
                      if isinstance(v, ast.Constant) and isinstance(v.value, int)]
        elif kw.arg == "donate_argnames":
            dnames += [v.value for v in values
                       if isinstance(v, ast.Constant) and isinstance(v.value, str)]
    out["static_names"] = tuple(statics)
    out["donate_nums"] = tuple(dnums)
    out["donate_names"] = tuple(dnames)
    return out


def _lru_bound(fn_node: ast.AST) -> str | None:
    """"bounded" / "unbounded" when fn is lru_cache-decorated (explicit
    ``maxsize=None`` is the unbounded spelling; the 128 default and any
    integer are bounded), None when it is not a cache factory at all."""
    for dec in getattr(fn_node, "decorator_list", []):
        if not isinstance(dec, ast.Call):
            if _dotted(dec).rsplit(".", 1)[-1] == "lru_cache":
                return "bounded"
            continue
        if _dotted(dec.func).rsplit(".", 1)[-1] != "lru_cache":
            continue
        for kw in dec.keywords:
            if kw.arg == "maxsize":
                if isinstance(kw.value, ast.Constant) and kw.value.value is None:
                    return "unbounded"
                return "bounded"
        if dec.args:
            first = dec.args[0]
            if isinstance(first, ast.Constant) and first.value is None:
                return "unbounded"
        return "bounded"
    return None


def _sub_root(node: ast.AST) -> str | None:
    """Base Name of a Subscript/Attribute store-target chain."""
    while isinstance(node, (ast.Subscript, ast.Attribute)):
        node = node.value
    return node.id if isinstance(node, ast.Name) else None


class TraceDomains:
    """Infer the trace domain and check HSL023-026 over it.

    Same engine contract as :class:`procdomain.ProcessDomains`: built
    from the program summaries and call graph, never importing analyzed
    code; ``findings()`` returns the rule violations and ``to_json()``
    the inferred graph (golden-tested for the jitdemo fixture and
    shipped in the check report's ``trace_domains`` section).
    """

    def __init__(self, program: Program, callgraph: CallGraph, raises=None):
        self.program = program
        self.callgraph = callgraph
        self.raises = raises

        self.entries: list[TraceEntry] = []
        #: pseudo-qname -> (nested def node, enclosing FunctionInfo)
        self.entry_bodies: dict[str, tuple[ast.AST, FunctionInfo]] = {}
        #: trace-domain program functions: qname -> witness chain
        self.trace_fns: dict[str, tuple[str, ...]] = {}
        self.trace_calls_total = 0
        self.trace_calls_unresolved = 0

        self.static_domains = declared_static_domains(program)
        self.known_kernels = _string_tuple_registry(program, "KNOWN_KERNELS")
        self.known_counters = _string_tuple_registry(program, "KNOWN_COUNTERS")

        self._find_entries()
        self._build_closure()
        self._kernel_ladders = self._build_ladders()
        self._donation = None  # built by donation_findings()
        self._findings: list[Finding] | None = None

    # -- entry detection -------------------------------------------------------

    def _find_entries(self) -> None:
        prog, cg = self.program, self.callgraph
        seen: set[tuple[str, str, int]] = set()

        def add(entry: TraceEntry) -> None:
            dedup = (entry.traced, entry.kind, entry.line)
            if dedup not in seen:
                seen.add(dedup)
                self.entries.append(entry)

        for q in sorted(prog.functions):
            fn = prog.functions[q]
            nested: dict[str, ast.AST] = {}
            for sub in ast.walk(fn.node):
                if (
                    isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef))
                    and sub is not fn.node
                ):
                    nested.setdefault(sub.name, sub)

            # Decorator form on the program function itself.
            for dec in fn.node.decorator_list:
                got = _transform_kind(dec)
                if got is None:
                    continue
                kind, call = got
                kw = _jit_kwargs(call)
                add(TraceEntry(
                    traced=q, kind=kind, form="decorator", host=q,
                    line=fn.node.lineno, node=None, **kw,
                ))

            # Decorator form on nested defs (shard_map bodies inside
            # factories: `@functools.partial(shard_map, mesh=...)`).
            for name in sorted(nested):
                nd = nested[name]
                for dec in getattr(nd, "decorator_list", []):
                    got = _transform_kind(dec)
                    if got is None:
                        continue
                    kind, call = got
                    kw = _jit_kwargs(call)
                    add(TraceEntry(
                        traced=f"{q}.<locals>.{name}", kind=kind,
                        form="decorator", host=q, line=nd.lineno, node=nd, **kw,
                    ))

            # Call form: jit(fn, key=...), shard_map(fn, ...),
            # pl.pallas_call(kernel, ...).
            for node in ast.walk(fn.node):
                if not isinstance(node, ast.Call) or not node.args:
                    continue
                tail = _dotted(node.func).rsplit(".", 1)[-1]
                if tail not in _TRANSFORMS and tail != "pallas_call":
                    continue
                raw = _dotted(node.args[0])
                if not raw:
                    continue
                kind = "pallas_kernel" if tail == "pallas_call" else tail
                kw = _jit_kwargs(node)
                if raw in nested:
                    add(TraceEntry(
                        traced=f"{q}.<locals>.{raw}", kind=kind, form="call",
                        host=q, line=node.lineno, node=nested[raw], **kw,
                    ))
                else:
                    got = cg.resolve_call(fn, raw)
                    if got is not None and got in prog.functions:
                        add(TraceEntry(
                            traced=got, kind=kind, form="call", host=q,
                            line=node.lineno, node=None, **kw,
                        ))

        for e in self.entries:
            if e.node is not None and e.traced not in self.entry_bodies:
                self.entry_bodies[e.traced] = (e.node, prog.functions[e.host])

    # -- closure ---------------------------------------------------------------

    def _dispatch(self, callee: str) -> tuple[str, ...]:
        if self.raises is not None:
            return self.raises.dispatch_targets(callee)
        return (callee,)

    def _resolve_traced(self, fn: FunctionInfo, raw: str) -> str | None:
        """resolve_call, minus the unique-method-name fallback for
        ungrounded receivers. Traced bodies call mostly jax APIs
        (``jax.lax.scan``, ``x.sum()``) whose names collide with program
        methods (``Dataset.scan``, ``Histogram.sum``); accepting the
        name-only fallback would pull host code into the trace domain
        and manufacture false purity findings. Rejections are counted in
        the unresolved ratio — the honest record of the blind spot."""
        got = self.callgraph.resolve_call(fn, raw)
        if got is None:
            return None
        parts = raw.split(".")
        if len(parts) == 1 or "()." in raw or parts[0] in ("self", "super"):
            return got
        prog = self.program
        root = parts[0]
        target = prog.resolve_symbol(fn.module, root, fn=fn)
        if target is not None and (
            target in prog.functions
            or target in prog.classes
            or any(
                m == target or m.startswith(target + ".")
                for m in prog.modules
            )
        ):
            return got
        src = fn.local_types.get(root)
        mod = prog.modules.get(fn.module)
        if src is None and mod is not None:
            src = mod.var_types.get(root)
        if src is not None:
            if src.endswith("()") and prog.class_of_ctor(
                fn.module, src[:-2], fn=fn
            ):
                return got
            if src.startswith("self."):
                return got
        return None

    def _build_closure(self) -> None:
        prog, cg = self.program, self.callgraph
        stack: list[str] = []

        for e in self.entries:
            if e.node is None and e.traced not in self.trace_fns:
                self.trace_fns[e.traced] = (e.traced,)
                stack.append(e.traced)

        # Nested entry bodies: program.py folds their calls into the
        # enclosing factory's summary, but the factory itself is host
        # code — so the nested body is re-walked here and its calls
        # resolved with the factory as context (the factory's imports
        # and local types are exactly the names the body closes over).
        for traced in sorted(self.entry_bodies):
            node, host_fn = self.entry_bodies[traced]
            for stmt in node.body:
                for sub in ast.walk(stmt):
                    if not isinstance(sub, ast.Call):
                        continue
                    raw = _dotted(sub.func)
                    if not raw:
                        continue
                    self.trace_calls_total += 1
                    got = self._resolve_traced(host_fn, raw)
                    if got is None:
                        self.trace_calls_unresolved += 1
                        continue
                    for t in self._dispatch(got):
                        if t in prog.functions and t not in self.trace_fns:
                            self.trace_fns[t] = (traced, t)
                            stack.append(t)

        while stack:
            q = stack.pop()
            fn = prog.functions.get(q)
            if fn is None:
                continue
            for call in fn.calls:
                self.trace_calls_total += 1
                callee = self._resolve_traced(fn, call.raw)
                if callee is None:
                    self.trace_calls_unresolved += 1
                    continue
                for t in self._dispatch(callee):
                    if t in prog.functions and t not in self.trace_fns:
                        self.trace_fns[t] = (*self.trace_fns[q], t)
                        stack.append(t)

    def unresolved_ratio(self) -> float:
        if not self.trace_calls_total:
            return 0.0
        return round(self.trace_calls_unresolved / self.trace_calls_total, 4)

    # -- HSL023: traced-effect purity ------------------------------------------

    def purity_findings(self) -> list[Finding]:
        out: list[Finding] = []
        prog = self.program

        for traced in sorted(self.entry_bodies):
            node, host_fn = self.entry_bodies[traced]
            mod = prog.modules[host_fn.module]
            out += self._purity_walk(traced, node, mod, (traced,))

        for q in sorted(self.trace_fns):
            fn = prog.functions[q]
            mod = prog.modules[fn.module]
            # A function lexically decorated with a transform is already
            # inside HSL002's sight: its host-sync materializations are
            # per-file findings, and re-reporting them here would double
            # every `.item()`-in-jit. The closure-only effects (counters,
            # locks, clock, conf, IO) still report — HSL002 never checks
            # those.
            lexical = any(
                _transform_kind(d) is not None for d in fn.node.decorator_list
            )
            out += self._purity_walk(
                q, fn.node, mod, self.trace_fns[q], skip_host_sync=lexical
            )
        return out

    def _purity_walk(
        self, owner: str, node: ast.AST, mod: ModuleInfo, chain: tuple[str, ...],
        skip_host_sync: bool = False,
    ) -> list[Finding]:
        out: list[Finding] = []
        prog = self.program
        witness = tuple(dict.fromkeys(
            prog.modules[prog.functions[c].module].path
            for c in chain if c in prog.functions
        )) or (mod.path,)
        via = " -> ".join(chain)

        def report(sub: ast.AST, what: str) -> None:
            if _suppressed(mod, sub.lineno, TRACED_EFFECT):
                return
            out.append(Finding(
                path=mod.path, line=sub.lineno, col=sub.col_offset,
                rule=TRACED_EFFECT,
                message=(
                    f"{what} inside the trace domain (traced via {via}) — "
                    f"jitted bodies must be host-effect-free: hoist the "
                    f"effect to the engagement site outside the traced "
                    f"function"
                ),
                witness_paths=witness,
            ))

        # Walk statement bodies only: decorator expressions (e.g. the
        # mesh argument of `@functools.partial(shard_map, mesh=...)`)
        # evaluate at definition time on the host, not inside the trace.
        for stmt in getattr(node, "body", []):
            for sub in ast.walk(stmt):
                if isinstance(sub, (ast.With, ast.AsyncWith)):
                    for item in sub.items:
                        if isinstance(item.context_expr, (ast.Name, ast.Attribute)):
                            report(sub, "lock acquire")
                    continue
                if not isinstance(sub, ast.Call):
                    continue
                dotted = _dotted(sub.func)
                parts = dotted.split(".") if dotted else []
                tail = parts[-1] if parts else ""
                if isinstance(sub.func, ast.Attribute) and sub.func.attr in _HOST_SYNC_ATTRS:
                    if not skip_host_sync:
                        report(sub, f".{sub.func.attr}() host materialization")
                elif (
                    isinstance(sub.func, ast.Name)
                    and sub.func.id in _HOST_SYNC_CASTS
                    and sub.args
                    and not all(isinstance(a, ast.Constant) for a in sub.args)
                ):
                    if not skip_host_sync:
                        report(sub, f"{sub.func.id}() host cast of a traced value")
                elif tail in _NP_SYNC_FNS and parts[0] in ("np", "numpy"):
                    if not skip_host_sync:
                        report(sub, f"{dotted}() host materialization")
                elif dotted in ("jax.device_get", "device_get"):
                    if not skip_host_sync:
                        report(sub, "jax.device_get host transfer")
                elif tail == "increment":
                    report(sub, "stats counter increment")
                elif tail == "emit":
                    report(sub, "event emit")
                elif tail in ("fault_point", "inject"):
                    report(sub, "fault-point evaluation")
                elif tail == "open" or tail in _FILE_IO_TAILS:
                    report(sub, "file IO")
                elif dotted in _WALLCLOCK:
                    report(sub, f"wall-clock read {dotted}()")
                elif len(parts) >= 2 and parts[-2] == "conf" and tail in ("get", "set"):
                    report(sub, f"configuration {tail} via conf")
                elif tail == "acquire":
                    report(sub, "explicit lock acquire")
        return out

    # -- HSL024: signature-space boundedness -----------------------------------

    def signature_findings(self) -> list[Finding]:
        out: list[Finding] = []
        prog = self.program
        declared = self.static_domains
        used_static: set[str] = set()

        for e in self.entries:
            if e.kind == "pallas_kernel":
                continue
            host = prog.functions[e.host]
            mod = prog.modules[host.module]
            used_static.update(e.static_names)

            if not e.key_literal and not _suppressed(mod, e.line, SIGNATURE_SPACE):
                out.append(Finding(
                    path=mod.path, line=e.line, col=0, rule=SIGNATURE_SPACE,
                    message=(
                        f"jit key= at {e.host} is not a string literal — "
                        f"per-call keys defeat recompile-storm grouping; use "
                        f"one constant key per jit site"
                    ),
                    witness_paths=(mod.path,),
                ))

            bound = _lru_bound(host.node)
            if e.kind == "jit" and e.form == "call":
                # The bound-None nested-def case (a plain function
                # manufacturing jit(local_closure) per call) is HSL015's
                # finding — only the cases HSL015 cannot see report here:
                # an explicitly unbounded factory, or jit of a program
                # function outside any cache.
                unmemoized_program_fn = (
                    bound is None
                    and e.node is None
                    and not self._memo_stored(host, e)
                )
                if bound == "unbounded" or unmemoized_program_fn:
                    if not _suppressed(mod, e.line, SIGNATURE_SPACE):
                        out.append(Finding(
                            path=mod.path, line=e.line, col=0, rule=SIGNATURE_SPACE,
                            message=(
                                f"jit callable manufactured in {e.host} outside "
                                f"a bounded cache — wrap the factory in "
                                f"functools.lru_cache with a real maxsize (or "
                                f"store the callable in a locked memo "
                                f"container) so the set of live jit callables "
                                f"is finite"
                            ),
                            witness_paths=(mod.path,),
                        ))

            if declared is not None and bound != "bounded":
                for name in e.static_names:
                    if name in declared or _suppressed(mod, e.line, SIGNATURE_SPACE):
                        continue
                    out.append(Finding(
                        path=mod.path, line=e.line, col=0, rule=SIGNATURE_SPACE,
                        message=(
                            f"static argument {name!r} of {e.traced} is not "
                            f"declared in compat.KNOWN_STATIC_DOMAINS — every "
                            f"static value must come from a declared bounded "
                            f"domain, or each new value recompiles"
                        ),
                        witness_paths=(mod.path,),
                    ))

        out += self._stale_domain_findings(used_static)
        out += self._pad_findings()
        return out

    def _memo_stored(self, host: FunctionInfo, e: TraceEntry) -> bool:
        """True when the jit result is stored into a subscripted memo
        container inside the host (the HSL015-sanctioned idiom:
        ``fn = jit(raw, key=...); _CACHE[key] = fn``)."""
        jit_names: set[str] = set()
        for sub in ast.walk(host.node):
            if not isinstance(sub, ast.Assign) or len(sub.targets) != 1:
                continue
            tgt, val = sub.targets[0], sub.value
            if (
                isinstance(val, ast.Call)
                and _dotted(val.func).rsplit(".", 1)[-1] in _TRANSFORMS
            ):
                if isinstance(tgt, ast.Name):
                    jit_names.add(tgt.id)
                elif isinstance(tgt, ast.Subscript):
                    return True
            elif (
                isinstance(tgt, ast.Subscript)
                and isinstance(val, ast.Name)
                and val.id in jit_names
            ):
                return True
        return False

    def _stale_domain_findings(self, used_static: set[str]) -> list[Finding]:
        """A KNOWN_STATIC_DOMAINS entry that no jit site uses as a
        static argument and no trace-hosting module uses as a parameter
        name is stale — the registry must stay honest both ways, like
        faults.KNOWN_POINTS."""
        declared = self.static_domains
        if not declared:
            return []
        prog = self.program
        host_modules = {prog.functions[e.host].module for e in self.entries}
        param_names: set[str] = set()
        for q, fn in prog.functions.items():
            if fn.module not in host_modules:
                continue
            args = fn.node.args
            for a in (
                list(args.posonlyargs) + list(args.args)
                + list(args.kwonlyargs)
            ):
                param_names.add(a.arg)
        site = _registry_site(prog, "KNOWN_STATIC_DOMAINS")
        if site is None:
            return []
        mod, line = site
        out = []
        for name in sorted(declared - used_static - param_names):
            if _suppressed(mod, line, SIGNATURE_SPACE):
                continue
            out.append(Finding(
                path=mod.path, line=line, col=0, rule=SIGNATURE_SPACE,
                message=(
                    f"KNOWN_STATIC_DOMAINS entry {name!r} matches no static "
                    f"argument and no parameter of any trace-hosting module — "
                    f"remove the stale entry (the declared-registry contract)"
                ),
                witness_paths=(mod.path,),
            ))
        return out

    def _is_rounder(self, qname: str | None) -> bool:
        """A tile-rounding helper: a program function any of whose
        return expressions uses ``//``/``<<``/``%`` arithmetic (the
        ``_next_mult`` / ``next_pow2`` shape) — its results range over a
        bounded lattice of shapes, so pads derived from it cannot storm
        the compile cache."""
        fn = self.program.functions.get(qname or "")
        if fn is None:
            return False
        for sub in ast.walk(fn.node):
            if isinstance(sub, ast.Return) and sub.value is not None:
                for b in ast.walk(sub.value):
                    if isinstance(b, ast.BinOp) and isinstance(
                        b.op, (ast.FloorDiv, ast.LShift, ast.Mod)
                    ):
                        return True
            if isinstance(sub, ast.AugAssign) and isinstance(sub.op, ast.LShift):
                return True  # the `v <<= 1` loop body of next_pow2
        return False

    def _pad_findings(self) -> list[Finding]:
        """Shape-determining pad widths in trace-hosting modules must
        derive from a rounding helper: a width element that references a
        raw shape-derived local (``n = x.shape[0]`` / ``len(x)``) with
        no tile-rounded atom next to it recompiles once per distinct
        input length."""
        out: list[Finding] = []
        prog, cg = self.program, self.callgraph
        host_modules = {prog.functions[e.host].module for e in self.entries}

        for q in sorted(prog.functions):
            fn = prog.functions[q]
            if fn.module not in host_modules:
                continue
            mod = prog.modules[fn.module]
            shapeish: set[str] = set()
            rounded: set[str] = set()
            for sub in ast.walk(fn.node):
                if not isinstance(sub, ast.Assign):
                    continue
                names: list[str] = []
                for tgt in sub.targets:
                    if isinstance(tgt, ast.Name):
                        names.append(tgt.id)
                    elif isinstance(tgt, ast.Tuple):
                        names += [e.id for e in tgt.elts if isinstance(e, ast.Name)]
                if not names:
                    continue
                val = sub.value
                is_shape = any(
                    (isinstance(b, ast.Attribute) and b.attr in ("shape", "size"))
                    or (isinstance(b, ast.Call) and _dotted(b.func) == "len")
                    for b in ast.walk(val)
                )
                is_rounded = any(
                    isinstance(b, ast.Call)
                    and self._is_rounder(cg.resolve_call(fn, _dotted(b.func)))
                    for b in ast.walk(val)
                )
                if is_rounded:
                    rounded.update(names)
                elif is_shape:
                    shapeish.update(names)

            for sub in ast.walk(fn.node):
                if not isinstance(sub, ast.Call) or len(sub.args) < 2:
                    continue
                parts = _dotted(sub.func).split(".")
                if parts[-1] != "pad" or parts[0] not in ("jnp", "np", "numpy", "jax"):
                    continue
                widths = sub.args[1]
                elements = (
                    [e for t in widths.elts for e in (t.elts if isinstance(t, ast.Tuple) else [t])]
                    if isinstance(widths, (ast.Tuple, ast.List))
                    else [widths]
                )
                for el in elements:
                    names = {b.id for b in ast.walk(el) if isinstance(b, ast.Name)}
                    if names & shapeish and not names & rounded:
                        if _suppressed(mod, sub.lineno, SIGNATURE_SPACE):
                            continue
                        out.append(Finding(
                            path=mod.path, line=sub.lineno, col=sub.col_offset,
                            rule=SIGNATURE_SPACE,
                            message=(
                                f"pad width in {q} derives from a raw "
                                f"data-dependent shape ({', '.join(sorted(names & shapeish))}) "
                                f"with no tile-rounding — every distinct input "
                                f"length mints a new compile signature; round "
                                f"the target size first (_next_mult idiom)"
                            ),
                            witness_paths=(mod.path,),
                        ))
        return out

    # -- HSL025: donation/aliasing safety --------------------------------------

    def donation_findings(self) -> list[Finding]:
        out: list[Finding] = []
        prog, cg = self.program, self.callgraph
        producers: list[dict] = []
        gateways: list[dict] = []
        gateway_fns: set[str] = set()
        staged_by_fn: dict[str, dict[str, set[str]]] = {}

        for q in sorted(prog.functions):
            fn = prog.functions[q]
            mod = prog.modules[fn.module]
            staged: set[str] = set()
            owned: set[str] = set()
            for sub in ast.walk(fn.node):
                if isinstance(sub, ast.Assign) and len(sub.targets) == 1:
                    tgt, val = sub.targets[0], sub.value
                    if not (isinstance(tgt, ast.Name) and isinstance(val, ast.Call)):
                        continue
                    tail = _dotted(val.func).rsplit(".", 1)[-1]
                    if tail == "stage_column":
                        staged.add(tgt.id)
                        producers.append({"fn": q, "line": sub.lineno, "kind": "stage_column"})
                    elif tail == "from_arrow" and any(
                        kw.arg == "zero_copy_ok"
                        and isinstance(kw.value, ast.Constant)
                        and kw.value.value is True
                        for kw in val.keywords
                    ):
                        staged.add(tgt.id)
                        producers.append(
                            {"fn": q, "line": sub.lineno, "kind": "zero_copy_from_arrow"}
                        )
                elif isinstance(sub, ast.Call):
                    d = _dotted(sub.func)
                    if d.rsplit(".", 1)[-1] == "own_arrays":
                        root = d.split(".")[0]
                        owned.add(root)
                        gateways.append({"fn": q, "line": sub.lineno})
                        gateway_fns.add(q)
            staged_by_fn[q] = {"staged": staged, "owned": owned}

            # In-place mutation of a staged view.
            for sub in ast.walk(fn.node):
                tgts: list[ast.AST] = []
                if isinstance(sub, ast.Assign):
                    tgts = [t for t in sub.targets if isinstance(t, ast.Subscript)]
                elif isinstance(sub, ast.AugAssign) and isinstance(sub.target, ast.Subscript):
                    tgts = [sub.target]
                for t in tgts:
                    root = _sub_root(t)
                    if root in staged and root not in owned:
                        if _suppressed(mod, sub.lineno, DONATION_SAFETY):
                            continue
                        out.append(Finding(
                            path=mod.path, line=sub.lineno, col=sub.col_offset,
                            rule=DONATION_SAFETY,
                            message=(
                                f"in-place mutation of zero-copy staged view "
                                f"{root!r} in {q} — writeable=False staged "
                                f"arrays are identity-stable by contract; call "
                                f"ColumnTable.own_arrays() (copying ownership "
                                f"gateway) before mutating"
                            ),
                            witness_paths=(mod.path,),
                        ))

        donation_sites = [e for e in self.entries if e.donates]
        for e in donation_sites:
            traced_fn = prog.functions.get(e.traced)
            if traced_fn is None:
                continue
            params = [a.arg for a in traced_fn.node.args.args]
            idxs = set(e.donate_nums)
            idxs.update(params.index(n) for n in e.donate_names if n in params)
            for q in sorted(prog.functions):
                fn = prog.functions[q]
                mod = prog.modules[fn.module]
                info = staged_by_fn.get(q, {"staged": set(), "owned": set()})
                for sub in ast.walk(fn.node):
                    if not isinstance(sub, ast.Call):
                        continue
                    if cg.resolve_call(fn, _dotted(sub.func)) != e.traced:
                        continue
                    for i in sorted(idxs):
                        if i >= len(sub.args):
                            continue
                        root = _sub_root(sub.args[i]) if not isinstance(
                            sub.args[i], ast.Name
                        ) else sub.args[i].id
                        if root is None:
                            continue
                        if root in info["staged"] and root not in info["owned"]:
                            if not _suppressed(mod, sub.lineno, DONATION_SAFETY):
                                out.append(Finding(
                                    path=mod.path, line=sub.lineno, col=sub.col_offset,
                                    rule=DONATION_SAFETY,
                                    message=(
                                        f"zero-copy staged view {root!r} donated "
                                        f"to {e.traced} in {q} — donation frees "
                                        f"the buffer the Arrow table still "
                                        f"aliases; own_arrays() first"
                                    ),
                                    witness_paths=(mod.path,),
                                ))
                        used_after = any(
                            isinstance(b, ast.Name)
                            and b.id == root
                            and isinstance(b.ctx, ast.Load)
                            and b.lineno > (sub.end_lineno or sub.lineno)
                            for b in ast.walk(fn.node)
                        )
                        if used_after and not _suppressed(mod, sub.lineno, DONATION_SAFETY):
                            out.append(Finding(
                                path=mod.path, line=sub.lineno, col=sub.col_offset,
                                rule=DONATION_SAFETY,
                                message=(
                                    f"buffer {root!r} is referenced after being "
                                    f"donated to {e.traced} in {q} — a donated "
                                    f"buffer is dead after the call on every "
                                    f"path; copy first or drop the reference"
                                ),
                                witness_paths=(mod.path,),
                            ))

        self._donation = {
            "staged_view_producers": [
                {
                    **p,
                    "ownership_witness": cg.find_path(p["fn"], gateway_fns)
                    if gateway_fns else None,
                }
                for p in producers
            ],
            "donation_sites": [
                {"fn": e.host, "line": e.line, "traced": e.traced}
                for e in donation_sites
            ],
            "own_arrays_gateways": gateways,
            "proven": True,  # flipped below if findings exist
        }
        if out:
            self._donation["proven"] = False
        return out

    # -- HSL026: kernel eligibility-ladder completeness ------------------------

    def _build_ladders(self) -> list[dict]:
        prog, cg = self.program, self.callgraph
        ladders: list[dict] = []
        pallas_hosts: dict[str, int] = {}
        for e in self.entries:
            if e.kind == "pallas_kernel" and e.host not in pallas_hosts:
                pallas_hosts[e.host] = e.line

        for host in sorted(pallas_hosts):
            key = next(
                (e.key for e in self.entries
                 if e.host == host and e.kind == "jit" and e.key),
                None,
            )
            factory = prog.functions[host]
            engagement: dict[str, list[str]] = {host: [host]}
            for q in sorted(prog.functions):
                if q == host or prog.functions[q].module != factory.module:
                    continue
                path = cg.find_path(q, {host})
                if path is not None:
                    engagement[q] = path

            gate = swallow = None
            counters: dict[str, tuple[str, int]] = {}
            for q in sorted(engagement):
                fn = prog.functions[q]
                for sub in ast.walk(fn.node):
                    # Gate: any comparison against an uppercase bound
                    # constant — whether in an `if` test or assigned to
                    # an eligibility flag (topk's `use_pallas = ...`).
                    if isinstance(sub, ast.Compare) and gate is None:
                        for b in ast.walk(sub):
                            if isinstance(b, ast.Name) and _uppercase_const(b.id):
                                gate = {"fn": q, "line": sub.lineno}
                                break
                    if (
                        isinstance(sub, ast.ExceptHandler)
                        and swallow is None
                        and _broad_handler(sub)
                    ):
                        swallow = {"fn": q, "line": sub.lineno}
                    if isinstance(sub, ast.Call):
                        d = _dotted(sub.func)
                        if (
                            d.rsplit(".", 1)[-1] == "increment"
                            and sub.args
                            and isinstance(sub.args[0], ast.Constant)
                            and isinstance(sub.args[0].value, str)
                            and sub.args[0].value.startswith("device.kernel.")
                        ):
                            counters.setdefault(sub.args[0].value, (q, sub.lineno))

            witness = max(engagement.values(), key=len)
            ladders.append({
                "kernel": key or host,
                "factory": host,
                "line": pallas_hosts[host],
                "engagement": sorted(engagement),
                "gate": gate,
                "swallow": swallow,
                "counters": {
                    name: {"fn": counters[name][0], "line": counters[name][1]}
                    for name in sorted(counters)
                },
                "witness": witness,
                "proven": bool(
                    gate and swallow is None
                    and any("fallback" in c for c in counters)
                    and any("fallback" not in c for c in counters)
                ),
            })
        return ladders

    def kernel_findings(self) -> list[Finding]:
        out: list[Finding] = []
        prog = self.program
        declared = self.known_kernels
        found_names = {lad["kernel"] for lad in self._kernel_ladders}

        for lad in self._kernel_ladders:
            host = lad["factory"]
            mod = prog.modules[prog.functions[host].module]
            witness = tuple(dict.fromkeys(
                prog.modules[prog.functions[q].module].path
                for q in lad["engagement"] if q in prog.functions
            ))
            if declared is not None and lad["kernel"] not in declared:
                if not _suppressed(mod, lad["line"], KERNEL_LADDER):
                    out.append(Finding(
                        path=mod.path, line=lad["line"], col=0, rule=KERNEL_LADDER,
                        message=(
                            f"Pallas engagement {lad['kernel']!r} (factory "
                            f"{host}) is not declared in ops.KNOWN_KERNELS — "
                            f"declare it so the eligibility ladder is tracked "
                            f"(the declared-registry contract)"
                        ),
                        witness_paths=witness,
                    ))
            missing = []
            if lad["gate"] is None:
                missing.append("exactness/eligibility gate (compare against an "
                               "uppercase bound constant)")
            if lad["swallow"] is not None:
                sw = lad["swallow"]
                missing.append(f"lowering errors that raise (a broad except in "
                               f"{sw['fn']} line {sw['line']} swallows them)")
            if not any("fallback" not in c for c in lad["counters"]):
                missing.append("success counter (device.kernel.* increment on "
                               "the engaged path)")
            if not any("fallback" in c for c in lad["counters"]):
                missing.append("fallback counter (device.kernel.* increment on "
                               "the fallback path)")
            if missing and not _suppressed(mod, lad["line"], KERNEL_LADDER):
                chain = " -> ".join(lad["witness"])
                out.append(Finding(
                    path=mod.path, line=lad["line"], col=0, rule=KERNEL_LADDER,
                    message=(
                        f"Pallas kernel {lad['kernel']!r} has an incomplete "
                        f"eligibility ladder (engagement chain {chain}): missing "
                        + "; ".join(missing)
                    ),
                    witness_paths=witness,
                ))
            if self.known_counters is not None:
                for cname in sorted(lad["counters"]):
                    if cname in self.known_counters:
                        continue
                    site = lad["counters"][cname]
                    if not _suppressed(mod, site["line"], KERNEL_LADDER):
                        out.append(Finding(
                            path=mod.path, line=site["line"], col=0,
                            rule=KERNEL_LADDER,
                            message=(
                                f"kernel counter {cname!r} is not declared in "
                                f"stats.KNOWN_COUNTERS — undeclared names "
                                f"raise at runtime"
                            ),
                            witness_paths=witness,
                        ))

        if declared is not None:
            site = _registry_site(prog, "KNOWN_KERNELS")
            if site is not None:
                mod, line = site
                for name in sorted(declared - found_names):
                    if _suppressed(mod, line, KERNEL_LADDER):
                        continue
                    out.append(Finding(
                        path=mod.path, line=line, col=0, rule=KERNEL_LADDER,
                        message=(
                            f"KNOWN_KERNELS entry {name!r} matches no Pallas "
                            f"engagement in the scanned program — remove the "
                            f"stale entry (the declared-registry contract)"
                        ),
                        witness_paths=(mod.path,),
                    ))
        return out

    # -- driver ----------------------------------------------------------------

    def findings(self) -> list[Finding]:
        if self._findings is None:
            out: list[Finding] = []
            out += self.purity_findings()
            out += self.signature_findings()
            out += self.donation_findings()
            out += self.kernel_findings()
            self._findings = out
        return self._findings

    def to_json(self) -> dict:
        self.findings()  # materialize the donation proof
        entries: dict[str, dict] = {}
        for e in sorted(self.entries, key=lambda e: (e.traced, e.line, e.kind)):
            cur = entries.setdefault(e.traced, {
                "kinds": [], "site": e.host, "line": e.line,
                "key": None, "static": [], "donates": False,
            })
            if e.kind not in cur["kinds"]:
                cur["kinds"].append(e.kind)
                cur["kinds"].sort()
            if e.key and cur["key"] is None:
                cur["key"] = e.key
            cur["static"] = sorted(set(cur["static"]) | set(e.static_names))
            cur["donates"] = cur["donates"] or e.donates
        return {
            "entries": entries,
            "trace_functions": {
                q: list(chain) for q, chain in sorted(self.trace_fns.items())
            },
            "unresolved": {
                "total": self.trace_calls_total,
                "unresolved": self.trace_calls_unresolved,
                "ratio": self.unresolved_ratio(),
            },
            "donation_proof": self._donation,
            "kernel_ladders": self._kernel_ladders,
        }
