"""Whole-program analysis engine tests (analysis/program.py,
callgraph.py, locks.py, check.py): fixture-package goldens, the seeded
lock-inversion regression, the per-rule corpus, and the repo-wide
guarantees the CI check gate rides on (cycle-free lock graph, zero
config/fault drift)."""

from __future__ import annotations

import ast
import json
import pathlib
import subprocess
import sys

import pytest

from hyperspace_tpu.analysis.callgraph import CallGraph
from hyperspace_tpu.analysis.check import (
    TEST_ALLOWLIST,
    changed_files as check_mod_changed_files,
    config_key_findings,
    default_paths,
    fault_point_findings,
    main as check_main,
    run_check,
    validator_corpus,
)
from hyperspace_tpu.analysis.lint import (
    EXIT_CLEAN,
    EXIT_FINDINGS,
    EXIT_INTERNAL_ERROR,
    RULES,
    lint_source,
)
from hyperspace_tpu.analysis.duradomain import DurabilityDomains
from hyperspace_tpu.analysis.effects import Effects
from hyperspace_tpu.analysis.locks import LockGraph, resource_findings
from hyperspace_tpu.analysis.procdomain import (
    SPAWN_ENTRY_POINTS,
    ProcessDomains,
    declared_entry_points,
    module_level_imports,
)
from hyperspace_tpu.analysis.program import Program, _index_module, _module_name
from hyperspace_tpu.analysis.tracedomain import (
    TraceDomains,
    declared_static_domains,
)
from hyperspace_tpu.analysis.races import (
    RACE_ALLOWLIST,
    atomicity_findings,
    jit_hygiene_findings,
    lockset_race_findings,
)
from hyperspace_tpu.analysis.raises import (
    DYNAMIC,
    DYNAMIC_RAISES,
    Raises,
    declared_contracts,
    error_contract_findings,
    known_fault_points,
    recovery_roots,
    swallowed_findings,
    unwind_findings,
)

TESTS_DIR = pathlib.Path(__file__).resolve().parent
FIXTURES = TESTS_DIR / "analysis_fixtures"
REPO_ROOT = TESTS_DIR.parent


# -- shared fixtures ----------------------------------------------------------

@pytest.fixture(scope="module")
def lockdemo():
    program = Program.load([FIXTURES / "lockdemo"])
    callgraph = CallGraph(program)
    return program, callgraph, LockGraph(program, callgraph)


@pytest.fixture(scope="module")
def repo_program():
    program = Program.load(default_paths(REPO_ROOT))
    callgraph = CallGraph(program)
    return program, callgraph


@pytest.fixture(scope="module")
def repo_check():
    """One timed full run_check over the real tree. run_check is pure
    (static analysis of on-disk sources), so every test that reads the
    report shares this pass — including the wall-time gate, which reads
    the clock captured here instead of paying for its own full run."""
    import time

    t0 = time.perf_counter()
    report = run_check(default_paths(REPO_ROOT), REPO_ROOT, [TESTS_DIR])
    return report, time.perf_counter() - t0


# -- fixture-package goldens --------------------------------------------------

class TestLockdemoGoldens:
    def test_call_graph_matches_golden(self, lockdemo):
        _, callgraph, _ = lockdemo
        golden = json.loads((FIXTURES / "goldens" / "lockdemo_callgraph.json").read_text())
        assert json.loads(json.dumps(callgraph.to_json())) == golden

    def test_lock_graph_matches_golden(self, lockdemo):
        _, _, lockgraph = lockdemo
        golden = json.loads((FIXTURES / "goldens" / "lockdemo_lockgraph.json").read_text())
        assert json.loads(json.dumps(lockgraph.to_json())) == golden

    def test_lock_identities_and_kinds(self, lockdemo):
        program, _, _ = lockdemo
        assert program.locks["lockdemo.alpha._registry_lock"].kind == "Lock"
        assert program.locks["lockdemo.alpha.Session._state_lock"].kind == "RLock"
        assert program.locks["lockdemo.alpha.Cache._lock"].cls == "Cache"

    def test_typed_attribute_call_resolution(self, lockdemo):
        # self.cache = Cache() makes self.cache.put_entry resolve without
        # any unique-name fallback.
        _, callgraph, _ = lockdemo
        assert "lockdemo.alpha.Cache.put_entry" in callgraph.callees(
            "lockdemo.alpha.Session.publish"
        )

    def test_cross_module_call_resolution(self, lockdemo):
        _, callgraph, _ = lockdemo
        assert "lockdemo.beta.audit" in callgraph.callees("lockdemo.alpha.register")
        assert "lockdemo.alpha.register" in callgraph.callees("lockdemo.beta.rollback")

    def test_reachability(self, lockdemo):
        _, callgraph, _ = lockdemo
        reach = callgraph.reachable("lockdemo.beta.rollback")
        assert "lockdemo.beta.audit" in reach  # rollback -> register -> audit


class TestSeededInversion:
    """The acceptance regression: HSL009 catches the deliberately
    inverted lock pair in the fixture package, with a two-chain witness
    naming both conflicting call chains."""

    def test_inversion_reported(self, lockdemo):
        _, _, lockgraph = lockdemo
        rules = [f.rule for f in lockgraph.inversions()]
        assert "HSL009" in rules

    def test_two_chain_witness(self, lockdemo):
        _, _, lockgraph = lockdemo
        pair = [
            f for f in lockgraph.inversions()
            if "_registry_lock" in f.message and "_audit_lock" in f.message
            and "inversion" in f.message
        ]
        assert len(pair) == 1
        msg = pair[0].message
        assert "chain 1" in msg and "chain 2" in msg
        # chain 1: register (holds registry) -> audit; chain 2:
        # rollback (holds audit) -> register.
        assert "lockdemo.alpha.register -> lockdemo.beta.audit" in msg
        assert "lockdemo.beta.rollback -> lockdemo.alpha.register" in msg

    def test_transitive_self_deadlock_reported(self, lockdemo):
        # rollback holds the (non-reentrant) audit lock and the chain
        # register -> audit re-acquires it: a real self-deadlock.
        _, _, lockgraph = lockdemo
        assert any(
            "re-acquired while already held" in f.message
            for f in lockgraph.inversions()
        )

    def test_rlock_reentry_not_flagged(self, lockdemo):
        # Session.refresh -> snapshot re-enters the session RLock: legal.
        _, _, lockgraph = lockdemo
        assert not any(
            "_state_lock" in f.message for f in lockgraph.inversions()
        )

    def test_edge_direction_recorded_both_ways(self, lockdemo):
        _, _, lockgraph = lockdemo
        best = lockgraph.order_edges()
        assert ("lockdemo.alpha._registry_lock", "lockdemo.beta._audit_lock") in best
        assert ("lockdemo.beta._audit_lock", "lockdemo.alpha._registry_lock") in best


# -- per-rule corpus ----------------------------------------------------------

CORPUS = sorted((FIXTURES / "rules").glob("hsl*.py"))


def _expected(path: pathlib.Path) -> set[tuple[int, str]]:
    out = set()
    for i, line in enumerate(path.read_text().splitlines(), 1):
        if "# expect:" in line:
            out.add((i, line.split("# expect:", 1)[1].strip()))
    return out


def _corpus_findings(path: pathlib.Path) -> set[tuple[int, str]]:
    """Run the full rule set (per-file lint + whole-program rules) over
    one corpus file, exactly as check.py composes them."""
    src = path.read_text()
    tree = ast.parse(src)
    findings = list(lint_source(src, str(path), tree=tree))
    name = _module_name(path)
    program = Program({name: _index_module(name, str(path), src, tree)})
    callgraph = CallGraph(program)
    findings += LockGraph(program, callgraph).inversions()
    findings += resource_findings(program)
    findings += config_key_findings(program, [])
    findings += fault_point_findings(program)
    effects = Effects(program, callgraph)
    findings += lockset_race_findings(program, effects)
    findings += atomicity_findings(program, effects)
    findings += jit_hygiene_findings(program)
    raises_obj = Raises(program, callgraph)
    contracts = declared_contracts(program)
    findings += error_contract_findings(program, raises_obj, contracts)
    findings += swallowed_findings(program, raises_obj)
    findings += unwind_findings(program, callgraph, raises_obj, contracts)[0]
    ddomains = DurabilityDomains(program, callgraph, raises_obj)
    # check.py's dedupe: a write site HSL027 claims reports once, under
    # the newer rule, never twice as HSL021+HSL027.
    findings += [
        f for f in ProcessDomains(program, callgraph, raises_obj).findings()
        if not (f.rule == "HSL021" and (f.path, f.line) in ddomains.claimed_sites)
    ]
    findings += TraceDomains(program, callgraph, raises_obj).findings()
    findings += ddomains.findings()
    return {(f.line, f.rule) for f in findings}


@pytest.mark.parametrize("path", CORPUS, ids=[p.stem for p in CORPUS])
def test_rule_corpus(path):
    """Each corpus file must produce exactly its `# expect:` annotations:
    flagged lines flag, clean lines stay clean, nothing extra fires."""
    assert _corpus_findings(path) == _expected(path)


def test_corpus_covers_every_rule():
    covered = {p.stem.upper() for p in CORPUS}
    declared = {r for r in RULES if r not in ("HSL000",)}
    assert covered == declared


# -- racedemo fixture package (effects + race rules) --------------------------

@pytest.fixture(scope="module")
def racedemo():
    program = Program.load([FIXTURES / "racedemo"])
    callgraph = CallGraph(program)
    return program, callgraph, Effects(program, callgraph)


class TestRacedemo:
    def test_effect_summaries_match_golden(self, racedemo):
        _, _, effects = racedemo
        golden = json.loads((FIXTURES / "goldens" / "racedemo_effects.json").read_text())
        assert json.loads(json.dumps(effects.to_json())) == golden

    def test_exactly_three_planted_findings(self, racedemo):
        program, _, effects = racedemo
        findings = (
            lockset_race_findings(program, effects)
            + atomicity_findings(program, effects)
            + jit_hygiene_findings(program)
        )
        assert sorted(f.rule for f in findings) == ["HSL013", "HSL014", "HSL015"]

    def test_hsl013_two_path_witness(self, racedemo):
        program, _, effects = racedemo
        (f,) = lockset_race_findings(program, effects)
        assert f.rule == "HSL013"
        # the witness names BOTH conflicting access paths with locksets
        assert "path 1" in f.message and "path 2" in f.message
        assert "racedemo.store.Store.put" in f.message
        assert "racedemo.store.Store.reset_unsafe" in f.message
        assert "holding racedemo.store.Store._lock" in f.message
        assert "holding no lock" in f.message
        assert "held at 5/6 accesses" in f.message

    def test_hsl014_names_both_critical_sections(self, racedemo):
        program, _, effects = racedemo
        (f,) = atomicity_findings(program, effects)
        assert f.rule == "HSL014"
        assert "bump_torn" in f.message
        assert "read under" in f.message and "re-acquired" in f.message

    def test_hsl015_flags_loop_lambda_only(self, racedemo):
        program, _, _ = racedemo
        (f,) = jit_hygiene_findings(program)
        assert f.rule == "HSL015"
        assert "fresh lambda" in f.message
        assert f.path.endswith("kernels.py")

    def test_guarded_state_stays_clean(self, racedemo):
        # _entries (consistently locked) and _FN_CACHE (memo under lock)
        # are tracked but not reported — the proof isn't vacuous.
        _, _, effects = racedemo
        assert "racedemo.store.Store._entries" in effects.by_state
        assert "racedemo.kernels._FN_CACHE" in effects.by_state

    def test_entry_lock_guarantee_credits_callers(self):
        # A helper only ever called under the lock is credited with it
        # (must-hold-on-entry fixpoint) — no false race on its accesses.
        src = (
            "import threading\n"
            "_lock = threading.Lock()\n"
            "_reg = {}\n"
            "def public(k, v):\n"
            "    with _lock:\n"
            "        _helper(k, v)\n"
            "def other(k):\n"
            "    with _lock:\n"
            "        _helper(k, None)\n"
            "def _helper(k, v):\n"
            "    _reg[k] = v\n"
            "def reader():\n"
            "    with _lock:\n"
            "        return dict(_reg)\n"
        )
        program = Program({"entrymod": _index_module("entrymod", "entrymod.py", src, ast.parse(src))})
        effects = Effects(program, CallGraph(program))
        assert effects.entry_locks["entrymod._helper"] == {"entrymod._lock"}
        assert lockset_race_findings(program, effects) == []


# -- raisedemo fixture package (raises + exception-flow rules) ----------------

@pytest.fixture(scope="module")
def raisedemo():
    program = Program.load([FIXTURES / "raisedemo"])
    callgraph = CallGraph(program)
    return program, callgraph, Raises(program, callgraph)


class TestRaisedemo:
    def test_raise_summaries_match_golden(self, raisedemo):
        _, _, raises_obj = raisedemo
        golden = json.loads((FIXTURES / "goldens" / "raisedemo_raises.json").read_text())
        assert json.loads(json.dumps(raises_obj.to_json())) == golden

    def test_exactly_three_planted_findings(self, raisedemo):
        program, callgraph, raises_obj = raisedemo
        contracts = declared_contracts(program)
        findings = (
            error_contract_findings(program, raises_obj, contracts)
            + swallowed_findings(program, raises_obj)
            + unwind_findings(program, callgraph, raises_obj, contracts)[0]
        )
        assert sorted(f.rule for f in findings) == ["HSL016", "HSL017", "HSL018"]

    def test_hsl016_witness_names_escape_and_contract(self, raisedemo):
        program, _, raises_obj = raisedemo
        (f,) = error_contract_findings(program, raises_obj)
        assert f.rule == "HSL016"
        assert "drifting_persist" in f.message
        assert "KeyError escapes" in f.message
        assert "PipelineError" in f.message  # the declared-but-narrower surface

    def test_hierarchy_narrowed_subtraction(self, raisedemo):
        # persist: EmptyStoreError (⊆ PipelineError) and the raise-from
        # transformation both stay inside the declared contract.
        _, _, raises_obj = raisedemo
        esc = raises_obj.escapes["raisedemo.api.persist"]
        assert sorted(esc) == ["EmptyStoreError", "PipelineError"]
        assert raises_obj.covers("PipelineError", "EmptyStoreError")
        assert not raises_obj.covers("EmptyStoreError", "PipelineError")

    def test_hsl017_flags_only_the_bare_swallow(self, raisedemo):
        program, _, raises_obj = raisedemo
        (f,) = swallowed_findings(program, raises_obj)
        assert f.rule == "HSL017"
        assert f.path.endswith("worker.py")
        assert "bare `except:`" in f.message

    def test_hsl018_proof_and_hole(self, raisedemo):
        program, callgraph, raises_obj = raisedemo
        contracts = declared_contracts(program)
        findings, proof = unwind_findings(program, callgraph, raises_obj, contracts)
        assert proof["demo.persist"]["covered"] is True
        (site,) = proof["demo.persist"]["sites"]
        assert site["chain"] == ["raisedemo.api.persist"]
        assert "declared error contract" in site["via"]
        assert proof["demo.orphan"]["covered"] is False
        (f,) = findings
        assert "demo.orphan" in f.message and "scrub" in f.message

    def test_fixture_points_extracted_from_ast(self, raisedemo):
        program, _, _ = raisedemo
        points, path = known_fault_points(program)
        assert points == {"demo.persist", "demo.orphan"}
        assert path.endswith("raisedemo/faults.py")


# -- procdemo fixture package (process domains + HSL019-022) ------------------

@pytest.fixture(scope="module")
def procdemo():
    program = Program.load([FIXTURES / "procdemo"])
    callgraph = CallGraph(program)
    raises_obj = Raises(program, callgraph)
    return program, callgraph, ProcessDomains(program, callgraph, raises_obj)


class TestProcdemo:
    def test_domain_graph_matches_golden(self, procdemo):
        _, _, domains = procdemo
        golden = json.loads((FIXTURES / "goldens" / "procdemo_domains.json").read_text())
        assert json.loads(json.dumps(domains.to_json())) == golden

    def test_exactly_four_planted_findings(self, procdemo):
        _, _, domains = procdemo
        rules = sorted(f.rule for f in domains.findings())
        assert rules == ["HSL019", "HSL020", "HSL021", "HSL022"]

    def test_hsl019_witness_names_entry_and_import_chain(self, procdemo):
        _, _, domains = procdemo
        (f,) = domains.spawn_import_findings()
        assert f.path.endswith("devkit.py")  # the module whose import is banned
        assert "procdemo.workers.shard_body" in f.message  # the seeding entry
        assert "procdemo.workers imports procdemo.devkit" in f.message
        # the witness chain carries BOTH files — --changed keeps the
        # finding when either side of the chain is what was edited
        assert any(p.endswith("workers.py") for p in f.witness_paths)
        assert any(p.endswith("devkit.py") for p in f.witness_paths)

    def test_hsl020_names_the_banned_type_and_site(self, procdemo):
        _, _, domains = procdemo
        (f,) = domains.exchange_typing_findings()
        assert "ColumnTable instance" in f.message
        assert "submit site" in f.message
        assert f.path.endswith("coord.py")

    def test_hsl020_path_list_submit_stays_clean(self, procdemo):
        # Same pool, same body, paths instead of a table: no finding at
        # the first submit line (the proof is not vacuous).
        _, _, domains = procdemo
        (f,) = domains.exchange_typing_findings()
        first_submit = min(
            s.line for s in domains.boundary_sites if s.kind == "submit"
        )
        assert f.line > first_submit

    def test_hsl021_flags_bare_write_not_atomic_publish(self, procdemo):
        _, _, domains = procdemo
        (f,) = domains.shared_file_findings()
        assert f.path.endswith("workers.py")
        assert "bad_manifest" in f.message
        # _publish_atomic (mkstemp + fsync + os.replace) stayed clean

    def test_hsl022_flags_carrier_without_install_state(self, procdemo):
        _, _, domains = procdemo
        (f,) = domains.continuity_findings()
        assert "bare_entry" in f.message
        assert "install_state" in f.message

    def test_service_body_deferred_engine_is_legal(self, procdemo):
        # worker_main boots devkit (jax) behind a deferred import: the
        # service module is in the domain, devkit is NOT pulled in
        # through it, and no finding lands on service.py.
        _, _, domains = procdemo
        assert "procdemo.service" in domains.domain_modules
        assert not any(
            f.path.endswith("service.py") for f in domains.findings()
        )

    def test_task_closure_and_boundary_inventory(self, procdemo):
        _, _, domains = procdemo
        assert "procdemo.workers._publish_atomic" in domains.task_fns
        chain = domains.task_fns["procdemo.workers._publish_atomic"]
        assert chain[0] == "procdemo.workers.shard_body"
        kinds = sorted(s.kind for s in domains.boundary_sites)
        assert kinds == ["put", "put", "return", "submit", "submit"]
        # both submits resolved their task-body target (declared ⇒ no
        # undeclared-target finding rode along)
        assert all(
            s.target == "procdemo.workers.shard_body"
            for s in domains.boundary_sites if s.kind == "submit"
        )


# -- jitdemo fixture package (trace domains + HSL023-026) ---------------------

@pytest.fixture(scope="module")
def jitdemo():
    program = Program.load([FIXTURES / "jitdemo"])
    callgraph = CallGraph(program)
    raises_obj = Raises(program, callgraph)
    return program, callgraph, TraceDomains(program, callgraph, raises_obj)


class TestJitdemo:
    def test_trace_graph_matches_golden(self, jitdemo):
        _, _, tdomains = jitdemo
        golden = json.loads((FIXTURES / "goldens" / "jitdemo_trace.json").read_text())
        assert json.loads(json.dumps(tdomains.to_json())) == golden

    def test_exactly_four_planted_findings(self, jitdemo):
        _, _, tdomains = jitdemo
        rules = sorted(f.rule for f in tdomains.findings())
        assert rules == ["HSL023", "HSL024", "HSL025", "HSL026"]

    def test_hsl023_witness_follows_the_closure(self, jitdemo):
        # The effect is two hops from the entry: leaky_norm -> _total.
        # HSL002 (lexical) cannot see it; the closure walk must, and
        # the finding must carry the chain.
        _, _, tdomains = jitdemo
        (f,) = [f for f in tdomains.findings() if f.rule == "HSL023"]
        assert f.path.endswith("traced.py")
        assert "stats counter increment" in f.message
        assert "jitdemo.traced.leaky_norm -> jitdemo.traced._total" in f.message
        assert any(p.endswith("traced.py") for p in f.witness_paths)

    def test_hsl023_engage_counterpart_stays_clean(self, jitdemo):
        # norm/engage hoists the same counter bump to the engagement
        # site — the proof is not vacuous.
        _, _, tdomains = jitdemo
        assert "jitdemo.traced.norm" in tdomains.trace_fns
        hits = [f for f in tdomains.findings() if f.rule == "HSL023"]
        assert len(hits) == 1
        assert all("jitdemo.traced.engage" not in f.message for f in hits)

    def test_hsl024_names_the_undeclared_static(self, jitdemo):
        # "order" is undeclared; "reps" (declared) stays clean.
        _, _, tdomains = jitdemo
        (f,) = [f for f in tdomains.findings() if f.rule == "HSL024"]
        assert "'order'" in f.message and "jitdemo.traced.poly" in f.message
        assert "reps" not in f.message

    def test_hsl025_mutation_names_the_gateway(self, jitdemo):
        # read_aliased mutates the staged view; read_owned (through
        # own_arrays) stays clean.
        _, _, tdomains = jitdemo
        (f,) = [f for f in tdomains.findings() if f.rule == "HSL025"]
        assert f.path.endswith("staging.py")
        assert "read_aliased" in f.message and "own_arrays" in f.message
        assert "read_owned" not in f.message

    def test_hsl026_flags_only_the_ladder_hole(self, jitdemo):
        # rowmax swallows its lowering errors; everything else on its
        # ladder (gate, both counters) is present, and tile_reduce's
        # complete ladder is proven.
        _, _, tdomains = jitdemo
        (f,) = [f for f in tdomains.findings() if f.rule == "HSL026"]
        assert "'jitdemo.rowmax'" in f.message
        assert "broad except in jitdemo.device.rowmax" in f.message
        assert "gate" not in f.message.split("missing", 1)[1]
        by_kernel = {lad["kernel"]: lad for lad in tdomains._kernel_ladders}
        assert by_kernel["jitdemo.tile_reduce"]["proven"] is True
        assert by_kernel["jitdemo.rowmax"]["proven"] is False
        assert by_kernel["jitdemo.tile_reduce"]["witness"] == [
            "jitdemo.device.tile_reduce", "jitdemo.device._make_tile_reduce",
        ]

    def test_entry_forms_and_kind_merge(self, jitdemo):
        # All entry shapes detected: bare @jit, partial(jit, ...),
        # call-form jit in factories, the shard_map body (which is also
        # the jit call-form target: kinds merge), and Pallas kernels.
        _, _, tdomains = jitdemo
        entries = json.loads(json.dumps(tdomains.to_json()))["entries"]
        assert entries["jitdemo.traced.make_exchange.<locals>.fn"]["kinds"] == [
            "jit", "shard_map",
        ]
        assert entries["jitdemo.traced.make_exchange.<locals>.fn"]["key"] == (
            "jitdemo.exchange"
        )
        kinds = {k for e in entries.values() for k in e["kinds"]}
        assert kinds == {"jit", "shard_map", "pallas_kernel"}

    def test_donation_proof_records_the_gateway_witness(self, jitdemo):
        _, _, tdomains = jitdemo
        proof = json.loads(json.dumps(tdomains.to_json()))["donation_proof"]
        assert proof["donation_sites"] == []
        # the planted mutation flips the proof off for the fixture
        assert proof["proven"] is False
        owned = [p for p in proof["staged_view_producers"]
                 if p["fn"].endswith("read_owned")]
        assert owned[0]["ownership_witness"] == ["jitdemo.staging.read_owned"]

    def test_static_domain_registry_extracted(self, jitdemo):
        program, _, _ = jitdemo
        assert declared_static_domains(program) == {"reps", "n"}


# -- durademo fixture package (durability domains + HSL027-030) ---------------

@pytest.fixture(scope="module")
def durademo():
    program = Program.load([FIXTURES / "durademo"])
    callgraph = CallGraph(program)
    raises_obj = Raises(program, callgraph)
    return program, callgraph, DurabilityDomains(program, callgraph, raises_obj)


class TestDurademo:
    def test_durability_graph_matches_golden(self, durademo):
        _, _, ddomains = durademo
        golden = json.loads((FIXTURES / "goldens" / "durademo_dura.json").read_text())
        assert json.loads(json.dumps(ddomains.to_json())) == golden

    def test_exactly_four_planted_findings(self, durademo):
        _, _, ddomains = durademo
        rules = sorted(f.rule for f in ddomains.findings())
        assert rules == ["HSL027", "HSL028", "HSL029", "HSL030"]

    def test_hsl027_names_root_and_idiom(self, durademo):
        _, _, ddomains = durademo
        (f,) = [f for f in ddomains.findings() if f.rule == "HSL027"]
        assert "'ledger'" in f.message
        assert "durademo.store.publish_fast" in f.message
        assert "fsync" in f.message
        assert f.witness_paths and f.witness_paths[0].endswith("store.py")
        # the proven direct counterpart and the delegated-clean site
        # stay quiet but are inventoried with their witness chains
        sites = {(s.fn, s.kind): s for s in ddomains.sites}
        delegated = sites[("durademo.store.save_ledger", "delegated")]
        assert delegated.ok
        assert delegated.chain == ("durademo.store.publish_json",)

    def test_hsl028_unproven_window_names_the_missing_point(self, durademo):
        _, _, ddomains = durademo
        (f,) = [f for f in ddomains.findings() if f.rule == "HSL028"]
        assert "'durademo.commit_before_stamp'" in f.message
        assert "no armed faults.fault_point('durademo.stamp')" in f.message
        proofs = ddomains._window_proofs
        assert proofs["durademo.batch_before_cursor"]["proven"] is True
        assert proofs["durademo.batch_before_cursor"]["point"]["line"] is not None
        assert proofs["durademo.commit_before_stamp"]["ordered"] is True
        assert proofs["durademo.commit_before_stamp"]["proven"] is False

    def test_hsl029_witness_follows_the_replay_chain(self, durademo):
        _, _, ddomains = durademo
        (f,) = [f for f in ddomains.findings() if f.rule == "HSL029"]
        assert "'time.time'" in f.message
        assert (
            "durademo.tailer.Tailer.poll -> durademo.tailer.Tailer._write_batch"
            in f.message
        )
        # the seq-named cursor write on the same replay path stays clean
        assert "_save_cursor" not in f.message

    def test_hsl030_closure_walk_finds_the_hidden_read(self, durademo):
        _, _, ddomains = durademo
        (f,) = [f for f in ddomains.findings() if f.rule == "HSL030"]
        assert "get_latest_id() live version read" in f.message
        assert "durademo.control.Planner.resolve" in f.message
        assert "durademo.control._live_floor" in f.message
        # both sanctioned shapes stay clean: the snapshot-dispatch split
        # and the default-fill idiom
        assert "plan_key" not in f.message and "decide" not in f.message

    def test_registries_extracted_and_claimed_sites_cover_every_site(self, durademo):
        program, _, ddomains = durademo
        assert set(ddomains.roots) == {"ledger", "batches", "cursor"}
        assert set(ddomains.windows) == {
            "durademo.batch_before_cursor", "durademo.commit_before_stamp",
        }
        assert set(ddomains.replay_roots) == {"durademo.tailer.Tailer.poll"}
        assert ddomains.known_points == {"durademo.tail", "durademo.stamp"}
        for s in ddomains.sites:
            mod = program.modules[program.functions[s.fn].module]
            assert (mod.path, s.line) in ddomains.claimed_sites


# -- repo-wide guarantees (what the CI gate asserts) --------------------------

class TestRepoWideGuarantees:
    def test_lock_graph_is_cycle_free(self, repo_program):
        """The acceptance proof: the full lock-acquisition graph —
        session RLock, metadata cache, device cache, serve scheduler
        condvar, plan/result caches, module memo locks — has no cycle."""
        program, callgraph = repo_program
        lockgraph = LockGraph(program, callgraph)
        assert lockgraph.inversions() == []
        # and it actually covers the locks the serving PR added:
        for lock_id in (
            "hyperspace_tpu.hyperspace.HyperspaceSession._state_lock",
            "hyperspace_tpu.metadata.cache.CreationTimeBasedCache._lock",
            "hyperspace_tpu.execution.device_cache.RefCache._lock",
            "hyperspace_tpu.serve.scheduler.QueryServer._cv",
            "hyperspace_tpu.serve.plan_cache.PlanCache._lock",
            "hyperspace_tpu.serve.result_cache.ResultCache._lock",
            "hyperspace_tpu.ops.filter._MASK_FN_LOCK",
            "hyperspace_tpu.utils.jit_memory._limit_lock",
        ):
            assert lock_id in program.locks, lock_id

    def test_lock_holders_reach_only_leaf_metric_locks(self, repo_program):
        # The shape of the healthy graph: every order edge terminates in
        # a metrics-registry leaf lock (which never calls out).
        program, callgraph = repo_program
        lockgraph = LockGraph(program, callgraph)
        inner = {b for (_, b) in lockgraph.order_edges()}
        outer = {a for (a, _) in lockgraph.order_edges()}
        assert not any(lock.startswith("hyperspace_tpu.obs.metrics") for lock in outer)
        assert inner  # the graph is not trivially empty

    def test_zero_config_key_drift(self, repo_program):
        program, _ = repo_program
        assert config_key_findings(program, [TESTS_DIR]) == []

    def test_zero_fault_point_drift(self, repo_program):
        program, _ = repo_program
        assert fault_point_findings(program) == []

    def test_zero_resource_findings(self, repo_program):
        program, _ = repo_program
        assert resource_findings(program) == []

    def test_repo_is_race_free_under_hsl013(self, repo_program):
        """The HSL013 analog of the HSL009 cycle-free proof: every
        shared state in serve/, the session, and the caches is accessed
        under a consistent lockset (docs/serving.md)."""
        program, callgraph = repo_program
        effects = Effects(program, callgraph)
        assert lockset_race_findings(program, effects) == []
        # and the proof is about the state that matters — the serving
        # plane's mutable attributes are all tracked:
        for state in (
            "hyperspace_tpu.serve.scheduler.QueryServer._inflight",
            "hyperspace_tpu.serve.scheduler.QueryServer._fifo",
            "hyperspace_tpu.serve.plan_cache.PlanCache._entries",
            "hyperspace_tpu.serve.result_cache.ResultCache._entries",
            "hyperspace_tpu.hyperspace.HyperspaceSession._last_profile",
            "hyperspace_tpu.hyperspace.HyperspaceSession.index_health",
            "hyperspace_tpu.metadata.cache.CreationTimeBasedCache._entry",
            "hyperspace_tpu.execution.device_cache.RefCache._entries",
            "hyperspace_tpu.ops.filter._MASK_FN_CACHE",
        ):
            assert state in effects.by_state, state

    def test_repo_has_no_atomicity_violations(self, repo_program):
        program, callgraph = repo_program
        effects = Effects(program, callgraph)
        assert atomicity_findings(program, effects) == []

    def test_repo_jit_sites_are_cache_hygienic(self, repo_program):
        """Every jit-of-local-fn site in ops/ is behind an lru_cache
        factory or an explicit memo — no per-call cache keys (the
        recompile-storm pattern behind the map-count segfault)."""
        program, _ = repo_program
        assert jit_hygiene_findings(program) == []

    def test_race_allowlist_is_narrow_and_justified(self, repo_program):
        program, callgraph = repo_program
        effects = Effects(program, callgraph)
        for state, why in RACE_ALLOWLIST.items():
            assert why, state
            # a stale entry silently widens the exemption surface
            assert state in effects.by_state, f"stale RACE_ALLOWLIST entry: {state}"

    def test_unresolved_call_accounting_and_bound(self, repo_program, repo_check):
        """The unresolved-call ratio is recorded in the report summary,
        and resolution quality can't silently degrade: the deliberately
        under-approximate resolver leaves stdlib/numpy/jax calls
        unresolved (~3/4 of all sites today), but a jump past the bound
        means a resolver regression is hiding lock/effect edges."""
        report, _ = repo_check
        s = report["summary"]
        assert s["calls_unresolved"] > 0
        assert 0.0 < s["calls_unresolved_ratio"] < 0.85
        program, callgraph = repo_program
        total = len(callgraph.edges) + len(callgraph.unresolved)
        assert s["calls_unresolved_ratio"] == round(len(callgraph.unresolved) / total, 4)

    def test_entry_lock_fixpoint_on_repo(self, repo_program):
        # io._evict_locked is only ever called with the IO cache lock
        # held — the fixpoint must prove it (this is what keeps its
        # unlocked-looking mutations out of HSL013).
        program, callgraph = repo_program
        effects = Effects(program, callgraph)
        assert (
            "hyperspace_tpu.execution.io._cache_lock"
            in effects.entry_locks["hyperspace_tpu.execution.io._evict_locked"]
        )

    def test_validator_corpus_passes(self):
        report = validator_corpus()
        assert report["status"] == "ok", report

    def test_run_check_clean(self, repo_check):
        report, _ = repo_check
        assert report["_findings"] == []
        assert report["summary"]["allowlisted"] == len(report["allowlisted"])
        assert report["summary"]["locks"] >= 20

    def test_seeded_typo_counter_is_caught(self, repo_program):
        # Sanity that the repo-wide zero isn't vacuous: a typo'd key in a
        # scratch module next to the real program is flagged with a
        # did-you-mean naming the declared key.
        src = 'def f(conf):\n    return conf.get("hyperspace.serve.workerz")\n'
        name, path = "scratch_mod", "scratch_mod.py"
        program = Program({name: _index_module(name, path, src, ast.parse(src))})
        findings = config_key_findings(program, [])
        assert [f.rule for f in findings] == ["HSL010"]
        assert "hyperspace.serve.workers" in findings[0].message

    def test_seeded_unthreaded_fault_point_is_caught(self, repo_program, monkeypatch):
        from hyperspace_tpu import faults as faults_mod

        program, _ = repo_program
        monkeypatch.setattr(
            faults_mod, "KNOWN_POINTS", (*faults_mod.KNOWN_POINTS, "ghost.point")
        )
        findings = fault_point_findings(program)
        assert [f.rule for f in findings] == ["HSL012"]
        assert "ghost.point" in findings[0].message
        assert "never threaded" in findings[0].message

    def test_allowlist_is_narrow_and_justified(self):
        for (suffix, rule), why in TEST_ALLOWLIST.items():
            assert not suffix.startswith("hyperspace_tpu/"), (
                "the allowlist is for test/benchmark surfaces only — "
                "package findings get fixed"
            )
            assert why


# -- exception-flow guarantees (HSL016-HSL018 on the real repo) ---------------

@pytest.fixture(scope="module")
def repo_raises(repo_program):
    program, callgraph = repo_program
    return Raises(program, callgraph)


class TestRepoExceptionFlow:
    def test_every_contract_holds(self, repo_program, repo_raises):
        """The acceptance proof: each public API's statically observed
        escape set ⊆ its declared ERROR_CONTRACTS entry."""
        program, _ = repo_program
        assert error_contract_findings(program, repo_raises) == []

    def test_contracts_cover_the_serving_surface(self, repo_program):
        program, _ = repo_program
        contracts = declared_contracts(program)
        for q in (
            "hyperspace_tpu.hyperspace.HyperspaceSession.run",
            "hyperspace_tpu.hyperspace.HyperspaceSession.run_query",
            "hyperspace_tpu.serve.scheduler.QueryServer.submit",
            "hyperspace_tpu.serve.scheduler.QueryHandle.result",
            "hyperspace_tpu.hyperspace.Hyperspace.recover",
            "hyperspace_tpu.actions.base.Action.run",
        ):
            assert q in contracts, q
            assert q in program.functions, q  # no dead entries

    def test_crash_point_escapes_the_query_path(self, repo_raises):
        """CrashPoint must REACH the public APIs: a simulated dying
        writer that got absorbed below session.run would mean some
        handler 'survived' a process death."""
        for q in (
            "hyperspace_tpu.hyperspace.HyperspaceSession.run",
            "hyperspace_tpu.actions.base.Action.run",
        ):
            esc = repo_raises.escapes[q]
            assert "CrashPoint" in esc, q
            # and the witness chain bottoms out in the fault harness
            assert esc["CrashPoint"].chain[-1] == "hyperspace_tpu.faults._hit"

    def test_hierarchy_grafts_local_types_onto_builtins(self, repo_raises):
        assert repo_raises.ancestors["FaultError"][:2] == ("FaultError", "OSError")
        assert "Exception" in repo_raises.ancestors["FaultError"]
        assert repo_raises.ancestors["CrashPoint"] == ("CrashPoint", "BaseException")
        assert "HyperspaceError" in repo_raises.ancestors["IndexCorruptionError"]

    def test_repo_has_no_swallowed_crashes(self, repo_program, repo_raises):
        program, _ = repo_program
        flagged = [
            f for f in swallowed_findings(program, repo_raises)
            if not f.path.endswith("benchmarks/bench_serve.py")  # allowlisted
        ]
        assert flagged == []

    def test_unwind_proof_covers_every_known_point(self, repo_program, repo_raises):
        """HSL018 acceptance: every fault point in faults.KNOWN_POINTS
        has a static propagation path to a recovery construct."""
        from hyperspace_tpu import faults as faults_mod

        program, callgraph = repo_program
        findings, proof = unwind_findings(program, callgraph, repo_raises)
        assert findings == []
        assert set(proof) == set(faults_mod.KNOWN_POINTS)
        for point, entry in proof.items():
            assert entry["covered"], point
            assert entry["sites"], point  # HSL012 guarantees this too
            for site in entry["sites"]:
                assert site["chain"][-1] == site["fn"]

    def test_recovery_roots_include_the_rollback_handler(self, repo_program):
        program, _ = repo_program
        roots = recovery_roots(program)
        assert "hyperspace_tpu.actions.base.Action.run" in roots
        assert any(v == "recover()" for v in roots.values())
        assert any(v == "declared error contract" for v in roots.values())
        # the rollback-handler detection stands on its own (no contracts)
        bare = recovery_roots(program, contracts={})
        assert bare.get("hyperspace_tpu.actions.base.Action.run") == "rollback handler"

    def test_dynamic_raises_table_is_narrow_and_fresh(self, repo_program):
        program, _ = repo_program
        for q, (types, why) in DYNAMIC_RAISES.items():
            assert q in program.functions, f"stale DYNAMIC_RAISES entry: {q}"
            assert types and why

    def test_result_contract_mirrors_worker_surface(self, repo_raises):
        # QueryHandle.result's declared surface comes from the
        # DYNAMIC_RAISES augmentation (raise self.error) + QueryTimeout.
        esc = repo_raises.escapes["hyperspace_tpu.serve.scheduler.QueryHandle.result"]
        assert {"QueryTimeout", "HyperspaceError", "OSError", "CrashPoint"} <= set(esc)

    def test_dead_symbol_report_shape(self, repo_check):
        report, _ = repo_check
        dead = report["dead_symbols"]
        assert dead["count"] == len(dead["functions"])
        assert report["summary"]["dead_symbols"] == dead["count"]
        # informational, under-approximate — but it must not claim the
        # whole program dead, and public entry points are never listed
        assert dead["count"] < report["summary"]["functions"] // 4
        assert not any(q.rsplit(".", 1)[-1] == "run_query" for q in dead["functions"])

    def test_check_wall_time_is_bounded(self, repo_check):
        """The engine's own cost is regression-gated: a full
        analysis.check pass (parse + lint + program + callgraph +
        effects + races + raises + rules + domains) stays under a
        minute."""
        report, elapsed = repo_check
        assert report["summary"]["files"] > 100
        assert elapsed < 60.0, f"analysis.check took {elapsed:.1f}s"


# -- process-domain guarantees (HSL019-022 on the real repo) ------------------

@pytest.fixture(scope="module")
def repo_domains(repo_program, repo_raises):
    program, callgraph = repo_program
    return ProcessDomains(program, callgraph, repo_raises)


@pytest.fixture(scope="module")
def repo_tdomains(repo_program, repo_raises):
    program, callgraph = repo_program
    return TraceDomains(program, callgraph, repo_raises)


@pytest.fixture(scope="module")
def repo_ddomains(repo_program, repo_raises):
    program, callgraph = repo_program
    return DurabilityDomains(program, callgraph, repo_raises)


class TestRepoProcessDomains:
    def test_spawn_domain_is_jax_pure_at_module_level(self, repo_domains):
        """The acceptance proof: every module a spawned worker imports
        at start — build_exchange, procpool, the fleet worker shim, the
        bench fleet mains, and their whole module-level import closure
        (package __init__s included) — is jax-free at module load. The
        runtime mirror (tests/test_procpool.py) asserts the same fact
        inside a real spawned interpreter."""
        assert repo_domains.spawn_import_findings() == []
        for m in (
            "hyperspace_tpu.execution.build_exchange",
            "hyperspace_tpu.parallel.procpool",
            "hyperspace_tpu.parallel",  # the package __init__ that leaked jax
            "hyperspace_tpu.serve.fleet.supervisor",
            "hyperspace_tpu.execution.io",
            "hyperspace_tpu.ops.sortkeys",
            "benchmarks.bench_serve",
        ):
            assert m in repo_domains.domain_modules, m

    def test_registry_entries_are_live_and_kinded(self, repo_domains):
        for q, (kind, why) in SPAWN_ENTRY_POINTS.items():
            assert kind in ("task", "task_body", "service", "service_body"), q
            assert why, q
        assert set(repo_domains.live_entries) == set(SPAWN_ENTRY_POINTS)

    def test_task_closure_covers_the_worker_bodies(self, repo_domains):
        # p2 reads spill through io.read_parquet and sorts through the
        # deferred sortkeys import — the closure must see both.
        fns = repo_domains.task_fns
        assert "hyperspace_tpu.execution.build_exchange.p2_owner" in fns
        assert "hyperspace_tpu.execution.io.read_parquet" in fns
        assert "hyperspace_tpu.execution.build_exchange.host_sort_perm" in fns
        # and it must NOT leak into the device build plane (the
        # write_table fallback misresolution this PR blocklisted).
        assert not any(q.startswith("hyperspace_tpu.ops.bucketize") for q in fns)
        assert not any(q.startswith("hyperspace_tpu.parallel.mesh") for q in fns)

    def test_every_spawn_target_is_declared(self, repo_domains):
        # Both directions of the registry contract (the HSL012 shape):
        # every statically detected spawn target resolves to a declared
        # entry; zero continuity findings on the tree.
        targets = {
            s.target for s in repo_domains.boundary_sites
            if s.kind in ("submit", "spawn", "fleet_target", "mp_process")
            and s.target is not None
        }
        assert "hyperspace_tpu.execution.build_exchange.p1_shard" in targets
        assert "hyperspace_tpu.execution.build_exchange.p2_owner" in targets
        assert "hyperspace_tpu.parallel.procpool._task_entry" in targets
        assert "hyperspace_tpu.serve.fleet.supervisor._worker_entry" in targets
        assert targets <= set(SPAWN_ENTRY_POINTS)
        assert repo_domains.continuity_findings() == []

    def test_exchange_surface_is_clean_and_sites_found(self, repo_domains):
        assert repo_domains.exchange_typing_findings() == []
        kinds = {s.kind for s in repo_domains.boundary_sites}
        # submit (builder), spawn (procpool/supervisor), fleet target
        # (bench), worker put (procpool), task-body returns (p1/p2).
        assert {"submit", "spawn", "fleet_target", "put", "return"} <= kinds

    def test_every_lease_acquire_has_a_reap_proof(self, repo_domains):
        assert repo_domains.shared_file_findings() == []
        acquires = repo_domains.lease_acquires
        assert acquires, "the lease O_EXCL sites must be inventoried"
        for a in acquires:
            assert a["reap_via"], a
        fns = {a["fn"] for a in acquires}
        assert "hyperspace_tpu.serve.fleet.lease.FileLease.try_acquire" in fns
        assert "hyperspace_tpu.utils.file_utils._locked_rename" in fns

    def test_worker_span_vocabulary_is_declared_and_fresh(self, repo_program, repo_domains):
        """KNOWN_WORKER_SPANS covers exactly what the task domain can
        emit — an undeclared name is a finding (checked above); a
        declared name nothing emits is a stale registry entry."""
        import ast as _ast

        from hyperspace_tpu.obs.trace import KNOWN_WORKER_SPANS

        program, _ = repo_program
        emitted = set()
        for q in repo_domains.task_fns:
            fn = program.functions.get(q)
            if fn is None:
                continue
            for node in _ast.walk(fn.node):
                if (
                    isinstance(node, _ast.Call) and node.args
                    and isinstance(node.args[0], _ast.Constant)
                    and isinstance(node.args[0].value, str)
                ):
                    attr = getattr(node.func, "attr", getattr(node.func, "id", ""))
                    if attr in ("span", "trace"):
                        emitted.add(node.args[0].value)
        assert emitted == set(KNOWN_WORKER_SPANS)

    def test_trace_domain_is_pure(self, repo_tdomains):
        """The acceptance proof for the device plane: the dispatch-
        augmented closure of every jit/shard_map/Pallas entry in the
        repo is host-effect-free, signature-bounded, donation-safe, and
        ladder-complete — zero HSL023-026 findings."""
        assert repo_tdomains.findings() == []

    def test_traced_helper_closure_found(self, repo_tdomains):
        # The fused device paths are in the domain with entry-rooted
        # witness chains — the closure is not vacuous.
        fns = repo_tdomains.trace_fns
        for q in (
            "hyperspace_tpu.ops.aggregate._segment_reduce_many",
            "hyperspace_tpu.ops.join._fused_join",
            "hyperspace_tpu.ops.join_agg._fused_join_agg_bounds",
            "hyperspace_tpu.ops.kmeans._lloyd",
            "hyperspace_tpu.plan.expr.evaluate",
        ):
            assert q in fns, q
        # expression evaluation enters through the filter kernels
        chain = fns["hyperspace_tpu.plan.expr.evaluate"]
        assert chain[0].startswith("hyperspace_tpu.ops.filter.")

    def test_every_pallas_ladder_is_proven(self, repo_tdomains):
        """Both Pallas kernels carry the complete eligibility ladder: an
        explicit rule, both device.kernel.* counters, and no broad except
        swallowing a lowering error, with the engagement chain from the
        public op down to the factory."""
        ladders = {lad["kernel"]: lad for lad in repo_tdomains._kernel_ladders}
        assert set(ladders) == {
            "ops.sortkeys.pallas_run_bounds",
            "ops.topk.pallas_tile",
        }
        for name, lad in ladders.items():
            assert lad["proven"], name
            assert lad["gate"] and lad["swallow"] is None, name
            assert set(lad["counters"]) == {
                "device.kernel.fused", "device.kernel.fallbacks",
            }, name
        assert ladders["ops.topk.pallas_tile"]["witness"] == [
            "hyperspace_tpu.ops.topk.topk",
            "hyperspace_tpu.ops.topk._pallas_topk",
            "hyperspace_tpu.ops.topk._make_tile_kernel",
        ]

    def test_known_kernels_registry_is_fresh(self, repo_tdomains):
        # Same both-directions contract as faults.KNOWN_POINTS: every
        # engagement declared, every declared entry live.
        assert repo_tdomains.known_kernels == {
            lad["kernel"] for lad in repo_tdomains._kernel_ladders
        }

    def test_donation_proof_is_gated_not_vacuous(self, repo_tdomains):
        """No donation anywhere today (that IS the HSL025 proof the
        ROADMAP's donated-buffer plans will build on), while the staging
        producer and the own_arrays gateway are both found."""
        proof = json.loads(json.dumps(repo_tdomains.to_json()))["donation_proof"]
        assert proof["donation_sites"] == []
        assert proof["proven"] is True
        (producer,) = proof["staged_view_producers"]
        assert producer["fn"] == (
            "hyperspace_tpu.execution.table.ColumnTable.from_arrow"
        )
        assert any(
            g["fn"] == "hyperspace_tpu.execution.io.read_parquet_cached"
            for g in proof["own_arrays_gateways"]
        )

    def test_trace_unresolved_accounting_and_bound(self, repo_tdomains, repo_check):
        """trace_domain.unresolved_ratio is recorded in the summary and
        bounded: traced bodies call mostly jax APIs the grounded
        resolver deliberately rejects (~0.85 today), but a jump past
        the bound means closure edges are silently vanishing."""
        report, _ = repo_check
        s = report["summary"]
        assert s["trace_entry_points"] >= 25
        assert s["trace_domain_functions"] >= 15
        assert s["trace_kernels_proven"] == 2
        assert 0.0 < s["trace_domain_unresolved_ratio"] < 0.9
        assert s["trace_domain_unresolved_ratio"] == repo_tdomains.unresolved_ratio()
        assert repo_tdomains.unresolved_ratio() == round(
            repo_tdomains.trace_calls_unresolved / repo_tdomains.trace_calls_total, 4
        )

    def test_static_domains_cover_the_device_plane(self, repo_program, repo_tdomains):
        from hyperspace_tpu.analysis.tracedomain import _lru_bound

        program, _ = repo_program
        declared = declared_static_domains(program)
        assert declared is not None and {"fns", "num_segments"} <= declared
        # every static argument outside a bounded lru factory (whose
        # memo key already bounds it) comes from the declared registry
        for e in repo_tdomains.entries:
            if e.kind == "pallas_kernel" or not e.static_names:
                continue
            host = program.functions[e.host]
            if _lru_bound(host.node) == "bounded":
                continue
            for n in e.static_names:
                assert n in declared, (e.traced, n)

    def test_durability_domain_is_pure(self, repo_ddomains):
        """The acceptance proof for the durable plane: every declared
        root publishes through the fsync-before-rename idiom, every
        torn window is ordered with an in-window fault point, every
        replay-path file name is deterministic, and no pinned-snapshot
        closure reads the live version vector — zero HSL027-030
        findings, with ANALYSIS_BASELINE.json still empty."""
        assert repo_ddomains.findings() == []

    def test_every_durable_root_carries_sites(self, repo_ddomains):
        """The inference is not vacuous: all 13 declared planes are
        found writing, and every site proves (or delegates to) the
        atomic idiom."""
        from hyperspace_tpu.analysis.duradomain import DURABLE_ROOTS

        assert set(repo_ddomains.roots) == set(DURABLE_ROOTS)
        by_root = {marker: [] for marker in repo_ddomains.roots}
        for s in repo_ddomains.sites:
            by_root[s.root].append(s)
        for marker, sites in by_root.items():
            assert sites, f"durable root {marker!r} has no write sites"
            for s in sites:
                assert s.ok, (marker, s.fn, s.line)
        # the two-phase anchors write through delegation chains into
        # file_utils — the witness machinery is exercised on the tree
        assert any(s.kind == "delegated" and s.chain for s in repo_ddomains.sites)

    def test_every_torn_window_is_proven(self, repo_ddomains):
        """All four exactly-once protocols: statically ordered writes
        AND a declared in-window fault point the crash sweeps kill at
        (tests/test_ingest.py, test_journal.py, test_controller.py
        parametrize over this registry by name)."""
        from hyperspace_tpu.analysis.duradomain import TORN_WINDOWS

        proofs = repo_ddomains._window_proofs
        assert set(proofs) == set(TORN_WINDOWS)
        for name, proof in proofs.items():
            assert proof["live"], name
            assert proof["ordered"], name
            assert proof["point"]["line"] is not None, name
            assert proof["proven"], name
            point = TORN_WINDOWS[name][3]
            assert point in repo_ddomains.known_points, name

    def test_replay_closure_covers_the_recovery_paths(self, repo_ddomains):
        from hyperspace_tpu.analysis.duradomain import REPLAY_ROOTS

        assert set(repo_ddomains.replay_roots) == set(REPLAY_ROOTS)
        for q in REPLAY_ROOTS:
            assert q in repo_ddomains.replay_fns, q
        # the CDC re-poll path actually reaches its batch writer
        assert (
            "hyperspace_tpu.ingest.tailer.CdcTailer._write_batch"
            in repo_ddomains.replay_fns
        )

    def test_durable_unresolved_accounting_and_bound(self, repo_ddomains, repo_check):
        """durable_domain.unresolved_ratio is recorded in the summary
        and bounded — a jump past the bound means delegation proofs and
        the replay closure are silently losing edges."""
        report, _ = repo_check
        s = report["summary"]
        assert s["durable_roots"] == len(repo_ddomains.roots)
        assert s["durable_write_sites"] == len(repo_ddomains.sites) > 0
        assert s["durable_domain_functions"] >= 100
        assert s["torn_windows"] == 4
        assert s["torn_windows_proven"] == 4
        assert s["replay_roots"] == 3
        assert s["replay_closure_functions"] > 100
        assert 0.0 < s["durable_domain_unresolved_ratio"] < 0.9
        assert s["durable_domain_unresolved_ratio"] == repo_ddomains.unresolved_ratio()
        assert repo_ddomains.unresolved_ratio() == round(
            repo_ddomains.dura_calls_unresolved / repo_ddomains.dura_calls_total, 4
        )
        # the report section the CI job reads lists every root, every
        # window with its in-window point witness, every replay path
        dura = report["durable_domains"]
        assert set(dura["roots"]) == set(repo_ddomains.roots)
        assert all(w["proven"] for w in dura["windows"].values())
        assert set(dura["replay"]) == set(repo_ddomains.replay_roots)

    def test_every_torn_window_has_a_crash_sweep_home(self):
        """The dynamic sweeps (test_ingest / test_journal /
        test_controller) parametrize over TORN_WINDOWS filtered by
        these prefixes and KeyError on an unknown name — so a window
        whose name starts with a NEW prefix would silently escape every
        sweep. This pin makes that a loud failure instead."""
        from hyperspace_tpu.analysis.duradomain import TORN_WINDOWS

        swept = ("ingest.", "journal.", "controller.")
        for name in TORN_WINDOWS:
            assert name.startswith(swept), (
                f"torn window {name!r} matches no crash-sweep prefix "
                f"{swept}; add a driver before registering it"
            )

    def test_module_level_imports_skip_deferred_and_type_checking(self):
        src = (
            "import os\n"
            "try:\n"
            "    import fast_json\n"
            "except ImportError:\n"
            "    import json as fast_json\n"
            "from typing import TYPE_CHECKING\n"
            "if TYPE_CHECKING:\n"
            "    import jax\n"
            "def f():\n"
            "    import jax.numpy as jnp\n"
            "    return jnp\n"
        )
        mod = _index_module("m", "m.py", src, ast.parse(src))
        targets = {t for t, _ in module_level_imports(mod)}
        assert "os" in targets and "fast_json" in targets and "json" in targets
        assert not any(t.startswith("jax") for t in targets)

    def test_declared_entry_points_extraction(self):
        src = (
            'SPAWN_ENTRY_POINTS = {\n'
            '    "m.body": ("task_body", "why"),\n'
            '    "m.shim": "service",\n'
            '}\n'
        )
        program = Program({"m": _index_module("m", "m.py", src, ast.parse(src))})
        got = declared_entry_points(program)
        assert got == {"m.body": ("task_body", "why"), "m.shim": ("service", "")}


# -- check CLI ----------------------------------------------------------------

def _validate_sarif_required(sarif: dict) -> None:
    """Assert the SARIF 2.1.0 REQUIRED-property set: sarifLog needs
    `version` + `runs`; each run needs `tool.driver.name`; each
    reportingDescriptor needs `id`; each result needs `message` (with
    text) and — per the artifactLocation/region constraints the spec
    puts on physicalLocation — a uri and a 1-based startLine. Every
    result.ruleId must resolve against the driver's rules."""
    assert sarif["version"] == "2.1.0"
    assert isinstance(sarif["runs"], list) and sarif["runs"]
    for run in sarif["runs"]:
        driver = run["tool"]["driver"]
        assert isinstance(driver["name"], str) and driver["name"]
        rule_ids = set()
        for rule in driver.get("rules", []):
            assert isinstance(rule["id"], str) and rule["id"]
            assert rule["shortDescription"]["text"]
            rule_ids.add(rule["id"])
        assert len(rule_ids) == len(driver.get("rules", []))  # ids unique
        assert isinstance(run["results"], list)
        for res in run["results"]:
            assert res["ruleId"] in rule_ids
            assert isinstance(res["message"]["text"], str) and res["message"]["text"]
            assert res.get("level") in ("none", "note", "warning", "error")
            assert res.get("baselineState", "new") in (
                "new", "unchanged", "updated", "absent",
            )
            for loc in res["locations"]:
                phys = loc["physicalLocation"]
                assert isinstance(phys["artifactLocation"]["uri"], str)
                assert phys["region"]["startLine"] >= 1


class TestCheckCli:
    def test_exit_clean_on_repo(self):
        proc = subprocess.run(
            [sys.executable, "-m", "hyperspace_tpu.analysis.check"],
            capture_output=True, text=True, cwd=REPO_ROOT,
        )
        assert proc.returncode == EXIT_CLEAN, proc.stdout + proc.stderr
        assert "cycle-free=True" in proc.stderr

    def test_exit_findings_without_baseline(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text("from jax import shard_map\n")
        assert check_main([str(bad), "--no-baseline"]) == EXIT_FINDINGS

    def test_exit_internal_error(self, monkeypatch):
        import hyperspace_tpu.analysis.check as check_mod

        monkeypatch.setattr(
            check_mod, "run_check",
            lambda *a, **k: (_ for _ in ()).throw(RuntimeError("boom")),
        )
        assert check_mod.main(["--no-baseline"]) == EXIT_INTERNAL_ERROR

    def test_baseline_masks_old_findings_only(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text("from jax import shard_map\n")
        baseline = tmp_path / "baseline.json"
        # 1. write the baseline: current findings become "known"
        assert check_main([str(bad), "--baseline", str(baseline),
                           "--write-baseline"]) == EXIT_CLEAN
        assert json.loads(baseline.read_text())["findings"]
        # 2. same findings, baseline present -> clean
        assert check_main([str(bad), "--baseline", str(baseline)]) == EXIT_CLEAN
        # 3. a NEW finding fails even with the baseline
        bad.write_text("from jax import shard_map\nimport numpy as np\nv = np.random.rand(3)\n")
        assert check_main([str(bad), "--baseline", str(baseline)]) == EXIT_FINDINGS

    def test_json_report_shape(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text("from jax import shard_map\n")
        out = tmp_path / "report.json"
        rc = check_main([str(bad), "--no-baseline", "--format", "json",
                         "--output", str(out)])
        assert rc == EXIT_FINDINGS
        report = json.loads(out.read_text())
        assert report["summary"]["new_findings"] == 1
        (finding,) = report["findings"]
        assert finding["rule"] == "HSL001"
        assert finding["slug"] == "fragile-jax-import"
        assert finding["new"] is True
        assert report["validator_corpus"]["status"] in ("ok", "skipped")
        assert "lock_graph" in report

    def test_docs_table_in_sync(self):
        # docs/configuration.md's key table is generated from
        # config.KNOWN_KEYS; this is the no-drift assertion.
        from hyperspace_tpu.analysis.check import docs_findings

        assert docs_findings(REPO_ROOT) == []

    def test_sarif_exit_codes_match_json(self, tmp_path):
        # the SARIF renderer changes the artifact, never the gate:
        # 0 = clean, 1 = new findings, 2 = internal error — same as json.
        clean = tmp_path / "clean.py"
        clean.write_text("x = 1\n")
        assert check_main([str(clean), "--no-baseline", "--format", "sarif"]) == EXIT_CLEAN
        bad = tmp_path / "bad.py"
        bad.write_text("from jax import shard_map\n")
        out = tmp_path / "report.sarif"
        rc = check_main([str(bad), "--no-baseline", "--format", "sarif",
                         "--output", str(out)])
        assert rc == EXIT_FINDINGS
        sarif = json.loads(out.read_text())
        assert sarif["version"] == "2.1.0"
        run = sarif["runs"][0]
        assert run["tool"]["driver"]["name"] == "hyperspace-analysis"
        rule_ids = {r["id"] for r in run["tool"]["driver"]["rules"]}
        assert {"HSL013", "HSL014", "HSL015"} <= rule_ids
        (result,) = run["results"]
        assert result["ruleId"] == "HSL001"
        assert result["baselineState"] == "new"
        assert result["locations"][0]["physicalLocation"]["region"]["startLine"] == 1

    def test_sarif_required_properties_across_all_rules(self, tmp_path):
        """Validate the SARIF 2.1.0 required-property set (runs/results/
        rules shape) over the full rule corpus — old and new rules alike
        — instead of spot-checking one finding."""
        out = tmp_path / "corpus.sarif"
        rc = check_main([str(FIXTURES / "rules"), "--no-baseline",
                         "--format", "sarif", "--output", str(out)])
        assert rc == EXIT_FINDINGS
        sarif = json.loads(out.read_text())
        _validate_sarif_required(sarif)
        fired = {r["ruleId"] for r in sarif["runs"][0]["results"]}
        # old rules, the exception-flow rules, the process-domain rules,
        # and the trace-domain rules all appear
        assert {"HSL001", "HSL011", "HSL013", "HSL016", "HSL017", "HSL018",
                "HSL019", "HSL020", "HSL021", "HSL022",
                "HSL023", "HSL024", "HSL025", "HSL026"} <= fired

    def test_sarif_required_properties_on_clean_run(self, tmp_path):
        clean = tmp_path / "clean.py"
        clean.write_text("x = 1\n")
        out = tmp_path / "clean.sarif"
        assert check_main([str(clean), "--no-baseline", "--format", "sarif",
                           "--output", str(out)]) == EXIT_CLEAN
        sarif = json.loads(out.read_text())
        _validate_sarif_required(sarif)
        assert sarif["runs"][0]["results"] == []

    def test_sarif_internal_error_exit(self, monkeypatch):
        import hyperspace_tpu.analysis.check as check_mod

        monkeypatch.setattr(
            check_mod, "run_check",
            lambda *a, **k: (_ for _ in ()).throw(RuntimeError("boom")),
        )
        assert check_mod.main(["--no-baseline", "--format", "sarif"]) == EXIT_INTERNAL_ERROR

    def test_sarif_baseline_state_unchanged(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text("from jax import shard_map\n")
        baseline = tmp_path / "baseline.json"
        assert check_main([str(bad), "--baseline", str(baseline),
                           "--write-baseline"]) == EXIT_CLEAN
        out = tmp_path / "report.sarif"
        rc = check_main([str(bad), "--baseline", str(baseline),
                         "--format", "sarif", "--output", str(out)])
        assert rc == EXIT_CLEAN  # known finding: gate passes...
        (result,) = json.loads(out.read_text())["runs"][0]["results"]
        assert result["baselineState"] == "unchanged"  # ...but SARIF keeps it

    def test_changed_mode_restricts_reporting(self, tmp_path, monkeypatch):
        import hyperspace_tpu.analysis.check as check_mod

        bad = tmp_path / "bad.py"
        bad.write_text("from jax import shard_map\n")
        other = tmp_path / "other.py"
        other.write_text("import numpy as np\nv = np.random.rand(3)\n")
        # only other.py "changed": bad.py's finding must be masked
        monkeypatch.setattr(
            check_mod, "changed_files", lambda root: ("origin/main", {"other.py"})
        )
        monkeypatch.setattr(check_mod, "_repo_root", lambda: tmp_path)
        out = tmp_path / "report.json"
        rc = check_mod.main([str(bad), str(other), "--no-baseline", "--changed",
                             "--format", "json", "--output", str(out)])
        assert rc == EXIT_FINDINGS
        report = json.loads(out.read_text())
        assert report["changed"] == {"base": "origin/main", "files": ["other.py"]}
        assert [f["rule"] for f in report["findings"]] == ["HSL005"]
        # nothing changed -> clean exit even with the bad file on disk
        monkeypatch.setattr(check_mod, "changed_files", lambda root: ("origin/main", set()))
        assert check_mod.main([str(bad), "--no-baseline", "--changed"]) == EXIT_CLEAN

    def test_changed_mode_keeps_findings_whose_witness_changed(self, tmp_path, monkeypatch):
        """The --changed blind-spot fix: a finding whose PRIMARY file is
        unchanged but whose witness chain crosses a changed file must
        still be reported — editing host.py (the spawn-domain module)
        is what creates the HSL019 finding reported at impure.py."""
        import hyperspace_tpu.analysis.check as check_mod

        host = tmp_path / "host.py"
        host.write_text(
            'SPAWN_ENTRY_POINTS = {"host.body": ("task_body", "x")}\n'
            "import impure\n"
            "def body():\n"
            "    return impure.K\n"
        )
        impure = tmp_path / "impure.py"
        impure.write_text("import jax\nK = 1\n")
        monkeypatch.setattr(check_mod, "_repo_root", lambda: tmp_path)
        # only host.py "changed": the HSL019 finding (primary: impure.py)
        # must survive through its witness chain
        monkeypatch.setattr(
            check_mod, "changed_files", lambda root: ("origin/main", {"host.py"})
        )
        out = tmp_path / "report.json"
        rc = check_mod.main([str(host), str(impure), "--no-baseline", "--changed",
                             "--format", "json", "--output", str(out)])
        assert rc == EXIT_FINDINGS
        report = json.loads(out.read_text())
        assert [f["rule"] for f in report["findings"]] == ["HSL019"]
        assert report["findings"][0]["path"].endswith("impure.py")
        # an unrelated change set still drops it
        monkeypatch.setattr(
            check_mod, "changed_files", lambda root: ("origin/main", {"elsewhere.py"})
        )
        assert check_mod.main([str(host), str(impure), "--no-baseline",
                               "--changed"]) == EXIT_CLEAN

    def test_changed_mode_falls_back_without_git(self, tmp_path, monkeypatch):
        import hyperspace_tpu.analysis.check as check_mod

        bad = tmp_path / "bad.py"
        bad.write_text("from jax import shard_map\n")
        monkeypatch.setattr(check_mod, "changed_files", lambda root: None)
        # git unavailable: full run, the finding still fails the gate
        assert check_mod.main([str(bad), "--no-baseline", "--changed"]) == EXIT_FINDINGS

    def test_changed_files_parses_git(self):
        # against the real repo: returns a base ref and a set of paths
        got = check_mod_changed_files(REPO_ROOT)
        if got is None:
            pytest.skip("git unavailable in this environment")
        base, files = got
        assert base in ("origin/main", "main", "HEAD")
        assert all(isinstance(p, str) for p in files)
