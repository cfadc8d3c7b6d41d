"""Translation validation (ISSUE 6, analysis/translation_validate.py).

Under test: circuit equivalence against the host expression oracle
(exhaustive + sampled tiers), regex↔DFA witness equivalence, the canonical
per-config fingerprint (stable across compile orders, sensitive to every
certified artifact), the process-wide certificate cache (re-reconciling an
unchanged corpus re-validates NOTHING; changing one config re-validates
exactly that config), the lowerability report's reason-code catalogue, the
mutation self-test (every planted miscompile class rejected — the tier-1
gate that the validator can never silently go blind), and the
--strict-verify wiring (a miscompiled snapshot is rejected at swap time
with the old snapshot still serving).

Deliberately import-light: collects on images without ``cryptography``."""

from __future__ import annotations

import json
from copy import deepcopy

import numpy as np
import pytest

from authorino_tpu.analysis.fixtures import (
    fixture_configs,
    fixture_policy,
    lowerability_fixture_entries,
)
from authorino_tpu.analysis.translation_validate import (
    _MUTANTS,
    SAMPLES_DEFAULT,
    certify_config,
    certify_snapshot,
    clear_certificate_cache,
    config_fingerprint,
    lowerability_report,
    mutation_self_test,
)
from authorino_tpu.compiler import ConfigRules, compile_corpus
from authorino_tpu.compiler.compile import FALSE_SLOT, TRUE_SLOT
from authorino_tpu.expressions import All, Any_, Operator, Pattern
from authorino_tpu.runtime import EngineEntry, PolicyEngine
from authorino_tpu.runtime.engine import SnapshotRejected


def _entries(configs):
    return [EngineEntry(id=c.name, hosts=[f"{c.name}.example.com"],
                        runtime=None, rules=c) for c in configs]


# ---------------------------------------------------------------------------
# clean corpora certify; certificates carry the right evidence
# ---------------------------------------------------------------------------


def test_fixture_corpus_certifies_clean():
    certs, failures, stats = certify_snapshot(fixture_policy(),
                                              use_cache=False)
    assert failures == []
    assert stats["validated"] == 3 and stats["failed"] == 0
    by_name = {c.config: c for c in certs}
    # every config got an exhaustive certificate with a fingerprint
    for c in certs:
        assert c.ok and c.mode == "exhaustive" and len(c.fingerprint) == 64
        assert c.n_assignments == 1 << c.n_atoms
    # the DFA-bearing configs cross-checked witnesses
    assert by_name["api"].dfa_rows >= 1 and by_name["api"].dfa_witnesses > 0
    # JSON-safe for /debug/vars and the CLI
    json.dumps([c.to_json() for c in certs])


def test_invalid_regex_tree_certifies():
    # whole-tree CPU-fallback leaves (invalid regex) are opaque atoms on
    # BOTH sides — including the error-ordering corner the oracle pins
    bad = Pattern("p", Operator.MATCHES, "([")
    ok = Pattern("m", Operator.EQ, "GET")
    shared = Any_(bad, ok)
    policy = compile_corpus([
        ConfigRules("t", evaluators=[(shared, Any_(ok)),
                                     (None, All(ok, bad))]),
        ConfigRules("s", evaluators=[(shared, shared)]),
    ])
    _, failures, stats = certify_snapshot(policy, use_cache=False)
    assert failures == [] and stats["validated"] == 2


def test_wide_config_uses_sampled_tier():
    pats = [Pattern(f"a.k{i}", Operator.EQ, f"v{i}") for i in range(18)]
    policy = compile_corpus([ConfigRules(name="w", evaluators=[
        (None, Any_(*pats))])])
    certs, failures, stats = certify_snapshot(policy, use_cache=False,
                                              seed=7)
    assert failures == [] and stats["sampled"] == 1
    (c,) = certs
    assert c.mode == "sampled" and c.seed == 7
    assert c.n_assignments == SAMPLES_DEFAULT + 2  # + all-true/all-false


def test_sampled_tier_catches_redirected_rule():
    pats = [Pattern(f"a.k{i}", Operator.EQ, f"v{i}") for i in range(18)]
    policy = compile_corpus([ConfigRules(name="w", evaluators=[
        (None, All(*pats))])])
    policy.eval_rule = policy.eval_rule.copy()
    policy.eval_rule[0, 0] = TRUE_SLOT
    _, failures, _ = certify_snapshot(policy, use_cache=False)
    assert any(f.kind == "translation-mismatch" for f in failures)


# ---------------------------------------------------------------------------
# each miscompile class is rejected with its intended kind
# ---------------------------------------------------------------------------


def test_own_row_dropped_rejected():
    """ISSUE 26: a DFA row missing from one config's own-row table is a
    corpus-global layout finding (the truth table cannot see it)."""
    _, failures, _ = certify_snapshot(_mutate("own-row-dropped"),
                                      use_cache=False)
    assert {f.kind for f in failures} == {"own-rows-layout"}
    assert failures[0].detail["config"] == 0


def test_own_leaf_rebound_rejected():
    """ISSUE 28: an own-table leaf that stands for another constant than its
    corpus leaf's is a corpus-global layout finding: the corpus arrays, all
    the truth tables read, are still right."""
    _, failures, _ = certify_snapshot(_mutate("own-leaf-rebound"),
                                      use_cache=False)
    assert {f.kind for f in failures} == {"own-rows-layout"}
    assert "const" in failures[0].message and failures[0].detail["config"] == 0


@pytest.mark.parametrize("mutant, what", [
    ("class-leaf-rebound", "const"), ("class-row-dropped", "DFA rows")])
def test_size_class_table_miscompiles_rejected(mutant, what):
    """ISSUE 34: a size class's table that stands for another thing than the
    corpus slot it maps back to is a layout finding of that class: the
    corpus arrays and the corpus-wide layout are still right."""
    _, failures, _ = certify_snapshot(_mutate(mutant), use_cache=False)
    assert {f.kind for f in failures} == {"own-rows-layout"}
    assert what in failures[0].message
    assert failures[0].location.startswith("classes[")


def _mutate(name):
    p = deepcopy(fixture_policy())
    dict(_MUTANTS)[name](p)
    return p


@pytest.mark.parametrize("mutant,kind", [
    ("circuit-child-flip", "translation-mismatch"),
    ("eval-rule-redirect", "translation-mismatch"),
    ("leaf-attr-swap", "translation-mismatch"),
    ("leaf-const-swap", "translation-mismatch"),
    ("dfa-transition-corrupt", "dfa-mismatch"),
    ("dfa-accept-flip", "dfa-mismatch"),
    ("dfa-pad-corrupt", "dfa-mismatch"),
])
def test_planted_miscompile_rejected(mutant, kind):
    _, failures, stats = certify_snapshot(_mutate(mutant), use_cache=False)
    assert failures, f"mutant {mutant} certified clean"
    assert kind in {f.kind for f in failures}
    assert stats["failed"] >= 1


def test_mutation_self_test_green():
    """The tier-1 gate (mirrors PR 4's test_repo_stays_lint_clean): every
    planted mutant class must be rejected and the clean fixture corpus
    must certify — a blind validator FAILS CI."""
    findings = mutation_self_test()
    assert findings == [], "\n".join(str(f) for f in findings)


def test_cli_verify_fixtures_runs_translation_validation(capsys):
    # --verify-fixtures now carries the certification + self-test, so the
    # CI entry point can never silently skip them
    from authorino_tpu.analysis.__main__ import main

    assert main(["--verify-fixtures"]) == 0
    assert "OK" in capsys.readouterr().out


def test_dfa_witnesses_cover_reject_side():
    """A transition redirected into a dead state makes the table REJECT
    strings the regex accepts — only witnesses derived from a fresh
    reference determinization can see that direction."""
    policy = compile_corpus([ConfigRules("c", evaluators=[
        (None, Pattern("p", Operator.MATCHES, r"^/api/v[0-9]+/"))])])
    policy.dfa_tables = policy.dfa_tables.copy()
    t = policy.dfa_tables[0]
    # kill the '/' transition out of the start state: everything the
    # pattern accepts is now unreachable in the audited table
    dead = int(t.max()) if int(t.max()) != int(t[0, ord("/")]) else 0
    t[0, ord("/")] = dead
    _, failures, _ = certify_snapshot(policy, use_cache=False)
    assert any(f.kind == "dfa-mismatch" for f in failures)


# ---------------------------------------------------------------------------
# fingerprints: canonical, order-independent, artifact-sensitive
# ---------------------------------------------------------------------------


def test_fingerprint_stable_across_compile_order():
    pa = compile_corpus(fixture_configs())
    pb = compile_corpus(list(reversed(fixture_configs())))
    fa = {n: config_fingerprint(pa, g) for n, g in pa.config_ids.items()}
    fb = {n: config_fingerprint(pb, g) for n, g in pb.config_ids.items()}
    assert fa == fb  # interner ids / buffer slots never leak into the fp


def test_fingerprint_changes_with_semantics_only():
    base = compile_corpus(fixture_configs())
    fp = {n: config_fingerprint(base, g)
          for n, g in base.config_ids.items()}
    changed = fixture_configs()
    changed[1] = ConfigRules(name="admin", evaluators=[
        (None, Pattern("auth.identity.org", Operator.EQ, "other-org"))])
    p2 = compile_corpus(changed)
    fp2 = {n: config_fingerprint(p2, g) for n, g in p2.config_ids.items()}
    assert fp2["admin"] != fp["admin"]
    assert fp2["api"] == fp["api"] and fp2["public"] == fp["public"]


def test_fingerprint_covers_dfa_artifacts():
    # a corrupted table must change the fingerprint, or the certificate
    # cache would mask the corruption on the next reconcile
    base = fixture_policy()
    row = base.config_ids["api"]
    fp = config_fingerprint(base, row)
    mut = deepcopy(base)
    mut.dfa_tables = mut.dfa_tables.copy()
    mut.dfa_tables[0, 0, ord("x")] ^= 1
    assert config_fingerprint(mut, row) != fp


# ---------------------------------------------------------------------------
# the certificate cache is provably incremental
# ---------------------------------------------------------------------------


def test_cache_skips_unchanged_and_revalidates_changed():
    clear_certificate_cache()
    _, _, s1 = certify_snapshot(compile_corpus(fixture_configs()))
    assert s1["validated"] == 3 and s1["cache_hits"] == 0
    # identical corpus, fresh compile: ZERO re-validations
    _, _, s2 = certify_snapshot(compile_corpus(fixture_configs()))
    assert s2["validated"] == 0 and s2["cache_hits"] == 3
    # change ONE config: exactly that config re-validates
    changed = fixture_configs()
    changed[2] = ConfigRules(name="public", evaluators=[
        (None, Pattern("request.method", Operator.EQ, "GET"))])
    certs, _, s3 = certify_snapshot(compile_corpus(changed))
    assert s3["validated"] == 1 and s3["cache_hits"] == 2
    assert next(c for c in certs if c.config == "public").cached is False
    clear_certificate_cache()


def test_cache_never_shields_a_mutant():
    # the mutant's fingerprint differs from the clean one (artifact bytes
    # are fingerprinted), so a warm cache cannot serve it a certificate
    clear_certificate_cache()
    certify_snapshot(fixture_policy())  # warm the cache with clean certs
    _, failures, stats = certify_snapshot(_mutate("dfa-transition-corrupt"))
    assert stats["failed"] >= 1 and failures
    clear_certificate_cache()


def test_cache_never_shields_padded_column_corruption():
    """Padded columns are corpus layout, not fingerprinted semantics — so
    their structural check must run UNCACHED: a corrupted padded column
    on an otherwise-unchanged config bypasses the certificate cache
    (review-found cache-masking hole, regression-pinned)."""
    clear_certificate_cache()
    certify_snapshot(fixture_policy())  # warm the cache with clean certs
    p = fixture_policy()
    row = p.config_ids["public"]
    p.eval_rule = p.eval_rule.copy()
    p.eval_rule[row, p.eval_rule.shape[1] - 1] = FALSE_SLOT
    _, failures, stats = certify_snapshot(p)  # cache ON — must still fail
    assert stats["failed"] >= 1
    assert any("padded evaluator" in f.message for f in failures)
    clear_certificate_cache()


def test_cache_never_serves_another_configs_certificate():
    """The fingerprint hashes the (source, compiled) PAIR: a miscompile
    whose wrong circuit is structurally identical to another validated
    config's circuit must NOT be served that config's cached certificate
    (review-found cache-aliasing hole, regression-pinned)."""
    clear_certificate_cache()
    cfgs = [ConfigRules("a", evaluators=[
                (None, Pattern("m", Operator.EQ, "GET"))]),
            ConfigRules("b", evaluators=[
                (None, Pattern("m", Operator.EQ, "POST"))])]
    p = compile_corpus(cfgs)
    ga, gb = p.config_ids["a"], p.config_ids["b"]
    # simulate a const-swap miscompile: b's rule slot now points at a's
    # (perfectly valid, already-certified) circuit
    p.eval_rule = p.eval_rule.copy()
    p.eval_rule[gb, 0] = p.eval_rule[ga, 0]
    assert config_fingerprint(p, ga) != config_fingerprint(p, gb)
    _, failures, stats = certify_snapshot(p)  # cache ON — must still fail
    assert stats["failed"] >= 1
    assert any(f.detail.get("config") == "b" for f in failures)
    clear_certificate_cache()


def test_shared_corrupt_table_attributed_to_each_config():
    """Two configs sharing one deduped (corrupt) DFA table must EACH report
    the failure under their own name — the memoized findings are copied,
    not mutated (review-found mis-attribution, regression-pinned)."""
    rx = Pattern("request.url_path", Operator.MATCHES, r"^/api/v[0-9]+/")
    policy = compile_corpus([
        ConfigRules("alpha", evaluators=[(None, rx)]),
        ConfigRules("beta", evaluators=[(None, rx)]),
    ])
    assert policy.dfa_tables.shape[0] >= 1
    policy.dfa_accept = policy.dfa_accept.copy()
    policy.dfa_accept[0, 0] = not bool(policy.dfa_accept[0, 0])
    _, failures, _ = certify_snapshot(policy, use_cache=False)
    named = {f.detail.get("config") for f in failures
             if f.kind == "dfa-mismatch"}
    assert {"alpha", "beta"} <= named


# ---------------------------------------------------------------------------
# --strict-verify: a miscompiled snapshot cannot swap in
# ---------------------------------------------------------------------------


def test_strict_verify_rejects_miscompiled_swap(monkeypatch):
    from authorino_tpu.runtime import engine as engine_mod

    clear_certificate_cache()
    eng = PolicyEngine(mesh=None, strict_verify=True, analyze_policies=False)
    # this test simulates a COMPILER bug by monkeypatching compile_corpus:
    # the incremental compile cache (ISSUE 8) would honestly skip the
    # recompile of an identical corpus, so force the monolithic path
    eng.compile_cache = None
    eng.apply_snapshot(_entries(fixture_configs()))
    g1, snap1 = eng.generation, eng._snapshot
    assert snap1.translation["validated"] == 3

    real = engine_mod.compile_corpus

    def miscompile(*a, **k):
        p = real(*a, **k)
        # structurally VALID (passes tensor lint) but semantically wrong:
        # only translation validation can catch it
        dict(_MUTANTS)["circuit-child-flip"](p)
        return p

    monkeypatch.setattr(engine_mod, "compile_corpus", miscompile)
    with pytest.raises(SnapshotRejected) as ei:
        eng.apply_snapshot(_entries(fixture_configs()))
    assert "translation-mismatch" in {f.kind for f in ei.value.findings}
    # old snapshot still serving, generation unbumped
    assert eng.generation == g1 and eng._snapshot is snap1
    assert eng.lookup("api.example.com") is not None

    # clean corpus swaps again — entirely from the certificate cache
    monkeypatch.setattr(engine_mod, "compile_corpus", real)
    eng.apply_snapshot(_entries(fixture_configs()))
    assert eng.generation == g1 + 1
    assert eng._snapshot.translation == {
        "validated": 0, "cache_hits": 3, "failed": 0, "sampled": 0,
        "dfa_witnesses": 0}
    clear_certificate_cache()


def test_engine_reconcile_is_incremental(monkeypatch):
    clear_certificate_cache()
    eng = PolicyEngine(mesh=None, strict_verify=True, analyze_policies=False)
    eng.apply_snapshot(_entries(fixture_configs()))
    assert eng.debug_vars()["translation_validation"]["validated"] == 3
    # re-reconcile the same corpus: zero re-validations (all cache hits)
    eng.apply_snapshot(_entries(fixture_configs()))
    tv = eng.debug_vars()["translation_validation"]
    assert tv["validated"] == 0 and tv["cache_hits"] == 3
    # change one config: exactly one re-validation
    changed = fixture_configs()
    changed[0] = ConfigRules(name="api", evaluators=[
        (None, Pattern("request.method", Operator.NEQ, "TRACE"))])
    eng.apply_snapshot(_entries(changed))
    tv = eng.debug_vars()["translation_validation"]
    assert tv["validated"] == 1 and tv["cache_hits"] == 2
    # and the metric counted the hits (noop-metrics images skip the read)
    try:
        from prometheus_client import REGISTRY

        v = REGISTRY.get_sample_value(
            "auth_server_translation_validate_total",
            {"result": "cache_hit"})
        assert v is not None and v >= 5
    except ImportError:
        pass
    clear_certificate_cache()


# ---------------------------------------------------------------------------
# lowerability report
# ---------------------------------------------------------------------------


def test_lowerability_reason_catalogue():
    entries = lowerability_fixture_entries()
    rules = [e.rules for e in entries if e.rules is not None]
    rep = lowerability_report(entries, compile_corpus(rules))
    assert rep["fast"] == 4 and rep["slow"] == 4
    cfg = rep["configs"]
    assert cfg["api"]["reasons"] == ["cpu-grid-overflow", "cpu-regex"]
    assert cfg["public"] == {"lane": "fast", "reasons": []}
    assert cfg["bad-regex"]["reasons"] == ["invalid-regex-fallback"]
    assert cfg["interpreter-only"] == {
        "lane": "slow", "reasons": ["no-authorization-rules"]}
    assert cfg["opa-unsupported"]["reasons"] == ["unsupported-comparator"]
    assert cfg["metadata-bound"]["reasons"] == ["metadata-dependency"]
    assert cfg["external-az"]["reasons"] == ["external-authorization"]
    # full aggregate counts survive even when the listing is bounded
    rep2 = lowerability_report(entries, compile_corpus(rules), max_listed=2)
    assert rep2["fast"] == 4 and rep2["slow"] == 4
    assert rep2["truncated"] is True and len(rep2["configs"]) == 2
    assert rep2["by_reason"] == rep["by_reason"]
    json.dumps(rep)  # /debug/vars + artifact contract


def test_lowerability_on_engine_debug_vars():
    eng = PolicyEngine(mesh=None)
    eng.apply_snapshot(_entries(fixture_configs()))
    low = eng.debug_vars()["lowerability"]
    assert low is not None and low["generation"] == 1
    assert low["fast"] == 3 and low["slow"] == 0
    assert ["fast", "", 1] in low["series"]


def test_lowerability_accepts_mesh_shard_list():
    """Mesh snapshots have no single corpus policy — the classifier reads
    each config's CPU-assist leaves from its owning shard (review-found
    sharded blind spot, regression-pinned)."""
    entries = lowerability_fixture_entries()
    rules = [e.rules for e in entries if e.rules is not None]
    # split the corpus in two like the sharded model's per-shard compiles
    shards = [compile_corpus(rules[:2]), compile_corpus(rules[2:])]
    rep = lowerability_report(entries, shards)
    assert rep["configs"]["api"]["reasons"] == ["cpu-grid-overflow",
                                                "cpu-regex"]
    assert rep["configs"]["bad-regex"]["reasons"] == [
        "invalid-regex-fallback"]
    # parity with the single-corpus classification
    assert rep["by_reason"] == lowerability_report(
        entries, compile_corpus(rules))["by_reason"]


def test_cli_coverage_report(capsys):
    from authorino_tpu.analysis.__main__ import main

    assert main(["--coverage-report", "--json"]) == 0
    report = json.loads(capsys.readouterr().out)
    cov = report["coverage"]
    # 4 fast + the 3 ISSUE 14 relations fixtures (hier/quota/roles — all
    # fast: the coverage corpus compiles with ovf_assist) vs 4 slow
    assert cov["fast"] == 7 and cov["slow"] == 4
    assert "unsupported-comparator" in cov["by_reason"]
    # the would-be-fast-if-fixed rollup rides the report (ISSUE 14)
    assert cov["blocking_reasons"]["unsupported-comparator"] == {
        "configs": 1, "sole_blocker": 1}
    assert {"hier", "quota", "roles"} <= set(cov["configs"])


# ---------------------------------------------------------------------------
# satellite: the wide-support analysis skip is no longer silent
# ---------------------------------------------------------------------------


def test_policy_analysis_skip_is_surfaced():
    from authorino_tpu.analysis.policy_analysis import MAX_ATOMS, analyze_policy

    pats = [Pattern(f"a.k{i}", Operator.EQ, f"v{i}")
            for i in range(MAX_ATOMS + 2)]
    policy = compile_corpus([
        ConfigRules(name="wide", evaluators=[(None, Any_(*pats))]),
        ConfigRules(name="narrow", evaluators=[(None, pats[0])]),
    ])
    _, summary = analyze_policy(policy)
    assert summary["skipped_wide"] == 1
    assert summary["skipped"] == [
        {"config": "wide", "evaluator": 0, "atoms": MAX_ATOMS + 2}]


def test_engine_surfaces_skipped_configs(monkeypatch):
    from authorino_tpu.analysis.policy_analysis import MAX_ATOMS

    pats = [Pattern(f"a.k{i}", Operator.EQ, f"v{i}")
            for i in range(MAX_ATOMS + 2)]
    wide = ConfigRules(name="ns/wide", evaluators=[(None, Any_(*pats))])
    eng = PolicyEngine(mesh=None)
    eng.apply_snapshot(_entries([wide]))
    summary = eng.debug_vars()["policy_analysis"]["summary"]
    assert summary["skipped_wide"] == 1
    assert summary["skipped"][0]["config"] == "ns/wide"
    try:
        from prometheus_client import REGISTRY

        v = REGISTRY.get_sample_value(
            "auth_server_policy_analysis_skipped_total",
            {"authconfig": "ns/wide"})
        assert v is not None and v >= 1
    except ImportError:
        pass


# ---------------------------------------------------------------------------
# certify_config unit corners
# ---------------------------------------------------------------------------


def test_padded_evaluator_columns_must_be_vacuous():
    policy = fixture_policy()
    row = policy.config_ids["public"]  # one real evaluator, padded to E
    policy.eval_rule = policy.eval_rule.copy()
    policy.eval_rule[row, policy.eval_rule.shape[1] - 1] = FALSE_SLOT
    _, failures = certify_config(policy, row)
    assert any("padded evaluator" in f.message for f in failures)


def test_empty_config_certifies():
    policy = compile_corpus([ConfigRules("empty", evaluators=[])])
    cert, failures = certify_config(policy, 0)
    assert failures == [] and cert.ok and cert.n_atoms == 0


def test_certify_unlinted_table_index_corruption_degrades_to_finding():
    """certify's public API must not assume the tensor lint ran first: an
    out-of-range dfa_table_of_row entry yields a dfa-mismatch finding,
    never an IndexError (review-found edge, regression-pinned)."""
    p = deepcopy(fixture_policy())
    p.dfa_table_of_row = p.dfa_table_of_row.copy()
    p.dfa_table_of_row[0] = p.dfa_tables.shape[0] + 7
    _, failures, stats = certify_snapshot(p, use_cache=False)
    assert stats["failed"] >= 1
    assert any(f.kind == "dfa-mismatch" and "table axis" in f.message
               for f in failures)


def test_mutation_self_test_on_structureless_corpus_reports_not_crashes():
    """A corpus without And/Or nodes or DFA tables cannot host several
    planters — the self-test must report them as unplantable findings,
    not crash (review-found edge, regression-pinned)."""
    policy = compile_corpus([ConfigRules("leafy", evaluators=[
        (None, Pattern("m", Operator.EQ, "GET"))])])
    findings = mutation_self_test(policy)
    assert findings  # planters for circuits/DFA tables cannot plant here
    assert all(f.kind == "validator-blind" and "could not be planted"
               in f.message for f in findings)
