"""Policy CI decision corpus (ISSUE 19, docs/policy_ci.md).

Covers the acceptance list: the 100k-record dedup proof (frequency
weights preserved exactly through distillation), corpus container
round-trip + typed rejection of corruption/magic/version/schema skew,
coverage-guided row synthesis (unexercised columns get oracle-verified
synthetic witnesses; uncoverable columns get typed reason codes, incl.
the relation-closure-implied case), the 3-seed cross-lane differential
(synthesized rows encode + decide bit-identically on fused, gather and
matmul, matching the host oracle AND the row's own recorded verdict /
attribution), the engine ``--corpus-pregate`` rejecting a planted
constant-deny edit on a ZERO-captured-traffic config on synthetic-origin
evidence alone (with /debug/vars and flight-recorder trails), and
``corpus_diff`` naming the exact generation that introduced a flip
across a 4-generation published snapshot chain.

Deliberately import-light; JAX_PLATFORMS=cpu."""

from __future__ import annotations

import hashlib
import json
import os
import random
import struct

import jax.numpy as jnp
import numpy as np
import pytest

from authorino_tpu.analysis.fixtures import (
    fixture_configs,
    fixture_policy,
    relations_fixture_policy,
)
from authorino_tpu.compiler import ConfigRules, compile_corpus
from authorino_tpu.compiler.encode import encode_batch_py
from authorino_tpu.compiler.pack import pack_batch
from authorino_tpu.corpus import (
    CORPUS_SCHEMA,
    CorpusFormatError,
    distill_records,
    read_corpus,
    read_corpus_file,
    synthesize_rows,
    write_corpus,
)
from authorino_tpu.corpus.bisect import corpus_diff, load_generation_chain
from authorino_tpu.corpus.pregate import corpus_preflight, replay_corpus
from authorino_tpu.corpus.synthesize import augment_corpus, coverage_report
from authorino_tpu.corpus.store import MAGIC
from authorino_tpu.expressions import All, Any_, Operator, Pattern
from authorino_tpu.models.policy_model import host_results
from authorino_tpu.ops import pattern_eval as pe
from authorino_tpu.runtime import EngineEntry, PolicyEngine
from authorino_tpu.runtime.change_safety import GuardThresholds
from authorino_tpu.runtime.engine import SnapshotRejected
from authorino_tpu.snapshots.distribution import (
    SnapshotPublisher,
    serialize_policy,
)

# small-fixture thresholds: one flipped row must be judgeable
TH = GuardThresholds(min_requests=8, min_config_requests=1,
                     min_config_allows=1)


def api_doc(i=0):
    return {"request": {"method": "GET", "url_path": f"/api/v1/x{i}",
                        "host": "h", "headers": {"x-tag": "aa"}},
            "auth": {"identity": {"org": "acme", "roles": ["admin"],
                                  "groups": []}}}


def api_records(n, shapes=1):
    return [{"authconfig": "api", "doc": api_doc(i % shapes),
             "t": 1.0 + i * 1e-3} for i in range(n)]


def constant_deny_admin():
    """fixture_configs() with 'admin' evaluator 0 rewritten to the
    unsatisfiable All(org EQ acme, org NEQ acme) — the planted edit on a
    config no captured traffic ever hits."""
    org = Pattern("auth.identity.org", Operator.EQ, "acme")
    norg = Pattern("auth.identity.org", Operator.NEQ, "acme")
    cfgs = fixture_configs()
    for i, c in enumerate(cfgs):
        if c.name == "admin":
            cfgs[i] = ConfigRules(name="admin", evaluators=[
                (None, All(org, norg)), c.evaluators[1]])
    return cfgs


def entries_of(cfgs):
    return [EngineEntry(id=c.name, hosts=[c.name], runtime=None, rules=c)
            for c in cfgs]


# ---------------------------------------------------------------------------
# 1. distillation: the 100k dedup proof
# ---------------------------------------------------------------------------


def test_100k_records_distill_with_weights_preserved():
    policy = fixture_policy()
    n, shapes = 100_000, 8
    d = distill_records(api_records(n, shapes=shapes), policy)
    rows = d["rows"]
    assert len(rows) == shapes
    assert sum(r["weight"] for r in rows) == n
    assert d["dedup_ratio"] == n / shapes
    c = d["counters"]
    assert c["records_in"] == n
    assert c["distilled"] == shapes
    assert c["deduped"] == n - shapes
    assert c["dropped_unparseable"] == 0
    # canonical row keys, not content-hash fallbacks, and stable metadata
    assert c["fallback_keys"] == 0
    for r in rows:
        assert r["origin"] == "captured"
        assert r["row_key"] and not r["row_key"].startswith("doc:")
        assert r["first_seen"] <= r["last_seen"]
        # re-decided through the exact host oracle
        assert r["verdict"] == "allow" and r["rule_index"] == -1


def test_distill_accounts_unparseable_never_drops_silently():
    policy = fixture_policy()
    recs = api_records(4) + [{"authconfig": "api", "doc": None, "t": 9.0},
                             {"doc": api_doc(), "t": 9.0}]
    d = distill_records(recs, policy)
    assert d["counters"]["dropped_unparseable"] == 2
    assert sum(r["weight"] for r in d["rows"]) == 4


def test_distill_keeps_missing_config_rows_bisectable():
    """A row whose config the distilling snapshot no longer carries keeps
    its captured verdict (content-hash key) instead of vanishing — it
    must stay replayable against OLDER generations by --corpus-diff."""
    policy = fixture_policy()
    recs = [{"authconfig": "retired", "doc": api_doc(), "t": 1.0,
             "verdict": "deny", "rule_index": 0}]
    d = distill_records(recs, policy)
    (row,) = d["rows"]
    assert row["verdict"] == "deny"
    assert row["row_key"].startswith("doc:")
    assert d["counters"]["fallback_keys"] == 1


# ---------------------------------------------------------------------------
# 2. container: round-trip + typed rejection
# ---------------------------------------------------------------------------


def test_container_round_trip_bit_identical(tmp_path):
    policy = fixture_policy()
    rows = distill_records(api_records(16, shapes=4), policy)["rows"]
    p = str(tmp_path / "c.atpucorp")
    write_corpus(p, rows, meta={"note": "t"})
    header, back = read_corpus_file(p)
    assert back == rows
    assert header["count"] == 4 and header["meta"] == {"note": "t"}
    # directory read concatenates containers oldest-name-first
    write_corpus(str(tmp_path / "a.atpucorp"), rows[:1])
    assert read_corpus(str(tmp_path)) == rows[:1] + rows


@pytest.mark.parametrize("mutate", ["truncate", "magic", "flip", "version",
                                    "schema"])
def test_container_rejects_skew_typed(tmp_path, mutate):
    policy = fixture_policy()
    rows = distill_records(api_records(4), policy)["rows"]
    p = str(tmp_path / "c.atpucorp")
    write_corpus(p, rows)
    blob = open(p, "rb").read()
    if mutate == "truncate":
        blob = blob[:10]
    elif mutate == "magic":
        blob = b"NOTACORP1\x00" + blob[len(MAGIC):]
    elif mutate == "flip":
        b = bytearray(blob)
        b[len(b) // 2] ^= 0xFF
        blob = bytes(b)
    else:
        # rebuild with a skewed header and a VALID checksum: the typed
        # version/schema gate must fire, not the checksum one
        (hlen,) = struct.unpack_from("<Q", blob, len(MAGIC))
        start = len(MAGIC) + 8
        header = json.loads(blob[start:start + hlen])
        header["version" if mutate == "version" else "schema"] += 1
        hb = json.dumps(header, sort_keys=True,
                        separators=(",", ":")).encode()
        body = MAGIC + struct.pack("<Q", len(hb)) + hb \
            + blob[start + hlen:-32]
        blob = body + hashlib.sha256(body).digest()
    with open(p, "wb") as f:
        f.write(blob)
    with pytest.raises(CorpusFormatError):
        read_corpus_file(p)


# ---------------------------------------------------------------------------
# 3. coverage + synthesis
# ---------------------------------------------------------------------------


def test_synthesis_covers_unexercised_columns_verified_by_oracle():
    policy = fixture_policy()
    captured = distill_records(api_records(32), policy)["rows"]
    aug = augment_corpus(policy, captured)
    assert aug["coverage_after"]["fraction"] \
        > aug["coverage_before"]["fraction"]
    for row in aug["rows"]:
        assert row["schema"] == CORPUS_SCHEMA
        assert row["origin"] == "synthetic" and row["weight"] == 1
        # every synthetic row re-verifies through the exact host oracle:
        # the recorded verdict AND first-false attribution hold
        own, rule_res, skipped = host_results(
            policy, row["doc"], policy.config_ids[row["authconfig"]])
        assert (row["verdict"] == "allow") == bool(own)
        fire = int(pe.firing_columns(rule_res[None, :], skipped[None, :])[0])
        assert fire == row["rule_index"]
    # each config gets an allow witness (the row a constant-deny flips)
    allows = {r["authconfig"] for r in aug["rows"]
              if r["verdict"] == "allow"}
    assert {"admin", "public"} <= allows
    # deny witnesses for the never-fired admin columns
    fired = {(r["authconfig"], r["rule_index"]) for r in aug["rows"]
             if r["verdict"] == "deny"}
    assert ("admin", 0) in fired and ("admin", 1) in fired


def test_uncoverable_columns_get_typed_reasons_never_skipped():
    # 'public' is All() — a tautology can never be the first-false column
    policy = fixture_policy()
    _, report = synthesize_rows(policy)
    assert report["targets"] == report["synthesized"] \
        + len(report["uncoverable"])
    assert {"config": "public", "evaluator": 0,
            "reason": "unsatisfiable"} in report["uncoverable"]
    # the relation-closure-implied case: hier evaluator 1 wants
    # InGroup(staff) true with InGroup(all) false, but the closure makes
    # staff a subset of all — infeasible in a way the boolean atom model
    # cannot see, caught at oracle-verification time with its own reason
    rpolicy = relations_fixture_policy()
    _, rreport = synthesize_rows(rpolicy)
    reasons = {(u["config"], u["evaluator"]): u["reason"]
               for u in rreport["uncoverable"]}
    assert reasons.get(("hier", 1)) == "materialization-failed"


def test_coverage_report_marks_exercised_columns():
    policy = fixture_policy()
    rows, _ = synthesize_rows(policy, targets=[("api", 0)])
    cov = coverage_report(policy, rows)
    api = cov["configs"]["api"]
    assert api["columns"][0]["exercised"]
    assert api["unexercised"] == [1]
    assert cov["columns_exercised"] == 1


# ---------------------------------------------------------------------------
# 4. cross-lane validity: synthesized rows ride every lane bit-identically
# ---------------------------------------------------------------------------


def _rand_corpus(rng: random.Random, n_configs=5):
    """Seeded random corpus over the synthesizable atom classes: interned
    equality, membership, DFA-decidable regex, int-lane numerics."""
    orgs = ("acme", "beta", "gamma")
    roles = ("admin", "dev", "ops")
    rxs = (r"^/api/v[0-9]+/", r"^/public/", r"^/v2/[a-z]+$")
    cfgs = []
    for i in range(n_configs):
        evs = [
            (None, All(Pattern("auth.identity.org", Operator.EQ,
                               rng.choice(orgs)),
                       Pattern("auth.identity.roles", Operator.INCL,
                               rng.choice(roles)))),
            (None, Any_(Pattern("request.size", Operator.GE,
                                str(rng.choice((10, 1024)))),
                        Pattern("request.url_path", Operator.MATCHES,
                                rng.choice(rxs)))),
        ]
        if rng.random() < 0.5:
            evs.reverse()
        cfgs.append(ConfigRules(name=f"c{i}", evaluators=evs))
    return cfgs


@pytest.mark.parametrize("seed", [7, 19, 31])
def test_synthesized_rows_bit_identical_across_lanes_and_oracle(seed):
    rng = random.Random(seed)
    policy = compile_corpus(_rand_corpus(rng), members_k=4, ovf_assist=True)
    rows, report = synthesize_rows(policy)
    assert report["synthesized"] >= len(policy.config_ids)  # not vacuous
    docs = [r["doc"] for r in rows]
    gids = [policy.config_ids[r["authconfig"]] for r in rows]
    db = pack_batch(policy, encode_batch_py(policy, docs, gids))
    assert not db.host_fallback.any()
    has_dfa = policy.n_byte_attrs > 0
    args = (jnp.asarray(db.attrs_val), jnp.asarray(db.members_c),
            jnp.asarray(db.cpu_dense), jnp.asarray(db.config_id),
            jnp.asarray(db.attr_bytes) if has_dfa else None,
            jnp.asarray(db.byte_ovf) if has_dfa else None,
            *pe._extra_operands(db))
    packed_g, packed_m = (np.asarray(pe.eval_bitpacked_jit(
        pe.to_device(policy, lane=lane), *args)) for lane in ("gather", "matmul"))
    np.testing.assert_array_equal(packed_g, packed_m)
    E = int(policy.eval_rule.shape[1])
    verdict, firing = pe.unpack_attribution(packed_m, E)
    for i, row in enumerate(rows):
        # the kernel agrees with the row's RECORDED verdict/attribution
        # (which synthesis already verified against the host oracle) —
        # so corpus rows mean the same thing on every lane
        assert bool(verdict[i]) == (row["verdict"] == "allow"), (seed, i)
        assert int(firing[i]) == row["rule_index"], (seed, i)


# ---------------------------------------------------------------------------
# 5. the pregate: weighted replay + the zero-traffic catch
# ---------------------------------------------------------------------------


def test_replay_corpus_weights_flips_by_frequency():
    old = fixture_policy()
    new = compile_corpus(constant_deny_admin())
    rows = distill_records(api_records(16), old)["rows"]
    admin_doc = api_doc()
    admin_doc["request"]["host"] = "/api/v1/h"  # baseline-allow on admin
    rows += [{"schema": CORPUS_SCHEMA, "authconfig": "admin",
              "doc": admin_doc, "verdict": "allow", "rule_index": -1,
              "rule": "", "weight": 40_000, "first_seen": 1.0,
              "last_seen": 2.0, "origin": "captured", "row_key": "k",
              "generation": 1}]
    rep = replay_corpus(old, new, rows)
    # one flipped ROW counts with its full collapsed frequency
    assert rep["flips"]["newly_denied"] == 40_000
    assert rep["replayed"] == 40_016 and rep["replayed_rows"] == 2
    assert rep["per_config"]["admin"]["newly_denied"] == 40_000
    assert rep["origins"]["captured"]["flips"] == 40_000
    assert rep["load_model"] == "corpus"


def test_corpus_preflight_catches_zero_traffic_edit_on_synth_rows_only():
    baseline = fixture_policy()
    candidate = compile_corpus(constant_deny_admin())
    captured = distill_records(api_records(32), baseline)["rows"]
    # captured evidence alone is BLIND: no admin traffic ever happened
    blind = corpus_preflight(baseline, candidate, captured, TH,
                             changed={"admin"})
    assert blind["breach"] is None
    # + synthesized witnesses: caught, attributed, provably synthetic
    synth = augment_corpus(baseline, captured)["rows"]
    pf = corpus_preflight(baseline, candidate, captured + synth, TH,
                         changed={"admin"})
    breach = pf["breach"]
    assert breach is not None and "admin" in breach["suspects"]
    origins = pf["report"]["origins"]
    assert origins["captured"]["flips"] == 0
    assert origins["synthetic"]["flips"] >= 1
    # clean churn (fresh tree objects, same semantics) stays silent
    clean = corpus_preflight(baseline, compile_corpus(fixture_configs()),
                             captured + synth, TH, changed={"admin"})
    assert clean["breach"] is None


def test_engine_corpus_pregate_rejects_with_zero_live_exposure(tmp_path):
    corpus_path = str(tmp_path / "c.atpucorp")
    baseline = fixture_policy()
    write_corpus(corpus_path,
                 distill_records(api_records(32), baseline)["rows"])
    engine = PolicyEngine(mesh=None, max_batch=8, lane_select=False,
                          analyze_policies=False, metadata_prefetch=False,
                          canary_thresholds=TH,
                          corpus_pregate=corpus_path)
    engine.apply_snapshot(entries_of(fixture_configs()))
    gen_before = engine.generation
    with pytest.raises(SnapshotRejected) as ei:
        engine.apply_snapshot(entries_of(constant_deny_admin()))
    # the typed rejection carries the weighted corpus diff
    assert "admin" in ei.value.corpus_diff["suspects"]
    assert engine.generation == gen_before
    dv = engine.debug_vars()["corpus"]
    assert dv["enabled"] and dv["rows_captured"] >= 1
    assert dv["rows_synthetic"] >= 1
    assert dv["last"]["result"] == "breach"
    # the catch came from synthetic-origin evidence (zero live traffic)
    assert dv["last"]["origins"]["synthetic"]["flips"] >= 1
    assert dv["last"]["origins"]["captured"]["flips"] == 0
    # a clean re-apply of the original semantics still lands
    engine.apply_snapshot(entries_of(fixture_configs()))
    assert engine.generation > gen_before


def test_engine_corpus_pregate_missing_file_skips_never_blocks(tmp_path):
    engine = PolicyEngine(mesh=None, max_batch=8, lane_select=False,
                          analyze_policies=False, metadata_prefetch=False,
                          canary_thresholds=TH,
                          corpus_pregate=str(tmp_path / "absent.atpucorp"))
    engine.apply_snapshot(entries_of(fixture_configs()))
    engine.apply_snapshot(entries_of(constant_deny_admin()))  # must land
    dv = engine.debug_vars()["corpus"]
    assert dv["last"]["result"] == "skipped"
    assert dv["load_error"]


# ---------------------------------------------------------------------------
# 6. history bisect: --corpus-diff names the exact generation
# ---------------------------------------------------------------------------


def _publish_chain(directory, bad_from=3, n=4):
    pub = SnapshotPublisher(directory, keep=n + 2)
    for gen in range(1, n + 1):
        cfgs = constant_deny_admin() if gen >= bad_from \
            else fixture_configs()
        pub.publish_blob(
            serialize_policy(compile_corpus(cfgs),
                             meta={"generation": gen}), gen, {})


def test_corpus_diff_attributes_flip_to_exact_generation(tmp_path):
    _publish_chain(str(tmp_path), bad_from=3, n=4)
    chain = load_generation_chain(str(tmp_path))
    assert [s.generation for s in chain] == [1, 2, 3, 4]
    baseline = fixture_policy()
    captured = distill_records(api_records(32), baseline)["rows"]
    rows = captured + augment_corpus(baseline, captured)["rows"]
    report = corpus_diff(chain, rows)
    assert report["flipped_rows"] >= 1
    assert set(report["by_generation"]) == {"3"}
    flip = report["flips"][0]
    assert (flip["generation"], flip["from_generation"]) == (3, 2)
    assert flip["authconfig"] == "admin"
    assert flip["direction"] == "newly-denied"
    assert flip["origins"] == ["synthetic"]


def test_corpus_diff_clean_chain_reports_no_flips(tmp_path):
    _publish_chain(str(tmp_path), bad_from=99, n=4)
    baseline = fixture_policy()
    captured = distill_records(api_records(8), baseline)["rows"]
    rows = captured + augment_corpus(baseline, captured)["rows"]
    report = corpus_diff(load_generation_chain(str(tmp_path)), rows)
    assert report["flips"] == [] and report["flipped_rows"] == 0


# ---------------------------------------------------------------------------
# 7. the verify-fixtures wiring stays armed
# ---------------------------------------------------------------------------


def test_verify_fixtures_corpus_selftest_is_clean_and_not_blind():
    from authorino_tpu.analysis.__main__ import (
        _corpus_selftest,
        _pickle_lint_selftest,
    )
    from authorino_tpu.corpus import synthesize as syn

    policy = fixture_policy()
    assert _corpus_selftest(policy) == []
    assert _pickle_lint_selftest() == []
    # a BLIND synthesizer must fail the self-test (and with it tier-1)
    real = syn.augment_corpus

    def blind(policy, rows, **kw):
        out = real(policy, rows, **kw)
        out["rows"] = []
        out["coverage_after"] = out["coverage_before"]
        return out

    syn.augment_corpus = blind
    try:
        assert _corpus_selftest(policy)
    finally:
        syn.augment_corpus = real
