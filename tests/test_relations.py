"""Compiled relations (ISSUE 14): hierarchy tables, numeric/set kernels,
metadata prefetch.

Pins the tentpole contracts:

  - ancestor closure math (deep chains, diamonds, cycles, unknowns)
  - numeric comparator semantics (int32 bounds, bounded-arithmetic
    constants, invalid constants erroring like invalid regexes)
  - 3-seed property: relation-table + numeric + large-set verdicts AND
    attribution are bit-identical across the matmul kernel lane, the
    gather lane, the mesh lane (2x2), the host oracle, and verdict-cache
    hits — including >= 8-level hierarchies and diamond graphs
  - ovf_assist: membership-overflow rows stay on the device lane, exactly
  - serialize round-trip, certifier mutation classes, lowerability
    (blocking_reasons rollup, metadata-prefetch caveat), rego numeric
    fragment differential, capture metadata digest, replay substitution
  - the metadata prefetch cache: detection, pinning, staleness
    fall-through, and the pipeline serving a pinned document

Deliberately import-light (collects without `cryptography`)."""

from __future__ import annotations

import asyncio
import random
import time

import jax.numpy as jnp
import numpy as np
import pytest

from authorino_tpu.analysis.fixtures import (
    fixture_relation,
    relations_fixture_configs,
    relations_fixture_policy,
)
from authorino_tpu.analysis.tensor_lint import tensor_lint
from authorino_tpu.analysis.translation_validate import (
    certify_snapshot,
    classify_entry,
    lowerability_report,
    relations_mutation_self_test,
)
from authorino_tpu.compiler.compile import (
    OP_RELATION,
    ConfigRules,
    compile_corpus,
)
from authorino_tpu.compiler.encode import encode_batch_py
from authorino_tpu.compiler.pack import batch_row_keys, pack_batch
from authorino_tpu.expressions.ast import (
    All,
    Any_,
    InGroup,
    Operator,
    Pattern,
    PatternError,
    parse_int_const,
    parse_int_value,
)
from authorino_tpu.models.policy_model import PolicyModel, host_results
from authorino_tpu.ops import pattern_eval as pe
from authorino_tpu.relations.closure import RelationClosure
from authorino_tpu.relations.prefetch import (
    MetadataPrefetcher,
    doc_digest,
    is_prefetchable,
    mark_prefetchable,
)
from authorino_tpu.runtime import EngineEntry, PolicyEngine


def run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


# ---------------------------------------------------------------------------
# closure math
# ---------------------------------------------------------------------------


def test_closure_deep_chain_and_diamond():
    rel = fixture_relation()
    # 9-level chain: lvl0 reaches every ancestor transitively
    assert rel.contains("lvl0", "lvl9")
    assert rel.contains("lvl0", "all")
    assert rel.depth() >= 8
    # diamond: alice reaches staff through BOTH eng and ops, exactly once
    assert rel.contains("alice", "staff") and rel.contains("alice", "all")
    assert rel.groups_of("alice") >= {"eng", "ops", "staff", "all"}
    # no sideways leakage
    assert not rel.contains("alice", "qa")
    assert not rel.contains("eve", "staff")
    # unknown entities are in no groups; groups don't contain themselves
    assert rel.groups_of("nobody") == frozenset()
    assert not rel.contains("staff", "staff")


def test_closure_cycle_safe_and_digest_canonical():
    cyc = RelationClosure([("a", "b"), ("b", "c"), ("c", "a")])
    # a cycle's members converge on the cycle's union — and terminate
    assert cyc.groups_of("a") == {"a", "b", "c"}
    # digest is order/duplication independent
    r1 = RelationClosure([("x", "y"), ("y", "z")])
    r2 = RelationClosure([("y", "z"), ("x", "y"), ("x", "y")])
    assert r1.digest == r2.digest and r1 == r2


# ---------------------------------------------------------------------------
# numeric semantics
# ---------------------------------------------------------------------------


def test_numeric_parse_and_bounded_arith():
    assert parse_int_value("42") == 42
    assert parse_int_value("-7") == -7
    assert parse_int_value("4.2") is None
    assert parse_int_value("") is None
    # out-of-int32 values SATURATE (order-exact against the strictly-
    # interior constants, so the rego interpreter-equivalence proof holds
    # for arbitrarily large integers)
    assert parse_int_value(str(1 << 40)) == (1 << 31) - 1
    assert parse_int_value(str(-(1 << 40))) == -(1 << 31)
    assert parse_int_const("1024*1024") == 1 << 20
    assert parse_int_const(" 10 - 3 ") == 7
    with pytest.raises(ValueError):
        parse_int_const("1 << 4")
    with pytest.raises(ValueError):
        parse_int_const(str(1 << 31))  # int32 overflow
    with pytest.raises(ValueError):
        parse_int_const(str((1 << 31) - 1))  # endpoint excluded (open bound)


def test_numeric_pattern_invalid_const_denies_like_invalid_regex():
    bad = Pattern("a.b", Operator.GT, "not-a-number")
    with pytest.raises(PatternError):
        bad.matches({"a": {"b": 5}})
    # lowered: the whole tree rides the CPU oracle (error ⇒ deny)
    pol = compile_corpus([ConfigRules(name="c", evaluators=[(None, bad)])])
    own, _, _ = host_results(pol, {"a": {"b": 5}}, 0)
    assert own is False
    m = PolicyModel(pol)
    assert m.decide([{"a": {"b": 5}}], ["c"]) == [False]


def test_numeric_boundaries_all_ops():
    cfg = ConfigRules(name="n", evaluators=[
        (None, Pattern("v.x", Operator.GT, "10")),
        (None, Pattern("v.x", Operator.GE, "10")),
        (None, Pattern("v.x", Operator.LT, "20")),
        (None, Pattern("v.x", Operator.LE, "20")),
    ])
    m = PolicyModel.from_configs([cfg])
    for x in (9, 10, 11, 19, 20, 21, -(1 << 31), (1 << 31) - 1, 1 << 40,
              -(1 << 40), "zzz", None, 10.5):
        doc = {"v": {"x": x}}
        assert m.decide([doc], ["n"]) == \
            [host_results(m.policy, doc, 0)[0]], f"x={x!r}"
    # saturation is order-exact: a >2^31 value must still satisfy GT
    assert Pattern("v.x", Operator.GT, "10").matches({"v": {"x": 1 << 40}})
    assert not Pattern("v.x", Operator.LE, "10").matches(
        {"v": {"x": 1 << 40}})


# ---------------------------------------------------------------------------
# 3-seed cross-lane property: kernel (both lanes), mesh 2x2, host oracle,
# verdict-cache hits — verdicts AND attribution bit-identical
# ---------------------------------------------------------------------------


def _random_corpus(rng: random.Random, n_configs=6, members_k=4):
    # one deep + diamond hierarchy shared by several configs, one disjoint
    deep = [(f"d{i}", f"d{i+1}") for i in range(9)]
    rel_a = RelationClosure(
        deep + [("u1", "left"), ("u1", "right"), ("left", "mid"),
                ("right", "mid"), ("mid", "top"), ("d0", "top"),
                ("u2", "left")])
    rel_b = RelationClosure([("x", "y"), ("y", "z"), ("w", "z")])
    groups_a = ["mid", "top", "left", "d5", "d9"]
    cfgs = []
    for i in range(n_configs):
        leaves = [
            InGroup("auth.identity.sub", rng.choice(groups_a), rel_a),
            InGroup("auth.identity.team", "z", rel_b),
            Pattern("req.n", rng.choice(
                [Operator.GT, Operator.GE, Operator.LT, Operator.LE]),
                str(rng.randrange(-5, 30))),
            Pattern("auth.identity.roles", Operator.INCL, f"r{i % 3}"),
            Pattern("auth.identity.roles", Operator.EXCL, f"ban{i % 2}"),
            Pattern("req.m", Operator.EQ, rng.choice(["GET", "POST"])),
        ]
        rng.shuffle(leaves)
        rule = All(leaves[0], Any_(*leaves[1:4]))
        cond = Any_(leaves[4], leaves[5]) if rng.random() < 0.5 else None
        cfgs.append(ConfigRules(name=f"cfg-{i}",
                                evaluators=[(cond, rule), (None, leaves[1])]))
    ents = [e for e in rel_a.entities] + ["stranger"]
    docs = []
    for _ in range(64):
        docs.append({
            "req": {"n": rng.choice([-10, 0, 3, 7, 29, 30, "x", None]),
                    "m": rng.choice(["GET", "POST", "PUT"])},
            "auth": {"identity": {
                "sub": rng.choice(ents),
                "team": rng.choice(["x", "y", "w", "z", "q"]),
                "roles": [f"r{rng.randrange(4)}"
                          for _ in range(rng.choice([1, 2, members_k + 2]))],
            }},
        })
    names = [f"cfg-{rng.randrange(n_configs)}" for _ in docs]
    return cfgs, docs, names


def _kernel_full(policy, docs, rows, lane):
    params = pe.to_device(policy, lane=lane)
    enc = encode_batch_py(policy, docs, rows)
    db = pack_batch(policy, enc)
    has_dfa = pe.has_dfa(params)
    own, own_rule, own_skip = pe.eval_full_jit(
        params, jnp.asarray(db.attrs_val), jnp.asarray(db.members_c),
        jnp.asarray(db.cpu_dense), jnp.asarray(db.config_id),
        jnp.asarray(db.attr_bytes) if has_dfa else None,
        jnp.asarray(db.byte_ovf) if has_dfa else None,
        *pe._extra_operands(db))
    return (np.asarray(own), np.asarray(own_rule), np.asarray(own_skip),
            db.host_fallback)


@pytest.mark.parametrize("seed", [3, 17, 29])
def test_relation_lanes_bit_identical_property(seed):
    rng = random.Random(seed)
    cfgs, docs, names = _random_corpus(rng)
    policy = compile_corpus(cfgs, members_k=4, ovf_assist=True)
    assert not tensor_lint(policy)
    rows = [policy.config_ids[n] for n in names]
    want = [host_results(policy, d, r) for d, r in zip(docs, rows)]
    w_fire = pe.firing_columns(np.stack([w[1] for w in want]),
                               np.stack([w[2] for w in want]))
    for lane in ("matmul", "gather"):
        own, own_rule, own_skip, fb = _kernel_full(policy, docs, rows, lane)
        assert not fb.any()  # ovf_assist: no lossy rows
        n = len(docs)
        fire = pe.firing_columns(own_rule[:n], own_skip[:n])
        for i in range(n):
            assert bool(own[i]) == want[i][0], (lane, i)
            assert int(fire[i]) == int(w_fire[i]), (lane, i)
    # the compiled artifact certifies against the host oracle too
    _, fails, _ = certify_snapshot(policy, use_cache=False)
    assert not fails, fails[:3]


@pytest.mark.mesh
@pytest.mark.parametrize("seed", [5, 23, 41])
def test_relation_mesh_2x2_parity(seed, mesh_devices):
    from authorino_tpu.parallel import ShardedPolicyModel, build_mesh

    rng = random.Random(seed)
    cfgs, docs, names = _random_corpus(rng)
    mesh = build_mesh(n_devices=4, dp=2)  # 2x2
    sharded = ShardedPolicyModel(cfgs, mesh, members_k=4, ovf_assist=True)
    own_rule, own_skip = sharded.run_full(docs, names)
    n = len(docs)
    fire = pe.firing_columns(own_rule[:n], own_skip[:n])
    for i, (d, name) in enumerate(zip(docs, names)):
        shard, row = sharded.locator[name]
        w_own, w_rule, w_skip = host_results(sharded.shards[shard], d, row)
        w_fire = pe.firing_columns(w_rule[None, :], w_skip[None, :])[0]
        got_own = bool(np.all(own_skip[i] | own_rule[i]))
        assert got_own == w_own, i
        assert int(fire[i]) == int(w_fire), i


def test_relation_verdict_cache_hits_identical():
    """The same relation/numeric rows through a cache-enabled engine twice:
    the second (cache-hit) pass resolves bit-identically and actually
    hits."""
    rel = fixture_relation()
    rule = All(InGroup("auth.identity.sub", "staff", rel),
               Pattern("request.size", Operator.LE, "1024"))
    engine = PolicyEngine(members_k=4, mesh=None, max_batch=8,
                          lane_select=False, verdict_cache_size=1024,
                          metadata_prefetch=False)
    engine.apply_snapshot([EngineEntry(
        id="c", hosts=["c"], runtime=None,
        rules=ConfigRules(name="c", evaluators=[(None, rule)]))])
    policy = engine._snapshot.policy
    docs = [{"auth": {"identity": {"sub": s}},
             "request": {"size": z}}
            for s, z in (("alice", 10), ("eve", 10), ("alice", 4096),
                         ("lvl0", 0), ("nobody", 1))]

    async def burst():
        return await asyncio.gather(*(engine.submit(d, "c") for d in docs))

    first = run(burst())
    hits0 = engine._verdict_cache.hits
    second = run(burst())
    assert engine._verdict_cache.hits > hits0
    for (r1, s1), (r2, s2), d in zip(first, second, docs):
        np.testing.assert_array_equal(r1, r2)
        np.testing.assert_array_equal(s1, s2)
        w_own, w_rule, w_skip = host_results(policy, d, 0)
        np.testing.assert_array_equal(r1, w_rule)
        np.testing.assert_array_equal(s1, w_skip)


# ---------------------------------------------------------------------------
# ovf_assist
# ---------------------------------------------------------------------------


def test_ovf_assist_exact_and_no_fallback():
    cfgs = [ConfigRules(name="m", evaluators=[(None, All(
        Pattern("auth.identity.roles", Operator.INCL, "admin"),
        Pattern("auth.identity.groups", Operator.EXCL, "banned")))])]
    K = 4
    docs = [
        {"auth": {"identity": {"roles": [f"r{i}" for i in range(9)]
                               + ["admin"], "groups": ["x"]}}},
        {"auth": {"identity": {"roles": [f"r{i}" for i in range(9)],
                               "groups": ["x"]}}},
        {"auth": {"identity": {"roles": ["admin"],
                               "groups": ["banned"] * 9}}},
        {"auth": {"identity": {"roles": ["admin"], "groups": ["ok"] * 9}}},
        {"auth": {"identity": {"roles": ["admin"], "groups": ["x"]}}},
    ]
    rows = [0] * len(docs)
    assisted = compile_corpus(cfgs, members_k=K, ovf_assist=True)
    legacy = compile_corpus(cfgs, members_k=K, ovf_assist=False)
    db_l = pack_batch(legacy, encode_batch_py(legacy, docs, rows))
    assert db_l.host_fallback[:4].all() and not db_l.host_fallback[4]
    for lane in ("matmul", "gather"):
        own, _, _, fb = _kernel_full(assisted, docs, rows, lane)
        assert not fb.any()
        assert [bool(b) for b in own[:len(docs)]] == \
            [host_results(assisted, d, 0)[0] for d in docs]
    # overflow state rides the row keys: same visible prefix, different
    # overflow answers must never alias
    db = pack_batch(assisted, encode_batch_py(assisted, docs, rows))
    assert db.member_ovf is not None and db.member_ovf.any()
    assert len(set(batch_row_keys(db, len(docs)))) == len(docs)


def test_ovf_assist_drops_grid_overflow_reason():
    from types import SimpleNamespace

    cfgs = [ConfigRules(name="m", evaluators=[(None, Pattern(
        "auth.identity.roles", Operator.INCL, "admin"))])]
    entry = SimpleNamespace(id="m", rules=cfgs[0], runtime=None)
    lane_a, reasons_a = classify_entry(
        entry, policy=compile_corpus(cfgs, members_k=4, ovf_assist=True))
    lane_l, reasons_l = classify_entry(
        entry, policy=compile_corpus(cfgs, members_k=4, ovf_assist=False))
    assert lane_a == lane_l == "fast"
    assert "cpu-grid-overflow" in reasons_l
    assert "cpu-grid-overflow" not in reasons_a


# ---------------------------------------------------------------------------
# serialize + certifier + lowerability satellites
# ---------------------------------------------------------------------------


def test_relation_serialize_roundtrip_and_certify():
    from authorino_tpu.snapshots.serialize import (
        deserialize_policy,
        serialize_policy,
    )

    pol = relations_fixture_policy()
    loaded, _ = deserialize_policy(serialize_policy(pol))
    for name in ("rel_bits", "leaf_rel_slot", "leaf_rel_col",
                 "num_attr_slot", "leaf_op", "leaf_const"):
        np.testing.assert_array_equal(getattr(pol, name),
                                      getattr(loaded, name))
    assert loaded.ovf_assist and loaded.n_rel_slots == pol.n_rel_slots
    assert [c.digest for c in loaded.rel_instances] == \
        [c.digest for c in pol.rel_instances]
    _, fails, _ = certify_snapshot(loaded, use_cache=False)
    assert not fails, fails[:3]
    # old-format blobs (no new lanes) still carry version 1
    plain = compile_corpus([ConfigRules(name="p", evaluators=[
        (None, Pattern("a.b", Operator.EQ, "x"))])])
    import json as _json
    import struct

    blob = serialize_policy(plain)
    hlen = struct.unpack_from("<Q", blob, 10)[0]
    assert _json.loads(blob[18:18 + hlen])["version"] == 1
    blob2 = serialize_policy(pol)
    hlen2 = struct.unpack_from("<Q", blob2, 10)[0]
    assert _json.loads(blob2[18:18 + hlen2])["version"] == 2


def test_relations_mutation_self_test_green():
    """Tier-1 gate: every ISSUE 14 miscompile class (hierarchy-closure bit
    flips, column redirects, numeric const/op/slot corruption) must be
    rejected by the certifier — a blind validator fails here."""
    assert relations_mutation_self_test() == []


def test_planted_relation_bit_flip_is_rejected():
    from copy import deepcopy

    pol = relations_fixture_policy()
    mut = deepcopy(pol)
    leaf = next(i for i in range(mut.n_leaves)
                if int(mut.leaf_op[i]) == OP_RELATION)
    col = int(mut.leaf_rel_col[leaf])
    inst, _ = mut.rel_col_names[col]
    row = next(iter(mut.rel_entity_rows[inst].values()))
    mut.rel_bits = mut.rel_bits.copy()
    mut.rel_bits[row, col >> 3] ^= np.uint8(1 << (col & 7))
    _, fails, _ = certify_snapshot(mut, use_cache=False)
    assert any(f.kind == "relation-mismatch" for f in fails)


def test_shared_column_slot_corruption_rejected_per_leaf():
    """Two leaves sharing one (closure, group) column on DIFFERENT
    selectors: corrupting the SECOND leaf's slot binding must be caught
    even though the first leaf already audited (and memoized) the
    column's bits."""
    from copy import deepcopy

    rel = RelationClosure([("alice", "staff"), ("bob", "staff")])
    pol = compile_corpus([
        ConfigRules(name="a", evaluators=[
            (None, InGroup("auth.identity.sub", "staff", rel))]),
        ConfigRules(name="b", evaluators=[
            (None, All(InGroup("context.user", "staff", rel),
                       InGroup("auth.identity.sub", "staff", rel)))]),
    ])
    _, fails, _ = certify_snapshot(pol, use_cache=False)
    assert not fails
    # both selectors query the same column through different slots
    rel_leaves = [i for i in range(pol.n_leaves)
                  if int(pol.leaf_op[i]) == OP_RELATION]
    assert len(rel_leaves) == 2
    assert int(pol.leaf_rel_col[rel_leaves[0]]) == \
        int(pol.leaf_rel_col[rel_leaves[1]])
    assert int(pol.leaf_rel_slot[rel_leaves[0]]) != \
        int(pol.leaf_rel_slot[rel_leaves[1]])
    mut = deepcopy(pol)
    mut.leaf_rel_slot = mut.leaf_rel_slot.copy()
    # rebind the SECOND leaf to the first leaf's slot (wrong attribute)
    mut.leaf_rel_slot[rel_leaves[1]] = int(pol.leaf_rel_slot[rel_leaves[0]])
    _, fails, _ = certify_snapshot(mut, use_cache=False)
    assert any(f.kind == "relation-mismatch" and "slot" in f.message
               for f in fails), fails


def test_blocking_reasons_rollup():
    from types import SimpleNamespace

    entries = [
        SimpleNamespace(id="a", rules=None, runtime=None),  # no rules only
        SimpleNamespace(id="b", rules=None, runtime=SimpleNamespace(
            metadata=[SimpleNamespace(type="METADATA_GENERIC_HTTP")],
            authorization=[SimpleNamespace(
                type="OPA",
                evaluator=SimpleNamespace(kernel_slot=None))])),
        SimpleNamespace(id="c", rules=ConfigRules(
            name="c", evaluators=[(None, Pattern(
                "request.method", Operator.EQ, "GET"))]), runtime=None),
    ]
    rep = lowerability_report(
        entries, compile_corpus([entries[2].rules]))
    b = rep["blocking_reasons"]
    # config b carries TWO reasons: neither is a sole blocker
    assert b["metadata-dependency"] == {"configs": 1, "sole_blocker": 0}
    assert b["unsupported-comparator"] == {"configs": 1, "sole_blocker": 0}
    assert b["no-authorization-rules"]["sole_blocker"] == 1
    assert rep["fast"] == 1 and rep["slow"] == 2


# ---------------------------------------------------------------------------
# metadata prefetch
# ---------------------------------------------------------------------------


class _FakeGenericHttp:
    """GenericHttp-shaped duck (is_prefetchable is duck-typed by design so
    the analysis layer stays import-light — the real GenericHttp lives
    behind the cryptography-gated evaluators.metadata package).  call()
    counts live fetches so tests can prove the pin bypassed it."""

    def __init__(self, endpoint, body=None, parameters=(), headers=()):
        from authorino_tpu.authjson.value import JSONValue

        self.endpoint = (endpoint if not isinstance(endpoint, str)
                         else JSONValue(static=endpoint))
        self.body = body
        self.parameters = list(parameters)
        self.headers = list(headers)
        self.calls = 0

    async def call(self, pipeline):
        self.calls += 1
        return {"live": True}


def _static_md_conf(name="flags", conditions=None, cache=None,
                    endpoint="http://md.internal/flags"):
    from authorino_tpu.evaluators.base import MetadataConfig

    return MetadataConfig(name, _FakeGenericHttp(endpoint),
                          type="METADATA_GENERIC_HTTP",
                          conditions=conditions, cache=cache)


def test_prefetchable_detection():
    from authorino_tpu.authjson.value import JSONValue
    from authorino_tpu.evaluators.base import MetadataConfig

    assert is_prefetchable(_static_md_conf())
    # templated endpoint → request-dependent
    ev = _FakeGenericHttp(JSONValue(pattern="http://x/{request.path}"))
    assert not is_prefetchable(MetadataConfig(
        "t", ev, type="METADATA_GENERIC_HTTP"))
    # selector-valued header → request-dependent
    from types import SimpleNamespace

    ev2 = _FakeGenericHttp("http://x", headers=[SimpleNamespace(
        name="h", value=JSONValue(pattern="auth.identity.sub"))])
    assert not is_prefetchable(MetadataConfig(
        "t2", ev2, type="METADATA_GENERIC_HTTP"))
    # conditions gate → request-dependent
    assert not is_prefetchable(_static_md_conf(
        conditions=Pattern("request.method", Operator.EQ, "GET")))
    # non-GenericHttp types never prefetch
    assert not is_prefetchable(MetadataConfig(
        "u", object(), type="METADATA_USERINFO"))
    conf = _static_md_conf()
    assert mark_prefetchable(conf) and conf.prefetchable
    assert conf.prefetch_pinned is False


def test_prefetcher_pins_and_pipeline_serves_without_fetch():
    conf = _static_md_conf()
    mark_prefetchable(conf)
    entry = EngineEntry(id="ns/c", hosts=["c"], runtime=None, rules=None)
    entry.runtime = type("RT", (), {"metadata": [conf]})()
    fetches = []

    def fake_fetch(evaluator):
        fetches.append(evaluator)
        return {"tier": "gold"}

    pf = MetadataPrefetcher(max_age_s=60.0, refresh_s=3600.0,
                            fetcher=fake_fetch)
    try:
        assert pf.reconcile([entry]) == 1
        assert conf.prefetch_pinned is True
        pf.refresh()
        rec = pf.lookup(("ns/c", "flags"))
        assert rec is not None and rec.doc == {"tier": "gold"}
        assert rec.digest == doc_digest({"tier": "gold"})
        # the pipeline's metadata call serves the PIN — the evaluator's
        # live call (which would hit the network) never runs
        got = run(conf.call(object()))
        assert got == {"tier": "gold"}
        assert conf.evaluator.calls == 0
        assert pf.digest_for("ns/c") is not None
        assert pf.export_docs() == {"ns/c": {"flags": {"tier": "gold"}}}
    finally:
        pf.stop()


def test_prefetcher_transient_failure_keeps_healthy_pin():
    """A failed re-pin must NOT evict a still-fresh healthy pin: the
    previous document keeps serving (with its original fetched_at) until
    the staleness bound — the contract the error metric documents."""
    conf = _static_md_conf()
    mark_prefetchable(conf)
    entry = EngineEntry(id="ns/c", hosts=["c"], runtime=None, rules=None)
    entry.runtime = type("RT", (), {"metadata": [conf]})()
    state = {"fail": False}

    def flaky(ev):
        if state["fail"]:
            raise RuntimeError("metadata service down")
        return {"tier": "gold"}

    pf = MetadataPrefetcher(max_age_s=60.0, refresh_s=3600.0, fetcher=flaky)
    try:
        pf.reconcile([entry])
        pf.refresh()
        assert pf.lookup(("ns/c", "flags")).doc == {"tier": "gold"}
        state["fail"] = True
        pf.refresh()  # transient failure
        rec = pf.lookup(("ns/c", "flags"))
        assert rec is not None and rec.doc == {"tier": "gold"}
        assert pf.to_json()["counters"]["error"] >= 1
    finally:
        pf.stop()


def test_prefetcher_staleness_falls_through():
    conf = _static_md_conf()
    mark_prefetchable(conf)
    entry = EngineEntry(id="ns/c", hosts=["c"], runtime=None, rules=None)
    entry.runtime = type("RT", (), {"metadata": [conf]})()
    pf = MetadataPrefetcher(max_age_s=0.0, refresh_s=3600.0,
                            fetcher=lambda ev: {"x": 1})
    try:
        pf.reconcile([entry])
        pf.refresh()
        time.sleep(0.01)
        assert pf.lookup(("ns/c", "flags")) is None  # stale → fall-through
        assert pf.to_json()["counters"]["stale"] >= 1
    finally:
        pf.stop()


def test_classify_entry_metadata_prefetch_caveat():
    from types import SimpleNamespace

    rules = ConfigRules(name="c", evaluators=[
        (None, Pattern("request.method", Operator.EQ, "GET"))])
    pol = compile_corpus([rules])

    def entry(pinned):
        return SimpleNamespace(id="c", rules=rules, runtime=SimpleNamespace(
            metadata=[SimpleNamespace(type="METADATA_GENERIC_HTTP",
                                      prefetchable=pinned,
                                      prefetch_pinned=pinned)],
            authorization=[SimpleNamespace(type="PATTERN_MATCHING",
                                           evaluator=SimpleNamespace())]))

    lane, reasons = classify_entry(entry(False), policy=pol)
    assert lane == "slow" and "metadata-dependency" in reasons
    lane, reasons = classify_entry(entry(True), policy=pol)
    assert lane == "fast" and "metadata-prefetch" in reasons


def test_engine_reconcile_registers_prefetch_and_reports_fast():
    conf = _static_md_conf()
    mark_prefetchable(conf)
    rules = ConfigRules(name="ns/c", evaluators=[
        (None, Pattern("request.method", Operator.EQ, "GET"))])
    runtime = type("RT", (), {"metadata": [conf], "authorization": []})()
    engine = PolicyEngine(members_k=4, mesh=None, lane_select=False,
                          metadata_prefetch=True)
    engine.metadata_prefetcher._fetcher = lambda ev: {"ok": True}
    try:
        engine.apply_snapshot([EngineEntry(id="ns/c", hosts=["c"],
                                           runtime=runtime, rules=rules)])
        assert conf.prefetch_pinned is True
        rep = engine._lowerability
        assert rep["configs"]["ns/c"]["lane"] == "fast"
        assert "metadata-prefetch" in rep["configs"]["ns/c"]["reasons"]
        dv = engine.debug_vars()
        assert dv["metadata_prefetch"]["registered"] == 1
    finally:
        engine.metadata_prefetcher.stop()


# ---------------------------------------------------------------------------
# rego numeric fragment differential
# ---------------------------------------------------------------------------


def test_rego_numeric_fragment_differential():
    from authorino_tpu.evaluators.authorization import rego
    from authorino_tpu.evaluators.authorization.rego_lower import (
        lower_verdict,
    )

    src = ("default allow = false\n"
           "allow { input.request.size > 1024 }\n"
           "allow { input.source.port >= 8000; input.source.port <= 8080 }\n"
           "allow { 4096 > input.request.size; "
           'input.request.method == "GET" }\n'
           "allow { input.request.size == 0 }\n")
    mod = rego.compile_module(src, package="t")
    lowered = lower_verdict(mod)
    assert lowered is not None
    rng = random.Random(9)
    for _ in range(300):
        doc = {"request": {"size": rng.choice(
            [-1, 0, 1, 1024, 1025, 4095, 4096, 10_000_000]),
            "method": rng.choice(["GET", "POST"])}}
        if rng.random() < 0.5:
            doc["source"] = {"port": rng.choice([7999, 8000, 8080, 8081])}
        want = bool(mod.evaluate(doc).get("allow"))
        assert lowered.matches(doc) == want, doc


# ---------------------------------------------------------------------------
# translate: relations spec + ingroup operator
# ---------------------------------------------------------------------------


def test_translate_relations_spec_and_ingroup():
    # the translate layer imports the full evaluator tree (cryptography-
    # gated on this image, like every translate suite)
    pytest.importorskip("cryptography")
    from authorino_tpu.controllers.translate import translate_auth_config

    spec = {
        "hosts": ["svc.example.com"],
        "relations": {"org": {"edges": [
            ["alice", "eng"], ["eng", "staff"], ["staff", "all"]]}},
        "authentication": {"anon": {"anonymous": {}}},
        "authorization": {"hier": {"patternMatching": {"patterns": [
            {"selector": "auth.identity.sub", "operator": "ingroup",
             "value": "staff", "relation": "org"},
            {"selector": "request.size", "operator": "le",
             "value": "1024*1024"},
        ]}}},
    }
    entry = run(translate_auth_config("c", "ns", spec))
    assert entry.rules is not None
    (cond, rule), = entry.rules.evaluators
    assert rule.matches({"auth": {"identity": {"sub": "alice"}},
                         "request": {"size": 10}})
    assert not rule.matches({"auth": {"identity": {"sub": "eve"}},
                             "request": {"size": 10}})
    assert not rule.matches({"auth": {"identity": {"sub": "alice"}},
                             "request": {"size": 1 << 21}})
    # unknown relation name is a TranslationError
    from authorino_tpu.controllers.translate import TranslationError

    bad = dict(spec)
    bad["authorization"] = {"h": {"patternMatching": {"patterns": [
        {"selector": "s", "operator": "ingroup", "value": "g",
         "relation": "nope"}]}}}
    with pytest.raises(TranslationError):
        run(translate_auth_config("c", "ns", bad))


# ---------------------------------------------------------------------------
# capture digest + replay substitution
# ---------------------------------------------------------------------------


def test_capture_record_carries_metadata_digest():
    from authorino_tpu.replay.capture import CAPTURE_FIELDS, CaptureLog

    cap = CaptureLog(enabled=True, size_mb=1.0)
    cap.offer("ns/c", {"request": {"path": "/x"}}, -1, "engine", 3,
              metadata_doc_digest="abc123")
    cap.offer("ns/d", {"request": {"path": "/y"}}, 0, "engine", 3)
    cap.flush()
    recs = cap.ring_records()
    assert len(recs) == 2
    by_cfg = {r["authconfig"]: r for r in recs}
    assert by_cfg["ns/c"]["metadata_doc_digest"] == "abc123"
    assert by_cfg["ns/d"]["metadata_doc_digest"] is None
    for r in recs:
        assert tuple(sorted(r)) == tuple(sorted(CAPTURE_FIELDS))


def test_replay_metadata_substitution_unblinds():
    from authorino_tpu.replay.replay import replay_records

    rule = Pattern("auth.metadata.flags.tier", Operator.EQ, "gold")
    pol = compile_corpus([ConfigRules(name="c", evaluators=[(None, rule)])])
    captured_doc = {"request": {"method": "GET", "path": "/x"},
                    "auth": {"metadata": {"flags": {"tier": "bronze"}}}}
    records = [{"schema": 2, "authconfig": "c", "doc": captured_doc,
                "verdict": "deny", "rule_index": 0, "lane": "engine",
                "generation": 1, "metadata_doc_digest": "stale-digest"}]
    # blind replay: captured (bronze) document → denied on both sides
    blind = replay_records(pol, pol, records)
    assert blind["metadata"]["substituted"] == 0
    # pinned document says tier=gold → the what-if re-decides under it
    docs = {"c": {"flags": {"tier": "gold"}}}
    seen = replay_records(pol, pol, records, metadata_docs=docs)
    assert seen["metadata"]["substituted"] == 1
    assert seen["metadata"]["digest_mismatches"] == 1
    assert seen["per_config"]["c"]["new_allows"] == 1
    # the caller's record is untouched (shallow-copy substitution)
    assert captured_doc["auth"]["metadata"]["flags"]["tier"] == "bronze"


# ---------------------------------------------------------------------------
# epoch/fingerprint sensitivity
# ---------------------------------------------------------------------------


def test_edge_change_refingerprints_relation_configs():
    from authorino_tpu.snapshots.fingerprint import (
        encoding_epoch,
        rules_fingerprint,
    )

    rel1 = RelationClosure([("a", "g"), ("g", "top")])
    rel2 = RelationClosure([("a", "g"), ("g", "top"), ("b", "g")])

    def cfg(rel):
        return ConfigRules(name="c", evaluators=[
            (None, InGroup("auth.identity.sub", "top", rel))])

    assert rules_fingerprint(cfg(rel1)) != rules_fingerprint(cfg(rel2))
    assert rules_fingerprint(cfg(rel1)) == rules_fingerprint(cfg(rel1))
    p1 = compile_corpus([cfg(rel1)])
    p2 = compile_corpus([cfg(rel2)])
    p1b = compile_corpus([cfg(rel1)])
    assert encoding_epoch(p1) != encoding_epoch(p2)
    # same interner object → identical epoch for identical layout
    p1c = compile_corpus([cfg(rel1)], interner=p1.interner)
    assert encoding_epoch(p1) == encoding_epoch(p1c)
    assert p1b is not p1
