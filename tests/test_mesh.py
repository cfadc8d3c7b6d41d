"""Multi-chip mesh as the headline lane (ISSUE 11).

Everything here runs on the 8-device virtual CPU mesh that tests/conftest.py
forces via XLA_FLAGS=--xla_force_host_platform_device_count=8 *before* jax
imports (the ``mesh_devices`` fixture asserts the forcing took) — no TPU
needed for tier-1 mesh coverage.

Covers the ISSUE 11 acceptance criteria:
  - bit-exact verdict + attribution parity, mesh vs single-corpus vs host
    oracle, across dp×mp shapes {1×1, 2×1, 2×2, 4×2}, including
    membership-overflow and CPU-fallback rows;
  - verdict-cache keying parity with PR 8: (encoding_epoch,
    rules_fingerprint) tokens, ≥95% survival across a 1-of-N mutation swap;
  - strict-verify lints the packed shards BEFORE the device upload;
  - injected one-device-down resolves batches on healthy devices via
    per-device breaker failover — zero host-degrade decisions until ALL
    devices are down;
  - a one-config mutation ships delta bytes only to the owning shard;
  - grid relief: a corpus that trips cpu-grid-overflow on one device serves
    from the fast lane when rule-sharded, and the lowerability report's
    reason-code count drops.
"""

from __future__ import annotations

import asyncio
import random

import numpy as np
import pytest

from authorino_tpu.compiler import ConfigRules
from authorino_tpu.expressions import All, Any_, Operator, Pattern
from authorino_tpu.models.policy_model import host_results
from authorino_tpu.ops.pattern_eval import firing_columns, unpack_attribution
from authorino_tpu.parallel import ShardedPolicyModel, build_mesh
from authorino_tpu.parallel.sharded_eval import (
    MeshUnavailable,
    _reset_mesh_state_for_tests,
)
from authorino_tpu.runtime import EngineEntry, PolicyEngine
from authorino_tpu.runtime.faults import FAULTS

from test_compiler_differential import oracle_verdict, random_doc, random_expr
from test_own_config_eval import _operands, all_operand_corpus, all_operand_docs

pytestmark = pytest.mark.mesh

# dp × mp shapes the acceptance sweep pins (all fit the 8 virtual devices)
SHAPES = [(1, 1), (2, 1), (2, 2), (4, 2)]


def counter_value(name: str, labels=None) -> float:
    from prometheus_client import REGISTRY

    v = REGISTRY.get_sample_value(name, labels or {})
    return v if v is not None else 0.0


@pytest.fixture(autouse=True)
def _fresh_mesh_state():
    """Per-device breakers/occupancy are process-wide per mesh (device
    health outlives snapshots) — isolate tests from each other."""
    _reset_mesh_state_for_tests()
    yield
    FAULTS.disarm()
    _reset_mesh_state_for_tests()


def lane_corpus():
    """A corpus exercising every lane: device-DFA regex rows (incl. byte
    overflow), compiled conditions, membership rows (overflow-capable), and
    a CPU-regex leaf (non-DFA subset)."""
    rx = Pattern("request.url_path", Operator.MATCHES, r"^/api/v[0-9]+/ok")
    cond = Pattern("request.method", Operator.EQ, "GET")
    gated = Pattern("request.path", Operator.EQ, "/gated")
    mem = All(Pattern("auth.identity.roles", Operator.INCL, "admin"),
              Pattern("auth.identity.groups", Operator.EXCL, "banned"))
    # backreference keeps this regex out of the DFA subset → cpu-regex lane
    cpu_rx = Pattern("request.query", Operator.MATCHES, r"^(a+)\1$")
    mix = Any_(rx, Pattern("auth.identity.roles", Operator.INCL, "root"))
    return {
        "cfg-rx": ConfigRules(name="cfg-rx",
                              evaluators=[(None, rx), (cond, gated)]),
        "cfg-mem": ConfigRules(name="cfg-mem", evaluators=[(None, mem)]),
        "cfg-mix": ConfigRules(name="cfg-mix", evaluators=[(cond, mix)]),
        "cfg-cpu": ConfigRules(name="cfg-cpu", evaluators=[(None, cpu_rx)]),
    }


def lane_docs():
    long_ok = "/api/v3/ok" + "x" * 120      # > DFA_VALUE_BYTES → byte overflow
    many = [f"r{k}" for k in range(70)]     # > any relieved K → host fallback
    return [
        ({"request": {"url_path": "/api/v1/ok", "method": "GET",
                      "path": "/gated"}, "auth": {"identity": {}}}, "cfg-rx"),
        ({"request": {"url_path": "/api/x", "method": "POST",
                      "path": "/other"}, "auth": {"identity": {}}}, "cfg-rx"),
        ({"request": {"url_path": long_ok, "method": "GET",
                      "path": "/other"}, "auth": {"identity": {}}}, "cfg-rx"),
        ({"request": {}, "auth": {"identity": {
            "roles": many + ["admin"], "groups": []}}}, "cfg-mem"),
        ({"request": {}, "auth": {"identity": {
            "roles": many, "groups": ["banned"]}}}, "cfg-mem"),
        ({"request": {}, "auth": {"identity": {
            "roles": ["admin"], "groups": []}}}, "cfg-mem"),
        ({"request": {"url_path": "/api/v9/ok", "method": "GET"},
          "auth": {"identity": {"roles": many}}}, "cfg-mix"),
        ({"request": {"url_path": "/zzz", "method": "POST"},
          "auth": {"identity": {"roles": many + ["root"]}}}, "cfg-mix"),
        ({"request": {"query": "aaaa"}, "auth": {}}, "cfg-cpu"),
        ({"request": {"query": "aaa"}, "auth": {}}, "cfg-cpu"),
    ]


def oracle_bits(model: ShardedPolicyModel, doc, name):
    shard, row = model.locator[name]
    _, rule, skipped = host_results(model.shards[shard], doc, int(row))
    return rule, skipped


# ---------------------------------------------------------------------------
# 1. bit-exact parity across dp×mp shapes (acceptance sweep)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dp,mp", SHAPES)
def test_bit_exact_parity_across_shapes(dp, mp, mesh_devices):
    """Mesh lane vs host oracle, all lanes, every pinned shape — run_full's
    (rule, skipped) matrices (host fallback applied, exactly what the
    engine serves) must equal the oracle's bit for bit."""
    corpus = lane_corpus()
    mesh = build_mesh(n_devices=dp * mp, dp=dp)
    model = ShardedPolicyModel(list(corpus.values()), mesh, members_k=4)
    docs = [d for d, _ in lane_docs()]
    names = [n for _, n in lane_docs()]
    rule, skipped = model.run_full(docs, names)
    for r, (doc, name) in enumerate(zip(docs, names)):
        want_rule, want_skip = oracle_bits(model, doc, name)
        E = len(want_rule)
        assert (skipped[r, :E] == want_skip).all(), (dp, mp, r, name)
        # rule bits compare where not condition-skipped: the kernel
        # evaluates skipped columns for real while the oracle leaves them
        # at the vacuous TRUE — both are outside the verdict contract
        live = ~want_skip
        assert (rule[r, :E][live] == want_rule[live]).all(), (dp, mp, r, name)
        # the boolean verdict agrees with the expression oracle
        evs = corpus[name].evaluators
        want = all(
            (cond is not None and not cond.matches(doc)) or rule_e.matches(doc)
            for cond, rule_e in evs)
        got = all(skipped[r, e] or rule[r, e] for e in range(len(evs)))
        assert got == want, (dp, mp, r, name)


def _served_single_corpus(configs, docs, names):
    """(verdict, firing) of the single-corpus served entry on the whole
    corpus, every operand lane the batch carries handed to it."""
    from authorino_tpu.compiler import compile_corpus
    from authorino_tpu.compiler.encode import encode_batch_py
    from authorino_tpu.compiler.pack import pack_batch
    from authorino_tpu.ops import pattern_eval as pe

    policy = compile_corpus(configs, members_k=4, ovf_assist=True)
    db = pack_batch(policy, encode_batch_py(
        policy, docs, [policy.config_ids[n] for n in names]))
    assert not db.host_fallback.any()  # the assist keeps every row exact
    packed = pe.eval_bitpacked_jit(pe.to_device(policy), *_operands(db))
    return unpack_attribution(np.asarray(packed),
                              int(policy.eval_rule.shape[1]))


@pytest.mark.parametrize("corpus,seed", [
    ("random", 101), ("random", 202), ("random", 303),
    # relation, numeric, overflow-assist, regex and CPU-fallback leaves in
    # one circuit, on a 2 x 2 mesh
    ("all-operand-lanes", 13), ("all-operand-lanes", 37)])
def test_attribution_parity_property(corpus, seed, mesh_devices):
    """Provenance parity (ISSUE 11 satellite): firing_columns /
    unpack_attribution over the shard-stacked bitpacked readback must match
    the host oracle — and the degrade lane (host_decide_many) must
    attribute identically to the device lane it replaces."""
    rng = random.Random(seed)
    if corpus == "random":
        configs = []
        for i in range(11):
            evaluators = []
            for _ in range(rng.randint(1, 3)):
                cond = random_expr(rng) if rng.random() < 0.3 else None
                evaluators.append((cond, random_expr(rng)))
            configs.append(ConfigRules(name=f"cfg-{i}", evaluators=evaluators))
        model = ShardedPolicyModel(configs, build_mesh(n_devices=8, dp=2),
                                   members_k=8)
        docs = [random_doc(rng) for _ in range(48)]
        names = [f"cfg-{rng.randrange(len(configs))}" for _ in docs]
    else:
        configs = all_operand_corpus(rng)
        model = ShardedPolicyModel(configs, build_mesh(n_devices=4, dp=2),
                                   members_k=4, ovf_assist=True)
        assert model.has_num and model.has_rel and model.has_ovf and model.has_dfa
        docs = all_operand_docs(rng)
        names = [rng.choice([c.name for c in configs]) for _ in docs]
    by_name = {c.name: c for c in configs}

    enc = model.encode(docs, names)
    packed = np.asarray(model.dispatch_full(enc))
    E = int(model.shards[0].eval_rule.shape[1])
    verdict, firing = unpack_attribution(packed, E)
    if corpus == "all-operand-lanes":
        assert not enc.host_fallback.any()
        n = len(docs)
        s_verdict, s_firing = _served_single_corpus(configs, docs, names)
        np.testing.assert_array_equal(verdict[:n], s_verdict[:n])
        np.testing.assert_array_equal(firing[:n], s_firing[:n])

    degraded = model.host_decide_many(names, docs)
    for r, (doc, name) in enumerate(zip(docs, names)):
        want_rule, want_skip = oracle_bits(model, doc, name)
        want_fire = int(firing_columns(want_rule[None, :],
                                       want_skip[None, :])[0])
        # degrade lane: always the oracle
        d_rule, d_skip = degraded[r]
        got_fire_d = int(firing_columns(d_rule[None, :], d_skip[None, :])[0])
        assert got_fire_d == want_fire, (r, name)
        if not enc.host_fallback[r]:
            # device lane: bit-identical attribution for non-lossy rows
            assert int(firing[r]) == want_fire, (r, name)
            assert bool(verdict[r]) == oracle_verdict(by_name[name], doc), (
                r, name)


def test_attribution_parity_through_dedup_fanout(mesh_devices):
    """Duplicate rows collapse to unique device work; the inverse fan-out
    must hand every duplicate the same verdict AND the same attribution
    (engine serving path, mesh snapshot)."""
    corpus = lane_corpus()
    engine = PolicyEngine(max_batch=32, members_k=4,
                          mesh=build_mesh(n_devices=8, dp=2))
    engine.apply_snapshot([
        EngineEntry(id=n, hosts=[n], runtime=None, rules=c)
        for n, c in corpus.items()])
    deny_doc = {"request": {"url_path": "/api/x", "method": "POST",
                            "path": "/other"}, "auth": {"identity": {}}}

    async def run():
        return await asyncio.gather(
            *(engine.submit(dict(deny_doc), "cfg-rx") for _ in range(6)))

    outs = asyncio.new_event_loop().run_until_complete(run())
    bits = {(tuple(map(bool, r)), tuple(map(bool, s))) for r, s in outs}
    assert len(bits) == 1  # every duplicate decided identically
    rule, skipped = outs[0]
    want_rule, want_skip = oracle_bits(engine._snapshot.sharded,
                                       deny_doc, "cfg-rx")
    E = len(want_rule)
    assert (np.asarray(skipped)[:E] == want_skip).all()
    live = ~want_skip
    assert (np.asarray(rule)[:E][live] == want_rule[live]).all()
    heat = engine._snapshot.heat
    assert heat is not None and heat.fold_calls >= 1


# ---------------------------------------------------------------------------
# 2. verdict-cache keying parity + survival across a 1-of-N mutation swap
# ---------------------------------------------------------------------------


def config_i(i: int, suffix: str = "") -> ConfigRules:
    return ConfigRules(name=f"ns/c{i}", evaluators=[
        (None, Pattern("request.path", Operator.EQ, f"/p{i}{suffix}"))])


def entries_for(configs):
    return [EngineEntry(id=c.name, hosts=[c.name], runtime=None, rules=c)
            for c in configs]


def test_mesh_cache_tokens_survive_one_of_n_mutation(mesh_devices):
    N = 40
    # lane selection off: cache-token survival is a DEVICE encode-path
    # contract (host-lane routing skips encode and the verdict cache)
    engine = PolicyEngine(max_batch=64, members_k=4,
                          mesh=build_mesh(n_devices=8, dp=2),
                          verdict_cache_size=4096, lane_select=False)
    engine.apply_snapshot(entries_for([config_i(i) for i in range(N)]))
    snap_old = engine._snapshot
    assert snap_old.mesh_tokens is not None  # PR 8 keying, not generations

    docs = [{"request": {"path": f"/p{i}"}} for i in range(N)]
    names = [f"ns/c{i}" for i in range(N)]

    async def run_all():
        return await asyncio.gather(
            *(engine.submit(d, n) for d, n in zip(docs, names)))

    loop = asyncio.new_event_loop()
    loop.run_until_complete(run_all())
    vc = engine._verdict_cache
    assert vc.counts()["entries"] >= N  # warmed: one entry per config row

    # 1-of-N mutation swap
    engine.apply_snapshot(entries_for(
        [config_i(0, suffix="x")] + [config_i(i) for i in range(1, N)]))
    snap_new = engine._snapshot

    # token parity is the survival mechanism: untouched configs keep the
    # exact (encoding_epoch, rules_fingerprint) token across the swap,
    # the mutated one gets a fresh fingerprint
    sharded = snap_new.sharded
    for i in range(1, N):
        s, r = sharded.locator[f"ns/c{i}"]
        assert snap_new.mesh_tokens[s][r] == snap_old.mesh_tokens[s][r], i
    s0, r0 = sharded.locator["ns/c0"]
    assert snap_new.mesh_tokens[s0][r0] != snap_old.mesh_tokens[s0][r0]

    hits_before = vc.counts()["hits"]
    loop.run_until_complete(run_all())
    hits = vc.counts()["hits"] - hits_before
    assert hits >= int(0.95 * N), hits  # ≥95% survival after 1-of-N swap


def test_mesh_inflight_pinning_inserts_under_own_tokens(mesh_devices):
    """A batch encoded against the OLD snapshot inserts under the old
    snapshot's tokens even if a swap lands mid-flight — token equality for
    untouched configs then makes those entries hit on the new snapshot."""
    engine = PolicyEngine(max_batch=8, members_k=4,
                          mesh=build_mesh(n_devices=8, dp=2),
                          verdict_cache_size=256)
    engine.apply_snapshot(entries_for([config_i(i) for i in range(4)]))
    old = engine._snapshot
    # swap BEFORE any traffic: in-flight pinning means the pinned snapshot
    # object (not engine._snapshot at completion time) provides the tokens
    engine.apply_snapshot(entries_for(
        [config_i(0, "x")] + [config_i(i) for i in range(1, 4)]))
    new = engine._snapshot
    assert old is not new
    s, r = new.sharded.locator["ns/c2"]
    assert new.mesh_tokens[s][r] == old.mesh_tokens[s][r]


# ---------------------------------------------------------------------------
# 3. strict verify: lint the packed shards BEFORE the upload (PR 4 caveat)
# ---------------------------------------------------------------------------


def test_strict_verify_lints_before_mesh_upload(monkeypatch, mesh_devices):
    from authorino_tpu.analysis import tensor_lint as lint_mod

    staged_at_lint = []
    real = lint_mod.lint_snapshot

    def probe(snap, *a, **kw):
        if getattr(snap, "sharded", None) is not None:
            # params is the DEVICE pytree — None means nothing staged yet
            staged_at_lint.append(snap.sharded.params is not None)
        return real(snap, *a, **kw)

    monkeypatch.setattr(lint_mod, "lint_snapshot", probe)
    engine = PolicyEngine(max_batch=8, members_k=4,
                          mesh=build_mesh(n_devices=8, dp=2),
                          strict_verify=True)
    engine.apply_snapshot(entries_for([config_i(i) for i in range(6)]))
    assert staged_at_lint == [False]          # lint ran pre-upload
    assert engine._snapshot.sharded.params is not None  # then staged
    assert engine._snapshot.lint_ok


def test_strict_verify_rejection_never_stages(monkeypatch, mesh_devices):
    from authorino_tpu.analysis import Finding
    from authorino_tpu.analysis import tensor_lint as lint_mod
    from authorino_tpu.runtime.engine import SnapshotRejected

    uploads = []
    real_upload = ShardedPolicyModel.upload

    def counting_upload(self, prev=None):
        uploads.append(self)
        return real_upload(self, prev)

    monkeypatch.setattr(ShardedPolicyModel, "upload", counting_upload)
    monkeypatch.setattr(
        lint_mod, "lint_snapshot",
        lambda snap, *a, **kw: [Finding(
            kind="shard-stack", message="synthetic corruption",
            layer="tensor_lint", severity="error")])
    engine = PolicyEngine(max_batch=8, members_k=4,
                          mesh=build_mesh(n_devices=8, dp=2),
                          strict_verify=True)
    with pytest.raises(SnapshotRejected):
        engine.apply_snapshot(entries_for([config_i(0)]))
    assert uploads == []  # a rejected corpus never shipped a byte


# ---------------------------------------------------------------------------
# 4. per-device failover: one device down ≠ host degrade
# ---------------------------------------------------------------------------


def run_batches(engine, n_rounds=6, n=8, idxs=(0, 1, 2, 3)):
    """Submit ``n_rounds`` batches of matching-path requests over the
    configs named by ``idxs`` (each doc matches its own config's pattern,
    so every verdict is expected allow)."""
    docs = [{"request": {"path": f"/p{idxs[i % len(idxs)]}"}}
            for i in range(n)]
    names = [f"ns/c{idxs[i % len(idxs)]}" for i in range(n)]
    async def round_():
        return await asyncio.gather(
            *(engine.submit(d, nm) for d, nm in zip(docs, names)))

    loop = asyncio.new_event_loop()
    outs = []
    for _ in range(n_rounds):
        outs += loop.run_until_complete(round_())
    got = [bool(rule[0]) for rule, _ in outs]
    return got, [True] * (n * n_rounds)


def test_one_device_down_fails_over_without_degrade(mesh_devices):
    engine = PolicyEngine(max_batch=8, members_k=4,
                          mesh=build_mesh(n_devices=8, dp=2),
                          verdict_cache_size=0, batch_dedup=False)
    engine.apply_snapshot(entries_for([config_i(i) for i in range(4)]))
    degraded_before = counter_value("auth_server_degraded_decisions_total",
                                    {"lane": "engine"})
    failover_before = counter_value("auth_server_device_failover_total",
                                    {"device": "0"})
    FAULTS.arm("one-device-down")  # kernel:raise:device=0
    try:
        got, expected = run_batches(engine)
    finally:
        FAULTS.disarm()
    assert got == expected  # verdicts exact throughout the incident
    # zero host-oracle decisions: every batch resolved on a healthy device
    assert counter_value("auth_server_degraded_decisions_total",
                         {"lane": "engine"}) == degraded_before
    assert counter_value("auth_server_device_failover_total",
                         {"device": "0"}) > failover_before
    mesh_vars = engine.debug_vars()["mesh"]
    b0 = mesh_vars["breakers"]["0"]
    assert b0["consecutive_failures"] > 0 or b0["state"] != "closed"
    assert mesh_vars["failovers"]["0"] > 0
    # healthy devices actually absorbed the traffic
    assert sum(int(v) for d, v in mesh_vars["launches"].items()
               if d != "0") > 0


def test_open_device_reprobes_and_rejoins_the_mesh(mesh_devices):
    """Recovery: an OPEN device whose cooldown elapsed must actually get
    its half-open probe from live traffic (due probes sort FIRST in
    dispatch_routed — closed-first ordering would starve the probe and
    strand the mesh in single-device dispatch forever), and a successful
    probe returns the lane to full-mesh launches."""
    # breaker_threshold reaches the per-DEVICE mesh breakers too (the
    # engine plumbs it into MeshState at first touch of the mesh).
    # Lane selection off: the probe must come from live DEVICE traffic —
    # with the cost model live, these small cuts would ride the host lane
    # and the reprobe timing would depend on explore cadence instead
    engine = PolicyEngine(max_batch=8, members_k=4,
                          mesh=build_mesh(n_devices=8, dp=2),
                          verdict_cache_size=0, batch_dedup=False,
                          breaker_threshold=3, lane_select=False)
    engine.apply_snapshot(entries_for([config_i(i) for i in range(4)]))
    FAULTS.arm("one-device-down")  # kernel:raise:device=0
    try:
        run_batches(engine, n_rounds=4)  # walk device 0's breaker open
    finally:
        FAULTS.disarm()
    state = engine._snapshot.sharded.state
    b0 = state.breakers.get(0)
    assert b0.state == "open"
    full_launches_before = state.launches[0]
    b0._opened_at -= b0.reset_s + 1.0  # cooldown elapsed (no wall sleep)
    got, expected = run_batches(engine, n_rounds=3)
    assert got == expected
    # the probe fired on device 0, succeeded, and closed the breaker
    assert b0.state == "closed"
    assert [t["state"] for t in b0.to_json()["transitions"]][-2:] == \
        ["half-open", "closed"]
    # ...and full-mesh launches resumed (device 0 participates again)
    assert state.launches[0] > full_launches_before


def test_all_devices_down_degrades_exactly(mesh_devices):
    engine = PolicyEngine(max_batch=8, members_k=4,
                          mesh=build_mesh(n_devices=8, dp=2),
                          verdict_cache_size=0, batch_dedup=False,
                          breaker_threshold=1000)
    engine.apply_snapshot(entries_for([config_i(i) for i in range(4)]))
    degraded_before = counter_value("auth_server_degraded_decisions_total",
                                    {"lane": "engine"})
    # every device id scoped down → MeshUnavailable → retry → host degrade
    FAULTS.arm(";".join(f"kernel:raise:device={d}" for d in range(8)))
    try:
        got, expected = run_batches(engine, n_rounds=2)
    finally:
        FAULTS.disarm()
    assert got == expected  # host oracle keeps answers exact
    assert counter_value("auth_server_degraded_decisions_total",
                         {"lane": "engine"}) > degraded_before


def test_mesh_unavailable_when_all_breakers_exhausted(mesh_devices):
    corpus = [config_i(i) for i in range(4)]
    model = ShardedPolicyModel([c for c in corpus],
                               build_mesh(n_devices=8, dp=2), members_k=4)
    enc = model.encode([{"request": {"path": "/p0"}}], ["ns/c0"])
    FAULTS.arm(";".join(f"kernel:raise:device={d}" for d in range(8)))
    try:
        with pytest.raises(MeshUnavailable):
            model.dispatch_routed(enc)
    finally:
        FAULTS.disarm()
    # every device recorded its failure
    assert all(v >= 1 for v in model.state.failovers.values())


# ---------------------------------------------------------------------------
# 5. per-shard delta uploads: a one-config mutation feeds its owning shard
# ---------------------------------------------------------------------------


def test_one_config_mutation_ships_to_owning_shard_only(mesh_devices):
    N = 8
    engine = PolicyEngine(max_batch=8, members_k=4,
                          mesh=build_mesh(n_devices=8, dp=2))
    engine.apply_snapshot(entries_for([config_i(i) for i in range(N)]))
    first = engine._snapshot.upload
    assert first["mode"] == "full"

    engine.apply_snapshot(entries_for(
        [config_i(0, suffix="x")] + [config_i(i) for i in range(1, N)]))
    up = engine._snapshot.upload
    assert up["mode"] == "delta"
    assert up["upload_bytes"] * 2 <= up["full_bytes"]  # ≪ full mesh upload
    owner, _ = engine._snapshot.sharded.locator["ns/c0"]
    per_shard = up["per_shard_bytes"]
    assert per_shard[str(owner)] > 0
    for s, b in per_shard.items():
        if s != str(owner):
            assert b == 0, (s, per_shard)  # unchanged shards got zero bytes

    # and the delta-staged corpus still serves exact verdicts (c0's new
    # pattern no longer matches /p0; the untouched configs all allow)
    got, expected = run_batches(engine, n_rounds=1, n=7,
                                idxs=tuple(range(1, N)))
    assert got == expected


# ---------------------------------------------------------------------------
# 6. grid relief: cpu-grid-overflow exiles serve from the fast lane
# ---------------------------------------------------------------------------


def membership_corpus(n=6):
    return [ConfigRules(name=f"m/c{i}", evaluators=[
        (None, Pattern("auth.identity.roles", Operator.INCL, f"g{i}"))])
        for i in range(n)]


def relief_docs(n=6, roles=40):
    # 40 roles overflow the single-corpus K=16 but fit the mesh's relieved
    # K (≥ 32; 64 on mp=4) — the exact rows grid relief rescues
    return ([{"auth": {"identity": {
        "roles": [f"x{k}" for k in range(roles)] + [f"g{i}"]}}}
        for i in range(n)],
        [f"m/c{i}" for i in range(n)])


def test_grid_relief_serves_overflow_from_fast_lane(mesh_devices):
    docs, names = relief_docs()
    single = PolicyEngine(max_batch=8, members_k=16, mesh=None)
    single.apply_snapshot(entries_for(membership_corpus()))
    sharded = PolicyEngine(max_batch=8, members_k=16,
                           mesh=build_mesh(n_devices=8, dp=2))
    sharded.apply_snapshot(entries_for(membership_corpus()))

    # single corpus: every row is a host-fallback exile (lossy compact K)
    from authorino_tpu.compiler.encode import encode_batch
    from authorino_tpu.compiler.pack import pack_batch

    pol = single._snapshot.policy
    rows = [pol.config_ids[n] for n in names]
    db = pack_batch(pol, encode_batch(pol, docs, rows))
    assert db.host_fallback[: len(docs)].all()

    # mesh: the same rows ride the kernel (no fallback), bit-exact verdicts
    enc = sharded._snapshot.sharded.encode(docs, names)
    assert not enc.host_fallback[: len(docs)].any()
    assert sharded._snapshot.sharded.decide(docs, names) == [True] * len(docs)

    # lowerability: the caveat count drops to zero on the mesh report
    single_report = single._lowerability["by_reason"]
    mesh_report = sharded._lowerability["by_reason"]
    assert single_report.get("cpu-grid-overflow", 0) == len(names)
    assert mesh_report.get("cpu-grid-overflow", 0) == 0


# ---------------------------------------------------------------------------
# 7. mesh↔mesh canary (control-plane parity)
# ---------------------------------------------------------------------------


def test_mesh_canary_promotes_clean_window(mesh_devices):
    engine = PolicyEngine(max_batch=8, members_k=4,
                          mesh=build_mesh(n_devices=8, dp=2),
                          canary_fraction=0.5, canary_window_s=0.3)
    engine.apply_snapshot(entries_for([config_i(i) for i in range(4)]))
    gen_baseline = engine._snapshot.generation
    engine.apply_snapshot(entries_for(
        [config_i(0, suffix="x")] + [config_i(i) for i in range(1, 4)]))
    assert engine._canary is not None  # mesh↔mesh swaps canary now
    phase = engine._canary
    # traffic over the configs the reconcile did NOT touch: both cohorts
    # must allow identically, so the guard window stays clean
    got, expected = run_batches(engine, n_rounds=2, n=6, idxs=(1, 2, 3))
    assert got == expected
    engine._canary_conclude(phase)
    assert engine._canary is None
    assert engine._snapshot.generation > gen_baseline
    assert engine._snapshot.sharded is phase.snap.sharded
