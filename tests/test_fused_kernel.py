"""Fused mega-kernel (ISSUE 17, ops/fused_kernel.py): the whole hot path
in ONE launch.

Covers: the 3-seed cross-lane differential (fused vs gather vs matmul vs
the host oracle — verdict AND attribution — over corpora exercising the
DFA byte scan incl. byte overflow, relation gathers, numeric compares,
membership overflow with and without ovf-assist, and CPU-fallback regex
rows); the staged pre-fusion baseline staying bit-exact while costing >1
launch on the ledger; the perf-guard pin that the fused engine lane
performs EXACTLY one launch per batch with the exact bitpacked D2H byte
count (plus the planted-extra-launch self-test on the fused lane); the
snapshot-swap prewarm hook; the entry-point audit listing the fused
entry; the certifier rejecting the new fused-layout mutant classes with
the fused lane selected; strict-verify rejection of a fused-layout
corruption leaving the old snapshot serving; lane resolution via
--kernel-lane / AUTHORINO_TPU_KERNEL_LANE / auto; the occupancy-shaped
mesh pad; and the mesh 2x2 fused parity sweep."""

import asyncio
import copy
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from authorino_tpu.compiler import ConfigRules, compile_corpus
from authorino_tpu.compiler.encode import encode_batch_py
from authorino_tpu.compiler.pack import pack_batch
from authorino_tpu.expressions import All, Any_, InGroup, Operator, Pattern
from authorino_tpu.models.policy_model import host_results
from authorino_tpu.ops import fused_kernel as fk
from authorino_tpu.ops import pattern_eval as pe
from authorino_tpu.relations.closure import RelationClosure
from authorino_tpu.runtime import EngineEntry, PolicyEngine
from authorino_tpu.runtime.kernel_cost import LEDGER

from test_kernel_cost import assert_launch_parity, delta, sample


def run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


K = 4  # members_k small enough that role lists overflow on purpose


def _corpus(rng: random.Random, n_configs=6):
    """Every lane in one corpus: relations (deep chain), numeric compares,
    membership (overflow-capable at K=4), eq, device-DFA regex rows (two
    distinct tables -> the grouped gather layout is non-trivial), and one
    CPU-regex config (backreference: outside the DFA subset)."""
    deep = [(f"d{i}", f"d{i + 1}") for i in range(6)]
    rel = RelationClosure(deep + [("u", "left"), ("left", "mid"),
                                  ("mid", "top")])
    groups = ["mid", "top", "left", "d3", "d5"]
    cfgs = []
    for i in range(n_configs):
        leaves = [
            InGroup("auth.identity.sub", rng.choice(groups), rel),
            Pattern("req.n", rng.choice(
                [Operator.GT, Operator.GE, Operator.LT, Operator.LE]),
                str(rng.randrange(-5, 30))),
            Pattern("auth.identity.roles", Operator.INCL, f"r{i % 3}"),
            Pattern("req.m", Operator.EQ, rng.choice(["GET", "POST"])),
            Pattern("req.path", Operator.MATCHES, rf"^/svc-{i % 3}/"),
        ]
        rng.shuffle(leaves)
        rule = All(leaves[0], Any_(*leaves[1:4]))
        cond = leaves[4] if rng.random() < 0.5 else None
        cfgs.append(ConfigRules(name=f"cfg-{i}",
                                evaluators=[(cond, rule), (None, leaves[4])]))
    cfgs.append(ConfigRules(name="cfg-cpu", evaluators=[
        (None, Pattern("req.q", Operator.MATCHES, r"^(a+)\1$"))]))
    return cfgs


def _docs(rng: random.Random, n=48):
    ents = [f"d{i}" for i in range(7)] + ["u", "left", "mid", "top",
                                          "stranger"]
    docs = []
    for _ in range(n):
        docs.append({
            "req": {"n": rng.choice([-10, 0, 3, 29, 30, "x", None]),
                    "m": rng.choice(["GET", "POST", "PUT"]),
                    # the long path exceeds DFA_VALUE_BYTES -> byte overflow
                    "path": rng.choice(["/svc-0/a", "/svc-1/b", "/zzz",
                                        "/svc-2/" + "x" * 200]),
                    "q": rng.choice(["aaaa", "aaa", "ab"])},
            "auth": {"identity": {
                "sub": rng.choice(ents),
                "roles": [f"r{rng.randrange(4)}"
                          for _ in range(rng.choice([1, 2, K + 3]))],
            }},
        })
    return docs


def _batch(policy, docs, names):
    rows = [policy.config_ids[n] for n in names]
    db = pack_batch(policy, encode_batch_py(policy, docs, rows))
    has_dfa = policy.n_byte_attrs > 0
    args = (
        jnp.asarray(db.attrs_val), jnp.asarray(db.members_c),
        jnp.asarray(db.cpu_dense), jnp.asarray(db.config_id),
        jnp.asarray(db.attr_bytes) if has_dfa else None,
        jnp.asarray(db.byte_ovf) if has_dfa else None,
        *pe._extra_operands(db),
    )
    return db, rows, args


# ---------------------------------------------------------------------------
# 1. cross-lane differential: fused == gather == matmul == host oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [7, 19, 31])
def test_fused_bit_identical_across_lanes_and_oracle(seed):
    rng = random.Random(seed)
    cfgs = _corpus(rng)
    policy = compile_corpus(cfgs, members_k=K, ovf_assist=True)
    docs = _docs(rng)
    names = [rng.choice([c.name for c in cfgs]) for _ in docs]
    db, rows, args = _batch(policy, docs, names)
    assert not db.host_fallback.any()  # ovf_assist: no lossy rows

    params = {lane: pe.to_device(policy, lane=lane)
              for lane in ("fused", "gather", "matmul")}
    for lane, p in params.items():
        assert pe.kernel_lane_of(p) == lane
    assert params["fused"]["fused"] is not None
    assert params["gather"]["fused"] is None

    # the in-kernel bitpacked readback, all three lanes, bit for bit
    packed_f = np.asarray(fk.eval_fused_kernel(params["fused"], db))
    assert packed_f.dtype == np.uint8
    for lane in ("gather", "matmul"):
        packed_l = np.asarray(pe.eval_bitpacked_jit(params[lane], *args))
        np.testing.assert_array_equal(packed_f, packed_l, err_msg=lane)

    # verdict AND attribution against the host oracle, every row
    E = int(policy.eval_rule.shape[1])
    verdict, firing = pe.unpack_attribution(packed_f, E)
    want = [host_results(policy, d, r) for d, r in zip(docs, rows)]
    w_fire = pe.firing_columns(np.stack([w[1] for w in want]),
                               np.stack([w[2] for w in want]))
    for i in range(len(docs)):
        assert bool(verdict[i]) == bool(want[i][0]), (seed, i)
        assert int(firing[i]) == int(w_fire[i]), (seed, i)


def test_fused_matches_gather_on_host_fallback_corpus():
    """Without ovf-assist, membership-overflow rows route to the host
    oracle — the fused lane's device results for those rows (and the pad
    tail) must still be bit-identical to the gather lane's."""
    rng = random.Random(5)
    cfgs = _corpus(rng)
    policy = compile_corpus(cfgs, members_k=K, ovf_assist=False)
    docs = _docs(rng)
    names = [rng.choice([c.name for c in cfgs]) for _ in docs]
    db, _, args = _batch(policy, docs, names)
    assert db.host_fallback.any()  # K+3 role lists overflow K=4

    packed_f = np.asarray(
        fk.eval_fused_kernel(pe.to_device(policy, lane="fused"), db))
    packed_g = np.asarray(
        pe.eval_bitpacked_jit(pe.to_device(policy, lane="gather"), *args))
    np.testing.assert_array_equal(packed_f, packed_g)


# ---------------------------------------------------------------------------
# 2. staged pre-fusion baseline: same bits, MORE launches
# ---------------------------------------------------------------------------


def test_staged_baseline_bit_exact_but_multi_launch():
    rng = random.Random(3)
    cfgs = _corpus(rng)
    policy = compile_corpus(cfgs, members_k=K, ovf_assist=True)
    docs = _docs(rng, n=32)
    names = [rng.choice([c.name for c in cfgs]) for _ in docs]
    db, _, _ = _batch(policy, docs, names)
    params = pe.to_device(policy, lane="fused")

    fused = np.asarray(fk.eval_fused_kernel(params, db))
    staged = np.asarray(fk.dispatch_staged(params, db))
    np.testing.assert_array_equal(fused, staged)

    # a DFA+relations+numeric corpus costs 5 stage launches unfused:
    # leaves, DFA scan, value lanes, circuit, bitpack
    assert fk.staged_launches(params, db) == 5

    # the ledger records them as real launches — the structural proof the
    # mega-kernel actually fuses something
    b0 = LEDGER.snapshot("host")
    fk.dispatch_staged(params, db, ledger_lane="host")
    d = delta(b0, LEDGER.snapshot("host"))
    assert d["launches"] == fk.staged_launches(params, db) > 1


# ---------------------------------------------------------------------------
# 3. perf guard: the fused engine lane is ONE launch per batch, exact D2H
# ---------------------------------------------------------------------------


ENGINE_REL = RelationClosure([("alice", "staff"), ("staff", "org")])
ENGINE_RULE = All(
    Pattern("request.method", Operator.EQ, "GET"),
    Pattern("request.url_path", Operator.MATCHES, r"^/api/"),
    InGroup("auth.identity.sub", "org", ENGINE_REL),
    Pattern("auth.identity.age", Operator.GE, "18"),
)


def build_fused_engine(rule=ENGINE_RULE, **kw) -> PolicyEngine:
    kw.setdefault("max_batch", 32)
    kw.setdefault("lane_select", False)
    kw.setdefault("batch_dedup", False)
    kw.setdefault("verdict_cache_size", 0)
    kw.setdefault("kernel_lane", "fused")
    engine = PolicyEngine(members_k=4, mesh=None, **kw)
    engine.apply_snapshot([
        EngineEntry(id="c", hosts=["c"], runtime=None,
                    rules=ConfigRules(name="c", evaluators=[(None, rule)]))
    ])
    return engine


def fused_doc(i: int, allow=True):
    return {"request": {"method": "GET",
                        "url_path": "/api/v1" if allow else "/other"},
            "auth": {"identity": {"sub": "alice", "age": 42,
                                  "tag": f"t{i}"}}}


async def submit_all(engine, docs):
    outs = await asyncio.gather(*(engine.submit(d, "c") for d in docs))
    return [bool(rule[0]) for rule, _ in outs]


class TestFusedEngineLane:
    def test_one_launch_per_batch_exact_d2h(self):
        lane0 = sample("auth_server_kernel_lane_total", {"lane": "fused"})

        async def go():
            engine = build_fused_engine()
            b0 = LEDGER.snapshot("engine")
            got = await submit_all(
                engine, [fused_doc(i, allow=i % 2 == 0) for i in range(6)])
            assert got == [i % 2 == 0 for i in range(6)]
            return engine, delta(b0, LEDGER.snapshot("engine"))

        engine, d = run(go())
        params = engine._snapshot.params
        assert params.get("fused") is not None
        assert pe.kernel_lane_of(params) == "fused"

        # launches_per_batch == 1.0 EXACTLY on the fused lane
        assert d["batches"] >= 1
        assert d["zero_launch_batches"] == 0
        assert d["launches"] == d["batches"]
        assert_launch_parity(d)

        # D2H is the in-kernel bitpacked readback and nothing else
        policy = engine._snapshot.policy
        E = int(policy.eval_rule.shape[1])
        W = pe.packed_width(1 + 2 * E)
        assert policy.fused_pack_w == W
        assert d["d2h_bytes"] == d["pad_rows"] * W

        # the lane counter moved by exactly the batches dispatched fused
        assert sample("auth_server_kernel_lane_total",
                      {"lane": "fused"}) - lane0 == d["batches"]

        # entry-point audit: the mega-kernel is a first-class audited entry
        names = [e["entry"] for e in
                 engine.debug_vars()["kernel_cost"]["entry_points"]]
        assert "fused_kernel" in names

    def test_planted_extra_launch_trips_gate_on_fused_lane(self):
        async def go():
            engine = build_fused_engine()
            b0 = LEDGER.snapshot("engine")
            await submit_all(engine, [fused_doc(i) for i in range(3)])
            LEDGER.observe_launch("engine")  # a stray unfused stage
            return delta(b0, LEDGER.snapshot("engine"))

        d = run(go())
        assert d["launches"] == d["batches"] + 1
        with pytest.raises(AssertionError, match="launch parity"):
            assert_launch_parity(d)


# ---------------------------------------------------------------------------
# 4. snapshot-swap prewarm (both frontends warm this module's entries)
# ---------------------------------------------------------------------------


def test_snapshot_swap_prewarms_fused_entry(monkeypatch):
    calls = []
    real = fk.prewarm_fused

    def probe(policy, params, **kw):
        calls.append(real(policy, params, **kw))
        return calls[-1]

    monkeypatch.setattr(fk, "prewarm_fused", probe)
    engine = build_fused_engine()
    assert calls == [True]  # warmed exactly once, at swap

    # no-op (False) on a snapshot without the fused subtree
    gp = pe.to_device(engine._snapshot.policy, lane="gather")
    assert fk.prewarm_fused(engine._snapshot.policy, gp) is False


# ---------------------------------------------------------------------------
# 5. certifier + strict-verify: fused-layout corruptions cannot serve
# ---------------------------------------------------------------------------


def _plant_perm(p):
    p.dfa_row_perm = p.dfa_row_perm.copy()
    p.dfa_row_perm[0] = p.dfa_row_perm[1]


def _plant_int8(p):
    p.leaf_op_i8 = p.leaf_op_i8.copy()
    p.leaf_op_i8[0] += 1


def _plant_packw(p):
    p.fused_pack_w = int(p.fused_pack_w) + 1


def test_certifier_rejects_fused_layout_with_fused_lane(monkeypatch):
    from authorino_tpu.analysis.translation_validate import certify_snapshot

    monkeypatch.setenv("AUTHORINO_TPU_KERNEL_LANE", "fused")
    rng = random.Random(11)
    policy = compile_corpus(_corpus(rng), members_k=K, ovf_assist=True)
    _, fails, _ = certify_snapshot(policy, use_cache=False)
    assert not fails, fails[:3]
    for plant in (_plant_perm, _plant_int8, _plant_packw):
        bad = copy.deepcopy(policy)
        plant(bad)
        _, fails, _ = certify_snapshot(bad, use_cache=False)
        assert any(f.kind == "fused-layout" for f in fails), plant.__name__


def test_strict_verify_fused_corruption_keeps_old_snapshot(monkeypatch):
    import authorino_tpu.snapshots.compile_cache as cc
    from authorino_tpu.runtime.engine import SnapshotRejected

    engine = build_fused_engine(strict_verify=True)
    assert run(submit_all(engine, [fused_doc(0)])) == [True]

    real = cc.compile_corpus

    def corrupting(*a, **kw):
        pol = real(*a, **kw)
        pol.fused_pack_w = int(pol.fused_pack_w) + 1  # fused-pack-width
        return pol

    monkeypatch.setattr(cc, "compile_corpus", corrupting)
    with pytest.raises(SnapshotRejected):
        engine.apply_snapshot([
            EngineEntry(id="c2", hosts=["c2"], runtime=None,
                        rules=ConfigRules(name="c2", evaluators=[
                            (None, Pattern("a.b", Operator.EQ, "x"))]))
        ])
    # the rejected corpus never swapped in: the old snapshot still serves
    assert run(submit_all(engine, [fused_doc(1)])) == [True]


# ---------------------------------------------------------------------------
# 6. lane resolution + occupancy pad units
# ---------------------------------------------------------------------------


def test_kernel_lane_env_and_auto_resolution(monkeypatch):
    policy = compile_corpus([ConfigRules(name="c", evaluators=[
        (None, Pattern("a.b", Operator.EQ, "x"))])], members_k=4)
    monkeypatch.setenv("AUTHORINO_TPU_KERNEL_LANE", "fused")
    p = pe.to_device(policy)
    assert p["fused"] is not None and pe.kernel_lane_of(p) == "fused"
    assert pe.kernel_body_of(p) == "pallas"
    monkeypatch.delenv("AUTHORINO_TPU_KERNEL_LANE")
    # explicit argument wins regardless of env
    monkeypatch.setenv("AUTHORINO_TPU_KERNEL_LANE", "gather")
    assert pe.to_device(policy, lane="fused")["fused"] is not None


def test_kernel_lane_auto_is_matmul_on_every_platform(monkeypatch):
    """`--kernel-lane auto` resolves to the matmul lane whatever the target
    device: on a TPU v5e it is the body that compiled and served (PR 21's
    chip run), and the fused lane's Pallas body does not lower there — so
    auto never arms it, and what serves is reported as lax, not Pallas."""

    class _Dev:
        def __init__(self, platform):
            self.platform = platform

    policy = compile_corpus([ConfigRules(name="c", evaluators=[
        (None, Pattern("a.b", Operator.EQ, "x"))])], members_k=4)
    monkeypatch.delenv("AUTHORINO_TPU_KERNEL_LANE", raising=False)
    monkeypatch.delenv("AUTHORINO_TPU_EVAL_LANE", raising=False)
    for platform in ("tpu", "cpu"):
        p = pe.to_device(policy, device=_Dev(platform), host=True)
        assert pe.kernel_lane_of(p) == "matmul", platform
        assert p["fused"] is None and pe.kernel_body_of(p) == "lax"
    # the operands follow the device they are built for: bf16 for the MXU,
    # f32 for the CPU backend (the native lane's host twin in a TPU process)
    tpu = pe.to_device(policy, device=_Dev("tpu"), host=True)
    cpu = pe.to_device(policy, device=_Dev("cpu"), host=True)
    assert str(tpu["matmul"]["mxu"].dtype) == "bfloat16"
    assert str(cpu["matmul"]["mxu"].dtype) == "float32"
    # the dense body's one-hot operands are built only on request
    assert "rule_m" not in tpu["matmul"]
    dense = pe.to_device(policy, device=_Dev("tpu"), host=True, dense=True)
    assert str(dense["matmul"]["rule_m"].dtype) == "bfloat16"


def test_occupancy_pad_shapes():
    # pow2 floor, never below the real row count, busiest-shard * dp
    assert fk.occupancy_pad([1, 1], dp=2, n_rows=2) == 16
    assert fk.occupancy_pad([0, 0], dp=2, n_rows=0) == 16
    assert fk.occupancy_pad([8, 1], dp=2, n_rows=9) == 16
    assert fk.occupancy_pad([20, 1], dp=2, n_rows=21) == 64
    assert fk.occupancy_pad([1, 1], dp=2, n_rows=100) == 128
    assert fk.occupancy_pad([64, 0], dp=2, n_rows=64, cap=64) == 128


# ---------------------------------------------------------------------------
# 7. mesh 2x2: fused lane parity under shard_map
# ---------------------------------------------------------------------------


@pytest.mark.mesh
@pytest.mark.parametrize("seed", [13, 37])
def test_mesh_2x2_fused_parity(seed, mesh_devices):
    from authorino_tpu.parallel import ShardedPolicyModel, build_mesh

    rng = random.Random(seed)
    cfgs = _corpus(rng)
    docs = _docs(rng)
    names = [rng.choice([c.name for c in cfgs]) for _ in docs]
    mesh = build_mesh(n_devices=4, dp=2)  # 2x2
    sharded = ShardedPolicyModel(cfgs, mesh, members_k=K, ovf_assist=True,
                                 kernel_lane="fused")
    assert sharded.has_fused
    own_rule, own_skip = sharded.run_full(docs, names)
    n = len(docs)
    fire = pe.firing_columns(own_rule[:n], own_skip[:n])
    for i, (d, name) in enumerate(zip(docs, names)):
        shard, row = sharded.locator[name]
        w_own, w_rule, w_skip = host_results(sharded.shards[shard], d,
                                             int(row))
        w_fire = pe.firing_columns(w_rule[None, :], w_skip[None, :])[0]
        got_own = bool(np.all(own_skip[i] | own_rule[i]))
        assert got_own == w_own, (seed, i)
        assert int(fire[i]) == int(w_fire), (seed, i)
