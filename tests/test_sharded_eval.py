"""Sharded (dp × mp) evaluation must agree with the single-corpus model and
the CPU oracle, on an 8-device virtual CPU mesh (conftest sets XLA flags)."""

import random

import jax
import numpy as np
import pytest

from authorino_tpu.compiler import ConfigRules, compile_corpus
from authorino_tpu.models import PolicyModel
from authorino_tpu.parallel import ShardedPolicyModel, build_mesh

from test_compiler_differential import oracle_verdict, random_doc, random_expr


def make_corpus(rng, n_configs):
    configs = []
    for i in range(n_configs):
        evaluators = []
        for _ in range(rng.randint(1, 3)):
            cond = random_expr(rng) if rng.random() < 0.3 else None
            evaluators.append((cond, random_expr(rng)))
        configs.append(ConfigRules(name=f"cfg-{i}", evaluators=evaluators))
    return configs


def test_eight_virtual_devices_present():
    assert len(jax.devices()) >= 8


@pytest.mark.parametrize("seed,dp", [(11, 2), (12, 4), (13, 1)])
def test_sharded_matches_oracle(seed, dp):
    rng = random.Random(seed)
    configs = make_corpus(rng, n_configs=13)  # uneven split across shards
    mesh = build_mesh(n_devices=8, dp=dp)
    sharded = ShardedPolicyModel(configs, mesh, members_k=8)
    single = PolicyModel.from_configs(configs, members_k=8)

    docs = [random_doc(rng) for _ in range(32)]
    names = [f"cfg-{rng.randrange(len(configs))}" for _ in docs]

    got = sharded.decide(docs, names)
    got_single = single.decide(docs, names)
    expected = [oracle_verdict(configs[int(n.split('-')[1])], d) for d, n in zip(docs, names)]
    assert got == expected
    assert got_single == expected


def test_sharded_params_actually_sharded():
    rng = random.Random(7)
    configs = make_corpus(rng, 8)
    mesh = build_mesh(n_devices=8, dp=2)  # mp = 4
    m = ShardedPolicyModel(configs, mesh)
    # leaf tables carry a leading [S=4] axis sharded over mp
    assert m.params["leaf_op"].shape[0] == 4
    shard_devs = {d for d in m.params["leaf_op"].sharding.device_set}
    assert len(shard_devs) == 8  # placed across the whole mesh


def test_sharded_matmul_lane_active():
    """The stacked params carry the MXU matmul operands (one per shard,
    leading [S] axis) — the sharded path must not silently fall back to the
    gather formulation."""
    rng = random.Random(31)
    configs = make_corpus(rng, 9)
    mesh = build_mesh(n_devices=8, dp=2)  # mp = 4
    m = ShardedPolicyModel(configs, mesh, members_k=8)
    assert m.has_matmul and m.params["matmul"] is not None
    assert m.params["matmul"]["attr_onehot"].shape[0] == 4  # [S, A, L]
    # and it still matches the oracle end-to-end
    docs = [random_doc(rng) for _ in range(16)]
    names = [f"cfg-{rng.randrange(9)}" for _ in docs]
    expected = [oracle_verdict(configs[int(n.split('-')[1])], d) for d, n in zip(docs, names)]
    assert m.decide(docs, names) == expected


def test_sharded_dfa_lane_rides_the_mesh():
    """Regexes concentrated in a few configs: only some shards naturally
    have DFA rows, the ShapeTargets union forces a uniform lane, and the
    device verdicts still match the oracle."""
    from authorino_tpu.expressions import All, Operator, Pattern

    configs = []
    for i in range(9):  # 9 configs over mp=4 shards → uneven
        pats = [Pattern("request.method", Operator.EQ, "GET")]
        if i % 3 == 0:  # regexes only in configs 0,3,6 → shards 0,3,2
            pats.append(Pattern("request.url_path", Operator.MATCHES, rf"^/svc-{i}/\d+$"))
        configs.append(ConfigRules(name=f"cfg-{i}", evaluators=[(None, All(*pats))]))
    mesh = build_mesh(n_devices=8, dp=2)
    m = ShardedPolicyModel(configs, mesh, members_k=4)
    assert m.has_dfa and m.params["leaf_dfa_row"] is not None

    docs, names, expected = [], [], []
    for i in range(9):
        for path, ok in [(f"/svc-{i}/42", True), (f"/svc-{i}/x", False)]:
            docs.append({"request": {"method": "GET", "url_path": path}})
            names.append(f"cfg-{i}")
            expected.append(ok if i % 3 == 0 else True)
    assert m.decide(docs, names) == expected


def test_sharded_full_outputs_match_single_corpus():
    """apply_full returns the same own (verdict, rule, skipped) tensors as
    the single-corpus eval_full_jit — the contract PolicyEngine serves."""
    import jax.numpy as jnp

    from authorino_tpu.ops.pattern_eval import eval_full_jit

    rng = random.Random(21)
    configs = make_corpus(rng, 11)
    mesh = build_mesh(n_devices=8, dp=2)
    sharded = ShardedPolicyModel(configs, mesh, members_k=8)
    single = PolicyModel.from_configs(configs, members_k=8)

    docs = [random_doc(rng) for _ in range(24)]
    names = [f"cfg-{rng.randrange(len(configs))}" for _ in docs]
    rows = [single.policy.config_ids[n] for n in names]

    enc_s = sharded.encode(docs, names)
    own_s, rule_s, skip_s = sharded.apply_full(enc_s)

    db = single.encode(docs, rows)
    has_dfa = single.policy.n_byte_attrs > 0
    own_1, rule_1, skip_1 = (
        np.asarray(a)
        for a in eval_full_jit(
            single.params,
            jnp.asarray(db.attrs_val),
            jnp.asarray(db.members_c),
            jnp.asarray(db.cpu_dense),
            jnp.asarray(db.config_id),
            jnp.asarray(db.attr_bytes) if has_dfa else None,
            jnp.asarray(db.byte_ovf) if has_dfa else None,
        )
    )
    B = len(docs)
    ok = ~enc_s.host_fallback[:B]  # compact-lossy rows go to the host oracle
    E = min(rule_s.shape[1], rule_1.shape[1])  # padding columns may differ
    assert (own_s[:B][ok] == own_1[:B][ok]).all()
    assert (rule_s[:B, :E][ok] == rule_1[:B, :E][ok]).all()
    assert (skip_s[:B, :E][ok] == skip_1[:B, :E][ok]).all()


def test_engine_serves_from_sharded_snapshot():
    """PolicyEngine auto-detects the multi-device mesh, compiles the corpus
    as a ShardedPolicyModel (non-default members_k plumbed through), and the
    batched submit path returns oracle-exact rule/skipped."""
    import asyncio

    from authorino_tpu.expressions import All, Any_, Operator, Pattern
    from authorino_tpu.runtime import EngineEntry, PolicyEngine

    engine = PolicyEngine(max_batch=4, members_k=4)
    entries = []
    exprs = {}
    for i in range(6):
        rule = All(
            Pattern("request.method", Operator.EQ, "GET"),
            Any_(
                Pattern("auth.identity.roles", Operator.INCL, f"r{i}"),
                Pattern("request.url_path", Operator.MATCHES, rf"^/pub-{i}/"),
            ),
        )
        exprs[f"ns/cfg-{i}"] = rule
        entries.append(
            EngineEntry(
                id=f"ns/cfg-{i}",
                hosts=[f"svc-{i}.example.com"],
                runtime=None,
                rules=ConfigRules(name=f"ns/cfg-{i}", evaluators=[(None, rule)]),
            )
        )
    engine.apply_snapshot(entries)
    assert engine._snapshot.sharded is not None  # 8 virtual devices → sharded
    # the base K is plumbed through; shards compile at the grid-relief K
    # (mp shards → ~mp× larger compact membership grid, capped)
    sharded = engine._snapshot.sharded
    assert sharded.members_k == 4
    assert sharded.shards[0].members_k == sharded.members_k_eff
    assert sharded.members_k_eff == 4 * sharded.n_shards

    docs = [
        {"request": {"method": "GET", "url_path": "/pub-2/x"},
         "auth": {"identity": {"roles": ["nope"]}}},
        {"request": {"method": "GET", "url_path": "/priv"},
         "auth": {"identity": {"roles": ["r3", "other"]}}},
        {"request": {"method": "POST", "url_path": "/pub-4/x"},
         "auth": {"identity": {"roles": ["r4"]}}},
        # membership overflow vs members_k=4 → host-fallback lane
        {"request": {"method": "GET", "url_path": "/priv"},
         "auth": {"identity": {"roles": [f"x{k}" for k in range(9)] + ["r5"]}}},
    ]
    names = ["ns/cfg-2", "ns/cfg-3", "ns/cfg-4", "ns/cfg-5"]

    async def run():
        return await asyncio.gather(*[engine.submit(d, n) for d, n in zip(docs, names)])

    results = asyncio.new_event_loop().run_until_complete(run())
    got = [bool(rule[0]) for rule, _ in results]
    expected = [bool(exprs[n].matches(d)) for d, n in zip(docs, names)]
    assert got == expected == [True, True, False, True]


class TestServingPathBitParity:
    """The mesh serving path and the single-corpus serving
    path must produce IDENTICAL per-evaluator (rule, skipped) bits on a
    corpus that exercises all three lanes — device-DFA regex rows (incl.
    byte-tensor overflow), membership overflow (host-fallback lane), and
    compiled evaluator conditions — across dp=1,2,4 mesh shapes."""

    K = 4  # small members_k so overflow is easy to trigger

    def corpus(self):
        from authorino_tpu.expressions import All, Any_, Operator, Pattern

        rx = Pattern("request.url_path", Operator.MATCHES, r"^/api/v[0-9]+/ok")
        cond = Pattern("request.method", Operator.EQ, "GET")
        gated = Pattern("request.path", Operator.EQ, "/gated")
        mem = All(Pattern("auth.identity.roles", Operator.INCL, "admin"),
                  Pattern("auth.identity.groups", Operator.EXCL, "banned"))
        mix = Any_(rx, Pattern("auth.identity.roles", Operator.INCL, "root"))
        return {
            "cfg-rx": ConfigRules(name="cfg-rx", evaluators=[(None, rx), (cond, gated)]),
            "cfg-mem": ConfigRules(name="cfg-mem", evaluators=[(None, mem)]),
            "cfg-mix": ConfigRules(name="cfg-mix", evaluators=[(cond, mix)]),
        }

    def docs(self):
        long_ok = "/api/v3/ok" + "x" * 120     # > DFA_VALUE_BYTES → byte overflow
        long_no = "/nope/" + "y" * 120
        many = [f"r{k}" for k in range(9)]     # > members_k → host fallback
        return [
            ({"request": {"url_path": "/api/v1/ok", "method": "GET", "path": "/gated"},
              "auth": {"identity": {}}}, "cfg-rx"),
            ({"request": {"url_path": "/api/x", "method": "POST", "path": "/other"},
              "auth": {"identity": {}}}, "cfg-rx"),
            ({"request": {"url_path": long_ok, "method": "GET", "path": "/other"},
              "auth": {"identity": {}}}, "cfg-rx"),
            ({"request": {"url_path": long_no, "method": "POST", "path": "/gated"},
              "auth": {"identity": {}}}, "cfg-rx"),
            ({"request": {}, "auth": {"identity": {"roles": many + ["admin"], "groups": []}}},
             "cfg-mem"),
            ({"request": {}, "auth": {"identity": {"roles": many, "groups": ["banned"]}}},
             "cfg-mem"),
            ({"request": {}, "auth": {"identity": {"roles": ["admin"], "groups": []}}},
             "cfg-mem"),
            ({"request": {"url_path": "/api/v9/ok", "method": "GET"},
              "auth": {"identity": {"roles": many}}}, "cfg-mix"),
            ({"request": {"url_path": "/zzz", "method": "POST"},
              "auth": {"identity": {"roles": many + ["root"]}}}, "cfg-mix"),
        ]

    @pytest.mark.parametrize("dp", [1, 2, 4])
    def test_bit_parity(self, dp):
        import asyncio

        from authorino_tpu.runtime import EngineEntry, PolicyEngine

        corpus = self.corpus()

        def engine_for(mesh):
            e = PolicyEngine(max_batch=16, members_k=self.K,
                             mesh=mesh)
            e.apply_snapshot([EngineEntry(id=n, hosts=[n], runtime=None, rules=c)
                              for n, c in corpus.items()])
            return e

        single = engine_for(None)
        sharded = engine_for(build_mesh(n_devices=8, dp=dp))
        assert sharded._snapshot.sharded is not None  # really on the mesh
        assert single._snapshot.policy is not None

        async def collect(engine):
            outs = await asyncio.gather(
                *(engine.submit(doc, name) for doc, name in self.docs()))
            return [(tuple(map(bool, r)), tuple(map(bool, s))) for r, s in outs]

        got_sharded = asyncio.run(collect(sharded))
        got_single = asyncio.run(collect(single))
        assert got_sharded == got_single

        # both agree with the expression oracle per evaluator slot
        for (doc, name), (rule_bits, skip_bits) in zip(self.docs(), got_single):
            evs = corpus[name].evaluators
            for e, (cond, rule) in enumerate(evs):
                want_skip = cond is not None and not cond.matches(doc)
                assert skip_bits[e] == want_skip, (name, e)
                if not want_skip:
                    assert rule_bits[e] == rule.matches(doc), (name, e)
