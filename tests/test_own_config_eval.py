"""The served entry evaluates the request's OWN config (ISSUE 28): own leaves,
own circuit, own evaluators, gathered from per-config tables by config_id.

Held here, on seeded random corpora at small sizes: the served entry
(``eval_bitpacked_jit``) equals the dense body's results selected by config,
bit for bit, and equals the host expression oracle; its operands grow with
the number of configs and not with configs x leaves; the row payload's CPU
columns are the own config's, the same bytes from the native encoder and
the Python one; a one-config change is still a rows delta."""

import os
import random
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from authorino_tpu.analysis.tensor_lint import tensor_lint
from authorino_tpu.compiler import ConfigRules, compile_corpus
from authorino_tpu.compiler.encode import encode_batch_py
from authorino_tpu.compiler.pack import pack_batch
from authorino_tpu.expressions import All, Any_, InGroup, Operator, Pattern
from authorino_tpu.models.policy_model import host_results
from authorino_tpu.ops import pattern_eval as pe
from authorino_tpu.relations.closure import RelationClosure
from authorino_tpu.snapshots.diff import plan_delta

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "benchmark"))
from corpora import tenant_rules  # noqa: E402

K = 4  # members_k: role lists longer than this overflow


# ---------------------------------------------------------------------------
# seeded random corpora
# ---------------------------------------------------------------------------

def _leaf(rng, i, kinds):
    kind = rng.choice(kinds)
    if kind == "eq":
        return Pattern(f"request.headers.x-h{rng.randrange(4)}",
                       rng.choice([Operator.EQ, Operator.NEQ]),
                       f"v{rng.randrange(3)}")
    if kind == "memb":
        return Pattern(rng.choice(["auth.identity.roles", "auth.identity.groups"]),
                       rng.choice([Operator.INCL, Operator.EXCL]),
                       f"r{rng.randrange(6)}")
    if kind == "regex":
        return Pattern(rng.choice(["request.url_path", "request.headers.x-id"]),
                       Operator.MATCHES,
                       rng.choice([rf"^/t{i % 5}/[a-z0-9/]*$", r"^/[a-z]+/x", rf"^id-{i % 3}-"]))
    return Pattern("auth.identity.age",
                   rng.choice([Operator.GT, Operator.GE, Operator.LT, Operator.LE]),
                   str(rng.randrange(10, 60)))


def _tree(rng, i, kinds, depth):
    if depth == 0 or rng.random() < 0.3:
        return _leaf(rng, i, kinds)
    node = rng.choice([All, Any_])
    return node(*[_tree(rng, i, kinds, depth - 1)
                  for _ in range(rng.randrange(2, 4))])


# one tree object referenced from many configs, as a named pattern is after
# the translation resolved its patternRef
_NAMED = All(Pattern("request.method", Operator.NEQ, "DELETE"),
             Any_(Pattern("request.url_path", Operator.MATCHES, r"^/named/"),
                  Pattern("auth.identity.roles", Operator.INCL, "r0")))


def _corpus(case, seed):
    rng = random.Random(seed)
    kinds = {"no-regex-corpus": ["eq", "memb"],
             # the C++ encoder predates the numeric lane
             "no-numeric": ["eq", "eq", "memb", "regex"],
             "numeric": ["eq", "num", "num", "regex"]}.get(
                 case, ["eq", "eq", "memb", "regex", "num"])
    n = 1 if case == "one-config" else rng.randrange(9, 20)
    cfgs = []
    for i in range(n):
        evaluators = []
        for _ in range(rng.randrange(1, 4)):
            cond = _tree(rng, i, kinds, 1) if rng.random() < 0.5 else None
            evaluators.append((cond, _tree(rng, i, kinds, 3)))
        if i % 4 == 1:
            evaluators.append((None, _NAMED))
        cfgs.append(ConfigRules(name=f"c-{i}", evaluators=evaluators))
    if n > 2:
        # unequal leaf counts: one config of a single eq leaf (no regex), one
        # far larger than the rest (the tables pad to it)
        cfgs[0] = ConfigRules(name="c-0", evaluators=[
            (None, Pattern("request.headers.x-h0", Operator.EQ, "v0"))])
        cfgs[2] = ConfigRules(name="c-2", evaluators=[
            (None, All(*[_leaf(rng, 2, kinds) for _ in range(25)]))])
    return cfgs


def _doc(rng):
    path = rng.choice(["/t0/a", "/t1/b/c", "/t3/", "/named/x", "/abc/x", "/T"])
    if rng.random() < 0.25:
        # past the widest byte lane a size class takes (256): regexes on
        # it take the CPU-lane answer
        path += "a" * 280 + rng.choice(["", "!"])
    return {
        "request": {
            "method": rng.choice(["GET", "DELETE"]),
            "url_path": path,
            "headers": dict({f"x-h{j}": f"v{rng.randrange(3)}" for j in range(4)},
                            **{"x-id": f"id-{rng.randrange(3)}-{rng.randrange(99)}"}),
        },
        "auth": {"identity": {
            # lists longer than K overflow the membership vector
            "roles": [f"r{rng.randrange(8)}" for _ in range(rng.randrange(0, 8))],
            "groups": [f"r{rng.randrange(8)}" for _ in range(rng.randrange(0, 3))],
            "age": rng.choice([5, 17, 18, 35, 59, 60, "n/a"]),
        }},
    }


def all_operand_corpus(rng: random.Random, n_configs=6):
    """Every operand lane in one circuit: relations (deep chain), numeric
    compares, membership (overflow-capable at K=4), eq, device-DFA regex rows
    (two distinct tables) and one CPU-regex config (backreference: outside
    the DFA subset).  Shared with the mesh, lane and staging-buffer tests."""
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


def all_operand_docs(rng: random.Random, n=48):
    ents = [f"d{i}" for i in range(7)] + ["u", "left", "mid", "top",
                                          "stranger"]
    return [{
        "req": {"n": rng.choice([-10, 0, 3, 29, 30, "x", None]),
                "m": rng.choice(["GET", "POST", "PUT"]),
                # the long path exceeds every class's width -> byte overflow
                "path": rng.choice(["/svc-0/a", "/svc-1/b", "/zzz",
                                    "/svc-2/" + "x" * 300]),
                "q": rng.choice(["aaaa", "aaa", "ab"])},
        "auth": {"identity": {
            "sub": rng.choice(ents),
            # K + 3 roles overflow the membership vector
            "roles": [f"r{rng.randrange(4)}"
                      for _ in range(rng.choice([1, 2, K + 3]))],
        }},
    } for _ in range(n)]


_ALL_OPERAND = ("all-operand-lanes", "host-fallback")


def _operands(db, config_id=None):
    def opt(a):
        return jnp.asarray(a) if a is not None else None

    cid = db.config_id if config_id is None else config_id
    return (jnp.asarray(db.attrs_val), jnp.asarray(db.members_c),
            jnp.asarray(db.cpu_dense), jnp.asarray(cid),
            opt(db.attr_bytes), opt(db.byte_ovf), opt(db.attrs_num),
            opt(db.num_valid), opt(db.rel_rows), opt(db.member_ovf))


def _dense_own(policy, dense_params, operands):
    """[B, 1+2E]: the dense body's results, row config_id selected."""
    verdict, (rule, skipped) = pe.eval_verdicts(dense_params, *operands)
    cid = np.asarray(operands[3])
    mask = cid[:, None] == np.arange(policy.n_configs)[None, :]
    return np.concatenate([
        (np.asarray(verdict) & mask).any(axis=1)[:, None],
        (np.asarray(rule) & mask[:, :, None]).any(axis=1),
        (np.asarray(skipped) & mask[:, :, None]).any(axis=1)], axis=1)


@pytest.mark.parametrize("lane", ["matmul", "gather"])
@pytest.mark.parametrize("case,seed", [
    ("random", 1), ("random", 2), ("random", 3), ("numeric", 4),
    ("no-regex-corpus", 5), ("one-config", 6), ("ovf-assist", 7),
    # relation, numeric, membership, regex and CPU-fallback leaves in one
    # circuit, with the overflow assist and (host-fallback) without it
    ("all-operand-lanes", 7), ("all-operand-lanes", 19),
    ("all-operand-lanes", 31), ("host-fallback", 5)])
def test_served_entry_equals_dense_body_and_oracle(case, seed, lane):
    B = 96
    assist = case in ("ovf-assist", "all-operand-lanes")
    if case in _ALL_OPERAND:
        rng = random.Random(seed)
        cfgs = all_operand_corpus(rng)
        docs = all_operand_docs(rng, n=B)
    else:
        cfgs = _corpus(case, seed)
        rng = random.Random(seed + 100)
        docs = [_doc(rng) for _ in range(B)]
    policy = compile_corpus(cfgs, members_k=K, ovf_assist=assist)
    assert tensor_lint(policy) == []
    own = policy.own
    if len(cfgs) > 2 and case not in _ALL_OPERAND:
        counts = (own.leaves >= 0).sum(axis=1)
        assert counts[0] == 1 and counts[2] > 10 and counts.max() == own.leaves.shape[1]
    rows = [rng.randrange(len(cfgs)) for _ in range(B)]
    db = pack_batch(policy, encode_batch_py(policy, docs, rows, batch_pad=128))
    assert db.cpu_dense.shape == (128, policy.n_own_cpu)
    if case != "no-regex-corpus":
        assert np.asarray(db.byte_ovf)[:B].any()
    fallback = np.asarray(db.host_fallback)[:B]
    if case in ("random", "ovf-assist") + _ALL_OPERAND:
        overflowed = np.asarray(
            encode_batch_py(policy, docs, rows).overflow).any(axis=1)
        assert overflowed.any() and fallback.any() != assist

    params = pe.to_device(policy, lane=lane)
    dense = pe.to_device(policy, lane=lane, dense=True)
    E = int(policy.eval_rule.shape[1])
    operands = _operands(db)
    served = pe.unpack_verdicts(pe.eval_bitpacked_jit(params, *operands), 1 + 2 * E)
    np.testing.assert_array_equal(served, _dense_own(policy, dense, operands))
    widths = pe.kernel_widths(params)
    assert widths["leaf_cols_per_row"] == own.leaves.shape[1] < policy.n_leaves

    # a config id that names no config owns nothing, on either body
    cid = np.asarray(db.config_id).copy()
    cid[:4] = [-1, policy.n_configs, 10**6, -(10**6)]
    operands = _operands(db, cid)
    off = pe.unpack_verdicts(pe.eval_bitpacked_jit(params, *operands), 1 + 2 * E)
    np.testing.assert_array_equal(off, _dense_own(policy, dense, operands))
    assert not off[:4].any()

    answers = set()
    firing = pe.firing_columns(served[:, 1:1 + E], served[:, 1 + E:])
    for i, (doc, row) in enumerate(zip(docs, rows)):
        if fallback[i]:
            continue  # membership overflow: the host oracle re-decides the row
        want = all(rule.matches(doc) for cond, rule in policy.config_exprs[row]
                   if cond is None or cond.matches(doc))
        assert bool(served[i, 0]) == want, (case, seed, lane, i)
        answers.add(want)
        # which rule fired, against the oracle the engine re-decides with
        w_own, w_rule, w_skip = host_results(policy, doc, row)
        assert w_own == want
        assert firing[i] == pe.firing_columns(w_rule[None], w_skip[None])[0], (
            case, seed, lane, i)
    assert answers == {True, False} or case == "one-config"


# ---------------------------------------------------------------------------
# size classes (ISSUE 34) on every operand lane
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("lane", ["matmul", "gather"])
@pytest.mark.parametrize("seed", [7, 19])
def test_two_size_classes_equal_dense_body_on_every_operand_lane(seed, lane):
    """The all-operand corpus beside one config of 80 regexes on the same
    path: two size classes.  The whole operands (every class, every row)
    and each class's view over its own rows equal the dense body, relation,
    numeric, membership-overflow, DFA and CPU-regex leaves alike."""
    B = 96
    rng = random.Random(seed)
    cfgs = all_operand_corpus(rng)
    cfgs.append(ConfigRules(name="cfg-big", evaluators=[
        (Pattern("req.m", Operator.EQ, "GET"),
         Any_(*[Pattern("req.path", Operator.MATCHES, rf"^/svc-{i}/[a-z]{{{i % 5}}}")
                for i in range(80)])),
        (None, Pattern("req.n", Operator.GE, "0"))]))
    docs = all_operand_docs(rng, n=B)
    policy = compile_corpus(cfgs, members_k=K, ovf_assist=True)
    assert tensor_lint(policy) == []
    small, big = policy.classes
    assert big.configs.tolist() == [len(cfgs) - 1]
    assert (big.widths()["dfa_rows_per_row"], small.widths()["dfa_rows_per_row"]) == (80, 1)
    assert small.widths()["leaf_cols_per_row"] < big.widths()["leaf_cols_per_row"]
    rows = [rng.randrange(len(cfgs)) for _ in range(B)]
    rows[:8] = [len(cfgs) - 1] * 8
    db = pack_batch(policy, encode_batch_py(policy, docs, rows, batch_pad=128))
    params = pe.to_device(policy, lane=lane)
    dense = pe.to_device(policy, lane=lane, dense=True)
    E = int(policy.eval_rule.shape[1])
    cid = np.asarray(db.config_id).copy()
    cid[-4:] = [-1, policy.n_configs, 10**6, -(10**6)]
    operands = _operands(db, cid)
    want = _dense_own(policy, dense, operands)
    served = pe.unpack_verdicts(pe.eval_bitpacked_jit(params, *operands), 1 + 2 * E)
    np.testing.assert_array_equal(served, want)
    assert served[:B, 0].any() and not served[:B, 0].all()
    for c, cls in enumerate(policy.classes):
        # the class's view answers its members' rows and reads False on the rest
        E_c = cls.own.evals.shape[2]
        got = pe.unpack_verdicts(pe.eval_bitpacked_jit(
            pe.class_view(params, c), *operands), 1 + 2 * E_c)
        mine = np.isin(cid, cls.configs)
        np.testing.assert_array_equal(got[mine, :1 + E_c], want[mine, :1 + E_c])
        np.testing.assert_array_equal(got[mine, 1 + E_c:], want[mine, 1 + E:1 + E + E_c])
        assert not got[~mine].any()


# ---------------------------------------------------------------------------
# tenant_rules: operands linear in the configs, ten leaf columns a row
# ---------------------------------------------------------------------------

def _tenants(n, changed=None):
    cfgs = []
    for i in range(n):
        pats = tenant_rules._patterns(i)
        if i == changed:
            pats[5]["operator"] = "neq"  # x-org: eq -> neq
        cfgs.append(ConfigRules(name=f"cfg-{i:05d}", evaluators=[(None, All(*[
            Pattern(p["selector"], Operator(p["operator"]), p["value"])
            for p in pats]))]))
    return cfgs


@pytest.mark.parametrize("lane", ["matmul", "gather"])
def test_tenant_rules_operands_grow_with_configs_not_configs_times_leaves(lane):
    sizes = {}
    for n in (64, 128):
        policy = compile_corpus(_tenants(n), members_k=16)
        view = pe.to_device(policy, host=True, lane=lane)
        sizes[n] = pe.operand_bytes(view)
        widths = pe.kernel_widths(view)
        # one size: one class, whose widths are the corpus's
        (only,) = widths.pop("classes")
        assert widths == {
            "leaf_cols_per_row": 10, "dfa_rows_per_row": 2,
            "dfa_rows_total": 2 * n, "dfa_states": 16}
        assert only == {"configs": n, "evaluators": 2,
                        "operand_bytes": pe.operand_bytes(view["classes"]),
                        **{k: widths[k] for k in (
                            "leaf_cols_per_row", "dfa_rows_per_row", "dfa_states")}}
        assert policy.n_own_cpu == 2  # the two regexes' overflow columns
    assert sizes[128] <= 2.2 * sizes[64]
    if lane == "matmul":
        # what the dense body's one-hot operands cost on top: G x L
        dense = {n: pe.operand_bytes(pe.to_device(
            compile_corpus(_tenants(n), members_k=16), host=True, lane=lane,
            dense=True)) - sizes[n] for n in (64, 128)}
        assert dense[128] > 2.5 * dense[64] and dense[128] > 4 * sizes[128]


def test_one_config_change_is_a_rows_delta_on_the_own_layout():
    old = pe.to_device(compile_corpus(_tenants(64), members_k=16), host=True)
    new = pe.to_device(compile_corpus(_tenants(64, changed=17), members_k=16),
                       host=True)
    plan = plan_delta(old, new)
    assert plan is not None and plan.mode == "delta"
    touched = {e.name: e for e in plan.entries if e.mode != "reuse"}
    # the changed leaf's row and config 17's row of its class's own tables
    assert set(touched) == {"leaf_op", "classes.0.own.leaf"}
    assert all(e.mode == "rows" for e in touched.values())
    assert touched["classes.0.own.leaf"].rows.tolist() == [17]
    assert plan.upload_bytes * 50 < plan.full_bytes


# ---------------------------------------------------------------------------
# the row payload's CPU columns: native encoder == Python encoder
# ---------------------------------------------------------------------------

def test_native_encoder_own_cpu_columns_equal_python_encoder_bytes():
    from authorino_tpu.native import get_native_encoder, load_library

    if load_library() is None:
        pytest.skip("native encoder unavailable")
    policy = compile_corpus(_corpus("no-numeric", 11), members_k=K)
    nat = get_native_encoder(policy)
    assert nat is not None
    rng = random.Random(12)
    docs = [_doc(rng) for _ in range(64)]
    rows = [rng.randrange(policy.n_configs) for _ in range(64)]
    py = pack_batch(policy, encode_batch_py(policy, docs, rows, batch_pad=64))
    enc = nat.encode_batch(docs, rows, batch_pad=64)
    assert enc is not None, "native encoder bailed"
    cc = pack_batch(policy, enc)
    assert py.cpu_dense.shape == (64, policy.n_own_cpu) and py.cpu_dense.any()
    assert cc.cpu_dense.tobytes() == py.cpu_dense.tobytes()
    assert pe.fuse_batch(cc)[0].tobytes() == pe.fuse_batch(py)[0].tobytes()
    # column j of a row is the answer of its config's j-th CPU-lane leaf
    wide = encode_batch_py(policy, docs, rows, batch_pad=64).cpu_lane
    for b, g in enumerate(rows):
        for j, leaf in enumerate(policy.own.cpu_leaves[g]):
            assert py.cpu_dense[b, j] == (leaf >= 0 and wide[b, leaf])
