"""Differential test: matmul (MXU) lane vs gather lane of the evaluation
kernel — same compiled corpus, same encoded batches, bit-identical outputs.

The gather lane is the semantic reference (ops/pattern_eval.py module doc);
the matmul lane is the default serving lane.  A bf16 variant runs only where
the backend has MXU-style bf16 dot support (skipped on CPU CI, exercised on
real TPU runs)."""

import os
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from authorino_tpu.compiler import ConfigRules, compile_corpus
from authorino_tpu.compiler.encode import encode_batch_py
from authorino_tpu.compiler.pack import pack_batch
from authorino_tpu.expressions import All, Any_, Operator, Pattern
from authorino_tpu.ops import pattern_eval as pe

from test_own_config_eval import _operands, all_operand_corpus, all_operand_docs


def _mixed_corpus(n_configs=23, seed=5):
    rng = random.Random(seed)
    configs = []
    for i in range(n_configs):
        pats = [
            Pattern("request.method", Operator.EQ, rng.choice(["GET", "POST"])),
            Pattern("auth.identity.org", Operator.NEQ, f"org-{i % 7}"),
            Pattern("auth.identity.roles", Operator.INCL, f"role-{i % 5}"),
            Pattern("auth.identity.groups", Operator.EXCL, f"banned-{i % 3}"),
            Pattern("request.url_path", Operator.MATCHES, rf"^/svc-{i % 4}/"),
        ]
        rule = All(pats[0], Any_(*pats[1:]))
        cond = Pattern("request.headers.x-env", Operator.NEQ, "dev") if i % 2 else None
        configs.append(ConfigRules(name=f"cfg-{i}", evaluators=[(cond, rule)]))
    return configs


def _docs(n, seed=11):
    rng = random.Random(seed)
    docs = []
    for _ in range(n):
        docs.append(
            {
                "request": {
                    "method": rng.choice(["GET", "POST", "PUT"]),
                    "url_path": rng.choice(["/svc-0/a", "/svc-1/b", "/other", "/svc-3/"]),
                    "headers": {"x-env": rng.choice(["dev", "prod"])},
                },
                "auth": {
                    "identity": {
                        "org": f"org-{rng.randrange(9)}",
                        "roles": [f"role-{rng.randrange(7)}" for _ in range(rng.randrange(0, 20))],
                        "groups": [f"banned-{rng.randrange(5)}" for _ in range(rng.randrange(0, 3))],
                    }
                },
            }
        )
    return docs


def _both_lane_params(policy):
    params_mm = pe.to_device(policy, lane="matmul", dense=True)
    params_g = pe.to_device(policy, lane="gather", dense=True)
    assert "rule_m" in params_mm["matmul"]
    assert params_g["matmul"] is None
    return params_mm, params_g


def test_matmul_lane_matches_gather_lane():
    policy = compile_corpus(_mixed_corpus(), members_k=4)
    params_mm, params_g = _both_lane_params(policy)
    docs = _docs(64)
    rows = [i % policy.n_configs for i in range(len(docs))]
    db = pack_batch(policy, encode_batch_py(policy, docs, rows, batch_pad=64))
    args = (
        jnp.asarray(db.attrs_val),
        jnp.asarray(db.members_c),
        jnp.asarray(db.cpu_dense),
        jnp.asarray(db.config_id),
        jnp.asarray(db.attr_bytes),
        jnp.asarray(db.byte_ovf),
    )
    v_mm, (r_mm, s_mm) = pe.eval_verdicts(params_mm, *args)
    v_g, (r_g, s_g) = pe.eval_verdicts(params_g, *args)
    np.testing.assert_array_equal(np.asarray(v_mm), np.asarray(v_g))
    np.testing.assert_array_equal(np.asarray(r_mm), np.asarray(r_g))
    np.testing.assert_array_equal(np.asarray(s_mm), np.asarray(s_g))


def test_matmul_lane_bf16_matches_gather_lane():
    """bf16 operand numerics (the real TPU configuration)."""
    if jax.default_backend() == "cpu":
        pytest.skip("CPU dot kernels lack BF16xBF16->F32")
    policy = compile_corpus(_mixed_corpus(31), members_k=4)
    params_mm, params_g = _both_lane_params(policy)
    assert params_mm["matmul"]["rule_m"].dtype == jnp.bfloat16
    docs = _docs(128, seed=17)
    rows = [i % policy.n_configs for i in range(len(docs))]
    db = pack_batch(policy, encode_batch_py(policy, docs, rows, batch_pad=128))
    args = (
        jnp.asarray(db.attrs_val),
        jnp.asarray(db.members_c),
        jnp.asarray(db.cpu_dense),
        jnp.asarray(db.config_id),
        jnp.asarray(db.attr_bytes),
        jnp.asarray(db.byte_ovf),
    )
    v_mm, _ = pe.eval_verdicts(params_mm, *args)
    v_g, _ = pe.eval_verdicts(params_g, *args)
    np.testing.assert_array_equal(np.asarray(v_mm), np.asarray(v_g))


@pytest.mark.parametrize("lane", ["matmul", "gather"])
def test_bitpacked_readback_roundtrips_both_lanes(lane):
    """The packed u8 bitmask readback (8 verdicts/byte, little bit order)
    must round-trip exactly against the unpacked [B, 1+2E] verdict arrays
    on BOTH the matmul and gather lanes — the D2H compression can never
    change an answer."""
    policy = compile_corpus(_mixed_corpus(), members_k=4)
    params = pe.to_device(policy, lane=lane)
    docs = _docs(64)
    rows = [i % policy.n_configs for i in range(len(docs))]
    db = pack_batch(policy, encode_batch_py(policy, docs, rows, batch_pad=64))
    args = (
        jnp.asarray(db.attrs_val),
        jnp.asarray(db.members_c),
        jnp.asarray(db.cpu_dense),
        jnp.asarray(db.config_id),
        jnp.asarray(db.attr_bytes),
        jnp.asarray(db.byte_ovf),
    )
    E = int(policy.eval_rule.shape[1])
    cols = 1 + 2 * E
    reference = np.asarray(pe.eval_packed_jit(params, *args))
    packed = np.asarray(pe.eval_bitpacked_jit(params, *args))
    assert packed.dtype == np.uint8
    assert packed.shape == (reference.shape[0], pe.packed_width(cols))
    np.testing.assert_array_equal(pe.unpack_verdicts(packed, cols), reference)
    # bits past the verdict columns are zero padding (byte-stable wire)
    tail_bits = pe.packed_width(cols) * 8 - cols
    if tail_bits:
        full = np.unpackbits(packed, axis=1, bitorder="little")
        assert not full[:, cols:].any()


def test_interner_overflow_falls_back_to_gather(monkeypatch):
    policy = compile_corpus(_mixed_corpus(5), members_k=4)
    monkeypatch.setattr(pe, "_F32_EXACT", len(policy.interner))
    params = pe.to_device(policy)
    assert params["matmul"] is None  # ids no longer exact in f32
    assert pe.kernel_lane_of(params) == "gather"


def test_environment_cannot_select_a_kernel_body(monkeypatch):
    """Which body evaluates a batch is decided by ``to_device`` from its
    input alone: the variables that once named a body change nothing."""
    policy = compile_corpus(_mixed_corpus(5), members_k=4)
    plain = pe.to_device(policy, host=True)
    # the retired names, spelled in parts: a search for them finds no use
    for knob, value in (("KERNEL", "fused"), ("EVAL", "gather")):
        monkeypatch.setenv(f"AUTHORINO_TPU_{knob}_LANE", value)
    params = pe.to_device(policy, host=True)
    assert jax.tree.structure(params) == jax.tree.structure(plain)
    assert pe.kernel_lane_of(params) == pe.kernel_lane_of(plain) == "matmul"
    assert "fused" not in params and params["classes"][0]["own"] is not None


# ---------------------------------------------------------------------------
# own-row DFA scan (ISSUE 26): the entries that return own-config results
# scan config_dfa_rows[config_id], not the corpus's DFA rows (and since
# ISSUE 28 evaluate the own leaves and circuit only: test_own_config_eval.py)
# ---------------------------------------------------------------------------

_RX = Operator.MATCHES


def _tenant_like(i, extra=()):
    """The benchmark's tenant_rules shape: two per-config regexes among
    eq / neq / incl / excl leaves under one All."""
    return All(
        Pattern("request.method", Operator.NEQ, "DELETE"),
        Pattern("request.url_path", _RX, rf"^/api/v[0-9]+/t{i}/[a-z0-9/_-]*$"),
        Pattern("request.headers.x-request-id", _RX, rf"^r{i}-[0-9a-f]{{8}}$"),
        Pattern("auth.identity.roles", Operator.INCL, f"role-{i % 5}"),
        Pattern("auth.identity.groups", Operator.EXCL, f"banned-{i}"),
        Pattern("request.headers.x-org", Operator.EQ, f"org-{i}"),
        *extra)


def _tenant_doc(i, rng, deny=None):
    """A request config ``i`` allows; ``deny`` breaks one rule of it."""
    doc = {
        "request": {
            "method": "GET",
            "url_path": f"/api/v{rng.randrange(1, 10)}/t{i}/items/{rng.randrange(10**6)}",
            "headers": {"x-request-id": f"r{i}-{rng.getrandbits(32):08x}",
                        "x-org": f"org-{i}", "x-env": "prod"},
        },
        "auth": {"identity": {"roles": [f"role-{i % 5}"], "groups": ["ok"]}},
    }
    if deny == "path":
        doc["request"]["url_path"] = f"/api/v1/t{i + 1}/items/1"
    elif deny == "rid":
        doc["request"]["headers"]["x-request-id"] += "Z"
    elif deny == "org":
        doc["request"]["headers"]["x-org"] = "nobody"
    elif deny == "long":
        # past the widest byte lane a size class takes (256): the regex is
        # answered from the CPU lane
        doc["request"]["url_path"] = f"/api/v1/t{i}/" + "a" * 280
    elif deny == "long-bad":
        doc["request"]["url_path"] = f"/api/v1/t{i}/" + "a" * 280 + "!"
    return doc


def _own_case(case):
    """(configs, docs, rows, config_id overrides, batch_pad, targets?) for
    one shape of corpus the own-row scan must not get wrong."""
    rng = random.Random(26)
    n = 12
    denies = [None, "path", "rid", "org"]
    cfgs = [ConfigRules(name=f"t-{i}", evaluators=[(None, _tenant_like(i))])
            for i in range(n)]
    docs = [_tenant_doc(i % n, rng, denies[(i // n) % 4]) for i in range(4 * n)]
    rows = [i % n for i in range(4 * n)]
    cid, pad, targets = {}, 64, False
    if case == "shared-leaf":
        # one regex leaf (deduplicated by the lowerer) under an `any` in a
        # third of the configs: the table is per config, not a partition
        shared = Pattern("request.url_path", _RX, r"^/shared/")
        for i in range(0, n, 3):
            cfgs[i] = ConfigRules(name=f"t-{i}", evaluators=[
                (None, Any_(shared, _tenant_like(i)))])
        docs += [{"request": {"method": "GET", "url_path": "/shared/x",
                              "headers": {}}, "auth": {"identity": {}}}] * 4
        rows += [0, 3, 1, 2]  # 0, 3 allow through the shared leaf; 1, 2 deny
    elif case == "no-regex-config":
        cfgs[5] = ConfigRules(name="t-5", evaluators=[
            (None, Pattern("request.headers.x-org", Operator.EQ, "org-5"))])
    elif case == "one-heavy-config":
        many = [Pattern("request.url_path", _RX, rf"^/api/v[0-9]+/t7/i{{1,{v + 1}}}tems")
                for v in range(11)]
        cfgs[7] = ConfigRules(name="t-7", evaluators=[
            (None, _tenant_like(7, extra=many))])
    elif case == "regex-guards-allow":
        # a regex in a `when` (false condition = evaluator skipped = allow)
        # and inside an `any`: with the own row missing, the regex would
        # read False and turn these denies into allows
        for i in range(0, n, 2):
            cfgs[i] = ConfigRules(name=f"t-{i}", evaluators=[
                (Pattern("request.url_path", _RX, rf"^/api/v[0-9]+/t{i}/"),
                 Pattern("request.headers.x-org", Operator.EQ, "nobody")),
                (None, Any_(Pattern("request.headers.x-org", Operator.EQ, "nobody"),
                            Pattern("request.url_path", _RX, r"^/api/")))])
    elif case == "byte-overflow":
        docs = [_tenant_doc(i % n, rng, ["long", "long-bad", None][i % 3])
                for i in range(3 * n)]
        rows = [i % n for i in range(3 * n)]
    elif case == "shape-targets":
        targets = True
    elif case == "config-id-out-of-range":
        cid = {0: -1, 1: n, 2: 10**6, 3: -(10**6)}
        pad = 128  # pad rows: config 0, no bytes
    return cfgs, docs, rows, cid, pad, targets


@pytest.mark.parametrize("lane", ["matmul", "gather"])
@pytest.mark.parametrize("case", [
    "tenant-rules", "shared-leaf", "no-regex-config", "one-heavy-config",
    "regex-guards-allow", "byte-overflow", "shape-targets",
    "config-id-out-of-range"])
def test_own_row_scan_equals_dense_and_oracle(case, lane):
    """eval_full_jit (own config only) against the dense body's results
    selected by config, bit for bit, and against the host expression oracle."""
    cfgs, docs, rows, cid_over, pad, targets = _own_case(case)
    policy = compile_corpus(cfgs, members_k=4)
    natural_d = policy.config_dfa_rows.shape[1]
    if targets:
        from authorino_tpu.compiler.compile import ShapeTargets
        other = compile_corpus(_mixed_corpus(29), members_k=4)
        wide = ShapeTargets.union([policy.shape_targets(), other.shape_targets()])
        wide.n_own_dfa_rows = natural_d + 3
        wide.n_dfa_rows += 5
        policy = compile_corpus(cfgs, members_k=4, targets=wide)
        assert policy.config_dfa_rows.shape == (wide.n_configs, natural_d + 3)
    if case == "one-heavy-config":
        assert natural_d >= 12 and (policy.config_dfa_rows[0] >= 0).sum() == 2
    if case == "shared-leaf":
        shared_rows = [r for r in policy.config_dfa_rows[0] if r >= 0
                       and r in policy.config_dfa_rows[3]]
        assert len(shared_rows) == 1
    params = pe.to_device(policy, lane=lane)
    dense = pe.to_device(policy, lane=lane, dense=True)
    db = pack_batch(policy, encode_batch_py(policy, docs, rows, batch_pad=pad))
    if case == "byte-overflow":
        assert np.asarray(db.byte_ovf)[: len(docs)].any()
    config_id = np.asarray(db.config_id).copy()
    for i, v in cid_over.items():
        config_id[i] = v
    head = (jnp.asarray(db.attrs_val), jnp.asarray(db.members_c),
            jnp.asarray(db.cpu_dense))
    tail = (jnp.asarray(db.attr_bytes), jnp.asarray(db.byte_ovf))
    own, own_rule, own_skipped = (np.asarray(x) for x in pe.eval_full_jit(
        params, *head, jnp.asarray(config_id), *tail))
    verdict, (rule, skipped) = pe.eval_verdicts(
        dense, *head, jnp.asarray(config_id), *tail)
    mask = config_id[:, None] == np.arange(policy.n_configs)[None, :]
    np.testing.assert_array_equal(own, (np.asarray(verdict) & mask).any(axis=1))
    np.testing.assert_array_equal(
        own_rule, (np.asarray(rule) & mask[:, :, None]).any(axis=1))
    np.testing.assert_array_equal(
        own_skipped, (np.asarray(skipped) & mask[:, :, None]).any(axis=1))
    # a config id that names no config owns nothing: every own column false
    for i in cid_over:
        assert not own[i] and not own_rule[i].any() and not own_skipped[i].any()
    assert not np.asarray(db.host_fallback).any()
    n_allow = 0
    for i, (doc, row) in enumerate(zip(docs, rows)):
        if i in cid_over:
            continue
        want = all(rule_.matches(doc) for cond, rule_ in policy.config_exprs[row]
                   if cond is None or cond.matches(doc))
        assert bool(own[i]) == want, (case, lane, i)
        n_allow += want
    assert 0 < n_allow < len(docs) - len(cid_over)  # both answers occur


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for j in (v if isinstance(v, (tuple, list)) else (v,)):
            if hasattr(j, "jaxpr"):
                j = j.jaxpr
            if hasattr(j, "eqns"):
                yield j


def _walk_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from _walk_eqns(sub)


@pytest.mark.parametrize("corpus", ["tenant-rules", "all-operand-lanes"])
def test_served_entry_holds_no_dense_dfa_intermediate(corpus):
    """Structure of the served entry for a corpus with R >> D: nothing of
    B x R x LB (the spread bytes) or B x R x 256 (the byte one-hot) elements
    is built, and the one scan carries [D, B] (the batch on the minor axis,
    ISSUE 32) — so a later edit cannot fall
    back to the dense scan unseen, with every operand lane present or not."""
    n, B = 23, 16
    rng = random.Random(3)
    if corpus == "tenant-rules":
        policy = compile_corpus(
            [ConfigRules(name=f"t-{i}", evaluators=[(None, _tenant_like(i))])
             for i in range(n)], members_k=4)
        docs = [_tenant_doc(i % n, rng) for i in range(B)]
        want = (2 * n, 2)
    else:
        policy = compile_corpus(all_operand_corpus(rng, n_configs=n),
                                members_k=4, ovf_assist=True)
        docs = all_operand_docs(rng, n=B)
        want = (3, 1)  # three distinct regexes, one a config
    R, D = policy.dfa_table_of_row.shape[0], policy.config_dfa_rows.shape[1]
    assert (R, D) == want
    db = pack_batch(policy, encode_batch_py(
        policy, docs, [i % n for i in range(B)], batch_pad=B))
    LB = db.attr_bytes.shape[2]
    for lane in ("matmul", "gather"):
        params = pe.to_device(policy, lane=lane)
        jaxpr = jax.make_jaxpr(pe.eval_bitpacked_jit)(params, *_operands(db))
        scans = []
        for eqn in _walk_eqns(jaxpr.jaxpr):
            for v in eqn.outvars:
                shape = tuple(getattr(v.aval, "shape", ()))
                assert not (R in shape and np.prod(shape) >= B * R * min(LB, 256)), \
                    (lane, eqn.primitive.name, shape)
            if eqn.primitive.name == "scan":
                nc, k = eqn.params["num_consts"], eqn.params["num_carry"]
                scans.append([tuple(v.aval.shape) for v in eqn.invars[nc:nc + k]])
        assert scans == [[(D, B)]], (lane, scans)
