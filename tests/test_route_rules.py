"""The configuration `routes-1k` (ISSUE 32) at a small size on the CPU: its
generator, the benchmark's plain reference, the program's host expression
oracle (with the evaluator `when`) and the served entry agree on every row,
and the rows hold every kind the generator promises; the regexes compile to
DFAs under MAX_STATES that agree with Python's `re`; the served widths are
18 DFA rows of 72 states and 40 leaves, the operands grow linearly with the
configs; the native encoder and the Python one stage the same bytes for rows
whose path overflowed; and the served scan equals the gather reference,
verdict and attribution bits, here and on the all-operand corpus."""

import asyncio
import os
import random
import re
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from authorino_tpu.authjson import (CheckRequestModel, HttpRequestAttributes,
                                    build_authorization_json)
from authorino_tpu.compiler import compile_corpus
from authorino_tpu.compiler.compile import DFA_VALUE_BYTES
from authorino_tpu.compiler.encode import encode_batch_py
from authorino_tpu.compiler.pack import pack_batch
from authorino_tpu.compiler.redfa import MAX_STATES, compile_regex_dfa
from authorino_tpu.controllers.translate import translate_auth_config
from authorino_tpu.models.policy_model import host_results
from authorino_tpu.ops import pattern_eval as pe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.join(ROOT, "benchmark"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from corpora import route_rules as rr  # noqa: E402
from reference import OK, PERMISSION_DENIED, Reference  # noqa: E402

from test_own_config_eval import (K, _dense_own, _operands,  # noqa: E402
                                  all_operand_corpus, all_operand_docs)

REQUESTS = {"deny_share": 0.5, "unrouted_share": 0.1, "long_path_share": 0.15}
N, ROWS, SEED = 8, 512, 2147483659


def _rules(manifests):
    return [asyncio.run(translate_auth_config(
        m["metadata"]["name"], rr.NAMESPACE, m["spec"])).rules for m in manifests]


def _doc(row):
    return build_authorization_json(
        CheckRequestModel(http=HttpRequestAttributes(
            method=row["method"], path=row["path"], host=row["host"],
            headers=dict(row["headers"], host=row["host"]))),
        {"identity": {"anonymous": True}})


def _exact_length_rows(rng):
    """Allowed rows whose path is exactly 64, 65 and 96 bytes (the last that
    fits the DFA value bytes, the first that does not, the longest), on the
    routes that can be long."""
    rows = []
    for length in (DFA_VALUE_BYTES, DFA_VALUE_BYTES + 1, rr.LONG_MAX):
        for k in rr.LONG_ROUTES:
            i = rng.randrange(N)
            prefix = f"/api/v{rng.randrange(1, 10)}/t{i}/"
            path = prefix + rr.ROUTES[k][4](rng, length - len(prefix))
            assert len(path) == length
            rows.append({
                "host": rr._host(i), "method": rr.ROUTES[k][1], "path": path,
                "headers": {"x-request-id": f"r{i}-{rng.getrandbits(32):08x}",
                            "x-role": f"role-{(i + k) % rr.ROLES}",
                            "x-org": f"org-{i}", "x-tier": "gold"},
                "kind": f"len-{length}", "broke": None, "route": k})
    return rows


@pytest.fixture(scope="module")
def world():
    manifests = rr.manifests({"n_configs": N})
    policy = compile_corpus(_rules(manifests))
    rng = random.Random(SEED)
    rows = rr.requests(dict({"n_configs": N}, **REQUESTS), ROWS, rng, kinds=True)
    rows += _exact_length_rows(rng)
    by_host = {m["spec"]["hosts"][0]: g for g, m in enumerate(manifests)}
    cfg = [by_host[r["host"]] for r in rows]
    docs = [_doc(r) for r in rows]
    reference = Reference(manifests)
    pad = 1024
    db = pack_batch(policy, encode_batch_py(policy, docs, cfg, batch_pad=pad))
    assert not np.asarray(db.host_fallback).any()
    E = int(policy.eval_rule.shape[1])
    buf, layout = pe.fuse_batch(db)
    served = pe.unpack_verdicts(pe.eval_bitpacked_staged_jit(
        pe.to_device(policy), jnp.asarray(buf), layout), 1 + 2 * E)
    return {"manifests": manifests, "policy": policy, "rows": rows, "cfg": cfg,
            "docs": docs, "db": db, "E": E, "served": served[:len(rows)],
            "reference": [reference.decide(r) for r in rows],
            "oracle": [host_results(policy, d, g) for d, g in zip(docs, cfg)]}


def test_reference_oracle_and_served_entry_agree_on_every_row(world):
    E, served = world["E"], world["served"]
    firing = pe.firing_columns(served[:, 1:1 + E], served[:, 1 + E:])
    for i, (code, (own, rule, skip)) in enumerate(zip(world["reference"], world["oracle"])):
        assert (code == OK) == own == bool(served[i, 0]), world["rows"][i]
        # the attribution bits: which evaluator fired, and which were skipped
        np.testing.assert_array_equal(served[i, 1 + E:], skip)
        assert firing[i] == pe.firing_columns(rule[None], skip[None])[0]
    assert 0.4 < world["reference"].count(PERMISSION_DENIED) / len(world["rows"]) < 0.6


@pytest.mark.parametrize("kind, allowed", [
    ("role", False), ("org", False), ("request-id", False), ("tier", False),
    ("tenant-prefix", False), ("unrouted-path", True), ("other-method", True),
    ("len-64", True), ("len-65", True), ("len-96", True)])
def test_rows_hold_every_kind_and_each_is_decided_as_meant(world, kind, allowed):
    if kind in rr.BREAKS:
        mine = [i for i, r in enumerate(world["rows"]) if r["broke"] == kind]
    else:
        mine = [i for i, r in enumerate(world["rows"])
                if r["kind"] == kind and r["broke"] is None]
    assert len(mine) >= 5
    for i in mine:
        assert (world["reference"][i] == OK) == allowed == bool(world["served"][i, 0])
    if kind in ("unrouted-path", "other-method"):
        # only the catch-all decided them: every route's evaluator was skipped
        assert all(world["served"][i, 1 + world["E"]:][:16].all() for i in mine)
        assert any(world["rows"][i]["headers"]["x-role"]
                   != f"role-{(world['cfg'][i] + world['rows'][i]['route']) % rr.ROLES}"
                   for i in mine)
    if kind.startswith("len-"):
        ovf = np.asarray(world["db"].byte_ovf)[mine].any(axis=1)
        assert ovf.all() == ovf.any() == (int(kind[4:]) > DFA_VALUE_BYTES)


def test_path_lengths_and_shares_are_what_the_configuration_states(world):
    rows = world["rows"][:ROWS]
    lengths = np.array([len(r["path"]) for r in rows])
    assert lengths.min() >= 17 and lengths.max() <= rr.LONG_MAX
    assert 0.09 < (lengths > DFA_VALUE_BYTES).mean() < 0.21
    assert 0.05 < np.mean([r["kind"] != "routed" for r in rows]) < 0.16
    assert len({r["route"] for r in rows}) == 16 and len({r["host"] for r in rows}) == N
    assert len({(r["host"], r["path"], r["headers"]["x-request-id"]) for r in rows}) == ROWS
    again = rr.requests(dict({"n_configs": N}, **REQUESTS), ROWS, random.Random(SEED))
    assert again == [{k: r[k] for k in ("host", "method", "path", "headers")} for r in rows]


# --- (e) the regexes ---------------------------------------------------------

def _dfa_accepts(dfa, value: str) -> bool:
    state = dfa.start
    for byte in value.encode():
        state = int(dfa.trans[state, byte])
    return bool(dfa.accept[state])


_PATTERNS = [(f"route-{k:02d}-{rr.ROUTES[k][0]}", rr.route_regex(7, k)) for k in range(16)] + [
    ("tenant-prefix", "^/api/v[0-9]+/t7/"), ("request-id", "^r7-[0-9a-f]{8}$")]


@pytest.mark.parametrize("pattern", [p for _, p in _PATTERNS], ids=[n for n, _ in _PATTERNS])
def test_regex_compiles_under_max_states_and_agrees_with_re(pattern):
    dfa = compile_regex_dfa(pattern)
    assert dfa is not None and dfa.n_states <= MAX_STATES
    rng = random.Random(5)
    rows = [rr._row(7, rng, REQUESTS) for _ in range(600)]  # tenant 7's rows
    values = [r["path"] for r in rows] + [r["headers"]["x-request-id"] for r in rows]
    values += [v[:-1] for v in values[:200]] + [v + "/" for v in values[:200]]
    want = [re.search(pattern, v) is not None for v in values]
    assert [_dfa_accepts(dfa, v) for v in values] == want
    assert any(want) and not all(want)


def test_largest_dfa_is_the_uuid_route_and_sets_the_state_axis():
    states = [compile_regex_dfa(rr.route_regex(999, k)).n_states for k in range(16)]
    assert max(states) == states[0] == 66 and rr.ROUTES[0][0] == "orders"


# --- (b) widths and operand growth -------------------------------------------

def test_widths_are_18_dfa_rows_of_72_states_and_operands_grow_linearly():
    sizes = {}
    for n in (64, 128):
        manifests = rr.manifests({"n_configs": n})
        policy = compile_corpus(_rules(manifests))
        view = pe.to_device(policy, host=True)
        widths = pe.kernel_widths(view)
        (only,) = widths.pop("classes")     # one size: one class
        assert widths == {
            "leaf_cols_per_row": 40, "dfa_rows_per_row": 18, "dfa_states": 72,
            "dfa_rows_total": 18 * n}
        assert (only["configs"], only["evaluators"]) == (n, 32)
        assert all(only[k] == widths[k] for k in (
            "leaf_cols_per_row", "dfa_rows_per_row", "dfa_states"))
        assert (policy.n_own_cpu, policy.eval_rule.shape[1]) == (18, 32)
        sizes[n] = pe.operand_bytes(view)
    assert 1.9 * sizes[64] < sizes[128] < 2.1 * sizes[64]
    assert 300_000 < sizes[128] / 128 < 360_000  # ~337 KB a config


# --- (c) the two encoders on overflowed paths --------------------------------

def test_native_and_python_encoders_stage_the_same_bytes_on_overflowed_paths(world):
    from authorino_tpu.native import get_native_encoder, load_library

    if load_library() is None:
        pytest.skip("native encoder unavailable")
    policy = world["policy"]
    nat = get_native_encoder(policy)
    assert nat is not None
    long_rows = [i for i, r in enumerate(world["rows"]) if len(r["path"]) > DFA_VALUE_BYTES]
    pick = (long_rows + list(range(64)))[:128]
    docs = [world["docs"][i] for i in pick]
    cfg = [world["cfg"][i] for i in pick]
    py = pack_batch(policy, encode_batch_py(policy, docs, cfg, batch_pad=128))
    enc = nat.encode_batch(docs, cfg, batch_pad=128)
    assert enc is not None, "native encoder bailed"
    cc = pack_batch(policy, enc)
    assert py.cpu_dense.shape == (128, 18)
    assert np.asarray(py.byte_ovf)[:len(long_rows)].any(axis=1).all()
    assert cc.cpu_dense.tobytes() == py.cpu_dense.tobytes()
    assert pe.fuse_batch(cc)[0].tobytes() == pe.fuse_batch(py)[0].tobytes()
    # an overflowed path's 17 CPU columns are Python's re on the whole value
    for b in range(len(long_rows)):
        g, doc = cfg[b], docs[b]
        for j, leaf in enumerate(policy.own.cpu_leaves[g]):
            rx = policy.leaf_regex[leaf]
            sel = policy.attr_selectors[policy.leaf_attr[leaf]]
            if sel == "request.url_path":
                assert py.cpu_dense[b, j] == (
                    rx.search(doc["request"]["url_path"]) is not None)


# --- (d) the served scan against the gather reference ------------------------

def _served_and_reference(policy, db):
    """([B, 1+2E] of the served entry, the same of `_eval_verdicts_gather`
    with each row's own config selected): verdict and attribution bits."""
    E = int(policy.eval_rule.shape[1])
    operands = _operands(db)
    served = pe.unpack_verdicts(
        pe.eval_bitpacked_jit(pe.to_device(policy), *operands), 1 + 2 * E)
    gather = pe.to_device(policy, lane="gather")
    assert pe.kernel_lane_of(gather) == "gather" and gather["matmul"] is None
    return served, _dense_own(policy, gather, operands)


@pytest.mark.parametrize("corpus", ["routes", "all-operand-7", "all-operand-19"])
def test_served_scan_equals_the_gather_reference(world, corpus):
    if corpus == "routes":
        policy, db = world["policy"], world["db"]
        assert np.asarray(db.byte_ovf).any(axis=1).sum() > 50
    else:
        rng = random.Random(int(corpus.rsplit("-", 1)[1]))
        policy = compile_corpus(all_operand_corpus(rng), members_k=K, ovf_assist=True)
        docs = all_operand_docs(rng, n=96)
        rows = [rng.randrange(policy.n_configs) for _ in docs]
        db = pack_batch(policy, encode_batch_py(policy, docs, rows, batch_pad=128))
    served, reference = _served_and_reference(policy, db)
    np.testing.assert_array_equal(served, reference)
    assert served[:, 0].any() and not served[:, 0].all()
