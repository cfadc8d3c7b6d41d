"""Size classes (ISSUE 34): a corpus of AuthConfigs of two sizes is cut into
classes, each with tables of its own widths, and a request row is evaluated
at the widths of its own config's class.

Held here, on the CPU: (a) a mixed corpus (12 small tenants, 2 large ones of
3 services x 16 routes) through the served entry class by class equals the
benchmark's plain reference on every row and the single padded layout bit
for bit (verdict, rule, skipped), with config ids out of range and -1
padding; (b) every config is in exactly one class, a class's widths cover
its members, and each benchmark generator gives one class whose tables are
the corpus's; (c) a reconcile that grows a small tenant into the large
class, and back, is served right and compiles nothing on a live request;
(d) over gRPC a cut of both classes answers each row as the reference does,
and the ledger's ``own_dfa_rows`` / ``own_dfa_slots`` count what (b) says."""

import asyncio
import copy
import os
import random
import sys
from concurrent.futures import ThreadPoolExecutor

import jax.numpy as jnp
import numpy as np
import pytest

from authorino_tpu.authjson import (CheckRequestModel, HttpRequestAttributes,
                                    build_authorization_json)
from authorino_tpu.compiler import compile as cc
from authorino_tpu.compiler import compile_corpus
from authorino_tpu.compiler.encode import encode_batch_py
from authorino_tpu.compiler.pack import pack_batch
from authorino_tpu.controllers.translate import translate_auth_config
from authorino_tpu.ops import pattern_eval as pe
from authorino_tpu.runtime.engine import PolicyEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.join(ROOT, "benchmark"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from corpora import mixed_tenants as mt  # noqa: E402
from corpora import named_conditions, route_rules, tenant_rules  # noqa: E402
from reference import OK, Reference  # noqa: E402

from test_batch_stages import native_ledger  # noqa: E402
from test_native_frontend import (_native_available, grpc_call,  # noqa: E402
                                  make_req)

PARAMS = {"n_configs": 12, "n_large": 2, "services": 3}
REQUESTS = {"large_share": 0.4, "deny_share": 0.5, "unrouted_share": 0.1,
            "long_path_share": 0.15}
# device_width (ISSUE 38): 2 x 16 x 256 state-steps a row fit the budget at
# the widest lane; 50 x 72 pass it at 64 already and keep the floor
SMALL = {"leaf_cols_per_row": 10, "dfa_rows_per_row": 2, "dfa_states": 16,
         "cpu_cols": 2, "evaluators": 2, "device_width": 256}
# 3 x 16 route regexes and the catch-all's two; 48 + 2 + 4 methods + 17
# roles + the organisation + the tier = 73 leaves; 49 evaluators in 64 columns
LARGE = {"leaf_cols_per_row": 73, "dfa_rows_per_row": 50, "dfa_states": 72,
         "cpu_cols": 50, "evaluators": 64, "device_width": 64}


def _entries(manifests, engine=None):
    return [asyncio.run(translate_auth_config(
        m["metadata"]["name"], mt.NAMESPACE, m["spec"], engine=engine))
        for m in manifests]


def _doc(row):
    return build_authorization_json(
        CheckRequestModel(http=HttpRequestAttributes(
            method=row["method"], path=row["path"], host=row["host"],
            headers=dict(row["headers"], host=row["host"]))),
        {"identity": {"anonymous": True}})


@pytest.fixture(scope="module")
def mixed():
    manifests = mt.manifests(PARAMS)
    policy = compile_corpus([e.rules for e in _entries(manifests)])
    by_host = {m["spec"]["hosts"][0]: g for g, m in enumerate(manifests)}
    return {"manifests": manifests, "policy": policy, "by_host": by_host,
            "reference": Reference(manifests)}


def _padded(policy):
    """The same corpus under the single padded layout: one class of every
    config at the corpus-wide widths (what served before size classes)."""
    one = copy.copy(policy)
    one.classes = cc.derive_classes(policy, natural=False)
    assert len(one.classes) == 1
    return one


def _unpack(packed, E):
    cols = pe.unpack_verdicts(packed, 1 + 2 * E)
    return cols[:, 0], cols[:, 1:1 + E], cols[:, 1 + E:]


def _by_class(policy, db, n):
    """The served path on the host: the batch's rows split by their config's
    class, each class's rows launched through the served entry with that
    class's operands and its own CPU columns, the answers put back."""
    params = pe.to_device(policy)
    cfg = np.asarray(db.config_id)[:n]
    G = policy.n_configs
    class_of = np.full((n,), -1)
    ok = (cfg >= 0) & (cfg < G)
    for c, cls in enumerate(policy.classes):
        class_of[ok & np.isin(cfg, cls.configs)] = c
    E = int(policy.eval_rule.shape[1])
    verdict = np.zeros((n,), dtype=bool)
    rule = np.zeros((n, E), dtype=bool)
    skipped = np.zeros((n, E), dtype=bool)
    for c, cls in enumerate(policy.classes):
        at = np.nonzero(class_of == c)[0]
        if not at.size:
            continue
        n_cpu = cls.own.cpu_leaves.shape[1]
        views = [np.asarray(db.attrs_val)[at], np.asarray(db.members_c)[at],
                 np.ascontiguousarray(np.asarray(db.cpu_dense)[at, :n_cpu]),
                 np.asarray(db.config_id)[at],
                 np.asarray(db.attr_bytes)[at], np.asarray(db.byte_ovf)[at]]
        layout = pe.fuse_layout(
            (name, v.dtype, v.shape) for name, v in zip(pe._FUSED_FIELDS, views))
        E_c = cls.own.evals.shape[2]
        v, r, s = _unpack(pe.eval_bitpacked_staged_jit(
            pe.class_view(params, c), jnp.asarray(pe.fuse_bytes(views)),
            layout), E_c)
        verdict[at], rule[at, :E_c], skipped[at, :E_c] = v, r, s
        # columns past the class's read TRUE_SLOT in the padded layout
        rule[at, E_c:] = True
    return verdict, rule, skipped


@pytest.mark.parametrize("seed", [2147483659, 7, 4000534001])
def test_class_by_class_equals_reference_and_the_single_padded_layout(mixed, seed):
    policy, rng = mixed["policy"], random.Random(seed)
    rows = mt.requests(dict(PARAMS, **REQUESTS), 384, rng)
    cfg = [mixed["by_host"][r["host"]] for r in rows]
    # config ids no config has, and the -1 padding of a short batch
    strays = [policy.n_configs, policy.n_configs + 5, -1, -1]
    docs = [_doc(r) for r in rows] + [_doc(rows[0])] * len(strays)
    n = len(docs)
    db = pack_batch(policy, encode_batch_py(
        policy, docs, cfg + [cfg[0]] * len(strays), batch_pad=512))
    assert not np.asarray(db.host_fallback).any()
    db.config_id = np.asarray(db.config_id).copy()
    db.config_id[len(rows):n] = strays
    E = int(policy.eval_rule.shape[1])
    operands = tuple(jnp.asarray(getattr(db, f)) for f in pe._FUSED_FIELDS[:6])
    want = _unpack(pe.eval_bitpacked_jit(
        pe.to_device(_padded(policy)), *operands), E)
    # the whole operands (every class, every row) and the served split
    whole = _unpack(pe.eval_bitpacked_jit(pe.to_device(policy), *operands), E)
    split = _by_class(policy, db, n)
    for got in (whole, tuple(x[:n] for x in whole), split):
        for a, b in zip(got, want):
            np.testing.assert_array_equal(a, b[:len(a)])
    verdict = split[0]
    assert not verdict[len(rows):].any() and not split[1][len(rows):].any()
    for i, row in enumerate(rows):
        assert (mixed["reference"].decide(row) == OK) == bool(verdict[i]), row
    share = sum(r["host"].startswith("api-") for r in rows) / len(rows)
    assert 0.3 < share < 0.5 and 0.35 < verdict[:len(rows)].mean() < 0.65


def test_every_config_is_in_exactly_one_class_and_widths_cover_members(mixed):
    policy = mixed["policy"]
    small, large = policy.classes
    assert small.widths() == dict(SMALL, configs=12)
    assert large.widths() == dict(LARGE, configs=2)
    seen = np.concatenate([c.configs for c in policy.classes])
    assert sorted(seen.tolist()) == list(range(policy.n_configs))
    sizes = cc._natural_sizes(policy)
    for cls in policy.classes:
        w, g = cls.widths(), cls.configs
        assert (cls.cfg_local[g] == np.arange(len(g))).all()
        assert (np.delete(cls.cfg_local, g) == -1).all()
        # a width is its members' natural maximum: it covers each, and one
        # member reaches it
        for key, have in (("leaf_cols_per_row", sizes["leaves"]),
                          ("cpu_cols", sizes["cpu"]),
                          ("dfa_rows_per_row", sizes["dfa"])):
            assert have[g].max() == w[key]
        assert cc._tile8(sizes["states"][g].max()) == w["dfa_states"]
        assert sizes["evals"][g].max() <= w["evaluators"]
        # the store holds the rows its members reach and no other
        reached = policy.config_dfa_rows[g]
        assert cls.dfa_rows.tolist() == sorted(set(reached[reached >= 0].tolist()))
    # no row is evaluated at CLASS_RATIO times its own config's size
    size = np.maximum(cc.config_row_bytes(policy, sizes), cc.CLASS_FLOOR_BYTES)
    for cls in policy.classes:
        assert size[cls.configs].max() < cc.CLASS_RATIO * size[cls.configs].min()
    # a DFA row two classes share (tenant j's request-id regex is small
    # tenant j's) sits in both stores
    assert set(small.dfa_rows.tolist()) & set(large.dfa_rows.tolist())


@pytest.mark.parametrize("generator, params, widths", [
    (tenant_rules, {"n_configs": 20}, (10, 2, 16, 2, 2, 256)),
    (tenant_rules, {"n_configs": 1}, (10, 2, 16, 2, 2, 256)),
    (named_conditions, {"n_configs": 10}, (5, 1, 16, 1, 2, 256)),
    (route_rules, {"n_configs": 6}, (40, 18, 64, 18, 32, 64)),
    (route_rules, {"n_configs": 12}, (40, 18, 72, 18, 32, 64))])
def test_a_corpus_of_one_size_is_one_class_with_the_corpus_widths(
        generator, params, widths):
    policy = compile_corpus(
        [e.rules for e in _entries(generator.manifests(params))])
    (only,) = policy.classes
    keys = ("leaf_cols_per_row", "dfa_rows_per_row", "dfa_states", "cpu_cols",
            "evaluators", "device_width")
    assert only.widths() == dict(zip(keys, widths), configs=params["n_configs"])
    # to the digit: the class's tables ARE the corpus-wide layout's
    own = policy.own
    for a, b in [(only.own.leaf_tab, own.leaf_tab), (only.own.evals, own.evals),
                 (only.own.cpu_leaves, own.cpu_leaves),
                 (only.config_dfa_rows, policy.config_dfa_rows),
                 (only.dfa_tables, policy.dfa_tables),
                 (only.dfa_accept, policy.dfa_accept),
                 (only.dfa_table_of_row, policy.dfa_table_of_row)] + [
            pair for (c1, a1), (c2, a2) in zip(only.own.levels, own.levels)
            for pair in ((c1, c2), (a1, a2))]:
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)
    assert cc.dfa_table_states(policy).max() <= policy.dfa_tables.shape[1]


def test_the_class_rule_reads_sizes_alone():
    KB = 1024
    # one size, and sizes under the floor: one class
    assert cc.split_classes(np.full(7, 300 * KB)).tolist() == [0] * 7
    assert cc.split_classes(np.array([10, 4 * KB, 60 * KB, 200 * KB])).max() == 0
    # a class closes at CLASS_RATIO times its smallest member
    got = cc.split_classes(np.array([3000 * KB, 100 * KB, 399 * KB, 400 * KB,
                                     1599 * KB, 1600 * KB]))
    assert got.tolist() == [2, 0, 0, 1, 1, 2]
    assert cc.split_classes(np.zeros((0,), dtype=np.int64)).shape == (0,)


# ---------------------------------------------------------------------------
# (c) (d): the served lane
# ---------------------------------------------------------------------------

needs_native = pytest.mark.skipif(
    not _native_available(), reason="native frontend unavailable")
MAX_BATCH = 64


def _apply(engine, manifests):
    engine.apply_snapshot(_entries(manifests, engine=engine))


@pytest.fixture(scope="module")
def served(mixed):
    from authorino_tpu.runtime.native_frontend import NativeFrontend

    engine = PolicyEngine(max_batch=MAX_BATCH, mesh=None)
    _apply(engine, mixed["manifests"])
    # lane selection and brownout off: every cut with a miss launches on the
    # device lane; no verdict cache: a repeated request launches again
    fe = NativeFrontend(engine, port=0, max_batch=MAX_BATCH, window_us=2000,
                        lane_select=False, brownout=False,
                        verdict_cache_size=0)
    port = fe.start()
    assert fe.wait_warm(600.0) and fe.warm_error is None
    try:
        yield fe, port, engine
    finally:
        fe.stop()


def _req(row):
    return make_req(row["host"], method=row["method"], path=row["path"],
                    headers=row["headers"])


def _burst(port, rows):
    with ThreadPoolExecutor(32) as pool:
        return list(pool.map(lambda r: grpc_call(port, _req(r)), rows))


def _fields():
    return {f: native_ledger(f) for f in (
        "batches", "launches", "rows", "device_rows", "pad_rows",
        "own_dfa_rows", "own_dfa_slots", "h2d_bytes", "h2d_transfers")}


def _misses(fe):
    return sum(ch._value.get() for (_, _, outcome), ch
               in list(fe._warm_children.items()) if outcome == "miss")


@needs_native
def test_a_cut_of_both_classes_answers_each_row_as_the_reference(served, mixed):
    fe, port, _ = served
    kernel = fe.debug_vars()["snapshot"]["kernel"]
    assert [{k: c[k] for k in SMALL} for c in kernel["classes"]] == [SMALL, LARGE]
    assert [c["configs"] for c in kernel["classes"]] == [12, 2]
    assert all(c["operand_bytes"] > 0 for c in kernel["classes"])
    # the scalars read the largest class
    assert {k: kernel[k] for k in ("leaf_cols_per_row", "dfa_rows_per_row",
                                   "dfa_states")} == {
        k: LARGE[k] for k in ("leaf_cols_per_row", "dfa_rows_per_row",
                              "dfa_states")}
    rows = mt.requests(dict(PARAMS, **REQUESTS), 400, random.Random(11))
    before, miss0 = _fields(), _misses(fe)
    compiled = pe.eval_bitpacked_staged_jit._cache_size()
    got = _burst(port, rows)
    for row, resp in zip(rows, got):
        assert resp.status.code == mixed["reference"].decide(row), row
    d = {f: v - before[f] for f, v in _fields().items()}
    assert d["rows"] == d["device_rows"] == len(rows)
    # one launch a class present in a cut: more launches than cuts, never
    # more than two a cut, one staged buffer each
    assert d["batches"] < d["launches"] <= 2 * d["batches"]
    assert d["h2d_transfers"] == d["launches"]
    # what the launched rows' own configs have, and what the launches scanned
    policy = mixed["policy"]
    own = (policy.config_dfa_rows >= 0).sum(axis=1)
    assert d["own_dfa_rows"] == sum(
        int(own[mixed["by_host"][r["host"]]]) for r in rows)
    assert d["own_dfa_rows"] <= d["own_dfa_slots"] < d["pad_rows"] * LARGE[
        "dfa_rows_per_row"]
    assert _misses(fe) == miss0
    assert pe.eval_bitpacked_staged_jit._cache_size() == compiled
    # a small tenant's staged row carries its class's two CPU columns
    a = fe._cur_rec.arrays[0]
    small, large = (fe._row_h2d_bytes(a, 64, n) for n in (w["cpu_cols"] for w in fe._cur_rec.classes))
    assert large - small == LARGE["cpu_cols"] - SMALL["cpu_cols"]


@needs_native
def test_each_class_fills_overflows_and_launches_at_its_own_width(served, mixed):
    """ISSUE 38: the small class scans up to 256 value bytes on the device,
    the large one 64; the slot is as wide as the widest, a row overflows
    past its OWN class's width, a launch's byte bucket never passes its
    class's, and the warm grid compiled every (class, pad, eff) of that."""
    fe, port, _ = served
    rec = fe._cur_rec
    assert [c["device_width"] for c in rec.classes] == [256, 64]
    assert rec.byte_width == 256
    assert all(a["attr_bytes"].shape[-1] == 256 for a in rec.arrays)
    grid = fe._bucket_grid(rec)
    assert sorted({e for _, e in grid}) == [16, 32, 64, 256]
    assert set(rec.layouts) == {(c, p, min(e, w)) for p, e in grid
                                for c, w in enumerate((256, 64))}
    rng = random.Random(38)
    small = mt._small_row(3, rng, dict(REQUESTS, deny_share=0.0))
    small["path"] = "/api/v1/t3/" + "a" * 190           # past 64, inside 256
    large = None
    while large is None or not 64 < len(large["path"]) <= 96 or large["kind"] != "routed":
        large = mt._large_row(1, rng, dict(PARAMS, **dict(
            REQUESTS, deny_share=0.0, long_path_share=1.0, unrouted_share=0.0)))
    fields = ("dfa_ovf_rows", "eff_cols", "launches", "dfa_dev_bytes",
              "dfa_host_bytes")
    compiled, miss0 = pe.eval_bitpacked_staged_jit._cache_size(), _misses(fe)
    got = {}
    for name, row in (("small", small), ("large", large)):
        before = {f: native_ledger(f) for f in fields}
        code = grpc_call(port, _req(row)).status.code
        assert code == mixed["reference"].decide(row) == OK, name
        got[name] = {f: native_ledger(f) - before[f] for f in fields}
    # the small tenant's 201-byte path rode the device at the 256 bucket
    assert got["small"] == {"dfa_ovf_rows": 0, "eff_cols": 256, "launches": 1,
                            "dfa_dev_bytes": 201 + len(small["headers"]["x-request-id"]),
                            "dfa_host_bytes": 0}
    # the large tenant's 65-96-byte path is the host's: its 129 path DFAs'
    # bytes are counted there, the request id's one DFA on the device, and
    # the launch ran the shortest bucket
    assert got["large"] == {
        "dfa_ovf_rows": 1, "eff_cols": 16, "launches": 1,
        "dfa_dev_bytes": len(large["headers"]["x-request-id"]),
        "dfa_host_bytes": (LARGE["dfa_rows_per_row"] - 1) * len(large["path"])}
    assert _misses(fe) == miss0
    assert pe.eval_bitpacked_staged_jit._cache_size() == compiled


@needs_native
def test_a_tenant_that_grows_into_the_large_class_and_back_is_served_right(
        served, mixed):
    fe, port, engine = served
    manifests = mixed["manifests"]
    # small tenant 3 takes a large tenant's rules (its host stays its own)
    grown = copy.deepcopy(manifests)
    donor = mt.manifests(dict(PARAMS, n_large=4))[PARAMS["n_configs"] + 3]
    grown[3]["spec"]["authorization"] = donor["spec"]["authorization"]
    rng = random.Random(5)
    host = manifests[3]["spec"]["hosts"][0]
    as_large = [dict(mt._large_row(3, rng, dict(PARAMS, **REQUESTS)), host=host)
                for _ in range(96)]
    as_small = [mt._small_row(3, rng, REQUESTS) for _ in range(96)]
    others = mt.requests(dict(PARAMS, **REQUESTS), 128, rng)
    for corpus, n_large in ((grown, 3), (manifests, 2)):
        _apply(engine, corpus)
        fe.refresh()
        assert fe.wait_warm(600.0) and fe.warm_error is None
        classes = fe.debug_vars()["snapshot"]["kernel"]["classes"]
        assert [c["configs"] for c in classes] == [14 - n_large, n_large]
        reference = Reference(corpus)
        miss0 = _misses(fe)
        compiled = pe.eval_bitpacked_staged_jit._cache_size()
        rows = as_large + as_small + others
        for row, resp in zip(rows, _burst(port, rows)):
            assert resp.status.code == reference.decide(row), row
        # nothing compiled on a live request
        assert _misses(fe) == miss0
        assert pe.eval_bitpacked_staged_jit._cache_size() == compiled
    allowed = [reference.decide(r) == OK for r in as_small]
    assert any(allowed) and not all(allowed)
