"""Regexes past the DFA compiler's old caps (96 states, counted repeats of
16): a tenant's OpenAPI routes written as one allowlist regex (119-392
states, a 24-hex object id) compiles to a DFA that agrees with Python's `re`;
the own-row scan is exact for state ids past 255 with the chip's bf16
arithmetic forced on the CPU (the one place a CPU run can see the TPU's
rounding); the dense and mesh bodies stay exact; the host's overflow scan
walks u16 tables; and the configuration `api-allowlist-1k` at 8 configs is
served on the native fast lane, answer for answer as the benchmark's plain
reference answers it.  A regex past the new caps still falls back, exactly,
and still takes its config off the native lane."""

import os
import random
import re
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from authorino_tpu.compiler import ConfigRules, compile_corpus
from authorino_tpu.compiler import compile as cc
from authorino_tpu.compiler.redfa import (MAX_REPEAT, MAX_STATES,
                                          compile_regex_dfa)
from authorino_tpu.expressions import Operator, Pattern
from authorino_tpu.ops import pattern_eval as pe
from authorino_tpu.runtime.engine import PolicyEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.join(ROOT, "benchmark"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from corpora import api_allowlist as aa  # noqa: E402
from reference import OK, PERMISSION_DENIED, Reference  # noqa: E402

from test_batch_stages import native_ledger  # noqa: E402
from test_native_frontend import _native_available, grpc_call  # noqa: E402
from test_size_classes import _burst, _doc, _entries, _misses, _req  # noqa: E402

needs_native = pytest.mark.skipif(
    not _native_available(), reason="native frontend unavailable")
N = 8
PARAMS = {"n_configs": N, "deny_share": 0.5}
MAX_BATCH = 32
HEX = "0123456789abcdef"


def _allowlist(k, i=3):
    """Tenant i's allowlist regex at exactly k collections."""
    cols = aa.COLLECTIONS[:k]
    subs = aa.SUB_RESOURCES[:aa.N_SUB]
    return (cols, subs,
            f"^/api/v[0-9]+/t{i}/({'|'.join(cols)})"
            f"(/[0-9a-f]{{{aa.OBJECT_ID}}}(/({'|'.join(subs)}))?)?$")


def _walk(dfa, value):
    state = dfa.start
    for byte in value.encode():
        state = int(dfa.trans[state, byte])
    return bool(dfa.accept[state]), state


def _near_misses(cols, subs, rng, n, i=3):
    """Allowed paths of the three forms and the generator's five near
    misses, built on these collections."""
    out = []
    for _ in range(n):
        head = f"/api/v{rng.randrange(1, 10)}/t{i}/"
        col, oid = rng.choice(cols), "".join(rng.choice(HEX) for _ in range(24))
        sub = rng.choice(subs)
        at = rng.randrange(24)
        out += [head + col, f"{head}{col}/{oid}", f"{head}{col}/{oid}/{sub}",
                head + rng.choice([c for c in aa.COLLECTIONS if c not in cols]
                                  or ["nouns"]),
                head + col[:at % len(col)] + col[at % len(col) + 1:],
                f"{head}{col}/{oid[:-1]}", f"{head}{col}/{oid}0/{sub}",
                f"{head}{col}/{oid[:at]}{oid[at].upper()}{oid[at + 1:]}",
                f"{head}{col}/{oid}/{rng.choice(aa.SUB_RESOURCES[aa.N_SUB:])}",
                f"{head}{col}/{oid}/{sub}/", f"x{head}{col}"]
    return out


def _random_strings(rng, n, alphabet="/apivt0123456789abcdefxyz", most=70):
    return ["".join(rng.choice(alphabet) for _ in range(rng.randrange(most)))
            for _ in range(n)]


# --- the compiler -------------------------------------------------------------

@pytest.mark.parametrize("k", [8, 24, 48])
def test_an_allowlist_past_the_old_caps_agrees_with_re(k):
    cols, subs, rx = _allowlist(k)
    dfa = compile_regex_dfa(rx)
    assert dfa is not None and 96 < dfa.n_states <= MAX_STATES
    assert dfa.trans.dtype == (np.uint16 if dfa.n_states > 256 else np.uint8)
    if k == 48:
        assert dfa.n_states > 256
    rng = random.Random(k)
    values = _near_misses(cols, subs, rng, 60) + _random_strings(rng, 400)
    gold = re.compile(rx)
    want = [gold.search(v) is not None for v in values]
    assert [_walk(dfa, v)[0] for v in values] == want
    assert any(want) and not all(want)


@pytest.mark.parametrize("n", [17, 24, 32, 64])
@pytest.mark.parametrize("anchored", [True, False], ids=["anchored", "search"])
def test_a_counted_repeat_past_16_agrees_with_re(n, anchored):
    rx = f"^[0-9a-f]{{{n}}}$" if anchored else f"id=[0-9a-f]{{{n}}};"
    dfa = compile_regex_dfa(rx)
    assert dfa is not None
    rng = random.Random(n)
    values = []
    for m in (n - 1, n, n + 1):
        body = "".join(rng.choice(HEX) for _ in range(m))
        values += [body, body.upper(), f"id={body};", f"xid={body};y",
                   f"id={body[:-1]}g;"]
    values += _random_strings(rng, 200, alphabet="id=;0123456789abcdefg",
                              most=2 * n)
    gold = re.compile(rx)
    assert [_walk(dfa, v)[0] for v in values] == \
        [gold.search(v) is not None for v in values]


def test_a_regex_past_the_new_caps_falls_back_exactly_and_leaves_the_native_lane():
    """A repeat past RE2's 1000, and a regex whose DFA passes MAX_STATES
    (the n-th byte from the end is an `a`: 2**11 states): no DFA, so the
    leaf is decided on the CPU regex lane, exactly, and its config gets no
    native plan while its neighbour's allowlist does."""
    past_repeat = f"^[a-z]{{{MAX_REPEAT + 1}}}$"
    past_states = "^(a|b)*a(a|b){10}$"
    assert compile_regex_dfa(past_repeat) is None
    assert compile_regex_dfa(past_states) is None
    assert compile_regex_dfa(f"^[a-z]{{{MAX_REPEAT}}}$") is not None
    from authorino_tpu.runtime.native_frontend import fast_lane_eligible

    manifests = aa.manifests({"n_configs": 2})
    manifests[1]["spec"]["authorization"]["rules"]["patternMatching"][
        "patterns"][1]["value"] = past_states
    engine = PolicyEngine(mesh=None)
    entries = _entries(manifests, engine=engine)
    engine.apply_snapshot(entries)
    policy = engine._snapshot.policy
    assert cc.cpu_regex_leaves(policy) == 1
    assert fast_lane_eligible(entries[0], policy) is not None
    assert fast_lane_eligible(entries[1], policy) is None
    from authorino_tpu.models import PolicyModel

    rows = aa.requests({"n_configs": 2, "deny_share": 0.0}, 40,
                       random.Random(5))
    for path in ("ab" + "a" * 10, "ab" * 6, "b" * 11, "a" * 10 + "b"):
        rows.append(dict(rows[0], host=aa._host(1), path=path))
    ref = Reference(manifests)
    want = [ref.decide(r) == OK for r in rows]
    assert any(want[-4:]) and not all(want[-4:])
    got = PolicyModel(policy).decide([_doc(r) for r in rows],
                                     [_name(entries, r) for r in rows])
    assert got == want


# --- the own-row scan under the chip's arithmetic ------------------------------

def _scan_operands(dfa, values, width=cc.DFA_VALUE_BYTES):
    S = int(cc._tile8(dfa.n_states))
    tables = np.zeros((1, S, 256), dtype=cc.dfa_state_dtype(S))
    tables[0, :dfa.n_states] = dfa.trans
    tables[0, dfa.n_states:] = np.arange(dfa.n_states, S)[:, None]
    accept = np.zeros((1, S), dtype=bool)
    accept[0, :dfa.n_states] = dfa.accept
    params = {"dfa_tables": jnp.asarray(tables),
              "dfa_accept": jnp.asarray(accept),
              "config_dfa_rows": jnp.zeros((1, 1), dtype=jnp.int32),
              "dfa_table_of_row": jnp.zeros((1,), dtype=jnp.int32),
              "dfa_byte_slot": jnp.zeros((1,), dtype=jnp.int32)}
    buf = np.zeros((len(values), 1, width), dtype=np.uint8)
    for b, v in enumerate(values):
        raw = v.encode()[:width]
        buf[b, 0, :len(raw)] = np.frombuffer(raw, dtype=np.uint8)
    return params, jnp.asarray(buf)


def test_the_own_row_scan_is_exact_past_255_with_bf16_forced():
    """The served scan with the chip's compute dtype (bf16) forced on the
    CPU, over a DFA whose reached and accepting state ids pass 256: every
    verdict is `re`'s.  A bf16 map or carry turns id 257 into 256 (the old
    scan's `own_tables.astype(cdt)`), and this test reads wrong there."""
    cols, subs, rx = _allowlist(48)
    dfa = compile_regex_dfa(rx)
    rng = random.Random(48)
    values = [v for v in _near_misses(cols, subs, rng, 40)
              if len(v.encode()) <= cc.DFA_VALUE_BYTES]
    finals = [_walk(dfa, v)[1] for v in values]
    accepted_high = [s for v, s in zip(values, finals)
                     if s > 256 and _walk(dfa, v)[0]]
    assert max(finals) > 256 and accepted_high
    params, buf = _scan_operands(dfa, values)
    assert pe.is_wide(params["dfa_tables"])
    B = len(values)
    got = pe._own_dfa_row_res(params, jnp.zeros((B,), dtype=jnp.int32),
                              jnp.ones((B,), dtype=bool), buf, jnp.bfloat16)
    want = [re.search(rx, v) is not None for v in values]
    assert np.asarray(got)[:, 0].tolist() == want


def test_a_store_of_256_states_or_fewer_keeps_u8_and_the_bf16_scan():
    cols, subs, rx = _allowlist(8)
    dfa = compile_regex_dfa(rx)
    values = _near_misses(cols, subs, random.Random(8), 20)
    params, buf = _scan_operands(dfa, values)
    assert params["dfa_tables"].dtype == jnp.uint8
    assert not pe.is_wide(params["dfa_tables"])
    B = len(values)
    got = pe._own_dfa_row_res(params, jnp.zeros((B,), dtype=jnp.int32),
                              jnp.ones((B,), dtype=bool), buf, jnp.bfloat16)
    assert np.asarray(got)[:, 0].tolist() == [
        re.search(rx, v) is not None for v in values]


# --- the corpus's store, and the dense and mesh bodies ------------------------

@pytest.fixture(scope="module")
def corpus():
    manifests = aa.manifests({"n_configs": N})
    entries = _entries(manifests)
    return {"manifests": manifests, "reference": Reference(manifests),
            "entries": entries,
            "policy": compile_corpus([e.rules for e in entries])}


def test_the_store_is_u16_past_256_states_and_one_class(corpus):
    policy = corpus["policy"]
    assert policy.dfa_tables.dtype == np.uint16 and policy.dfa_tables.shape[1] > 256
    (only,) = policy.classes
    assert only.dfa_tables.dtype == np.uint16
    # D 2 x S 384 x 64 state-steps a row: under the budget, at the floor
    assert only.device_width == cc.DFA_VALUE_BYTES
    assert cc.cpu_regex_leaves(policy) == 0
    from authorino_tpu.analysis.tensor_lint import tensor_lint
    assert tensor_lint(policy) == []


def test_a_published_snapshot_keeps_the_u16_store(corpus):
    """A replica loads the u16 store as the leader compiled it: the blob
    carries the dtype, the classes derived from it are u16 again, and the
    reloaded corpus lints clean (a u8 copy of ids past 255 would not)."""
    from authorino_tpu.analysis.tensor_lint import tensor_lint
    from authorino_tpu.snapshots.serialize import (deserialize_policy,
                                                   serialize_policy)

    policy = corpus["policy"]
    back, _ = deserialize_policy(serialize_policy(policy))
    assert back.dfa_tables.dtype == np.uint16
    assert np.array_equal(back.dfa_tables, policy.dfa_tables)
    assert [c.dfa_tables.dtype for c in back.classes] == [np.dtype(np.uint16)]
    assert tensor_lint(back) == []
    back.dfa_tables = back.dfa_tables.astype(np.uint8)
    assert "dfa-next-state" in {f.kind for f in tensor_lint(back)}


def test_a_narrower_class_of_a_wide_corpus_keeps_u8(corpus):
    """A class cut from a corpus whose widest table passes 256 states keeps
    u8 tables and the bf16 scan when its own state axis is 256 or fewer."""
    small = [ConfigRules("small", evaluators=[(None, Pattern(
        "request.url_path", Operator.MATCHES, _allowlist(8)[2]))])]
    policy = compile_corpus([e.rules for e in corpus["entries"]] + small * 1)
    assert policy.dfa_tables.dtype == np.uint16
    by_dtype = {c.dfa_tables.dtype for c in policy.classes
                if c.dfa_tables.shape[1] <= 256}
    assert by_dtype <= {np.dtype(np.uint8)}


def _rows(seed, n=300):
    return aa.requests(PARAMS, n, random.Random(seed), kinds=True)


def _name(entries, row):
    (name,) = [e.rules.name for e in entries if row["host"] in e.hosts]
    return name


@pytest.mark.parametrize("body", ["dense", "own"])
def test_the_dense_and_own_bodies_are_exact_with_bf16_forced(
        corpus, monkeypatch, body):
    """The dense body (``forward``: PolicyModel, the mesh step) and the
    own-config body (``eval_own``: the served entries) on the corpus's u16
    store, with the chip's bf16 forced: the dense body's tables travel in
    f32, the own body's scan is the wide one."""
    monkeypatch.setattr(pe, "_mm_dtype", lambda device=None: jnp.bfloat16)
    from authorino_tpu.models import PolicyModel

    model = PolicyModel(corpus["policy"])
    mm = model.params["matmul"]
    assert mm["mxu"].dtype == jnp.bfloat16
    assert mm["dfa_tables_f"].dtype == jnp.float32
    rows = _rows(41)
    want = [corpus["reference"].decide(r) == OK for r in rows]
    docs = [_doc(r) for r in rows]
    names = [_name(corpus["entries"], r) for r in rows]
    if body == "dense":
        got = model.decide(docs, names)
    else:
        db = model.encode(docs, [corpus["policy"].config_ids[n] for n in names])
        verdict, _, _ = pe.eval_full_jit(
            model.params, jnp.asarray(db.attrs_val), jnp.asarray(db.members_c),
            jnp.asarray(db.cpu_dense), jnp.asarray(db.config_id),
            jnp.asarray(db.attr_bytes), jnp.asarray(db.byte_ovf),
            *pe._extra_operands(db))
        got = np.asarray(verdict)[:len(rows)].tolist()
    assert got == want and any(want) and not all(want)


def test_the_mesh_body_is_exact_on_a_wide_corpus(corpus, monkeypatch):
    monkeypatch.setattr(pe, "_mm_dtype", lambda device=None: jnp.bfloat16)
    from authorino_tpu.parallel import ShardedPolicyModel, build_mesh

    model = ShardedPolicyModel([e.rules for e in corpus["entries"]],
                               build_mesh(n_devices=8, dp=2))
    assert model.params["matmul"]["dfa_tables_f"].dtype == jnp.float32
    rows = _rows(42, 120)
    got = model.decide([_doc(r) for r in rows],
                       [_name(corpus["entries"], r) for r in rows])
    assert got == [corpus["reference"].decide(r) == OK for r in rows]


# --- the served path -----------------------------------------------------------

@pytest.fixture(scope="module")
def served(corpus):
    from authorino_tpu.runtime.native_frontend import NativeFrontend

    engine = PolicyEngine(max_batch=MAX_BATCH, mesh=None)
    engine.apply_snapshot(_entries(corpus["manifests"], engine=engine))
    fe = NativeFrontend(engine, port=0, max_batch=MAX_BATCH, window_us=2000,
                        lane_select=False, brownout=False)
    port = fe.start()
    assert fe.wait_warm(900.0) and fe.warm_error is None
    try:
        yield fe, port, engine
    finally:
        fe.stop()


@needs_native
@pytest.mark.parametrize("seed", [41, 2147483700, 4000538003])
def test_every_row_is_answered_as_the_reference_answers_it(served, corpus, seed):
    fe, port, _ = served
    rows = _rows(seed)
    want = [corpus["reference"].decide(r) for r in rows]
    assert set(want) == {OK, PERMISSION_DENIED}
    assert {r["broke"] for r in rows} >= set(aa.PATH_BREAKS)
    miss0, slow0 = _misses(fe), fe.stats()["slow"]
    got = [resp.status.code for resp in _burst(port, rows)]
    assert [r["broke"] for r, g, w in zip(rows, got, want) if g != w] == []
    # every config on the native fast lane, nothing compiled on a request
    assert fe.stats()["slow"] == slow0 and _misses(fe) == miss0


@needs_native
def test_the_snapshot_says_no_config_and_no_leaf_left_the_lane(served):
    fe, _, _ = served
    snap = fe.debug_vars()["snapshot"]
    assert snap["slow_configs"] == 0 and snap["fast_configs"] == N
    kernel = snap["kernel"]
    assert kernel["dfa_cpu_leaves"] == 0 and kernel["dfa_states"] > 256
    assert [c["state_bytes"] for c in kernel["classes"]] == [2]


def _long_path(i, length, match):
    """A path the tenant's allowlist admits (a long API version), `length`
    bytes; with its last byte changed where `match` is False."""
    cols, subs = aa._api(i)
    tail = f"/t{i}/{cols[0]}/{'a' * aa.OBJECT_ID}/{subs[0]}"
    digits = length - len("/api/v") - len(tail)
    path = "/api/v" + "7" * digits + tail
    return path if match else path[:-1] + "Z"


@needs_native
@pytest.mark.parametrize("match", [True, False], ids=["match", "last-byte-differs"])
@pytest.mark.parametrize("length", [64, 65, 100, 300])
def test_a_path_past_the_width_is_scanned_by_the_host_over_u16_tables(
        served, corpus, length, match):
    fe, port, _ = served
    i = 2   # a tenant whose allowlist passes 256 states
    assert compile_regex_dfa(aa.path_regex(i)).n_states > 256
    row = next(r for r in _rows(length, 100)
               if r["broke"] is None and r["host"] == aa._host(i))
    row["path"] = _long_path(i, length, match)
    assert len(row["path"]) == length
    assert (re.search(aa.path_regex(i), row["path"]) is not None) == match
    rows0 = native_ledger("dfa_ovf_rows")
    code = grpc_call(port, _req(row)).status.code
    assert code == corpus["reference"].decide(row) == (
        OK if match else PERMISSION_DENIED)
    fe._fold_kept()
    assert native_ledger("dfa_ovf_rows") - rows0 == (1 if length > 64 else 0)
