"""The configuration `edge-1k` (ISSUE 38) at a small size on the CPU, through
the normal served path (translate -> engine -> native front end -> gRPC):
requests of Envoy's shape (18-28 headers, values of 100-380 bytes on three
regex attributes) are answered as the benchmark's plain reference answers
them, every row; a value at 63..257 bytes gets the reference's verdict
whichever lane scans it (the device inside the class's 256-byte lane, the
host past it); every (class, pad, eff) the dispatch can pick was compiled by
the warm grid; translation validation's boundary witnesses and the
encoding epoch follow the class's width; the counters the benchmark's new
metrics read count what they say."""

import copy
import dataclasses
import os
import random
import re
import sys

import numpy as np
import pytest

from authorino_tpu.analysis import translation_validate as tv
from authorino_tpu.compiler import compile as cc
from authorino_tpu.compiler import compile_corpus
from authorino_tpu.compiler.redfa import MAX_STATES, compile_regex_dfa
from authorino_tpu.ops import pattern_eval as pe
from authorino_tpu.runtime.engine import PolicyEngine
from authorino_tpu.snapshots.fingerprint import encoding_epoch

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.join(ROOT, "benchmark"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from corpora import edge_requests as er  # noqa: E402
from reference import OK, PERMISSION_DENIED, Reference  # noqa: E402

from test_batch_stages import native_ledger  # noqa: E402
from test_native_frontend import _native_available, grpc_call  # noqa: E402
from test_size_classes import _burst, _entries, _misses, _req  # noqa: E402

needs_native = pytest.mark.skipif(
    not _native_available(), reason="native frontend unavailable")
N = 24
REQUESTS = {"browser_share": 0.7, "deny_share": 0.5, "write_share": 0.3,
            "cookie_pairs": [2, 9], "cookie_tail_share": 0.03}
PARAMS = dict(REQUESTS, n_configs=N)
MAX_BATCH = 32
LENGTHS = (63, 64, 65, 127, 128, 129, 255, 256, 257)


@pytest.fixture(scope="module")
def corpus():
    manifests = er.manifests({"n_configs": N})
    return {"manifests": manifests, "reference": Reference(manifests),
            "policy": compile_corpus([e.rules for e in _entries(manifests)])}


@pytest.fixture(scope="module")
def served(corpus):
    from authorino_tpu.runtime.native_frontend import NativeFrontend

    engine = PolicyEngine(max_batch=MAX_BATCH, mesh=None)
    engine.apply_snapshot(_entries(corpus["manifests"], engine=engine))
    # lane selection and brownout off: every cut with a miss launches on the
    # device lane; the verdict cache stays on, as served
    fe = NativeFrontend(engine, port=0, max_batch=MAX_BATCH, window_us=2000,
                        lane_select=False, brownout=False)
    port = fe.start()
    assert fe.wait_warm(900.0) and fe.warm_error is None
    try:
        yield fe, port, engine
    finally:
        fe.stop()


def _clock(fe, row):
    table = fe._mod.fe_loop_clock()
    return (table["phases"].get(row) or table["rows"][row])["count"]


# --- the corpus ---------------------------------------------------------------

@pytest.mark.parametrize("k", range(5), ids=[
    "path", "request-id", "user-agent", "referer", "cookie"])
def test_regex_compiles_under_max_states_and_agrees_with_re(k):
    pattern = er.regexes(7)[k]
    dfa = compile_regex_dfa(pattern)
    assert dfa is not None and dfa.n_states <= MAX_STATES
    rng = random.Random(5)
    rows = [r for r in er.requests(PARAMS, 1500, rng, kinds=True)]
    attr = er.REGEX_READ[k]
    values = [r["path"] if attr == "path" else r["headers"].get(attr, "")
              for r in rows]
    values += [v[:-1] for v in values[:200]] + [v + "\x7f" for v in values[:200]]

    def accepts(value):
        state = dfa.start
        for byte in value.encode():
            state = int(dfa.trans[state, byte])
        return bool(dfa.accept[state])

    want = [re.search(pattern, v) is not None for v in values]
    assert [accepts(v) for v in values] == want
    assert any(want) and not all(want)


def test_state_counts_are_the_issues_and_every_config_is_one_class_at_256(corpus):
    states = [compile_regex_dfa(rx).n_states for rx in er.regexes(999)]
    assert states == [16, 38, 29, 32, 21]
    policy = corpus["policy"]
    (only,) = policy.classes
    assert only.widths() == {
        "configs": N, "leaf_cols_per_row": 11, "dfa_rows_per_row": 5,
        "dfa_states": 40, "device_width": 256, "cpu_cols": 5, "evaluators": 2}
    assert policy.byte_width == 256
    assert (policy.config_byte_width == 256).all()


@pytest.mark.parametrize("dfa_rows, states, width", [
    (2, 24, 256),     # tenants-1k: 12,288 state-steps a row at 256
    (5, 40, 256),     # edge-1k: 51,200
    (18, 72, 64),     # routes-1k: 82,944 at 64 already: the floor
    (130, 72, 64),    # mixed-tenants-1k's large class
    (2, 16, 256),     # ... and its small one
    (1, 16, 256), (4, 64, 256), (8, 64, 128), (16, 64, 64), (9, 64, 64),
    (0, 8, 256)])
def test_the_width_rule_reads_a_rows_scan_alone(dfa_rows, states, width):
    assert cc.class_device_width(dfa_rows, states) == width
    assert width in cc.DFA_WIDTHS and width >= cc.DFA_VALUE_BYTES
    wider = [w for w in cc.DFA_WIDTHS if w > width]
    assert all(dfa_rows * states * w > cc.DFA_SCAN_BUDGET for w in wider)


def test_no_environment_variable_sets_the_width(monkeypatch):
    import importlib.util

    monkeypatch.setenv("AUTHORINO_TPU_DFA_VALUE_BYTES", "16")
    # a fresh copy of the module, executed under a name of its own: a reload
    # in place would leave every other importer holding classes (the
    # CompiledPolicy an isinstance() checks) that the module no longer has
    name = cc.__name__ + "_fresh"
    spec = importlib.util.spec_from_file_location(name, cc.__file__)
    again = importlib.util.module_from_spec(spec)
    sys.modules[name] = again   # dataclasses look their module up by name
    try:
        spec.loader.exec_module(again)
        assert again.DFA_VALUE_BYTES == 64
        assert again.class_device_width(2, 24) == 256
    finally:
        del sys.modules[name]


# --- the served path against the reference -------------------------------------

@needs_native
@pytest.mark.parametrize("seed", [38, 2147483700, 4000538001, 4000538002])
def test_every_row_is_answered_as_the_reference_answers_it(served, corpus, seed):
    fe, port, _ = served
    rows = er.requests(PARAMS, 700, random.Random(seed), kinds=True)
    want = [corpus["reference"].decide(r) for r in rows]
    assert set(want) == {OK, PERMISSION_DENIED}
    miss0, slow0 = _misses(fe), fe.stats()["slow"]
    got = [resp.status.code for resp in _burst(port, rows)]
    wrong = [(r["kind"], r["broke"]) for r, g, w in zip(rows, got, want) if g != w]
    assert wrong == []
    # every config on the native fast lane, nothing compiled on a live request
    assert fe.stats()["slow"] == slow0 and _misses(fe) == miss0


def _agent(length, match):
    value = "Mozilla/5.0 (" + "x" * (length - len("Mozilla/5.0 ("))
    return value if match else value[:-1] + "\x7f"


def _cookie(length, match, i):
    tail = f"tenant=t{i}-0123abcd"
    value = "k=" + "v" * (length - len(tail) - 4) + "; " + tail
    return value if match else value[:-1] + "g"


@needs_native
@pytest.mark.parametrize("match", [True, False], ids=["match", "last-byte-differs"])
@pytest.mark.parametrize("length", LENGTHS)
@pytest.mark.parametrize("attr", ["user-agent", "cookie"])
def test_a_value_at_every_boundary_gets_the_references_verdict(
        served, corpus, attr, length, match):
    """The same value at 63..257 bytes, matching and with its last byte
    changed: inside the class's 256 bytes the device scans it, at 257 the
    host does, and either gives what Python's `re` gives."""
    fe, port, _ = served
    i = 3
    row = next(r for r in er.requests(PARAMS, 400, random.Random(length), kinds=True)
               if r["kind"] == "browser" and r["broke"] is None
               and r["host"] == er._host(i) and len(r["headers"]["cookie"]) <= 256)
    value = _agent(length, match) if attr == "user-agent" else _cookie(length, match, i)
    assert len(value.encode()) == length
    row["headers"][attr] = value
    assert (re.search(er.regexes(i)[2 if attr == "user-agent" else 4], value)
            is not None) == match
    scans, rows0 = _clock(fe, "ovf_scan"), native_ledger("dfa_ovf_rows")
    code = grpc_call(port, _req(row)).status.code
    assert code == corpus["reference"].decide(row) == (OK if match else PERMISSION_DENIED)
    assert _clock(fe, "ovf_scan") - scans == (1 if length > 256 else 0)
    fe._fold_kept()
    assert native_ledger("dfa_ovf_rows") - rows0 == (1 if length > 256 else 0)


@needs_native
def test_every_variant_the_dispatch_can_pick_was_compiled_by_the_warm_grid(served):
    fe, port, _ = served
    rec = fe._cur_rec
    grid = fe._bucket_grid(rec)
    # powers of two to the floor, then the class's width: no 128 bucket
    assert sorted({e for _, e in grid}) == [16, 32, 64, 256]
    assert sorted({p for p, _ in grid}) == [16, 32]
    assert set(grid) == rec.warm
    # a staging layout, hence a compiled variant, for every (class, pad, eff)
    assert set(rec.layouts) == {(0, p, e) for p, e in grid}
    compiled = pe.eval_bitpacked_staged_jit._cache_size()
    miss0 = _misses(fe)
    # cuts of every byte bucket: short values alone, then longer and longer
    base = er.requests(PARAMS, 64, random.Random(9), kinds=True)
    sdk = [r for r in base if r["kind"] == "sdk" and r["broke"] is None]
    for length in (0, 20, 40, 100, 200, 300):
        rows = copy.deepcopy(sdk[:8])
        for r in rows:
            r["headers"]["x-request-id"] = er._uuid4(random.Random(length))
            r["headers"]["referer"] = "https://elsewhere.example/" + "a" * length
        _burst(port, rows)
    assert _misses(fe) == miss0
    assert pe.eval_bitpacked_staged_jit._cache_size() == compiled


@needs_native
def test_the_new_counters_count_what_they_say(served, corpus):
    """`eff_cols` sums the launches' byte buckets, `dfa_dev_bytes` and
    `dfa_host_bytes` the value bytes the rows' DFAs read on either side (a
    DFA a byte), `req_bytes` and `req_headers` what the requests carried."""
    fe, port, _ = served
    fields = ("launches", "eff_cols", "dfa_dev_bytes", "dfa_host_bytes",
              "eff_slack_cols", "rows", "dfa_ovf_rows")
    rows = [r for r in er.requests(PARAMS, 300, random.Random(77), kinds=True)
            if r["broke"] is None][:60]
    fe._fold_kept()
    before = {f: native_ledger(f) for f in fields}
    parsed, nbytes, nheads = (_clock(fe, r) for r in ("parse", "req_bytes", "req_headers"))
    reqs = [_req(r) for r in rows]
    for req in reqs:  # one at a time: a cut a request, nothing deduplicated
        assert grpc_call(port, req).status.code == OK
    fe._fold_kept()
    d = {f: native_ledger(f) - before[f] for f in fields}
    assert d["rows"] == d["launches"] == len(rows)

    def read(row, attr):
        return len((row["path"] if attr == "path"
                    else row["headers"].get(attr, "")).encode())

    # one DFA an attribute: a row's bytes are its five values' lengths
    on_host = sum(read(r, a) for r in rows for a in er.REGEX_READ if read(r, a) > 256)
    on_device = sum(read(r, a) for r in rows for a in er.REGEX_READ if read(r, a) <= 256)
    assert (d["dfa_dev_bytes"], d["dfa_host_bytes"]) == (on_device, on_host)
    assert d["dfa_ovf_rows"] == sum(
        any(read(r, a) > 256 for a in er.REGEX_READ) for r in rows)
    # a launch ran the bucket of its own row's longest device-side value
    want = 0
    for r in rows:
        longest = max([read(r, a) for a in er.REGEX_READ if read(r, a) <= 256])
        want += min(b for b in (16, 32, 64, 256) if b >= longest)
    assert d["eff_cols"] == want and d["eff_slack_cols"] == 0
    assert _clock(fe, "parse") - parsed == len(rows)
    assert _clock(fe, "req_bytes") - nbytes == sum(len(q.SerializeToString()) for q in reqs)
    assert _clock(fe, "req_headers") - nheads == sum(
        len(q.attributes.request.http.headers) for q in reqs)
    classes = fe.debug_vars()["snapshot"]["kernel"]["classes"]
    assert [c["device_width"] for c in classes] == [256]


# --- validation and fingerprint follow the class's width -----------------------

@pytest.mark.parametrize("width", cc.DFA_WIDTHS)
def test_boundary_witness_is_as_long_as_the_class_is_wide(width):
    dfa = compile_regex_dfa(er.agent_regex(5))
    wits, skipped = tv._table_witnesses(
        dfa.trans.astype(np.int64), dfa.accept, width)
    assert max(len(w) for w in wits) == width
    assert all(len(w) <= width for w in wits)
    assert (tv._simulate_kernel_scan(dfa.trans.astype(np.int64), dfa.accept,
                                     wits, width)
            == [re.search(er.agent_regex(5), w.decode("latin-1")) is not None
                for w in wits]).all()


def _narrowed(policy, width):
    one = copy.copy(policy)
    one.classes = tuple(dataclasses.replace(c, device_width=width)
                        for c in policy.classes)
    for memo in ("_config_byte_width", "_dfa_row_widths", "_enc_epoch"):
        one.__dict__.pop(memo, None)
    return one


def test_validation_and_epoch_follow_the_class_width(corpus):
    policy = corpus["policy"]
    assert (tv._dfa_row_widths(policy) == 256).all()
    cert, fails, _ = tv.certify_snapshot(policy)
    assert fails == []
    narrow = _narrowed(policy, 64)
    assert (tv._dfa_row_widths(narrow) == 64).all()
    assert (narrow.config_byte_width == 64).all() and narrow.byte_width == 64
    leaf = int(np.nonzero(policy.leaf_op == cc.OP_REGEX_DFA)[0][0])
    _, wide_n, _ = tv._check_dfa_leaf(policy, leaf, {})
    _, narrow_n, narrow_skipped = tv._check_dfa_leaf(narrow, leaf, {})
    assert wide_n >= narrow_n > 0
    # a row of a narrower class encodes a 100-byte value as overflow + host
    # columns, not as bytes: another encoding, so another epoch
    assert encoding_epoch(policy) != encoding_epoch(narrow)
    assert encoding_epoch(policy) == encoding_epoch(_narrowed(policy, 256))
