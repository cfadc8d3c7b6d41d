"""One staged buffer a launch (ISSUE 31): the native lane lays a launch's
request operands end to end in one uint8 host buffer, hands it to the
runtime in one transfer, and the served entry decodes it on the device.

Held here, on the CPU: the staged entry equals the six-operand entry bit for
bit over the warm grid, for both wire dtypes, with and without DFA operands,
for a full cut and a deduplicated one; a served launch counts one transfer
and the bytes it counted before; where the probe that guards byte order
fails, the warm fails and nothing launches; the warm grid compiles every
variant that serves; /debug/vars names the function that serves."""

import random
import re
import time
import types

import numpy as np
import pytest

from authorino_tpu import protos
from authorino_tpu.compiler import ConfigRules, compile_corpus
from authorino_tpu.compiler.compile import DFA_VALUE_BYTES
from authorino_tpu.compiler.encode import encode_batch_py
from authorino_tpu.compiler.pack import pack_batch
from authorino_tpu.expressions import All, Operator
from authorino_tpu.ops import pattern_eval as pe

from test_batch_stages import native_ledger
from test_native_frontend import (REQUESTS, _native_available, build_engine,
                                  grpc_call, make_req, response_key)
from test_own_config_eval import (K, _operands, _tenants, all_operand_corpus,
                                  all_operand_docs, tenant_rules)

MAX_BATCH = 64  # the slot arrays' rows: pads 64, 32, 16


# ---------------------------------------------------------------------------
# (a) the staged entry against the six-operand entry, from slot arrays
# ---------------------------------------------------------------------------

def _tenant_docs(rng, n_configs, n):
    rows = tenant_rules.requests({"n_configs": n_configs, "deny_share": 0.4},
                                 n, rng)
    docs = [{"request": {"method": r["method"], "url_path": r["path"],
                         "headers": r["headers"]}} for r in rows]
    return docs, [int(r["host"].split(".")[0][4:]) for r in rows]


def _slot(case, dtype):
    """(params, slot arrays as refresh() allocates them, rows filled) for
    one encoded cut of MAX_BATCH - 7 requests; ``dtype`` is the wire dtype
    of the ids (int32 is what a corpus past 32,767 strings ships)."""
    rng = random.Random(31)
    count = MAX_BATCH - 7
    cfgs = _tenants(48)
    if case == "no-dfa":
        # the same tenants without their two regexes: no DFA operands
        cfgs = [ConfigRules(name=c.name, evaluators=[(None, All(*[
            leaf for leaf in c.evaluators[0][1].children
            if leaf.operator is not Operator.MATCHES]))]) for c in cfgs]
    policy = compile_corpus(cfgs, members_k=16)
    docs, rows = _tenant_docs(rng, 48, count)
    db = pack_batch(policy, encode_batch_py(policy, docs, rows,
                                            batch_pad=MAX_BATCH),
                    trim_bytes=False)
    params = pe.to_device(policy)
    a = {"attrs_val": np.asarray(db.attrs_val).astype(dtype),
         "members": np.asarray(db.members_c).astype(dtype),
         "cpu_dense": np.asarray(db.cpu_dense).view(np.uint8).copy(),
         "config_id": np.asarray(db.config_id).astype(np.int32),
         "attr_bytes": np.zeros((MAX_BATCH, max(policy.n_byte_attrs, 1),
                                 policy.byte_width), dtype=np.uint8),
         "byte_ovf": np.zeros((MAX_BATCH, max(policy.n_byte_attrs, 1)),
                              dtype=np.uint8)}
    has_dfa = pe.has_dfa(params)
    if has_dfa:
        a["attr_bytes"][:] = np.asarray(db.attr_bytes)
        a["byte_ovf"][:] = np.asarray(db.byte_ovf)
    return params, a, count, has_dfa, policy.n_own_cpu


def _grid(has_dfa):
    effs = [16, 32, DFA_VALUE_BYTES] if has_dfa else [0]
    return [(pad, eff) for pad in (MAX_BATCH, 32, 16) for eff in effs]


@pytest.mark.parametrize("cut", ["full", "dedup"])
@pytest.mark.parametrize("dtype", [np.int16, np.int32])
@pytest.mark.parametrize("case", ["tenant_rules", "no-dfa"])
def test_staged_entry_equals_six_operand_entry_over_the_warm_grid(
        case, dtype, cut):
    import jax.numpy as jnp

    from authorino_tpu.runtime.native_frontend import NativeFrontend

    params, a, count, has_dfa, n_cpu = _slot(case, dtype)
    assert has_dfa == (case != "no-dfa")
    # one size: one class, whose view holds the same tables
    (view,) = [pe.class_view(params, c) for c in range(len(params["classes"]))]
    rec = types.SimpleNamespace(arrays=[a], layouts={}, classes=[{"cpu_cols": n_cpu}])
    answers = set()
    for pad, eff in _grid(has_dfa):
        if cut == "full":
            rows = slice(pad)
        else:
            # what _dispatch builds after dedup: the unique rows, padded by
            # repeating the first
            unique = list(range(0, min(count, pad) - 3, 2))
            rows = np.asarray(unique + [unique[0]] * (pad - len(unique)))
        views = NativeFrontend._operand_views(a, rows, eff, n_cpu)
        assert len(views) == (6 if has_dfa else 4)
        layout = NativeFrontend._stage_layout(rec, 0, pad, eff)
        assert layout is rec.layouts[(0, pad, eff)]
        assert NativeFrontend._stage_layout(rec, 0, pad, eff) is layout
        buf = pe.fuse_bytes(views)
        assert buf.dtype == np.uint8 and buf.ndim == 1
        # the same bytes, to the byte: what the ledger counts a launch
        assert buf.size == layout[-1][3] + layout[-1][4] == (
            pad * NativeFrontend._row_h2d_bytes(a, eff, n_cpu))
        assert [f[1] for f in layout[:2]] == [np.dtype(dtype).name] * 2
        staged = np.asarray(pe.eval_bitpacked_staged_jit(
            view, jnp.asarray(buf), layout))
        six = np.asarray(pe.eval_bitpacked_jit(
            params, *(jnp.asarray(v) for v in views)))
        np.testing.assert_array_equal(staged, six)
        answers |= set((staged[:, 0] & 1).tolist())
    assert answers == {0, 1}, "both verdicts were compared"


@pytest.mark.parametrize("cut", ["full", "dedup"])
@pytest.mark.parametrize("eff", [16, 32, 64])
def test_a_corpus_with_no_value_past_64_stages_what_a_64_wide_slot_stages(eff, cut):
    """ISSUE 38: the class of `tenant_rules` takes the 256-byte lane, so the
    slot's `attr_bytes` are [B, NB, 256] where the parent's were [B, NB,
    64]; no value of its rows passes 64, a launch's bucket is picked from
    the rows' own values, and the staged buffer is byte for byte what the
    64-wide slot staged."""
    from authorino_tpu.runtime.native_frontend import NativeFrontend

    params, a, count, has_dfa, n_cpu = _slot("tenant_rules", np.int16)
    assert has_dfa and a["attr_bytes"].shape[-1] == 256
    assert not a["attr_bytes"][..., DFA_VALUE_BYTES:].any()
    narrow = dict(a, attr_bytes=np.ascontiguousarray(
        a["attr_bytes"][..., :DFA_VALUE_BYTES]))
    rows = slice(32) if cut == "full" else np.asarray(
        list(range(0, 29, 2)) + [0] * 17)
    wide_buf = pe.fuse_bytes(NativeFrontend._operand_views(a, rows, eff, n_cpu))
    narrow_buf = pe.fuse_bytes(
        NativeFrontend._operand_views(narrow, rows, eff, n_cpu))
    assert wide_buf.tobytes() == narrow_buf.tobytes()
    assert (NativeFrontend._row_h2d_bytes(a, eff, n_cpu)
            == NativeFrontend._row_h2d_bytes(narrow, eff, n_cpu))


@pytest.mark.parametrize("pad", [16, 32, 64])
def test_staged_entry_equals_the_entry_on_all_operand_lanes(pad):
    """Every operand the kernel takes (relations, numerics, membership
    overflow, DFA bytes): the served staged entry decodes the engine lane's
    buffer with the same ``_defuse``."""
    rng = random.Random(7)
    policy = compile_corpus(all_operand_corpus(rng), members_k=K,
                            ovf_assist=True)
    docs = all_operand_docs(rng, n=pad - 3)
    rows = [rng.randrange(policy.n_configs) for _ in docs]
    db = pack_batch(policy, encode_batch_py(policy, docs, rows, batch_pad=pad))
    params = pe.to_device(policy)
    buf, layout = pe.fuse_batch(db)
    assert [f[0] for f in layout] == list(pe._FUSED_FIELDS)
    import jax.numpy as jnp

    want = np.asarray(pe.eval_bitpacked_jit(params, *_operands(db)))
    for entry in (pe.eval_bitpacked_staged_jit, pe.eval_fused_jit):
        np.testing.assert_array_equal(
            np.asarray(entry(params, jnp.asarray(buf), layout)), want)


def test_the_decode_has_a_scope_of_its_own_in_both_staged_entries():
    """`pattern_eval/defuse` beside the kernel's phases in the served
    entry's compiled module, and in the engine lane's, which runs the same
    body."""
    import jax.numpy as jnp

    params, a, _, _, n_cpu = _slot("tenant_rules", np.int16)
    from authorino_tpu.runtime.native_frontend import NativeFrontend

    rec = types.SimpleNamespace(arrays=[a], layouts={}, classes=[{"cpu_cols": n_cpu}])
    layout = NativeFrontend._stage_layout(rec, 0, 16, 16)
    buf = jnp.zeros(layout[-1][3] + layout[-1][4], dtype=jnp.uint8)
    for entry, scopes in (
            (pe.eval_bitpacked_staged_jit,
             ("defuse", "own_gather", "own_leaf_compares", "membership",
              "dfa_scan", "own_circuit", "bitpack")),
            (pe.eval_fused_jit, ("defuse", "bitpack"))):
        hlo = entry.lower(params, buf, layout).compile().as_text()
        # what a device trace's events carry: op_name, the scopes in it
        names = set(re.findall(r'op_name="([^"]+)"', hlo))
        top = f"jit({entry.__name__})/pattern_eval/"
        for scope in scopes:
            assert any(n.startswith(top) and f"/{scope}/" in n
                       for n in names), (entry.__name__, scope)


# ---------------------------------------------------------------------------
# (b) (c) (d): the served lane
# ---------------------------------------------------------------------------

needs_native = pytest.mark.skipif(
    not _native_available(), reason="native frontend unavailable")


@pytest.fixture(scope="module")
def served():
    from authorino_tpu.runtime.native_frontend import NativeFrontend

    engine = build_engine()
    # lane selection and brownout off: every cut with a miss launches on the
    # device lane; no verdict cache: a repeated request launches again
    fe = NativeFrontend(engine, port=0, max_batch=MAX_BATCH, window_us=2000,
                        lane_select=False, brownout=False,
                        verdict_cache_size=0)
    port = fe.start()
    assert fe.wait_warm(300.0) and fe.warm_error is None
    try:
        yield fe, port, engine
    finally:
        fe.stop()


def _ledger():
    return {f: native_ledger(f) for f in (
        "launches", "h2d_transfers", "h2d_bytes", "pad_rows")}


def _delta(before):
    return {f: v - before[f] for f, v in _ledger().items()}


KERNEL_REQS = [r for r in REQUESTS if r.attributes.request.http.host in (
    "fast-eq.test", "fast-cond.test", "fast-rx.test", "fast-rego.test")]


def _answers(port):
    return [response_key(grpc_call(port, r)) for r in KERNEL_REQS]


def _current(fe):
    with fe._lock:
        return fe._snaps[fe._next_snap_id - 1]


def _launched(fe, since, launches, timeout_s=10.0):
    """(pad, eff) of the launches among the batches committed after the
    first ``since``; a batch commits when `post` ends, after its answers
    are on the wire, so wait for ``launches`` of them."""
    deadline = time.monotonic() + timeout_s
    while True:
        ring = fe.batch_stages.to_json()
        at = {f: i for i, f in enumerate(ring["fields"])}
        rows = ring["batches"][:ring["committed"] - since]
        shapes = [(r[at["pad"]], r[at["eff"]]) for r in rows if r[at["pad"]]]
        if len(shapes) >= launches or time.monotonic() > deadline:
            return shapes
        time.sleep(0.005)


def _same_bytes(fe, since, d):
    """The ledger's bytes are pad x _row_h2d_bytes of every launch since."""
    shapes = _launched(fe, since, d["launches"])
    assert len(shapes) == d["launches"]
    a = _current(fe).arrays[0]
    assert d["h2d_bytes"] == sum(
        pad * fe._row_h2d_bytes(a, eff, _current(fe).classes[0]["cpu_cols"])
        for pad, eff in shapes)
    assert d["pad_rows"] == sum(pad for pad, _ in shapes)


@needs_native
def test_served_launch_is_one_transfer_of_the_same_bytes(served):
    fe, port, _ = served
    before, since = _ledger(), fe.batch_stages.to_json()["committed"]
    want = _answers(port)
    d = _delta(before)
    assert d["launches"] >= 1
    assert d["h2d_transfers"] == d["launches"], "one transfer a launch"
    _same_bytes(fe, since, d)
    assert any(k[0] == 0 for k in want) and any(k[0] != 0 for k in want)


@needs_native
def test_probe_failure_fails_the_warm_loudly(served, monkeypatch):
    """A backend whose byte order fails the one-time probe has no launch
    format: the swap gate's warm fails with an error that names the byte
    order, `wait_warm` answers False (the CLI stops there), and a cut
    served meanwhile launches nothing on the device; the dispatch-failure
    path answers it on the CPU twin."""
    fe, port, _ = served
    want = _answers(port)
    try:
        with monkeypatch.context() as m:
            m.setattr(pe, "_FUSED_OK", False)
            before = _ledger()
            fe.refresh()  # a new snapshot record: its layouts are built anew
            assert not fe.wait_warm(300.0)
            assert "byte order" in fe.warm_error
            assert _current(fe).layouts == {}
            assert response_key(grpc_call(port, KERNEL_REQS[0])) == want[0]
            d = _delta(before)
            assert d["launches"] == d["h2d_transfers"] == d["h2d_bytes"] == 0
    finally:
        fe.refresh()
        assert fe.wait_warm(300.0)
    assert fe.warm_error is None
    before = _ledger()
    assert _answers(port) == want
    d = _delta(before)
    assert d["h2d_transfers"] == d["launches"] >= 1


@needs_native
def test_overflowed_values_count_rows_and_the_kernel_reports_its_widths(served):
    """ISSUE 32: the ledger's `dfa_ovf_rows` counts a row once, however
    many of its values passed its class's byte width and whatever a retry does,
    beside the encoder's count of values; /debug/vars names the state axis
    of the served table store and one launch's temporaries."""
    fe, port, engine = served
    width = int(engine._snapshot.policy.config_byte_width.max())
    long_path = "/api/v3/ok" + "c" * (width + 9)
    assert response_key(grpc_call(port, make_req("fast-rx.test", path="/api/v3/ok")))[0] == 0
    rows0, values0 = native_ledger("dfa_ovf_rows"), fe.stats()["dfa_overflow"]
    all_rows0 = native_ledger("rows")
    for k in range(3):
        req = make_req("fast-rx.test", path=long_path + str(k))
        assert response_key(grpc_call(port, req))[0] == 0
    assert response_key(grpc_call(port, make_req("fast-rx.test", path="/api/nope")))[0] != 0
    assert native_ledger("dfa_ovf_rows") - rows0 == 3
    assert fe.stats()["dfa_overflow"] - values0 == 3
    assert native_ledger("rows") - all_rows0 == 4
    kernel = fe.debug_vars()["snapshot"]["kernel"]
    policy = engine._snapshot.policy
    assert kernel["dfa_states"] == policy.dfa_tables.shape[1] > 0
    assert kernel["dfa_states"] % 8 == 0
    assert kernel["launch_temp_bytes"] > 0
    assert kernel["launch_temp_bytes"] == _current(fe).launch_temp_bytes


def _burst(port, reqs):
    """The requests at once on one channel, so that they share cuts."""
    import grpc

    pb = protos.external_auth_pb2
    with grpc.insecure_channel(f"127.0.0.1:{port}") as ch:
        call = ch.unary_unary(
            "/envoy.service.auth.v3.Authorization/Check",
            request_serializer=pb.CheckRequest.SerializeToString,
            response_deserializer=pb.CheckResponse.FromString)
        futures = [call.future(r, timeout=60) for r in reqs]
        return [f.result() for f in futures]


@needs_native
def test_served_batches_compile_nothing_after_the_warm_grid(served):
    """50 batches and more of mixed (pad, eff): every one lands on a variant
    the warm grid compiled: no `miss`, no new jit variant."""
    fe, port, _ = served
    rec = _current(fe)
    assert set(fe._bucket_grid(rec)) <= rec.warm
    # one layout a (size class, bucket): this corpus is one class
    assert set(rec.layouts) == {(0, pad, eff)
                                for pad, eff in fe._bucket_grid(rec)}

    def misses():
        return sum(ch._value.get() for (_, _, outcome), ch
                   in list(fe._warm_children.items()) if outcome == "miss")

    compiled = pe.eval_bitpacked_staged_jit._cache_size()
    six = pe.eval_bitpacked_jit._cache_size()
    miss0, since = misses(), fe.batch_stages.to_json()["committed"]
    launches0 = native_ledger("launches")
    rng = random.Random(3)
    sizes = [1, 2, 5, 9, 17, 30, 33, 50, 64, 90]
    for i in range(60):
        n = sizes[i % len(sizes)]
        # the regex lane's value sets the byte width: short, middling, long
        tail = "x" * rng.choice([0, 0, 14, 30, 45])
        reqs = [make_req("fast-rx.test",
                         path=f"/api/v{rng.randrange(1, 99)}/ok{tail}",
                         headers={"x-n": str(j)}) for j in range(n)]
        got = _burst(port, reqs)
        assert all(r.status.code == 0 for r in got)
    launches = native_ledger("launches") - launches0
    assert launches >= 50
    shapes = set(_launched(fe, since, launches))
    assert len({p for p, _ in shapes}) >= 3 and len({e for _, e in shapes}) >= 2
    assert shapes <= rec.warm
    assert misses() == miss0
    assert pe.eval_bitpacked_staged_jit._cache_size() == compiled
    assert pe.eval_bitpacked_jit._cache_size() == six


@needs_native
def test_debug_vars_entry_names_the_served_jitted_function(served, monkeypatch):
    fe, port, _ = served
    real = pe.eval_bitpacked_staged_jit
    ran = []

    def spy(*a, **k):
        ran.append(real.__name__)
        return real(*a, **k)

    monkeypatch.setattr(pe, "eval_bitpacked_staged_jit", spy)
    assert grpc_call(port, make_req(
        "fast-eq.test", headers={"x-org": "acme"})).status.code == 0
    entry = fe.debug_vars()["snapshot"]["kernel"]["entry"]
    assert ran and all(entry in name for name in ran), (entry, ran)
