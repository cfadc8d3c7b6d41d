"""Kernel cost observatory (ISSUE 16, runtime/kernel_cost.py +
docs/performance.md "Kernel cost model").

The perf-guard plane: structural device-cost counts (launches, H2D/D2H
bytes, pad waste) pinned as EXACT values, not wall-clock thresholds —
they do not swing with the host, so a regression here is a real shape
change in the dispatch plane, never flake.

Covers: one-launch-per-batch parity with exact H2D/D2H byte math on the
engine lane; the planted-extra-launch self-test (the gate demonstrably
trips when a stray launch appears); zero-launch parity for fully
cache/dedup-resolved batches; host-lane serving folding rows with ZERO
device launches; mesh lane counting ONE collective launch per
shard-step (not one per shard); the native-frontend per-row H2D
arithmetic (pure shape math, unit-tested without the C++ module); the
warm-jit-grid entry-point audit (PR 1's grid predates the bitpacked /
fused readback and the PR 14 relations operands — pinned here so the
surface cannot drift again); the modeled-cost regression anomaly
(>=2x per-row jump -> cost-regression flight-recorder record, advisory);
the /debug/profile smoke; and the new metric families.

Deliberately import-light: collects on images without `cryptography`."""

from __future__ import annotations

import asyncio
import os

import numpy as np
import pytest

from authorino_tpu.compiler import ConfigRules
from authorino_tpu.compiler.compile import compile_corpus
from authorino_tpu.compiler.encode import encode_batch
from authorino_tpu.compiler.pack import pack_batch
from authorino_tpu.expressions import All, Operator, Pattern
from authorino_tpu.ops.pattern_eval import packed_width, staged_h2d_bytes
from authorino_tpu.runtime import EngineEntry, PolicyEngine
from authorino_tpu.runtime.flight_recorder import FlightRecorder
from authorino_tpu.runtime.kernel_cost import (
    LEDGER,
    CostModel,
    entry_points,
    params_fingerprint,
)


def run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


def sample(name, labels=None):
    from prometheus_client import REGISTRY

    v = REGISTRY.get_sample_value(name, labels or {})
    return 0.0 if v is None else v


# the raw (underived) ledger fields — deltas over these are exact
RAW = ("batches", "launches", "zero_launch_batches", "rows",
       "device_rows", "h2d_bytes", "d2h_bytes", "pad_rows",
       "pad_waste_rows", "dedup_avoided_rows", "cache_avoided_rows")


def delta(before, after):
    return {k: after[k] - before[k] for k in RAW}


def assert_launch_parity(d):
    """The structural perf-regression gate: every batch that reached the
    device performed exactly ONE launch (ROADMAP item 2's one-dispatch
    target), and cache/dedup-resolved batches performed exactly zero.  A
    failover re-dispatch, a stray warm-up launch, or an un-fused operand
    upload all break this equality."""
    assert d["launches"] == d["batches"] - d["zero_launch_batches"], (
        f"launch parity broken: {d['launches']} launches for "
        f"{d['batches']} batches ({d['zero_launch_batches']} zero-launch)")


RULE = All(
    Pattern("request.method", Operator.EQ, "GET"),
    Pattern("auth.identity.org", Operator.EQ, "acme"),
)


def build_engine(**kw) -> PolicyEngine:
    kw.setdefault("max_batch", 32)
    kw.setdefault("lane_select", False)
    kw.setdefault("batch_dedup", False)
    kw.setdefault("verdict_cache_size", 0)
    engine = PolicyEngine(members_k=4, mesh=None, **kw)
    engine.apply_snapshot([
        EngineEntry(id="c", hosts=["c"], runtime=None,
                    rules=ConfigRules(name="c", evaluators=[(None, RULE)]))
    ])
    return engine


def doc(i: int, allow=True):
    return {"request": {"method": "GET"},
            "auth": {"identity": {"org": "acme" if allow else "evil",
                                  "tag": f"t{i}"}}}


async def submit_all(engine, docs):
    outs = await asyncio.gather(*(engine.submit(d, "c") for d in docs))
    return [bool(rule[0]) for rule, _ in outs]


def per_row_h2d(policy) -> int:
    """Exact fused-staging bytes for ONE padded row of this policy —
    the same encode/pack path the engine ships, at batch_pad=1."""
    db1 = pack_batch(policy, encode_batch(policy, [doc(0)], [0],
                                          batch_pad=1))
    return staged_h2d_bytes(db1)


# ---------------------------------------------------------------------------
# engine lane: exact structural pins + the planted-launch self-test
# ---------------------------------------------------------------------------

class TestEngineLane:
    def test_one_launch_per_batch_exact_bytes(self):
        m0 = {k: sample(f"auth_server_kernel_{k}_total", {"lane": "engine"})
              for k in ("launches", "h2d_bytes", "d2h_bytes",
                        "pad_waste_rows")}

        async def go():
            engine = build_engine()
            b0 = LEDGER.snapshot("engine")
            assert await submit_all(engine, [doc(i) for i in range(5)]) \
                == [True] * 5
            return engine, delta(b0, LEDGER.snapshot("engine"))

        engine, d = run(go())
        policy = engine._snapshot.policy
        E = int(policy.eval_rule.shape[1])
        W = packed_width(1 + 2 * E)

        assert d["rows"] == 5
        assert d["device_rows"] == 5          # no dedup/cache configured
        assert d["batches"] >= 1
        assert d["zero_launch_batches"] == 0
        assert_launch_parity(d)
        # pad bucketing holds whatever the cut count: bytes are LINEAR in
        # the padded rows, so the per-row pins are exact even if the loop
        # split the 5 submissions across cuts
        assert d["pad_rows"] >= 5
        assert d["pad_waste_rows"] == d["pad_rows"] - 5
        assert d["h2d_bytes"] == d["pad_rows"] * per_row_h2d(policy)
        assert d["d2h_bytes"] == d["pad_rows"] * W
        assert d["dedup_avoided_rows"] == 0
        assert d["cache_avoided_rows"] == 0

        # the counter families moved by exactly the ledger deltas
        assert sample("auth_server_kernel_launches_total",
                      {"lane": "engine"}) - m0["launches"] == d["launches"]
        assert sample("auth_server_kernel_h2d_bytes_total",
                      {"lane": "engine"}) - m0["h2d_bytes"] == d["h2d_bytes"]
        assert sample("auth_server_kernel_d2h_bytes_total",
                      {"lane": "engine"}) - m0["d2h_bytes"] == d["d2h_bytes"]
        assert sample("auth_server_kernel_pad_waste_rows_total",
                      {"lane": "engine"}) - m0["pad_waste_rows"] \
            == d["pad_waste_rows"]

        # derived ratios on the /debug/vars block
        lane = LEDGER.to_json()["engine"]
        assert lane["launches_per_batch"] <= 1.0
        assert lane["d2h_bytes_per_pad_row"] >= 1.0

    def test_planted_extra_launch_trips_gate(self):
        async def go():
            engine = build_engine()
            b0 = LEDGER.snapshot("engine")
            await submit_all(engine, [doc(i) for i in range(3)])
            # plant a stray launch, exactly what a failover re-dispatch
            # or an accidental double-dispatch would record
            LEDGER.observe_launch("engine")
            return delta(b0, LEDGER.snapshot("engine"))

        d = run(go())
        assert d["launches"] == d["batches"] + 1
        with pytest.raises(AssertionError, match="launch parity"):
            assert_launch_parity(d)

    def test_dedup_collapses_device_rows(self):
        async def go():
            engine = build_engine(batch_dedup=True, verdict_cache_size=256)
            b0 = LEDGER.snapshot("engine")
            assert await submit_all(engine, [doc(7)] * 4) == [True] * 4
            d1 = delta(b0, LEDGER.snapshot("engine"))
            b1 = LEDGER.snapshot("engine")
            assert await submit_all(engine, [doc(7)] * 4) == [True] * 4
            return d1, delta(b1, LEDGER.snapshot("engine"))

        d1, d2 = run(go())
        # first round: identical rows collapse before the launch
        assert d1["rows"] == 4
        assert d1["device_rows"] >= 1
        assert (d1["dedup_avoided_rows"] + d1["cache_avoided_rows"]
                == 4 - d1["device_rows"])
        assert_launch_parity(d1)
        # second round: every row verdict-cache-resolved -> ZERO launches,
        # ZERO device rows, ZERO bytes — and the batch still counts
        assert d2["rows"] == 4
        assert d2["cache_avoided_rows"] == 4
        assert d2["device_rows"] == 0
        assert d2["launches"] == 0
        assert d2["zero_launch_batches"] == d2["batches"] >= 1
        assert d2["h2d_bytes"] == 0 and d2["d2h_bytes"] == 0
        assert_launch_parity(d2)

    def test_debug_vars_block_and_entry_points(self):
        engine = build_engine()
        kc = engine.debug_vars()["kernel_cost"]
        assert set(kc) == {"ledger", "modeled", "entry_points"}
        assert kc["ledger"].keys() <= {"engine", "host", "mesh", "native"}
        names = [e["entry"] for e in kc["entry_points"]]
        assert names == ["eval_bitpacked", "eval_fused",
                         "eval_bitpacked_staged"]
        for e in kc["entry_points"]:
            assert e["operands"][:4] == ["attrs_val", "members_c",
                                         "cpu_dense", "config_id"]

    def test_modeled_cost_populated_at_reconcile(self):
        engine = build_engine()
        modeled = engine.debug_vars()["kernel_cost"]["modeled"]
        assert modeled["component"] == "engine"
        assert modeled["generations_analyzed"] >= 1
        cur = modeled["current"]
        assert cur["regressions"] == []
        e = cur["entries"]["eval_bitpacked"]
        assert e["flops_per_row"] > 0
        assert e["bytes_per_row"] > 0
        assert sample("auth_server_kernel_modeled_flops_per_row",
                      {"entry": "eval_bitpacked"}) > 0


# ---------------------------------------------------------------------------
# host lane: light load served host-side = rows folded, ZERO launches
# ---------------------------------------------------------------------------

class TestHostLane:
    def test_host_lane_zero_device_launches(self):
        async def go():
            engine = build_engine(lane_select=True, max_batch=8)
            # teach the cost model a fast host and a slow device, and pin
            # exploration off: the next small cuts decide HOST
            engine.lanes.cost.observe_host(1e-3, 10)
            engine.lanes.cost.observe_device(0.1, 8)
            engine._device_ewma = 0.1
            engine.lanes.explore_every = 0
            h0 = LEDGER.snapshot("host")
            e0 = LEDGER.snapshot("engine")
            assert await submit_all(engine, [doc(i) for i in range(4)]) \
                == [True] * 4
            return (delta(h0, LEDGER.snapshot("host")),
                    delta(e0, LEDGER.snapshot("engine")))

        dh, de = run(go())
        assert dh["rows"] == 4
        assert dh["batches"] >= 1
        # a host-lane batch is structurally free of the device: no
        # launches, no bytes on the link, no padded rows burned
        assert dh["launches"] == 0
        assert dh["device_rows"] == 0
        assert dh["h2d_bytes"] == 0 and dh["d2h_bytes"] == 0
        assert dh["pad_rows"] == 0
        assert de["launches"] == 0 and de["batches"] == 0


# ---------------------------------------------------------------------------
# mesh lane: ONE collective launch per shard-step, not one per shard
# ---------------------------------------------------------------------------

@pytest.mark.mesh
class TestMeshLane:
    def test_one_collective_launch_per_shard_step(self, mesh_devices):
        from authorino_tpu.parallel import build_mesh

        async def go():
            mesh = build_mesh(n_devices=8, dp=2)
            engine = PolicyEngine(max_batch=32, members_k=4, mesh=mesh,
                                  lane_select=False, batch_dedup=False,
                                  verdict_cache_size=0)
            engine.apply_snapshot([
                EngineEntry(id=f"c{i}", hosts=[f"c{i}"], runtime=None,
                            rules=ConfigRules(
                                name=f"c{i}", evaluators=[(None, RULE)]))
                for i in range(4)
            ])
            m0 = LEDGER.snapshot("mesh")
            e0 = LEDGER.snapshot("engine")
            outs = await asyncio.gather(
                *(engine.submit(doc(i), f"c{i % 4}") for i in range(6)))
            assert [bool(rule[0]) for rule, _ in outs] == [True] * 6
            dv = engine.debug_vars()
            return (delta(m0, LEDGER.snapshot("mesh")),
                    delta(e0, LEDGER.snapshot("engine")), dv)

        dm, de, dv = run(go())
        assert dm["rows"] == 6
        assert dm["device_rows"] == 6
        assert dm["batches"] >= 1
        # the 2x4 mesh runs ONE psum-merged program per shard-step: the
        # parity gate would trip at 8x if launches were counted per shard
        assert_launch_parity(dm)
        assert dm["h2d_bytes"] > 0 and dm["d2h_bytes"] > 0
        # sharded batches fold into the mesh lane, never the engine lane
        assert de["batches"] == 0 and de["launches"] == 0

        ep = dv["kernel_cost"]["entry_points"]
        assert [e["entry"] for e in ep] == ["sharded_step"]
        assert ep[0]["n_shards"] >= 2
        assert "one launch per shard-step" in ep[0]["kind"]


# ---------------------------------------------------------------------------
# native frontend: per-row H2D arithmetic is pure shape math — unit-tested
# here without the C++ module; the full-lane pins ride the native suite
# ---------------------------------------------------------------------------

class TestNativeRowBytes:
    def _arrays(self):
        return {
            "attrs_val": np.zeros((4, 3), np.int32),      # 12 B/row
            "members": np.zeros((4, 2, 4), np.int32),     # 32 B/row
            "cpu_dense": np.zeros((4, 5), np.bool_),      # 5 B/row
            "config_id": np.zeros((4,), np.int32),        # 4 B/row
            "attr_bytes": np.zeros((4, 2, 8), np.uint8),  # eff-trimmed
            "byte_ovf": np.zeros((4, 2), np.bool_),       # 2 B/row
            "shard_of": np.zeros((4,), np.int32),         # 4 B/row
        }

    def test_row_h2d_bytes_exact(self):
        nf = pytest.importorskip(
            "authorino_tpu.runtime.native_frontend",
            reason="native frontend module import needs cryptography")
        NativeFrontend = nf.NativeFrontend

        a = self._arrays()
        base = 12 + 32 + 5 + 4
        assert NativeFrontend._row_h2d_bytes(a, 0, 5) == base
        # DFA lane ships the eff-trimmed byte columns + overflow flags
        assert NativeFrontend._row_h2d_bytes(a, 6, 5) \
            == base + 2 * 6 + 2
        # a size class stages its own CPU columns, not the corpus's widest
        assert NativeFrontend._row_h2d_bytes(a, 6, 2) \
            == base - 3 + 2 * 6 + 2
        # mesh slots are counted by the sharded model's own dispatch_full
        # (the native lane hands it the slot arrays): routing adds one
        # shard_of element per row
        from authorino_tpu.parallel.sharded_eval import (
            ShardedPolicyModel,
            _ShardedEncoded,
        )

        enc = _ShardedEncoded(
            attrs_val=a["attrs_val"], members_c=a["members"],
            cpu_dense=a["cpu_dense"],
            attr_bytes=np.ascontiguousarray(a["attr_bytes"][..., :6]),
            byte_ovf=a["byte_ovf"], shard_of=a["shard_of"],
            row_of=a["config_id"], host_fallback=np.zeros((4,), bool))
        assert ShardedPolicyModel._encoded_h2d_bytes(None, enc) \
            == 4 * (base + 2 * 6 + 2 + 4)


class TestNativeResolveCount:
    """``resolved_native``: one a cut completed through the native lane's
    one-call resolve (``fe_resolve_cut``), counted on the cut's lane beside
    ``batches``; the mesh step and a degraded slot complete the old way and
    count none.  No server runs, so the completions complete nothing."""

    B, SNAP = 16, 0x5EED_0016

    @pytest.mark.parametrize("path,counted", [
        ("served", 1), ("cache-only", 1), ("mesh-step", 0), ("degraded", 0)])
    def test_a_served_cut_counts_one_and_a_degraded_slot_none(self, path,
                                                              counted):
        import time

        nf = pytest.importorskip(
            "authorino_tpu.runtime.native_frontend",
            reason="native frontend module import needs cryptography")
        from authorino_tpu.native import load_library
        from authorino_tpu.native.verdict_cache import (NativeVerdictCache,
                                                        key_segments,
                                                        plan_cut)

        lib = load_library()
        if lib is None:
            pytest.skip("native library unavailable")
        B, SNAP = self.B, self.SNAP
        fe = nf.NativeFrontend(PolicyEngine(max_batch=B, mesh=None), port=0,
                               max_batch=B, slo_ms=250.0, lane_select=False)
        fe._mod = lib
        rec = nf._SnapRec(snap_id=SNAP, policy=None, params=None,
                          encoder=None)
        rows = np.arange(B, dtype=np.int32)
        packed = np.ones((B, 1), dtype=np.uint8)
        fan, pad, handle = None, B, packed
        if path == "served":
            handle = nf._Launched()
            handle.parts.append((packed, None, B, 0))
        elif path == "cache-only":
            cache = NativeVerdictCache(2 * B)
            keys = rows.view(np.uint8).reshape(B, 4)
            segs = key_segments([keys])
            tokens, eligible = np.zeros(B, np.uint64), np.ones(B, bool)
            warm = plan_cut(cache, segs, B, tokens, eligible, True)
            cache.commit(warm.ticket, np.ones(B, np.uint8), None)
            fan = plan_cut(cache, segs, B, tokens, eligible, True)
            assert len(fan.unique_rows) == 0
            fe._verdict_cache, handle, pad = cache, nf._Launched(), 0
        kept = []
        post = fe._post_complete_telemetry
        fe._post_complete_telemetry = lambda *a, **k: (
            kept.append(a[6].tolist()), post(*a, **k))
        before = LEDGER.snapshot("native")
        if path == "degraded":
            fe._degrade_slot(rec, SNAP, 0, B)
        else:
            bt = fe.batch_stages.begin(SNAP, 0, B)
            bt.ready()
            fe._complete_device_batch(rec, SNAP, 0, B, pad, 0, rows, None,
                                      handle, time.monotonic(),
                                      time.time_ns(), fan, 0, bt)
        d = LEDGER.snapshot("native")
        assert d["resolved_native"] - before["resolved_native"] == counted
        # every row allowed, as the readback (or the cache) said; the
        # degraded slot has no policy and denies fail-closed, keeping none
        assert kept == ([] if path == "degraded" else [[1] * B])


# ---------------------------------------------------------------------------
# warm-jit-grid audit: the entry points a snapshot can dispatch through,
# with the operand lanes each stages (PR 1's grid surface, re-pinned)
# ---------------------------------------------------------------------------

class TestEntryPointAudit:
    def _cfg(self, *leaves):
        return ConfigRules(name="a", evaluators=[(None, All(*leaves))])

    def test_plain_corpus_base_operands(self):
        pol = compile_corpus([self._cfg(
            Pattern("m", Operator.EQ, "GET"))],
            members_k=4, ovf_assist=False)
        ep = entry_points(policy=pol)
        assert [e["entry"] for e in ep] == [
            "eval_bitpacked", "eval_fused", "eval_bitpacked_staged"]
        for e in ep:
            assert e["operands"] == ["attrs_val", "members_c",
                                     "cpu_dense", "config_id"]

    def test_regex_corpus_adds_dfa_operands(self):
        pol = compile_corpus([self._cfg(
            Pattern("p", Operator.MATCHES, r"^/api/v1"))],
            members_k=4, ovf_assist=False)
        ops = entry_points(policy=pol)[0]["operands"]
        assert "attr_bytes" in ops and "byte_ovf" in ops
        assert "attrs_num" not in ops and "rel_rows" not in ops

    def test_numeric_corpus_adds_numeric_operands(self):
        pol = compile_corpus([self._cfg(
            Pattern("v.x", Operator.GT, "10"))],
            members_k=4, ovf_assist=False)
        ops = entry_points(policy=pol)[0]["operands"]
        assert "attrs_num" in ops and "num_valid" in ops

    def test_relations_corpus_adds_relation_operands(self):
        from authorino_tpu.expressions import InGroup
        from authorino_tpu.relations.closure import RelationClosure

        rel = RelationClosure([("alice", "staff"), ("staff", "org")])
        pol = compile_corpus([self._cfg(
            InGroup("auth.identity.sub", "org", rel))],
            members_k=4, ovf_assist=True)
        ops = entry_points(policy=pol)[0]["operands"]
        assert "rel_rows" in ops
        assert "member_ovf" in ops  # ovf_assist lane

    def test_no_snapshot_is_empty(self):
        assert entry_points() == []


# ---------------------------------------------------------------------------
# modeled-cost regression gate: >=2x per-row jump between generations ->
# cost-regression anomaly on the flight recorder (advisory, never blocks)
# ---------------------------------------------------------------------------

class TestCostRegression:
    @staticmethod
    def _model(flops_per_row):
        def fake(*, policy=None, params=None, sharded=None, pad=16):
            return {"eval_bitpacked": {
                "entry": "eval_bitpacked", "pad": pad, "eff": 0,
                "flops": flops_per_row[0] * pad,
                "bytes_accessed": 100.0 * pad,
                "flops_per_row": flops_per_row[0],
                "bytes_per_row": 100.0,
            }}
        return fake

    def test_regression_records_anomaly(self, tmp_path):
        frec = FlightRecorder(capacity=32, dump_dir=str(tmp_path),
                              min_dump_interval_s=0.0)
        cm = CostModel("engine")
        f = [1000.0]
        cm._model_entries = self._model(f)
        rec1 = cm.analyze(1, recorder=frec)
        assert rec1["regressions"] == []

        f[0] = 2000.0  # exactly the 2x gate
        rec2 = cm.analyze(2, recorder=frec)
        assert len(rec2["regressions"]) == 1
        r = rec2["regressions"][0]
        assert r["entry"] == "eval_bitpacked"
        assert r["axis"] == "flops_per_row"
        assert r["ratio"] == 2.0
        assert r["previous_generation"] == 1

        tail = frec.to_json()["tail"]
        hits = [e for e in tail if e["kind"] == "cost-regression"]
        assert len(hits) == 1
        assert hits[0]["lane"] == "engine"
        assert hits[0]["detail"]["generation"] == 2

        js = cm.to_json()
        assert js["regressions_seen"] == 1
        assert js["last_regression"]["entry"] == "eval_bitpacked"

    def test_below_threshold_is_silent(self, tmp_path):
        frec = FlightRecorder(capacity=32, dump_dir=str(tmp_path),
                              min_dump_interval_s=0.0)
        cm = CostModel("engine")
        f = [1000.0]
        cm._model_entries = self._model(f)
        cm.analyze(1, recorder=frec)
        f[0] = 1999.0  # 1.999x: under the gate
        rec2 = cm.analyze(2, recorder=frec)
        assert rec2["regressions"] == []
        assert not [e for e in frec.to_json()["tail"]
                    if e["kind"] == "cost-regression"]

    def test_same_generation_analyzed_once(self):
        cm = CostModel("engine")
        f = [1000.0]
        cm._model_entries = self._model(f)
        rec1 = cm.analyze(5)
        f[0] = 9000.0  # canary promote re-installs generation 5
        rec2 = cm.analyze(5)
        assert rec2 is rec1
        assert cm.to_json()["generations_analyzed"] == 1

    def test_fingerprint_shapes(self):
        fp = params_fingerprint({"a": np.zeros((2, 3), np.int16),
                                 "b": None})
        assert isinstance(fp, tuple) and fp
        assert fp == params_fingerprint({"a": np.ones((2, 3), np.int16),
                                         "b": None})
        assert fp != params_fingerprint({"a": np.zeros((2, 4), np.int16),
                                         "b": None})


# ---------------------------------------------------------------------------
# /debug/profile smoke (armed): 200 + trace dir on disk; bad seconds 400
# ---------------------------------------------------------------------------

class TestDebugProfile:
    def test_profile_smoke_and_validation(self):
        from aiohttp.test_utils import TestClient, TestServer

        from authorino_tpu.service.http_server import build_app

        engine = build_engine()

        async def body():
            client = TestClient(TestServer(
                build_app(engine, enable_profile=True)))
            await client.start_server()
            try:
                resp = await client.get("/debug/profile?seconds=0.1")
                ok = resp.status, await resp.json()
                bad = (await client.get(
                    "/debug/profile?seconds=abc")).status
                nan = (await client.get(
                    "/debug/profile?seconds=nan")).status
                return ok, bad, nan
            finally:
                await client.close()

        (status, js), bad, nan = run(body())
        assert status == 200
        assert js["seconds"] == 0.1
        assert os.path.isdir(js["trace_dir"])
        assert bad == 400 and nan == 400
