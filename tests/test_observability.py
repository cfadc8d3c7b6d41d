"""Batch-aware observability suite (ISSUE 1): device/engine telemetry on
/metrics, span-linked batch tracing through the built-in OTLP/JSON exporter,
the /debug/* introspection surface, and the satellite fixes (stranded OTLP
enqueue, duplicate metric registration, observe_bucketed fallback, C++/Python
stage-bucket parity).

Deliberately import-light: this file must collect on images without
`cryptography` (the evaluators.identity tree), so identity/authorization
evaluators are minimal fakes over evaluators.base."""

from __future__ import annotations

import asyncio
import math
import re
import threading
import time
from pathlib import Path

import pytest

from authorino_tpu.compiler import ConfigRules
from authorino_tpu.expressions import All, Operator, Pattern
from authorino_tpu.runtime import EngineEntry, PolicyEngine
from authorino_tpu.utils import metrics as metrics_mod
from authorino_tpu.utils import tracing as tracing_mod


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


RULE = All(
    Pattern("request.method", Operator.EQ, "GET"),
    Pattern("auth.identity.org", Operator.EQ, "acme"),
)


def build_engine(**kw) -> PolicyEngine:
    engine = PolicyEngine(max_batch=32, members_k=4,
                          mesh=None, **kw)
    engine.apply_snapshot([
        EngineEntry(id="c", hosts=["c"], runtime=None,
                    rules=ConfigRules(name="c", evaluators=[(None, RULE)]))
    ])
    return engine


def doc(allow=True):
    return {"request": {"method": "GET"},
            "auth": {"identity": {"org": "acme" if allow else "evil"}}}


# ---------------------------------------------------------------------------
# collector: OTLP/JSON sink on a background thread's own loop, so tests can
# exercise both loop-context and loop-less exporter paths against it
# ---------------------------------------------------------------------------

def start_collector():
    from aiohttp import web

    got: list = []
    holder: dict = {}
    started = threading.Event()

    def runner():
        async def main():
            app = web.Application()

            async def v1_traces(request):
                got.append(await request.json())
                return web.json_response({})

            app.router.add_post("/v1/traces", v1_traces)
            r = web.AppRunner(app)
            await r.setup()
            site = web.TCPSite(r, "127.0.0.1", 0)
            await site.start()
            holder["port"] = site._server.sockets[0].getsockname()[1]
            holder["loop"] = asyncio.get_running_loop()
            holder["stop"] = asyncio.Event()
            started.set()
            await holder["stop"].wait()
            await r.cleanup()

        asyncio.run(main())

    t = threading.Thread(target=runner, daemon=True)
    t.start()
    assert started.wait(10)
    holder["thread"] = t
    holder["endpoint"] = f"http://127.0.0.1:{holder['port']}"
    return got, holder


def stop_collector(holder):
    holder["loop"].call_soon_threadsafe(holder["stop"].set)
    holder["thread"].join(timeout=10)


def collected_spans(got):
    out = []
    for payload in got:
        for rs in payload.get("resourceSpans", []):
            for ss in rs.get("scopeSpans", []):
                out.extend(ss.get("spans", []))
    return out


# ---------------------------------------------------------------------------
# tentpole: engine batch telemetry lands on /metrics; /debug/vars answers
# ---------------------------------------------------------------------------

class TestEngineTelemetry:
    def test_metrics_exposition_does_not_hold_the_event_loop(self, monkeypatch):
        """PR 28: at 10,000 AuthConfigs the exposition is seconds of Python
        (27 MB of per-AuthConfig series); the loop that serves it also
        answers /readyz, /debug/vars and starts /debug/profile, so it runs
        off the loop: a slow scrape must not delay the others."""
        import threading

        import prometheus_client
        from aiohttp.test_utils import TestClient, TestServer

        from authorino_tpu.service.http_server import build_app

        scraping, release = threading.Event(), threading.Event()

        def slow_exposition(*a, **k):
            scraping.set()
            assert release.wait(30)
            return b"# slow\n"

        monkeypatch.setattr(prometheus_client, "generate_latest", slow_exposition)

        async def body():
            client = TestClient(TestServer(build_app(build_engine())))
            await client.start_server()
            try:
                scrape = asyncio.ensure_future(client.get("/metrics"))
                while not scraping.is_set():
                    await asyncio.sleep(0.01)
                # the scrape is in flight and blocked: the loop still answers
                resp = await asyncio.wait_for(client.get("/debug/vars"), 10)
                assert resp.status == 200 and not scrape.done()
                release.set()
                resp = await scrape
                return resp.status, await resp.read()
            finally:
                release.set()
                await client.close()

        assert run(body()) == (200, b"# slow\n")

    def test_batch_histograms_and_debug_vars_via_http(self):
        """Acceptance: requests through the engine surface batch-occupancy /
        device-dispatch histograms on /metrics, drained native-frontend
        counters appear, and /debug/vars returns queue depth + config
        generation — all through the real HTTP endpoints."""
        from aiohttp.test_utils import TestClient, TestServer

        from authorino_tpu.service.http_server import build_app

        engine = build_engine()
        before = {
            "size": sample("auth_server_batch_size_count", {"lane": "engine"}),
            "occ": sample("auth_server_batch_pad_occupancy_count", {"lane": "engine"}),
            "wait": sample("auth_server_batch_queue_wait_seconds_count", {"lane": "engine"}),
            "disp": sample("auth_server_device_dispatch_seconds_count", {"lane": "engine"}),
            "fb": sample("auth_server_batch_host_fallback_count", {"lane": "engine"}),
            "fb_sum": sample("auth_server_batch_host_fallback_sum", {"lane": "engine"}),
        }

        async def body():
            outs = await asyncio.gather(*(engine.submit(doc(), "c")
                                          for _ in range(24)))
            for rule, skipped in outs:
                assert bool(rule[0]) and not bool(skipped[0])

            # drained native-frontend counters: the same drain class the
            # frontend's periodic thread runs, fed a stub fe_stats() here
            # (the C++ library is not buildable on every test image)
            drain = metrics_mod.NativeStatsDrain()
            drain.fold({"fast": 3, "slow": 1, "slow_pending": 2, "slow_queued": 1})
            drain.fold({"fast": 7, "slow": 1, "slow_pending": 5, "slow_queued": 0})

            client = TestClient(TestServer(build_app(engine)))
            await client.start_server()
            try:
                resp = await client.get("/metrics")
                assert resp.status == 200
                text = await resp.text()

                resp = await client.get("/debug/vars")
                assert resp.status == 200
                dv = await resp.json()
            finally:
                await client.close()
            return text, dv

        text, dv = run(body())

        # at least one micro-batch ran: every per-batch series moved
        assert sample("auth_server_batch_size_count", {"lane": "engine"}) > before["size"]
        assert sample("auth_server_batch_pad_occupancy_count", {"lane": "engine"}) > before["occ"]
        assert sample("auth_server_batch_queue_wait_seconds_count", {"lane": "engine"}) > before["wait"]
        assert sample("auth_server_device_dispatch_seconds_count", {"lane": "engine"}) > before["disp"]
        assert sample("auth_server_batch_host_fallback_count", {"lane": "engine"}) > before["fb"]
        # no fallback rows in this corpus: the per-batch counts are all 0
        assert sample("auth_server_batch_host_fallback_sum", {"lane": "engine"}) == before["fb_sum"]
        # occupancy is a ratio ≤ 1.0
        occ_sum = sample("auth_server_batch_pad_occupancy_sum", {"lane": "engine"})
        occ_n = sample("auth_server_batch_pad_occupancy_count", {"lane": "engine"})
        assert 0.0 < occ_sum / occ_n <= 1.0

        # the scrape text carries the new families + the drained native events
        assert 'auth_server_batch_size_bucket{' in text
        assert 'auth_server_device_dispatch_seconds_bucket{' in text
        assert 'auth_server_native_frontend_events_total{event="fast"}' in text
        assert sample("auth_server_native_frontend_events_total", {"event": "fast"}) >= 7.0
        # queue gauges show the LAST folded backlog
        assert sample("auth_server_native_frontend_queue_depth", {"queue": "slow_pending"}) == 5.0
        assert sample("auth_server_native_frontend_queue_depth", {"queue": "slow_queued"}) == 0.0

        # /debug/vars: config generation + queue depth + snapshot shape
        assert dv["engine"]["generation"] >= 1
        assert dv["engine"]["queue_depth"] == 0  # all futures resolved
        assert dv["engine"]["snapshot"]["configs"] == 1
        assert dv["engine"]["snapshot"]["compiled_configs"] == 1
        assert "pid" in dv["process"]

        # snapshot generation gauge followed apply_snapshot
        assert sample("auth_server_snapshot_generation",
                      {"component": "engine"}) >= 1.0

    def test_debug_profile_disabled_by_default(self):
        from aiohttp.test_utils import TestClient, TestServer

        from authorino_tpu.service.http_server import build_app

        engine = build_engine()

        async def body():
            client = TestClient(TestServer(build_app(engine)))
            await client.start_server()
            try:
                resp = await client.get("/debug/profile?seconds=0.1")
                return resp.status
            finally:
                await client.close()

        assert run(body()) == 403


# ---------------------------------------------------------------------------
# tentpole: span-linked batch tracing via the built-in OTLP/JSON exporter
# ---------------------------------------------------------------------------

class TestDeviceBatchSpans:
    def test_device_batch_span_links_request_spans(self):
        got, holder = start_collector()
        try:
            assert tracing_mod.setup_tracing(holder["endpoint"]) is True
            assert tracing_mod._native_exporter is not None
            engine = build_engine()

            async def body():
                spans = [tracing_mod.RequestSpan.from_headers({}, f"rid-{i}")
                         for i in range(6)]
                outs = await asyncio.gather(*(
                    engine.submit(doc(), "c", span=s) for s in spans))
                assert all(bool(r[0]) for r, _ in outs)
                await tracing_mod.shutdown_tracing()  # cancel task + flush
                from authorino_tpu.utils.http import close_sessions

                await close_sessions()
                return spans

            spans = run(body())
            exported = collected_spans(got)
            batches = [s for s in exported if s["name"] == "DeviceBatch"]
            assert batches, f"no DeviceBatch span exported: {exported}"
            links = [l for b in batches for l in b.get("links", [])]
            linked_ids = {l["spanId"] for l in links}
            assert {s.span_id for s in spans} <= linked_ids
            assert {l["traceId"] for l in links} >= {s.trace_id for s in spans}
            attrs = {a["key"]: a["value"] for b in batches
                     for a in b["attributes"]}
            assert "batch.size" in attrs and "batch.pad" in attrs
            assert "batch.eff" in attrs
            total = sum(int(a["value"]["intValue"])
                        for b in batches for a in b["attributes"]
                        if a["key"] == "batch.size")
            assert total == 6
            # pad is the pow2 bucket ≥ size
            for b in batches:
                ba = {a["key"]: int(a["value"]["intValue"])
                      for a in b["attributes"]}
                assert ba["batch.pad"] >= ba["batch.size"]
                assert int(b["endTimeUnixNano"]) >= int(b["startTimeUnixNano"])
        finally:
            tracing_mod._native_exporter = None
            stop_collector(holder)

    def test_phase_child_spans_under_request_span(self):
        from authorino_tpu.authjson import CheckRequestModel, HttpRequestAttributes
        from authorino_tpu.evaluators import (
            AuthorizationConfig, IdentityConfig, RuntimeAuthConfig)
        from authorino_tpu.pipeline import AuthPipeline

        class FakeIdentity:
            async def call(self, pipeline):
                return {"anonymous": True}

        class FakeAuthz:
            async def call(self, pipeline):
                return True

        got, holder = start_collector()
        try:
            assert tracing_mod.setup_tracing(holder["endpoint"]) is True

            async def body():
                cfg = RuntimeAuthConfig(
                    identity=[IdentityConfig("anon", FakeIdentity())],
                    authorization=[AuthorizationConfig("ok", FakeAuthz())],
                )
                req = CheckRequestModel(http=HttpRequestAttributes(
                    method="GET", path="/", host="svc.test"))
                span = tracing_mod.RequestSpan.from_headers({}, "rid-phase")
                pipeline = AuthPipeline(req, cfg, span=span)
                result = await pipeline.evaluate()
                assert result.success()
                span.end()
                await tracing_mod.shutdown_tracing()  # cancel task + flush
                from authorino_tpu.utils.http import close_sessions

                await close_sessions()
                return span

            span = run(body())
            exported = collected_spans(got)
            by_name = {s["name"]: s for s in exported}
            assert "Check" in by_name
            for phase in ("identity", "authorization"):
                assert phase in by_name, f"missing {phase} span: {by_name.keys()}"
                ps = by_name[phase]
                assert ps["traceId"] == span.trace_id
                assert ps["parentSpanId"] == span.span_id
                assert int(ps["endTimeUnixNano"]) >= int(ps["startTimeUnixNano"])
            # empty phases produce no spans
            assert "metadata" not in by_name and "response" not in by_name
        finally:
            tracing_mod._native_exporter = None
            stop_collector(holder)


# ---------------------------------------------------------------------------
# satellite: stranded loop-less enqueue must still export
# ---------------------------------------------------------------------------

class TestLooplessEnqueue:
    def test_spans_enqueued_without_loop_export_via_timer(self):
        got, holder = start_collector()
        try:
            exporter = tracing_mod.NativeOtlpExporter(
                holder["endpoint"], {}, flush_interval_s=0.05)
            # no running loop in this thread: the old code stranded these
            exporter.enqueue({
                "traceId": "ab" * 16, "spanId": "cd" * 8,
                "name": "Stranded", "kind": 1,
                "startTimeUnixNano": "1", "endTimeUnixNano": "2",
                "status": {},
            })
            deadline = time.monotonic() + 10
            while not got and time.monotonic() < deadline:
                time.sleep(0.02)
            spans = collected_spans(got)
            assert [s["name"] for s in spans] == ["Stranded"]
            assert not exporter._queue
        finally:
            stop_collector(holder)


# ---------------------------------------------------------------------------
# satellite: duplicate registration returns the ORIGINAL collector
# ---------------------------------------------------------------------------

class TestDuplicateRegistration:
    def test_counter_reused_on_duplicate(self):
        c1 = metrics_mod._counter("test_obs_dup_counter", "dup test", ())
        assert not isinstance(c1, metrics_mod._NoopMetric)
        c1.inc(2)
        c2 = metrics_mod._counter("test_obs_dup_counter", "dup test", ())
        assert c2 is c1  # NOT a fresh noop: recording must keep working
        c2.inc(3)
        assert sample("test_obs_dup_counter_total") == 5.0

    def test_histogram_and_gauge_reused_on_duplicate(self):
        h1 = metrics_mod._histogram("test_obs_dup_hist", "dup test", (),
                                    buckets=(1.0, 2.0))
        h2 = metrics_mod._histogram("test_obs_dup_hist", "dup test", (),
                                    buckets=(1.0, 2.0))
        assert h2 is h1
        h2.observe(1.5)
        assert sample("test_obs_dup_hist_count") == 1.0
        g1 = metrics_mod._gauge("test_obs_dup_gauge", "dup test", ())
        g2 = metrics_mod._gauge("test_obs_dup_gauge", "dup test", ())
        assert g2 is g1
        g2.set(7)
        assert sample("test_obs_dup_gauge") == 7.0

    def test_module_reload_keeps_series_recording(self):
        import importlib

        before = sample("auth_server_authconfig_total",
                        {"namespace": "obs-ns", "authconfig": "obs-cfg"})
        importlib.reload(metrics_mod)
        # the reloaded module's collectors are the REGISTRY originals
        metrics_mod.authconfig_total.labels("obs-ns", "obs-cfg").inc()
        assert sample("auth_server_authconfig_total",
                      {"namespace": "obs-ns", "authconfig": "obs-cfg"}) == before + 1


# ---------------------------------------------------------------------------
# satellite: observe_bucketed fallback (prometheus internals missing)
# ---------------------------------------------------------------------------

class _FallbackChild:
    """Quacks like a Histogram child WITHOUT `_buckets`/`_sum` — forces the
    per-observe fallback path."""

    def __init__(self, bounds):
        self._upper_bounds = bounds
        self.obs = []

    def observe(self, v):
        self.obs.append(v)


class TestObserveBucketedFallback:
    def test_residual_shift_matches_drained_sum(self):
        bounds = [1.0, 2.0, 4.0, math.inf]
        child = _FallbackChild(bounds)
        counts = [5, 3, 0, 2]
        target_sum = 5 * 0.8 + 3 * 1.7 + 2 * 5.0  # consistent with the shape
        metrics_mod.observe_bucketed(child, counts, target_sum)
        assert len(child.obs) == 10
        assert sum(child.obs) == pytest.approx(target_sum, abs=1e-9)
        # every observe lands in its source bucket
        in_b0 = [v for v in child.obs if v <= 1.0]
        in_b1 = [v for v in child.obs if 1.0 < v <= 2.0]
        in_b3 = [v for v in child.obs if v > 4.0]
        assert (len(in_b0), len(in_b1), len(in_b3)) == (5, 3, 2)

    def test_thinning_above_cap_preserves_shape(self):
        bounds = [1.0, math.inf]
        child = _FallbackChild(bounds)
        counts = [250_000, 50_000]  # 300k total > the 200k fallback cap
        target_sum = 250_000 * 0.5 + 50_000 * 1.5
        metrics_mod.observe_bucketed(child, counts, target_sum)
        total = len(child.obs)
        assert total == pytest.approx(200_000, abs=2)
        lo = sum(1 for v in child.obs if v <= 1.0)
        hi = total - lo
        # proportional thinning: the 5:1 bucket ratio survives
        assert lo / hi == pytest.approx(5.0, rel=0.01)
        # the scaled sum survives the thinning (residual shift is exact
        # whenever the target is consistent with the bucket shape)
        scale = total / 300_000
        assert sum(child.obs) == pytest.approx(target_sum * scale, rel=1e-6)

    def test_zero_total_is_a_noop(self):
        child = _FallbackChild([1.0, math.inf])
        metrics_mod.observe_bucketed(child, [0, 0], 0.0)
        assert child.obs == []


# ---------------------------------------------------------------------------
# satellite: STAGE_BUCKETS must mirror native/frontend.cpp STAGE_BOUNDS_NS
# ---------------------------------------------------------------------------

class TestStageBucketParity:
    def test_stage_buckets_match_cpp_bounds(self):
        cpp = (Path(__file__).resolve().parent.parent
               / "native" / "frontend.cpp").read_text()
        m = re.search(r"STAGE_BOUNDS_NS\[\]\s*=\s*\{([^}]*)\}", cpp)
        assert m, "STAGE_BOUNDS_NS not found in native/frontend.cpp"
        bounds_ns = [int(tok.strip().rstrip("L"))
                     for tok in m.group(1).replace("\n", " ").split(",")
                     if tok.strip()]
        py_ns = [round(b * 1e9) for b in metrics_mod.STAGE_BUCKETS]
        assert py_ns == bounds_ns, (
            "utils/metrics.py STAGE_BUCKETS and native/frontend.cpp "
            "STAGE_BOUNDS_NS diverged — drained stage histograms would land "
            "in the wrong Prometheus buckets")
        # and the C++ bucket count (bounds + overflow) matches the drain's
        m2 = re.search(r"N_STAGE_BUCKETS\s*=\s*(\d+)", cpp)
        assert m2 and int(m2.group(1)) == len(bounds_ns) + 1


# ---------------------------------------------------------------------------
# satellite (ISSUE 3): dedup/cache stat keys must not drift between the
# Python stats() merge, the C++ fe_stats exporter, and the drain's
# series-materialization list
# ---------------------------------------------------------------------------

class TestDedupCacheStatKeyParity:
    # the C++ credential-cache counters the verdict cache folds into
    CPP_KEYS = ("dyn_hit", "dyn_miss", "dyn_add")
    # Python-side verdict-cache keys merged into stats() next to them
    PY_KEYS = ("vdict_hit", "vdict_miss", "vdict_add", "vdict_evict")

    def test_cpp_exports_every_dyn_key(self):
        pymod = (Path(__file__).resolve().parent.parent
                 / "native" / "pymod.cpp").read_text()
        for key in self.CPP_KEYS:
            assert re.search(r'put\("%s"' % key, pymod), (
                f"native/pymod.cpp fe_stats no longer exports {key!r} — "
                "the verdict cache folds into these keys (native_frontend."
                "stats()) and the drain labels series by them")

    def test_python_stats_merge_uses_the_same_keys(self):
        # source-scan (not import: runtime/native_frontend.py needs
        # cryptography via the evaluator tree)
        src = (Path(__file__).resolve().parent.parent / "authorino_tpu"
               / "runtime" / "native_frontend.py").read_text()
        for key in self.CPP_KEYS + self.PY_KEYS:
            assert re.search(r'"%s"' % key, src), (
                f"native_frontend.stats() no longer references {key!r}")

    def test_drain_materializes_every_key(self):
        for key in self.CPP_KEYS + self.PY_KEYS:
            assert key in metrics_mod.NATIVE_ENSURE_KEYS, (
                f"{key!r} missing from NATIVE_ENSURE_KEYS — its "
                "auth_server_native_frontend_events_total series would "
                "not exist on /metrics until the first delta")

    def test_drain_creates_zero_valued_series(self):
        from prometheus_client import REGISTRY

        drain = metrics_mod.NativeStatsDrain()
        drain.fold({"fast": 1})  # any fold materializes the ensure list
        for key in metrics_mod.NATIVE_ENSURE_KEYS:
            # raw registry read: the series must EXIST (0.0), not be absent
            assert REGISTRY.get_sample_value(
                "auth_server_native_frontend_events_total",
                {"event": key}) is not None

    def test_verdict_cache_series_exist(self):
        metrics_mod.observe_dedup("testlane", 10, 4, 3, 3, 1)
        assert sample("auth_server_verdict_cache_hits_total",
                      {"lane": "testlane"}) == 3.0
        assert sample("auth_server_verdict_cache_misses_total",
                      {"lane": "testlane"}) == 3.0
        assert sample("auth_server_verdict_cache_evictions_total",
                      {"lane": "testlane"}) == 1.0
        # dedup ratio histogram: 10 rows → 4 device rows = 0.6 collapsed
        assert sample("auth_server_batch_dedup_ratio_sum",
                      {"lane": "testlane"}) == pytest.approx(0.6)
        assert sample("auth_server_batch_dedup_ratio_count",
                      {"lane": "testlane"}) == 1.0


# ---------------------------------------------------------------------------
# drain plumbing details
# ---------------------------------------------------------------------------

class TestNativeStatsDrain:
    def test_deltas_not_absolutes(self):
        drain = metrics_mod.NativeStatsDrain()
        base = sample("auth_server_native_frontend_events_total",
                      {"event": "denied"})
        drain.fold({"denied": 10})
        drain.fold({"denied": 10})  # no movement: no double count
        drain.fold({"denied": 25})
        assert sample("auth_server_native_frontend_events_total",
                      {"event": "denied"}) == base + 25

    def test_counter_reset_never_goes_negative(self):
        drain = metrics_mod.NativeStatsDrain()
        base = sample("auth_server_native_frontend_events_total",
                      {"event": "allowed"})
        drain.fold({"allowed": 100})
        drain.fold({"allowed": 3})  # fe restarted: counters reset
        drain.fold({"allowed": 5})
        assert sample("auth_server_native_frontend_events_total",
                      {"event": "allowed"}) == base + 102

    def test_empty_fold_is_noop(self):
        metrics_mod.NativeStatsDrain().fold({})


# ---------------------------------------------------------------------------
# ISSUE 36: the front end's loop clock — its rows are named once, in
# native/frontend.cpp, and the drain folds the thread's own phases as deltas
# ---------------------------------------------------------------------------

def _cpp_list(name):
    cpp = (Path(__file__).resolve().parent.parent
           / "native" / "frontend.cpp").read_text()
    m = re.search(name + r"\[[A-Z_]+\]\s*=\s*\{([^}]*)\}", cpp)
    assert m, f"{name} not found in native/frontend.cpp"
    return re.findall(r'"([a-z_]+)"', m.group(1))


class TestLoopClockDrain:
    # frontend.cpp: the phases come first, N_LOOP_PHASES = 9 of them
    ROWS = _cpp_list("CLOCK_ROW_NAMES")
    PHASES = ROWS[:9]

    @staticmethod
    def table(rows=None, **sum_ns):
        def row(v):
            return {"count": 1, "sum_ns": v, "max_ns": v}
        return {"phases": {p: row(v) for p, v in sum_ns.items()},
                "rows": {r: row(v) for r, v in (rows or {}).items()}}

    def test_cpp_names_nine_phases_and_four_more_rows(self):
        assert self.PHASES == ["idle", "read", "parse", "encode", "ovf_scan",
                               "cut", "respond", "write", "other"]
        # ... then the overflow scan's two counts, a request's bytes and
        # headers, the two syscalls inside `read` and `write`, the
        # messages parsed where recv put them, and the cuts the timer made
        assert self.ROWS[9:] == ["turn", "req_wait", "req_exec", "req_respond",
                                 "ovf_dfas", "ovf_loads", "req_bytes",
                                 "req_headers", "recv", "send", "msg_inplace",
                                 "cut_timer"]
        # the two counters that have an operator's use (docs/observability.md)
        assert _cpp_list("LOOP_COUNTER_NAMES") == ["send_blocked", "cuts_deferred"]

    def test_fe_stats_keys_are_not_the_clocks(self):
        # fe_stats() is folded as events: the clock has a call of its own
        pymod = (Path(__file__).resolve().parent.parent
                 / "native" / "pymod.cpp").read_text()
        assert '{"fe_loop_clock", fe_loop_clock_py' in pymod
        stats = pymod[pymod.index("PyObject* fe_stats_py"):]
        stats = stats[:stats.index("return d;")]
        for row in self.ROWS + _cpp_list("LOOP_COUNTER_NAMES"):
            assert f'put("{row}"' not in stats

    @pytest.mark.parametrize("phase", PHASES)
    def test_phase_folds_as_deltas_in_seconds(self, phase):
        drain = metrics_mod.NativeStatsDrain()
        name, labels = "auth_server_frontend_loop_seconds_total", {"phase": phase}
        base = sample(name, labels)
        drain.fold_loop_clock(self.table(**{phase: 2_000_000_000}))
        drain.fold_loop_clock(self.table(**{phase: 2_000_000_000}))
        drain.fold_loop_clock(self.table(**{phase: 3_500_000_000}))
        assert sample(name, labels) == pytest.approx(base + 3.5)

    @pytest.mark.parametrize("row", ROWS[9:])
    def test_rows_that_are_no_phase_of_the_thread_are_not_folded(self, row):
        from prometheus_client import REGISTRY

        drain = metrics_mod.NativeStatsDrain()
        drain.fold_loop_clock(self.table(rows={row: 5_000_000_000}, idle=1))
        assert REGISTRY.get_sample_value(
            "auth_server_frontend_loop_seconds_total", {"phase": row}) is None

    def test_a_stopped_frontend_folds_nothing(self):
        metrics_mod.NativeStatsDrain().fold_loop_clock({})
