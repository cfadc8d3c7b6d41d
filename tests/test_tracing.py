"""W3C trace-context propagation tests (ref: pkg/trace — spans propagate
traceparent + x-request-id into outbound evaluator calls)."""

import asyncio

from aiohttp import web
from aiohttp.test_utils import TestServer

from authorino_tpu.authjson import CheckRequestModel, HttpRequestAttributes, JSONValue
from authorino_tpu.evaluators import IdentityConfig, MetadataConfig, RuntimeAuthConfig
from authorino_tpu.evaluators.identity import Noop
from authorino_tpu.evaluators.metadata import GenericHttp
from authorino_tpu.pipeline import AuthPipeline
from authorino_tpu.utils.tracing import RequestSpan


def run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


def test_traceparent_parse_and_mint():
    parent = "00-0123456789abcdef0123456789abcdef-00f067aa0ba902b7-01"
    span = RequestSpan.from_headers({"traceparent": parent}, request_id="req-1")
    assert span.trace_id == "0123456789abcdef0123456789abcdef"  # trace id propagates
    assert span.span_id != "00f067aa0ba902b7"  # new span id per hop
    out = span.inject({})
    assert out["traceparent"].startswith("00-0123456789abcdef0123456789abcdef-")
    assert out["x-request-id"] == "req-1"

    minted = RequestSpan.from_headers({}, request_id="req-2")
    assert len(minted.trace_id) == 32 and len(minted.span_id) == 16


def test_outbound_propagation_through_generic_http():
    async def body():
        seen = {}

        async def meta(request):
            seen["traceparent"] = request.headers.get("traceparent")
            seen["x-request-id"] = request.headers.get("x-request-id")
            return web.json_response({"ok": True})

        app = web.Application()
        app.router.add_get("/meta", meta)
        server = TestServer(app)
        await server.start_server()
        try:
            base = str(server.make_url("")).rstrip("/")
            cfg = RuntimeAuthConfig(
                identity=[IdentityConfig("anon", Noop())],
                metadata=[MetadataConfig("m", GenericHttp(endpoint=JSONValue(static=base + "/meta")))],
            )
            req = CheckRequestModel(
                http=HttpRequestAttributes(
                    method="GET", path="/", host="svc.example.com",
                    headers={"traceparent": "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01"},
                )
            )
            span = RequestSpan.from_headers(req.http.headers, "rid-9")
            pipeline = AuthPipeline(req, cfg, span=span)
            result = await pipeline.evaluate()
            assert result.success()
            assert seen["traceparent"].startswith("00-" + "ab" * 16 + "-")
            assert seen["x-request-id"] == "rid-9"
        finally:
            await server.close()
            from authorino_tpu.utils.http import close_sessions

            await close_sessions()

    run(body())


class TestNativeOtlpExport:
    def test_spans_export_to_fake_collector(self):
        """The built-in OTLP/HTTP JSON exporter (no OTel SDK needed) must
        deliver finished request spans to a collector: hex ids, request-id
        attribute, error status, service.name resource, and the basic-auth
        header derived from the endpoint URL userinfo
        (ref pkg/trace/exporter.go:26-117)."""
        from aiohttp import web
        from aiohttp.test_utils import TestServer

        from authorino_tpu.utils import tracing

        async def body():
            got = {}

            async def v1_traces(request):
                got["auth"] = request.headers.get("authorization", "")
                got["payload"] = await request.json()
                return web.json_response({})

            app = web.Application()
            app.router.add_post("/v1/traces", v1_traces)
            server = TestServer(app)
            await server.start_server()
            try:
                base = str(server.make_url("")).rstrip("/")
                url = base.replace("http://", "http://u:pw@", 1)
                assert tracing.setup_tracing(url) is True
                assert tracing._native_exporter is not None
                tracing._native_exporter.flush_interval_s = 0.01

                tp = "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01"
                span = tracing.RequestSpan.from_headers({"traceparent": tp}, "rid-1")
                span.end()
                span2 = tracing.RequestSpan.from_headers({}, "rid-2")
                span2.end(error="Unauthorized")
                await tracing._native_exporter.flush()

                import base64

                assert got["auth"] == "Basic " + base64.b64encode(b"u:pw").decode()
                rs = got["payload"]["resourceSpans"][0]
                res_attrs = {a["key"]: a["value"]["stringValue"]
                             for a in rs["resource"]["attributes"]}
                assert res_attrs["service.name"] == "authorino-tpu"
                spans = rs["scopeSpans"][0]["spans"]
                assert len(spans) == 2
                by_rid = {s["attributes"][0]["value"]["stringValue"]: s for s in spans}
                assert by_rid["rid-1"]["traceId"] == "ab" * 16  # propagated
                assert len(by_rid["rid-2"]["traceId"]) == 32    # minted hex
                assert by_rid["rid-2"]["status"] == {"code": 2, "message": "Unauthorized"}
                assert by_rid["rid-1"]["status"] == {}
                assert int(spans[0]["endTimeUnixNano"]) >= int(spans[0]["startTimeUnixNano"])
            finally:
                tracing._native_exporter = None
                await server.close()
                from authorino_tpu.utils.http import close_sessions

                await close_sessions()

        run(body())

    def test_grpc_endpoint_without_sdk_stays_propagation_only(self):
        from authorino_tpu.utils import tracing

        assert tracing.setup_tracing("rpc://collector:4317") is False
        assert tracing._native_exporter is None


class TestNativeFrontendTracing:
    def test_active_tracing_samples_spans_and_keeps_fast_lane(self):
        """With span export active, the native frontend head-samples:
        1-in-N requests take the Python pipeline and produce exported spans
        with the propagated trace id, the rest keep serving natively —
        observability must not cost the fast lane wholesale."""
        import grpc

        from aiohttp import web
        from aiohttp.test_utils import TestServer

        from authorino_tpu import protos
        from authorino_tpu.compiler import ConfigRules
        from authorino_tpu.expressions import Operator, Pattern
        from authorino_tpu.evaluators import (
            AuthorizationConfig, IdentityConfig, RuntimeAuthConfig)
        from authorino_tpu.evaluators.authorization import PatternMatching
        from authorino_tpu.evaluators.identity import Noop
        from authorino_tpu.runtime import EngineEntry, PolicyEngine
        from authorino_tpu.runtime.native_frontend import NativeFrontend
        from authorino_tpu.utils import tracing

        pb = protos.external_auth_pb2

        async def setup_collector():
            got = []

            async def v1_traces(request):
                got.append(await request.json())
                return web.json_response({})

            app = web.Application()
            app.router.add_post("/v1/traces", v1_traces)
            server = TestServer(app)
            await server.start_server()
            return server, got

        async def body():
            server, got = await setup_collector()
            try:
                assert tracing.setup_tracing(str(server.make_url("")).rstrip("/"))
                tracing._native_exporter.flush_interval_s = 0.01

                rule = Pattern("request.method", Operator.EQ, "GET")
                engine = PolicyEngine(max_batch=16, mesh=None)
                cfg_id = "ns/traced"
                pm = PatternMatching(rule, batched_provider=engine.provider_for(cfg_id),
                                     evaluator_slot=0)
                runtime = RuntimeAuthConfig(
                    identity=[IdentityConfig("anon", Noop())],
                    authorization=[AuthorizationConfig("rules", pm)])
                engine.apply_snapshot([EngineEntry(
                    id=cfg_id, hosts=["traced.test"], runtime=runtime,
                    rules=ConfigRules(name=cfg_id, evaluators=[(None, rule)]))])
                fe = NativeFrontend(engine, port=0, max_batch=16, window_us=500,
                                    trace_sample_n=4)
                port = fe.start()
                try:
                    req = pb.CheckRequest()
                    http = req.attributes.request.http
                    http.method = "GET"
                    http.path = "/x"
                    http.host = "traced.test"
                    http.headers["traceparent"] = "00-" + "77" * 16 + "-" + "88" * 8 + "-01"

                    def call():
                        with grpc.insecure_channel(f"127.0.0.1:{port}") as ch:
                            return ch.unary_unary(
                                "/envoy.service.auth.v3.Authorization/Check",
                                request_serializer=pb.CheckRequest.SerializeToString,
                                response_deserializer=pb.CheckResponse.FromString,
                            )(req, timeout=10)

                    import asyncio as aio

                    # 1st request is the sample (counter starts at 0):
                    # slow lane + exported span with the propagated id
                    resp = await aio.to_thread(call)
                    assert resp.status.code == 0
                    stats = fe.stats()
                    assert stats["fast"] == 0 and stats["slow"] == 1, stats
                    assert stats["trace_sampled"] == 1
                    await tracing._native_exporter.flush()
                    assert got, "no span exported"
                    sp = got[0]["resourceSpans"][0]["scopeSpans"][0]["spans"][0]
                    assert sp["traceId"] == "77" * 16
                    # next 3 of every 4 stay native; the 5th samples again
                    for _ in range(7):
                        resp = await aio.to_thread(call)
                        assert resp.status.code == 0
                    stats = fe.stats()
                    assert stats["trace_sampled"] == 2, stats
                    assert stats["fast"] == 6 and stats["slow"] == 2, stats
                finally:
                    fe.stop()
            finally:
                tracing._native_exporter = None
                await server.close()
                from authorino_tpu.utils.http import close_sessions

                await close_sessions()

        run(body())
