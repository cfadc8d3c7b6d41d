"""Raw-HTTP ext_authz adapter: POST/GET /check, K8s ValidatingWebhook
(AdmissionReview) support, health and metrics endpoints
(semantics: ref pkg/service/auth.go:89-235, main.go:490-492,419-432).

An incoming HTTP request is synthesized into the same CheckRequestModel the
gRPC path produces (headers lower-cased, body captured, TLS peer cert →
source.certificate) and runs through the identical engine/pipeline."""

from __future__ import annotations

import asyncio
import json
import os
import time
from typing import Optional

from aiohttp import web

from ..authjson.wellknown import (
    CheckRequestModel,
    HttpRequestAttributes,
    PeerAttributes,
)
from ..runtime.engine import PolicyEngine
from ..utils import metrics as metrics_mod
from ..utils.rpc import NOT_FOUND, OK, http_status_for

__all__ = ["build_app", "make_check_handler"]

DEFAULT_MAX_BODY = 1024 * 1024  # --max-http-request-body-size analog


def synthesize_check_request(request: web.Request, body: bytes) -> CheckRequestModel:
    """(ref: pkg/service/auth.go:140-177)"""
    headers = {k.lower(): v for k, v in request.headers.items()}
    peer = request.transport.get_extra_info("peername") if request.transport else None
    source = PeerAttributes(
        address=peer[0] if peer else "", port=peer[1] if peer and len(peer) > 1 else 0
    )
    # TLS peer certificate → Attributes.Source.Certificate (ref :166-172)
    ssl_obj = request.transport.get_extra_info("ssl_object") if request.transport else None
    if ssl_obj is not None:
        try:
            import ssl as _ssl

            der = ssl_obj.getpeercert(binary_form=True)
            if der:
                source.certificate = _ssl.DER_cert_to_PEM_cert(der)
        except Exception:
            pass
    path = request.path_qs
    return CheckRequestModel(
        http=HttpRequestAttributes(
            id=headers.get("x-request-id", ""),
            method=request.method,
            headers=headers,
            path=path,
            host=headers.get("host", request.host or ""),
            scheme=request.scheme,
            protocol="HTTP/1.1",
            body=body.decode("utf-8", "replace") if body else "",
            raw_body=body,
            size=len(body) if body else -1,
        ),
        source=source,
    )


def _admission_review(body: bytes) -> Optional[dict]:
    """Detect a v1 AdmissionReview payload (ref: pkg/service/auth.go:191-234)."""
    if not body:
        return None
    try:
        payload = json.loads(body)
    except Exception:
        return None
    if isinstance(payload, dict) and payload.get("kind") == "AdmissionReview":
        return payload
    return None


def make_check_handler(engine: PolicyEngine, max_body: int = DEFAULT_MAX_BODY):
    async def check(request: web.Request) -> web.StreamResponse:
        # request.read() buffers the complete (possibly chunked) body;
        # content.read(n) would return only what's already streamed in
        try:
            body = await request.read()
        except web.HTTPRequestEntityTooLarge:
            return web.Response(status=413, text="request body too large")
        if len(body) > max_body:
            return web.Response(status=413, text="request body too large")

        check_request = synthesize_check_request(request, body)
        from ..utils.tracing import RequestSpan

        # Envoy's HTTP ext_authz filter forwards its route timeout in
        # x-envoy-expected-rq-timeout-ms: propagate it as the Check()
        # deadline so the dispatcher can shed doomed requests before encode
        deadline = None
        timeout_ms = check_request.http.headers.get(
            "x-envoy-expected-rq-timeout-ms")
        if timeout_ms:
            try:
                deadline = time.monotonic() + max(float(timeout_ms), 0.0) / 1e3
            except ValueError:
                pass
        # front-door admission (ISSUE 7): a request that is doomed on
        # arrival while the engine is overloaded is answered typed before
        # a span or pipeline exists — the submit-time gate stays the one
        # true admission point (this check is deterministic)
        precheck = getattr(engine, "admission_precheck", None)
        if precheck is not None:
            rejected = precheck(deadline)
            if rejected is not None:
                status = http_status_for(rejected.code, rejected.status)
                metrics_mod.response_status.labels(str(status)).inc()
                return web.Response(
                    status=status,
                    headers={"X-Ext-Auth-Reason": rejected.message or ""},
                    text="")
        span = RequestSpan.from_headers(
            check_request.http.headers, check_request.http.id
        )
        try:
            result = await engine.check(check_request, span=span,
                                        deadline=deadline)
        finally:
            span.end(error=None)

        status = http_status_for(result.code, result.status)
        metrics_mod.response_status.labels(str(status)).inc()

        admission = _admission_review(body)
        if admission is not None:
            review = {
                "apiVersion": "admission.k8s.io/v1",
                "kind": "AdmissionReview",
                "response": {
                    "uid": (admission.get("request") or {}).get("uid", ""),
                    "allowed": result.code == OK,
                },
            }
            if result.code != OK and result.message:
                review["response"]["status"] = {"message": result.message}
            return web.json_response(review)

        from multidict import CIMultiDict

        # multidict: repeated header names must survive (e.g. one
        # WWW-Authenticate challenge per identity config — ref config.go:29-40)
        headers: CIMultiDict = CIMultiDict()
        for hs in result.headers:
            for k, v in hs.items():
                headers.add(k, v)
        if result.code != OK and result.message:
            # reason travels in the X-Ext-Auth-Reason header (ref :470-480)
            headers["X-Ext-Auth-Reason"] = result.message
        return web.Response(status=status, headers=headers, text=result.body or "")

    return check


def build_app(engine: PolicyEngine, readiness=None, max_body: int = DEFAULT_MAX_BODY,
              frontend=None, enable_profile: bool = False) -> web.Application:
    """``frontend`` is the NativeFrontend instance (or a zero-arg callable
    resolving to one — the CLI builds this app before the frontend starts)
    whose live stats /debug/vars folds in.  ``enable_profile`` arms the
    /debug/profile jax.profiler hook (opt-in: a trace capture costs real
    device time and writes to disk)."""
    app = web.Application(client_max_size=max_body + 1024)

    async def healthz(_):
        return web.Response(text="ok")  # liveness (ref main.go:428-432)

    async def readyz(request: web.Request):
        # readiness aggregates reconciler state (ref pkg/health/health.go:48-71)
        # plus the fault-tolerance surfaces (docs/robustness.md): a draining
        # server answers 503 so the LB stops routing here while in-flight
        # work completes; a tripped device circuit is SURFACED but stays
        # ready — host-degraded verdicts are exact, removing the endpoint
        # would only shift load onto healthy peers' devices
        if getattr(engine, "draining", False):
            return web.Response(status=503, text="draining")
        if readiness is None or readiness():
            # a kernel that failed to lower/compile for the serving
            # snapshot is NOT ready, whatever the degrade path can still
            # answer: the device this server exists for is not serving
            owners = (("engine", engine), ("native", _frontend()))
            for lane, owner in owners:
                err = getattr(owner, "warm_error", None) if owner else None
                if isinstance(err, str) and err:
                    return web.Response(
                        status=503,
                        text=f"not ready: {lane} kernel warm failed: {err}")
            degraded = []
            for lane, owner in owners:
                breaker = getattr(owner, "breaker", None) if owner else None
                if breaker is not None and breaker.state != "closed":
                    degraded.append(
                        f"{lane} device circuit {breaker.state}")
                # overload is surfaced but STAYS ready: admission is
                # shedding typed rejections precisely so accepted work
                # still meets its SLO — removing the endpoint would just
                # move the queue to a peer
                adm = getattr(owner, "admission", None) if owner else None
                if adm is not None and adm.overloaded:
                    degraded.append(f"{lane} admission overloaded")
            # change safety (ISSUE 10): an active quarantine is surfaced
            # but STAYS ready — the quarantined configs serve their prior
            # (exact, vetted) artifacts; 503ing would take down every
            # healthy config with them
            if getattr(engine, "quarantine_active", False):
                degraded.append("quarantine active")
            # crash-safe warm restart (ISSUE 20): a state-dir snapshot
            # older than --max-snapshot-age is surfaced but STAYS ready —
            # fail-static old verdicts beat no verdicts; the first live
            # control-plane swap clears the reason
            plane = getattr(engine, "state_plane", None)
            if plane is not None:
                try:
                    stale = plane.stale_reason()
                except Exception:
                    stale = None
                if stale:
                    degraded.append(stale)
            if degraded:
                return web.Response(
                    text=f"ok (degraded: {'; '.join(degraded)})")
            return web.Response(text="ok")
        return web.Response(status=503, text="not ready")

    async def server_metrics(_):
        try:
            from prometheus_client import CONTENT_TYPE_LATEST, generate_latest

            # the registry drains what is kept as arrays (per-AuthConfig
            # counters, rule heat maps, the tenant plane) before it collects
            # a family (utils.metrics._DrainCollector): the series are
            # current on THIS scrape.  Off the event loop: the per-AuthConfig
            # families make the text 27 MB at 10,000 configs, seconds of
            # Python during which this loop would answer nothing else
            # (/readyz, /debug/vars, a /debug/profile asked for NOW would
            # start seconds late)
            body = await asyncio.get_running_loop().run_in_executor(
                None, generate_latest)
            return web.Response(body=body, content_type="text/plain")
        except Exception:
            return web.Response(status=501, text="prometheus_client unavailable")

    def _frontend():
        return frontend() if callable(frontend) else frontend

    async def debug_vars(_):
        """Live introspection snapshot (the expvar analog): engine queue
        depths + config generation, compiled-snapshot shape, and — when the
        native frontend serves — its raw fe_stats counters, slow-lane
        backlog, and warmed jit grid.  Everything here is a GIL-atomic
        read; safe to scrape under load."""
        import time as _time

        from ..utils.jax_env import jax_process_info

        fe = _frontend()
        if fe is not None:
            # before the engine's part is read: the lanes share the tenant
            # plane and the heat maps, and the native lane folds its kept
            # cuts into them when it is read
            metrics_mod.fold_kept()
        data = {
            "engine": engine.debug_vars(),
            # what the kernels run on: platform, device kind + count,
            # library versions, compile-cache placement and traffic, the
            # boot stamps and each device's memory; `time_ns` places the
            # reading on the clock a running capture's `started_unix_ns` is on
            "process": {"pid": os.getpid(), "time": _time.time(),
                        "time_ns": _time.time_ns(),
                        **jax_process_info()},
        }
        if profile_state["capture"] is not None:
            data["process"]["profile"] = profile_state["capture"]
        if fe is not None:
            try:
                fe.drain_native_stats()  # /metrics reflects this scrape too
            except Exception:
                pass
            data["native_frontend"] = fe.debug_vars()
        return web.json_response(data)

    async def debug_decisions(request: web.Request):
        """Head-sampled decision log (ISSUE 9, docs/observability.md
        "Decision provenance"): the bounded ring of structured decision
        records — host, authconfig, verdict, firing rule, lane, latency,
        snapshot generation.  ``?n=K`` returns the newest K records;
        ``?tenant=NAME`` (ISSUE 15) returns that tenant's stratified
        sub-ring — its newest records survive even when a hot tenant has
        filled the global ring.  Query it live, or feed the JSON to
        ``python -m authorino_tpu.analysis --decisions``."""
        from ..runtime import provenance as prov_mod

        n = None
        if "n" in request.query:
            try:
                n = int(request.query["n"])
            except ValueError:
                return web.Response(status=400, text="bad n")
        tenant = request.query.get("tenant") or None
        metrics_mod.fold_kept()  # the native lane samples when it folds
        return web.json_response(
            prov_mod.DECISIONS.to_json(n=n, tenant=tenant))

    async def debug_tenants(_):
        """Tenant QoS plane (ISSUE 15, docs/tenancy.md): weights/quotas,
        fair-cut evidence, per-tenant admission + wait state, top-tenant
        stats with SLO burn, and the noisy-neighbor containment set."""
        plane = getattr(engine, "tenancy", None)
        if plane is None:
            return web.json_response({"enabled": False})
        metrics_mod.fold_kept()  # the native lane's kept cuts, first
        return web.json_response(plane.to_json())

    async def debug_replay(request: web.Request):
        """Traffic-replay state (ISSUE 13, docs/replay.md): capture-log
        accounting (ring bytes/records, drops, segments) and the last
        replay-preflight verdict.  ``?flush=1`` (POST) forces the pending
        capture segment to disk — handy before pointing
        ``analysis --replay ... --log DIR`` at a live server's capture
        directory."""
        import asyncio as _asyncio

        from ..replay.capture import CAPTURE

        if request.query.get("flush") and request.method == "POST":
            await _asyncio.get_running_loop().run_in_executor(
                None, CAPTURE.flush)
        return web.json_response({
            "capture": CAPTURE.to_json(),
            "pregate": {
                "enabled": getattr(engine, "replay_pregate", False),
                "budget_s": getattr(engine, "replay_pregate_budget_s",
                                    None),
                "last": getattr(engine, "_last_pregate", None),
            },
        })

    async def debug_canary(request: web.Request):
        """Change-safety state + manual override (ISSUE 10,
        docs/robustness.md "Change safety"): GET returns the canary/
        quarantine/rollback-history state; ``?action=promote`` promotes an
        in-progress canary immediately, ``?action=rollback`` rolls it back
        (or, with none active, pointer-swaps to the previous retained
        generation), ``?action=clear-quarantine`` releases the quarantine.
        Driven by ``python -m authorino_tpu.analysis --promote/--rollback``."""
        import asyncio as _asyncio

        action = request.query.get("action", "")
        if not action:
            metrics_mod.fold_kept()  # the baseline cohort's newest cuts
            return web.json_response(engine.change_safety_vars())
        if request.method != "POST":
            # promote/rollback/clear-quarantine change the serving
            # snapshot — never off an idempotent-by-contract GET (link
            # prefetchers, dashboard refreshes)
            return web.json_response(
                {"error": "state-changing actions require POST"},
                status=405)
        ops = {
            "promote": engine.canary_promote,
            "rollback": engine.canary_rollback,
            "clear-quarantine": engine.clear_quarantine,
        }
        op = ops.get(action)
        if op is None:
            return web.Response(
                status=400,
                text=f"unknown action {action!r} "
                     f"(want promote|rollback|clear-quarantine)")
        # promote/rollback fan out to swap listeners (native C++ snapshot
        # rebuild) — never on the serving event loop
        applied = await _asyncio.get_running_loop().run_in_executor(None, op)
        return web.json_response({
            "action": action, "applied": bool(applied),
            "change_safety": engine.change_safety_vars(),
        })

    async def debug_batches(request: web.Request):
        """The native lane's ring of batch timelines (docs/observability.md
        "Debug surface"): the newest ``?n=K`` batches (default 64, the ring
        holds 2,048), newest first, each with its eight stamps on
        ``time.monotonic_ns()``."""
        fe = _frontend()
        if fe is None:
            return web.json_response({"error": "no native frontend"},
                                     status=404)
        try:
            n = int(request.query.get("n", 64))
        except ValueError:
            return web.Response(status=400, text="bad n")
        return web.json_response(fe.batch_stages.to_json(n))

    # `capture`: {trace_dir, started_unix_ns} while a capture runs
    profile_state = {"busy": False, "capture": None}

    async def debug_profile(request: web.Request):
        """Opt-in on-demand device profile: captures a jax.profiler trace
        for ?seconds=N (cap 60) into a fresh temp dir and returns its path.
        Host TraceMe events (the program's own `atpu/...` spans among them)
        and the device tracer, no Python frames: the Python tracer slows
        the host it measures severalfold.  ``?python=1`` turns it on for
        the operator who wants frames.  Single-flight — a capture in
        progress answers 409."""
        if not enable_profile:
            return web.Response(
                status=403,
                text="profiling disabled (start with --debug-profile)")
        import math

        try:
            seconds = float(request.query.get("seconds", 1.0))
        except ValueError:
            return web.Response(status=400, text="bad seconds")
        if not math.isfinite(seconds):
            # NaN passes float() and poisons min/max + asyncio.sleep —
            # the capture would never stop and busy would stick
            return web.Response(status=400, text="bad seconds")
        seconds = min(max(seconds, 0.1), 60.0)
        if profile_state["busy"]:
            return web.Response(status=409, text="profile capture in progress")
        profile_state["busy"] = True
        try:
            import asyncio
            import tempfile
            import time as _time

            import jax.profiler

            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = int(
                request.query.get("python", "0") not in ("", "0"))
            trace_dir = tempfile.mkdtemp(prefix="authorino-tpu-profile-")
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            profile_state["capture"] = {"trace_dir": trace_dir,
                                        "started_unix_ns": _time.time_ns()}
            try:
                await asyncio.sleep(seconds)
            finally:
                profile_state["capture"] = None
                jax.profiler.stop_trace()
            return web.json_response({
                "trace_dir": trace_dir, "seconds": seconds,
                "python_tracer_level": options.python_tracer_level})
        except Exception as e:
            return web.Response(status=500, text=f"profile capture failed: {e}")
        finally:
            profile_state["busy"] = False

    app.router.add_get("/healthz", healthz)
    app.router.add_get("/readyz", readyz)
    app.router.add_get("/metrics", server_metrics)
    app.router.add_get("/server-metrics", server_metrics)
    app.router.add_get("/debug/vars", debug_vars)
    app.router.add_get("/debug/decisions", debug_decisions)
    app.router.add_get("/debug/tenants", debug_tenants)
    app.router.add_get("/debug/canary", debug_canary)
    app.router.add_post("/debug/canary", debug_canary)
    app.router.add_get("/debug/replay", debug_replay)
    app.router.add_post("/debug/replay", debug_replay)
    app.router.add_get("/debug/profile", debug_profile)
    app.router.add_get("/debug/batches", debug_batches)
    # catch-all LAST: Envoy's HTTP ext_authz filter forwards the ORIGINAL
    # request path (path_prefix + :path), so /check is just the conventional
    # prefix — any path must evaluate (ref: pkg/service/auth.go:89-177
    # synthesizes the CheckRequest from the incoming request itself)
    app.router.add_route("*", "/{tail:.*}", make_check_handler(engine, max_body))
    return app
