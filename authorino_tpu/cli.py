"""CLI bootstrap (the analog of main.go: `authorino server|webhooks|version`,
ref main.go:134-220).  One process boots the gRPC ext_authz server, the
raw-HTTP /check server, the wristband OIDC discovery server and the control
plane (YAML-dir source standalone, or in-cluster watch when running in
Kubernetes).

Flags fall back to env vars through a typed helper
(ref: pkg/utils/envvar.go:13-33)."""

from __future__ import annotations

import argparse
import asyncio
import logging
import os
import signal
import sys
import threading
from typing import Any, Optional


def env_var(name: str, default: Any) -> Any:
    """(ref: pkg/utils/envvar.go)"""
    raw = os.environ.get(name)
    if raw is None:
        return default
    if isinstance(default, bool):
        return raw.lower() in ("1", "true", "yes")
    if isinstance(default, int):
        try:
            return int(raw)
        except ValueError:
            return default
    if isinstance(default, float):
        try:
            return float(raw)
        except ValueError:
            return default
    return raw


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="authorino-tpu")
    sub = p.add_subparsers(dest="command")

    s = sub.add_parser("server", help="Run the authorization server")
    s.add_argument("--watch-dir", default=env_var("WATCH_DIR", ""), help="Directory of AuthConfig/Secret manifests (standalone mode)")
    s.add_argument("--in-cluster", action="store_true", default=env_var("IN_CLUSTER", False), help="Watch AuthConfigs via the Kubernetes API")
    s.add_argument("--ext-auth-grpc-port", type=int, default=env_var("EXT_AUTH_GRPC_PORT", 50051))
    s.add_argument("--ext-auth-http-port", type=int, default=env_var("EXT_AUTH_HTTP_PORT", 5001))
    s.add_argument("--oidc-http-port", type=int, default=env_var("OIDC_HTTP_PORT", 8083))
    s.add_argument("--metrics-addr-port", type=int, default=env_var("METRICS_PORT", 8080))
    s.add_argument("--timeout", type=int, default=env_var("TIMEOUT", 0), help="Per-request timeout in ms (0 = none)")
    s.add_argument("--max-http-request-body-size", type=int, default=env_var("MAX_HTTP_REQUEST_BODY_SIZE", 1024 * 1024))
    s.add_argument("--batch-size", type=int, default=env_var("BATCH_SIZE", 256), help="Max micro-batch size for TPU dispatch")
    s.add_argument("--batch-window-us", type=int, default=env_var("BATCH_WINDOW_US", 500),
                   help="Micro-batch gather window in microseconds (native "
                        "frontend's C++ batcher ONLY; the Python engine "
                        "lane's old max_delay_s mirror of this flag is "
                        "retired — it dispatches adaptively, see "
                        "--no-adaptive-window)")
    s.add_argument("--max-inflight-batches", type=int,
                   default=env_var("MAX_INFLIGHT_BATCHES", 48),
                   help="Device dispatch window: micro-batches in flight "
                        "concurrently (launched, readback pending).  Size "
                        "so window × batch-size ≥ device RTT × target RPS")
    s.add_argument("--dispatch-workers", type=int,
                   default=env_var("DISPATCH_WORKERS", 4),
                   help="CPU workers for the encode stage of the pipelined "
                        "dispatcher (host encode/pack + fused H2D staging)")
    s.add_argument("--verdict-cache-size", type=int,
                   default=env_var("VERDICT_CACHE_SIZE", 32768),
                   help="Entries in the snapshot-scoped verdict LRU keyed by "
                        "(generation, encoded-row digest); 0 disables it.  "
                        "Exactness-preserving: invalidation is structural "
                        "(generation bump on snapshot swap)")
    s.add_argument("--no-batch-dedup", action="store_true",
                   default=not env_var("BATCH_DEDUP", True),
                   help="Disable within-micro-batch row dedup (by default "
                        "duplicate encoded rows collapse to one device "
                        "evaluation + a scatter map; set BATCH_DEDUP=0 for "
                        "the env-var equivalent)")
    s.add_argument("--device-timeout", type=int,
                   default=env_var("DEVICE_TIMEOUT_MS", 30000),
                   help="Completer watchdog in ms: an in-flight micro-batch "
                        "whose readback never arrives is abandoned after "
                        "this long, counted as a circuit-breaker failure, "
                        "and retried/degraded host-side (0 disables)")
    s.add_argument("--breaker-threshold", type=int,
                   default=env_var("BREAKER_THRESHOLD", 5),
                   help="Consecutive micro-batch failures that trip the "
                        "device circuit breaker OPEN (whole batches decided "
                        "host-side; see docs/robustness.md)")
    s.add_argument("--breaker-reset", type=float,
                   default=env_var("BREAKER_RESET_S", 5.0),
                   help="Seconds an OPEN circuit waits before admitting one "
                        "half-open probe batch to test device recovery")
    s.add_argument("--admission-target-ms", type=float,
                   default=env_var("ADMISSION_TARGET_MS", 50.0),
                   help="CoDel-style admission wait target in ms: drives "
                        "the OVERLOADED state machine, doomed-deadline "
                        "rejection, and the dynamic queue bound "
                        "(service_rate x target).  NOTE the bound floors "
                        "at one full pipeline's worth of standing work "
                        "(max-inflight-batches x batch-size) so bursts the "
                        "window could absorb are never rejected — use "
                        "--admission-queue-cap for a hard bound below "
                        "that.  See docs/robustness.md 'Overload & "
                        "brownout'")
    s.add_argument("--admission-queue-cap", type=int,
                   default=env_var("ADMISSION_QUEUE_CAP", 0),
                   help="Hard cap on the engine submit queue in requests "
                        "(0 = the wait-targeted dynamic cap only)")
    s.add_argument("--no-adaptive-window", action="store_true",
                   default=not env_var("ADAPTIVE_WINDOW", True),
                   help="Disable the adaptive in-flight window/batch-cut "
                        "controller (the lane then runs at the static "
                        "--max-inflight-batches operating point, the old "
                        "behavior)")
    s.add_argument("--no-brownout", action="store_true",
                   default=not env_var("BROWNOUT", True),
                   help="Disable host-lane brownout (spilling small "
                        "head-of-queue batches to the exact host oracle "
                        "while the device window is saturated)")
    s.add_argument("--brownout-max-batch", type=int,
                   default=env_var("BROWNOUT_MAX_BATCH", 32),
                   help="Rows per brownout spill batch (small by design: "
                        "the host lane absorbs latency-critical work, not "
                        "bulk throughput)")
    s.add_argument("--no-lane-select", action="store_true",
                   default=not env_var("LANE_SELECT", True),
                   help="Disable cost-model lane selection (docs/"
                        "performance.md 'Lane selection'): the host "
                        "oracle then serves only as brownout/degrade "
                        "fallback and every batch cut rides the device — "
                        "light-load p50 returns to one device RTT")
    s.add_argument("--lane-host-max-rows", type=int,
                   default=env_var("LANE_HOST_MAX_ROWS", 64),
                   help="Largest batch cut the cost model may answer "
                        "host-side (larger cuts are batch-shaped work: "
                        "the device amortizes its RTT over full pads)")
    s.add_argument("--no-speculative-dispatch", action="store_true",
                   default=not env_var("SPECULATIVE_DISPATCH", True),
                   help="Disable speculative dual-dispatch of the circuit "
                        "breaker's half-open probe batch (normally the "
                        "probe rides BOTH lanes and resolves first-wins, "
                        "so clients never wait out a probe against a "
                        "still-sick device)")
    s.add_argument("--no-tenant-qos", action="store_true",
                   default=not env_var("TENANT_QOS", True),
                   help="TENANT QoS (docs/tenancy.md): disable the tenant "
                        "plane — weighted-fair batch cuts over per-tenant "
                        "virtual queues, per-tenant quotas + tenant-aware "
                        "doomed shedding at admission, per-tenant SLO/"
                        "deny/wait folds, and noisy-neighbor containment. "
                        "Off returns the globally-fair (FIFO) cut")
    s.add_argument("--tenant-weight", action="append", default=[],
                   metavar="TENANT=WEIGHT",
                   help="Operator weight override for one tenant "
                        "(AuthConfig id, e.g. ns/name=4).  Repeatable; "
                        "overrides the authorino.tpu/qos-weight and "
                        "qos-class annotations")
    s.add_argument("--tenant-default-weight", type=float,
                   default=env_var("TENANT_DEFAULT_WEIGHT", 1.0),
                   help="Fair-share weight of un-annotated tenants (the "
                        "default QoS class)")
    s.add_argument("--tenant-quota-rps", type=float,
                   default=env_var("TENANT_QUOTA_RPS", 0.0),
                   help="Default per-tenant admission token-bucket rate "
                        "(requests/s; 0 = no quota).  Per-tenant values "
                        "come from the authorino.tpu/qos-quota-rps "
                        "annotation.  Over-quota tenants get typed "
                        "RESOURCE_EXHAUSTED scoped to THAT tenant — the "
                        "global OVERLOADED latch is untouched")
    s.add_argument("--tenant-contain-threshold", type=float,
                   default=env_var("TENANT_CONTAIN_THRESHOLD", 3.0),
                   help="Noisy-neighbor containment trigger: contain a "
                        "tenant whose served share exceeds (weighted "
                        "share x this) while the global queue wait is "
                        "over the admission target.  Contained rows "
                        "answer via the exact host-oracle lane or paced "
                        "typed rejections; auto-releases on decay")
    s.add_argument("--tenant-top-k", type=int,
                   default=env_var("TENANT_TOP_K", 16),
                   help="Tenant-labelled metric cardinality: only the "
                        "top-K tenants by volume get their own label "
                        "value, the rest fold into `other` "
                        "(docs/tenancy.md cardinality policy)")
    s.add_argument("--expose-deny-reason", action="store_true",
                   default=env_var("EXPOSE_DENY_REASON", False),
                   help="PRIVACY KNOB (decision provenance): name the "
                        "attributed firing rule in the client-visible "
                        "X-Ext-Auth-Reason header on denials.  Off by "
                        "default — clients see the generic 'Unauthorized' "
                        "while Envoy dynamic_metadata and the operator "
                        "surfaces (/metrics rule heat map, /debug/"
                        "decisions) always carry the attribution")
    s.add_argument("--slo-ms", type=float, default=env_var("SLO_MS", 0.0),
                   help="Per-request latency SLO in ms (0 = SLO tracking "
                        "off): arms the multi-window burn-rate tracker "
                        "(auth_server_slo_burn_rate{lane,window} gauges + "
                        "the /debug/vars slo block) on both lanes")
    s.add_argument("--decision-log-size", type=int,
                   default=env_var("DECISION_LOG_SIZE", 1024),
                   help="Bounded decision-log ring capacity "
                        "(/debug/decisions; head-sampled records)")
    s.add_argument("--decision-log-sample", type=int,
                   default=env_var("DECISION_LOG_SAMPLE", 64),
                   help="Head-sample 1-in-N decisions into the decision "
                        "log (at most one record per micro-batch — zero "
                        "per-request work on the native lane)")
    s.add_argument("--canary-fraction", type=float,
                   default=env_var("CANARY_FRACTION", 0.0),
                   help="CHANGE SAFETY (docs/robustness.md): fraction of "
                        "requests (deterministic hash of host|path|method) "
                        "routed to a newly reconciled snapshot generation "
                        "while the rest keeps serving the previous one "
                        "(0 = swaps serve 100%% immediately, the pre-ISSUE-10 "
                        "behavior).  Guards compare the cohorts; a breach "
                        "inside the window auto-rolls-back and quarantines "
                        "the poison configs, a clean window promotes")
    s.add_argument("--canary-window", type=float,
                   default=env_var("CANARY_WINDOW_S", 30.0),
                   help="Canary observation window in seconds before a "
                        "clean new generation promotes to 100%%")
    s.add_argument("--capture", action="store_true",
                   default=env_var("CAPTURE", False),
                   help="TRAFFIC REPLAY (docs/replay.md): arm the opt-in "
                        "full-fidelity capture log — sampled decisions "
                        "(authconfig + raw authorization JSON + verdict + "
                        "attributed rule) land in a byte-bounded in-memory "
                        "ring, fed off the hot path by the capture drain "
                        "thread.  The ring is what --replay-pregate "
                        "replays; add --capture-log-dir to persist it")
    s.add_argument("--capture-log-dir",
                   default=env_var("CAPTURE_LOG_DIR", ""),
                   help="Persist captured records as rotated checksummed "
                        "segments (*.atpucap) in this directory, pruned to "
                        "--capture-log-size-mb, readable offline by "
                        "'analysis --replay OLD NEW --log DIR'.  Implies "
                        "--capture")
    s.add_argument("--capture-log-size-mb", type=float,
                   default=env_var("CAPTURE_LOG_SIZE_MB", 64.0),
                   help="Capture budget in MB of ENCODED record bytes — "
                        "bounds the in-memory ring (oldest evicted) AND "
                        "the on-disk segment directory (oldest pruned); "
                        "bytes, not records, so fat documents cannot blow "
                        "the bound")
    s.add_argument("--capture-sample", type=int,
                   default=env_var("CAPTURE_SAMPLE", 1),
                   help="Capture 1-in-N decisions (1 = every decision; "
                        "the sampler is a per-batch stride, zero "
                        "per-request work)")
    s.add_argument("--replay-pregate", action="store_true",
                   default=env_var("REPLAY_PREGATE", False),
                   help="CHANGE SAFETY (docs/replay.md): before a "
                        "corpus-changing reconcile starts its canary, "
                        "replay the candidate snapshot against the live "
                        "capture ring through the exact host oracle; a "
                        "verdict diff breaching the canary guard "
                        "thresholds REJECTS the swap (typed "
                        "SnapshotRejected + replay-pregate-breach flight "
                        "bundle) with zero live exposure; a clean "
                        "preflight tightens the canary's guards")
    s.add_argument("--replay-pregate-budget-ms", type=float,
                   default=env_var("REPLAY_PREGATE_BUDGET_MS", 2000.0),
                   help="Wall-clock bound on the reconcile-path pregate "
                        "replay; records past the budget are reported as "
                        "truncated (partial evidence), never silently "
                        "skipped")
    s.add_argument("--corpus-pregate", default=env_var("CORPUS_PREGATE", ""),
                   help="POLICY CI (docs/policy_ci.md): a decision-corpus "
                        "file or directory (*.atpucorp — build with "
                        "'analysis --corpus-distill').  Before a "
                        "corpus-changing reconcile starts its canary, the "
                        "frequency-weighted corpus PLUS synthesized "
                        "truth-table witness rows for never-fired rules "
                        "are replayed old-vs-new; a weighted verdict diff "
                        "breaching the canary guard thresholds REJECTS "
                        "the swap (typed SnapshotRejected + "
                        "corpus-pregate-breach flight bundle) — including "
                        "edits to rules live traffic never exercised")
    s.add_argument("--corpus-pregate-budget-ms", type=float,
                   default=env_var("CORPUS_PREGATE_BUDGET_MS", 2000.0),
                   help="Wall-clock bound on the reconcile-path corpus "
                        "replay; rows past the budget are reported as "
                        "truncated (partial evidence), never silently "
                        "skipped")
    s.add_argument("--snapshot-history", type=int,
                   default=env_var("SNAPSHOT_HISTORY", 4),
                   help="Previous snapshot generations retained for "
                        "rollback (pointer swap — old device buffers are "
                        "double-buffer safe; bounds device/host memory of "
                        "retired corpora)")
    s.add_argument("--flight-keep", type=int,
                   default=env_var("AUTHORINO_TPU_FLIGHT_KEEP", 16),
                   help="Flight-recorder on-disk bundle retention: only "
                        "the newest N diagnostic bundles survive in "
                        "--flight-dir (anomaly storms must not fill the "
                        "disk)")
    s.add_argument("--flight-dir", default=env_var("AUTHORINO_TPU_FLIGHT_DIR", ""),
                   help="Directory for flight-recorder diagnostic bundles "
                        "(default: <tmp>/authorino-tpu-flight).  Bundles "
                        "auto-dump on anomalies: breaker OPEN, watchdog "
                        "fire, snapshot rejection, admission OVERLOADED")
    s.add_argument("--no-flight-recorder", action="store_true",
                   default=not env_var("AUTHORINO_TPU_FLIGHT_RECORDER", True),
                   help="Disable the lifecycle flight recorder (the "
                        "bounded event ring + anomaly bundle dumps)")
    s.add_argument("--drain-timeout", type=float,
                   default=env_var("DRAIN_TIMEOUT_S", 10.0),
                   help="Graceful-shutdown bound in seconds: SIGTERM stops "
                        "admission, then in-flight requests/batches get this "
                        "long to complete before the process exits")
    s.add_argument("--fault-profile", default=env_var("AUTHORINO_TPU_FAULTS", ""),
                   help="ARM THE FAULT-INJECTION PLANE (testing/chaos only): "
                        "a named profile (device-down, flaky, flap, "
                        "slow-device, wedge) or a rule spec — see "
                        "runtime/faults.py and docs/robustness.md")
    s.add_argument("--ovf-assist", action="store_true",
                   default=env_var("AUTHORINO_TPU_OVF_ASSIST", False),
                   help="ISSUE 14: answer membership-overflow rows "
                        "IN-KERNEL from exact precomputed assist columns "
                        "under a compact overflow mask, instead of routing "
                        "whole requests to the host oracle — the "
                        "cpu-grid-overflow lowerability caveat drops for "
                        "assisted corpora (the host-fallback lane remains "
                        "the degrade backstop)")
    s.add_argument("--no-metadata-prefetch", action="store_true",
                   default=not env_var("AUTHORINO_TPU_METADATA_PREFETCH",
                                       True),
                   help="Disable the metadata prefetch cache (ISSUE 14, "
                        "relations/prefetch.py): request-independent "
                        "external-metadata documents are pinned at "
                        "reconcile cadence and served with zero network "
                        "I/O; stale pins fall through to the live fetch")
    s.add_argument("--metadata-max-age", type=float,
                   default=env_var("METADATA_PREFETCH_MAX_AGE_S", 300.0),
                   help="Staleness bound in seconds for pinned prefetched "
                        "metadata documents: past it the pipeline falls "
                        "through to the live fetch (typed, exact)")
    s.add_argument("--metadata-refresh", type=float,
                   default=env_var("METADATA_PREFETCH_REFRESH_S", 60.0),
                   help="Background re-pin cadence in seconds for "
                        "prefetched metadata documents")
    s.add_argument("--strict-verify", action="store_true",
                   default=env_var("STRICT_VERIFY", False),
                   help="Tensor-lint every compiled snapshot before the "
                        "swap/generation bump (analysis/tensor_lint.py): a "
                        "snapshot with structural findings is rejected and "
                        "the previous one keeps serving (counted in "
                        "auth_server_snapshot_rejected_total)")
    s.add_argument("--snapshot-publish-dir", default=env_var("SNAPSHOT_PUBLISH_DIR", ""),
                   help="Compile-leader mode: publish every vetted compiled "
                        "snapshot into this directory (atomic blob + "
                        "MANIFEST.json; serve it to replicas over a shared "
                        "volume or any static HTTP server). "
                        "docs/control_plane.md")
    s.add_argument("--snapshot-source", default=env_var("SNAPSHOT_SOURCE", ""),
                   help="Serving-replica mode: poll this directory or "
                        "http(s) URL for leader-published snapshots and "
                        "apply each new vetted one without compiling. "
                        "Uncertified/corrupt snapshots are rejected and the "
                        "previous one keeps serving")
    s.add_argument("--snapshot-poll", type=float, default=env_var("SNAPSHOT_POLL_S", 5.0),
                   help="Replica poll interval in seconds (default 5)")
    s.add_argument("--fleet-hotset-k", type=int,
                   default=env_var("FLEET_HOTSET_K", 1024),
                   help="Verdict-cache warm-join (docs/fleet.md): a leader "
                        "publishes its top-K hot verdict-cache entries as "
                        "HOTSET.json next to the snapshot manifest, and a "
                        "replica seeds its cache from it at join, so a "
                        "cold replica joining mid-flood inherits the hot "
                        "set instead of re-missing it. 0 disables")
    s.add_argument("--fleet-hotset-s", type=float,
                   default=env_var("FLEET_HOTSET_S", 30.0),
                   help="Leader hot-set publish cadence in seconds "
                        "(default 30)")
    s.add_argument("--state-dir", default=env_var("STATE_DIR", ""),
                   help="Durable local state plane (docs/robustness.md "
                        "'Crash recovery & warm restart'): persist the last "
                        "vetted snapshot + verdict-cache hot set here and, "
                        "at boot, serve them fail-statically BEFORE the "
                        "control plane connects — a SIGKILLed process "
                        "restarts warm.  Must not equal --snapshot-source")
    s.add_argument("--max-snapshot-age", type=float,
                   default=env_var("MAX_SNAPSHOT_AGE_S", 0.0),
                   help="Staleness bound in seconds for a warm-restart "
                        "snapshot (0 = unbounded): past it the engine "
                        "still serves (fail-static) but /readyz degrades "
                        "to 'ok (degraded: stale snapshot, age=...)' and "
                        "a stale-snapshot flight anomaly records evidence")
    s.add_argument("--native-frontend", choices=["auto", "on", "off"],
                   default=env_var("NATIVE_FRONTEND", "auto"),
                   help="Serve the ext_authz gRPC port from the C++ device-owner "
                        "frontend (native/frontend.cpp): 'auto' uses it when the "
                        "native library loads and TLS is not requested; 'on' "
                        "requires it; 'off' uses the Python grpc.aio server")
    s.add_argument("--evaluator-cache-size", type=int, default=env_var("EVALUATOR_CACHE_SIZE", 4096))
    s.add_argument("--deep-metrics-enabled", action="store_true", default=env_var("DEEP_METRICS_ENABLED", False))
    s.add_argument("--debug-profile", action="store_true",
                   default=env_var("DEBUG_PROFILE", False),
                   help="Arm the /debug/profile?seconds=N endpoint (captures "
                        "a jax.profiler trace to a temp dir on demand)")
    s.add_argument("--auth-config-label-selector", default=env_var("AUTH_CONFIG_LABEL_SELECTOR", ""))
    s.add_argument("--secret-label-selector", default=env_var("SECRET_LABEL_SELECTOR", "authorino.kuadrant.io/managed-by=authorino"))
    s.add_argument("--allow-superseding-host-subsets", action="store_true", default=env_var("ALLOW_SUPERSEDING_HOST_SUBSETS", False))
    s.add_argument("--enable-leader-election", action="store_true", default=env_var("ENABLE_LEADER_ELECTION", False), help="Leader-elect the status writer (in-cluster mode)")
    s.add_argument("--tls-cert", default=env_var("TLS_CERT", ""), help="PEM cert for the ext_authz gRPC + HTTP listeners (ref main.go:456-470; TLS >= 1.2)")
    s.add_argument("--tls-cert-key", default=env_var("TLS_CERT_KEY", ""))
    s.add_argument("--oidc-tls-cert", default=env_var("OIDC_TLS_CERT", ""), help="PEM cert for the OIDC discovery listener")
    s.add_argument("--oidc-tls-cert-key", default=env_var("OIDC_TLS_CERT_KEY", ""))
    s.add_argument("--tracing-service-endpoint", default=env_var("TRACING_SERVICE_ENDPOINT", ""), help="OTLP endpoint (rpc://host:port or http(s)://...)")
    s.add_argument("--tracing-service-insecure", action="store_true", default=env_var("TRACING_SERVICE_INSECURE", False))
    s.add_argument("--log-level", default=env_var("LOG_LEVEL", "info"))
    s.add_argument("--jax-platform", default=env_var("JAX_PLATFORM", ""), help="Force a jax platform (e.g. cpu) — useful without TPU access")

    w = sub.add_parser("webhooks", help="Run the CRD conversion/validation webhook server")
    w.add_argument("--webhook-service-port", type=int, default=env_var("WEBHOOK_SERVICE_PORT", 9443))
    w.add_argument("--tls-cert", default=env_var("TLS_CERT", ""), help="PEM cert for the webhook listener")
    w.add_argument("--tls-cert-key", default=env_var("TLS_CERT_KEY", ""))
    w.add_argument("--log-level", default=env_var("LOG_LEVEL", "info"))

    sub.add_parser("version", help="Print version")
    return p


def _parse_tenant_weights(pairs) -> dict:
    """--tenant-weight ns/name=4 (repeatable) -> {tenant: weight}.  Junk
    entries are skipped with a warning — a typo must not stop serving."""
    out = {}
    for raw in pairs or []:
        tenant, sep, w = str(raw).rpartition("=")
        try:
            if not sep or not tenant:
                raise ValueError(raw)
            out[tenant] = float(w)
        except ValueError:
            logging.getLogger("authorino_tpu").warning(
                "ignoring malformed --tenant-weight %r "
                "(want TENANT=WEIGHT)", raw)
    return out


def _ssl_ctx(cert: str, key: str, what: str = "--tls-cert"):
    """Server-side TLS context, minimum 1.2 like the reference
    (ref main.go:456-470)."""
    import ssl

    if bool(cert) != bool(key):
        raise SystemExit(f"{what} and {what}-key must be provided together")
    if not cert:
        return None
    ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
    ctx.minimum_version = ssl.TLSVersion.TLSv1_2
    ctx.load_cert_chain(cert, key)
    return ctx


async def run_webhooks(args) -> None:
    """(ref: main.go `webhooks` command — conversion webhook server)"""
    from aiohttp import web

    from .service.webhooks import build_webhook_app

    logging.basicConfig(level=getattr(logging, args.log_level.upper(), logging.INFO))
    log = logging.getLogger("authorino_tpu.webhooks")

    ssl_ctx = _ssl_ctx(args.tls_cert, args.tls_cert_key)

    runner = web.AppRunner(build_webhook_app())
    await runner.setup()
    await web.TCPSite(runner, "0.0.0.0", args.webhook_service_port, ssl_context=ssl_ctx).start()
    log.info("webhooks listening on :%d (tls=%s)", args.webhook_service_port, bool(ssl_ctx))

    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        try:
            loop.add_signal_handler(sig, stop.set)
        except NotImplementedError:
            pass
    await stop.wait()
    await runner.cleanup()


async def run_server(args) -> None:
    from aiohttp import web

    import jax

    if args.jax_platform:
        jax.config.update("jax_platforms", args.jax_platform)
    from .utils.jax_env import boot_stamp, setup_jax

    compile_cache_dir = setup_jax()
    jax.devices()  # the backend comes up here, not under the first reconcile
    boot_stamp("backend_s")

    from .controllers.reconciler import AuthConfigReconciler, SecretReconciler
    from .controllers.sources import YamlDirSource
    from .evaluators import cache as cache_mod
    from .k8s.client import InMemoryCluster, LabelSelector, RestCluster
    from .runtime.engine import PolicyEngine
    from .service.grpc_server import build_server
    from .service.http_server import build_app
    from .service.oidc_server import build_oidc_app
    from .utils import metrics as metrics_mod

    logging.basicConfig(
        level=getattr(logging, args.log_level.upper(), logging.INFO),
        format="%(asctime)s %(levelname)s %(name)s %(message)s",
    )
    log = logging.getLogger("authorino_tpu")
    log.info("jax compile cache: %s", compile_cache_dir)

    cache_mod.EVALUATOR_CACHE_MAX_ENTRIES = args.evaluator_cache_size
    metrics_mod.DEEP_METRICS_ENABLED = args.deep_metrics_enabled

    # TLS material loads BEFORE the control plane starts: a bad flag/path
    # must fail at startup, not mid-boot with a leader lease already held.
    # The reads are back-to-back, which narrows (but cannot eliminate —
    # ssl.load_cert_chain only takes paths) the window in which a live
    # cert rotation could leave the gRPC and HTTP listeners on different
    # certificates; a restart converges them.
    ext_ssl = _ssl_ctx(args.tls_cert, args.tls_cert_key)
    oidc_ssl = _ssl_ctx(args.oidc_tls_cert, args.oidc_tls_cert_key, "--oidc-tls-cert")
    tls_credentials = None
    if ext_ssl is not None:
        import grpc as grpc_mod

        with open(args.tls_cert_key, "rb") as f:
            key_pem = f.read()
        with open(args.tls_cert, "rb") as f:
            cert_pem = f.read()
        tls_credentials = grpc_mod.ssl_server_credentials([(key_pem, cert_pem)])

    if args.tracing_service_endpoint:
        from .utils.tracing import setup_tracing

        setup_tracing(args.tracing_service_endpoint, insecure=args.tracing_service_insecure)

    # decision observability (ISSUE 9, docs/observability.md): the deny-
    # reason privacy knob, the decision-log ring, and the flight recorder
    from .runtime import provenance as prov_mod
    from .runtime.flight_recorder import RECORDER

    prov_mod.EXPOSE_DENY_REASON = bool(
        getattr(args, "expose_deny_reason", False))
    prov_mod.DECISIONS.configure(
        capacity=int(getattr(args, "decision_log_size", 1024)),
        sample_n=int(getattr(args, "decision_log_sample", 64)))
    RECORDER.configure(
        dump_dir=(str(getattr(args, "flight_dir", "") or "") or None),
        enabled=not getattr(args, "no_flight_recorder", False),
        keep=int(getattr(args, "flight_keep", 16)))

    # traffic capture (ISSUE 13, docs/replay.md): opt-in — a persistence
    # dir implies capture (persisting an unarmed log captures nothing)
    from .replay.capture import CAPTURE

    capture_dir = str(getattr(args, "capture_log_dir", "") or "")
    if getattr(args, "capture", False) or capture_dir:
        CAPTURE.configure(
            enabled=True,
            directory=capture_dir or None,
            size_mb=float(getattr(args, "capture_log_size_mb", 64.0)),
            sample_n=int(getattr(args, "capture_sample", 1)))
        log.info("traffic capture ARMED: sample 1-in-%d, %.1f MB budget%s",
                 CAPTURE.sample_n, CAPTURE.size_bytes / 1048576,
                 f", persisting to {capture_dir}" if capture_dir else
                 " (in-memory ring only)")

    fault_profile = str(getattr(args, "fault_profile", "") or "")
    if fault_profile:
        from .runtime import faults

        faults.FAULTS.arm(fault_profile)
        log.warning("fault injection ARMED via --fault-profile (%s): this "
                    "is a chaos/testing mode", fault_profile)

    if str(getattr(args, "snapshot_publish_dir", "") or "") \
            and not args.strict_verify:
        # a leader's published snapshots are only admissible at replicas
        # when certified, and certification only happens under strict
        # verify — publishing uncertified blobs would wedge every replica
        # on its last vetted snapshot with nothing flagging it here
        log.warning("--snapshot-publish-dir implies --strict-verify "
                    "(replicas only admit certified snapshots): enabling it")
        args.strict_verify = True

    if str(getattr(args, "state_dir", "") or "") and not args.strict_verify:
        # same admissibility argument as the publish dir: the warm-restart
        # loader IS the replica admission gate, and it only admits
        # certified blobs — persisting uncertified local reconciles would
        # make every warm restart a silent cold start
        log.warning("--state-dir implies --strict-verify (the warm-restart "
                    "loader only admits certified snapshots): enabling it")
        args.strict_verify = True

    device_timeout_ms = int(getattr(args, "device_timeout", 0) or 0)
    # NOTE: --batch-window-us no longer reaches the engine (the old
    # max_delay_s mirror was a documented no-op since the pipelined
    # dispatcher landed); it still feeds the native C++ gather window below
    engine = PolicyEngine(
        max_batch=args.batch_size,
        timeout_s=(args.timeout / 1000.0) if args.timeout else None,
        admission_target_s=float(getattr(args, "admission_target_ms", 50.0)) / 1e3,
        admission_queue_cap=int(getattr(args, "admission_queue_cap", 0)),
        adaptive_window=not getattr(args, "no_adaptive_window", False),
        brownout=not getattr(args, "no_brownout", False),
        brownout_max_batch=int(getattr(args, "brownout_max_batch", 32)),
        lane_select=not getattr(args, "no_lane_select", False),
        lane_host_max_rows=int(getattr(args, "lane_host_max_rows", 64)),
        speculative_dispatch=not getattr(args, "no_speculative_dispatch",
                                         False),
        max_inflight_batches=args.max_inflight_batches,
        dispatch_workers=args.dispatch_workers,
        verdict_cache_size=args.verdict_cache_size,
        batch_dedup=not args.no_batch_dedup,
        strict_verify=args.strict_verify,
        device_timeout_s=(device_timeout_ms / 1000.0) or None,
        breaker_threshold=int(getattr(args, "breaker_threshold", 5)),
        breaker_reset_s=float(getattr(args, "breaker_reset", 5.0)),
        slo_ms=float(getattr(args, "slo_ms", 0.0)),
        canary_fraction=float(getattr(args, "canary_fraction", 0.0)),
        canary_window_s=float(getattr(args, "canary_window", 30.0)),
        snapshot_history=int(getattr(args, "snapshot_history", 4)),
        replay_pregate=bool(getattr(args, "replay_pregate", False)),
        replay_pregate_budget_s=float(
            getattr(args, "replay_pregate_budget_ms", 2000.0)) / 1e3,
        corpus_pregate=str(getattr(args, "corpus_pregate", "") or ""),
        corpus_pregate_budget_s=float(
            getattr(args, "corpus_pregate_budget_ms", 2000.0)) / 1e3,
        ovf_assist=bool(getattr(args, "ovf_assist", False)) or None,
        metadata_prefetch=not getattr(args, "no_metadata_prefetch", False),
        metadata_prefetch_max_age_s=float(
            getattr(args, "metadata_max_age", 300.0)),
        metadata_prefetch_refresh_s=float(
            getattr(args, "metadata_refresh", 60.0)),
        tenant_qos=not getattr(args, "no_tenant_qos", False),
        tenant_default_weight=float(
            getattr(args, "tenant_default_weight", 1.0)),
        tenant_weights=_parse_tenant_weights(
            getattr(args, "tenant_weight", [])),
        tenant_quota_rps=float(getattr(args, "tenant_quota_rps", 0.0)),
        tenant_contain_threshold=float(
            getattr(args, "tenant_contain_threshold", 3.0)),
        tenant_top_k=int(getattr(args, "tenant_top_k", 16)),
    )

    # snapshot distribution (ISSUE 8, docs/control_plane.md): a compile
    # LEADER publishes every vetted snapshot into --snapshot-publish-dir
    # (serve it over HTTP or a shared volume); a serving REPLICA polls
    # --snapshot-source and applies each new vetted snapshot WITHOUT
    # compiling — compile once, serve many.  A replica keeps serving its
    # last vetted snapshot when the leader goes away.
    snapshot_replica = None
    publish_dir = str(getattr(args, "snapshot_publish_dir", "") or "")
    snapshot_source = str(getattr(args, "snapshot_source", "") or "")
    if (publish_dir and snapshot_source
            and not snapshot_source.startswith(("http://", "https://"))
            and os.path.realpath(publish_dir)
            == os.path.realpath(snapshot_source)):
        # same directory as both feed and sink is always a misconfig (the
        # publisher already refuses to republish LOADED snapshots, but
        # locally-reconciled ones would still collide with the feed)
        raise RuntimeError(
            "--snapshot-publish-dir and --snapshot-source point at the "
            "same directory: a node is either a compile leader or a "
            "serving replica, not its own upstream")
    if publish_dir:
        from .snapshots.distribution import SnapshotPublisher

        publisher = SnapshotPublisher(publish_dir)
        publisher.attach(engine)
        log.info("snapshot leader: publishing vetted snapshots to %s",
                 publish_dir)
        hotset_k = int(getattr(args, "fleet_hotset_k", 1024) or 0)
        if hotset_k > 0:
            # warm-join hot-set cadence (ISSUE 18, docs/fleet.md): fold
            # the verdict cache's top-K into HOTSET.json next to the
            # manifest.  Advisory end to end — a failed publish only
            # costs joiners a cold cache
            from .fleet import warmjoin as warmjoin_mod

            hotset_stop = threading.Event()
            hotset_s = max(1.0, float(getattr(args, "fleet_hotset_s", 30.0)))

            def _hotset_loop() -> None:
                while not hotset_stop.wait(hotset_s):
                    try:
                        digest = warmjoin_mod.export_hotset(
                            engine, k=hotset_k)
                        if digest is not None:
                            publisher.publish_hotset(digest)
                    except Exception:
                        log.exception("hot-set publish failed (warm-join "
                                      "is advisory; serving unaffected)")

            threading.Thread(target=_hotset_loop, daemon=True,
                             name="atpu-fleet-hotset").start()
            log.info("fleet hot-set: publishing top-%d verdicts every "
                     "%.0fs", hotset_k, hotset_s)
    # Durable local state plane (ISSUE 20, docs/robustness.md "Crash
    # recovery & warm restart"): warm-start from the local blob BEFORE the
    # replica's first poll, so a restarted process serves exact verdicts
    # fail-statically and the first successful poll swaps in the leader's
    # snapshot via the normal delta path (a reachable leader always wins).
    state_plane = None
    state_dir = str(getattr(args, "state_dir", "") or "")
    if state_dir:
        for other, flag in ((snapshot_source, "--snapshot-source"),
                            (publish_dir, "--snapshot-publish-dir")):
            if (other and not other.startswith(("http://", "https://"))
                    and os.path.realpath(state_dir)
                    == os.path.realpath(other)):
                # the state dir persists LOADED snapshots by design
                # (include_loaded) — pointed at the distribution feed it
                # would republish what it consumed (the exact loop the
                # published_origin breaker exists to prevent), and pointed
                # at the publish dir two writers would fight over MANIFEST
                raise RuntimeError(
                    f"--state-dir and {flag} point at the same directory: "
                    "the state plane is this process's private "
                    "crash-recovery store, never a distribution feed")
        from .runtime.state_plane import StatePlane

        state_plane = StatePlane(
            engine, state_dir,
            max_snapshot_age_s=float(getattr(args, "max_snapshot_age", 0.0)),
            hotset_k=int(getattr(args, "fleet_hotset_k", 1024) or 1024),
            hotset_s=max(1.0, float(getattr(args, "fleet_hotset_s", 30.0))))
        engine.state_plane = state_plane
        summary = state_plane.warm_start()
        state_plane.start()
        log.info("state plane: %s (snapshot=%s hotset=%s, "
                 "max_snapshot_age=%.0fs)", state_dir,
                 summary.get("snapshot"), summary.get("hotset"),
                 state_plane.max_snapshot_age_s)
    if snapshot_source:
        from .snapshots.distribution import SnapshotReplica

        if args.watch_dir or args.in_cluster:
            log.warning("--snapshot-source with a local control plane: the "
                        "replica feed and local reconciles will race for "
                        "the serving snapshot — pick one")
        snapshot_replica = SnapshotReplica(
            engine, snapshot_source,
            poll_s=float(getattr(args, "snapshot_poll", 5.0)))
        try:
            snapshot_replica.poll_once()  # best-effort warm start
            if int(getattr(args, "fleet_hotset_k", 1024) or 0) > 0:
                # verdict-cache warm-join (ISSUE 18, docs/fleet.md): seed
                # the cache from the leader's published hot-set digest so
                # a replica joining mid-flood starts warm.  Fail-open:
                # mismatch or absence just means joining cold
                from .fleet import warmjoin as warmjoin_mod
                from .snapshots.distribution import load_hotset

                imported, _ = warmjoin_mod.import_hotset(
                    engine, load_hotset(snapshot_source))
                if imported:
                    log.info("warm-join: inherited %d hot verdict(s) "
                             "from the leader's published hot set",
                             imported)
        except Exception:
            log.exception("snapshot warm start failed (replica keeps "
                          "polling; serving an empty index until a vetted "
                          "snapshot loads)")
        snapshot_replica.start()
        log.info("snapshot replica: polling %s every %.1fs",
                 snapshot_source, float(getattr(args, "snapshot_poll", 5.0)))

    selector = LabelSelector.parse(args.auth_config_label_selector) if args.auth_config_label_selector else None
    secret_selector = LabelSelector.parse(args.secret_label_selector) if args.secret_label_selector else None

    source = None
    status_updater = None
    cluster = RestCluster() if args.in_cluster else InMemoryCluster()
    reconciler = AuthConfigReconciler(
        engine,
        cluster=cluster,
        label_selector=selector,
        allow_superseding_host_subsets=args.allow_superseding_host_subsets,
    )
    secret_reconciler = SecretReconciler(engine, secret_label_selector=secret_selector)
    if args.in_cluster:
        # real-cluster control plane: watch AuthConfigs/Secrets, leader-elect
        # the status writer (ref: main.go:241-336)
        from .controllers.sources import K8sWatchSource
        from .controllers.status_updater import AuthConfigStatusUpdater

        source = K8sWatchSource(
            cluster, reconciler, secret_reconciler, secret_label_selector=secret_selector
        )
        # block serving until the first list lands (cache-sync semantics);
        # retries internally while the apiserver is unreachable
        await source.sync()
        boot_stamp("reconcile_s")
        source.start()
        from .k8s.leader import leader_election_id

        status_updater = AuthConfigStatusUpdater(
            reconciler, cluster, leases=cluster,
            namespace=os.environ.get("POD_NAMESPACE", "default"),
            leader_election=args.enable_leader_election,
            # per-shard lease: derived from the watched label selector so
            # label-sharded instances don't contend for one lease
            lease_name=leader_election_id(args.auth_config_label_selector or ""),
        ).start()
        log.info("watching AuthConfigs via the Kubernetes API")
    elif args.watch_dir:
        source = YamlDirSource(args.watch_dir, reconciler, cluster, secret_reconciler)
        await source.sync()
        boot_stamp("reconcile_s")
        source.start()
        log.info("watching manifests under %s", args.watch_dir)
    else:
        log.warning("no --watch-dir and not --in-cluster: serving an empty index")

    # HTTP /check (+ /metrics, /debug/vars, /debug/profile).  The native
    # frontend starts below, after this app — the holder closure lets
    # /debug/vars see it once it exists
    native_holder: dict = {}
    app = build_app(engine,
                    readiness=lambda: (reconciler.ready()
                                       and native_holder.get("warm", True)),
                    max_body=args.max_http_request_body_size,
                    frontend=lambda: native_holder.get("fe"),
                    enable_profile=bool(getattr(args, "debug_profile", False)))
    runner = web.AppRunner(app)
    await runner.setup()
    await web.TCPSite(runner, "0.0.0.0", args.ext_auth_http_port, ssl_context=ext_ssl).start()
    log.info("http /check listening on :%d (tls=%s)", args.ext_auth_http_port, bool(ext_ssl))

    # OIDC discovery (wristbands)
    oidc_runner = web.AppRunner(build_oidc_app(engine))
    await oidc_runner.setup()
    await web.TCPSite(oidc_runner, "0.0.0.0", args.oidc_http_port, ssl_context=oidc_ssl).start()
    log.info("oidc discovery listening on :%d (tls=%s)", args.oidc_http_port, bool(oidc_ssl))

    # gRPC ext_authz: the C++ device-owner frontend when possible (fast-lane
    # configs never touch Python per request; everything else rides the
    # asyncio pipeline via its slow queue), else the Python grpc.aio server.
    # The frontend has no TLS termination — TLS forces the Python server
    # (or a TLS-terminating proxy in front of the native listener).
    grpc_server = None
    native_fe = None
    native_mode = str(getattr(args, "native_frontend", "off")).lower()
    if native_mode not in ("auto", "on", "off"):
        # argparse validates choices only for CLI tokens, not env defaults —
        # a NATIVE_FRONTEND typo must not silently serve the slow path
        raise RuntimeError(f"invalid --native-frontend/NATIVE_FRONTEND value "
                           f"{native_mode!r} (want auto|on|off)")
    if native_mode in ("auto", "on") and tls_credentials is None:
        try:
            from .runtime.native_frontend import NativeFrontend

            native_fe = NativeFrontend(
                engine, port=args.ext_auth_grpc_port,
                max_batch=max(args.batch_size, 64),
                window_us=args.batch_window_us, bind_all=True,
                verdict_cache_size=args.verdict_cache_size,
                batch_dedup=not args.no_batch_dedup,
                strict_verify=args.strict_verify,
                device_timeout_s=(device_timeout_ms / 1000.0) or None,
                breaker_threshold=int(getattr(args, "breaker_threshold", 5)),
                breaker_reset_s=float(getattr(args, "breaker_reset", 5.0)),
                admission_target_s=float(getattr(
                    args, "admission_target_ms", 50.0)) / 1e3,
                brownout=not getattr(args, "no_brownout", False),
                brownout_max_rows=int(getattr(args, "brownout_max_batch", 32)),
                lane_select=not getattr(args, "no_lane_select", False),
                lane_host_max_rows=int(getattr(args, "lane_host_max_rows",
                                               64)),
                slo_ms=float(getattr(args, "slo_ms", 0.0)),
            )
            native_fe.start()
            # /debug/vars picks the frontend up; /readyz is 503 until warmed
            native_holder.update(fe=native_fe, warm=False)
            log.info("native grpc ext_authz listening on :%d; warming the "
                     "jit grid", args.ext_auth_grpc_port)
        except Exception as e:
            if native_fe is not None:
                # start() may fail after the C++ socket bound — release the
                # port or the grpc.aio fallback below cannot bind it
                try:
                    native_fe.stop()
                except Exception:
                    pass
            native_fe = None
            if native_mode == "on":
                raise
            log.warning("native frontend unavailable (%s); using grpc.aio", e)
    elif native_mode == "on" and tls_credentials is not None:
        raise RuntimeError("--native-frontend=on is incompatible with --tls-cert "
                           "(terminate TLS in front of the native listener)")
    if native_fe is not None:
        # not called ready until every warm-grid variant has compiled: a
        # cold XLA compile must not land on live batches (the completer
        # watchdog would time them into the degrade path), and a kernel
        # that cannot compile is a start-up error, not a log line
        warmed = await asyncio.get_running_loop().run_in_executor(
            None, native_fe.wait_warm, 3600.0)
        if not warmed:
            err = native_fe.warm_error or "warm grid timed out"
            await asyncio.get_running_loop().run_in_executor(
                None, native_fe.stop, 0.0)
            raise RuntimeError(f"native kernel warm grid failed: {err}")
        native_holder["warm"] = True
        boot_stamp("warm_s")
        log.info("native jit grid warm")
    if native_fe is None:
        grpc_server = build_server(
            engine, address=f"0.0.0.0:{args.ext_auth_grpc_port}",
            tls_credentials=tls_credentials,
        )
        await grpc_server.start()
        log.info("grpc ext_authz listening on :%d (tls=%s)", args.ext_auth_grpc_port, bool(tls_credentials))

    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        try:
            loop.add_signal_handler(sig, stop.set)
        except NotImplementedError:
            pass
    try:
        await stop.wait()
    finally:
        # graceful drain (ISSUE 5, docs/robustness.md): SIGTERM → stop
        # admitting (readyz flips 503 so the LB stops routing here; new
        # engine submits fail fast UNAVAILABLE), let in-flight RPCs and
        # device batches complete within --drain-timeout, flush telemetry,
        # then exit.  Runs on signal AND on task cancellation (embedders/
        # tests cancel the serve task): the native frontend's threads must
        # stop before interpreter teardown or they race the atexit executor
        # shutdown.  Every step is isolated — a second cancellation or one
        # failing stop must not skip the remaining teardown (esp.
        # native_fe.stop)
        import time as _time

        drain_s = float(getattr(args, "drain_timeout", 10.0))
        # ONE shared deadline across every drain stage: the gRPC grace, the
        # native frontend's drain loops and the engine drain each consume
        # only what is left, so SIGTERM-to-exit stays ≈ --drain-timeout
        # (not stages × timeout — a k8s terminationGracePeriodSeconds just
        # above the flag must always suffice)
        drain_deadline = _time.monotonic() + drain_s

        def drain_left() -> float:
            return max(0.5, drain_deadline - _time.monotonic())

        log.info("shutting down: draining (bound %.1fs)", drain_s)
        engine.begin_drain()

        async def best_effort(awaitable) -> None:
            try:
                await asyncio.shield(asyncio.ensure_future(awaitable))
            except (Exception, asyncio.CancelledError) as e:
                log.warning("shutdown step failed: %r", e)

        loop = asyncio.get_running_loop()
        # control plane first: no new snapshots compile mid-drain
        if snapshot_replica is not None:
            await best_effort(loop.run_in_executor(
                None, lambda: snapshot_replica.stop(min(2.0, drain_left()))))
        if status_updater is not None:
            await best_effort(status_updater.stop())
        if source is not None:
            await best_effort(source.stop())
        # the gRPC servers stop ACCEPTING and wait out in-flight Checks;
        # native stop() drains its slow lane + in-flight device batches and
        # runs the final telemetry fold before fe_stop
        if grpc_server is not None:
            await best_effort(grpc_server.stop(drain_left()))
        if native_fe is not None:
            # stop() runs two internally-bounded drain loops; halve the
            # remaining budget so their sum stays inside it
            await best_effort(loop.run_in_executor(
                None, lambda: native_fe.stop(drain_left() / 2)))
        # the engine dispatcher: every queued request and in-flight batch
        # resolves (host-degraded if the device is wedged) before exit
        drained = True
        try:
            drained = await loop.run_in_executor(None, engine.drain,
                                                 drain_left())
        except Exception as e:
            log.warning("engine drain failed: %r", e)
        log.info("drain %s", "complete" if drained else
                 "TIMED OUT (undrained work abandoned)")
        if CAPTURE.enabled:
            # persist the capture tail segment: a replayable log must not
            # lose its newest window to an orderly shutdown
            await best_effort(loop.run_in_executor(
                None, lambda: CAPTURE.flush(min(2.0, drain_left()))))
        if state_plane is not None:
            # best-effort final state flush (ISSUE 20): the last vetted
            # snapshot rides the publisher flush, the hot set exports once
            # more — so the NEXT boot warm-starts from the freshest state
            await best_effort(loop.run_in_executor(
                None, lambda: state_plane.shutdown(min(2.0, drain_left()))))
        await best_effort(runner.cleanup())
        await best_effort(oidc_runner.cleanup())
        from .utils.tracing import shutdown_tracing

        await best_effort(shutdown_tracing())  # flush the last spans


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    if args.command == "version":
        from . import __version__

        print(__version__)
        return 0
    if args.command == "server":
        asyncio.run(run_server(args))
        return 0
    if args.command == "webhooks":
        asyncio.run(run_webhooks(args))
        return 0
    build_parser().print_help()
    return 1


if __name__ == "__main__":
    sys.exit(main())
