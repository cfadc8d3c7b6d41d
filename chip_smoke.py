#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served path starts on the chip.

Drives the system's main path once, through the entry point a user calls
(``python -m authorino_tpu server --watch-dir DIR --native-frontend on``,
every other flag at its default), at BASELINE.json class 4: 1,000
AuthConfigs x 10 pattern rules, one host each, anonymous identity, v1beta2
YAML manifests.  Every config carries two per-config ``matches`` regexes
(the device DFA lane), an ``incl`` and an ``excl`` (the membership lane)
and six eq/neq leaves.

This process never imports jax.  From ``--seed`` it writes the manifests
and a request table, decides every request with the host expression oracle
(authorino_tpu.expressions over authorino_tpu.authjson), and starts the
server as its one child — the only process that opens the chip.  It waits
for the native warm grid, sends the gRPC Check()s (256 in flight) and the
HTTP /check requests, compares every answer with the oracle, reads
/metrics and /debug/vars, SIGTERMs the child and checks its exit code.

It refuses (exit 1, reasons on stderr, no result line) unless the kernels
ran on a TPU and nothing but the device path served: see ``judge``.  On
success the last line of stdout is the contract line
``{"ok": true, "device": {...}}``; the line before it is the full summary.
"""

from __future__ import annotations

import argparse
import asyncio
import hashlib
import json
import os
import random
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request
from typing import Any, Dict, List, Optional, Sequence, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

N_CONFIGS = 1000
N_GRPC = 4096
N_HTTP = 16
IN_FLIGHT = 256
NAMESPACE = "smoke"

# server log lines that mean a failure was absorbed instead of surfaced
SWALLOWED = (
    "Traceback (most recent call last)",
    "kernel warm grid failed",
    "failed to compile at",
    "native frontend unavailable",
    "native encoder build failed",
    "native encoder load failed",
    "native batch dispatch failed",
    "native batch completion failed",
    "retrying once on a fresh dispatch",
    "decided on the CPU backend after device failure",
)


# ---------------------------------------------------------------------------
# corpus + request table (deterministic under the seed, jax-free)
# ---------------------------------------------------------------------------


def config_patterns(i: int) -> List[Dict[str, str]]:
    """The ten pattern rules of config ``i``: two regexes and two
    membership leaves that differ per config, six equality leaves."""
    return [
        {"selector": "request.method", "operator": "neq", "value": "DELETE"},
        {"selector": "request.url_path", "operator": "matches",
         "value": f"^/api/v[0-9]+/t{i}/[a-z0-9/_-]*$"},
        {"selector": "request.headers.x-request-id", "operator": "matches",
         "value": f"^r{i}-[0-9a-f]{{8}}$"},
        {"selector": "request.headers.x-role", "operator": "incl",
         "value": f"role-{i % 17}"},
        {"selector": "request.headers.x-tier", "operator": "excl",
         "value": f"banned-{i}"},
        {"selector": "request.headers.x-org", "operator": "eq",
         "value": f"org-{i}"},
        {"selector": "request.headers.x-env", "operator": "neq",
         "value": "dev"},
        {"selector": "request.headers.x-region", "operator": "eq",
         "value": f"region-{i % 7}"},
        {"selector": "request.headers.x-plan", "operator": "neq",
         "value": f"free-{i}"},
        {"selector": "request.headers.x-client", "operator": "eq",
         "value": f"client-{i}"},
    ]


def host_of(i: int) -> str:
    return f"svc-{i}.smoke.test"


def make_corpus(n_configs: int) -> List[Dict[str, Any]]:
    """v1beta2 AuthConfig manifests: anonymous identity, one
    patternMatching evaluator with the ten rules, one host each."""
    return [{
        "apiVersion": "authorino.kuadrant.io/v1beta2",
        "kind": "AuthConfig",
        "metadata": {"name": f"cfg-{i:04d}", "namespace": NAMESPACE},
        "spec": {
            "hosts": [host_of(i)],
            "authentication": {"anon": {"anonymous": {}}},
            "authorization": {"rules": {"patternMatching": {
                "patterns": config_patterns(i)}}},
        },
    } for i in range(n_configs)]


def write_manifests(corpus: Sequence[Dict[str, Any]], directory: str) -> None:
    import yaml

    os.makedirs(directory, exist_ok=True)
    for lo in range(0, len(corpus), 100):
        path = os.path.join(directory, f"authconfigs-{lo:05d}.yaml")
        with open(path, "w") as f:
            yaml.safe_dump_all(corpus[lo:lo + 100], f, sort_keys=True)


def _allowed_values(i: int, rng: random.Random) -> Dict[str, str]:
    """Method, path and headers that satisfy all ten rules of config i.
    The request id is drawn per request, so encoded rows are unique and
    neither the verdict cache nor batch dedup can stand in for a launch."""
    return {
        "method": rng.choice(["GET", "POST", "PUT"]),
        "path": f"/api/v{rng.randrange(1, 10)}/t{i}/items/{rng.randrange(10**6)}",
        "x-request-id": f"r{i}-{rng.getrandbits(32):08x}",
        "x-role": f"role-{i % 17}",
        "x-tier": rng.choice(["gold", "silver", f"banned-{i + 1}"]),
        "x-org": f"org-{i}",
        "x-env": rng.choice(["prod", "staging"]),
        "x-region": f"region-{i % 7}",
        "x-plan": rng.choice(["team", f"free-{i + 1}"]),
        "x-client": f"client-{i}",
    }


# one way to break each of the ten rules, in config_patterns order
_VIOLATIONS = (
    ("method", lambda i, v: "DELETE"),
    ("path", lambda i, v: v["path"].replace(f"/t{i}/", f"/t{i + 1}/")),
    ("x-request-id", lambda i, v: v["x-request-id"][:-1] + "Z"),
    ("x-role", lambda i, v: f"role-{(i + 1) % 17}"),
    ("x-tier", lambda i, v: f"banned-{i}"),
    ("x-org", lambda i, v: f"org-{i + 1}"),
    ("x-env", lambda i, v: "dev"),
    ("x-region", lambda i, v: f"region-{(i + 1) % 7}"),
    ("x-plan", lambda i, v: f"free-{i}"),
    ("x-client", lambda i, v: f"client-{i + 1}"),
)


def make_requests(seed: int, n_configs: int, n_grpc: int,
                  n_http: int) -> List[Dict[str, Any]]:
    """The request table: config, transport, method, path, headers.  About
    half satisfy every rule; each of the rest breaks exactly one rule drawn
    uniformly from the ten, so every lane both allows and denies."""
    rng = random.Random(seed)
    table = []
    for k in range(n_grpc + n_http):
        i = rng.randrange(n_configs)
        vals = _allowed_values(i, rng)
        if rng.random() < 0.5:
            key, breaker = _VIOLATIONS[rng.randrange(len(_VIOLATIONS))]
            vals[key] = breaker(i, vals)
        table.append({
            "transport": "grpc" if k < n_grpc else "http",
            "config": i,
            "host": host_of(i),
            "method": vals.pop("method"),
            "path": vals.pop("path"),
            "headers": vals,
        })
    return table


def oracle_verdicts(corpus: Sequence[Dict[str, Any]],
                    table: Sequence[Dict[str, Any]]) -> List[bool]:
    """Every request decided by the host expression oracle, from the
    manifests as written (not from the generator's intent): the rules of
    the request's AuthConfig AND-ed over the authorization JSON the server
    would build for it."""
    from authorino_tpu.authjson import (
        CheckRequestModel,
        HttpRequestAttributes,
        build_authorization_json,
    )
    from authorino_tpu.expressions import All, Operator, Pattern

    rules = []
    for manifest in corpus:
        pats = manifest["spec"]["authorization"]["rules"][
            "patternMatching"]["patterns"]
        rules.append(All(*[
            Pattern(p["selector"], Operator.from_string(p["operator"]),
                    p["value"]) for p in pats]))
    out = []
    for req in table:
        headers = dict(req["headers"], host=req["host"])
        doc = build_authorization_json(
            CheckRequestModel(http=HttpRequestAttributes(
                method=req["method"], path=req["path"], host=req["host"],
                headers=headers)),
            {"identity": {"anonymous": True}})
        out.append(bool(rules[req["config"]].matches(doc)))
    return out


def table_digest(table: Sequence[Dict[str, Any]],
                 expected: Sequence[bool]) -> str:
    blob = json.dumps([table, list(expected)], sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest()


# ---------------------------------------------------------------------------
# the server child
# ---------------------------------------------------------------------------


def _free_ports(n: int) -> List[int]:
    socks = [socket.socket() for _ in range(n)]
    try:
        for s in socks:
            s.bind(("127.0.0.1", 0))
        return [s.getsockname()[1] for s in socks]
    finally:
        for s in socks:
            s.close()


def start_server(watch_dir: str, log_path: str, ports: Dict[str, int],
                 server_args: Sequence[str] = ()) -> subprocess.Popen:
    cmd = [sys.executable, "-m", "authorino_tpu", "server",
           "--watch-dir", watch_dir, "--native-frontend", "on",
           "--ext-auth-grpc-port", str(ports["grpc"]),
           "--ext-auth-http-port", str(ports["http"]),
           "--oidc-http-port", str(ports["oidc"]), *server_args]
    log = open(log_path, "wb")
    try:
        return subprocess.Popen(cmd, cwd=HERE, stdout=log,
                                stderr=subprocess.STDOUT)
    finally:
        log.close()  # the child holds its own descriptor


def _get(url: str, timeout: float = 10.0) -> Tuple[int, bytes]:
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def debug_vars(http_port: int) -> Optional[Dict[str, Any]]:
    try:
        status, body = _get(f"http://127.0.0.1:{http_port}/debug/vars")
    except (OSError, urllib.error.URLError):
        return None
    return json.loads(body) if status == 200 else None


def wait_ready(child: subprocess.Popen, http_port: int, timeout_s: float,
               expected_platform: str) -> Tuple[Optional[Dict[str, Any]], str]:
    """Poll /debug/vars until the native warm grid is complete and /readyz
    answers 200.  Returns (vars, "") when ready, else (last vars, reason).
    A wrong platform refuses as soon as the child reports it — before any
    request is served."""
    deadline = time.monotonic() + timeout_s
    last = None
    while time.monotonic() < deadline:
        if child.poll() is not None:
            return last, f"server exited with code {child.returncode} before ready"
        last = debug_vars(http_port) or last
        if last is not None:
            platform = (last.get("process") or {}).get("platform")
            if platform != expected_platform:
                return last, (f"platform is {platform!r}, not "
                              f"{expected_platform!r}")
            snap = (last.get("native_frontend") or {}).get("snapshot") or {}
            if snap.get("warm_error"):
                return last, f"kernel warm failed: {snap['warm_error']}"
            if snap.get("warm_done") and snap.get("warm"):
                status, body = _get(f"http://127.0.0.1:{http_port}/readyz")
                if status == 200:
                    return last, ""
        time.sleep(1.0)
    return last, f"not ready within {timeout_s:.0f}s"


def stop_server(child: subprocess.Popen, grace_s: float = 60.0) -> Optional[int]:
    """SIGTERM, wait out the drain, return the exit code (None: had to be
    killed)."""
    if child.poll() is None:
        child.send_signal(signal.SIGTERM)
        try:
            child.wait(grace_s)
        except subprocess.TimeoutExpired:
            child.kill()
            child.wait(30)
            return None
    return child.returncode


# ---------------------------------------------------------------------------
# traffic
# ---------------------------------------------------------------------------


def _check_request_bytes(req: Dict[str, Any]) -> bytes:
    from authorino_tpu import protos

    msg = protos.external_auth_pb2.CheckRequest()
    http = msg.attributes.request.http
    http.method = req["method"]
    http.path = req["path"]
    http.host = req["host"]
    http.headers["host"] = req["host"]
    for k, v in req["headers"].items():
        http.headers[k] = v
    return msg.SerializeToString()


def run_grpc(port: int, table: Sequence[Dict[str, Any]],
             in_flight: int = IN_FLIGHT,
             timeout_s: float = 120.0) -> List[Optional[int]]:
    """Send every gRPC row with ``in_flight`` Check()s outstanding; returns
    the CheckResponse status code per row (None: the RPC itself failed)."""
    import grpc

    from authorino_tpu import protos

    payloads = [_check_request_bytes(r) for r in table]

    async def drive() -> List[Optional[int]]:
        sem = asyncio.Semaphore(in_flight)
        async with grpc.aio.insecure_channel(f"127.0.0.1:{port}") as ch:
            call = ch.unary_unary(
                "/envoy.service.auth.v3.Authorization/Check",
                request_serializer=lambda b: b,
                response_deserializer=(
                    protos.external_auth_pb2.CheckResponse.FromString))

            async def one(payload: bytes) -> Optional[int]:
                async with sem:
                    try:
                        resp = await call(payload, timeout=timeout_s)
                    except grpc.aio.AioRpcError as e:
                        print(f"chip_smoke: Check() failed: {e.code()} "
                              f"{e.details()}", file=sys.stderr)
                        return None
                    return int(resp.status.code)

            return list(await asyncio.gather(*[one(p) for p in payloads]))

    return asyncio.run(drive())


def run_http(port: int, table: Sequence[Dict[str, Any]],
             timeout_s: float = 300.0) -> List[Optional[int]]:
    """The raw-HTTP adapter (the Python engine lane): the request's own
    method, path and headers are what the AuthConfig sees."""
    out: List[Optional[int]] = []
    for req in table:
        r = urllib.request.Request(
            f"http://127.0.0.1:{port}{req['path']}", method=req["method"],
            headers=dict(req["headers"], Host=req["host"]))
        try:
            with urllib.request.urlopen(r, timeout=timeout_s) as resp:
                out.append(resp.status)
        except urllib.error.HTTPError as e:
            out.append(e.code)
        except (OSError, urllib.error.URLError) as e:
            print(f"chip_smoke: /check failed: {e}", file=sys.stderr)
            out.append(None)
    return out


# ---------------------------------------------------------------------------
# evidence
# ---------------------------------------------------------------------------

def parse_metrics(text: str) -> Dict[str, List[Tuple[Dict[str, str], float]]]:
    """Prometheus exposition → {sample name: [(labels, value), ...]}."""
    from prometheus_client.parser import text_string_to_metric_families

    out: Dict[str, List[Tuple[Dict[str, str], float]]] = {}
    for family in text_string_to_metric_families(text):
        for sample in family.samples:
            out.setdefault(sample.name, []).append(
                (sample.labels, sample.value))
    return out


def metric_sum(metrics, name: str, **match: str) -> float:
    return sum(v for labels, v in metrics.get(name, ())
               if all(labels.get(k) == w for k, w in match.items()))


def metric_by(metrics, name: str, *keys: str) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for labels, v in metrics.get(name, ()):
        k = ",".join(labels.get(key, "") for key in keys)
        out[k] = out.get(k, 0.0) + v
    return {k: v for k, v in sorted(out.items()) if v}


def scrape(http_port: int) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    dv = debug_vars(http_port) or {}
    status, body = _get(f"http://127.0.0.1:{http_port}/metrics")
    return dv, (parse_metrics(body.decode()) if status == 200 else {})


def cache_entries(directory: Optional[str]) -> Optional[int]:
    if not directory:
        return None
    try:
        return sum(1 for n in os.listdir(directory) if n.endswith("-cache"))
    except OSError:
        return 0


def native_source_digest() -> str:
    from authorino_tpu.native import source_digest

    return source_digest()


_FAIL_COUNTERS = (
    "auth_server_degraded_decisions_total",
    "auth_server_batch_retries_total",
    "auth_server_device_watchdog_timeouts_total",
    "auth_server_brownout_decisions_total",
    "auth_server_brownout_batches_total",
)


def summarize(dv: Dict[str, Any], metrics: Dict[str, Any],
              warm_miss_at_ready: float) -> Dict[str, Any]:
    """The facts of one run, as the server reports them."""
    proc = dv.get("process") or {}
    fe = dv.get("native_frontend") or {}
    eng = dv.get("engine") or {}
    snap = fe.get("snapshot") or {}
    ledger = ((fe.get("kernel_cost") or {}).get("ledger")
              or (eng.get("kernel_cost") or {}).get("ledger") or {})
    wire = {k: ledger.get(k) or {} for k in ("native", "mesh", "host", "engine")}
    # rows a device launch decided on the wire lanes: the unique rows that
    # shipped plus the duplicates that fanned out from them (on a mesh the
    # engine lane's few HTTP rows fold into the same "mesh" ledger lane)
    wire_device_rows = sum(
        int(wire[k].get("device_rows", 0)) + int(wire[k].get("dedup_avoided_rows", 0))
        for k in ("native", "mesh") if wire[k].get("launches"))
    return {
        "platform": proc.get("platform"),
        "device_kind": proc.get("device_kind"),
        "device_count": proc.get("device_count"),
        "versions": {k: proc.get(k) for k in ("jax", "jaxlib", "libtpu")},
        "corpus": {
            "authconfigs": (eng.get("snapshot") or {}).get("configs"),
            "leaves": (eng.get("snapshot") or {}).get("n_leaves"),
            "sharded": (eng.get("snapshot") or {}).get("sharded"),
            "fast_configs": snap.get("fast_configs"),
        },
        "native_frontend": {
            "running": fe.get("running"),
            "source_digest": fe.get("source_digest"),
            "stats": {k: (fe.get("stats") or {}).get(k)
                      for k in ("fast", "slow", "allowed", "denied",
                                "notfound", "invalid", "parse_errors")},
        },
        "kernel": snap.get("kernel"),
        "warm_grid": snap.get("warm"),
        "ledger": wire,
        "wire_device_rows": wire_device_rows,
        "lane_decisions": metric_by(
            metrics, "auth_server_lane_decisions_total", "lane", "reason"),
        "lane_rows": {"native": (fe.get("lane_select") or {}).get("rows"),
                      "engine": (eng.get("lane_select") or {}).get("rows")},
        "jit_warm_cache": metric_by(
            metrics, "auth_server_jit_warm_cache_total", "outcome"),
        "jit_warm_miss_after_ready": metric_sum(
            metrics, "auth_server_jit_warm_cache_total", outcome="miss")
        - warm_miss_at_ready,
        "failure_counters": {name: metric_sum(metrics, name)
                             for name in _FAIL_COUNTERS},
        "breakers": {"engine": (eng.get("breaker") or {}).get("state"),
                     "native": (fe.get("breaker") or {}).get("state")},
        # per-shard upload bytes come from the cumulative counter: the
        # serving snapshot's own report reads zero whenever a re-list
        # reconciled an identical corpus (a reuse "upload")
        "mesh": ({"dp": eng["mesh"].get("dp"), "mp": eng["mesh"].get("mp"),
                  "launches": eng["mesh"].get("launches"),
                  "upload_bytes_by_shard": metric_by(
                      metrics, "auth_server_mesh_shard_upload_bytes_total",
                      "shard")}
                 if eng.get("mesh") else None),
        "compile_cache": proc.get("compile_cache"),
    }


def judge(s: Dict[str, Any], expected_platform: str, n_grpc: int,
          n_configs: int) -> List[str]:
    """Every reason this run does not prove the device path served."""
    why = []
    if s["platform"] != expected_platform:
        why.append(f"platform is {s['platform']!r}, not {expected_platform!r}")
    fe = s["native_frontend"]
    stats = fe["stats"]
    if not fe["running"] or stats.get("fast") != n_grpc or stats.get("slow"):
        why.append("the native gRPC listener's fast lane did not serve "
                   f"every Check(): {stats} for {n_grpc} sent")
    if fe["source_digest"] != s["native_source_digest"]:
        why.append("the served native library was not built from the "
                   f"sources on disk: {fe['source_digest']} vs "
                   f"{s['native_source_digest']}")
    if s["corpus"]["authconfigs"] != n_configs \
            or s["corpus"]["fast_configs"] != n_configs:
        why.append(f"corpus not fully served natively: {s['corpus']}")
    if s["mismatches"]:
        why.append(f"{len(s['mismatches'])} verdict mismatches against the "
                   f"host oracle, first: {s['mismatches'][:3]}")
    for name, v in s["failure_counters"].items():
        if v:
            why.append(f"{name} = {v:g}")
    for lane, state in s["breakers"].items():
        if state != "closed":
            why.append(f"{lane} breaker is {state!r}")
    if s["wire_device_rows"] < 0.9 * n_grpc:
        why.append(f"only {s['wire_device_rows']} of {n_grpc} gRPC rows were "
                   f"decided by a device launch (lanes: {s['lane_decisions']})")
    if s["jit_warm_miss_after_ready"]:
        why.append("jit_warm_cache_total{outcome=miss} moved after ready: "
                   f"{s['jit_warm_miss_after_ready']:g}")
    if s["mesh"] is not None:
        launches = s["mesh"].get("launches") or {}
        idle = sorted(d for d, n in launches.items() if not n)
        if not launches or idle:
            why.append("mesh launches is zero on "
                       f"{idle or 'every device'}")
        fed = s["mesh"].get("upload_bytes_by_shard") or {}
        starved = [str(k) for k in range(int(s["mesh"].get("mp") or 0))
                   if not fed.get(str(k))]
        if not fed or starved:
            why.append("mesh upload bytes are zero on shard(s) "
                       f"{starved or 'all'}")
    elif (s["device_count"] or 0) > 1:
        why.append(f"{s['device_count']} devices visible but the corpus is "
                   "not sharded over them")
    if s["log_findings"]:
        why.append(f"server log: {s['log_findings'][:3]}")
    if s["exit_code"] != 0:
        why.append(f"server exit code {s['exit_code']}")
    return why


def scan_log(path: str) -> List[str]:
    found = []
    try:
        with open(path, errors="replace") as f:
            for line in f:
                if any(marker in line for marker in SWALLOWED):
                    found.append(line.strip()[:300])
    except OSError as e:
        found.append(f"server log unreadable: {e}")
    return found


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def run_smoke(out_dir: str, seed: int, expected_platform: str,
              n_configs: int = N_CONFIGS, n_grpc: int = N_GRPC,
              n_http: int = N_HTTP, server_args: Sequence[str] = (),
              ready_timeout_s: float = 900.0
              ) -> Tuple[Dict[str, Any], List[str]]:
    """Generate, boot, drive, compare, stop.  Returns (summary, reasons):
    an empty reason list is a pass."""
    os.makedirs(out_dir, exist_ok=True)
    watch_dir = os.path.join(out_dir, "manifests")
    log_path = os.path.join(out_dir, "server.log")
    corpus = make_corpus(n_configs)
    table = make_requests(seed, n_configs, n_grpc, n_http)
    expected = oracle_verdicts(corpus, table)
    write_manifests(corpus, watch_dir)
    with open(os.path.join(out_dir, "requests.json"), "w") as f:
        json.dump({"seed": seed, "requests": table, "expected": expected}, f)

    from authorino_tpu.utils.jax_env import DEFAULT_CACHE_DIR  # jax-free

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_CACHE_DIR
    ports = dict(zip(("grpc", "http", "oidc"), _free_ports(3)))
    summary: Dict[str, Any] = {
        "seed": seed,
        "rules": n_configs * len(config_patterns(0)),
        "requests": {"grpc": n_grpc, "http": n_http,
                     "hosts": len({r["host"] for r in table}),
                     "expect_allow": sum(expected),
                     "digest": table_digest(table, expected)},
        "native_source_digest": native_source_digest(),
        "compile_cache_entries_before_boot": cache_entries(cache_dir),
    }
    # a chip belongs to one process: a parent that has touched jax holds
    # it, and the child that needs it then fails or hangs (a CPU is not
    # exclusive, which is what lets the tier-1 test call this from pytest)
    if expected_platform != "cpu" and "jax" in sys.modules:
        raise RuntimeError("the smoke parent imported jax; only the server "
                           "child may open the chip")
    t0 = time.monotonic()
    child = start_server(watch_dir, log_path, ports, server_args)
    try:
        dv, not_ready = wait_ready(child, ports["http"], ready_timeout_s,
                                   expected_platform)
        summary["time_to_ready_s"] = round(time.monotonic() - t0, 1)
        if not_ready:
            summary["exit_code"] = stop_server(child)
            summary["log_findings"] = scan_log(log_path)
            summary["process"] = (dv or {}).get("process")
            return summary, [not_ready] + [
                f"server log: {line}" for line in summary["log_findings"][:3]]
        # counted where the server says its cache is (normally cache_dir)
        cache_dir = dv["process"]["compile_cache"]["dir"]
        summary["compile_cache_entries_at_ready"] = cache_entries(cache_dir)
        _, m0 = scrape(ports["http"])
        warm_miss0 = metric_sum(m0, "auth_server_jit_warm_cache_total",
                                outcome="miss")

        grpc_rows = [r for r in table if r["transport"] == "grpc"]
        http_rows = [r for r in table if r["transport"] == "http"]
        t1 = time.monotonic()
        got = run_grpc(ports["grpc"], grpc_rows)
        summary["grpc_wall_s"] = round(time.monotonic() - t1, 2)
        got += run_http(ports["http"], http_rows)

        mismatches = []
        for k, (req, want, code) in enumerate(zip(table, expected, got)):
            if req["transport"] == "grpc":
                ok = code == (0 if want else 7)  # OK / PERMISSION_DENIED
            else:
                ok = code == (200 if want else 403)
            if not ok:
                mismatches.append({"row": k, "transport": req["transport"],
                                   "host": req["host"], "want_allow": want,
                                   "got": code})
        summary["mismatches"] = mismatches

        dv, metrics = scrape(ports["http"])
        summary.update(summarize(dv, metrics, warm_miss0))
        summary["compile_cache_entries_at_end"] = cache_entries(cache_dir)
    finally:
        summary["exit_code"] = stop_server(child)
    summary["log_findings"] = scan_log(log_path)
    return summary, judge(summary, expected_platform, n_grpc, n_configs)


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=21)
    ap.add_argument("--out", default=os.path.join(HERE, "chip_smoke_out"),
                    help="directory for the manifests, the request table "
                         "and the server log")
    args = ap.parse_args(argv)
    # the accepted platform is not an argument: this command proves a TPU
    summary, why = run_smoke(args.out, args.seed, expected_platform="tpu")
    summary["claim"] = None
    if why:
        print(json.dumps(summary), file=sys.stderr)
        for reason in why:
            print(f"chip_smoke: REFUSED: {reason}", file=sys.stderr)
        return 1
    print(json.dumps(summary))
    print(json.dumps({"ok": True, "device": {
        "platform": summary["platform"], "kind": summary["device_kind"],
        "count": summary["device_count"]}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
