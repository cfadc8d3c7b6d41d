"""The system under test as the benchmark sees it: one child process,
`python -m authorino_tpu server --watch-dir DIR --native-frontend on`, every
other flag at its default, and what it says about itself over HTTP.

Lifecycle and scrape are copied from chip_smoke.py (proven on the chip by
PR 21) so that a later change to that file cannot move the benchmark.
"""

from __future__ import annotations

import hashlib
import json
import os
import re
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request
from typing import Any, Dict, List, Optional, Sequence, Tuple

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

# a device failure that was absorbed: any of these above zero refuses the run
FAIL_COUNTERS = (
    "auth_server_degraded_decisions_total",
    "auth_server_batch_retries_total",
    "auth_server_device_watchdog_timeouts_total",
)

NATIVE_SOURCES = ("encoder.cpp", "frontend.cpp", "pymod.cpp")


def free_ports(n: int) -> List[int]:
    socks = [socket.socket() for _ in range(n)]
    try:
        for s in socks:
            s.bind(("127.0.0.1", 0))
        return [s.getsockname()[1] for s in socks]
    finally:
        for s in socks:
            s.close()


def write_manifests(corpus: Sequence[Dict[str, Any]], directory: str) -> None:
    import yaml

    os.makedirs(directory, exist_ok=True)
    for lo in range(0, len(corpus), 100):
        path = os.path.join(directory, f"authconfigs-{lo:05d}.yaml")
        with open(path, "w") as f:
            yaml.safe_dump_all(corpus[lo:lo + 100], f, sort_keys=True)


def start(root: str, watch_dir: str, log_path: str, ports: Dict[str, int],
          tmp_dir: str, profile: bool) -> subprocess.Popen:
    """The one child.  Its temporary files (a pulled trace, flight-recorder
    bundles) go under the run's own directory."""
    cmd = [sys.executable, "-m", "authorino_tpu", "server",
           "--watch-dir", watch_dir, "--native-frontend", "on",
           "--ext-auth-grpc-port", str(ports["grpc"]),
           "--ext-auth-http-port", str(ports["http"]),
           "--oidc-http-port", str(ports["oidc"])]
    if profile:
        cmd.append("--debug-profile")
    os.makedirs(tmp_dir, exist_ok=True)
    env = dict(os.environ, TMPDIR=tmp_dir)
    with open(log_path, "wb") as log:  # the child holds its own descriptor
        return subprocess.Popen(cmd, cwd=root, stdout=log,
                                stderr=subprocess.STDOUT, env=env)


def get(url: str, timeout: float = 30.0) -> Tuple[int, bytes]:
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def debug_vars(http_port: int) -> Optional[Dict[str, Any]]:
    try:
        status, body = get(f"http://127.0.0.1:{http_port}/debug/vars")
    except (OSError, urllib.error.URLError):
        return None
    return json.loads(body) if status == 200 else None


def wait_ready(child: subprocess.Popen, http_port: int, timeout_s: float,
               expected_platform: str) -> Tuple[Optional[Dict[str, Any]], str]:
    """Poll /debug/vars until the native warm grid is complete and /readyz
    answers 200.  Returns (vars, "") when ready, else (last vars, reason).  A
    wrong platform refuses as soon as the child reports it."""
    deadline = time.monotonic() + timeout_s
    last = None
    while time.monotonic() < deadline:
        if child.poll() is not None:
            return last, f"server exited with code {child.returncode} before ready"
        last = debug_vars(http_port) or last
        if last is not None:
            platform = (last.get("process") or {}).get("platform")
            if platform != expected_platform:
                return last, f"platform is {platform!r}, not {expected_platform!r}"
            snap = (last.get("native_frontend") or {}).get("snapshot") or {}
            if snap.get("warm_error"):
                return last, f"kernel warm failed: {snap['warm_error']}"
            if snap.get("warm_done") and snap.get("warm"):
                status, _ = get(f"http://127.0.0.1:{http_port}/readyz")
                if status == 200:
                    return last, ""
        time.sleep(0.5)
    return last, f"not ready within {timeout_s:.0f}s"


def stop(child: subprocess.Popen, grace_s: float = 60.0) -> Optional[int]:
    """SIGTERM, wait out the drain, return the exit code (None: killed)."""
    if child.poll() is None:
        child.send_signal(signal.SIGTERM)
        try:
            child.wait(grace_s)
        except subprocess.TimeoutExpired:
            child.kill()
            child.wait(30)
            return None
    return child.returncode


_SAMPLE = re.compile(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{(.*)\})?\s+(\S+)$")
_LABEL = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def parse_metrics(text: str, prefixes: Sequence[str]) -> Dict[str, List[Tuple[Dict[str, str], float]]]:
    """Prometheus exposition -> {sample name: [(labels, value)]}, for the
    sample names that start with one of `prefixes` (the page also carries
    series per AuthConfig, which nothing here reads)."""
    out: Dict[str, List[Tuple[Dict[str, str], float]]] = {}
    for line in text.splitlines():
        if not line.startswith(tuple(prefixes)):
            continue
        m = _SAMPLE.match(line)
        if not m:
            continue
        name, labels, value = m.groups()
        out.setdefault(name, []).append(
            (dict(_LABEL.findall(labels or "")), float(value)))
    return out


def metric_sum(metrics, name: str, **match: str) -> float:
    return sum(v for labels, v in metrics.get(name, ())
               if all(labels.get(k) == w for k, w in match.items()))


METRIC_PREFIXES = FAIL_COUNTERS + (
    "auth_server_jit_warm_cache_total",
    "auth_server_frontend_stage_duration_seconds",
)


def scrape(http_port: int) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """(/debug/vars, /metrics) as the child reports them now."""
    dv = debug_vars(http_port) or {}
    status, body = get(f"http://127.0.0.1:{http_port}/metrics")
    return dv, (parse_metrics(body.decode(), METRIC_PREFIXES) if status == 200 else {})


def native_source_digest(root: str) -> str:
    """sha256 over the extension's sources on disk, as
    authorino_tpu.native.source_digest computes the digest the server
    reports for the library it loaded."""
    h = hashlib.sha256()
    for name in NATIVE_SOURCES:
        with open(os.path.join(root, "native", name), "rb") as f:
            h.update(name.encode() + b"\0" + f.read() + b"\0")
    return h.hexdigest()


def scan_log(path: str) -> List[str]:
    """Each marked line of the server's log, with the lines that follow it
    (a traceback's frames and its last line, which names the error)."""
    found = []
    try:
        with open(path, errors="replace") as f:
            lines = f.read().splitlines()
    except OSError as e:
        return [f"server log unreadable: {e}"]
    for k, line in enumerate(lines):
        if any(marker in line for marker in SWALLOWED):
            found.append(" | ".join(x.strip()[:200] for x in lines[k:k + 14]))
    return found
