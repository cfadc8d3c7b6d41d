"""One run of one cell: generate, boot the server, drive it over the wire,
compare every answer with the plain reference, gate, reduce, report.

Nothing here names a cell, a configuration, a traffic mix or a metric,
end-to-end or per-layer: each is found by the name BENCHMARK.json gives it,
as a file under configs/, corpora/, traffic/, metrics/ and readers/.  This process never
imports jax: the server child is the only process that opens the chip.
"""

from __future__ import annotations

import importlib.util
import json
import os
import random
import shutil
import subprocess
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

import child
import hbm
import trafficgen
import wire
from reference import Reference

HERE = os.path.dirname(os.path.abspath(__file__))

RECORD = np.dtype([("row", "<u4"), ("code", "<i4"), ("due", "<f4"),
                   ("sent", "<f4"), ("done", "<f4")])
GRACE_S = 60.0     # how long after the close an answer is still waited for
# The profiler's Python tracer slows the host severalfold, so a traced run
# records the window's last TRACE_S seconds but TRACE_END_S, and reads
# counters and spans over the part of the window before them.  What is set
# against the trace's own times (rows under the kernel's seconds) is counted
# by two readings of the ledger inside the traced seconds, LEDGER_IN_TRACE_S
# after the profile was asked for.
TRACE_S = 3.0
TRACE_END_S = 1.0
LEDGER_IN_TRACE_S = (0.3, 2.0)
LIMITS = {"wrong": 0, "unanswered": 0}  # exact comparisons: the limit is 0


class Refused(Exception):
    """The run proves nothing: no result line, a non-zero exit."""


# ---------------------------------------------------------------------------
# the cell, from data
# ---------------------------------------------------------------------------


def _load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """benchmark/<kind>/<name>.py, by name."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise Refused(f"no {kind} module {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(manifest: Dict[str, Any], root: str, workload: str) -> Dict[str, Any]:
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r} in BENCHMARK.json")
    cell = dict(cells[workload])
    entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    cell["config_file"] = _load_json(os.path.join(root, entry["file"]))
    cell["mix"] = _load_json(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))

    def mine(metric: Dict[str, Any]) -> bool:
        return "workloads" not in metric or workload in metric["workloads"]

    cell["end_to_end"] = [m for m in manifest["end_to_end"] if mine(m)]
    reported = {m["name"] for m in cell["end_to_end"]}
    # a per-layer metric with no list of its own is read in every cell that
    # reports the end-to-end metric it moves
    cell["per_layer"] = [m for m in manifest["per_layer"]
                         if (workload in m["workloads"] if "workloads" in m
                             else m["moves"] in reported)]
    return cell


# ---------------------------------------------------------------------------
# traffic
# ---------------------------------------------------------------------------


def build_loadgen(out_root: str) -> str:
    """The copied generator, built with the g++ line of
    authorino_tpu.native.build_loadgen; rebuilt when its source is newer."""
    src = os.path.join(HERE, "loadgen", "loadgen.cpp")
    binary = os.path.join(out_root, "loadgen")
    if (os.path.exists(binary)
            and os.path.getmtime(binary) >= os.path.getmtime(src)):
        return binary
    os.makedirs(out_root, exist_ok=True)
    cmd = ["g++", "-O2", "-std=c++17", src, "-o", binary + ".tmp"]
    subprocess.run(cmd, check=True, capture_output=True, timeout=120)
    os.replace(binary + ".tmp", binary)
    return binary


def make_traffic(cell: Dict[str, Any], generator, manifests: Sequence[dict],
                 seed: int, seconds: float) -> Dict[str, Any]:
    """Rows, what the reference answers to each, and the generator's input."""
    config, mix = cell["config_file"], cell["mix"]
    rows = generator.requests(dict(config["params"], **config["requests"]),
                              int(mix["distinct_rows"]),
                              random.Random(seed))
    ref = Reference(manifests)
    expected = np.fromiter((ref.decide(r) for r in rows), dtype=np.int32,
                           count=len(rows))
    order = trafficgen.order(mix, seed)
    due = trafficgen.due_times(mix, seed, seconds)
    blob = (wire.payload_section(rows) + wire.section(order.tobytes())
            + wire.section(b"" if due is None else due.tobytes()))
    return {"rows": rows, "expected": expected, "order": order, "due": due,
            "stdin": blob}


def run_loadgen(binary: str, port: int, mix: Dict[str, Any], seconds: float,
                stdin: bytes) -> Tuple[np.ndarray, Dict[str, Any]]:
    # an open loop has no depth to keep
    cmd = [binary, "127.0.0.1", str(port), str(float(mix["warm_s"])),
           str(seconds), str(int(mix.get("depth", 0))), str(int(mix["conns"])),
           str(GRACE_S)]
    proc = subprocess.Popen(cmd, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE)
    try:
        out, err = proc.communicate(
            stdin, timeout=float(mix["warm_s"]) + seconds + GRACE_S + 60)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.communicate()
        raise Refused("the load generator did not end")
    if proc.returncode != 0:
        raise Refused(f"the load generator failed ({proc.returncode}): "
                      f"{err.decode(errors='replace')[-500:]}")
    summary = json.loads(err.decode().strip().splitlines()[-1])
    return np.frombuffer(out, dtype=RECORD), summary


# ---------------------------------------------------------------------------
# what the window produced
# ---------------------------------------------------------------------------


def compare(records: np.ndarray, expected: np.ndarray, seconds: float,
            codes: Optional[np.ndarray] = None) -> Dict[str, Any]:
    """Every request that touched the window, answer against reference.
    An answer that came after the close is late, not wrong; one that never
    came, or says another thing than the reference, decides `correct`.
    `codes` puts other answers in the place of those received (the control)."""
    w_ms = seconds * 1e3
    touched = (records["due"] >= 0) | (records["done"] >= 0)
    rec = records[touched]
    answered = ~np.isnan(rec["done"])
    got = rec["code"] if codes is None else codes[touched]
    right = got == expected[rec["row"]]
    wrong = int((answered & ~right).sum())
    unanswered = int((~answered).sum())
    in_window = (rec["due"] >= 0) & (rec["due"] < w_ms)
    return {
        "records": rec, "right": right & answered, "in_window": in_window,
        "numbers": {"wrong": wrong, "unanswered": unanswered},
        "compared": int(touched.sum()),
        "attempted": int(in_window.sum()),
        "failed": int((in_window & ~(right & answered)).sum()),
        "correct": wrong <= LIMITS["wrong"] and unanswered <= LIMITS["unanswered"]
        and bool(touched.sum()),
    }


# ---------------------------------------------------------------------------
# the gate: what makes a run prove nothing
# ---------------------------------------------------------------------------


def gate(dv: Dict[str, Any], metrics: Dict[str, Any], warm_miss_at_ready: float,
         expected_platform: str, chips: int, source_digest: str,
         exit_code: Optional[int], log_findings: Sequence[str]) -> List[str]:
    why = []
    proc = dv.get("process") or {}
    fe = dv.get("native_frontend") or {}
    eng = dv.get("engine") or {}
    if proc.get("platform") != expected_platform:
        why.append(f"platform is {proc.get('platform')!r}, not {expected_platform!r}")
    if int(proc.get("device_count") or 0) < chips:
        why.append(f"{proc.get('device_count')} devices, the cell asks for {chips}")
    if not fe.get("running"):
        why.append("the native frontend is not running")
    if fe.get("source_digest") != source_digest:
        why.append("the served native library was not built from the sources "
                   f"on disk: {fe.get('source_digest')} vs {source_digest}")
    for name in child.FAIL_COUNTERS:
        v = child.metric_sum(metrics, name)
        if v:
            why.append(f"{name} = {v:g}: a device failure was absorbed")
    for lane, owner in (("engine", eng), ("native", fe)):
        state = (owner.get("breaker") or {}).get("state")
        if state != "closed":
            why.append(f"{lane} breaker is {state!r}")
    miss = child.metric_sum(metrics, "auth_server_jit_warm_cache_total",
                            outcome="miss") - warm_miss_at_ready
    if miss:
        why.append(f"jit_warm_cache_total{{outcome=miss}} moved by {miss:g} "
                   "after ready: a compilation inside the run")
    if log_findings:
        why.append(f"server log: {list(log_findings[:3])}")
    if exit_code != 0:
        why.append(f"server exit code {exit_code}")
    return why


# ---------------------------------------------------------------------------
# the traced run
# ---------------------------------------------------------------------------


def pull_trace(http_port: int, at: float, out: Dict[str, Any]) -> None:
    """Ask the child to record TRACE_S seconds of its own process, the only
    one that can trace the chip, starting at monotonic time `at`."""
    time.sleep(max(0.0, at - time.monotonic()))
    out["sent"] = time.monotonic()
    try:
        status, body = child.get(
            f"http://127.0.0.1:{http_port}/debug/profile?seconds={TRACE_S}",
            timeout=TRACE_S + 120)
        out["status"], out["body"] = status, body.decode(errors="replace")
    except OSError as e:
        out["status"], out["body"] = None, str(e)


def ledger_in_trace(http_port: int, at: float, pulled: Dict[str, Any],
                    out: Dict[str, Any]) -> None:
    """Two readings of /debug/vars that lie inside the traced seconds.  The
    child starts and stops its profiler on the event loop that also answers
    /debug/vars, and sleeps TRACE_S seconds between the two: a reading asked
    for after the profile request was sent is answered once the profiler
    runs, and one that is back within TRACE_S of that request was answered
    before the profiler stopped.  Readings that cannot be placed so are
    dropped, and the readers that need them find nothing to read."""
    got = []
    for offset in LEDGER_IN_TRACE_S:
        time.sleep(max(0.0, at + offset - time.monotonic()))
        asked = time.monotonic()
        got.append((asked, child.debug_vars(http_port), time.monotonic()))
    (asked0, first, _), (_, last, back1) = got
    sent = pulled.get("sent")
    inside = bool(first and last and sent is not None and sent < asked0
                  and back1 < sent + TRACE_S)
    if inside:
        out["trace_vars0"], out["trace_vars1"] = first, last
    print(f"benchmark: ledger readings {'inside' if inside else 'not inside'} "
          f"the trace: profile sent {sent}, readings "
          + ", ".join(f"asked {a:.3f} back {b:.3f}" for a, _, b in got),
          file=sys.stderr)


def reduce_trace(trace_dir: str) -> Optional[Dict[str, Any]]:
    """.xplane.pb -> busy, idle, modules and ops, in a helper process started
    after the child has exited and held to the CPU."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "trace_reduce.py"), trace_dir,
         str(TRACE_S)],
        capture_output=True, env=env, timeout=200)
    if proc.returncode != 0:
        print("benchmark: trace reduction failed: "
              + proc.stderr.decode(errors="replace")[-800:], file=sys.stderr)
        return None
    return json.loads(proc.stdout.decode().strip().splitlines()[-1])


def read_metrics(entries: Sequence[Dict[str, Any]],
                 ctx: Dict[str, Any]) -> Dict[str, Any]:
    """Each metric, end-to-end or per-layer, through the reader its own file
    (metrics/<name>.json) names.  A reader that finds nothing to read
    returns None and the metric is left out of the line."""
    out = {}
    for metric in entries:
        spec = _load_json(os.path.join(HERE, "metrics", metric["name"] + ".json"))
        reader = load_module("readers", spec["reader"])
        value = reader.read(ctx, **spec.get("args", {}))
        if value is not None:
            out[metric["name"]] = {"value": float(value), "unit": metric["unit"]}
    return out


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def device_memory_bytes(dv: Dict[str, Any], sampled: Optional[int]) -> int:
    """The peak of HBM in use as libtpu's metric service gave it to the
    sampler (hbm.py).  Where no service answered: what the child says its
    control plane uploaded, a lower bound of the peak, and a note."""
    if sampled:
        return int(sampled)
    print("benchmark: no HBM reading from TPU_RUNTIME_METRICS_PORTS; "
          "memory_peak_bytes is the uploaded operands only", file=sys.stderr)
    upload = ((dv.get("engine") or {}).get("control_plane") or {}).get("upload") or {}
    return int(upload.get("full_bytes") or 0)


def run_cell(manifest: Dict[str, Any], root: str, workload: str, seed: int,
             seconds: float, trace: bool, expected_platform: str,
             t_start: float, out_root: Optional[str] = None) -> Dict[str, Any]:
    """The whole run of a cell of BENCHMARK.json.  Returns the result (the
    line's keys, plus `evidence` for callers that look further); raises
    Refused where it proves nothing."""
    return run(load_cell(manifest, root, workload), root, seed, seconds, trace,
               expected_platform, t_start, out_root)


def run(cell: Dict[str, Any], root: str, seed: int, seconds: float, trace: bool,
        expected_platform: str, t_start: float,
        out_root: Optional[str] = None) -> Dict[str, Any]:
    """One run of a loaded cell (tests hand in a tiny one)."""
    config, mix, workload = cell["config_file"], cell["mix"], cell["name"]
    if not os.path.isfile(os.path.join(root, "authorino_tpu", "__main__.py")):
        raise Refused(f"no system under test under {root}")
    out_root = out_root or os.path.join(HERE, "_chip")
    out_dir = os.path.join(out_root, f"{workload}-{seed}-{int(trace)}")
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    log_path = os.path.join(out_dir, "server.log")

    generator = load_module("corpora", config["generator"])
    manifests = generator.manifests(config["params"])
    watch_dir = os.path.join(out_dir, "manifests")
    child.write_manifests(manifests, watch_dir)
    ports = dict(zip(("grpc", "http", "oidc"), child.free_ports(3)))
    t_child = time.monotonic()
    server = child.start(root, watch_dir, log_path, ports,
                         os.path.join(out_dir, "tmp"), profile=trace)
    exit_code: Optional[int] = None
    memory = hbm.Sampler()
    memory.start()
    try:
        # while the child boots: the generator, the table, the reference
        binary = build_loadgen(out_root)
        traffic = make_traffic(cell, generator, manifests, seed, seconds)
        dv, not_ready = child.wait_ready(server, ports["http"], 1100.0,
                                         expected_platform)
        if not_ready:
            raise Refused(not_ready)
        ready_s = time.monotonic() - t_child
        _, m_ready = child.scrape(ports["http"])
        warm_miss = child.metric_sum(m_ready, "auth_server_jit_warm_cache_total",
                                     outcome="miss")

        untraced: Dict[str, Any] = {}
        pulled: Dict[str, Any] = {}
        helpers = []
        if trace:
            t_open = time.monotonic() + float(mix["warm_s"])
            t_trace = t_open + seconds - TRACE_END_S - TRACE_S
            if t_trace - t_open < 2.0:
                raise Refused(f"a traced run needs more than {seconds:g} seconds")

            def scrapes():
                """The counters at the window's open and just before the
                profiler starts: the untraced part of the window."""
                for key, at in (("0", t_open), ("1", t_trace - 0.5)):
                    time.sleep(max(0.0, at - time.monotonic()))
                    untraced["t" + key] = time.monotonic()
                    untraced["vars" + key], untraced["metrics" + key] = \
                        child.scrape(ports["http"])

            helpers = [threading.Thread(target=scrapes),
                       threading.Thread(target=pull_trace, args=(
                           ports["http"], t_trace, pulled)),
                       threading.Thread(target=ledger_in_trace, args=(
                           ports["http"], t_trace, pulled, untraced))]
            for h in helpers:
                h.start()
        records, gen = run_loadgen(binary, ports["grpc"], mix, seconds,
                                   traffic["stdin"])
        for h in helpers:
            h.join(TRACE_S + 200)
        dv_end, m_end = child.scrape(ports["http"])
    finally:
        hbm_peak = memory.stop()
        exit_code = child.stop(server)

    setup_s = gen["t_window_monotonic"] - t_start
    cmp = compare(records, traffic["expected"], seconds)
    why = gate(dv_end, m_end, warm_miss, expected_platform, int(cell["chips"]),
               child.native_source_digest(root), exit_code,
               child.scan_log(log_path))
    if gen["dead_conns"]:
        why.append(f"{gen['dead_conns']} connections died")
    if why:
        raise Refused("; ".join(why))

    proc = dv_end["process"]
    device = {"platform": proc["platform"], "kind": proc["device_kind"],
              "count": proc["device_count"],
              "memory_peak_bytes": device_memory_bytes(dv_end, hbm_peak)}
    result: Dict[str, Any] = {
        "correct": cmp["correct"], "attempted": cmp["attempted"],
        "failed": cmp["failed"], "metrics": {}, "device": device}
    # what the readers of metrics/*.json are handed
    ctx = {"cell": cell, "seconds": seconds, "setup_s": setup_s,
           "ready_s": ready_s, "records": cmp["records"],
           "right": cmp["right"], "in_window": cmp["in_window"],
           "traffic": traffic,
           "manifests": manifests, "device_kind": proc["device_kind"]}
    if trace:
        if pulled.get("status") != 200:
            raise Refused(f"/debug/profile answered {pulled.get('status')}: "
                          f"{str(pulled.get('body'))[:300]}")
        reduced = reduce_trace(json.loads(pulled["body"])["trace_dir"])
        if not reduced or not reduced.get("busy_s"):
            raise Refused("the trace shows no operation on the device")
        device["busy_s"], device["window_s"] = reduced["busy_s"], reduced["window_s"]
        ctx.update(
            vars0=untraced["vars0"], vars1=untraced["vars1"],
            metrics0=untraced["metrics0"], metrics1=untraced["metrics1"],
            untraced_s=untraced["t1"] - untraced["t0"], trace=reduced,
            trace_vars0=untraced.get("trace_vars0"),
            trace_vars1=untraced.get("trace_vars1"))
        result["metrics"] = read_metrics(cell["per_layer"], ctx)
        result["breakdown"] = reduced["breakdown"]
    else:
        result["metrics"] = read_metrics(cell["end_to_end"], ctx)
        silent = [m["name"] for m in cell["end_to_end"]
                  if m["name"] not in result["metrics"]]
        if silent:
            raise Refused(f"end-to-end metrics with nothing to read: {silent}")
    result["compared"] = {
        name: {"value": cmp["numbers"][name], "limit": limit}
        for name, limit in LIMITS.items()}
    result["compared"]["answers"] = {"value": cmp["compared"], "at_least": 1}
    result["evidence"] = {"cmp": cmp, "records": records, "traffic": traffic,
                          "generator": gen,
                          "ready_s": ready_s, "setup_s": setup_s,
                          "vars": dv_end, "out_dir": out_dir}
    shutil.rmtree(os.path.join(out_dir, "tmp"), ignore_errors=True)
    return result


def print_result(result: Dict[str, Any]) -> None:
    """The compared numbers beside their limits as the last lines of
    standard error, the contract's line as the last of standard output."""
    line = {k: v for k, v in result.items() if k != "evidence"}
    for name, entry in line["compared"].items():
        print(f"benchmark: compared {name} = {entry['value']} "
              + (f"(limit {entry['limit']})" if "limit" in entry
                 else f"(at least {entry['at_least']})"), file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line))
