"""The decision kernel in the device trace.  The program names no scope
yet, so the kernel is the XLA module of the jitted entry the server says it
serves with (/debug/vars native_frontend.snapshot.kernel.entry); its time is
the sum of that module's events, and its launches their number.

what="ms_per_launch": kernel milliseconds a launch, from the trace alone.

The other two set rows against that time.  The profiler slows the host, so
the launches it records are not cut as the served ones are: their rows are
counted by the ledger between two readings taken inside the traced seconds
(harness.ledger_in_trace), rows a launch there times the trace's launches.
Without those readings there is nothing to read.
what="ms_per_krow": kernel milliseconds per 1,000 real rows launched.
what="roofline_pct": the least time the chip needs for the work those rows
require (work.py: from the corpus and the rows alone) over the kernel time.
"""

import numpy as np

import work
from readers_common import ledger_delta

MIN_LAUNCHES = 4  # fewer between the two readings say nothing of the rest


def read(ctx, what):
    snap = ((ctx["vars1"].get("native_frontend") or {}).get("snapshot") or {})
    entry = (snap.get("kernel") or {}).get("entry")
    if not entry:
        return None
    hits = [m for name, m in ctx["trace"]["modules"].items() if entry in name]
    launches = sum(m["count"] for m in hits)
    seconds = sum(m["seconds"] for m in hits)
    if not launches or seconds <= 0:
        return None
    if what == "ms_per_launch":
        return seconds * 1e3 / launches
    if not ctx.get("trace_vars0") or not ctx.get("trace_vars1"):
        return None
    inside = [ledger_delta(ctx, "native", field, "trace_vars0", "trace_vars1")
              for field in ("device_rows", "launches")]
    if inside[1] < MIN_LAUNCHES or inside[0] <= 0:
        return None
    rows = launches * inside[0] / inside[1]
    if what == "ms_per_krow":
        return seconds * 1e3 / (rows / 1e3)
    if what != "roofline_pct":
        raise ValueError(what)
    leaves = {h: work.config_leaves(m) for m in ctx["manifests"]
              for h in m["spec"]["hosts"]}
    table = ctx["traffic"]["rows"]
    per_row = np.array([work.required(leaves[r["host"]], r) for r in table],
                       dtype=np.float64)
    ops, nbytes = per_row[ctx["traffic"]["order"]].mean(axis=0) * rows
    least, _ = work.least_seconds(ops, nbytes, ctx["device_kind"])
    return 100.0 * least / seconds
