#!/usr/bin/env python3
"""The device's idle time, put down to what the host was doing: each idle
interval of a capture is split by the batch whose launch ends it.  A tool, not
a metric's reader: the two planes of a capture do not share a clock as they
stand (PERF.md section 6, PR 25), so ISSUE 25's three `idle_*` metrics were
left out; what is printed here says beside every split how the clocks were
brought together and what was checked.

The program writes a host span `atpu/native/<stage>` with a `batch` argument
around each stage of a batch's life (authorino_tpu/runtime/batch_stages.py).
`pickup` is a mark at dispatch entry that carries `mono_ns`, the program's
monotonic clock at that instant, and `flush_mono_ns`, when the front end cut
the batch: their difference places the flush on the profiler's clock.  An
idle interval that ends where a batch's module starts is then three parts:

  no_cut   before that batch's flush: the front end had no cut to give;
  host     flush to the end of its `launch` span: the cut waited for a
           dispatcher thread, then plan, encode and the jitted call;
  runtime  after `launch` returned, until the module's first operation.

Which module is which batch's: the runtime numbers its programs (`run_id`
on a device module, and on the host's `DoEnqueueProgram` event that queued
it) in the order the device runs them, and a batch launches one program.
An enqueue that lies inside a `launch` span says how far the launches (in
the order they began) lie from the runs; dispatcher threads launch side by
side, so the most common distance is taken for all.  Nothing but the time
ties an enqueue to its span: the runtime's events of a dispatcher thread lie
on a line of their own, to which ProfileData gives no name or number, and a
few in a capture (6 of 467, 4 of 390) are made by a `pjrt-tpu-tasks` thread.
A module whose launch was made before the capture began has only the third
part.

The device plane read 0.3 to 1.8 ms early on a v5e, by one offset a capture.
Causality bounds the offset from both sides: no module starts before its
enqueue began, none ends after its `CompleteCallbacks` began.  The device
plane is moved to the middle of that interval (16 to 140 us wide in those
captures), and `clock` in the result says where it was.  The window and the
busy time are trace_reduce's and do not move, so the three parts sum to its
idle time.

Nothing is split that is not checked (`checks` in the result).  The matching,
on the host's clock alone: no enqueue lies before the launch it is put down
to began, and most lie inside it (of two distances only one can hold for
most).  The clock: the bounds of the offset do not cross, no module begins
before its launch span did (it may well begin before the span ends), no
`resolve` span begins before its module ended.  A capture that fails one of
these gives `checks` and `clock` and no parts.  On the four captures of
PR 25 every check holds at the distance the vote finds and one or more fails
at each of the four distances next to it.

`split_gaps` is the reduction over plain data and is what the tests check;
`main` reads an `.xplane.pb` in a process held to the CPU.

    JAX_PLATFORMS=cpu python benchmark/gap_split.py <trace_dir> [<requested_seconds>]
"""

from __future__ import annotations

import bisect
import collections
import glob
import json
import os
import sys
from typing import Any, Dict, List, Optional, Sequence

import trace_reduce

SPAN_PREFIX = "atpu/native/"
ENQUEUE, COMPLETE = "DoEnqueueProgram", "CompleteCallbacks"
PARTS = ("no_cut", "host", "runtime")
# counts in `checks` that one clock and one launch a module forbid
FORBIDDEN = ("clock_bounds_cross", "enqueue_before_launch",
             "launch_begins_late", "resolve_begins_early")


def batches_of(spans: Sequence[Dict[str, Any]]) -> List[Dict[str, float]]:
    """Host spans -> one record a batch that launched inside the capture,
    in the order their launches began: when it was cut (where its pickup
    mark is in the capture too), when its `launch` span began and ended,
    when its `resolve` span began."""
    by_batch: Dict[int, Dict[str, float]] = {}
    for s in spans:
        b = by_batch.setdefault(int(s["batch"]), {})
        if s["stage"] == "launch":
            b["launch_start"] = s["start_ns"]
            b["launch_end"] = s["start_ns"] + s["dur_ns"]
        elif s["stage"] == "resolve":
            b["resolve_start"] = s["start_ns"]
        elif s["stage"] == "pickup" and s.get("mono_ns"):
            b["flush"] = s["start_ns"] - (s["mono_ns"] - s["flush_mono_ns"])
    launched = [b for b in by_batch.values() if "launch_end" in b]
    return sorted(launched, key=lambda b: b["launch_start"])


def clock_offset(modules: Sequence[Dict[str, Any]], enqueues: Dict[int, float],
                 completions: Dict[int, float]) -> Optional[Dict[str, float]]:
    """How far the device plane has to move to obey causality: at least
    `low` (a module begins after its enqueue began), at most `high` (it
    ends before its completion callback began).  Where the two cross there
    is no one offset, and split_gaps splits nothing."""
    low = [enqueues[m["run_id"]] - m["start_ns"]
           for m in modules if m["run_id"] in enqueues]
    high = [completions[m["run_id"]] - (m["start_ns"] + m["dur_ns"])
            for m in modules if m["run_id"] in completions]
    if not low or not high:
        return None
    return {"low_ns": max(low), "high_ns": min(high),
            "shift_ns": (max(low) + min(high)) / 2}


def first_run(batches: Sequence[Dict[str, float]], runs: Sequence[int],
              enqueues: Dict[int, float]) -> Optional[int]:
    """The index, among the device's runs, of the first launch inside the
    capture (negative: its module is not in it)."""
    starts = [b["launch_start"] for b in batches]
    votes: collections.Counter = collections.Counter()
    for at, run in enumerate(runs):
        t = enqueues.get(run)
        if t is None:
            continue
        k = bisect.bisect_right(starts, t) - 1
        if k >= 0 and t <= batches[k]["launch_end"]:
            votes[at - k] += 1
    return votes.most_common(1)[0][0] if votes else None


def split_gaps(planes: Sequence[Dict[str, Any]], modules: Sequence[Dict[str, Any]],
               host: Dict[str, Any], requested_s: float = 0.0) -> Optional[Dict[str, Any]]:
    """planes: device planes as trace_reduce.read_xplane gives them;
    modules: [{"plane", "run_id", "start_ns", "dur_ns"}], the device's module
    events with the runtime's number; host: {"spans": [{"stage", "batch",
    "start_ns", "dur_ns"} and, on a pickup mark, "mono_ns" and
    "flush_mono_ns"], "enqueues": {run_id: start_ns}, "completions": {run_id:
    start_ns}}.  `checks` and `clock` always; seconds of each part, averaged
    over the device planes as trace_reduce averages busy time, only where
    every check holds.  None where there is nothing to check."""
    batches = batches_of(host["spans"])
    enqueues = {int(k): v for k, v in host["enqueues"].items()}
    completions = {int(k): v for k, v in host["completions"].items()}
    clock = clock_offset(modules, enqueues, completions)
    if not batches or clock is None:
        return None
    shift = clock["shift_ns"]
    lo, hi = float("inf"), float("-inf")
    per_plane = []
    for plane in planes:
        if not plane["name"].startswith("/device:"):
            continue
        lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
        ops = lines.get(trace_reduce.OPS_LINE) or lines.get(trace_reduce.MODULES_LINE)
        mine = sorted((m for m in modules if m["plane"] == plane["name"]),
                      key=lambda m: m["run_id"])
        first = first_run(batches, [m["run_id"] for m in mine], enqueues)
        if not ops or first is None:
            continue
        merged = [(a + shift, b + shift) for a, b in trace_reduce._union(
            [(s, s + d) for _, s, d in ops])]
        lo, hi = min(lo, merged[0][0]), max(hi, merged[-1][1])
        per_plane.append((merged, mine, first))
    if not per_plane:
        return None
    window_ns = max(hi - lo, requested_s * 1e9)
    parts = {p: 0.0 for p in PARTS}
    checks = {"launches": len(batches), "modules": 0, "before_capture": 0,
              "matched": 0, "after_capture": 0, "enqueue_in_launch": 0,
              "clock_bounds_cross": int(clock["low_ns"] > clock["high_ns"]),
              "enqueue_before_launch": 0, "launch_begins_late": 0,
              "resolve_begins_early": 0}
    for merged, mine, first in per_plane:
        starts = [m["start_ns"] + shift for m in mine]
        checks["modules"] += len(mine)
        for at, m in enumerate(mine):
            if at < first:
                checks["before_capture"] += 1
            elif at - first < len(batches):
                b = batches[at - first]
                checks["matched"] += 1
                # both on the host's clock: these judge the matching alone
                enqueued = enqueues.get(m["run_id"], float("inf"))
                checks["enqueue_before_launch"] += enqueued < b["launch_start"]
                checks["enqueue_in_launch"] += (
                    b["launch_start"] <= enqueued <= b["launch_end"])
                checks["launch_begins_late"] += b["launch_start"] > starts[at]
                checks["resolve_begins_early"] += (
                    b.get("resolve_start", float("inf")) < starts[at] + m["dur_ns"])
            else:
                checks["after_capture"] += 1
        # the window beyond the device's own events is idle time at an edge
        # that no event places: it is laid before the first operation
        edge = window_ns - (merged[-1][1] - merged[0][0])
        gaps = [(merged[0][0] - edge, merged[0][0])] if edge > 0 else []
        gaps += [(a[1], b[0]) for a, b in zip(merged, merged[1:])]
        for g0, g1 in gaps:
            # the module that begins where the gap ends (its own event
            # opens a little before its first operation)
            k = bisect.bisect_right(starts, g1) - 1 - first
            if not 0 <= k < len(batches):
                # launched before the capture began: only the wait is seen
                parts["runtime"] += g1 - g0
                continue
            b = batches[k]
            flush = min(b.get("flush", b["launch_start"]), b["launch_end"])
            no_cut = max(0.0, min(g1, flush) - g0)
            runtime = max(0.0, g1 - max(g0, b["launch_end"]))
            parts["no_cut"] += no_cut
            parts["runtime"] += runtime
            parts["host"] += (g1 - g0) - no_cut - runtime
    n = len(per_plane)
    out: Dict[str, Any] = {
        "window_s": window_ns * 1e-9, "devices": n, "checks": checks,
        "clock": {k[:-3] + "_ms": v * 1e-6 for k, v in clock.items()}}
    if (not any(checks[k] for k in FORBIDDEN)
            and 2 * checks["enqueue_in_launch"] > checks["matched"]):
        out.update({p + "_s": v * 1e-9 / n for p, v in parts.items()})
    return out


def read_capture(path: str):
    """(planes, modules, host) of an .xplane.pb, for split_gaps: the device
    planes as trace_reduce reads them, then the numbers and spans that it
    leaves out."""
    from jax.profiler import ProfileData

    modules: List[Dict[str, Any]] = []
    host: Dict[str, Any] = {"spans": [], "enqueues": {}, "completions": {}}
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name != trace_reduce.MODULES_LINE:
                continue
            for e in line.events:
                if device or e.name in (ENQUEUE, COMPLETE):
                    run = dict(e.stats).get("run_id")
                    if run is None:
                        continue
                    if device:
                        modules.append({"plane": plane.name, "run_id": int(run),
                                        "start_ns": float(e.start_ns),
                                        "dur_ns": float(e.duration_ns)})
                    else:
                        host["enqueues" if e.name == ENQUEUE else "completions"][
                            int(run)] = float(e.start_ns)
                elif e.name.startswith(SPAN_PREFIX):
                    stats = dict(e.stats)
                    if "batch" in stats:
                        host["spans"].append({
                            "stage": e.name[len(SPAN_PREFIX):],
                            "start_ns": float(e.start_ns),
                            "dur_ns": float(e.duration_ns),
                            **{k: int(stats[k]) for k in (
                                "batch", "mono_ns", "flush_mono_ns") if k in stats}})
    return trace_reduce.read_xplane(path), modules, host


def main(argv: Sequence[str]) -> int:
    found = sorted(glob.glob(os.path.join(argv[1], "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        print(f"no .xplane.pb under {argv[1]}", file=sys.stderr)
        return 1
    requested = float(argv[2]) if len(argv) > 2 else 0.0
    print(json.dumps(split_gaps(*read_capture(found[-1]), requested)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
