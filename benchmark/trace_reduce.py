#!/usr/bin/env python3
"""From a profiler trace to numbers: device busy and idle time, the time of
each XLA module (a jitted entry, which is how a kernel is found until the
program names its scopes), the operations that took most time and the
longest idle gaps.

`reduce_planes` is the reduction itself, over plain data, and is what the
tests check against the recorded trace in tests/data.  `main` reads an
`.xplane.pb` with jax.profiler.ProfileData and is run as a helper process,
held to the CPU, after the server has exited: nothing but the server ever
opens the chip.

    python benchmark/trace_reduce.py <trace_dir> [<requested_seconds>]
"""

from __future__ import annotations

import glob
import json
import os
import sys
from typing import Any, Dict, List, Sequence, Tuple

Event = Tuple[str, float, float]  # name, start_ns, duration_ns

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10


def _union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            if hi > merged[-1][1]:
                merged[-1] = (merged[-1][0], hi)
        else:
            merged.append((lo, hi))
    return merged


def _short(name: str) -> str:
    """An XLA op's event carries its whole HLO line: keep the instruction's
    name, the part before " = "."""
    return name.split(" = ", 1)[0].lstrip("%")[:80]


def _by_name(events: Sequence[Event]) -> Dict[str, List[float]]:
    out: Dict[str, List[float]] = {}
    for name, _, dur in events:
        entry = out.setdefault(_short(name), [0, 0.0])
        entry[0] += 1
        entry[1] += dur * 1e-9
    return out


def reduce_planes(planes: Sequence[Dict[str, Any]],
                  requested_s: float = 0.0) -> Dict[str, Any]:
    """planes: [{"name", "lines": [{"name", "events": [(name, start_ns,
    dur_ns)]}]}], device planes only.  Busy time is the union of the
    intervals in which an operation ran, averaged over the device planes;
    the window is the span of all device events, or the length that was
    asked for where that is longer (a device idle at either edge)."""
    devices = [p for p in planes if p["name"].startswith("/device:")]
    busy: List[float] = []
    lo, hi = float("inf"), float("-inf")
    modules: Dict[str, List[float]] = {}
    ops: Dict[str, List[float]] = {}
    gaps: List[Tuple[float, float]] = []
    for plane in devices:
        lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
        op_events = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []
        if not op_events:
            continue
        merged = _union([(s, s + d) for _, s, d in op_events])
        busy.append(sum(b - a for a, b in merged) * 1e-9)
        lo, hi = min(lo, merged[0][0]), max(hi, merged[-1][1])
        gaps += [(b[0] - a[1], a[1]) for a, b in zip(merged, merged[1:])]
        for name, (n, s) in _by_name(op_events).items():
            entry = ops.setdefault(name, [0, 0.0])
            entry[0] += n
            entry[1] += s
        for name, (n, s) in _by_name(lines.get(MODULES_LINE) or []).items():
            entry = modules.setdefault(name, [0, 0.0])
            entry[0] += n
            entry[1] += s
    if not busy:
        return {"busy_s": 0.0, "window_s": requested_s, "devices": 0,
                "modules": {}, "breakdown": {"device_ops": [], "idle_gaps": []}}
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1][1])[:TOP]
    # no host span is on the profiler's clock yet: a gap is named by where
    # in the trace it lies, not by what the host was doing
    top_gaps = sorted(gaps, reverse=True)[:TOP]
    return {
        "busy_s": sum(busy) / len(busy),
        "window_s": max((hi - lo) * 1e-9, requested_s),
        "devices": len(busy),
        "modules": {k: {"count": int(v[0]), "seconds": v[1]}
                    for k, v in modules.items()},
        "breakdown": {
            "device_ops": [[name, v[1] / len(busy)] for name, v in top_ops],
            "idle_gaps": [[f"unattributed@{(at - lo) * 1e-6:.1f}ms", gap * 1e-9]
                          for gap, at in top_gaps],
        },
    }


def read_xplane(path: str) -> List[Dict[str, Any]]:
    """The device planes of an .xplane.pb as plain data."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:"):
            continue  # host threads: millions of events nothing here reads
        planes.append({"name": plane.name, "lines": [
            {"name": line.name,
             "events": [(e.name, float(e.start_ns), float(e.duration_ns))
                        for e in line.events]}
            for line in plane.lines if line.name in (OPS_LINE, MODULES_LINE)]})
    return planes


def main(argv: Sequence[str]) -> int:
    found = sorted(glob.glob(os.path.join(argv[1], "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        print(f"no .xplane.pb under {argv[1]}", file=sys.stderr)
        return 1
    requested = float(argv[2]) if len(argv) > 2 else 0.0
    print(json.dumps(reduce_planes(read_xplane(found[-1]), requested)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
