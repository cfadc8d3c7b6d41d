#!/usr/bin/env python3
"""The driver's stated rules for BENCHMARK.json, applied here before a PR is
sent: a manifest the driver would refuse before any run fails a test, not a
PR.  `cases(root)` yields (case id, what is wrong or None), one case for
each file and rule; tests/test_manifest.py runs each as a test, and

    python benchmark/manifest_check.py

prints the failures and exits non-zero where there are any.
"""

from __future__ import annotations

import json
import os
import re
import sys
from typing import Any, Dict, Iterator, List, Optional, Tuple

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
FILE_NAME = re.compile(r"^[A-Za-z0-9_.\-/]+$")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

Case = Tuple[str, Optional[str]]


def _line(s: Any) -> Optional[str]:
    """1 to 200 printable ASCII characters on one line."""
    if not isinstance(s, str) or not 1 <= len(s) <= 200:
        return f"must be 1 to 200 characters, has {len(s) if isinstance(s, str) else s!r}"
    bad = [c for c in s if not 32 <= ord(c) < 127]
    return f"non-printable or non-ASCII characters {bad[:5]!r}" if bad else None


def _inside(path: str, paths: List[str]) -> bool:
    return any(path == p or path.startswith(p.rstrip("/") + "/") for p in paths)


def _keys(entry: Dict[str, Any], must: set, may: set = frozenset()) -> Optional[str]:
    keys = set(entry)
    if keys - must - may or must - keys:
        return f"keys {sorted(keys)} are not {sorted(must)} (+ {sorted(may)})"
    return None


def cases(root: str) -> Iterator[Case]:
    path = os.path.join(root, "BENCHMARK.json")
    yield "file.size", (None if os.path.getsize(path) <= 64 * 1024
                        else "BENCHMARK.json is over 64 KiB")
    with open(path) as f:
        b = json.load(f)
    yield "top.keys", (None if set(b) == TOP_KEYS
                       else f"top-level keys are {sorted(b)}")
    paths, command = b["paths"], b["command"]
    yield "paths.count", None if 1 <= len(paths) <= 16 else "1 to 16 paths"
    for p in paths:
        ok = (PATH.match(p) and not p.startswith("/") and ".." not in p.split("/")
              and os.path.isdir(os.path.join(root, p)))
        yield f"paths[{p}]", None if ok else "not a relative directory of the repo"
    yield "command.list", (None if isinstance(command, list) and 1 <= len(command) <= 32
                           else "a list of 1 to 32 strings")
    for word in command:
        problem = _line(word)
        if not problem and (word.startswith("/") or ".." in word.split("/")):
            problem = "an absolute path or one that leads out of the repo"
        if not problem and os.path.exists(os.path.join(root, word)) \
                and not _inside(word, paths):
            problem = "names a file of the repo outside `paths`"
        yield f"command[{word}]", problem
    rs = b["run_seconds"]
    yield "run_seconds", (None if isinstance(rs, int) and 1 <= rs <= 51
                          else f"{rs!r} is not a whole number from 1 to 51")

    configs, cells = b["configs"], b["workloads"]
    e2e, layer = b["end_to_end"], b["per_layer"]
    yield "configs.count", None if 1 <= len(configs) <= 24 else "1 to 24 configs"
    yield "workloads.count", None if 1 <= len(cells) <= 24 else "1 to 24 cells"
    yield "end_to_end.count", None if 1 <= len(e2e) <= 16 else "1 to 16 metrics"
    yield "per_layer.count", None if 1 <= len(layer) <= 128 else "1 to 128 metrics"
    for kind, entries in (("configs", configs), ("workloads", cells),
                          ("metrics", e2e + layer)):
        names = [x["name"] for x in entries]
        dup = sorted({n for n in names if names.count(n) > 1})
        yield f"{kind}.unique", f"duplicate names {dup}" if dup else None
        for n in names:
            yield f"{kind}[{n}].name", None if NAME.match(n) else "not a name"

    used = {c["config"] for c in cells}
    files = [c["file"] for c in configs]
    for c in configs:
        at = f"configs[{c['name']}]"
        yield f"{at}.keys", _keys(c, {"name", "source", "file", "reduced", "why"})
        yield f"{at}.source", _line(c["source"])
        yield f"{at}.why", _line(c["why"])
        yield f"{at}.used", None if c["name"] in used else "used by no cell"
        ok = (_inside(c["file"], paths) and files.count(c["file"]) == 1
              and os.path.isfile(os.path.join(root, c["file"])))
        yield f"{at}.file", None if ok else "not a file of its own under `paths`"
        bad = [k for k in c["reduced"] if not NAME.match(k)]
        yield f"{at}.reduced", (f"{bad} may not be reduced" if bad or len(c["reduced"]) > 16
                                else None)
        if ok:
            yield from _config_file(root, c)

    config_names = {c["name"] for c in configs}
    pairs = [(c["config"], c["traffic"]) for c in cells]
    for c in cells:
        at = f"workloads[{c['name']}]"
        yield f"{at}.keys", _keys(c, {"name", "config", "traffic", "chips", "why"})
        yield f"{at}.config", None if c["config"] in config_names else "no such config"
        yield f"{at}.traffic", (
            None if NAME.match(c["traffic"]) and os.path.isfile(os.path.join(
                root, paths[0], "traffic", c["traffic"] + ".json"))
            else "no traffic file of that name")
        yield f"{at}.chips", None if c["chips"] in (1, 4) else "chips is 1 or 4"
        yield f"{at}.why", _line(c["why"])
        yield f"{at}.pair", (None if pairs.count((c["config"], c["traffic"])) == 1
                             else "the pair of config and traffic appears twice")
    four = sum(1 for c in cells if c["chips"] == 4)
    yield "workloads.four_chips", (None if four <= max(1, len(cells) // 2)
                                   else f"{four} of {len(cells)} cells ask for 4 chips")

    cell_names = [c["name"] for c in cells]
    e2e_names = {m["name"] for m in e2e}
    yield "end_to_end.setup_s", None if "setup_s" in e2e_names else "no setup_s"

    def cells_of(m: Dict[str, Any]) -> List[str]:
        return m.get("workloads", cell_names)

    for m in e2e:
        at = f"end_to_end[{m['name']}]"
        yield f"{at}.keys", _keys(m, {"name", "unit", "better", "bound", "source"},
                                  {"workloads"})
        yield f"{at}.unit", None if UNIT.match(m["unit"]) else "not a unit"
        yield f"{at}.better", None if m["better"] in ("lower", "higher") else "lower or higher"
        yield f"{at}.source", (None if m["source"] in ("host_clock", "device_trace")
                               else "host_clock or device_trace")
        yield f"{at}.bound", (None if 0.01 <= m["bound"] <= 0.25
                              else f"bound {m['bound']} is outside 0.01 to 0.25")
        unknown = sorted(set(cells_of(m)) - set(cell_names))
        yield f"{at}.workloads", f"unknown cells {unknown}" if unknown else None
        yield from _metric_file(root, paths[0], m, at, ("unit", "better", "source"))
    reports = {n: {m["name"] for m in e2e if n in cells_of(m)} for n in cell_names}
    for m in layer:
        at = f"per_layer[{m['name']}]"
        yield f"{at}.keys", _keys(m, {"name", "unit", "better", "source", "layer", "moves"},
                                  {"workloads"})
        yield f"{at}.unit", None if UNIT.match(m["unit"]) else "not a unit"
        yield f"{at}.better", None if m["better"] in ("lower", "higher") else "lower or higher"
        yield f"{at}.source", None if m["source"] in SOURCES else f"not one of {sorted(SOURCES)}"
        yield f"{at}.layer", _line(m["layer"])
        yield f"{at}.moves", None if m["moves"] in e2e_names else "moves no end-to-end metric"
        mine = m.get("workloads") or [n for n in cell_names if m["moves"] in reports[n]]
        stray = [n for n in mine if n not in reports or m["moves"] not in reports[n]]
        yield f"{at}.workloads", (f"cells {stray} do not report {m['moves']}"
                                  if stray or not mine else None)
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            yield f"{at}.share", None if m["unit"] == "%" else "a share has the unit %"
        yield from _metric_file(root, paths[0], m, at, (
            "layer", "unit", "better", "source", "moves", "workloads"))
    for n in cell_names:
        has_layer = any(n in (m.get("workloads")
                              or [c for c in cell_names if m["moves"] in reports[c]])
                        for m in layer)
        ok = "setup_s" in reports[n] and len(reports[n]) >= 2 and has_layer
        yield f"workloads[{n}].reports", (
            None if ok else "needs setup_s, another end-to-end metric and a per-layer metric")

    for p in paths:
        for base, dirs, names in os.walk(os.path.join(root, p)):
            dirs[:] = [d for d in dirs if d not in ("_chip", "__pycache__", ".pytest_cache")]
            for n in names:
                rel = os.path.relpath(os.path.join(base, n), root)
                yield f"files[{rel}]", (None if FILE_NAME.match(rel)
                                        else "a file name of other characters")


def _config_file(root: str, c: Dict[str, Any]) -> Iterator[Case]:
    at = f"configs[{c['name']}].file"
    with open(os.path.join(root, c["file"])) as f:
        body = json.load(f)
    yield f"{at}.source", (None if body.get("source") == c["source"]
                           else "the file's source differs from BENCHMARK.json's")
    yield f"{at}.source_line", _line(body.get("source"))
    yield f"{at}.reduced", (None if body.get("reduced") == c["reduced"]
                            else "the file's reduced differs from BENCHMARK.json's")
    missing = [k for k in ("generator", "params", "requests", "assumed", "guarantees")
               if k not in body]
    yield f"{at}.keys", f"lacks {missing}" if missing else None
    module = os.path.join(os.path.dirname(os.path.dirname(
        os.path.join(root, c["file"]))), "corpora", str(body.get("generator")) + ".py")
    yield f"{at}.generator", None if os.path.isfile(module) else f"no generator {module}"


def _metric_file(root: str, bench_dir: str, m: Dict[str, Any], at: str,
                 same: Tuple[str, ...]) -> Iterator[Case]:
    """Every metric has a file of its own that names its reader and agrees
    with the entry in BENCHMARK.json on the keys in `same`."""
    at += ".file"
    path = os.path.join(root, bench_dir, "metrics", m["name"] + ".json")
    if not os.path.isfile(path):
        yield at, f"no {path}"
        return
    with open(path) as f:
        body = json.load(f)
    differ = [k for k in same if body.get(k) != m.get(k)]
    yield at, f"{differ} differ from BENCHMARK.json" if differ else None
    reader = os.path.join(root, bench_dir, "readers", str(body.get("reader")) + ".py")
    yield f"{at}.reader", None if os.path.isfile(reader) else f"no reader {reader}"


def main() -> int:
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    bad = [(case, problem) for case, problem in cases(root) if problem]
    for case, problem in bad:
        print(f"{case}: {problem}")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
