"""BENCHMARK.json and every file it names obey the rules the driver states:
one case for each file and rule (manifest_check.cases), and the rules
themselves refuse what PR 22 was refused for."""

import json
import os
import shutil

import pytest

import manifest_check
from conftest import ROOT

CASES = list(manifest_check.cases(ROOT))


@pytest.mark.parametrize("problem", [p for _, p in CASES],
                         ids=[case for case, _ in CASES])
def test_rule(problem):
    assert problem is None, problem


def _broken_copy(tmp_path, edit):
    """A copy of the manifest and the benchmark's data with one edit."""
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("_chip", "__pycache__", "tests"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    edit(manifest)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    return {case for case, problem in manifest_check.cases(str(tmp_path)) if problem}


def _set(path, value):
    def edit(manifest):
        node = manifest
        for key in path[:-1]:
            node = node[key]
        node[path[-1]] = value
    return edit


@pytest.mark.parametrize("edit, caught", [
    (_set(["configs", 0, "source"], "1k AuthConfigs × 10 rules"),
     "configs[tenants-1k].source"),                       # PR 22's refusal
    (_set(["configs", 0, "source"], "x" * 201), "configs[tenants-1k].source"),
    (_set(["workloads", 0, "why"], "two\nlines"), "workloads[tenants-1k.unique-sat].why"),
    (_set(["end_to_end", 0, "unit"], "checks per s"), "end_to_end[checks_per_s].unit"),
    (_set(["end_to_end", 0, "bound"], 0.3), "end_to_end[checks_per_s].bound"),
    (_set(["per_layer", 0, "moves"], "nothing"), "per_layer[ready_s].moves"),
    (_set(["workloads", 1, "traffic"], "absent"), "workloads[conditions-200.unique-sat].traffic"),
    (_set(["workloads", 0, "name"], "-bad name"), "workloads[-bad name].name"),
    (_set(["run_seconds"], 52), "run_seconds"),
    (_set(["command"], ["python3", "../bench.py"]), "command[../bench.py]"),
], ids=["non-ascii-source", "long-source", "two-line-why", "unit-with-space",
        "bound-over-quarter", "moves-nothing", "no-traffic-file", "bad-name",
        "run-seconds", "command-out-of-repo"])
def test_rules_refuse(tmp_path, edit, caught):
    assert caught in _broken_copy(tmp_path, edit)


def test_four_chip_share(tmp_path):
    def edit(manifest):
        for cell in manifest["workloads"][:3]:
            cell["chips"] = 4
    assert "workloads.four_chips" in _broken_copy(tmp_path, edit)
