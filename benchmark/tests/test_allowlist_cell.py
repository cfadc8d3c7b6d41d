"""`api-allowlist-1k` through the harness on the CPU at a tiny size: the
plain reference decides every generated row as the rule the row breaks says
(Python's re.search standing in for RE2 on these regexes), a sound run is
correct with every config on the fast lane and its path DFAs in u16 tables,
the control and a planted fault (every fifth tenant's allowlist loses a
collection) read `wrong` > 0, and the per-layer metrics this configuration
brought are files the harness's own reader loop reads."""

import json
import os
import random
import time

import pytest

import child
import control
import harness
from conftest import BENCH, ROOT
from reference import OK, PERMISSION_DENIED, Reference

SECONDS = 2.0
CELL = "api-allowlist-1k.unique-sat"
NEW_METRICS = ("slow_configs", "dfa_cpu_leaves", "wide_dfa_roofline")


def tiny_cell(n_configs=12, rows=4096):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = harness.load_cell(json.load(f), ROOT, CELL)
    cell["config_file"]["params"]["n_configs"] = n_configs
    cell["mix"].update(distinct_rows=rows, warm_s=1.0)
    return cell


def tiny_run(tmp, **kw):
    return harness.run(tiny_cell(**kw), ROOT, 2**31 + 41, SECONDS, False, "cpu",
                       time.monotonic(), out_root=str(tmp))


@pytest.fixture(scope="module")
def sound(tmp_path_factory):
    return tiny_run(tmp_path_factory.mktemp("chip"), rows=20000)


def test_generator_rows_against_the_reference():
    config = tiny_cell(n_configs=1000)["config_file"]
    generator = harness.load_module("corpora", config["generator"])
    params = dict(config["params"], **config["requests"])
    rows = generator.requests(params, 20000, random.Random(41), kinds=True)
    ref = Reference(generator.manifests(config["params"]))
    for row in rows:
        want = OK if row["broke"] is None else PERMISSION_DENIED
        assert ref.decide(row) == want, (row["broke"], row["path"])
        assert "\n" not in row["path"] and len(row["path"].encode()) <= 64
    assert set(generator.PATH_BREAKS) <= {r["broke"] for r in rows}


def test_sound_run_is_correct_and_rode_the_fast_lane(sound):
    assert sound["correct"] and sound["failed"] == 0 < sound["attempted"]
    assert sound["compared"]["wrong"] == {"value": 0, "limit": 0}
    fe = sound["evidence"]["vars"]["native_frontend"]
    snap = fe["snapshot"]
    assert snap["slow_configs"] == 0
    kernel = snap["kernel"]
    assert kernel["dfa_cpu_leaves"] == 0 and kernel["dfa_states"] > 256
    assert kernel["dfa_rows_per_row"] == 2
    assert [c["state_bytes"] for c in kernel["classes"]] == [2]
    native = fe["kernel_cost"]["ledger"]["native"]
    assert native["dfa_ovf_rows"] == 0
    assert fe["stats"]["slow"] == 0 and fe["stats"]["fast"] >= native["rows"]


def test_control_is_not_correct(sound):
    program, ctl = control.readings(sound, SECONDS)
    assert program["correct"] and program["numbers"]["wrong"] == 0
    assert not ctl["correct"] and ctl["numbers"]["wrong"] > 10


def test_altered_allowlist_is_not_correct(tmp_path, monkeypatch):
    """The server is given a corpus in which every fifth tenant's allowlist
    lacks its first collection, which the reference still allows."""
    write = child.write_manifests

    def altered(corpus, directory):
        corpus = json.loads(json.dumps(corpus))
        for manifest in corpus[::5]:
            rules = manifest["spec"]["authorization"]["rules"]
            (leaf,) = [p for p in rules["patternMatching"]["patterns"]
                       if p["selector"] == "request.url_path"]
            head, rest = leaf["value"].split("/(", 1)
            first, others = rest.split("|", 1)
            leaf["value"] = f"{head}/({others}"
        write(corpus, directory)

    monkeypatch.setattr(child, "write_manifests", altered)
    result = tiny_run(tmp_path)
    assert result["correct"] is False
    assert result["compared"]["wrong"]["value"] > 0 and result["failed"] > 0


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_metric_file_names_a_reader_the_harness_loads(name):
    cell = tiny_cell()
    assert name in {m["name"] for m in cell["per_layer"]}
    spec = harness._load_json(os.path.join(BENCH, "metrics", name + ".json"))
    assert callable(harness.load_module("readers", spec["reader"]).read)
