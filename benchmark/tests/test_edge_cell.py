"""`edge-1k` through the harness on the CPU at a tiny size: a sound run is
correct with every config on the fast lane and the long values scanned at
their class's width, the control and the planted fault (every fifth config
has its referer, cookie or user-agent regex changed) read `wrong` > 0, the
generator's measured shares lie inside ISSUE 38's aims, and the per-layer
metrics this configuration brought are files the harness's own reader loop
reads."""

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
CELL = "edge-1k.unique-sat"
NEW_METRICS = ("fe_bytes_per_check", "fe_headers_per_check", "dfa_eff_bytes",
               "dfa_dev_bytes_pct", "long_value_roofline")


def tiny_cell(n_configs=20, rows=4096):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = harness.load_cell(json.load(f), ROOT, CELL)
    cell["config_file"]["params"]["n_configs"] = n_configs
    cell["mix"].update(distinct_rows=rows, warm_s=1.0)
    return cell


def tiny_run(tmp, **kw):
    return harness.run(tiny_cell(**kw), ROOT, 2**31 + 38, SECONDS, False, "cpu",
                       time.monotonic(), out_root=str(tmp))


@pytest.fixture(scope="module")
def sound(tmp_path_factory):
    return tiny_run(tmp_path_factory.mktemp("chip"), rows=40000)


def test_generator_rows_against_the_reference():
    cell = tiny_cell()
    config = cell["config_file"]
    generator = harness.load_module("corpora", config["generator"])
    params = dict(config["params"], **config["requests"])
    rows = generator.requests(params, 6000, random.Random(38), kinds=True)
    ref = Reference(generator.manifests(config["params"]))
    for row in rows:
        want = OK if row["broke"] is None else PERMISSION_DENIED
        assert ref.decide(row) == want, (row["kind"], row["broke"])
    broke = {(r["kind"], r["broke"]) for r in rows}
    assert broke == {(kind, b) for kind, bs in generator.BREAKS.items()
                     for b in bs + (None,)}


def test_measured_shares_lie_inside_the_aims():
    config = tiny_cell(n_configs=1000)["config_file"]
    generator = harness.load_module("corpora", config["generator"])
    got = generator.measure(dict(config["params"], **config["requests"]), 8192, 1)
    assert 1200 <= got["check_request_bytes"]["mean"] <= 2500
    assert 18 <= got["headers_per_request"]["mean"] <= 30
    past = got["rows_with_a_regex_read_value_past_pct"]
    assert past["64"] >= 65 and 8 <= past["256"] <= 15


def test_sound_run_is_correct_and_rode_the_fast_lane(sound):
    assert sound["correct"] and sound["failed"] == 0 < sound["attempted"]
    assert sound["compared"]["wrong"] == {"value": 0, "limit": 0}
    codes = set(sound["evidence"]["traffic"]["expected"].tolist())
    assert codes == {OK, PERMISSION_DENIED}
    fe = sound["evidence"]["vars"]["native_frontend"]
    kernel = fe["snapshot"]["kernel"]
    # twenty tenants have one- and two-digit ids: the widest DFA is the
    # UUID4 form's 38 states, a state axis of 40; five DFA rows and eleven
    # leaves a request (the two `any`s share their x-client-kind leaf)
    assert (kernel["dfa_rows_per_row"], kernel["dfa_states"],
            kernel["leaf_cols_per_row"]) == (5, 40, 11)
    (only,) = kernel["classes"]
    assert only["device_width"] == 256
    ledger = fe["kernel_cost"]["ledger"]
    native = ledger["native"]
    # every row rode the fast lane and the kernel; only the cookies past
    # 256 bytes were the host's, and the chip read most of the value bytes
    # (on this CPU backend the lane selector may answer a small cut on the
    # host twin, where a launch at eff 256 is slow; on the chip
    # `host_lane_rows_pct` reads 0: PERF.md section 5)
    assert (ledger.get("host") or {}).get("rows", 0) < 0.2 * native["rows"]
    assert 0.04 < native["dfa_ovf_rows"] / native["rows"] < 0.16
    dev, host = native["dfa_dev_bytes"], native["dfa_host_bytes"]
    assert dev / (dev + host) > 0.8
    assert 128 < native["eff_cols"] / native["launches"] <= 256
    assert fe["stats"]["slow"] == 0 and fe["stats"]["fast"] >= native["rows"]
    rows = fe["front"]["rows"]
    parsed = fe["front"]["phases"]["parse"]["count"]
    assert 1200 < rows["req_bytes"]["count"] / parsed < 2500
    assert 18 <= rows["req_headers"]["count"] / parsed <= 30


def test_control_is_not_correct(sound):
    program, ctl = control.readings(sound, SECONDS)
    assert program["correct"] and program["numbers"]["wrong"] == 0
    assert not ctl["correct"] and ctl["numbers"]["wrong"] > 10


def test_altered_long_value_regex_is_not_correct(tmp_path, monkeypatch):
    """The server is given a corpus in which the referer, the cookie or the
    user-agent regex of every fifth config differs from what the reference
    was given."""
    write = child.write_manifests

    def altered(corpus, directory):
        corpus = json.loads(json.dumps(corpus))
        swaps = (("app-t", "web-t"), ("tenant=t", "tenant=u"),
                 ("Mozilla/5", "Mozilla/4"))
        for k, manifest in enumerate(corpus[::5]):
            rules = manifest["spec"]["authorization"]["rules"]
            patterns = rules["patternMatching"]["patterns"]
            old, new = swaps[k % 3]
            leaf = {"app-t": patterns[4]["any"][0], "tenant=t": patterns[5]["any"][0],
                    "Mozilla/5": patterns[3]}[old]
            assert old in leaf["value"]
            leaf["value"] = leaf["value"].replace(old, new)
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
