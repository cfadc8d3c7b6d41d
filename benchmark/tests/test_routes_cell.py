"""`routes-1k` through the harness on the CPU at a tiny size: a sound run is
correct, the control and the planted fault (every fifth config has one
route's regex changed) read `wrong` > 0, and the per-layer metrics this
configuration brought are files the harness's own reader loop reads."""

import json
import os
import time

import pytest

import child
import control
import harness
from conftest import BENCH, ROOT
from reference import OK, PERMISSION_DENIED

SECONDS = 2.0
CELL = "routes-1k.unique-sat"
NEW_METRICS = ("dfa_states", "launch_temp_bytes", "dfa_ovf_rows_pct",
               "dfa_scan_roofline")


def tiny_cell(n_configs=10, rows=4096):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = harness.load_cell(json.load(f), ROOT, CELL)
    cell["config_file"]["params"]["n_configs"] = n_configs
    cell["mix"].update(distinct_rows=rows, warm_s=1.0)
    return cell


def tiny_run(tmp, **kw):
    return harness.run(tiny_cell(**kw), ROOT, 2**31 + 32, SECONDS, False, "cpu",
                       time.monotonic(), out_root=str(tmp))


@pytest.fixture(scope="module")
def sound(tmp_path_factory):
    return tiny_run(tmp_path_factory.mktemp("chip"), rows=70000)


def test_sound_run_is_correct_and_rode_the_fast_lane(sound):
    assert sound["correct"] and sound["failed"] == 0 < sound["attempted"]
    assert sound["compared"]["wrong"] == {"value": 0, "limit": 0}
    codes = set(sound["evidence"]["traffic"]["expected"].tolist())
    assert codes == {OK, PERMISSION_DENIED}
    fe = sound["evidence"]["vars"]["native_frontend"]
    kernel = fe["snapshot"]["kernel"]
    # ten tenants have one-digit ids: the UUID route is 64 states, not 66
    assert (kernel["dfa_rows_per_row"], kernel["dfa_states"],
            kernel["leaf_cols_per_row"]) == (18, 64, 40)
    assert kernel["launch_temp_bytes"] > 0
    ledger = fe["kernel_cost"]["ledger"]
    native = ledger["native"]
    # every row rode the fast lane and the kernel; the long paths were
    # scanned by the encoder and counted a row each
    assert (ledger.get("host") or {}).get("rows", 0) == 0
    assert 0.08 < native["dfa_ovf_rows"] / native["rows"] < 0.22
    assert fe["stats"]["slow"] == 0 and fe["stats"]["fast"] >= native["rows"]


def test_control_is_not_correct(sound):
    program, ctl = control.readings(sound, SECONDS)
    assert program["correct"] and program["numbers"]["wrong"] == 0
    assert not ctl["correct"] and ctl["numbers"]["wrong"] > 10


def test_altered_route_regex_is_not_correct(tmp_path, monkeypatch):
    """The server is given a corpus in which one route's regex of every
    fifth config differs from what the reference was given."""
    write = child.write_manifests

    def altered(corpus, directory):
        corpus = json.loads(json.dumps(corpus))
        for manifest in corpus[::5]:
            when = manifest["spec"]["authorization"]["route-01"]["when"]
            when[0]["value"] = when[0]["value"].replace("users/", "members/")
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
