"""`mixed-tenants-1k` through the harness on the CPU at a tiny size: the
generator is deterministic in its seed and holds its shares over the cell's
131,072 distinct rows; a sound run is correct, serves two size classes and
counts what its launches scanned; the control and the planted fault (one
route's regex of one large tenant, one rule of every fifth small tenant) read
`wrong` > 0; and the per-layer metrics this configuration brought are files
the harness's own reader loop reads."""

import json
import os
import random
import time

import pytest

import child
import control
import harness
from conftest import BENCH, ROOT
from reference import OK, PERMISSION_DENIED

SECONDS = 2.0
CELL = "mixed-tenants-1k.unique-sat"
NEW_METRICS = ("launches_per_cut", "dfa_slot_fill_pct", "own_class_roofline")


def tiny_cell(n_configs=10, n_large=2, services=2, rows=4096):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = harness.load_cell(json.load(f), ROOT, CELL)
    cell["config_file"]["params"].update(
        n_configs=n_configs, n_large=n_large, services=services)
    cell["mix"].update(distinct_rows=rows, warm_s=1.0)
    return cell


def tiny_run(tmp, **kw):
    return harness.run(tiny_cell(**kw), ROOT, 2**31 + 34, SECONDS, False, "cpu",
                       time.monotonic(), out_root=str(tmp))


def test_generator_is_deterministic_and_holds_its_shares():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = harness.load_cell(json.load(f), ROOT, CELL)
    config, n = cell["config_file"], int(cell["mix"]["distinct_rows"])
    assert n == 131072
    generator = harness.load_module("corpora", config["generator"])
    params = dict(config["params"], **config["requests"])
    rows = generator.requests(params, n, random.Random(2**31 + 34), kinds=True)
    plain = [{k: r[k] for k in ("host", "method", "path", "headers")} for r in rows]
    assert generator.requests(params, n, random.Random(2**31 + 34)) == plain
    assert generator.requests(params, 64, random.Random(7)) != plain[:64]
    assert len({(r["host"], r["headers"]["x-request-id"], r["path"])
                for r in rows}) == n
    large = [r for r in rows if r["kind"] != "small"]
    small = [r for r in rows if r["kind"] == "small"]

    def share(part, whole):
        return len(part) / len(whole)

    # within a point of what the configuration states
    assert abs(share(large, rows) - 0.4) < 0.01
    assert abs(share([r for r in large if r["kind"] != "routed"], large) - 0.10) < 0.01
    assert abs(share([r for r in large if len(r["path"]) > 64], large) - 0.15) < 0.01
    assert all(len(r["path"]) <= 96 for r in large)
    assert all(len(r["path"]) <= 64 for r in small)
    for part in (large, small):
        assert abs(share([r for r in part if r["broke"]], part) - 0.5) < 0.01
    # uniform over the 8 large tenants, their 8 services and the 16 route
    # kinds; the small rows over the 1,000 small tenants
    hosts = {}
    for r in large:
        hosts[r["host"]] = hosts.get(r["host"], 0) + 1
    assert len(hosts) == 8 and max(hosts.values()) < 1.1 * min(hosts.values())
    assert len({r["route"] for r in large}) == 8 * 16
    assert len({r["host"] for r in small}) == 1000


@pytest.fixture(scope="module")
def sound(tmp_path_factory):
    return tiny_run(tmp_path_factory.mktemp("chip"), rows=70000)


def test_sound_run_is_correct_and_serves_two_classes(sound):
    assert sound["correct"] and sound["failed"] == 0 < sound["attempted"]
    assert sound["compared"]["wrong"] == {"value": 0, "limit": 0}
    codes = set(sound["evidence"]["traffic"]["expected"].tolist())
    assert codes == {OK, PERMISSION_DENIED}
    fe = sound["evidence"]["vars"]["native_frontend"]
    kernel = fe["snapshot"]["kernel"]
    small, large = kernel["classes"]
    keys = ("configs", "leaf_cols_per_row", "dfa_rows_per_row", "dfa_states",
            "cpu_cols", "evaluators")
    assert [small[k] for k in keys] == [10, 10, 2, 16, 2, 2]
    # 2 x 16 route regexes and the catch-all's two, 33 evaluators in 64 columns
    assert [large[k] for k in keys] == [2, 57, 34, 72, 34, 64]
    # the scalars read the largest class
    assert (kernel["dfa_rows_per_row"], kernel["dfa_states"],
            kernel["leaf_cols_per_row"]) == (34, 72, 57)
    assert kernel["operand_bytes"] > small["operand_bytes"] + large["operand_bytes"]
    ledger = fe["kernel_cost"]["ledger"]
    native = ledger["native"]
    # every row rode the fast lane and the kernel, a launch a class present
    assert (ledger.get("host") or {}).get("rows", 0) == 0
    assert native["batches"] < native["launches"] <= 2 * native["batches"]
    assert native["h2d_transfers"] == native["launches"]
    # what the launched rows' own configs have against what was scanned: 40 %
    # of the rows at 34 DFA rows, the rest at 2, pads and all
    assert 0.5 < native["own_dfa_rows"] / native["own_dfa_slots"] <= 1.0
    assert 12.0 < native["own_dfa_rows"] / native["device_rows"] < 17.0
    assert 0.03 < native["dfa_ovf_rows"] / native["rows"] < 0.10
    assert fe["stats"]["slow"] == 0 and fe["stats"]["fast"] >= native["rows"]


def test_control_is_not_correct(sound):
    program, ctl = control.readings(sound, SECONDS)
    assert program["correct"] and program["numbers"]["wrong"] == 0
    assert not ctl["correct"] and ctl["numbers"]["wrong"] > 10


@pytest.mark.parametrize("which", ["large", "small"])
def test_altered_corpus_is_not_correct(tmp_path, monkeypatch, which):
    """The server is given a corpus in which one route's regex of one large
    tenant, or one rule of every fifth small tenant, differs from what the
    reference was given."""
    write = child.write_manifests

    def altered(corpus, directory):
        corpus = json.loads(json.dumps(corpus))
        if which == "large":
            when = corpus[-1]["spec"]["authorization"]["s1-route-01"]["when"]
            when[0]["value"] = when[0]["value"].replace("users/", "members/")
        else:
            for manifest in corpus[:-2:5]:
                rules = manifest["spec"]["authorization"]["rules"]
                rules["patternMatching"]["patterns"][5]["operator"] = "neq"
        write(corpus, directory)

    monkeypatch.setattr(child, "write_manifests", altered)
    result = tiny_run(tmp_path, rows=8192)
    assert result["correct"] is False
    assert result["compared"]["wrong"]["value"] > 0 and result["failed"] > 0


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_metric_file_names_a_reader_the_harness_loads(name):
    cell = tiny_cell()
    assert name in {m["name"] for m in cell["per_layer"]}
    spec = harness._load_json(os.path.join(BENCH, "metrics", name + ".json"))
    assert callable(harness.load_module("readers", spec["reader"]).read)
