"""BENCHMARK.json and the files it names, held in tier-1 (benchmark/tests is
outside it): the manifest's own checker finds no problem on the tree, the
generators, the reference and the program's host expression oracle agree on
seeded samples of their rows, and one cell, cut to a tiny size, serves end
to end on the CPU through the harness the benchmark runs."""

import os
import random
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness  # noqa: E402
import manifest_check  # noqa: E402
from reference import OK, PERMISSION_DENIED, Reference  # noqa: E402


def _manifest():
    return harness._load_json(os.path.join(ROOT, "BENCHMARK.json"))


def _oracle_verdicts(corpus, table):
    """Every request decided by the host expression oracle, from the
    manifests as written (not from the generator's intent): the rules of
    the request's AuthConfig AND-ed over the authorization JSON the server
    would build for it."""
    from authorino_tpu.authjson import (
        CheckRequestModel,
        HttpRequestAttributes,
        build_authorization_json,
    )
    from authorino_tpu.expressions import All, Operator, Pattern

    rules = []
    for manifest in corpus:
        pats = manifest["spec"]["authorization"]["rules"][
            "patternMatching"]["patterns"]
        rules.append(All(*[
            Pattern(p["selector"], Operator.from_string(p["operator"]),
                    p["value"]) for p in pats]))
    out = []
    for req in table:
        headers = dict(req["headers"], host=req["host"])
        doc = build_authorization_json(
            CheckRequestModel(http=HttpRequestAttributes(
                method=req["method"], path=req["path"], host=req["host"],
                headers=headers)),
            {"identity": {"anonymous": True}})
        out.append(bool(rules[req["config"]].matches(doc)))
    return out


def test_manifest_check_finds_no_problem_on_the_tree():
    problems = [c for c in manifest_check.cases(ROOT) if c[1] is not None]
    assert problems == []
    assert sum(1 for _ in manifest_check.cases(ROOT)) > 50


@pytest.mark.parametrize("workload", [
    w["name"] for w in _manifest()["workloads"]])
def test_every_cell_loads_with_its_files(workload):
    manifest = _manifest()
    cell = harness.load_cell(manifest, ROOT, workload)
    entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    assert cell["config_file"]["source"] == entry["source"]
    assert cell["config_file"]["reduced"] == entry["reduced"]
    reported = {m["name"] for m in cell["end_to_end"]}
    assert "setup_s" in reported and len(reported) >= 2
    assert cell["per_layer"] and all(
        m["moves"] in reported for m in cell["per_layer"])


def test_tenants_10k_rows_agree_with_reference_and_host_oracle():
    cell = harness.load_cell(_manifest(), ROOT, "tenants-10k.unique-sat")
    config = cell["config_file"]
    assert config["params"] == {"n_configs": 10000} and config["reduced"] == []
    assert (cell["traffic"], cell["chips"]) == ("unique-sat", 1)
    assert config["generator"] == "tenant_rules_guarded"
    generator = harness.load_module("corpora", config["generator"])
    manifests = generator.manifests(config["params"])
    assert len(manifests) == 10000
    assert len({h for m in manifests for h in m["spec"]["hosts"]}) == 10000
    rows = generator.requests(dict(config["params"], **config["requests"]),
                              512, random.Random(2147493647))
    by_host = {m["spec"]["hosts"][0]: i for i, m in enumerate(manifests)}
    reference = Reference(manifests)
    answers = [reference.decide(r) for r in rows]
    oracle = _oracle_verdicts(
        manifests, [dict(r, config=by_host[r["host"]]) for r in rows])
    assert answers == [OK if allow else PERMISSION_DENIED for allow in oracle]
    # half of the rows break one rule; hosts spread over the whole corpus
    assert 0.4 < answers.count(PERMISSION_DENIED) / len(rows) < 0.6
    assert len({r["host"] for r in rows}) > 450


def _tenant_rules():
    return harness.load_module("corpora", "tenant_rules")


def _with_config(rows):
    """The generator's rows with the index of the config their host names."""
    return [dict(r, config=int(r["host"].split(".")[0][4:])) for r in rows]


def test_tenant_rules_rows_are_deterministic_under_seed():
    gen = _tenant_rules()
    manifests = gen.manifests({"n_configs": 8})
    assert manifests == gen.manifests({"n_configs": 8})
    params = {"n_configs": 8, "deny_share": 0.5}
    rows = gen.requests(params, 64, random.Random(7))
    assert rows == gen.requests(params, 64, random.Random(7))
    assert rows != gen.requests(params, 64, random.Random(8))
    want = _oracle_verdicts(manifests, _with_config(rows))
    # the table holds both verdicts, and every config per-config regexes
    # and a membership leaf
    assert 0 < sum(want) < len(want)
    for manifest in manifests:
        ops = [p["operator"] for p in manifest["spec"]["authorization"][
            "rules"]["patternMatching"]["patterns"]]
        assert len(ops) == 10
        assert ops.count("matches") == 2 and "incl" in ops
    regexes = {p["value"] for i in range(8) for p in gen._patterns(i)
               if p["operator"] == "matches"}
    assert len(regexes) == 16  # two per config, all distinct


@pytest.mark.parametrize("key", [k for k, _ in _tenant_rules()._VIOLATIONS])
def test_each_violation_breaks_exactly_its_rule(key):
    """The oracle, not the generator's intent, decides: the way to break a
    request named ``key`` flips the verdict to deny, and of the config's ten
    rules it breaks the one in its own place and no other."""
    gen = _tenant_rules()
    index = [k for k, _ in gen._VIOLATIONS].index(key)
    breaker = gen._VIOLATIONS[index][1]
    corpus = gen.manifests({"n_configs": 3})
    rng = random.Random(1)
    for i in range(3):
        vals = gen._allowed(i, rng)
        ok = dict(vals)
        vals[key] = breaker(i, vals)

        def row(v):
            v = dict(v)
            return {"config": 0, "host": gen._host(i), "method": v.pop("method"),
                    "path": v.pop("path"), "headers": v}

        assert _oracle_verdicts(corpus[i:i + 1], [row(ok), row(vals)]) == \
            [True, False], (i, key)
        # one rule a corpus: the broken row fails the key's own rule alone
        patterns = corpus[i]["spec"]["authorization"]["rules"][
            "patternMatching"]["patterns"]
        alone = [{"spec": {"authorization": {"rules": {"patternMatching": {
            "patterns": [p]}}}}} for p in patterns]
        failed = [j for j in range(len(alone)) if _oracle_verdicts(
            alone, [dict(row(vals), config=j)]) == [False]]
        assert failed == [index], (i, key)


def test_tiny_cell_serves_end_to_end_on_cpu(tmp_path, monkeypatch):
    """The harness the benchmark runs, on a tenth of a second of its
    yardstick: `tenants-1k.unique-sat` cut to 20 configs, served for two
    seconds by the CLI's child over loopback HTTP/2 on the CPU, every
    answer compared with the reference.  One device for the child, as on
    the chip (this directory's conftest forces eight, which would shard the
    corpus), so the staged entry serves and a launch is one transfer."""
    flags = os.environ.get("XLA_FLAGS", "").replace(
        "--xla_force_host_platform_device_count=8", "").strip()
    monkeypatch.setenv("XLA_FLAGS", flags)
    cell = harness.load_cell(_manifest(), ROOT, "tenants-1k.unique-sat")
    cell["config_file"]["params"]["n_configs"] = 20
    cell["mix"].update(distinct_rows=4096, warm_s=1.0)
    result = harness.run(cell, ROOT, 2**31 + 5, 2.0, False, "cpu",
                         time.monotonic(), out_root=str(tmp_path))
    assert result["correct"] and result["failed"] == 0 < result["attempted"]
    assert result["compared"]["wrong"]["value"] == 0
    assert result["compared"]["unanswered"]["value"] == 0
    assert result["device"]["platform"] == "cpu"
    assert set(result["metrics"]) == {"checks_per_s", "setup_s"}
    fe = result["evidence"]["vars"]["native_frontend"]
    assert fe["snapshot"]["kernel"]["entry"] == "eval_bitpacked_staged"
    native = fe["kernel_cost"]["ledger"]["native"]
    assert native["h2d_transfers"] == native["launches"] > 0


def test_routes_1k_cell_is_as_the_issue_states_and_its_files_agree():
    manifest = _manifest()
    cell = harness.load_cell(manifest, ROOT, "routes-1k.unique-sat")
    config = cell["config_file"]
    assert (cell["traffic"], cell["chips"]) == ("unique-sat", 1)
    assert config["params"] == {"n_configs": 1000} and config["reduced"] == []
    assert config["requests"] == {"deny_share": 0.5, "unrouted_share": 0.1,
                                  "long_path_share": 0.15}
    assert config["generator"] == "route_rules" and len(config["source"]) <= 200
    assert cell["mix"] == {"loop": "closed", "conns": 8, "depth": 128, "warm_s": 10.0,
                           "distinct_rows": 131072, "order": "cycle"}
    reads = {m["name"] for m in cell["per_layer"]}
    assert {"dfa_states", "launch_temp_bytes", "dfa_ovf_rows_pct", "dfa_scan_roofline",
            "dfa_rows_per_row", "kernel_ms_per_launch", "device_idle_pct"} <= reads
    # the roofline share that lists its cells lists this one alone; the
    # counters are read in every cell that reports checks_per_s
    assert "pattern_eval_roofline" not in reads
    for other in ("tenants-1k.unique-sat", "tenants-10k.unique-sat"):
        names = {m["name"] for m in harness.load_cell(manifest, ROOT, other)["per_layer"]}
        assert {"dfa_states", "launch_temp_bytes", "dfa_ovf_rows_pct"} <= names
        assert "dfa_scan_roofline" not in names
    generator = harness.load_module("corpora", config["generator"])
    manifests = generator.manifests({"n_configs": 3})
    evaluators = manifests[2]["spec"]["authorization"]
    assert len(evaluators) == 17 and "when" not in manifests[2]["spec"]
    assert sum("when" in ev for ev in evaluators.values()) == 16
    Reference(manifests)  # the plain reference, unedited, takes the corpus


def test_mixed_tenants_1k_cell_is_as_the_issue_states_and_its_files_agree():
    manifest = _manifest()
    cell = harness.load_cell(manifest, ROOT, "mixed-tenants-1k.unique-sat")
    assert manifest["workloads"][5]["name"] == cell["name"]    # appended by PR 34
    assert manifest["configs"][4]["name"] == cell["config"] == "mixed-tenants-1k"
    config = cell["config_file"]
    assert (cell["traffic"], cell["chips"]) == ("unique-sat", 1)
    assert config["params"] == {"n_configs": 1000, "n_large": 8, "services": 8}
    assert config["requests"] == {"large_share": 0.4, "deny_share": 0.5,
                                  "unrouted_share": 0.1, "long_path_share": 0.15}
    assert config["reduced"] == [] and config["generator"] == "mixed_tenants"
    assert len(config["source"]) <= 200 and len(cell["why"]) <= 200
    # the other configurations' three guarantees, word for word, and its own
    routes = harness.load_cell(manifest, ROOT, "routes-1k.unique-sat")["config_file"]
    assert config["guarantees"][:3] == routes["guarantees"]
    assert len(config["guarantees"]) == 4 and "class" in config["guarantees"][3]
    reads = {m["name"] for m in cell["per_layer"]}
    assert {"launches_per_cut", "dfa_slot_fill_pct", "own_class_roofline",
            "kernel_ms_per_launch", "dfa_rows_per_row", "h2d_bytes_per_row",
            "device_idle_pct", "host_lane_rows_pct"} <= reads
    assert not {"pattern_eval_roofline", "dfa_scan_roofline"} & reads
    for other in manifest["workloads"]:
        if other["name"] == cell["name"]:
            continue
        names = {m["name"] for m in
                 harness.load_cell(manifest, ROOT, other["name"])["per_layer"]}
        assert {"launches_per_cut", "dfa_slot_fill_pct"} <= names
        assert "own_class_roofline" not in names
    generator = harness.load_module("corpora", config["generator"])
    manifests = generator.manifests({"n_configs": 4, "n_large": 2, "services": 8})
    assert [m["spec"]["hosts"][0] for m in manifests[4:]] == [
        "api-0.bench.test", "api-1.bench.test"]
    evaluators = manifests[5]["spec"]["authorization"]
    assert len(evaluators) == 129 and "when" not in manifests[5]["spec"]
    assert sum("when" in ev for ev in evaluators.values()) == 128
    assert evaluators["s7-route-00"]["when"][0]["value"].startswith(
        "^/api/v[0-9]+/t1/s7/orders/")
    Reference(manifests)  # the plain reference, unedited, takes the corpus


@pytest.mark.parametrize("name, num, den, scale", [
    ("launches_per_cut", "launches", "batches", None),
    ("dfa_slot_fill_pct", "own_dfa_rows", "own_dfa_slots", 100.0),
    ("cuts_per_fold", "batches", "telemetry_folds", None)])
def test_size_class_metrics_read_ledger_counters_the_program_has(
        name, num, den, scale):
    """ISSUE 34's counters' metrics (and ISSUE 35's `cuts_per_fold`) are
    data only, over a reader that was there; a program without the counters
    gives them nothing to read."""
    from authorino_tpu.runtime import kernel_cost

    spec = harness._load_json(os.path.join(BENCH, "metrics", name + ".json"))
    assert spec["reader"] == "ledger_ratio" and spec["args"].get("scale") == scale
    assert spec["args"]["num"] == [["native", num]]
    assert spec["args"]["den"] == [["native", den]]
    assert {num, den} <= set(kernel_cost._FIELDS) and "workloads" not in spec
    reader = harness.load_module("readers", spec["reader"])

    def at(**fields):
        return {"native_frontend": {"kernel_cost": {"ledger": {"native": fields}}}}

    ctx = {"vars0": at(**{num: 10, den: 20}), "vars1": at(**{num: 40, den: 60})}
    assert reader.read(ctx, **spec["args"]) == (scale or 1.0) * 30 / 40
    # the parent's ledger has no such fields: nothing to read, no error
    old = {"vars0": at(rows=1), "vars1": at(rows=9)}
    if name != "launches_per_cut":
        assert reader.read(old, **spec["args"]) is None
    if name == "cuts_per_fold":
        entry = [m for m in _manifest()["per_layer"] if m["name"] == name]
        # PR 35 put it last of 39; later PRs append and move nothing
        assert entry == [_manifest()["per_layer"][38]] == [{
            "name": name, "unit": "cuts", "better": "higher",
            "source": "program_counter", "moves": "checks_per_s",
            "layer": "launch, readback and fan-out"}]


def test_cache_hit_rows_pct_reads_ledger_counters_the_program_has():
    """ISSUE 33's metric is data only: its file names the `ledger_ratio`
    reader and [lane, field] pairs the kernel-cost ledger folds, and every
    cell that reports `checks_per_s` reads it."""
    from authorino_tpu.runtime import kernel_cost

    manifest = _manifest()
    entry = [m for m in manifest["per_layer"] if m["name"] == "cache_hit_rows_pct"]
    assert entry == [{"name": "cache_hit_rows_pct", "unit": "%", "better": "higher",
                      "source": "program_counter", "moves": "checks_per_s",
                      "layer": "batch cut, dedup and verdict cache"}]
    assert manifest["per_layer"][34] is entry[0]        # appended, not inserted
    spec = harness._load_json(
        os.path.join(BENCH, "metrics", "cache_hit_rows_pct.json"))
    assert spec["reader"] == "ledger_ratio" and spec["args"]["scale"] == 100.0
    assert os.path.isfile(os.path.join(BENCH, "readers", spec["reader"] + ".py"))
    assert spec["args"]["num"] == [["native", "cache_avoided_rows"]]
    assert spec["args"]["den"] == [["native", "rows"], ["host", "rows"]]
    for lane, field in spec["args"]["num"] + spec["args"]["den"]:
        assert lane in kernel_cost.LANES and field in kernel_cost._FIELDS
    for cell in manifest["workloads"]:
        names = {m["name"] for m in
                 harness.load_cell(manifest, ROOT, cell["name"])["per_layer"]}
        assert "cache_hit_rows_pct" in names


@pytest.mark.parametrize("sources, runs", [
    ({"ops/pattern_eval.py": 'widths = {"leaf_cols_per_row": 10}'}, True),
    ({"ops/pattern_eval.py": 'widths = {"dfa_rows_per_row": 2}'}, False),
    ({"README.md": "leaf_cols_per_row"}, False),
    ({}, False),
], ids=["own-config", "dense", "not-in-code", "no-package"])
def test_tenants_10k_generator_refuses_a_program_with_dense_operands(
        tmp_path, sources, runs):
    """The parent of PR 28 is killed for its memory at 10,000 configs; the
    cell's generator refuses it before the child starts, so that side of the
    driver's check fails cleanly.  The rows and manifests are tenant_rules'."""
    guarded = harness.load_module("corpora", "tenant_rules_guarded")
    plain = harness.load_module("corpora", "tenant_rules")
    for rel, text in sources.items():
        path = tmp_path / "authorino_tpu" / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(text)
    params = {"n_configs": 12}
    if runs:
        assert guarded.manifests(params, str(tmp_path)) == plain.manifests(params)
    else:
        with pytest.raises(harness.Refused, match="leaf_cols_per_row"):
            guarded.manifests(params, str(tmp_path))
    assert guarded.manifests(params) == plain.manifests(params)  # this tree
    args = (dict(params, deny_share=0.5), 64)
    assert guarded.requests(*args, random.Random(7)) == \
        plain.requests(*args, random.Random(7))


def test_edge_1k_cell_is_as_the_issue_states_and_its_files_agree():
    """ISSUE 38: the configuration and its one cell are appended, the file
    states the source, what is known and assumed of it, the other
    configurations' guarantees word for word, and the generator's own
    measurements inside the issue's aims."""
    manifest = _manifest()
    cell = harness.load_cell(manifest, ROOT, "edge-1k.unique-sat")
    # appended after the six cells before it (later configurations follow)
    assert manifest["workloads"][6]["name"] == cell["name"]
    assert manifest["configs"][5]["name"] == cell["config"] == "edge-1k"
    checks = [m for m in manifest["end_to_end"] if m["name"] == "checks_per_s"]
    assert checks[0]["workloads"][6] == cell["name"]
    config = cell["config_file"]
    assert (cell["traffic"], cell["chips"]) == ("unique-sat", 1)
    assert config["params"] == {"n_configs": 1000}
    assert config["requests"] == {
        "browser_share": 0.7, "deny_share": 0.5, "write_share": 0.3,
        "cookie_pairs": [2, 9], "cookie_tail_share": 0.03}
    assert config["reduced"] == [] and config["generator"] == "edge_requests"
    assert len(config["source"]) <= 200 and len(cell["why"]) <= 200
    assert config["known_of_the_source"] and len(config["assumed"]) >= 10
    routes = harness.load_cell(manifest, ROOT, "routes-1k.unique-sat")["config_file"]
    assert config["guarantees"][:3] == routes["guarantees"]
    assert len(config["guarantees"]) == 4 and "length" in config["guarantees"][3]
    measured = config["measured_of_the_generator"]
    assert (measured["rows"], measured["seed"]) == (131072, 0)
    size = measured["check_request_bytes"]
    assert 1200 <= size["mean"] <= 2500 and 1200 <= size["p50"] <= size["p99"] <= 2500
    heads = measured["headers_per_request"]
    assert 18 <= heads["min"] <= heads["mean"] <= heads["max"] <= 30
    past = measured["rows_with_a_regex_read_value_past_pct"]
    assert past["64"] >= 65 and past["64"] > past["128"] > past["256"]
    assert 8 <= past["256"] <= 15
    reads = {m["name"] for m in cell["per_layer"]}
    assert {"fe_bytes_per_check", "fe_headers_per_check", "dfa_eff_bytes",
            "dfa_dev_bytes_pct", "long_value_roofline", "dfa_ovf_rows_pct",
            "fe_ovf_scan_pct", "fe_parse_pct", "fe_read_pct", "fe_encode_pct",
            "fe_us_per_check", "h2d_bytes_per_row", "kernel_ms_per_launch",
            "device_idle_pct", "host_lane_rows_pct"} <= reads
    assert not {"pattern_eval_roofline", "dfa_scan_roofline",
                "own_class_roofline"} & reads
    for other in manifest["workloads"]:
        if other["name"] == cell["name"]:
            continue
        names = {m["name"] for m in
                 harness.load_cell(manifest, ROOT, other["name"])["per_layer"]}
        assert {"fe_bytes_per_check", "fe_headers_per_check", "dfa_eff_bytes",
                "dfa_dev_bytes_pct"} <= names
        assert "long_value_roofline" not in names
    generator = harness.load_module("corpora", config["generator"])
    manifests = generator.manifests({"n_configs": 3})
    (evaluator,) = manifests[2]["spec"]["authorization"].values()
    patterns = evaluator["patternMatching"]["patterns"]
    assert len(patterns) == 10 and "when" not in manifests[2]["spec"]
    leaves = [leaf for p in patterns for leaf in p.get("any", [p])]
    assert sum(leaf["operator"] == "matches" for leaf in leaves) == 5
    Reference(manifests)  # the plain reference, unedited, takes the corpus


def test_edge_requests_measure_is_what_the_file_records():
    """`measured_of_the_generator` is the generator's own `measure`, at a
    size a test can afford: the same shares to a few points."""
    config = harness.load_cell(
        _manifest(), ROOT, "edge-1k.unique-sat")["config_file"]
    generator = harness.load_module("corpora", config["generator"])
    got = generator.measure(dict(config["params"], **config["requests"]), 4096, 0)
    want = config["measured_of_the_generator"]
    assert abs(got["check_request_bytes"]["mean"]
               - want["check_request_bytes"]["mean"]) < 40
    assert abs(got["headers_per_request"]["mean"]
               - want["headers_per_request"]["mean"]) < 0.5
    for width in ("64", "128", "256"):
        assert abs(got["rows_with_a_regex_read_value_past_pct"][width]
                   - want["rows_with_a_regex_read_value_past_pct"][width]) < 3


@pytest.mark.parametrize("name, reader, counters", [
    ("fe_bytes_per_check", "front_clock", ("req_bytes", "parse")),
    ("fe_headers_per_check", "front_clock", ("req_headers", "parse")),
    ("dfa_eff_bytes", "ledger_ratio", ("eff_cols", "launches")),
    ("dfa_dev_bytes_pct", "ledger_ratio", ("dfa_dev_bytes", "dfa_host_bytes")),
    ("long_value_roofline", "trace_kernel", ())])
def test_edge_metrics_read_counters_the_program_has(name, reader, counters):
    """ISSUE 38's metrics are data only, over readers that were there; a
    program without the counters (the parent) gives them nothing to read
    and the reader does not raise."""
    from authorino_tpu.runtime import kernel_cost

    spec = harness._load_json(os.path.join(BENCH, "metrics", name + ".json"))
    assert spec["reader"] == reader
    assert os.path.isfile(os.path.join(BENCH, "readers", reader + ".py"))
    per_layer = _manifest()["per_layer"]
    entry = [m for m in per_layer if m["name"] == name]
    # the five were appended together (later PRs' metrics follow them)
    end = [m["name"] for m in per_layer].index("long_value_roofline") + 1
    assert len(entry) == 1 and entry[0] in per_layer[end - 5:end]
    assert entry[0].get("workloads") == spec.get("workloads")
    module = harness.load_module("readers", reader)
    if reader == "ledger_ratio":
        assert set(counters) <= set(kernel_cost._FIELDS)

        def at(**fields):
            return {"native_frontend": {"kernel_cost": {"ledger": {"native": fields}}}}

        a, b = counters
        ctx = {"vars0": at(**{a: 10, b: 20}), "vars1": at(**{a: 40, b: 60})}
        want = (100.0 * 30 / 70 if name == "dfa_dev_bytes_pct" else 30 / 40)
        assert module.read(ctx, **spec["args"]) == pytest.approx(want)
        old = {"vars0": at(rows=1), "vars1": at(rows=9)}
        if name == "dfa_dev_bytes_pct":
            assert module.read(old, **spec["args"]) is None
    elif reader == "front_clock":
        cpp = open(os.path.join(ROOT, "native", "frontend.cpp")).read()
        assert all(f'"{row}"' in cpp for row in counters)

        def at(rows):
            cell = {"count": 0, "sum_ns": 0, "max_ns": 0}
            return {"native_frontend": {"front": {
                "phases": {"parse": dict(cell, count=rows.pop("parse")),
                           "idle": dict(cell, sum_ns=1)},
                "rows": {k: dict(cell, count=v) for k, v in rows.items()}}}}

        per, den = counters
        ctx = {"vars0": at({per: 1000, "parse": 10}),
               "vars1": at({per: 31000, "parse": 30})}
        assert module.read(ctx, **spec["args"]) == 1500.0
        old = {"vars0": at({"parse": 10}), "vars1": at({"parse": 30})}
        assert module.read(old, **spec["args"]) is None


def test_api_allowlist_1k_cell_is_appended_and_its_files_agree():
    """The allowlist configuration and its one cell come last, its file
    states what it assumes and guarantees (`edge-1k`'s four and one of its
    own), and the generator's recorded path lengths are its own."""
    manifest = _manifest()
    cell = harness.load_cell(manifest, ROOT, "api-allowlist-1k.unique-sat")
    assert manifest["workloads"][-1]["name"] == cell["name"]
    assert manifest["configs"][-1]["name"] == cell["config"] == "api-allowlist-1k"
    checks = [m for m in manifest["end_to_end"] if m["name"] == "checks_per_s"]
    assert checks[0]["workloads"][-1] == cell["name"]
    config = cell["config_file"]
    assert (cell["traffic"], cell["chips"]) == ("unique-sat", 1)
    assert config["params"] == {"n_configs": 1000}
    assert config["requests"] == {"deny_share": 0.5}
    assert config["reduced"] == [] and config["generator"] == "api_allowlist"
    edge = harness.load_cell(manifest, ROOT, "edge-1k.unique-sat")["config_file"]
    assert config["guarantees"][:4] == edge["guarantees"]
    assert "off the device" in config["guarantees"][4]
    measured = config["measured_of_the_generator"]
    states = measured["path_regex_dfa_states"]
    assert 96 < states["min"] <= states["p50"] <= states["max"] <= 1024
    assert states["past_96_pct"] == 100.0 and states["past_256_pct"] > 0
    generator = harness.load_module("corpora", config["generator"])
    got = generator.measure(dict(config["params"], **config["requests"]), 4096, 0)
    assert got["rows_with_a_path_past_64_pct"] == 0.0
    assert got["path_bytes"]["max"] <= measured["path_bytes"]["max"] <= 64
    reads = {m["name"] for m in cell["per_layer"]}
    assert {"wide_dfa_roofline", "slow_configs", "dfa_cpu_leaves",
            "dfa_states", "kernel_ms_per_launch"} <= reads
    Reference(generator.manifests({"n_configs": 3}))  # the reference takes it


@pytest.mark.parametrize("name, path", [
    ("slow_configs", ["native_frontend", "snapshot", "slow_configs"]),
    ("dfa_cpu_leaves", ["native_frontend", "snapshot", "kernel", "dfa_cpu_leaves"])])
def test_lane_metrics_read_the_snapshot_and_are_read_in_every_cell(name, path):
    """The two lane counters are data over `vars_path`, read in every cell
    (no `workloads` list); a program without the counter (the parent) gives
    them nothing to read, and the reader does not raise."""
    spec = harness._load_json(os.path.join(BENCH, "metrics", name + ".json"))
    assert (spec["reader"], spec["args"]["path"]) == ("vars_path", path)
    (entry,) = [m for m in _manifest()["per_layer"] if m["name"] == name]
    assert "workloads" not in entry and entry["layer"] == "lane selection and brownout"
    module = harness.load_module("readers", "vars_path")
    at = {}
    for key in reversed(path):
        at = {key: at or 3}
    assert module.read({"vars1": at}, **spec["args"]) == 3
    assert module.read({"vars1": {"native_frontend": {"snapshot": {}}}},
                       **spec["args"]) is None
