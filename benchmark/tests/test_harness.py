"""The harness end to end on the CPU at a tiny size, with the platform it
expects passed in (the command line has no such option); the control and
the planted fault come out as not correct; and the pieces of the yardstick
give known numbers: the schedule, the trace reduction, the required work.
"""

import json
import os
import struct
import subprocess
import sys
import time

import numpy as np
import pytest

import child
import control
import harness
import trace_reduce
import trafficgen
import wire
import work
from conftest import BENCH, ROOT
from reference import OK, PERMISSION_DENIED, Reference

SECONDS = 2.0


# No cell of BENCHMARK.json offers load in an open loop yet (the program
# collapses under it, PERF.md section 7): the generator's open loop is driven
# here with a mix a later PR would add as a traffic file.
OPEN_MIX = {"loop": "open", "conns": 8, "rate_per_s": 400.0, "warm_s": 1.0,
            "distinct_rows": 4096, "order": "cycle"}


def tiny_cell(workload, n_configs=20, rows=4096, mix=None):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = harness.load_cell(json.load(f), ROOT, workload)
    cell["config_file"]["params"]["n_configs"] = n_configs
    cell["mix"].update(distinct_rows=rows, warm_s=1.0)
    if mix:
        cell["mix"] = dict(mix)
    return cell


def tiny_run(tmp, workload, platform="cpu", seed=2**31 + 5, **kw):
    return harness.run(tiny_cell(workload, **kw), ROOT, seed, SECONDS, False,
                       platform, time.monotonic(), out_root=str(tmp))


@pytest.fixture(scope="module")
def sound(tmp_path_factory):
    """One sound run of each load shape, shared by the tests that read it."""
    tmp = tmp_path_factory.mktemp("chip")
    return {"closed": tiny_run(tmp, "tenants-1k.unique-sat", rows=70000),
            "open": tiny_run(tmp, "tenants-1k.unique-sat", mix=OPEN_MIX)}


def test_line_has_the_contracts_keys(sound, capsys):
    result = sound["closed"]
    harness.print_result(result)
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "compared"]
    assert line["correct"] is True and line["failed"] == 0 < line["attempted"]
    assert set(line["metrics"]) == {"checks_per_s", "setup_s"}
    assert all(set(m) == {"value", "unit"} for m in line["metrics"].values())
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert line["compared"]["wrong"] == {"value": 0, "limit": 0}
    assert "compared wrong = 0 (limit 0)" in err.strip().splitlines()[-3]


def test_open_loop_offers_its_rate_and_records_lateness(sound):
    result = sound["open"]
    assert result["correct"]
    # the open loop offered what its mix says, whatever was in flight
    assert result["attempted"] == 400 * SECONDS
    assert result["metrics"]["checks_per_s"]["value"] == pytest.approx(400, rel=0.05)
    cmp = result["evidence"]["cmp"]
    rec = cmp["records"][cmp["in_window"]]
    late = rec["sent"] - rec["due"]     # the generator's own lateness, ms
    latency = rec["done"] - rec["due"]  # timed from the instant it was due
    assert 0 <= np.percentile(late, 99) < 1000
    assert (latency >= late).all() and 0 < np.median(latency) < 1000


def test_second_configuration_agrees_with_the_reference(tmp_path):
    result = tiny_run(tmp_path, "conditions-200.unique-sat")
    assert result["correct"] and result["attempted"] > 0
    codes = set(result["evidence"]["traffic"]["expected"].tolist())
    assert codes == {OK, PERMISSION_DENIED}


def test_traced_run_reports_the_per_layer_metrics(tmp_path, monkeypatch):
    """The rest of a traced run with no chip: the CPU child's profile holds no
    device plane, so the recorded v5e trace and the v5e's peaks stand in."""
    with open(os.path.join(BENCH, "tests", "data", "trace_v5e_tenants1k.json")) as f:
        planes = json.load(f)["planes"]
    monkeypatch.setattr(harness, "reduce_trace", lambda trace_dir:
                        trace_reduce.reduce_planes(planes, harness.TRACE_S))
    peaks = work.peaks
    monkeypatch.setattr(work, "peaks", lambda kind: peaks("TPU v5e"))
    cell = tiny_cell("tenants-1k.unique-sat", rows=70000)
    result = harness.run(cell, ROOT, 7, 7.0, True, "cpu", time.monotonic(),
                         out_root=str(tmp_path))
    assert result["correct"]
    assert set(result["metrics"]) == {m["name"] for m in cell["per_layer"]}
    assert 0 < result["metrics"]["pattern_eval_roofline"]["value"] < 100
    want = json.load(open(os.path.join(
        BENCH, "tests", "data", "trace_v5e_tenants1k.json")))["known"]
    assert result["metrics"]["kernel_ms_per_launch"]["value"] == pytest.approx(
        want["kernel_s"] * 1e3 / want["launches"])
    assert set(result["device"]) >= {"busy_s", "window_s"}
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


def test_gate_refuses_another_platform(tmp_path):
    with pytest.raises(harness.Refused, match="platform is 'cpu', not 'tpu'"):
        tiny_run(tmp_path, "tenants-1k.unique-sat", platform="tpu")


def test_no_system_under_test_refuses(tmp_path):
    """A directory that holds only BENCHMARK.json and the benchmark."""
    cell = tiny_cell("tenants-1k.unique-sat")
    with pytest.raises(harness.Refused, match="no system under test"):
        harness.run(cell, str(tmp_path), 1, SECONDS, False, "cpu",
                    time.monotonic(), out_root=str(tmp_path))


def test_control_is_not_correct(sound):
    """The reference behind a verdict cache keyed on a 16-bit digest, in
    the program's place: wrong answers, where the program has none."""
    program, ctl = control.readings(sound["closed"], SECONDS)
    assert program["correct"] and program["numbers"]["wrong"] == 0
    assert not ctl["correct"] and ctl["numbers"]["wrong"] > 10


def test_altered_answer_is_not_correct(tmp_path, monkeypatch):
    """The timed path broken underneath: the server is given a corpus in
    which one rule of every fifth config differs from what the reference
    was given, so it produces other answers than the reference."""
    write = child.write_manifests

    def altered(corpus, directory):
        corpus = json.loads(json.dumps(corpus))
        for manifest in corpus[::5]:
            rules = manifest["spec"]["authorization"]["rules"]
            rules["patternMatching"]["patterns"][0]["value"] = "GET"
        write(corpus, directory)

    monkeypatch.setattr(child, "write_manifests", altered)
    result = tiny_run(tmp_path, "tenants-1k.unique-sat")
    assert result["correct"] is False
    assert result["compared"]["wrong"]["value"] > 0 and result["failed"] > 0


def test_unanswered_is_not_correct():
    records = np.zeros(4, dtype=harness.RECORD)
    records["due"], records["sent"] = [0, 1, 2, 3], [0, 1, 2, 3]
    records["done"] = [5, 6, np.nan, 8]
    cmp = harness.compare(records, np.zeros(1, dtype=np.int32), 1.0)
    assert cmp["numbers"] == {"wrong": 0, "unanswered": 1}
    assert not cmp["correct"] and cmp["failed"] == 1


def test_late_answer_is_late_not_wrong():
    records = np.zeros(2, dtype=harness.RECORD)
    records["due"] = [10, 900]
    records["done"] = [30, 4000]  # the second comes 3 s after a 1 s window
    cmp = harness.compare(records, np.zeros(1, dtype=np.int32), 1.0)
    assert cmp["correct"] and cmp["failed"] == 0
    ctx = {"records": cmp["records"], "right": cmp["right"], "seconds": 1.0}
    got = harness.read_metrics([{"name": "checks_per_s", "unit": "x"}], ctx)
    assert got["checks_per_s"]["value"] == 1.0  # the late one is not the window's


# ---------------------------------------------------------------------------
# the yardstick's pieces
# ---------------------------------------------------------------------------


def test_open_schedule_is_reproducible_and_equal_across_seeds():
    mix = {"loop": "open", "rate_per_s": 1000.0, "warm_s": 1.0}
    a, b = trafficgen.due_times(mix, 7, 4.0), trafficgen.due_times(mix, 7, 4.0)
    c = trafficgen.due_times(mix, 2**31 + 9, 4.0)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert len(a) == len(c) == 5000
    # the same set of gaps in another order: the same load to the request
    for part, start in ((slice(0, 1000), 0.0), (slice(1000, 5000), 1.0)):
        assert np.allclose(np.sort(np.diff(a[part], prepend=start)),
                           np.sort(np.diff(c[part], prepend=start)))
    assert a[999] < 1.0 <= a[1000] and a[-1] < 5.0 and abs(c[-1] - a[-1]) < 1e-9
    gaps = np.diff(a[1000:])
    assert gaps.mean() == pytest.approx(1e-3, rel=0.01)
    assert gaps.std() == pytest.approx(1e-3, rel=0.05)  # exponential: sd = mean


def test_zipf_order_keeps_most_draws_in_the_head():
    mix = {"distinct_rows": 65536, "order": "zipf", "zipf_theta": 0.99,
           "draws": 200000}
    order = trafficgen.order(mix, 3)
    assert np.array_equal(order, trafficgen.order(mix, 3))
    weights = 1.0 / np.arange(1, 65537) ** 0.99
    weights /= weights.sum()
    assert 0.92 < weights[:32768].sum() < 0.96  # about ln 32768 / ln 65536
    counts = np.sort(np.bincount(order, minlength=65536))[::-1]
    assert counts[0] == pytest.approx(200000 * weights[0], rel=0.05)
    assert counts[:10].sum() == pytest.approx(200000 * weights[:10].sum(), rel=0.05)


def test_wire_bytes_parse_as_the_request(sound):
    from authorino_tpu import protos

    row = sound["closed"]["evidence"]["traffic"]["rows"][0]
    msg = protos.external_auth_pb2.CheckRequest.FromString(wire.check_request(row))
    http = msg.attributes.request.http
    assert (http.method, http.path, http.host) == (row["method"], row["path"], row["host"])
    assert dict(http.headers) == dict(row["headers"], host=row["host"])


def test_reference_semantics_of_a_missing_value():
    spec = {"hosts": ["h"], "authentication": {"a": {"anonymous": {}}},
            "authorization": {"r": {"patternMatching": {"patterns": [
                {"selector": "request.headers.x-a", "operator": "excl", "value": "v"},
                {"selector": "request.headers.x-b", "operator": "neq", "value": "v"},
                {"any": [
                    {"selector": "request.headers.x-c", "operator": "incl", "value": "v"},
                    {"selector": "request.headers.x-d", "operator": "eq", "value": ""}]},
            ]}}}}
    ref = Reference([{"spec": spec}])
    req = {"host": "h", "method": "GET", "path": "/", "headers": {}}
    assert ref.decide(req) == OK  # excl and neq hold, x-d renders ""
    assert ref.decide(dict(req, headers={"x-d": "z"})) == PERMISSION_DENIED
    assert ref.decide(dict(req, headers={"x-d": "z", "x-c": "v"})) == OK
    assert ref.decide(dict(req, host="other")) == 5


def test_required_work_of_three_rows():
    """Hand-computed: each leaf reads its attribute's bytes and its constant's,
    one operation a value byte; one verdict byte out."""
    leaves = [
        {"selector": "request.method", "operator": "eq", "value": "POST"},
        {"selector": "request.url_path", "operator": "matches", "value": "^/t1/[0-9]+$"},
        {"selector": "request.headers.x-g", "operator": "incl", "value": "g-1"},
    ]
    rows = [
        {"host": "h", "method": "GET", "path": "/t1/42", "headers": {"x-g": "g-1"}},
        {"host": "h", "method": "POST", "path": "/t1/4?q=1", "headers": {}},
        {"host": "h", "method": "DELETE", "path": "/", "headers": {"x-g": "longer"}},
    ]
    # value bytes: 3+6+3, 4+5+0, 6+1+6; constants 4+12+3 = 19
    assert work.required(leaves, rows[0]) == (12, 12 + 19 + 1)
    assert work.required(leaves, rows[1]) == (9, 9 + 19 + 1)
    assert work.required(leaves, rows[2]) == (13, 13 + 19 + 1)
    manifest = {"spec": {"patterns": {"p": leaves[:1]}, "when": [{"patternRef": "p"}],
                         "authorization": {"r": {"when": [leaves[2]], "patternMatching": {
                             "patterns": [{"all": [leaves[1], {"any": [leaves[2]]}]}]}}}}}
    assert work.config_leaves(manifest) == [leaves[0], leaves[2], leaves[1], leaves[2]]
    seconds, bound = work.least_seconds(393e12, 819e9 / 2, "TPU v5 lite")
    assert (seconds, bound) == (1.0, "ops")
    with pytest.raises(KeyError):
        work.peaks("TPU v9")


def test_stage_quantile_interpolates_bucket_deltas():
    name = "auth_server_frontend_stage_duration_seconds_bucket"

    def page(counts):
        return {name: [({"stage": "exec", "le": le}, c) for le, c in counts]}

    ctx = {"metrics0": page([("0.001", 10), ("0.01", 10), ("+Inf", 10)]),
           "metrics1": page([("0.001", 10), ("0.01", 110), ("+Inf", 110)])}
    read = harness.load_module("readers", "stage_quantile").read
    assert read(ctx, stage="exec", q=0.5) == pytest.approx(5500.0)
    assert read(ctx, stage="wait", q=0.5) is None


def test_ledger_ratio_reads_window_deltas():
    def dv(rows, device, launches):
        return {"native_frontend": {"kernel_cost": {"ledger": {"native": {
            "rows": rows, "device_rows": device, "launches": launches}}}}}

    ctx = {"vars0": dv(100, 50, 5), "vars1": dv(1100, 250, 15)}
    read = harness.load_module("readers", "ledger_ratio").read
    assert read(ctx, num=[["native", "device_rows"]], den=[["native", "launches"]]) == 20.0
    assert read(ctx, num=[["native", "device_rows"]],
                den=[["native", "rows"], ["host", "rows"]], scale=100.0) == 20.0
    assert read({"vars0": dv(1, 1, 1), "vars1": dv(1, 1, 1)},
                num=[["native", "rows"]], den=[["native", "launches"]]) is None


def test_rows_under_the_kernel_come_from_readings_inside_the_trace():
    """The trace's launches carry the rows the ledger counted between two
    readings inside the traced seconds, not those of the served window."""
    def dv(rows, launches):
        return {"native_frontend": {
            "snapshot": {"kernel": {"entry": "eval_k"}},
            "kernel_cost": {"ledger": {"native": {
                "device_rows": rows, "launches": launches}}}}}

    ctx = {"vars0": dv(0, 0), "vars1": dv(170000, 1000),  # served: 170 a launch
           "trace": {"modules": {"jit_eval_k(1)": {"count": 50, "seconds": 0.5}}},
           "trace_vars0": dv(171000, 1005), "trace_vars1": dv(176120, 1025)}
    read = harness.load_module("readers", "trace_kernel").read
    assert read(ctx, what="ms_per_launch") == pytest.approx(10.0)
    # 256 rows a launch inside the trace: 50 launches carried 12,800 rows
    assert read(ctx, what="ms_per_krow") == pytest.approx(500.0 / 12.8)
    few = dict(ctx, trace_vars1=dv(171512, 1007))
    assert read(few, what="ms_per_krow") is None
    none = dict(ctx, trace_vars0=None, trace_vars1=None)
    assert read(none, what="ms_per_krow") is None
    assert read(none, what="roofline_pct") is None
    assert read(none, what="ms_per_launch") == pytest.approx(10.0)


def test_ledger_readings_outside_the_trace_are_dropped(monkeypatch, capsys):
    monkeypatch.setattr(harness, "LEDGER_IN_TRACE_S", (0.0, 0.01))
    monkeypatch.setattr(child, "debug_vars", lambda port: {"n": 1})
    now = time.monotonic()
    out = {}
    harness.ledger_in_trace(0, now, {"sent": now - 0.1}, out)
    assert out == {"trace_vars0": {"n": 1}, "trace_vars1": {"n": 1}}
    for pulled in ({"sent": now - harness.TRACE_S}, {"sent": now + 60}, {}):
        out = {}
        harness.ledger_in_trace(0, now, pulled, out)
        assert out == {}
    assert "ledger readings not inside the trace" in capsys.readouterr().err


# --- the trace reduction ---------------------------------------------------

def test_reduction_of_the_recorded_trace():
    """tests/data/trace_v5e_tenants1k.json: the first launches of a trace of
    tenants-1k.unique-sat pulled on the chip (PR 24), device plane only."""
    with open(os.path.join(BENCH, "tests", "data", "trace_v5e_tenants1k.json")) as f:
        recorded = json.load(f)
    reduced = trace_reduce.reduce_planes(recorded["planes"])
    want = recorded["known"]
    assert reduced["devices"] == 1
    assert reduced["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert reduced["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    kernel = [m for name, m in reduced["modules"].items() if "eval_bitpacked" in name]
    assert len(kernel) == len(reduced["modules"]) == 2  # two compiled shapes
    assert sum(m["count"] for m in kernel) == want["launches"]
    assert sum(m["seconds"] for m in kernel) == pytest.approx(want["kernel_s"], rel=1e-9)
    assert reduced["breakdown"]["device_ops"][0][0] == want["top_op"]
    assert len(reduced["breakdown"]["idle_gaps"]) <= 10


def test_reduction_of_known_intervals():
    planes = [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [("jit_k(1)", 0, 4e6), ("jit_k(1)", 10e6, 4e6),
                                           ("jit_other(2)", 20e6, 1e6)]},
        {"name": "XLA Ops", "events": [("%a = f32[] add()", 0, 3e6), ("%b = f32[] mul()", 2e6, 2e6),
                                       ("%a = f32[] add()", 10e6, 4e6), ("%c", 20e6, 1e6)]},
    ]}, {"name": "/host:CPU", "lines": [{"name": "python", "events": [("x", 0, 1e9)]}]}]
    reduced = trace_reduce.reduce_planes(planes, requested_s=0.03)
    assert reduced["busy_s"] == pytest.approx(0.009)     # 4 + 4 + 1 ms, overlap once
    assert reduced["window_s"] == pytest.approx(0.03)    # asked for longer than seen
    assert reduced["modules"]["jit_k(1)"] == {"count": 2, "seconds": pytest.approx(0.008)}
    assert reduced["breakdown"]["device_ops"][0] == ["a", pytest.approx(0.007)]
    gaps = reduced["breakdown"]["idle_gaps"]
    assert [round(g[1], 6) for g in gaps] == [0.006, 0.006]
    idle = harness.load_module("readers", "trace_idle").read({"trace": reduced})
    assert idle == pytest.approx(70.0)
    assert trace_reduce.reduce_planes(planes[1:])["busy_s"] == 0.0


_ld = wire._ld


def _vi(field, n):
    return wire._varint(field << 3) + wire._varint(n)


def test_xplane_file_is_read_in_a_helper_process(tmp_path):
    """A hand-written XSpace (planes=1: XPlane{name=2, lines=3: XLine{name=2,
    timestamp_ns=3, events=4: XEvent{metadata_id=1, offset_ps=2,
    duration_ps=3}}, event_metadata=4}) through trace_reduce.py as the
    harness runs it: held to the CPU, its last line the reduction."""
    def event(meta, offset_ns, dur_ns):
        return _ld(4, _vi(1, meta) + _vi(2, offset_ns * 1000) + _vi(3, dur_ns * 1000))

    def meta(key, name):
        return _ld(4, _vi(1, key) + _ld(2, _vi(1, key) + _ld(2, name.encode())))

    ops = _ld(3, _vi(1, 1) + _ld(2, b"XLA Ops") + _vi(3, 1000)
              + event(1, 0, 2000000) + event(1, 5000000, 1000000))
    modules = _ld(3, _vi(1, 2) + _ld(2, b"XLA Modules") + _vi(3, 1000)
                  + event(2, 0, 2000000) + event(2, 5000000, 1000000))
    plane = _ld(1, _vi(1, 1) + _ld(2, b"/device:TPU:0") + ops + modules
                + meta(1, "%fusion.1 = f32[] fusion()") + meta(2, "jit_eval_bitpacked_jit(7)"))
    out = tmp_path / "plugins" / "profile" / "t"
    out.mkdir(parents=True)
    (out / "host.xplane.pb").write_bytes(plane)
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "trace_reduce.py"), str(tmp_path), "0.01"],
        capture_output=True, env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=120)
    assert proc.returncode == 0, proc.stderr.decode()[-500:]
    reduced = json.loads(proc.stdout.decode().strip().splitlines()[-1])
    assert reduced["busy_s"] == pytest.approx(0.003)
    assert reduced["window_s"] == pytest.approx(0.01)
    assert reduced["modules"]["jit_eval_bitpacked_jit(7)"]["count"] == 2
    assert reduced["breakdown"]["device_ops"][0][0] == "fusion.1"


def test_hbm_reading_is_decoded():
    import hbm

    answer = bytes.fromhex(  # libtpu's answer on the chip (my chip run, PR 24)
        "0a4d0a227470752e72756e74696d652e68626d2e6d656d6f72792e75736167652e"
        "62797465731a270a0f0a096465766963652d696412021800120c08d5a2f3d50610"
        "b3a0afa7021a06108080b1af011801")
    assert hbm.usage_bytes(answer) == [367804416]
    assert hbm.usage_bytes(b"") == []


def test_loadgen_records_are_twenty_bytes():
    assert harness.RECORD.itemsize == 20 == struct.calcsize("<Iifff")
