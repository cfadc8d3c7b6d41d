"""The readers of the front end's loop clock and of the generator's records,
over plain data: two recorded /debug/vars readings of the table, and
synthetic records with a known round trip and depth.  That a traced run
reports the metrics is the chip's to show (PERF.md section 5)."""

import json
import os

import numpy as np
import pytest

import harness
from conftest import BENCH

FRONT = ("fe_loop_busy_pct", "fe_read_pct", "fe_parse_pct", "fe_encode_pct",
         "fe_ovf_scan_pct", "fe_respond_pct", "fe_write_pct",
         "fe_us_per_check", "fe_reqs_per_wake", "fe_residence_ms")
CLIENT = ("client_rtt_ms",)
STAGED = ("fe_respond_p50_us", "cut_fill_ms")


def spec_of(name):
    with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
        return json.load(f)


def metric(name):
    """The reader and arguments that benchmark/metrics/<name>.json names."""
    spec = spec_of(name)
    reader = harness.load_module("readers", spec["reader"])
    return lambda ctx: reader.read(ctx, **spec.get("args", {}))


NOT_PHASES = ("turn", "req_wait", "req_exec", "req_respond")


def front_dv(**rows):
    """One /debug/vars reading: {row: (count, sum_ns)}, split as
    fe_loop_clock() splits it: the thread's phases, and the rows that are
    not phases of it."""
    table = {r: {"count": c, "sum_ns": s, "max_ns": 0} for r, (c, s) in rows.items()}
    return {"native_frontend": {"front": {
        "phases": {r: v for r, v in table.items() if r not in NOT_PHASES},
        "rows": {r: v for r, v in table.items() if r in NOT_PHASES},
        "counters": {}, "slow_turns": [], "mark_mono_ns": 1}}}


# two readings 10.1 s of the thread's own clock apart: 4 s idle, 6.1 s busy
BEFORE = front_dv(
    idle=(100, 1e9), read=(50, 2e8), parse=(1000, 1e8), encode=(1000, 3e8),
    ovf_scan=(10, 1e7), cut=(4, 1e6), respond=(1000, 1e8), write=(80, 2e8),
    other=(9, 1e6), turn=(100, 912e6), req_wait=(1000, 5e8),
    req_exec=(1000, 4e9), req_respond=(1000, 1e8))
AFTER = front_dv(
    idle=(2100, 5e9), read=(2050, 17e8), parse=(501000, 11e8),
    encode=(501000, 18e8), ovf_scan=(50010, 51e7), cut=(1957, 51e6),
    respond=(501000, 6e8), write=(4080, 12e8), other=(1009, 51e6),
    turn=(2100, 7012e6), req_wait=(501000, 5e8 + 25e10),
    req_exec=(501000, 4e9 + 2e12), req_respond=(401000, 1e8 + 4e10))
CTX = {"untraced_s": 10.7, "vars0": BEFORE, "vars1": AFTER}


def test_every_what_reads_the_difference_of_two_readings():
    # the thread's own wall: 4 + 1.5 + 1 + 1.5 + 0.5 + 0.05 + 0.5 + 1 + 0.05 s
    # = 10.1 s, whatever the scrapes took (untraced_s is not read)
    wall = 10.1
    assert metric("fe_loop_busy_pct")(CTX) == pytest.approx(100 * 6.1 / wall)
    assert metric("fe_read_pct")(CTX) == pytest.approx(100 * 1.5 / wall)
    assert metric("fe_parse_pct")(CTX) == pytest.approx(100 * 1.0 / wall)
    assert metric("fe_encode_pct")(CTX) == pytest.approx(100 * 1.5 / wall)
    assert metric("fe_ovf_scan_pct")(CTX) == pytest.approx(100 * 0.5 / wall)
    assert metric("fe_respond_pct")(CTX) == pytest.approx(100 * 0.5 / wall)
    assert metric("fe_write_pct")(CTX) == pytest.approx(100 * 1.0 / wall)
    # the six read shares and the unread `cut` and `other` are the busy share
    shares = sum(metric(n)(CTX) for n in FRONT[1:7]) + 100 * 0.1 / wall
    assert shares == pytest.approx(metric("fe_loop_busy_pct")(CTX))
    # 6.1 s of turns over 500,000 requests; 500,000 requests over 2,000 wakes
    assert metric("fe_us_per_check")(CTX) == pytest.approx(12.2)
    assert metric("fe_reqs_per_wake")(CTX) == pytest.approx(250.0)
    # each stage's own mean: 0.5 + 4.0 ms over 500,000, 0.1 ms over 400,000
    assert metric("fe_residence_ms")(CTX) == pytest.approx(0.5 + 4.0 + 0.1)


@pytest.mark.parametrize("name", FRONT)
def test_a_program_without_the_table_gives_nothing_to_read(name):
    older = {"native_frontend": {"stages": {}}}
    assert metric(name)({"untraced_s": 2.0, "vars0": older, "vars1": older}) is None
    assert metric(name)({"untraced_s": 2.0, "vars0": {}, "vars1": AFTER}) is None
    front = json.loads(json.dumps(AFTER["native_frontend"]["front"]))
    args = spec_of(name)["args"]
    gone = (list(args.get("rows", ())) + [args.get("per"), args.get("den")])[0]
    gone = gone or args["per"]
    del front["rows" if gone in NOT_PHASES else "phases"][gone]
    part = {"native_frontend": {"front": front}}
    assert metric(name)({"untraced_s": 2.0, "vars0": BEFORE, "vars1": part}) is None


@pytest.mark.parametrize("name", FRONT)
def test_a_zero_count_gives_nothing_to_read(name):
    """Two readings with nothing between them: no wall time, no request, no
    wake to divide by."""
    assert metric(name)({"untraced_s": 2.0, "vars0": AFTER, "vars1": AFTER}) is None


def test_an_idle_server_reads_zero_shares_and_no_means():
    later = json.loads(json.dumps(AFTER))
    idle = later["native_frontend"]["front"]["phases"]["idle"]
    idle["count"] += 20
    idle["sum_ns"] += 2e9
    ctx = {"untraced_s": 2.0, "vars0": AFTER, "vars1": later}
    assert metric("fe_loop_busy_pct")(ctx) == 0.0
    assert metric("fe_parse_pct")(ctx) == 0.0
    assert metric("fe_reqs_per_wake")(ctx) == 0.0
    assert metric("fe_us_per_check")(ctx) is None
    assert metric("fe_residence_ms")(ctx) is None


def test_a_row_that_is_no_phase_stays_out_of_the_wall_time():
    """`rows` never enters the denominator, whatever it holds: a share is
    over the phases alone."""
    more = json.loads(json.dumps(AFTER))
    more["native_frontend"]["front"]["rows"]["req_new"] = {
        "count": 5, "sum_ns": 9e12, "max_ns": 0}
    ctx = dict(CTX, vars1=more)
    assert metric("fe_loop_busy_pct")(ctx) == metric("fe_loop_busy_pct")(CTX)
    assert metric("fe_read_pct")(ctx) == metric("fe_read_pct")(CTX)


def test_front_clock_refuses_a_what_it_does_not_know():
    reader = harness.load_module("readers", "front_clock")
    with pytest.raises(ValueError):
        reader.read(CTX, what="p99", rows=["turn"])


# --- the two metrics over readers that were there ----------------------------

def test_fill_and_respond_are_data_over_readers_that_were_there():
    fill, respond = spec_of("cut_fill_ms"), spec_of("fe_respond_p50_us")
    assert (fill["reader"], fill["args"]) == (
        "stage_clock", {"stages": ["fill"], "what": "mean_ms"})
    assert (respond["reader"], respond["args"]) == (
        "stage_quantile", {"stage": "respond", "q": 0.5})

    def stages(**rows):
        return {"native_frontend": {"stages": {
            s: dict(zip(("count", "sum_ns", "max_ns"), v)) for s, v in rows.items()}}}

    ctx = {"untraced_s": 2.0, "vars0": stages(fill=(10, 1e6, 1), pickup=(10, 1, 1)),
           "vars1": stages(fill=(110, 151e6, 1), pickup=(110, 1, 1))}
    assert metric("cut_fill_ms")(ctx) == pytest.approx(1.5)
    # the parent's table has no such stage: nothing to read, no error
    old = {"untraced_s": 2.0, "vars0": stages(pickup=(10, 1, 1)),
           "vars1": stages(pickup=(110, 1, 1))}
    assert metric("cut_fill_ms")(old) is None
    name = "auth_server_frontend_stage_duration_seconds_bucket"

    def scrape(*cum):
        return {name: [({"stage": "respond", "le": le}, v) for le, v in zip(
            ("1e-05", "2.5e-05", "5e-05", "+Inf"), cum)]}

    ctx = {"metrics0": scrape(0, 0, 0, 0), "metrics1": scrape(0, 40, 100, 100)}
    assert metric("fe_respond_p50_us")(ctx) == pytest.approx(25 + 25 * 10 / 60)


# --- the generator's records -------------------------------------------------

def records(sent_ms, rtt_ms):
    rec = np.zeros(len(sent_ms), dtype=harness.RECORD)
    rec["sent"] = rec["due"] = sent_ms
    rec["done"] = np.asarray(sent_ms) + rtt_ms
    return rec


def closed(conns, depth):
    return {"mix": {"loop": "closed", "conns": conns, "depth": depth}}


def test_round_trip_is_the_mean_of_done_less_sent():
    sent = np.arange(-8.0, 1000.0, 0.25)
    rec = records(sent, 6.0)
    ctx = {"cell": closed(4, 8), "seconds": 1.0, "records": rec}
    assert metric("client_rtt_ms")(ctx) == pytest.approx(6.0, abs=1e-4)
    # a traced run reads the untraced part of the window alone
    slow_tail = records(sent, np.where(sent < 500.0, 6.0, 60.0))
    ctx = {"cell": closed(4, 8), "seconds": 1.0, "untraced_s": 0.5,
           "records": slow_tail}
    assert metric("client_rtt_ms")(ctx) == pytest.approx(6.0, abs=1e-4)
    assert metric("client_rtt_ms")(dict(ctx, untraced_s=None)) > 6.0


def test_unanswered_requests_are_left_out_of_the_round_trip():
    rec = records(np.arange(0.0, 100.0, 1.0), 3.0)
    rec["done"][::2] = np.nan
    ctx = {"cell": closed(1, 4), "seconds": 0.1, "records": rec}
    assert metric("client_rtt_ms")(ctx) == pytest.approx(3.0, abs=1e-4)
    assert metric("client_rtt_ms")(dict(ctx, records=rec[:0])) is None


def test_open_loop_has_a_round_trip_too():
    rec = records(np.arange(0.0, 1000.0, 0.5), 4.0)
    ctx = {"cell": {"mix": {"loop": "open", "conns": 8, "rate_per_s": 2000.0}},
           "seconds": 1.0, "records": rec}
    assert metric("client_rtt_ms")(ctx) == pytest.approx(4.0, abs=1e-4)


def test_the_generators_share_has_no_metric_yet():
    """`sent` is stamped when the generator queues a request, so the cycle
    less the round trip is ~0 by construction: no metric is read from it
    until loadgen.cpp stamps at send() (PERF.md section 7)."""
    assert not os.path.exists(
        os.path.join(BENCH, "metrics", "client_turnaround_ms.json"))
    assert spec_of("client_rtt_ms").get("args", {}) == {}


@pytest.mark.parametrize("name", FRONT + CLIENT + STAGED)
def test_each_new_metric_is_in_the_manifest_as_its_file_has_it(name):
    manifest = harness._load_json(os.path.join(os.path.dirname(BENCH),
                                               "BENCHMARK.json"))
    entry = {m["name"]: m for m in manifest["per_layer"]}[name]
    spec = spec_of(name)
    for key in ("layer", "unit", "better", "source", "moves"):
        assert entry[key] == spec[key]
    assert entry.get("workloads") == spec.get("workloads")
    assert entry["moves"] == "checks_per_s"
    assert os.path.isfile(os.path.join(BENCH, "readers", spec["reader"] + ".py"))
