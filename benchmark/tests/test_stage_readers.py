"""The readers of the program's batch stage clock and its boot stamps, over
plain data; gap_split.py (a tool, no metric's reader) over known intervals,
over a small recorded capture and over a capture made on the CPU.  That a
traced run reports every per-layer metric is test_harness.py's to check."""

import json
import os
import subprocess
import sys

import pytest

import gap_split
import harness
import trace_reduce
from conftest import BENCH

STAGE_METRICS = {
    "cut_pickup_ms", "dispatch_plan_ms", "dispatch_encode_ms",
    "dispatch_launch_ms", "inflight_ms", "resolve_ms", "post_ms",
    "dispatcher_busy_pct", "readback_busy_pct"}
BOOT_METRICS = {"boot_backend_s", "boot_reconcile_s", "boot_warm_s"}


def metric(name):
    """The reader and arguments that benchmark/metrics/<name>.json names."""
    with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
        spec = json.load(f)
    reader = harness.load_module("readers", spec["reader"])
    return lambda ctx: reader.read(ctx, **spec["args"])


def stages_dv(threads=6, **stages):
    return {"native_frontend": {"dispatch_threads": threads, "stages": {
        s: dict(zip(("count", "sum_ns", "max_ns"), v)) for s, v in stages.items()}}}


# --- the stage clock's table ------------------------------------------------

def test_stage_means_and_busy_shares_read_window_deltas():
    ctx = {"untraced_s": 2.0,
           "vars0": stages_dv(pickup=(10, 1e6, 5e5), plan=(10, 2e6, 1),
                              encode=(8, 8e6, 1), launch=(8, 4e6, 1),
                              device=(8, 0, 0), resolve=(9, 9e6, 1),
                              post=(9, 1e6, 1)),
           "vars1": stages_dv(pickup=(110, 51e6, 5e5), plan=(110, 202e6, 1),
                              encode=(88, 168e6, 1), launch=(88, 84e6, 1),
                              device=(88, 1600e6, 0), resolve=(99, 99e6, 1),
                              post=(99, 361e6, 1))}
    assert metric("cut_pickup_ms")(ctx) == pytest.approx(0.5)
    assert metric("dispatch_plan_ms")(ctx) == pytest.approx(2.0)
    assert metric("dispatch_encode_ms")(ctx) == pytest.approx(2.0)
    assert metric("dispatch_launch_ms")(ctx) == pytest.approx(1.0)
    assert metric("inflight_ms")(ctx) == pytest.approx(20.0)
    assert metric("resolve_ms")(ctx) == pytest.approx(1.0)
    assert metric("post_ms")(ctx) == pytest.approx(4.0)
    # plan + encode + launch = 0.44 s of 2 s x 6 dispatcher threads
    assert metric("dispatcher_busy_pct")(ctx) == pytest.approx(100 * 0.44 / 12)
    # resolve + post = 0.45 s of 2 s on the one readback thread
    assert metric("readback_busy_pct")(ctx) == pytest.approx(22.5)


@pytest.mark.parametrize("name", sorted(STAGE_METRICS))
def test_stage_metric_of_a_program_without_the_table_reads_nothing(name):
    older = {"native_frontend": {"dispatch_threads": 6}}
    assert metric(name)({"untraced_s": 2.0, "vars0": older, "vars1": older}) is None
    still = stages_dv(**{s: (5, 5, 5) for s in (
        "pickup", "plan", "encode", "launch", "device", "resolve", "post")})
    quiet = {"untraced_s": 2.0, "vars0": still, "vars1": still}
    if name.endswith("_ms"):  # no batch between the scrapes: no mean
        assert metric(name)(quiet) is None
    else:
        assert metric(name)(quiet) == 0.0


# --- a value at a path ------------------------------------------------------

@pytest.mark.parametrize("name", sorted(BOOT_METRICS))
def test_boot_stamp_is_read_at_its_path(name):
    key = name[len("boot_"):]
    ctx = {"vars1": {"process": {"boot": {key: 12.345}}}}
    assert metric(name)(ctx) == 12.345
    assert metric(name)({"vars1": {"process": {"platform": "tpu"}}}) is None
    assert metric(name)({"vars1": {"process": {"boot": {key: "soon"}}}}) is None
    assert metric(name)({"vars1": None}) is None


# --- the gap split ----------------------------------------------------------

def span(stage, batch, start, dur, **more):
    return dict(stage=stage, batch=batch, start_ns=float(start),
                dur_ns=float(dur), **more)


def known_capture():
    """One device, three modules of 100 ns (runs 41, 42, 43) at 1000, 1400
    and 2000 on a device clock that reads 10 early: run 42 is enqueued at
    1405 on the host's clock and its completion callback begins at 1515, so
    the device plane has to move by 5 to 15, and is moved by 10: modules at
    1010, 1410 and 2010.  Run 41 was launched before the capture began.
    Batch 7 (run 42): cut at 1150 (a pickup mark at 1200 whose monotonic
    stamps lie 50 apart), launch span 1250-1450 with the enqueue inside it:
    the gap 1110-1410 is 40 no cut and 260 host.  Batch 8 (run 43): cut at
    1460 while run 42 still ran, launch span 1600-1900, enqueue at 1700:
    the gap 1510-2010 is 390 host and 110 runtime."""
    planes = [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [
            ("jit_k(1)", 1000.0, 100.0), ("jit_k(1)", 1400.0, 100.0),
            ("jit_k(1)", 2000.0, 100.0)]},
        {"name": "XLA Ops", "events": [
            ("%a = f()", 1000.0, 100.0), ("%a = f()", 1400.0, 100.0),
            ("%a = f()", 2000.0, 100.0)]}]}]
    modules = [dict(plane="/device:TPU:0", run_id=41 + k, start_ns=at, dur_ns=100.0)
               for k, at in enumerate((1000.0, 1400.0, 2000.0))]
    spans = [
        span("pickup", 7, 1200, 1, mono_ns=90050, flush_mono_ns=90000),
        span("plan", 7, 1201, 20), span("encode", 7, 1221, 29),
        span("launch", 7, 1250, 200), span("resolve", 7, 1520, 10),
        span("pickup", 8, 1500, 1, mono_ns=90340, flush_mono_ns=90300),
        span("plan", 8, 1501, 99), span("launch", 8, 1600, 300),
        span("resolve", 8, 2120, 10),
        span("resolve", 6, 1115, 10), span("post", 6, 1125, 10)]
    host = {"spans": spans, "enqueues": {"42": 1405.0, "43": 1700.0},
            "completions": {"42": 1515.0, "43": 2130.0}}
    return planes, modules, host


def test_gap_split_of_known_intervals():
    planes, modules, host = known_capture()
    found = gap_split.split_gaps(planes, modules, host)
    assert found["clock"] == pytest.approx(
        {"low_ms": 5e-6, "high_ms": 15e-6, "shift_ms": 10e-6})
    assert found["window_s"] == pytest.approx(1100e-9)
    assert found["no_cut_s"] == pytest.approx(40e-9)
    assert found["host_s"] == pytest.approx(650e-9)
    assert found["runtime_s"] == pytest.approx(110e-9)
    assert found["checks"] == {
        "launches": 2, "modules": 3, "before_capture": 1, "matched": 2,
        "after_capture": 0, "enqueue_in_launch": 2, "clock_bounds_cross": 0,
        "enqueue_before_launch": 0, "launch_begins_late": 0,
        "resolve_begins_early": 0}
    # a window asked for beyond the device's own events is idle time laid
    # before the first operation, whose launch the capture does not hold
    wider = gap_split.split_gaps(planes, modules, host, requested_s=2000e-9)
    assert wider["window_s"] == pytest.approx(2000e-9)
    assert wider["runtime_s"] == pytest.approx(1010e-9)
    assert wider["no_cut_s"] == found["no_cut_s"]
    assert wider["host_s"] == found["host_s"]


def test_gap_split_splits_nothing_that_one_clock_forbids():
    planes, modules, host = known_capture()

    def moved(**to):
        spans = [dict(s, start_ns=to[s["stage"]])
                 if s["batch"] == 8 and s["stage"] in to else s
                 for s in host["spans"]]
        return gap_split.split_gaps(planes, modules, dict(host, spans=spans))

    # batch 8's launch span begins at 2015, after its module began at 2010
    # and after its enqueue at 1700; run 42's enqueue still says which run
    # is whose
    late = moved(launch=2015.0)
    assert late["checks"]["launch_begins_late"] == 1
    assert late["checks"]["enqueue_before_launch"] == 1
    # its resolve span begins at 2050, before its module ended at 2110
    early = moved(resolve=2050.0)
    assert early["checks"]["resolve_begins_early"] == 1
    # a completion before the module's own end: the bounds of the offset cross
    crossed = gap_split.split_gaps(
        planes, modules, dict(host, completions={"42": 1504.0}))
    assert crossed["checks"]["clock_bounds_cross"] == 1
    assert crossed["clock"]["low_ms"] > crossed["clock"]["high_ms"]
    for found in (late, early, crossed):
        assert found["clock"] and found["window_s"] > 0
        assert not any(part + "_s" in found for part in gap_split.PARTS)


def test_gap_split_wants_most_enqueues_inside_their_launch():
    """Both enqueues made after their launch spans returned, as a
    `pjrt-tpu-tasks` thread makes a few: with none inside a span nothing
    says which run is whose; with one of two, the vote stands on it and is
    not borne out by most."""
    planes, modules, host = known_capture()
    assert gap_split.split_gaps(planes, modules, dict(
        host, enqueues={"42": 1455.0, "43": 1905.0})) is None
    found = gap_split.split_gaps(planes, modules, dict(
        host, enqueues={"42": 1405.0, "43": 1905.0}))
    assert found["checks"]["matched"] == 2
    assert found["checks"]["enqueue_in_launch"] == 1
    assert "host_s" not in found


def test_gap_split_reads_nothing_it_cannot_place():
    planes, modules, host = known_capture()
    assert gap_split.split_gaps(planes, modules, dict(host, spans=[])) is None
    assert gap_split.split_gaps([], modules, host) is None
    host_only = [dict(planes[0], name="/host:CPU")]
    assert gap_split.split_gaps(host_only, modules, host) is None
    # a runtime that numbers no run: nothing says which module is whose
    assert gap_split.split_gaps(planes, modules, dict(host, enqueues={})) is None


def idle_s(planes, found):
    """The idle seconds of trace_reduce's own window, which the three parts
    have to sum to."""
    reduced = trace_reduce.reduce_planes(planes, found["window_s"])
    ctx = {"trace": reduced}
    pct = harness.load_module("readers", "trace_idle").read(ctx)
    return pct / 100 * reduced["window_s"]


def test_gap_parts_sum_to_the_idle_share():
    planes, modules, host = known_capture()
    found = gap_split.split_gaps(planes, modules, host)
    assert sum(found[p + "_s"] for p in gap_split.PARTS) == pytest.approx(
        idle_s(planes, found))


def test_gap_parts_of_the_recorded_capture():
    """tests/data/capture_v5e_conditions200.json: the first eight launches
    of a capture of conditions-200.unique-sat on a v5e (PR 25), device
    operations and the host plane's program spans, enqueues and completions.
    Seven of its launches lie inside the cut; the device plane read 1.74 to
    2.00 ms early: as it stands, every module begins before the host call
    that queued it."""
    with open(os.path.join(BENCH, "tests", "data",
                           "capture_v5e_conditions200.json")) as f:
        rec = json.load(f)
    found = gap_split.split_gaps(rec["planes"], rec["modules"], rec["host"])
    known = rec["known"]
    for key in ("no_cut_s", "host_s", "runtime_s", "window_s"):
        assert found[key] == pytest.approx(known["split"][key])
    assert found["checks"] == {
        "launches": 7, "modules": 8, "before_capture": 0, "matched": 7,
        "after_capture": 1, "enqueue_in_launch": 7, "clock_bounds_cross": 0,
        "enqueue_before_launch": 0, "launch_begins_late": 0,
        "resolve_begins_early": 0}
    assert 1.7 < found["clock"]["low_ms"] < found["clock"]["high_ms"] < 2.1
    idle = idle_s(rec["planes"], found)
    assert idle == pytest.approx(known["window_s"] - known["busy_s"])
    assert abs(sum(found[p + "_s"] for p in gap_split.PARTS) - idle) < 1e-12
    assert found["host_s"] > found["runtime_s"] > 0


def test_capture_is_read_in_a_process_of_its_own(tmp_path):
    """A capture made on the CPU (a child writes two program spans under a
    profiler session): gap_split.py reads its host plane, finds the spans
    with their arguments and, there being no device plane, splits nothing."""
    make = (
        "import sys, time, jax.profiler as p\n"
        "o = p.ProfileOptions(); o.python_tracer_level = 0\n"
        "p.start_trace(sys.argv[1], profiler_options=o)\n"
        "now = time.monotonic_ns()\n"
        "with p.TraceAnnotation('atpu/native/pickup', batch=5, mono_ns=now,"
        " flush_mono_ns=now - 700): pass\n"
        "with p.TraceAnnotation('atpu/native/launch', batch=5): time.sleep(0.01)\n"
        "with p.TraceAnnotation('atpu/other', batch=5): pass\n"
        "p.stop_trace()\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    subprocess.run([sys.executable, "-c", make, str(tmp_path)], check=True,
                   env=env, timeout=120, capture_output=True)
    show = ("import sys, json, glob; sys.path.insert(0, sys.argv[1])\n"
            "import gap_split\n"
            "f = glob.glob(sys.argv[2] + '/**/*.xplane.pb', recursive=True)[-1]\n"
            "planes, modules, host = gap_split.read_capture(f)\n"
            "print(json.dumps([planes, modules, host]))\n")
    out = subprocess.run([sys.executable, "-c", show, BENCH, str(tmp_path)],
                         check=True, env=env, timeout=120, capture_output=True)
    planes, modules, host = json.loads(out.stdout.decode().strip().splitlines()[-1])
    assert planes == [] and modules == []
    assert [s["stage"] for s in host["spans"]] == ["pickup", "launch"]
    pickup, launch = host["spans"]
    assert pickup["batch"] == launch["batch"] == 5
    assert pickup["mono_ns"] - pickup["flush_mono_ns"] == 700
    assert launch["dur_ns"] >= 10e6 and launch["start_ns"] >= pickup["start_ns"]
    tool = subprocess.run(
        [sys.executable, os.path.join(BENCH, "gap_split.py"), str(tmp_path), "3.0"],
        check=True, env=env, timeout=120, capture_output=True)
    assert json.loads(tool.stdout.decode().strip().splitlines()[-1]) is None
