"""The native lane's batch stage clock (runtime/batch_stages.py) and what
carries it out of the process: /debug/vars, /debug/batches, the Prometheus
stage family, the profiler's host plane; the profile's new default; the
boot stamps; the kernel's named scopes.  CPU only: counts and names, no
device time."""

from __future__ import annotations

import asyncio
import glob
import os
import re
import threading
import time

import numpy as np
import pytest

from authorino_tpu.runtime.batch_stages import (FIELDS, RING, STAGES, STAMPS,
                                                StageClock)
from authorino_tpu.runtime.kernel_cost import LEDGER
from authorino_tpu.runtime.lane_select import HOST as L_HOST
from authorino_tpu.runtime.native_frontend import NativeFrontend

from test_native_frontend import (_native_available, build_engine, grpc_call,
                                  make_req)
from test_observability import build_engine as plain_engine, run

needs_native = pytest.mark.skipif(
    not _native_available(), reason="native frontend unavailable")

DEVICE_BATCH = STAGES
CACHE_ONLY = ("fill", "pickup", "plan", "resolve", "post")
HOST_LANE = ("fill", "pickup", "plan")


def counts(fe):
    return {s: v["count"] for s, v in fe.batch_stages.totals().items()}


def native_ledger(field):
    return (LEDGER.to_json().get("native") or {}).get(field, 0)


def settle(fe, stage, want, timeout_s=10.0):
    """`post` ends after the answer is on the wire: wait for its record."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline and counts(fe)[stage] < want:
        time.sleep(0.005)
    assert counts(fe)[stage] >= want, counts(fe)


@pytest.fixture()
def frontend():
    engine = build_engine()
    # lane selection off: a one-row cut must reach the device lane
    fe = NativeFrontend(engine, port=0, max_batch=16, window_us=500,
                        lane_select=False)
    port = fe.start()
    assert fe.wait_warm(300.0)
    try:
        yield fe, port, engine
    finally:
        fe.stop()


# ---------------------------------------------------------------------------
# the clock alone
# ---------------------------------------------------------------------------


def walk(clock, flush_ns=0, launch=True):
    # the front end hands both stamps or neither: first row, then the flush
    b = clock.begin(7, 3, 5, flush_ns, flush_ns - 500 if flush_ns else 0)
    with b.stage("plan"):
        pass
    if launch:
        with b.stage("encode"):
            pass
        with b.stage("launch"):
            pass
    b.ready()
    with b.stage("resolve"):
        pass
    with b.stage("post"):
        pass
    return b


@pytest.mark.parametrize("flush,launch,want", [
    (True, True, DEVICE_BATCH),
    (True, False, CACHE_ONLY),
    (False, True, tuple(s for s in STAGES if s not in ("fill", "pickup"))),
])
def test_clock_records_the_stages_that_ran(flush, launch, want):
    clock = StageClock("native")
    b = walk(clock, time.monotonic_ns() - 1000 if flush else 0, launch)
    totals = clock.totals()
    assert {s for s, v in totals.items() if v["count"]} == set(want)
    assert all(v["count"] in (0, 1) for v in totals.values())
    assert all(v["max_ns"] == v["sum_ns"] >= 0 for v in totals.values())
    taken = [t for t in b.t if t]
    assert taken == sorted(taken), "stamps run forward"
    if flush:
        assert totals["pickup"]["sum_ns"] >= 1000
        assert totals["fill"]["sum_ns"] == 500
        assert b.t[0] + 500 == b.t[1] < b.t[2], "first, flush, entry"


def test_ring_keeps_the_newest_batches_newest_first():
    clock = StageClock("native")
    for _ in range(RING + 5):
        walk(clock)
    whole = clock.to_json()
    assert whole["committed"] == RING + 5
    assert whole["fields"] == list(FIELDS)
    assert whole["fields"][-len(STAMPS):] == [s + "_ns" for s in STAMPS]
    seq = [row[0] for row in whole["batches"]]
    assert seq == list(range(RING + 5, 5, -1))
    newest = dict(zip(whole["fields"], clock.to_json(3)["batches"][0]))
    assert newest["seq"] == RING + 5 and newest["snap"] == 7
    assert newest["slot"] == 3 and newest["rows"] == 5
    assert newest["first_ns"] == newest["flush_ns"] == 0
    assert 0 < newest["entry_ns"] <= newest["posted_ns"]
    assert len(clock.to_json(3)["batches"]) == 3
    assert clock.to_json(0)["batches"] == []


def test_clock_counts_every_batch_under_contention():
    """Dispatcher threads record side by side: no update is lost."""
    import sys

    clock = StageClock("native")
    per, workers = 400, 12

    def work():
        for _ in range(per):
            b = clock.begin(1, 0, 1)
            with b.stage("plan"):
                pass

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work) for _ in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert clock.totals()["plan"]["count"] == per * workers


def test_drain_row_counts_drains_and_is_no_batch_stage():
    """`drain` is there from start-up with zeros, is fed by drains and not
    by batches, and has no column in the per-batch ring."""
    clock = StageClock("native")
    zero = {"count": 0, "sum_ns": 0, "max_ns": 0}
    assert clock.totals()["drain"] == zero
    assert list(clock.totals()) == list(STAGES) + ["drain"]
    for _ in range(3):
        walk(clock, time.monotonic_ns())
    assert clock.totals()["drain"] == zero
    assert clock.totals()["post"]["count"] == 3
    clock.record_drain(500)
    clock.record_drain(2000)
    assert clock.totals()["drain"] == {"count": 2, "sum_ns": 2500,
                                       "max_ns": 2000}
    assert clock.totals()["post"]["count"] == 3
    assert "drain" not in STAGES
    assert not any("drain" in f for f in FIELDS)
    assert len(clock.to_json(1)["batches"][0]) == len(FIELDS)


@needs_native
def test_every_drain_is_recorded_whoever_runs_it(frontend):
    """A registry read (a scrape), /debug/vars and the housekeeping cadence
    each run the drain and the row counts each; a batch runs none.  (The
    cadence may add one of its own anywhere in between.)"""
    from prometheus_client import REGISTRY

    from authorino_tpu.utils import metrics as metrics_mod

    fe, port, _ = frontend

    def drains():
        return fe.batch_stages.totals()["drain"]["count"]

    before = drains()
    REGISTRY.get_sample_value("auth_server_authconfig_total",
                              {"namespace": "ns", "authconfig": "fast-eq"})
    assert before + 1 <= drains() <= before + 2
    before = drains()
    seen = fe.debug_vars()["stages"]["drain"]  # the read drains, then reads
    assert before + 1 <= seen["count"] <= before + 2
    assert seen["sum_ns"] >= seen["max_ns"] > 0
    before, posts = drains(), counts(fe)["post"]
    for k in range(3):
        grpc_call(port, make_req("fast-eq.test",
                                 headers={"x-org": f"drain-row-{k}"}))
    settle(fe, "post", posts + 3)
    assert drains() <= before + 1
    before = drains()
    fe.hist_drain_s = 0.02  # the housekeeping thread's cadence, shortened
    deadline = time.monotonic() + 10
    while drains() < before + 3 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert drains() >= before + 3
    fe.stop()
    assert fe._observe_drain not in metrics_mod.DRAIN_OBSERVERS


def test_clock_cost_a_batch_is_small():
    """All eight stages of one batch, no profiler session: tens of
    microseconds at most (PERF.md section 6 gives the reading; the bound
    here only catches a clock that grew work per row or took a slow path)."""
    clock = StageClock("native")
    n = 2000
    walk(clock, time.monotonic_ns())
    t0 = time.perf_counter()
    for _ in range(n):
        walk(clock, time.monotonic_ns())
    per_batch_us = (time.perf_counter() - t0) / n * 1e6
    print(f"stage clock: {per_batch_us:.1f} us a batch")
    assert per_batch_us < 500


# ---------------------------------------------------------------------------
# the served lane
# ---------------------------------------------------------------------------


@needs_native
def test_each_lane_records_its_stages_once_a_batch(frontend):
    fe, port, _ = frontend
    req = make_req("fast-eq.test", headers={"x-org": "stage-clock"})
    before, b0, l0 = counts(fe), native_ledger("batches"), native_ledger("launches")

    grpc_call(port, req)  # a new row: rides the device
    settle(fe, "post", before["post"] + 1)
    after = counts(fe)
    assert {s: after[s] - before[s] for s in STAGES} == dict.fromkeys(STAGES, 1)

    grpc_call(port, req)  # the same row: the verdict cache answers it
    settle(fe, "post", after["post"] + 1)
    cached = counts(fe)
    assert {s: cached[s] - after[s] for s in STAGES} == {
        s: int(s in CACHE_ONLY) for s in STAGES}

    # the lane's ledger counts the same batches and launches
    assert cached["resolve"] - before["resolve"] == native_ledger("batches") - b0 == 2
    assert cached["launch"] - before["launch"] == native_ledger("launches") - l0 == 1

    fe.lanes.enabled = True
    fe.lanes.decide = lambda *a, **k: (L_HOST, "test")
    grpc_call(port, make_req("fast-eq.test", headers={"x-org": "host-lane"}))
    settle(fe, "plan", cached["plan"] + 1)
    hosted = counts(fe)
    assert {s: hosted[s] - cached[s] for s in STAGES} == {
        s: int(s in HOST_LANE) for s in STAGES}

    totals = fe.batch_stages.totals()
    for s in STAGES:
        assert totals[s]["sum_ns"] >= totals[s]["max_ns"] >= 0
    ring = fe.batch_stages.to_json(2)
    newest, older = (dict(zip(ring["fields"], row)) for row in ring["batches"])
    assert newest["seq"] > older["seq"]
    assert older["device_rows"] == 1 and older["pad"] >= 1
    assert newest["device_rows"] == 0 and newest["pad"] == 0  # cache-only
    stamps = [older[s + "_ns"] for s in STAMPS]
    assert all(stamps), "a device batch takes all nine stamps"
    assert stamps == sorted(stamps)
    # the ring rides every flight bundle, whatever triggers it
    from authorino_tpu.runtime.flight_recorder import RECORDER

    held = RECORDER.bundle("test")["vars"]["native_batches"]
    assert held["fields"] == list(FIELDS) and held["batches"][0][0] == newest["seq"]


@needs_native
def test_native_stage_family_on_metrics(frontend):
    from prometheus_client import REGISTRY

    fe, port, _ = frontend

    def sample(stage):
        return REGISTRY.get_sample_value(
            "auth_server_pipeline_stage_seconds_count",
            {"lane": "native", "stage": stage}) or 0.0

    before = {s: sample(s) for s in STAGES}
    n0 = counts(fe)["post"]
    grpc_call(port, make_req("fast-eq.test", headers={"x-org": "family"}))
    settle(fe, "post", n0 + 1)
    assert {s: sample(s) - before[s] for s in STAGES} == dict.fromkeys(STAGES, 1.0)


@needs_native
def test_debug_surface_carries_stages_boot_profile_and_batches(frontend):
    from aiohttp.test_utils import TestClient, TestServer

    from authorino_tpu.service.http_server import build_app
    from authorino_tpu.utils.jax_env import boot_stamp

    fe, port, engine = frontend
    boot_stamp("backend_s")
    n0 = counts(fe)["post"]
    for org in ("a", "b", "c"):
        grpc_call(port, make_req("fast-eq.test", headers={"x-org": org}))
    settle(fe, "post", n0 + 3)

    async def body():
        client = TestClient(TestServer(
            build_app(engine, frontend=fe, enable_profile=True)))
        await client.start_server()
        try:
            quiet = await (await client.get("/debug/vars")).json()
            capture = asyncio.ensure_future(
                client.get("/debug/profile?seconds=0.5"))
            during = None
            for _ in range(100):
                await asyncio.sleep(0.02)
                during = await (await client.get("/debug/vars")).json()
                if "profile" in during["process"]:
                    break
            answer = await (await capture).json()
            after = await (await client.get("/debug/vars")).json()
            batches = await (await client.get("/debug/batches?n=2")).json()
            bad = (await client.get("/debug/batches?n=x")).status
            return quiet, during, answer, after, batches, bad
        finally:
            await client.close()

    quiet, during, answer, after, batches, bad = run(body())
    assert set(quiet["native_frontend"]["stages"]) == set(STAGES) | {"drain"}
    assert quiet["native_frontend"]["stages"]["post"]["count"] >= 3
    proc = quiet["process"]
    assert abs(proc["time_ns"] * 1e-9 - proc["time"]) < 1.0
    assert proc["boot"]["backend_s"] > 0
    assert isinstance(proc["device_memory"], list)
    assert "profile" not in proc and "profile" not in after["process"]
    live = during["process"]["profile"]
    assert live["trace_dir"] == answer["trace_dir"]
    assert 0 <= during["process"]["time_ns"] - live["started_unix_ns"] < 5e9
    assert bad == 400
    seqs = [row[0] for row in batches["batches"]]
    assert len(seqs) == 2 and seqs[0] > seqs[1]


@pytest.mark.parametrize("query,level", [
    ("", 0), ("&python=0", 0), ("&python=1", 1)])
def test_profile_leaves_the_python_tracer_off_unless_asked(monkeypatch, query, level):
    import jax.profiler
    from aiohttp.test_utils import TestClient, TestServer

    from authorino_tpu.service.http_server import build_app

    seen = {}
    monkeypatch.setattr(
        jax.profiler, "start_trace",
        lambda d, **kw: seen.update(kw, dir=d))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: seen.update(stopped=True))

    async def body():
        client = TestClient(TestServer(
            build_app(plain_engine(), enable_profile=True)))
        await client.start_server()
        try:
            resp = await client.get("/debug/profile?seconds=0.1" + query)
            return resp.status, await resp.json()
        finally:
            await client.close()

    status, js = run(body())
    assert status == 200 and seen["stopped"]
    assert seen["profiler_options"].python_tracer_level == level
    assert js["python_tracer_level"] == level


@needs_native
def test_capture_holds_the_native_spans_with_their_batch(frontend, tmp_path):
    import jax.profiler

    fe, port, _ = frontend
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    n0 = counts(fe)["post"]
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        grpc_call(port, make_req("fast-eq.test", headers={"x-org": "traced"}))
        settle(fe, "post", n0 + 1)
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    assert found
    spans = {}
    for plane in jax.profiler.ProfileData.from_file(found[-1]).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("atpu/native/"):
                    spans.setdefault(e.name.rsplit("/", 1)[1], []).append(
                        dict(e.stats))
    # `fill` ran in C++ before the batch had a number and `device` crosses
    # threads: neither has a span of its own
    assert set(spans) == set(STAGES) - {"fill", "device"}, sorted(spans)
    seq = {s["batch"] for stage in spans.values() for s in stage}
    assert len(seq) == 1, "one batch, one sequence number on every span"
    pickup = spans["pickup"][0]
    assert (0 < pickup["first_mono_ns"] <= pickup["flush_mono_ns"]
            <= pickup["mono_ns"] <= time.monotonic_ns())


# ---------------------------------------------------------------------------
# the kernel's scopes
# ---------------------------------------------------------------------------


@needs_native
def test_lowered_kernel_carries_its_phase_scopes(frontend):
    import jax.numpy as jnp

    from authorino_tpu.compiler.intern import PAD
    from authorino_tpu.compiler.pack import wire_dtype
    from authorino_tpu.ops.pattern_eval import eval_bitpacked_jit

    fe, _, _ = frontend
    rec = fe._cur_rec
    policy, dt = rec.policy, wire_dtype(rec.policy)
    pad, eff = 4, 8
    nb = max(policy.n_byte_attrs, 1)
    hlo = eval_bitpacked_jit.lower(
        rec.params,
        jnp.zeros((pad, policy.n_attrs), dtype=dt),
        jnp.full((pad, policy.n_member_attrs, policy.members_k), PAD, dtype=dt),
        jnp.zeros((pad, policy.n_own_cpu), dtype=bool),
        jnp.zeros((pad,), dtype=np.int32),
        jnp.zeros((pad, nb, eff), dtype=np.uint8),
        jnp.zeros((pad, nb), dtype=bool),
    ).compile().as_text()
    # what a device trace's events carry: op_name, the scopes in it
    names = set(re.findall(r'op_name="([^"]+)"', hlo))
    for scope in ("own_gather", "own_leaf_compares", "membership", "dfa_scan",
                  "own_circuit", "bitpack"):
        assert any(n.startswith("jit(eval_bitpacked_jit)/pattern_eval/")
                   and f"/{scope}/" in n for n in names), scope
