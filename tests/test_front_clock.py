"""The loop clock of the C++ front end's one epoll thread
(native/frontend.cpp "The loop clock", docs/observability.md "The front
end's loop clock"), the `fill` stamp it hands the batch stage clock, the
exact sums of the three per-request stage histograms, and what carries them
out of the process.  CPU only: counts, names and the clock's own arithmetic,
never a device time."""

from __future__ import annotations

import json
import os
import re
import socket
import threading
import time

import grpc
import pytest

from authorino_tpu.runtime.batch_stages import STAGES
from authorino_tpu.runtime.native_frontend import NativeFrontend
from authorino_tpu.utils import metrics as metrics_mod

from test_batch_stages import counts, native_ledger, settle
from test_native_frontend import (_native_available, build_engine, grpc_call,
                                  make_req)

pytestmark = pytest.mark.skipif(
    not _native_available(), reason="native frontend unavailable")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROW = {"count", "sum_ns", "max_ns"}
COUNTERS = {"send_blocked", "cuts_deferred"}
STAGE_OF_ROW = {"req_wait": "wait", "req_exec": "exec",
                "req_respond": "respond"}
# counts alone: no time of their own
SCAN_COUNTS = ("ovf_dfas", "ovf_loads", "req_bytes", "req_headers", "msg_inplace",
               "cut_timer")
# the syscalls' own time, each inside a phase: `recv` in `read`, `send` in
# `write`
SYSCALL_OF_PHASE = {"read": "recv", "write": "send"}


@pytest.fixture(scope="module")
def frontend():
    engine = build_engine()
    fe = NativeFrontend(engine, port=0, max_batch=16, window_us=500,
                        lane_select=False)
    port = fe.start()
    assert fe.wait_warm(300.0)
    try:
        yield fe, port
    finally:
        fe.stop()


def clock(fe):
    return fe._mod.fe_loop_clock()


def row_of(table, row):
    """A phase of the thread (`phases`) or one of the rows that are not
    (`rows`: `turn` and the per-request `req_*`): the call splits them."""
    return table["phases"].get(row) or table["rows"][row]


def wall(table):
    return sum(r["sum_ns"] for r in table["phases"].values())


def delta(after, before, row, field="count"):
    return row_of(after, row)[field] - row_of(before, row)[field]


def quiet(fe, want_answers, timeout_s=10.0):
    """Every answer submitted and written: the thread sits in epoll_wait."""
    deadline = time.monotonic() + timeout_s
    while (clock(fe)["phases"]["respond"]["count"] < want_answers
           and time.monotonic() < deadline):
        time.sleep(0.005)
    time.sleep(0.05)
    return clock(fe)


def health_call(port):
    with grpc.insecure_channel(f"127.0.0.1:{port}") as ch:
        call = ch.unary_unary("/grpc.health.v1.Health/Check",
                              request_serializer=lambda b: b,
                              response_deserializer=lambda b: b)
        return call(b"", timeout=10)


def fast_req(tag):
    return make_req("fast-eq.test", headers={"x-org": f"front-clock-{tag}"})


# ---------------------------------------------------------------------------
# the table
# ---------------------------------------------------------------------------


def test_table_splits_the_threads_phases_from_the_rows_that_are_not(frontend):
    fe, _ = frontend
    table = clock(fe)
    assert set(table) == {"phases", "rows", "counters", "slow_turns",
                          "mark_mono_ns"}
    assert all(set(row) == ROW for part in ("phases", "rows")
               for row in table[part].values())
    assert set(table["counters"]) == COUNTERS
    assert list(table["phases"]) == ["idle", "read", "parse", "encode",
                                     "ovf_scan", "cut", "respond", "write",
                                     "other"]
    assert set(table["rows"]) == ({"turn"} | set(STAGE_OF_ROW) | set(SCAN_COUNTS)
                                  | set(SYSCALL_OF_PHASE.values()))
    assert 0 < table["mark_mono_ns"] <= time.monotonic_ns()


def test_phases_add_up_to_the_threads_wall_time(frontend):
    """Between two stamps every nanosecond is charged to one phase: the sum
    over the thread's own phases is the distance between its marks, to the
    nanosecond when it is at rest; against this process's clock the two
    reads' open intervals stay inside 2 %."""
    fe, port = frontend
    best = None
    for attempt in range(3):
        grpc_call(port, fast_req(f"wall-a{attempt}"))  # it stamped a moment ago
        a, t0 = clock(fe), time.monotonic_ns()
        for k in range(10):
            grpc_call(port, fast_req(f"wall-{attempt}-{k}"))
            time.sleep(0.03)
        time.sleep(0.6)  # 100 ms time-outs: idle is stamped at each
        grpc_call(port, fast_req(f"wall-b{attempt}"))
        b, t1 = clock(fe), time.monotonic_ns()
        spent = wall(b) - wall(a)
        err = abs(spent - (t1 - t0)) / (t1 - t0)
        best = err if best is None else min(best, err)
        if best <= 0.02:
            break
    assert best <= 0.02, best
    a = quiet(fe, 0)
    time.sleep(0.25)
    b = clock(fe)
    # `mark_mono_ns` is the thread's last stamp: between two of them the
    # table is whole, which is what a reader of two scrapes divides by
    assert wall(b) - wall(a) == b["mark_mono_ns"] - a["mark_mono_ns"] > 0
    # the busy stretches are the phases but idle
    assert wall(b) - b["phases"]["idle"]["sum_ns"] == b["rows"]["turn"]["sum_ns"]
    assert 0 <= b["phases"]["idle"]["count"] - b["rows"]["turn"]["count"] <= 1


def test_recv_and_send_lie_inside_read_and_write(frontend):
    """The two syscalls are rows, not phases: with them in the table the
    phases still add up to the thread's wall time to the nanosecond, and
    each syscall's time lies inside its phase's, one call a count of it."""
    fe, port = frontend
    a = quiet(fe, 0)
    for k in range(8):
        grpc_call(port, fast_req(f"syscalls-{k}"))
    health_call(port)
    b = quiet(fe, a["phases"]["respond"]["count"] + 8)
    assert not set(SYSCALL_OF_PHASE.values()) & set(b["phases"])
    assert wall(b) - wall(a) == b["mark_mono_ns"] - a["mark_mono_ns"] > 0
    for table in (b, None):  # over the window, and over the whole run
        for phase, call in SYSCALL_OF_PHASE.items():
            if table is None:
                inside, outside = b["rows"][call], b["phases"][phase]
                assert inside["sum_ns"] <= outside["sum_ns"]
                assert inside["count"] == outside["count"]
                continue
            assert 0 < delta(b, a, call, "sum_ns") <= delta(b, a, phase, "sum_ns")
            assert delta(b, a, call) == delta(b, a, phase) > 0
    # grpcio sends each message whole in one DATA frame: read where it lay
    assert 0 < delta(b, a, "msg_inplace") <= delta(b, a, "parse") == 8


def test_counts_are_the_front_ends_own_counters(frontend):
    fe, port = frontend
    a, s0, fills = quiet(fe, 0), dict(fe._mod.fe_stats()), counts(fe)["fill"]
    for k in range(6):
        grpc_call(port, fast_req(f"count-{k}"))
    grpc_call(port, make_req("nobody.test"))  # not found: a direct answer
    grpc_call(port, make_req("slow-tmpl.test", method="POST", path="/x"))
    b = quiet(fe, a["phases"]["respond"]["count"] + 7)
    s1 = dict(fe._mod.fe_stats())
    moved = {k: s1[k] - s0[k] for k in s1}
    assert moved["notfound"] == 1 and moved["fast"] + moved["slow"] >= 7
    # every Check request is parsed once, whatever lane takes it (a hybrid
    # row the kernel passed counts as fast and, handed on, as slow)
    assert delta(b, a, "parse") == 8 == (
        moved["fast"] + moved["notfound"] + moved["slow"] - moved["hybrid"])
    assert delta(b, a, "encode") == moved["fast"] >= 6
    # batch rows and the slow lane's answers come back through drain_done;
    # the direct answer does not
    assert delta(b, a, "respond") == 7
    assert delta(b, a, "other") >= 1
    # a cut flushed is a cut the stage clock began: its `fill`
    cuts = delta(b, a, "cut")
    settle(fe, "fill", fills + cuts)
    assert cuts == counts(fe)["fill"] - fills >= 1
    # one request at a time: every slot was cut part-full, by the window
    assert delta(b, a, "cut_timer") == cuts
    assert delta(b, a, "read") >= 8 and delta(b, a, "write") >= 8
    for row in b["phases"].values():
        assert row["sum_ns"] >= row["max_ns"] >= 0
    assert b["counters"] == a["counters"]  # nobody was held back


def test_cut_timer_counts_the_cuts_the_window_made(frontend):
    """40 Checks in one write: two slots of 16 are cut full as the rows come,
    and the window's timer cuts the last 8."""
    from test_h2_framer import Client, allow, request

    fe, port = frontend
    a = quiet(fe, 0)
    c = Client(port)
    try:
        c.send(b"".join(request(1 + 2 * k, allow(f"burst-{k}")) for k in range(40)))
        assert all(c.answer(1 + 2 * k)[1].status.code == 0 for k in range(40))
    finally:
        c.close()
    b = quiet(fe, a["phases"]["respond"]["count"] + 40)
    assert delta(b, a, "encode") == 40
    assert (delta(b, a, "cut"), delta(b, a, "cut_timer")) == (3, 1)


def test_ovf_scan_counts_the_rows_the_batch_events_carry(frontend):
    fe, port = frontend
    a, l0 = quiet(fe, 0), native_ledger("dfa_ovf_rows")
    posts = counts(fe)["post"]
    long_path = "/api/v2/ok/" + "x" * 290  # past every class's byte width
    for k in range(3):
        grpc_call(port, make_req("fast-rx.test", path=f"{long_path}{k}"))
    grpc_call(port, make_req("fast-rx.test", path="/api/v2/ok/short"))
    settle(fe, "post", posts + 4)
    fe._fold_kept()
    b = clock(fe)
    assert delta(b, a, "ovf_scan") == native_ledger("dfa_ovf_rows") - l0 == 3
    assert delta(b, a, "ovf_scan", "sum_ns") > 0
    assert delta(b, a, "encode") == 4
    # the config's one DFA entered once a scanned row; `^/api/v[0-9]+/ok`
    # has no end anchor, so its accept absorbs after the tenth byte
    assert delta(b, a, "ovf_dfas") == 3
    assert delta(b, a, "ovf_loads") == 3 * len("/api/v2/ok")
    assert all(row_of(b, r)["sum_ns"] == row_of(b, r)["max_ns"] == 0
               for r in SCAN_COUNTS)


def test_req_bytes_and_req_headers_count_what_a_request_carried(frontend):
    """ISSUE 38: two counts a Check request beside `parse`'s own, the bytes
    of its CheckRequest message and the headers parsed out of it, so that a
    cell's requests can be set beside another's (231-367 bytes and 3-10
    headers in the old cells, 1.2-2.5 KB and 18-30 behind an Envoy edge)."""
    fe, port = frontend
    a = quiet(fe, 0)
    reqs = [make_req("fast-eq.test", headers={"x-org": "acme"}),
            make_req("fast-rx.test", path="/api/v2/ok/" + "y" * 500, headers={
                f"x-h{j}": "v" * (10 + j) for j in range(20)})]
    for req in reqs:
        grpc_call(port, req)
    b = quiet(fe, 0)
    assert delta(b, a, "parse") == len(reqs)
    assert delta(b, a, "req_bytes") == sum(len(r.SerializeToString()) for r in reqs)
    assert delta(b, a, "req_headers") == sum(
        len(r.attributes.request.http.headers) for r in reqs)


# ---------------------------------------------------------------------------
# the three per-request stages: exact sums beside the buckets
# ---------------------------------------------------------------------------


def hist(stage):
    from prometheus_client import REGISTRY

    name = "auth_server_frontend_stage_duration_seconds"
    buckets = [REGISTRY.get_sample_value(
        name + "_bucket", {"stage": stage, "le": le}) or 0.0
        for le in [repr(float(b)) for b in metrics_mod.STAGE_BUCKETS] + ["+Inf"]]
    return (buckets,
            REGISTRY.get_sample_value(name + "_sum", {"stage": stage}) or 0.0,
            REGISTRY.get_sample_value(name + "_count", {"stage": stage}) or 0.0)


@pytest.mark.parametrize("row", sorted(STAGE_OF_ROW))
def test_request_stage_sum_is_exact_and_lies_inside_its_buckets(frontend, row):
    fe, port = frontend
    stage = STAGE_OF_ROW[row]
    fe.drain_histograms()
    a, (cum0, sum0, n0) = quiet(fe, 0), hist(stage)
    for k in range(5):
        grpc_call(port, fast_req(f"{row}-{k}"))
    b = quiet(fe, a["phases"]["respond"]["count"] + 5)
    fe.drain_histograms()
    cum1, sum1, n1 = hist(stage)
    n, total_ns = delta(b, a, row), delta(b, a, row, "sum_ns")
    assert n == n1 - n0 == 5
    # the histogram's _sum is the table's sum, not an estimate from midpoints
    assert sum1 - sum0 == pytest.approx(total_ns * 1e-9, rel=1e-9, abs=1e-12)
    per = [c1 - c0 for c0, c1 in zip(cum0, cum1)]
    per = [per[0]] + [hi - lo for lo, hi in zip(per, per[1:])]
    edges = [0.0] + list(metrics_mod.STAGE_BUCKETS)
    assert sum(per) == 5
    low = sum(k * edges[i] for i, k in enumerate(per))
    high = (float("inf") if per[-1] else
            sum(k * edges[i + 1] for i, k in enumerate(per[:-1])))
    assert low <= total_ns * 1e-9 <= high
    assert b["rows"][row]["max_ns"] * 5 >= total_ns


def test_stage_hist_hands_out_what_the_rows_gained(frontend):
    fe, port = frontend
    fe.hist_drain_s, keep = 3600.0, fe.hist_drain_s  # no drain beside this one
    try:
        fe._last_hist_drain = time.monotonic()
        fe._mod.fe_stage_hist()
        a = quiet(fe, 0)
        for k in range(4):
            grpc_call(port, fast_req(f"hand-{k}"))
        b = quiet(fe, a["phases"]["respond"]["count"] + 4)
        got = fe._mod.fe_stage_hist()
        assert set(got) == {"wait", "exec", "respond", "bounds_ns", "sum_ns"}
        for row, stage in STAGE_OF_ROW.items():
            assert sum(got[stage]) == delta(b, a, row) == 4
            assert got["sum_ns"][stage] == delta(b, a, row, "sum_ns")
        again = fe._mod.fe_stage_hist()
        assert set(again["sum_ns"].values()) == {0}
    finally:
        fe.hist_drain_s = keep


def test_no_midpoint_estimate_is_left():
    src = open(os.path.join(ROOT, "authorino_tpu", "runtime",
                            "native_frontend.py")).read()
    body = src[src.index("def drain_histograms"):src.index("def _dispatch_loop")]
    assert "mids" not in body and "est_sum" not in body
    assert 'stages["sum_ns"][stage]' in body


# ---------------------------------------------------------------------------
# `fill`: the stage in front of `pickup`
# ---------------------------------------------------------------------------


def test_a_cuts_stamps_are_ordered_and_fill_reaches_every_sink(frontend):
    from prometheus_client import REGISTRY

    fe, port = frontend

    def sample():
        return REGISTRY.get_sample_value(
            "auth_server_pipeline_stage_seconds_count",
            {"lane": "native", "stage": "fill"}) or 0.0

    assert STAGES[:2] == ("fill", "pickup")
    before, fam0, posts = counts(fe), sample(), counts(fe)["post"]
    a = quiet(fe, 0)
    grpc_call(port, fast_req("fill"))
    settle(fe, "post", posts + 1)
    after = fe.batch_stages.totals()
    assert after["fill"]["count"] == before["fill"] + 1
    assert after["pickup"]["count"] == before["pickup"] + 1
    assert sample() == fam0 + 1
    ring = fe.batch_stages.to_json(1)
    newest = dict(zip(ring["fields"], ring["batches"][0]))
    assert 0 < newest["first_ns"] <= newest["flush_ns"] <= newest["entry_ns"]
    # one row, cut by the timer: the cut filled for about the window, and
    # the row's `wait` is the same interval on the same two stamps
    b = clock(fe)
    assert newest["flush_ns"] - newest["first_ns"] == delta(b, a, "req_wait", "sum_ns")
    assert newest["flush_ns"] - newest["first_ns"] >= fe.window_us * 1000 * 0.5
    assert fe.debug_vars()["stages"]["fill"]["count"] == after["fill"]["count"]


# ---------------------------------------------------------------------------
# what carries the clock out
# ---------------------------------------------------------------------------


def test_debug_vars_passes_the_table_on(frontend):
    fe, _ = frontend
    front = fe.debug_vars()["front"]
    assert set(front) == set(clock(fe))
    assert front["phases"]["idle"]["count"] > 0
    json.dumps(front)  # JSON-safe as it stands


def test_loop_seconds_family_carries_every_phase_after_one_drain(frontend):
    from prometheus_client import REGISTRY

    fe, port = frontend

    def drained():
        """One drain of this front end, then what it has folded so far (its
        own ledger of the table's sums, as its drain last read them) beside
        the process's family, with no other drain of it in between."""
        fe.drain_native_stats()
        with fe._drain_lock:
            table = clock(fe)
            folded = {p: fe._stats_drain._last[("loop", p)]
                      for p in table["phases"]}
            family = {p: REGISTRY.get_sample_value(
                "auth_server_frontend_loop_seconds_total", {"phase": p})
                for p in (*table["phases"], *table["rows"])}
        return folded, family

    grpc_call(port, fast_req("family"))
    a = clock(fe)
    folded0, first = drained()
    assert {p for p, v in first.items() if v is not None} == set(a["phases"])
    time.sleep(0.25)
    grpc_call(port, fast_req("family-2"))
    quiet(fe, 0)
    folded1, second = drained()
    b = clock(fe)
    for phase in a["phases"]:
        # deltas, not absolutes: the family gained what this front end's
        # drains read the table to have gained, to the float's rounding
        assert second[phase] - first[phase] == pytest.approx(
            (folded1[phase] - folded0[phase]) * 1e-9, abs=1e-6), phase
        # and what a drain reads is the table: it lies between the readings
        # taken before and after it
        assert (row_of(a, phase)["sum_ns"] <= folded0[phase] <= folded1[phase]
                <= row_of(b, phase)["sum_ns"]), phase
    assert folded1["idle"] - folded0["idle"] >= 0.2e9
    assert second["idle"] >= 0.25


# ---------------------------------------------------------------------------
# slow turns
# ---------------------------------------------------------------------------


def check_stub(ch):
    from authorino_tpu import protos

    pb = protos.external_auth_pb2
    return ch.unary_unary(
        "/envoy.service.auth.v3.Authorization/Check",
        request_serializer=pb.CheckRequest.SerializeToString,
        response_deserializer=pb.CheckResponse.FromString)


def test_a_turn_held_past_5_ms_lands_in_slow_turns(frontend):
    """No hook holds the thread: a request whose path is 4 MiB and keeps
    the config's DFA alive to its end (digits inside `v[0-9]+`: no state on
    the way absorbs) makes the host scan of its overflowed value a slow
    callback by itself (4 Mi dependent table steps do not fit in 5 ms)."""
    fe, port = frontend
    a = quiet(fe, 0)
    time.sleep(0.35)  # an idle server: three 100 ms time-outs, none slow
    assert clock(fe)["slow_turns"] == a["slow_turns"]
    t0 = time.monotonic_ns()
    with grpc.insecure_channel(
            f"127.0.0.1:{port}",
            options=[("grpc.max_send_message_length", -1)]) as ch:
        check_stub(ch)(make_req("fast-rx.test",
                                path="/api/v2" + "7" * (4 << 20)),
                       timeout=30)
    t1 = time.monotonic_ns()
    b = quiet(fe, a["phases"]["respond"]["count"] + 1)
    assert delta(b, a, "ovf_scan") == 1
    assert b["phases"]["ovf_scan"]["max_ns"] > 5_000_000
    new = b["slow_turns"][len(a["slow_turns"]):] if len(
        b["slow_turns"]) < 64 else b["slow_turns"][-4:]
    held = [t for t in new if t["requests"] == 1]
    assert len(held) == 1, new
    turn = held[0]
    assert set(turn) == {"wake_mono_ns", "idle_ns", "busy_ns", "events",
                         "requests", "answers"}
    assert turn["busy_ns"] > b["phases"]["ovf_scan"]["max_ns"]
    assert t0 <= turn["wake_mono_ns"] <= t1 and turn["events"] >= 1
    assert b["rows"]["turn"]["max_ns"] >= turn["busy_ns"]
    grpc_call(port, fast_req("after-slow"))  # a turn of the usual length
    later = quiet(fe, b["phases"]["respond"]["count"] + 1)["slow_turns"]
    assert later == b["slow_turns"]


def test_a_long_idle_with_rows_owed_lands_in_slow_turns(frontend, monkeypatch):
    """The readback side (here held 150 ms before it completes a cut) keeps
    a cut: the thread idles with encoded rows unanswered, and says so."""
    fe, port = frontend
    a = quiet(fe, 0)
    posts = counts(fe)["post"]
    complete = fe._complete_device_batch

    def held(*args, **kwargs):
        time.sleep(0.15)
        return complete(*args, **kwargs)

    monkeypatch.setattr(fe, "_complete_device_batch", held)
    grpc_call(port, fast_req("owed"))
    monkeypatch.undo()
    settle(fe, "post", posts + 1)
    b = quiet(fe, 0)
    new = b["slow_turns"][len(a["slow_turns"]):]
    waited = [t for t in new if t["idle_ns"] > 50_000_000]
    assert waited, new  # the 100 ms time-out inside the hold, at least
    assert all(t["requests"] == 0 and t["busy_ns"] < t["idle_ns"] for t in waited)
    # the cut is completed: nothing is owed, and an idle server's time-outs
    # are quiet again (what is owed moves where pending_batches does)
    time.sleep(0.35)
    assert clock(fe)["slow_turns"] == b["slow_turns"]


# ---------------------------------------------------------------------------
# the two counters: who holds the thread back
# ---------------------------------------------------------------------------


def test_a_full_pipeline_defers_the_timers_cut_and_fill_shows_it(
        frontend, monkeypatch):
    """Six cuts held uncompleted: the timer's next cut is held back
    (`cuts_deferred`), and its `fill` passes the window by the time held."""
    fe, port = frontend
    a, posts = quiet(fe, 0), counts(fe)["post"]
    gate, complete = threading.Event(), fe._complete_device_batch

    def held(*args, **kwargs):
        gate.wait(20)
        return complete(*args, **kwargs)

    monkeypatch.setattr(fe, "_complete_device_batch", held)
    try:
        with grpc.insecure_channel(f"127.0.0.1:{port}") as ch:
            call, calls = check_stub(ch), []
            for k in range(7):  # one cut each: the window is 0.5 ms
                calls.append(call.future(fast_req(f"defer-{k}"), timeout=30))
                time.sleep(0.02)
            time.sleep(0.05)
            b = clock(fe)
            assert delta(b, a, "cut") == 6 and delta(b, a, "encode") == 7
            assert b["counters"]["cuts_deferred"] > a["counters"]["cuts_deferred"]
            monkeypatch.undo()
            gate.set()
            assert all(c.result() is not None for c in calls)
    finally:
        gate.set()
    settle(fe, "post", posts + 7)
    ring = fe.batch_stages.to_json(7)
    fills = [dict(zip(ring["fields"], bt)) for bt in ring["batches"]]
    longest = max(f["flush_ns"] - f["first_ns"] for f in fills)
    assert longest > 50_000_000 > 10 * fe.window_us * 1000
    c = quiet(fe, a["phases"]["respond"]["count"] + 7)
    assert delta(c, a, "cut") == 7


def test_a_peer_that_does_not_read_blocks_the_send(frontend):
    """A hand-rolled HTTP/2 client that sends Checks and never reads: the
    kernel's buffers fill, `send` gives EAGAIN, and the counter says whose
    time it is.  The server goes on answering the others."""
    fe, port = frontend
    a = quiet(fe, 0)
    method = b"/envoy.service.auth.v3.Authorization/Check"
    hp = b"\x83\x86\x04" + bytes([len(method)]) + method + b"\x01\x01a"
    body = make_req("nobody.test").SerializeToString()
    msg = b"\x00" + len(body).to_bytes(4, "big") + body

    def stream(sid):
        return (len(hp).to_bytes(3, "big") + b"\x01\x04" + sid.to_bytes(4, "big")
                + hp + len(msg).to_bytes(3, "big") + b"\x00\x01"
                + sid.to_bytes(4, "big") + msg)

    s, sid, blocked = socket.socket(), 1, False
    try:
        s.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 2048)
        s.settimeout(10)
        s.connect(("127.0.0.1", port))
        # its windows opened, as Envoy does: the answers go out, and none
        # waits in its stream for a WINDOW_UPDATE
        s.sendall(b"PRI * HTTP/2.0\r\n\r\nSM\r\n\r\n"
                  + b"\x00\x00\x06\x04\x00\x00\x00\x00\x00\x00\x04\x7f\xff\xff\xff"
                  + b"\x00\x00\x04\x08\x00\x00\x00\x00\x00"
                  + (0x7fffffff - 65535).to_bytes(4, "big"))
        for _ in range(300):
            s.sendall(b"".join(stream(sid + 2 * k) for k in range(2000)))
            sid += 4000
            time.sleep(0.01)
            if clock(fe)["counters"]["send_blocked"] > a["counters"]["send_blocked"]:
                blocked = True
                break
    except OSError:
        pass  # our own send timed out: the server stopped reading us
    finally:
        s.close()
    if not blocked:
        pytest.skip("the kernel took every answer: no send blocked")
    assert grpc_call(port, fast_req("beside-the-blocked")).status.code in (0, 7)
    b = quiet(fe, 0)
    assert delta(b, a, "other") > 0 and delta(b, a, "write") > 0


# ---------------------------------------------------------------------------
# the names are written once, in C++
# ---------------------------------------------------------------------------


def _front_metrics():
    folder = os.path.join(ROOT, "benchmark", "metrics")
    out = {}
    for name in sorted(os.listdir(folder)):
        with open(os.path.join(folder, name)) as f:
            spec = json.load(f)
        if spec.get("reader") == "front_clock":
            out[name[:-len(".json")]] = spec
    return out


def test_docs_table_names_the_phases_the_call_returns(frontend):
    fe, _ = frontend
    table = clock(fe)
    doc = open(os.path.join(ROOT, "docs", "observability.md")).read()
    section = doc[doc.index("### The front end's loop clock"):]
    section = section[:section.index("\n## ")]
    first, second = section.split("| row | what it is |")
    phases = re.findall(r"^\| `([a-z_]+)` \|", first, flags=re.M)
    assert phases == list(table["phases"])
    rest = re.findall(r"`([a-z_]+)`", "".join(
        re.findall(r"^\| (`[^|]*) \|", second, flags=re.M)))
    assert rest == list(table["rows"])
    listed = section[section.index("`counters`:"):section.index("`mark_mono_ns` is")]
    assert re.findall(r"^- `([a-z_]+)`:", listed, flags=re.M) == list(table["counters"])
    assert "It reads 0 in health" in listed  # each has its operator's use
    assert "`mark_mono_ns` is the thread's last stamp" in section
    assert '{phase="idle"}' in section


@pytest.mark.parametrize("name", sorted(_front_metrics()))
def test_metric_file_names_rows_the_call_returns(frontend, name):
    fe, _ = frontend
    table = clock(fe)
    rows = set(table["phases"]) | set(table["rows"])
    args = _front_metrics()[name]["args"]
    named = set(args.get("rows", ())) | {args.get("per"), args.get("den")} - {None}
    assert named and named <= rows, (name, named - rows)
    assert args["what"] in ("busy_pct", "per_count_us", "ratio", "mean_ms")


def test_every_metric_of_the_issue_has_its_file():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    want = {"fe_loop_busy_pct", "fe_read_pct", "fe_parse_pct", "fe_encode_pct",
            "fe_ovf_scan_pct", "fe_respond_pct", "fe_write_pct",
            "fe_us_per_check", "fe_reqs_per_wake", "fe_residence_ms",
            "fe_respond_p50_us", "cut_fill_ms", "client_rtt_ms"}
    assert want <= set(per_layer)
    # the cycle less the round trip is ~0 by construction while the
    # generator stamps `sent` at its own queue: no metric reads it
    assert "client_turnaround_ms" not in per_layer
    assert all(per_layer[n]["moves"] == "checks_per_s" for n in want)
    # ISSUE 37's two, data files over the same reader, in the two cells with
    # rows past DFA_VALUE_BYTES
    scan = {"fe_ovf_scan_us", "fe_ovf_loads_per_dfa"}
    assert all(per_layer[n]["workloads"] == [
        "routes-1k.unique-sat", "mixed-tenants-1k.unique-sat"] for n in scan)
    # ISSUE 38's two, counts alone over `parse`'s count, in every cell
    sizes = {"fe_bytes_per_check", "fe_headers_per_check"}
    assert all("workloads" not in per_layer[n] for n in sizes)
    # the framer's three: the syscalls' shares of the thread, and the Checks
    # read where recv put them, in every cell
    framer = {"fe_recv_pct", "fe_send_pct", "fe_inplace_share"}
    assert all("workloads" not in per_layer[n] and per_layer[n]["moves"] == "checks_per_s"
               for n in framer)
    # the cuts the window's timer made, over every cut, in every cell
    assert "workloads" not in per_layer["cut_timer_share"]
    assert set(_front_metrics()) == scan | sizes | framer | {"cut_timer_share"} | {
        n for n in want if n.startswith("fe_") and n != "fe_respond_p50_us"}
