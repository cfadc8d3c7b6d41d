"""Cost-model lane selection + speculative dual-dispatch (ISSUE 12,
runtime/lane_select.py + docs/performance.md "Lane selection").

Covers: the cost-model decision law (units), the host lane serving light
load first-class (stub device proves ZERO device launches), the
latency-critical-head deadline rescue, lane-aware admission, speculative
first-wins resolution (never double-resolves a future, never double-burns
the SLO, losing lane cancelled/ignored cleanly — including a wedged
losing lane held past the watchdog), and 3-seed verdict+attribution
parity across both lanes against the host expression oracle.

Deliberately import-light: collects on images without `cryptography`
(no evaluators.identity / native_frontend imports)."""

from __future__ import annotations

import asyncio
import threading
import time

import numpy as np
import pytest

from authorino_tpu.compiler import ConfigRules
from authorino_tpu.expressions import All, Any_, Operator, Pattern
from authorino_tpu.ops.pattern_eval import firing_columns
from authorino_tpu.runtime import EngineEntry, PolicyEngine
from authorino_tpu.runtime import engine as engine_mod
from authorino_tpu.runtime import faults
from authorino_tpu.runtime.admission import AdmissionController
from authorino_tpu.runtime.lane_select import (
    DEVICE,
    HOST,
    LaneCostModel,
    LaneSelector,
    R_BATCH,
    R_COST,
    R_DISABLED,
    R_EXPLORE,
    R_HOST_BUSY,
    Speculation,
)
from authorino_tpu.utils.rpc import DEADLINE_EXCEEDED


@pytest.fixture(autouse=True)
def _disarm_faults():
    yield
    faults.FAULTS.disarm()


def run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


async def wait_until(pred, timeout=5.0, step=0.005):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        await asyncio.sleep(step)
    return pred()


RULE = All(
    Pattern("auth.identity.roles", Operator.INCL, "admin"),
    Pattern("auth.identity.groups", Operator.EXCL, "banned"),
)


def build_engine(**kw) -> PolicyEngine:
    kw.setdefault("verdict_cache_size", 0)
    kw.setdefault("max_batch", 8)
    engine = PolicyEngine(members_k=4, mesh=None, **kw)
    engine.apply_snapshot([
        EngineEntry(id="c", hosts=["c"], runtime=None,
                    rules=ConfigRules(name="c", evaluators=[(None, RULE)]))
    ])
    return engine


def doc(i: int, allow: bool) -> dict:
    return {"auth": {"identity": {
        "roles": ["admin", f"r{i}"] if allow else [f"r{i}"],
        "groups": []}}}


async def submit_all(engine, docs, **kw):
    outs = await asyncio.gather(
        *(engine.submit(d, "c", **kw) for d in docs))
    return [bool(rule[0]) for rule, _ in outs]


def seed_model(engine, host_row_s=1e-4, device_rtt_s=0.1):
    """Teach the cost model a fast host lane and a slow device, so the
    next small cut decides HOST deterministically."""
    engine.lanes.cost.observe_host(host_row_s * 10, 10)
    engine.lanes.cost.observe_device(device_rtt_s, 8)
    engine._device_ewma = device_rtt_s


class FakeHandle:
    def __init__(self, ready_at):
        self.ready_at = ready_at

    def is_ready(self):
        return time.monotonic() >= self.ready_at

    def __array__(self, dtype=None):
        return np.zeros((1, 1))


class SlowStubDevice:
    """Replaces _encode_and_launch: batches 'complete' after a fixed
    latency (allow-all verdicts), so lane routing is observable."""

    def __init__(self, engine, latency_s):
        self.engine = engine
        self.latency_s = latency_s
        self.launched_batches = 0
        self.launched_rows = 0
        engine._encode_and_launch = self._launch

    def _launch(self, snap, batch):
        n = len(batch)
        self.launched_batches += 1
        self.launched_rows += n
        binfo = {"batch_size": n, "pad": n, "eff": 0,
                 "start_ns": time.time_ns(), "duration_s": 0.0}

        def finalize(packed):
            rule = np.ones((n, 1), dtype=bool)
            return rule, np.zeros((n, 1), dtype=bool), None

        return engine_mod._Inflight(
            self.engine, batch,
            FakeHandle(time.monotonic() + self.latency_s),
            finalize, binfo, np.zeros(n))


# ---------------------------------------------------------------------------
# cost model units
# ---------------------------------------------------------------------------


class TestCostModel:
    def test_host_cost_scales_with_rows(self):
        c = LaneCostModel("t-hc")
        c.observe_host(0.001, 10)  # 100us/row
        assert c.host_cost(1) == pytest.approx(1e-4, rel=0.01)
        assert c.host_cost(50) == pytest.approx(5e-3, rel=0.01)

    def test_device_cost_inflates_with_occupancy_and_mesh(self):
        c = LaneCostModel("t-dc")
        c.observe_device(0.1, 256)
        base = c.device_cost(0, 8)
        assert base == pytest.approx(0.1, rel=0.01)
        assert c.device_cost(8, 8) == pytest.approx(2 * base, rel=0.01)
        c.mesh_penalty = 4.0  # 3 of 4 devices down
        assert c.device_cost(0, 8) == pytest.approx(4 * base, rel=0.01)

    def test_cold_start_prefers_device(self):
        # no observations at all: there is no evidence to flip the old
        # device-always behavior, so the selector must keep it
        s = LaneSelector("t-cold")
        assert s.decide(4, 0, 8)[0] == DEVICE

    def test_burn_bias_bounded_and_directional(self):
        c = LaneCostModel("t-burn")
        assert c.burn_bias() == 1.0
        c.observe_slo(DEVICE, 100, 100)
        assert 1.0 < c.burn_bias() <= 2.0  # device burning -> host favored
        c2 = LaneCostModel("t-burn2")
        c2.observe_slo(HOST, 100, 100)
        assert 0.5 <= c2.burn_bias() < 1.0

    def test_burn_decays(self):
        c = LaneCostModel("t-decay")
        t0 = 100.0
        c.observe_slo(DEVICE, 100, 100, now=t0)
        assert c.burn_frac(DEVICE) == 1.0
        # a clean minute later, the bad history has decayed away
        c.observe_slo(DEVICE, 1000, 0, now=t0 + 120.0)
        assert c.burn_frac(DEVICE) < 0.05

    def test_min_service_is_the_admission_floor(self):
        c = LaneCostModel("t-floor")
        c.observe_host(0.001, 10)
        c.observe_device(0.5, 8)
        assert c.min_service_s() == pytest.approx(1e-4, rel=0.01)


class TestSelector:
    def seeded(self, **kw):
        c = LaneCostModel(kw.pop("lane", "t-sel"))
        c.observe_host(0.001, 10)   # 100us/row
        c.observe_device(0.1, 256)  # 100ms RTT
        return LaneSelector("t-sel", cost=c, **kw)

    def test_small_cut_goes_host_large_goes_device(self):
        s = self.seeded(host_max_rows=64)
        assert s.decide(4, 0, 8) == (HOST, R_COST)
        assert s.decide(65, 0, 8) == (DEVICE, R_BATCH)
        # crossover: 100us x n vs 100ms -> device wins past ~1000 rows,
        # but the host_max_rows cap binds first by design
        assert s.decide(64, 0, 8)[0] == HOST

    def test_host_busy_and_disabled(self):
        s = self.seeded(host_concurrency=1)
        s.host_inflight = 1
        assert s.decide(4, 0, 8) == (DEVICE, R_HOST_BUSY)
        s2 = self.seeded()
        s2.enabled = False
        assert s2.decide(4, 0, 8) == (DEVICE, R_DISABLED)

    def test_burn_bias_flips_a_close_call(self):
        c = LaneCostModel("t-flip")
        c.observe_host(0.08, 1)    # host 80ms/row — close to the RTT
        c.observe_device(0.1, 8)   # device 100ms
        s = LaneSelector("t-flip", cost=c, explore_every=0)
        assert s.decide(1, 0, 8)[0] == HOST  # raw cost: 80 < 100
        c.observe_slo(HOST, 100, 100)        # host burning budget
        which, why = s.decide(1, 0, 8)
        assert which == DEVICE and why == "slo-burn"

    def test_explore_probes_the_device_periodically(self):
        s = self.seeded(explore_every=8)
        picks = [s.decide(2, 0, 8) for _ in range(8)]
        assert picks[-1] == (DEVICE, R_EXPLORE)
        assert all(w == HOST for w, _ in picks[:-1])


class TestSpeculation:
    def test_first_claim_wins_exactly_once(self):
        sp = Speculation("t")
        assert sp.claim(HOST) is True
        assert sp.claim(DEVICE) is False
        assert sp.winner == HOST

    def test_acquire_is_idempotent_for_the_owner(self):
        sp = Speculation("t")
        assert sp.acquire(DEVICE) is True
        assert sp.acquire(DEVICE) is True   # the owner keeps ownership
        assert sp.acquire(HOST) is False

    def test_concurrent_claims_single_winner(self):
        for _ in range(50):
            sp = Speculation("t")
            wins = []
            barrier = threading.Barrier(2)

            def claim(which):
                barrier.wait()
                if sp.claim(which):
                    wins.append(which)

            ts = [threading.Thread(target=claim, args=(w,))
                  for w in (HOST, DEVICE)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            assert len(wins) == 1 and wins[0] == sp.winner


# ---------------------------------------------------------------------------
# lane-aware admission
# ---------------------------------------------------------------------------


class TestLaneAwareAdmission:
    def test_lane_floor_rescues_tight_deadlines_at_admission(self):
        a = AdmissionController("t-lane-adm", target_s=0.05, min_cap=1000)
        now = 50.0
        # device RTT 5s, deadline budget 1s: doomed without the floor...
        assert a.admit(0, now=now, deadline=now + 1.0, rtt_s=5.0) is not None
        # ...admitted with a microsecond host-lane floor
        a.lane_floor = lambda: 1e-4
        assert a.admit(0, now=now, deadline=now + 1.0, rtt_s=5.0) is None
        # an already-expired deadline is still doomed, floor or not
        code, _ = a.admit(0, now=now, deadline=now - 0.01, rtt_s=5.0)
        assert code == DEADLINE_EXCEEDED

    def test_broken_floor_never_breaks_admission(self):
        a = AdmissionController("t-lane-adm2", target_s=0.05, min_cap=10)

        def boom():
            raise RuntimeError("floor broke")

        a.lane_floor = boom
        assert a.admit(0, now=1.0, deadline=2.0, rtt_s=0.0) is None

    def test_engine_wires_the_floor_only_when_enabled(self):
        e1 = build_engine(lane_select=True)
        assert e1.admission.lane_floor is not None
        e2 = build_engine(lane_select=False)
        assert e2.admission.lane_floor is None

    def test_floor_collapses_when_host_lane_saturated(self):
        """Backpressure stays honest: with the host concurrency cap taken,
        the admission floor falls back to the device RTT — admission must
        not admit tight-deadline work the host lane cannot rescue."""
        engine = build_engine()
        seed_model(engine, device_rtt_s=5.0)
        assert engine.admission.lane_floor() < 1.0
        engine.lanes.host_inflight = engine.lanes.host_limit
        assert engine.admission.lane_floor() == float("inf")
        now = time.monotonic()
        assert engine.admission.admit(0, now=now, deadline=now + 1.0,
                                      rtt_s=5.0) is not None
        engine.lanes.host_inflight = 0
        assert engine.admission.admit(0, now=now, deadline=now + 1.0,
                                      rtt_s=5.0) is None


# ---------------------------------------------------------------------------
# engine integration: the host lane as a first-class serving lane
# ---------------------------------------------------------------------------


class TestHostLaneServing:
    def test_light_load_served_host_side_zero_device_launches(self):
        engine = build_engine()
        stub = SlowStubDevice(engine, latency_s=0.2)
        seed_model(engine)
        engine.lanes.explore_every = 0  # pin: no periodic device probe
        outs = run(submit_all(engine, [doc(i, i % 2 == 0)
                                       for i in range(4)]))
        assert outs == [True, False, True, False]
        assert stub.launched_batches == 0  # the cut never touched a device
        ls = engine.lanes.to_json()
        assert ls["rows"][HOST] == 4
        assert any(k.startswith("host:") for k in ls["decisions"])

    def test_large_cut_rides_the_device(self):
        engine = build_engine(max_batch=64, lane_host_max_rows=4)
        stub = SlowStubDevice(engine, latency_s=0.01)
        seed_model(engine)

        async def burst():
            return await submit_all(engine, [doc(i, True)
                                             for i in range(32)])

        assert all(run(burst()))
        assert stub.launched_batches >= 1  # > host_max_rows: batch work

    def test_host_lane_observes_cost_and_service(self):
        engine = build_engine()
        SlowStubDevice(engine, latency_s=0.2)
        seed_model(engine)
        engine.lanes.explore_every = 0
        before = engine.lanes.cost.host_batches
        run(submit_all(engine, [doc(0, True)]))
        assert engine.lanes.cost.host_batches > before
        assert engine.lanes.cost.host_row_s > 0

    def test_cache_only_batches_never_feed_the_device_rtt(self):
        """A fully verdict-cache-resolved batch (zero device rows) must
        not drag the device RTT EWMA down to cache-turnaround time —
        that would read as a fast device and pin small cuts device-side
        under cache-hit-heavy traffic."""
        engine = build_engine()
        stub = SlowStubDevice(engine, latency_s=0.0)
        real = stub._launch

        def cache_only(snap, batch):
            item = real(snap, batch)
            item.binfo["device_rows"] = 0
            return item

        engine._encode_and_launch = cache_only
        before = engine.lanes.cost.device_batches
        run(submit_all(engine, [doc(0, True)]))
        assert engine.lanes.cost.device_batches == before
        assert engine.lanes.cost.device_rtt_s == 0.0

    def test_explore_decision_reaches_the_device(self):
        engine = build_engine()
        stub = SlowStubDevice(engine, latency_s=0.01)
        seed_model(engine)
        engine.lanes.explore_every = 2  # every 2nd host win explores

        async def series():
            for i in range(4):
                await submit_all(engine, [doc(i, True)])

        run(series())
        assert stub.launched_batches >= 1
        assert "device:explore" in engine.lanes.to_json()["decisions"]

    def test_deadline_head_rescued_not_shed(self):
        """A device-bound cut whose head cannot make the device RTT is
        answered host-side instead of shed typed DEADLINE_EXCEEDED."""
        engine = build_engine(max_batch=16, lane_host_max_rows=2)
        SlowStubDevice(engine, latency_s=0.5)
        seed_model(engine, device_rtt_s=0.5)
        engine.lanes.explore_every = 0

        async def mixed():
            # 8 > lane_host_max_rows: the CUT rides the device; two of its
            # members carry deadlines inside the 0.5s device horizon
            tight = time.monotonic() + 0.1
            futs = [engine.submit(doc(i, True), "c",
                                  deadline=tight if i < 2 else None)
                    for i in range(8)]
            return await asyncio.gather(*futs, return_exceptions=True)

        outs = run(mixed())
        assert not any(isinstance(o, Exception) for o in outs)
        assert all(bool(r[0][0]) for r in outs)
        dec = engine.lanes.to_json()["decisions"]
        assert dec.get("host:deadline", 0) >= 1

    def test_degrade_teaches_the_cost_model(self):
        """Every host-oracle batch feeds the per-row EWMA — degrade
        included: an engine whose device is down routes subsequent cuts
        host-side AT THE CUT (first-class) instead of bouncing every
        batch off the open breaker's degrade path."""
        engine = build_engine(breaker_threshold=2)
        faults.FAULTS.arm("kernel:raise:p=1.0")
        try:
            assert run(submit_all(engine, [doc(0, True)])) == [True]
            assert engine.lanes.cost.host_row_s > 0  # degrade taught it
            assert run(submit_all(engine, [doc(1, False)])) == [False]
        finally:
            faults.FAULTS.disarm()
        assert engine.lanes.to_json()["rows"][HOST] >= 1

    def test_drain_waits_out_host_lane_batches(self):
        engine = build_engine()
        SlowStubDevice(engine, latency_s=0.05)
        seed_model(engine)
        run(submit_all(engine, [doc(0, True)]))
        assert engine.drain(timeout_s=5.0) is True
        assert engine.lanes.host_inflight == 0

    def test_debug_vars_lane_block(self):
        engine = build_engine()
        ls = engine.debug_vars()["lane_select"]
        for key in ("enabled", "host_max_rows", "speculative", "decisions",
                    "rows", "speculative_outcomes", "cost"):
            assert key in ls
        for key in ("host_row_ewma_s", "device_rtt_ewma_s", "mesh_penalty",
                    "burn_bias"):
            assert key in ls["cost"]


# ---------------------------------------------------------------------------
# speculative dual-dispatch: first-wins, no double-resolve, no double-burn
# ---------------------------------------------------------------------------


def trip_to_half_open(engine, reset_s=0.02):
    """Drive the lane breaker OPEN and past its cooldown, so the next
    dispatch claims the half-open probe slot."""
    for _ in range(engine.breaker.threshold):
        engine.breaker.record_failure()
    assert engine.breaker.state == "open"
    time.sleep(reset_s + 0.01)


class TestSpeculativeDualDispatch:
    def test_probe_rides_both_lanes_host_wins_device_confirms(self):
        engine = build_engine(breaker_threshold=2, breaker_reset_s=0.02,
                              slo_ms=1000.0)
        stub = SlowStubDevice(engine, latency_s=0.3)
        seed_model(engine, device_rtt_s=0.3)
        # force the CUT onto the device so the probe is a device dispatch
        engine.lanes.host_max_rows = 0
        trip_to_half_open(engine)
        slo_before = engine.slo.total

        async def probe():
            t0 = time.monotonic()
            outs = await submit_all(engine, [doc(i, True) for i in range(3)])
            return outs, time.monotonic() - t0

        outs, took = run(probe())
        assert outs == [True, True, True]
        # the host twin answered: clients never waited out the 0.3s probe
        assert took < 0.25, f"clients waited out the probe: {took:.3f}s"
        # the device half DID launch: the dispatcher hands the batch to the
        # host twin first, and the twin can answer before that same thread
        # has reached the launch
        run(wait_until(lambda: stub.launched_batches == 1))
        assert stub.launched_batches == 1
        spec = engine.lanes.to_json()["speculative_outcomes"]
        assert spec.get("launched") == 1
        assert spec.get("host-win") == 1
        # the device half closes the breaker when its readback lands
        run(wait_until(lambda: engine.breaker.state == "closed"))
        assert engine.breaker.state == "closed"
        run(wait_until(
            lambda: engine.lanes.to_json()["speculative_outcomes"].get(
                "device-win", 0) == 0 and engine._inflight == 0))
        # SLO burned exactly once for the batch (host side), never twice
        assert engine.slo.total == slo_before + 3
        assert engine._inflight == 0  # the window slot was freed

    def test_wedged_losing_device_cancelled_past_watchdog(self):
        """The losing device half wedges forever: the watchdog abandons it
        WITHOUT re-failing the already-resolved batch — no double-resolve,
        no retry storm, slot freed, outcome counted device-fail."""
        engine = build_engine(breaker_threshold=2, breaker_reset_s=0.02,
                              device_timeout_s=0.1, slo_ms=1000.0)
        stub = SlowStubDevice(engine, latency_s=10_000.0)  # never ready
        seed_model(engine, device_rtt_s=0.05)
        engine.lanes.host_max_rows = 0
        trip_to_half_open(engine)
        slo_before = engine.slo.total

        async def probe():
            outs = await submit_all(engine, [doc(0, True), doc(1, False)])
            assert outs == [True, False]
            # the watchdog fires twice (launch + the one retry), then the
            # spec-aware failure path frees the slot without degrading
            assert await wait_until(lambda: engine._inflight == 0,
                                    timeout=8.0)

        run(probe())
        spec = engine.lanes.to_json()["speculative_outcomes"]
        assert spec.get("host-win") == 1
        assert spec.get("device-fail", 0) >= 1
        # SLO burned once on the host side; the wedged loser added nothing
        assert engine.slo.total == slo_before + 2
        # the device halves kept feeding the breaker: it re-opened
        assert engine.breaker.state == "open"
        assert stub.launched_batches >= 1

    def test_device_wins_when_host_is_slow(self):
        """Host twin loses the race: the device resolves, the late host
        result is confirmation only (no double-resolve, host-win absent)."""
        engine = build_engine(breaker_threshold=2, breaker_reset_s=0.02,
                              slo_ms=1000.0)
        SlowStubDevice(engine, latency_s=0.02)
        seed_model(engine, device_rtt_s=0.02)
        engine.lanes.host_max_rows = 0
        # make the host twin slow: wrap the host decide with a sleep
        real = engine._host_decide_batch

        def slow_host(snap, batch, fold=True, lane="engine"):
            time.sleep(0.3)
            return real(snap, batch, fold=fold, lane=lane)

        engine._host_decide_batch = slow_host
        trip_to_half_open(engine)
        slo_before = engine.slo.total
        outs = run(submit_all(engine, [doc(0, True)]))
        assert outs == [True]
        run(wait_until(
            lambda: engine.lanes.host_inflight == 0, timeout=5.0))
        spec = engine.lanes.to_json()["speculative_outcomes"]
        assert spec.get("device-win") == 1
        assert spec.get("host-win", 0) == 0
        assert engine.slo.total == slo_before + 1  # burned once (device)
        assert engine.breaker.state == "closed"

    def test_no_speculation_when_disabled_or_breaker_closed(self):
        engine = build_engine(speculative_dispatch=False,
                              breaker_threshold=2, breaker_reset_s=0.02)
        SlowStubDevice(engine, latency_s=0.02)
        seed_model(engine)
        engine.lanes.host_max_rows = 0
        trip_to_half_open(engine)
        assert run(submit_all(engine, [doc(0, True)])) == [True]
        assert engine.lanes.to_json()["speculative_outcomes"] == {}
        # closed breaker: plain dispatch never speculates either
        engine2 = build_engine()
        SlowStubDevice(engine2, latency_s=0.02)
        seed_model(engine2)
        engine2.lanes.host_max_rows = 0
        assert run(submit_all(engine2, [doc(0, True)])) == [True]
        assert engine2.lanes.to_json()["speculative_outcomes"] == {}

    def test_futures_resolve_exactly_once(self):
        """Direct first-wins check at the resolution layer: after the host
        twin resolved, a device completion for the same batch must not
        overwrite results (and vice versa)."""
        engine = build_engine(breaker_threshold=2, breaker_reset_s=0.02)
        SlowStubDevice(engine, latency_s=0.15)
        seed_model(engine, device_rtt_s=0.15)
        engine.lanes.host_max_rows = 0
        trip_to_half_open(engine)

        async def probe():
            rule, skipped = await engine.submit(doc(0, False), "c")
            first = bool(rule[0])
            # wait out the device completion; the resolved value must not
            # flip (the stub answers allow-all — a second resolution would
            # surface as True)
            await asyncio.sleep(0.3)
            return first

        assert run(probe()) is False  # the host oracle's (exact) verdict


# ---------------------------------------------------------------------------
# parity: verdict + attribution identical across lanes (3 seeds)
# ---------------------------------------------------------------------------


def rand_corpus(rng, n_cfg=6):
    entries = []
    rules = []
    for i in range(n_cfg):
        rule = All(
            Pattern("request.method", Operator.NEQ, "DELETE"),
            Any_(
                Pattern("auth.identity.org", Operator.EQ, f"org-{i}"),
                Pattern("auth.identity.roles", Operator.INCL,
                        f"role-{rng.randrange(4)}"),
            ),
        )
        rules.append(rule)
        entries.append(EngineEntry(
            id=f"cfg-{i}", hosts=[f"h{i}"], runtime=None,
            rules=ConfigRules(name=f"cfg-{i}", evaluators=[(None, rule)])))
    return entries, rules


def rand_doc(rng, i):
    return {
        "request": {"method": rng.choice(["GET", "POST", "DELETE"])},
        "auth": {"identity": {
            "org": f"org-{rng.randrange(8)}",
            "roles": [f"role-{rng.randrange(4)}" for _ in range(2)],
        }},
    }


@pytest.mark.parametrize("seed", [7, 23, 91])
def test_lane_parity_verdict_and_attribution(seed):
    """Random traffic through the engine with the host lane FORCED on vs
    the device lane forced on: verdicts AND firing columns must both equal
    the host expression oracle — the bit-identical-verdicts property the
    speculative race relies on."""
    import random

    rng = random.Random(seed)
    entries, rules = rand_corpus(rng)
    docs = [rand_doc(rng, i) for i in range(48)]
    which_cfg = [rng.randrange(len(entries)) for _ in docs]

    def serve(force_host: bool):
        engine = PolicyEngine(members_k=4, mesh=None, verdict_cache_size=0,
                              max_batch=8, lane_select=force_host,
                              speculative_dispatch=False)
        engine.apply_snapshot(entries)
        if force_host:
            seed_model(engine, device_rtt_s=10.0)  # host always wins
            engine.lanes.explore_every = 0

        async def go():
            outs = []
            for d, ci in zip(docs, which_cfg):
                rule, skipped = await engine.submit(d, f"cfg-{ci}")
                outs.append((np.asarray(rule, dtype=bool),
                             np.asarray(skipped, dtype=bool)))
            return outs

        out = run(go())
        if force_host:
            assert engine.lanes.to_json()["rows"][HOST] == len(docs)
        return out

    host_outs = serve(True)
    dev_outs = serve(False)
    for (hr, hs), (dr, ds), d, ci in zip(host_outs, dev_outs, docs,
                                         which_cfg):
        want = bool(rules[ci].matches(d))
        assert bool(hr[0]) == bool(dr[0]) == want
        hf = int(firing_columns(hr[None, :], hs[None, :])[0])
        df = int(firing_columns(dr[None, :], ds[None, :])[0])
        assert hf == df, f"attribution diverged: host {hf} device {df}"


def test_mesh_cost_feed_units():
    """cost_feed() is total/healthy: 1.0 with a healthy mesh, rising as
    per-device breakers trip (unit-level — the mesh lane itself runs in
    tests/test_mesh.py on forced host devices)."""

    class _B:
        def __init__(self, state):
            self.state = state

    class _Set:
        def __init__(self, states):
            self.breakers = {i: _B(s) for i, s in enumerate(states)}

    class _State:
        pass

    from authorino_tpu.parallel.sharded_eval import ShardedPolicyModel

    m = ShardedPolicyModel.__new__(ShardedPolicyModel)
    m.state = _State()
    m.state.breakers = _Set(["closed"] * 4)
    assert m.cost_feed() == 1.0
    m.state.breakers = _Set(["closed", "closed", "open", "open"])
    assert m.cost_feed() == 2.0
    m.state.breakers = _Set(["open"] * 4)
    assert m.cost_feed() == 4.0
