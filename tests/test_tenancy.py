"""Tenant QoS plane (ISSUE 15): weighted-fair batch cuts, per-tenant
quotas/SLO, noisy-neighbor containment, stratified decision sampling, and
the tenant-label cardinality lint.

Deliberately import-light: collects on images without `cryptography`
(no evaluators.identity / native_frontend imports)."""

from __future__ import annotations

import asyncio
import time
from collections import deque

import numpy as np
import pytest

from authorino_tpu.compiler import ConfigRules
from authorino_tpu.expressions import All, Operator, Pattern
from authorino_tpu.runtime import EngineEntry, PolicyEngine
from authorino_tpu.runtime import provenance as prov_mod
from authorino_tpu.runtime.admission import ADMIT, AdmissionController
from authorino_tpu.runtime.flight_recorder import RECORDER
from authorino_tpu.tenancy import (
    R_TENANT_CONTAINED,
    R_TENANT_QUOTA,
    FairCutter,
    NoisyNeighborDetector,
    TenantAdmission,
    TenantPlane,
    TenantStats,
    WeightBook,
)
from authorino_tpu.utils.rpc import RESOURCE_EXHAUSTED, CheckAbort


def run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


RULE = All(
    Pattern("auth.identity.roles", Operator.INCL, "admin"),
    Pattern("auth.identity.groups", Operator.EXCL, "banned"),
)


def build_engine(n_tenants=3, annotations=None, **kw) -> PolicyEngine:
    kw.setdefault("verdict_cache_size", 0)
    kw.setdefault("max_batch", 8)
    engine = PolicyEngine(members_k=4, mesh=None, **kw)
    engine.apply_snapshot([
        EngineEntry(id=f"t{i}", hosts=[f"t{i}"], runtime=None,
                    rules=ConfigRules(name=f"t{i}",
                                      evaluators=[(None, RULE)]),
                    annotations=(annotations or {}).get(f"t{i}"))
        for i in range(n_tenants)
    ])
    return engine


def doc(i: int, allow: bool = True) -> dict:
    return {"auth": {"identity": {
        "roles": ["admin", f"r{i}"] if allow else [f"r{i}"],
        "groups": []}}}


class P:
    """Minimal _Pending stand-in for the cutter/admission units."""

    def __init__(self, tenant, seq=0):
        self.config_name = tenant
        self.seq = seq
        self.t_enq = time.monotonic()


# ---------------------------------------------------------------------------
# weights from annotations
# ---------------------------------------------------------------------------


class TestWeights:
    def test_class_weight_quota_resolution(self):
        book = WeightBook()
        book.rebuild({
            "gold": {"authorino.tpu/qos-class": "Gold"},
            "explicit": {"authorino.tpu/qos-weight": "7.5"},
            "quota": {"authorino.tpu/qos-quota-rps": "25"},
            "junk": {"authorino.tpu/qos-weight": "not-a-number"},
            "plain": None,
        })
        assert book.weight("gold") == 4.0
        assert book.weight("explicit") == 7.5
        assert book.weight("junk") == 1.0       # typo never zeroes a share
        assert book.weight("plain") == 1.0
        assert book.weight("never-seen") == 1.0
        assert book.quota_rps("quota") == 25.0
        assert book.quota_rps("plain") == 0.0

    def test_override_beats_annotation(self):
        book = WeightBook(overrides={"t": 9.0})
        book.rebuild({"t": {"authorino.tpu/qos-weight": "2"}})
        assert book.weight("t") == 9.0

    def test_share_is_relative_to_backlogged_set(self):
        book = WeightBook()
        book.rebuild({"a": {"authorino.tpu/qos-weight": "3"}, "b": None})
        assert book.share("a", ["a", "b"]) == pytest.approx(0.75)
        assert book.share("a", ["a"]) == 1.0
        assert book.share("b", []) == 1.0

    def test_engine_binds_annotations_at_reconcile(self):
        engine = build_engine(annotations={
            "t0": {"authorino.tpu/qos-weight": "4"}})
        assert engine.tenancy.book.weight("t0") == 4.0
        assert engine.tenancy.book.weight("t1") == 1.0


# ---------------------------------------------------------------------------
# weighted-fair cut: work conservation, share accuracy, ordering
# ---------------------------------------------------------------------------


class TestFairCut:
    def test_sole_backlogged_tenant_gets_the_full_batch(self):
        """Work conservation: with one tenant backlogged, fairness must
        never leave batch slots empty."""
        book = WeightBook()
        book.rebuild({"a": None})
        cutter = FairCutter(book.weight)
        q = deque(P("a", i) for i in range(40))
        batch = cutter.cut(q, 16)
        assert len(batch) == 16
        assert [p.seq for p in batch] == list(range(16))

    def test_uncontended_cut_equals_unfair_pop(self):
        cutter = FairCutter(lambda t: 1.0)
        q = deque(P("a", i) for i in range(5))
        batch = cutter.cut(q, 8)
        assert [p.seq for p in batch] == list(range(5)) and not q

    @pytest.mark.parametrize("weights", [
        {"a": 1.0, "b": 1.0},
        {"a": 1.0, "b": 4.0},
        {"a": 1.0, "b": 2.0, "c": 4.0},
    ])
    def test_share_accuracy_within_one_batch_of_slack(self, weights):
        """Property (ISSUE 15 satellite): with every tenant persistently
        backlogged, cumulative selected counts track the weight mix within
        one batch of slack, under three weight mixes."""
        book = WeightBook()
        book.rebuild({t: {"authorino.tpu/qos-weight": str(w)}
                      for t, w in weights.items()})
        cutter = FairCutter(book.weight)
        n, cuts = 16, 24
        got = {t: 0 for t in weights}
        q = deque()
        seq = 0
        for _ in range(cuts):
            # replenish so every tenant stays deeply backlogged
            for t in weights:
                for _ in range(2 * n):
                    q.append(P(t, seq))
                    seq += 1
            for p in cutter.cut(q, n):
                got[p.config_name] += 1
        total_w = sum(weights.values())
        for t, w in weights.items():
            expected = cuts * n * w / total_w
            assert abs(got[t] - expected) <= n, (
                f"tenant {t}: got {got[t]}, expected ~{expected:.0f} "
                f"(mix {weights})")

    def test_work_conserving_spill_when_a_tenant_drains(self):
        """Unused share spills: a tenant with fewer rows than its share
        frees the rest of the batch to the backlogged tenant."""
        book = WeightBook()
        book.rebuild({"big": {"authorino.tpu/qos-weight": "8"},
                      "small": None})
        cutter = FairCutter(book.weight)
        q = deque([P("big", i) for i in range(3)]
                  + [P("small", 100 + i) for i in range(40)])
        batch = cutter.cut(q, 16)
        assert len(batch) == 16
        assert sum(1 for p in batch if p.config_name == "big") == 3
        assert sum(1 for p in batch if p.config_name == "small") == 13

    def test_arrival_order_preserved_within_batch_and_remainder(self):
        cutter = FairCutter(lambda t: 1.0)
        items = []
        q = deque()
        for i in range(30):
            p = P("hot" if i % 3 else "cold", i)
            q.append(p)
            items.append(p)
        batch = cutter.cut(q, 10)
        assert [p.seq for p in batch] == sorted(p.seq for p in batch)
        assert [p.seq for p in q] == sorted(p.seq for p in q)
        # nothing duplicated or lost
        assert {id(p) for p in batch} | {id(p) for p in q} == \
            {id(p) for p in items}
        assert len(batch) + len(q) == 30

    def test_hot_tenant_cannot_starve_cold_rows(self):
        """The regression the fair cut exists to kill: a 10x hot tenant
        fills at most its share of each contended cut, so a cold tenant's
        lone rows ride the NEXT batch, not the end of the hot backlog."""
        cutter = FairCutter(lambda t: 1.0)
        q = deque([P("hot", i) for i in range(200)])
        q.append(P("cold", 999))
        batch = cutter.cut(q, 16)
        assert any(p.config_name == "cold" for p in batch)


# ---------------------------------------------------------------------------
# fairness must reorder, never re-decide: byte-identical verdicts
# ---------------------------------------------------------------------------


class TestFairnessExactness:
    def test_verdict_and_attribution_identical_fair_vs_unfair(self):
        """Property (ISSUE 15 satellite): the same multi-tenant workload
        through a fair-cut engine and an unfair (tenant_qos=False) engine
        produces byte-identical per-request (rule, skipped) columns."""
        fair = build_engine(n_tenants=3, tenant_qos=True)
        unfair = build_engine(n_tenants=3, tenant_qos=False)
        docs = [doc(i, allow=(i % 3 != 1)) for i in range(48)]
        names = [f"t{i % 3}" for i in range(48)]

        async def burst(engine):
            outs = await asyncio.gather(
                *(engine.submit(d, n) for d, n in zip(docs, names)))
            return outs

        got_fair = run(burst(fair))
        got_unfair = run(burst(unfair))
        for (r1, s1), (r2, s2) in zip(got_fair, got_unfair):
            assert np.array_equal(np.asarray(r1), np.asarray(r2))
            assert np.array_equal(np.asarray(s1), np.asarray(s2))

    def test_contended_cut_is_fair_in_the_engine(self):
        """Structural: with tenancy on, the engine's contended cuts run
        through the FairCutter (the cutter's counters move)."""
        engine = build_engine(n_tenants=2, max_batch=4)
        docs = [doc(i) for i in range(64)]

        async def burst():
            await asyncio.gather(*(
                engine.submit(d, f"t{i % 2}") for i, d in enumerate(docs)))

        run(burst())
        assert engine.tenancy.cutter.cuts > 0


# ---------------------------------------------------------------------------
# per-tenant quotas + tenant-aware doomed depth
# ---------------------------------------------------------------------------


class TestTenantQuota:
    def test_over_quota_tenant_rejected_typed_and_scoped(self):
        engine = build_engine(
            n_tenants=2,
            annotations={"t0": {"authorino.tpu/qos-quota-rps": "1"}})

        async def burst():
            codes = []
            ok = 0
            for i in range(40):
                try:
                    await engine.submit(doc(i), "t0")
                    ok += 1
                except CheckAbort as e:
                    codes.append((e.code, e.message))
            # the un-quota'd tenant keeps its full budget
            for i in range(8):
                await engine.submit(doc(i), "t1")
            return ok, codes

        ok, codes = run(burst())
        assert codes, "quota never fired"
        assert ok >= 1, "the burst allowance must admit the first arrivals"
        assert all(c == RESOURCE_EXHAUSTED for c, _ in codes)
        assert all("tenant t0" in m for _, m in codes)
        # tenant-scoped: the GLOBAL latch is untouched
        assert engine.admission.state == ADMIT
        rej = engine.tenancy.admission.rejected["t0"]
        assert rej[R_TENANT_QUOTA] == len(codes)
        assert "t1" not in engine.tenancy.admission.rejected

    def test_doom_depth_is_per_tenant(self):
        book = WeightBook()
        book.rebuild({"hot": None, "cold": None})
        adm = TenantAdmission(book)
        for _ in range(1000):
            adm.on_enqueue("hot")
        # the cold tenant waits behind ITS backlog (none), not the hot
        # tenant's 1000-deep standing queue
        assert adm.doom_depth("cold", 1000) == 0
        # the hot tenant's effective depth: backlog / its fair share (1/2)
        assert adm.doom_depth("hot", 1000) == 1000  # clamped to global
        adm.on_dequeue([P("hot") for _ in range(900)])
        assert adm.doom_depth("hot", 100) == 100

    def test_queue_share_bound_scopes_to_the_flooding_tenant(self):
        """Per-tenant queue-occupancy bound: once the shared queue is past
        half its cap, the tenant whose own backlog exceeds its GLOBAL
        weighted share of the cap is rejected typed — other tenants keep
        admitting, and below half-cap the bound never bites (work
        conservation)."""
        from authorino_tpu.tenancy.quota import R_TENANT_SHARE

        book = WeightBook()
        book.rebuild({f"t{i}": None for i in range(32)})
        adm = TenantAdmission(book)
        for _ in range(200):
            adm.on_enqueue("t0")
        for _ in range(3):
            adm.on_enqueue("t1")
        # queue past half the cap: the flooder is bounded...
        rej = adm.share_reject("t0", global_depth=203, effective_cap=256)
        assert rej is not None and rej[1] == R_TENANT_SHARE
        # ...its victims are not
        assert adm.share_reject("t1", 203, 256) is None
        # an idle queue absorbs bursts whole, whatever the occupancy
        assert adm.share_reject("t0", 100, 256) is None

    def test_global_share_ignores_backlog_composition(self):
        book = WeightBook()
        book.rebuild({f"t{i}": None for i in range(10)})
        assert book.global_share("t0") == pytest.approx(0.1)
        # unknown tenants ride the default weight against the known set
        assert book.global_share("stranger") == pytest.approx(1.0 / 11.0)

    def test_admission_controller_uses_doom_depth(self):
        ctrl = AdmissionController("x", target_s=0.01)
        ctrl._service_rate = 100.0  # 100 rows/s
        now = time.monotonic()
        deadline = now + 0.5
        # global depth 1000 -> predicted wait 10s: doomed
        assert ctrl.admit(1000, now=now, deadline=deadline) is not None
        # same global depth but a 0-deep tenant view: admitted (depth
        # bounds still read the REAL depth — min_cap floor admits here)
        assert ctrl.admit(0, now=now, deadline=deadline,
                          doom_depth=0) is None


# ---------------------------------------------------------------------------
# per-tenant stats folds and the burn window
# ---------------------------------------------------------------------------


class _StubHeat:
    configs_per_shard = None

    def __init__(self, names):
        self.names = names

    def name(self, row, shard=None):
        return self.names[row] if 0 <= row < len(self.names) else ""


class TestTenantStats:
    def test_fold_is_vectorized_per_batch(self):
        stats = TenantStats("test-lane")
        heat = _StubHeat(["a", "b"])
        rows = np.array([0, 0, 0, 1, 0, 1])
        firing = np.array([-1, 0, -1, -1, 2, -1])
        waits = np.array([0.01, 0.02, 0.03, 0.001, 0.02, 0.002])
        stats.fold(heat, rows, firing=firing, waits=waits,
                   bad_mask=waits > 0.015)
        assert stats.fold_calls == 1
        j = stats.to_json()
        by = {r["tenant"]: r for r in j["top"]}
        assert by["a"]["requests"] == 4 and by["a"]["denies"] == 2
        assert by["b"]["requests"] == 2 and by["b"]["denies"] == 0
        assert by["a"]["slo_bad"] == 3 and by["b"]["slo_bad"] == 0

    def test_shares_decay_toward_live_traffic(self):
        stats = TenantStats("test-lane2")
        heat = _StubHeat(["hot", "cold"])
        t0 = time.monotonic()
        for k in range(10):
            stats.fold(heat, np.array([0] * 9 + [1]),
                       firing=np.full(10, -1), now=t0 + 0.1 * (k + 1))
        shares = stats.shares()
        assert shares["hot"] > 5 * shares["cold"]

    def test_burn_window(self):
        """A tenant's burn reads both half-window buckets; a full window
        later the old halves age out.  The per-key reference agrees."""
        stats = TenantStats("burn-lane", burn_window_s=10.0)
        stats.burn_budget = 1.0 - 0.9
        burn = KeyedBurn(window_s=10.0, objective=0.9)
        heat = _StubHeat(["t"])
        t0 = 1000.0
        bad = np.arange(100) < 50
        stats.fold(heat, np.zeros(100, dtype=int), bad_mask=bad, now=t0)
        burn.fold("t", 100, 50, now=t0)
        (row,) = stats._burn_json(top=8)["top_burn"]
        assert row == {"key": "t", "burn_rate": pytest.approx(5.0),
                       "total": 100, "bad": 50}
        assert burn.burn("t", now=t0) == pytest.approx(5.0)
        # half a window later the first bucket is the previous one
        stats.fold(heat, np.zeros(100, dtype=int), bad_mask=~bad | bad,
                   now=t0 + 6.0)
        (row,) = stats._burn_json(top=8)["top_burn"]
        assert (row["total"], row["bad"]) == (200, 150)
        # a full window after that both halves are stale
        stats.fold(heat, np.zeros(100, dtype=int),
                   bad_mask=np.zeros(100, dtype=bool), now=t0 + 17.0)
        burn.fold("t", 100, 0, now=t0 + 17.0)
        (row,) = stats._burn_json(top=8)["top_burn"]
        assert (row["total"], row["bad"], row["burn_rate"]) == (100, 0, 0.0)
        assert burn.burn("t", now=t0 + 17.0) == pytest.approx(0.0)

    def test_top_k_bound_caps_minted_labels(self):
        from authorino_tpu.utils import metrics as metrics_mod

        stats = TenantStats("test-lane3", top_k=4)
        heat = _StubHeat([f"cfg{i}" for i in range(100)])
        stats.fold(heat, np.arange(100), firing=np.full(100, -1))
        stats.flush()
        bound = metrics_mod.TENANT_LABEL_BOUNDS[
            "auth_server_tenant_requests_total"]
        assert len(stats._label_of) <= bound


# ---------------------------------------------------------------------------
# ISSUE 29: the plane's state as arrays by slot.  The loop over a batch's
# distinct tenants that it replaced (tenancy/stats.py at PR 28) stays here as
# the plain reference: same numbers for the same folds.
# ---------------------------------------------------------------------------


import threading
from typing import Any, Dict, Optional


class KeyedBurn:
    """The per-key burn window `TenantStats` folded into, one call a tenant,
    until ISSUE 29 (utils/slo.py at PR 28): the plain reference for the
    burn arrays it keeps now.

    The per-lane :class:`SloTracker` keeps a per-second ring — affordable
    once per lane, not once per tenant.  Here each key holds exactly TWO
    half-window buckets (current + previous) that rotate in place, so the
    whole table is O(live keys) memory and O(1) per fold: burn reads the
    sum of both buckets — a sliding window with half-window granularity,
    plenty for the noisy-neighbor detector and the /debug/tenants view.
    Keys idle past a full window are dropped on the amortized sweep."""

    def __init__(self, window_s: float = 60.0, objective: float = 0.999,
                 max_keys: int = 8192):
        self.window_s = float(window_s)
        self.half_s = self.window_s / 2.0
        self.budget = 1.0 - min(max(float(objective), 0.0), 0.999999)
        self.max_keys = int(max_keys)
        self._lock = threading.Lock()
        # key -> [bucket_start, total, bad, prev_total, prev_bad]
        self._k: Dict[str, list] = {}
        self._last_gc = 0.0

    def _rotate(self, rec: list, now: float) -> None:
        if now - rec[0] < self.half_s:
            return
        if now - rec[0] >= self.window_s:
            rec[3] = rec[4] = 0  # both halves stale
        else:
            rec[3], rec[4] = rec[1], rec[2]
        rec[0], rec[1], rec[2] = now, 0, 0

    def fold(self, key: str, n: int, bad: int,
             now: Optional[float] = None) -> None:
        if n <= 0:
            return
        now = time.monotonic() if now is None else now
        with self._lock:
            rec = self._k.get(key)
            if rec is None:
                rec = self._k[key] = [now, 0, 0, 0, 0]
            self._rotate(rec, now)
            rec[1] += int(n)
            rec[2] += int(bad)
            if len(self._k) > self.max_keys or \
                    now - self._last_gc > self.window_s:
                self._last_gc = now
                for k in [k for k, r in self._k.items()
                          if now - r[0] > self.window_s]:
                    self._k.pop(k, None)

    def counts(self, key: str, now: Optional[float] = None):
        now = time.monotonic() if now is None else now
        with self._lock:
            rec = self._k.get(key)
            if rec is None:
                return 0, 0
            self._rotate(rec, now)
            return rec[1] + rec[3], rec[2] + rec[4]

    def burn(self, key: str, now: Optional[float] = None) -> float:
        total, bad = self.counts(key, now=now)
        if not total:
            return 0.0
        return (bad / total) / self.budget

    def to_json(self, top: int = 8,
                now: Optional[float] = None) -> Dict[str, Any]:
        now = time.monotonic() if now is None else now
        rows = []
        with self._lock:
            for k, rec in self._k.items():
                total = rec[1] + rec[3]
                bad = rec[2] + rec[4]
                if total:
                    rows.append((k, round((bad / total) / self.budget, 4),
                                 total, bad))
        rows.sort(key=lambda r: -r[1])
        return {
            "window_s": self.window_s,
            "keys": len(rows),
            "top_burn": [{"key": k, "burn_rate": b, "total": t, "bad": d}
                         for k, b, t, d in rows[:top]],
        }


class _LoopStats:
    """`TenantStats` at PR 28, cut to what a fold writes and a reader
    reads; where it called `.labels(...).inc()` it adds into `counted`."""

    def __init__(self, lane, top_k, burn_window_s):
        self.lane, self.top_k = lane, top_k
        self.t = {}       # name -> dict of the old _TenantCounters slots
        self.lane_delta = {}
        self.burn = KeyedBurn(window_s=burn_window_s)
        self.label_of = {}
        self.counted = {}
        self.sunk = []
        self.total_requests = 0

    def fold(self, heat, rows, firing=None, shards=None, waits=None,
             bad_mask=None, denied_mask=None, lane=None, now=0.0):
        rows = np.asarray(rows, dtype=np.int64)
        n = int(rows.size)
        lane = lane or self.lane
        self.total_requests += n
        flat = rows
        if shards is not None and heat.configs_per_shard:
            flat = np.asarray(shards) * heat.configs_per_shard + rows
        if denied_mask is None and firing is not None:
            denied_mask = np.asarray(firing) >= 0
        uniq, inv = np.unique(flat, return_inverse=True)
        tot = np.bincount(inv, minlength=len(uniq))
        den = (np.bincount(inv[denied_mask], minlength=len(uniq))
               if denied_mask is not None and np.any(denied_mask)
               else np.zeros(len(uniq), dtype=np.int64))
        if waits is not None:
            wsum = np.bincount(inv, weights=waits, minlength=len(uniq))
            wmin = np.full(len(uniq), np.inf)
            np.minimum.at(wmin, inv, waits)
        bad = None
        if bad_mask is not None:
            bad = (np.bincount(inv[bad_mask], minlength=len(uniq))
                   if np.any(bad_mask)
                   else np.zeros(len(uniq), dtype=np.int64))
        per_lane = self.lane_delta.setdefault(lane, {})
        for i, u in enumerate(uniq):
            name = heat.name(int(u))
            if not name:
                continue
            c = self.t.get(name)
            if c is None:
                c = self.t[name] = dict(
                    requests=0, denies=0, slo_bad=0, wait_ewma=0.0,
                    rate_ewma=0.0, rate_t=now, rate_pend=0, last_seen=now)
            k = int(tot[i])
            c["requests"] += k
            c["denies"] += int(den[i])
            c["last_seen"] = now
            c["rate_pend"] += k
            dt = now - c["rate_t"]
            if dt > 0.05:
                inst = c["rate_pend"] / dt
                c["rate_ewma"] = inst if not c["rate_ewma"] else \
                    0.7 * c["rate_ewma"] + 0.3 * inst
                c["rate_t"] = now
                c["rate_pend"] = 0
            if waits is not None:
                mean = float(wsum[i]) / k
                c["wait_ewma"] = mean if not c["wait_ewma"] else \
                    0.8 * c["wait_ewma"] + 0.2 * mean
                self.sunk.append((name, mean, float(wmin[i]), now))
            b = int(bad[i]) if bad is not None else 0
            if b:
                c["slo_bad"] += b
            if bad is not None:
                self.burn.fold(name, k, b, now=now)
            d = per_lane.setdefault(name, [0, 0, 0])
            d[0] += k
            d[1] += int(den[i])
            d[2] += b

    def shares(self):
        total = sum(c["rate_ewma"] for c in self.t.values())
        if total <= 0:
            return {}
        return {t: c["rate_ewma"] / total for t, c in self.t.items()
                if c["rate_ewma"] > 0}

    def flush(self):
        ranked = sorted(self.t.items(), key=lambda kv: -kv[1]["requests"])
        for name, _ in ranked[:self.top_k]:
            if name not in self.label_of and len(self.label_of) < 32:
                self.label_of[name] = name
        for lane, per in self.lane_delta.items():
            for name, amounts in per.items():
                label = self.label_of.get(name, "other")
                for family, amount in zip(("requests", "denied", "slo_bad"),
                                          amounts):
                    if amount:
                        key = (family, lane, label)
                        self.counted[key] = self.counted.get(key, 0) + amount
        self.lane_delta.clear()
        return {self.label_of[name]: round(c["wait_ewma"], 6)
                for name, c in self.t.items() if name in self.label_of}

    def top(self, top=16):
        ranked = sorted(self.t.items(), key=lambda kv: -kv[1]["requests"])
        total_rate = sum(c["rate_ewma"] for _, c in ranked) or 1.0
        return [{
            "tenant": name, "requests": c["requests"],
            "denies": c["denies"], "slo_bad": c["slo_bad"],
            "queue_wait_ewma_ms": round(c["wait_ewma"] * 1e3, 3),
            "share": round(c["rate_ewma"] / total_rate, 4),
        } for name, c in ranked[:top]]


def _tenant_counters(lane, labels):
    from prometheus_client import REGISTRY

    out = {}
    for family in ("requests", "denied", "slo_bad"):
        for label in labels:
            v = REGISTRY.get_sample_value(
                f"auth_server_tenant_{family}_total",
                {"lane": lane, "tenant": label})
            if v:
                out[(family, lane, label)] = int(v)
    return out


@pytest.mark.parametrize("seed", [21, 22])
@pytest.mark.parametrize("n_shards", [None, 2])
@pytest.mark.parametrize("denied_as", ["firing", "denied_mask"])
def test_array_fold_equals_the_loop_it_replaced(seed, n_shards, denied_as):
    rng = np.random.default_rng(seed)
    G = 60
    tag = f"eq{seed}-{n_shards}-{denied_as}"
    n_rows = G * (n_shards or 1)
    names = [f"{tag}/t{i}" for i in range(n_rows)]
    names[5] = ""  # a padded row: no tenant
    heat = prov_mod.HeatMap(names, [["r0", "r1"]] * n_rows, 2,
                            configs_per_shard=G if n_shards else None)
    stats = TenantStats(tag, top_k=4, burn_window_s=4.0)
    loop = _LoopStats(tag, top_k=4, burn_window_s=4.0)
    sunk = []
    stats.wait_sink = lambda *a: sunk.append(a)
    lanes = [None, f"{tag}-host"]
    now = 100.0
    labels_seen = set()
    for step in range(120):
        # inside the 50 ms rate window, past it, and past half and whole
        # burn windows
        now += float(rng.choice([0.004, 0.02, 0.08, 0.5, 2.5, 5.0],
                                p=[0.3, 0.3, 0.2, 0.1, 0.05, 0.05]))
        B = int(rng.integers(1, 200))
        # zipf-ish rows: a few tenants hot, most cold
        rows = np.minimum(rng.zipf(1.3, B) - 1, G - 1)
        shards = rng.integers(0, n_shards, B) if n_shards else None
        firing = np.where(rng.random(B) < 0.4, rng.integers(0, 2, B), -1)
        kw = dict(shards=shards, lane=lanes[step % 2], now=now)
        if denied_as == "firing":
            kw["firing"] = firing
        else:
            kw["denied_mask"] = firing >= 0
        if step % 3:
            kw["waits"] = rng.random(B) * 0.1
        if step % 4:
            kw["bad_mask"] = rng.random(B) < (0.5 if step % 8 == 1 else 0.0)
        stats.fold(heat, rows, **kw)
        loop.fold(heat, rows, **kw)
        if step % 25 == 24:
            stats.flush(now=now)
            gauges = loop.flush()
            labels_seen |= set(loop.label_of) | {"other"}
            assert stats._label_of == loop.label_of
            for label, wait in gauges.items():
                from prometheus_client import REGISTRY

                assert REGISTRY.get_sample_value(
                    "auth_server_tenant_queue_wait_seconds",
                    {"tenant": label}) == pytest.approx(wait, abs=1e-6)
    got = stats.to_json()
    assert got["tenants_seen"] == len(loop.t)
    assert got["requests_total"] == loop.total_requests
    assert [r["tenant"] for r in got["top"]] == \
        [r["tenant"] for r in loop.top()]
    for mine, theirs in zip(got["top"], loop.top()):
        assert mine == pytest.approx(theirs, rel=1e-9, abs=1e-9)
    # the whole burn table (the top 8 of /debug/tenants are cut from it;
    # ties at one rate may be cut differently)
    want = {r["key"]: r for r in loop.burn.to_json(top=n_rows)["top_burn"]}
    mine = {r["key"]: r for r in stats._burn_json(top=n_rows)["top_burn"]}
    assert got["slo_burn"]["keys"] == len(want) == len(mine) > 8
    assert len(got["slo_burn"]["top_burn"]) == 8
    assert got["slo_burn"]["top_burn"][0]["burn_rate"] == \
        max(r["burn_rate"] for r in want.values())
    for key, r in want.items():
        assert mine[key] == pytest.approx(r), key
    assert stats.shares() == pytest.approx(loop.shares(), rel=1e-9)
    hot = names[0] or names[1]
    assert stats.share(hot) == pytest.approx(loop.shares().get(hot, 0.0))
    assert {k: v["requests"] for k, v in stats.export_fold().items()} == \
        {k: c["requests"] for k, c in loop.t.items()}
    # the wait sink heard the same (tenant, mean, least, now) calls; inside
    # a batch the arrays go by slot where the loop went by row
    assert len(sunk) == len(loop.sunk)
    for mine, theirs in zip(sorted(sunk, key=lambda c: (c[3], c[0])),
                            sorted(loop.sunk, key=lambda c: (c[3], c[0]))):
        assert mine[0] == theirs[0]
        assert mine[1:] == pytest.approx(theirs[1:])
    # the counters, as a scrape reads them: the registry drains first
    loop.flush()
    labels_seen |= set(loop.label_of) | {"other"}
    read = {}
    for lane in (tag, f"{tag}-host"):
        read.update(_tenant_counters(lane, labels_seen))
    assert read == loop.counted


def test_idle_tenants_are_dropped_and_rows_resolve_again():
    stats = TenantStats("gc-lane", max_tenants=4, gc_idle_s=10.0)
    heat = _StubHeat([f"gc/t{i}" for i in range(8)])
    stats.fold(heat, np.arange(8), firing=np.full(8, -1), now=1.0)
    stats.fold(heat, np.array([6, 7, 7]), firing=np.array([0, -1, 0]),
               now=50.0)
    stats.flush(now=50.0)  # over max_tenants: the six idle ones go
    got = stats.to_json()
    assert got["tenants_seen"] == 2
    assert {r["tenant"]: (r["requests"], r["denies"]) for r in got["top"]} \
        == {"gc/t6": (2, 1), "gc/t7": (3, 1)}
    # a dropped tenant comes back as new; a kept one keeps its counts
    stats.fold(heat, np.array([0, 7]), firing=np.array([-1, -1]), now=51.0)
    by = {r["tenant"]: r["requests"] for r in stats.to_json()["top"]}
    assert by == {"gc/t0": 1, "gc/t6": 2, "gc/t7": 4}


def test_detector_reads_no_shares_without_pressure():
    """`check` runs every 0.1 s on the thread that completes batches: with
    no pressure and nobody contained it needs no share, and asks for none."""
    book = WeightBook()
    book.rebuild({"a": None})
    stats = TenantStats("idle-detector")
    asked = []
    stats.shares = lambda: asked.append(1) or {}
    wait = [0.0]
    det = NoisyNeighborDetector(book, stats, wait_ewma=lambda: wait[0],
                                target_s=lambda: 0.05, lane="idle-detector")
    det._hot_since["a"] = 1.0
    det.check(now=10.0)
    assert not asked and not det._hot_since
    wait[0] = 1.0
    det.check(now=11.0)
    assert asked


# ---------------------------------------------------------------------------
# noisy-neighbor containment: detect, contain, auto-release
# ---------------------------------------------------------------------------


class TestContainment:
    def _detector(self, wait=None):
        wait = [0.5] if wait is None else wait
        book = WeightBook()
        book.rebuild({"hot": None, "c1": None, "c2": None, "c3": None})
        stats = TenantStats("contain-lane")
        det = NoisyNeighborDetector(
            book, stats, wait_ewma=lambda: wait[0],
            target_s=lambda: 0.05, lane="contain-lane",
            threshold=2.0, sustain_s=0.0, release_s=0.0)
        return book, stats, det, wait

    def _feed(self, stats, hot_frac, t0, k0=0, n=10):
        heat = _StubHeat(["hot", "c1", "c2", "c3"])
        hot_n = int(16 * hot_frac)
        rows = np.array([0] * hot_n + [1, 2, 3] * ((16 - hot_n) // 3 + 1))
        for k in range(n):
            stats.fold(heat, rows[:16], firing=np.full(16, -1),
                       now=t0 + 0.1 * (k0 + k + 1))

    def test_contain_fires_and_auto_releases(self):
        book, stats, det, wait = self._detector()
        t0 = time.monotonic()
        self._feed(stats, hot_frac=0.9, t0=t0)
        ring0 = RECORDER.events_total
        det.check(now=t0 + 2.0)
        assert det.is_contained("hot")
        assert det.contain_total == 1
        assert RECORDER.events_total > ring0  # tenant-contained recorded
        # decay: traffic rebalances and the global wait clears
        self._feed(stats, hot_frac=0.25, t0=t0 + 2.0, k0=20, n=30)
        wait[0] = 0.0
        det.check(now=t0 + 10.0)
        assert not det.is_contained("hot")
        assert det.release_total == 1

    def test_no_containment_without_global_pressure(self):
        """A hot tenant on an idle box is just traffic: the fair cut
        already bounds its share — containment needs BOTH conditions."""
        book, stats, det, wait = self._detector(wait=[0.0])
        t0 = time.monotonic()
        self._feed(stats, hot_frac=0.9, t0=t0)
        det.check(now=t0 + 2.0)
        assert not det.has_contained()

    def test_contained_pacing_rejects_past_allowance(self):
        book, stats, det, wait = self._detector()
        det.allowance_rps = 1.0
        t0 = time.monotonic()
        self._feed(stats, hot_frac=0.9, t0=t0)
        det.check(now=t0 + 2.0)
        assert det.is_contained("hot")
        now = t0 + 2.001  # on the detector's own (synthetic) timeline
        allowed = sum(1 for _ in range(50)
                      if not det.pace_reject("hot", now=now))
        assert 1 <= allowed < 50  # the burst allowance, then paced drops

    def test_engine_wires_contained_rejection_typed(self):
        engine = build_engine(n_tenants=2)
        det = engine.tenancy.detector
        det._contained["t0"] = {"since": time.monotonic()}
        from authorino_tpu.tenancy.quota import TokenBucket

        det._pacers["t0"] = TokenBucket(0.000001, burst=0.000001)

        async def one():
            try:
                await engine.submit(doc(1), "t0")
                return None
            except CheckAbort as e:
                return e

        e = run(one())
        assert e is not None and e.code == RESOURCE_EXHAUSTED
        assert "tenant t0" in e.message
        assert engine.admission.state == ADMIT
        rej = engine.tenancy.admission.rejected["t0"]
        assert rej[R_TENANT_CONTAINED] == 1
        det._contained.clear()
        det._pacers.clear()


# ---------------------------------------------------------------------------
# lane parity (satellite): degraded batches still burn the right tenant
# ---------------------------------------------------------------------------


class TestLaneParity:
    def test_degrade_lane_feeds_tenant_fold(self):
        """Breaker OPEN -> whole batches decide via the host oracle: the
        tenant counters must move exactly like the device lane's."""
        engine = build_engine(n_tenants=2, breaker_threshold=1)
        for _ in range(3):
            engine.breaker.record_failure()

        async def burst():
            await asyncio.gather(*(
                engine.submit(doc(i, allow=False), f"t{i % 2}")
                for i in range(8)))

        run(burst())
        j = engine.tenancy.stats.to_json()
        by = {r["tenant"]: r for r in j["top"]}
        assert by["t0"]["requests"] == 4 and by["t1"]["requests"] == 4
        assert by["t0"]["denies"] == 4 and by["t1"]["denies"] == 4

    def test_device_and_host_lane_counts_agree(self):
        """The same workload with and without a forced-open breaker lands
        identical per-tenant request/deny counts (parity across lanes)."""
        counts = {}
        for mode, threshold in (("device", 5), ("degrade", 1)):
            engine = build_engine(n_tenants=2, breaker_threshold=threshold)
            if mode == "degrade":
                for _ in range(3):
                    engine.breaker.record_failure()

            async def burst(engine=engine):
                await asyncio.gather(*(
                    engine.submit(doc(i, allow=(i % 4 != 1)), f"t{i % 2}")
                    for i in range(16)))

            run(burst())
            j = engine.tenancy.stats.to_json()
            counts[mode] = {r["tenant"]: (r["requests"], r["denies"])
                            for r in j["top"]}
        assert counts["device"] == counts["degrade"]


# ---------------------------------------------------------------------------
# stratified decision sampling (satellite)
# ---------------------------------------------------------------------------


class TestStratifiedDecisions:
    def test_cold_tenant_records_survive_hot_flood(self):
        log = prov_mod.DecisionLog(capacity=8, sample_n=1,
                                   tenant_capacity=2)
        log.record(lane="l", host="h", authconfig="cold", verdict=True,
                   rule=None, rule_index=-1, latency_ms=1, generation=1)
        for i in range(50):
            log.record(lane="l", host="h", authconfig="hot", verdict=False,
                       rule="0:x", rule_index=0, latency_ms=1, generation=1)
        # the global ring is all hot now...
        assert all(r["authconfig"] == "hot"
                   for r in log.to_json()["records"])
        # ...but the cold tenant's sub-ring survives
        cold = log.to_json(tenant="cold")["records"]
        assert len(cold) == 1 and cold[0]["authconfig"] == "cold"

    def test_at_most_one_record_per_tenant_per_batch(self):
        saved = (prov_mod.DECISIONS.capacity, prov_mod.DECISIONS.sample_n)
        prov_mod.DECISIONS.configure(sample_n=1)
        try:
            heat = prov_mod.HeatMap(["hot", "cold"], [["r"], ["r"]], 1)
            rows = np.array([0] * 20 + [1])
            firing = np.full(21, -1)
            before = prov_mod.DECISIONS.records_total
            prov_mod.fold_and_sample(heat, rows, firing, 21, lane="l")
            got = prov_mod.DECISIONS.records_total - before
            # one batch, two tenants -> exactly two records at 1-in-1
            assert got == 2
            names = [r["authconfig"]
                     for r in prov_mod.DECISIONS.to_json(n=2)["records"]]
            assert set(names) == {"hot", "cold"}
        finally:
            prov_mod.DECISIONS.configure(capacity=saved[0],
                                         sample_n=saved[1])

    def test_single_tenant_batches_still_one_record_per_batch(self):
        """The perf-guard contract holds: one tenant -> at most one record
        per batch whatever the batch size."""
        saved = prov_mod.DECISIONS.sample_n
        prov_mod.DECISIONS.configure(sample_n=1)
        try:
            heat = prov_mod.HeatMap(["only"], [["r"]], 1)
            before = prov_mod.DECISIONS.records_total
            prov_mod.fold_and_sample(heat, np.zeros(64, dtype=int),
                                     np.full(64, -1), 64, lane="l")
            assert prov_mod.DECISIONS.records_total - before == 1
        finally:
            prov_mod.DECISIONS.configure(sample_n=saved)

    def test_cold_tenant_first_appearance_always_samples(self):
        saved = prov_mod.DECISIONS.sample_n
        prov_mod.DECISIONS.configure(sample_n=1000)
        try:
            log = prov_mod.DECISIONS
            assert log.should_sample_tenant("brand-new-tenant", 5)
            assert not log.should_sample_tenant("brand-new-tenant", 5)
        finally:
            prov_mod.DECISIONS.configure(sample_n=saved)


# ---------------------------------------------------------------------------
# tenant-label cardinality lint (satellite, wired as tier-1)
# ---------------------------------------------------------------------------


class TestCardinalityLint:
    def test_registry_lints_clean(self):
        from authorino_tpu.analysis.metrics_catalog import (
            tenant_cardinality_lint,
        )

        assert tenant_cardinality_lint() == []

    def test_planted_violation_is_caught(self):
        from authorino_tpu.analysis.metrics_catalog import (
            _PlantedTenantFamily,
            tenant_cardinality_lint,
            tenant_lint_self_test,
        )

        violations = tenant_cardinality_lint(
            extra=(_PlantedTenantFamily(),))
        assert any("planted_violation" in v for v in violations)
        # the combined self-test (what --verify-fixtures runs) is clean
        assert tenant_lint_self_test() == []

    def test_stale_bound_is_caught(self):
        from authorino_tpu.analysis.metrics_catalog import (
            tenant_cardinality_lint,
        )
        from authorino_tpu.utils import metrics as metrics_mod

        bounds = dict(metrics_mod.TENANT_LABEL_BOUNDS)
        bounds["auth_server_tenant_ghost_total"] = 8
        assert any("ghost" in v for v in tenant_cardinality_lint(bounds))

    def test_missing_bound_is_caught(self):
        from authorino_tpu.analysis.metrics_catalog import (
            tenant_cardinality_lint,
        )
        from authorino_tpu.utils import metrics as metrics_mod

        bounds = dict(metrics_mod.TENANT_LABEL_BOUNDS)
        bounds.pop("auth_server_tenant_requests_total")
        assert any("tenant_requests" in v
                   for v in tenant_cardinality_lint(bounds))


# ---------------------------------------------------------------------------
# per-tenant canary guard (tenant-rejection-rate)
# ---------------------------------------------------------------------------


class TestTenantCanaryGuard:
    def test_tenant_rejection_delta_breaches(self):
        from authorino_tpu.runtime.change_safety import CanaryGuard

        guard = CanaryGuard(changed={"t"}, check_interval_s=0.0)
        heat = _StubHeat(["t"])
        rows = np.zeros(16, dtype=int)
        firing = np.full(16, -1)
        for _ in range(4):
            guard.observe_batch(False, rows, firing, heat)
            guard.observe_batch(True, rows, firing, heat)
        # the canary cohort's tenant eats rejections the baseline doesn't
        guard.observe_tenant_rejection(True, "t", n=64)
        breach = guard.breach(force=True)
        assert breach is not None
        assert "tenant-rejection-rate" in breach["guards"]
        assert "t" in breach["suspects"]

    def test_unchanged_tenant_rejections_do_not_breach(self):
        from authorino_tpu.runtime.change_safety import CanaryGuard

        guard = CanaryGuard(changed={"other"}, check_interval_s=0.0)
        heat = _StubHeat(["t"])
        rows = np.zeros(16, dtype=int)
        firing = np.full(16, -1)
        for _ in range(4):
            guard.observe_batch(False, rows, firing, heat)
            guard.observe_batch(True, rows, firing, heat)
        guard.observe_tenant_rejection(True, "t", n=64)
        assert guard.breach(force=True) is None


# ---------------------------------------------------------------------------
# /debug/tenants + /debug/decisions?tenant=
# ---------------------------------------------------------------------------


class TestDebugSurfaces:
    def test_debug_tenants_endpoint(self):
        from aiohttp.test_utils import TestClient, TestServer

        from authorino_tpu.service.http_server import build_app

        engine = build_engine(n_tenants=2)

        async def body():
            await engine.submit(doc(1), "t0")
            client = TestClient(TestServer(build_app(engine)))
            await client.start_server()
            try:
                resp = await client.get("/debug/tenants")
                assert resp.status == 200
                plane = await resp.json()
                resp2 = await client.get("/debug/decisions?tenant=t0")
                assert resp2.status == 200
                dec = await resp2.json()
            finally:
                await client.close()
            return plane, dec

        plane, dec = run(body())
        assert plane["enabled"] is True
        assert plane["stats"]["requests_total"] >= 1
        assert dec["tenant"] == "t0"

    def test_engine_debug_vars_carry_tenancy(self):
        engine = build_engine(n_tenants=1)
        dv = engine.debug_vars()
        assert dv["tenancy"]["enabled"] is True
        assert "containment" in dv["tenancy"]
        assert "fair_cut" in dv["tenancy"]


# ---------------------------------------------------------------------------
# repo hygiene: the new subsystem stays clean
# ---------------------------------------------------------------------------


def test_tenancy_code_stays_clean():
    import os

    from authorino_tpu.analysis.code_lint import lint_paths

    root = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "authorino_tpu", "tenancy")
    assert [str(f) for f in lint_paths([root])] == []
