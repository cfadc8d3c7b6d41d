"""The native lane's keep (ISSUE 35): `post` keeps a completed cut's rows and
one fold takes many kept cuts.  Every count is a sum, so cuts folded together
leave what the same cuts folded one by one leave; every reader that folds
first sees every completed cut; a fold that fails loses no other cut."""

from __future__ import annotations

import time
import types

import numpy as np
import pytest

from authorino_tpu.runtime import PolicyEngine
from authorino_tpu.runtime import native_frontend as nf_mod
from authorino_tpu.runtime import provenance as prov_mod
from authorino_tpu.runtime.kernel_cost import LEDGER
from authorino_tpu.runtime.native_frontend import NativeFrontend, _SnapRec
from authorino_tpu.utils import metrics as metrics_mod

G, B, E = 48, 64, 3
SLO_MS = 250.0


def _frontend(started=False):
    engine = PolicyEngine(max_batch=64, mesh=None)
    fe = NativeFrontend(engine, port=0, max_batch=B, slo_ms=SLO_MS,
                        lane_select=False)
    if not started:
        fe._mod = types.SimpleNamespace(fe_complete_batch=lambda *a: None)
    return fe


def _snapshot(tag, snap_id, firing=True, shards=False):
    """A snapshot record with a heat map of its own: G configs (two shards of
    G/2 on the sharded form), every seventh a hybrid one."""
    heat = prov_mod.HeatMap(
        [f"{tag}/c{i}" for i in range(G)], [["r0", "r1", "r2"]] * G,
        E if firing else 0, configs_per_shard=G // 2 if shards else None)
    keys = ([(s, r) for s in range(2) for r in range(G // 2)] if shards
            else list(range(G)))
    labels = {key: (tag, f"c{i}") for i, key in enumerate(keys)}
    heat.bind_authconfigs(labels, hybrid=keys[::7])
    sharded = (types.SimpleNamespace(configs_per_shard=G // 2) if shards
               else None)
    return _SnapRec(snap_id=snap_id, policy=None, params=None, encoder=None,
                    sharded=sharded, heat=heat, row_labels=labels)


def _cuts(seed, n, firing=True, shards=False, rows_from=0):
    """`n` cuts of up to B rows over configs `rows_from`..G: rows, shards,
    verdict, firing, round trip (every fourth past the SLO), and whether a
    host-lane worker completed it."""
    rng = np.random.default_rng(seed)
    out = []
    for k in range(n):
        count = int(rng.integers(1, B + 1))
        flat = rng.integers(rows_from, G, count)
        denied = rng.random(count) < 0.4
        out.append(dict(
            count=count,
            rows=(flat % (G // 2) if shards else flat).astype(np.int32),
            shards=(flat // (G // 2)).astype(np.int32) if shards else None,
            verdict=(~denied).astype(np.uint8),
            firing=(np.where(denied, rng.integers(0, E, count), -1)
                    .astype(np.int32) if firing else None),
            dispatch_s=(2 * SLO_MS if k % 4 == 3 else 1.0) * 1e-3,
            device=k % 5 != 4))
    return out


def _keep(fe, rec, cut):
    pad = B if cut["device"] else 0
    fe._post_complete_telemetry(
        rec, cut["count"], pad, 0, cut["rows"], cut["shards"], cut["verdict"],
        cut["dispatch_s"], time.time_ns(),
        device_rows=cut["count"] if cut["device"] else 0,
        device=cut["device"], firing=cut["firing"],
        dedup=(cut["count"], 0, cut["count"], 0) if cut["device"] else None)


def _hist_count(family, lane="native"):
    child = family.labels(lane)
    return sum(b.get() for b in child._buckets)


def _reading(fe, recs):
    """Everything the folds add into, tenants by name (a tenant's slot is in
    the order its rows were first met, which the grouping changes)."""
    stats = fe.tenancy.stats
    tenants = {name: (t["requests"], t["denies"], t["slo_bad"])
               for name, t in stats.export_fold().items()}
    delta = stats._lane_delta.get("native")
    lane_delta = ({name: tuple(delta[:, slot].tolist())
                   for name, slot in stats._slot.items()}
                  if delta is not None else {})
    return {
        "heat": [(r.heat._counts.tolist(), r.heat.requests.tolist(),
                  r.heat.ok.tolist(), r.heat.seen.tolist()) for r in recs],
        "tenants": tenants,
        "tenant_lane_delta": lane_delta,
        "tenant_requests_total": stats.total_requests,
        "lane_rows": dict(fe.lanes.rows),
        "lane_device_batches": fe.lanes.cost.device_batches,
        "lane_burn": {k: (v[0] > 0, v[1] > 0)
                      for k, v in fe.lanes.cost._burn.items()},
        "slo": (fe.slo.total, fe.slo.bad_total),
    }


def _series():
    return {
        "batch_size": _hist_count(metrics_mod.batch_size),
        "pad_occupancy": _hist_count(metrics_mod.batch_pad_occupancy),
        "dispatch": _hist_count(metrics_mod.device_dispatch_duration),
        "dedup_ratio": _hist_count(metrics_mod.batch_dedup_ratio),
        "cache_misses": metrics_mod.verdict_cache_misses.labels(
            "native")._value.get(),
    }


def _moved(before):
    return {k: v - before[k] for k, v in _series().items()}


@pytest.mark.parametrize("shards", [False, True], ids=["flat", "sharded"])
@pytest.mark.parametrize("firing", [True, False], ids=["firing", "verdict"])
@pytest.mark.parametrize("keep", [1, 3, 8, 16])
def test_cuts_folded_together_equal_cuts_folded_one_by_one(keep, firing,
                                                           shards):
    """48 cuts of two snapshots, interleaved, host-lane cuts among them:
    folded `keep` at a time and folded one by one they leave the same heat
    map (rule counts, requests, OKs, the gate's `seen`), the same tenant
    plane, lane deltas, SLO totals and batch series, element for element."""
    assert nf_mod.KEEP_CUTS == 16
    tag = f"keep{keep}-{firing}-{shards}"
    cuts = _cuts(35, 48, firing=firing, shards=shards)
    readings, series = [], []
    for together in (False, True):
        fe = _frontend()
        recs = [_snapshot(f"{tag}/s{i}", i + 1, firing, shards)
                for i in range(2)]
        # cuts in flight besides the one completing: `post` only keeps
        fe._rb_inflight = 5 if together else 0
        before = _series()
        folds = LEDGER.snapshot("native")["telemetry_folds"]
        for k, cut in enumerate(cuts):
            _keep(fe, recs[k % 2], cut)
            if together and (k + 1) % keep == 0:
                fe._fold_kept()  # a no-op where the 16th cut folded itself
        assert not fe._keep
        vars_post = {"folds": fe._folds, "folded_cuts": fe._folded_cuts,
                     "keep_max": fe._keep_max}
        n_folds = 48 // keep if together else 48
        assert vars_post == {"folds": n_folds, "folded_cuts": 48,
                             "keep_max": keep if together else 1}
        assert LEDGER.snapshot("native")["telemetry_folds"] - folds == n_folds
        # a fold is one heat fold and one tenant fold a snapshot in it
        per_fold = min(keep, 2) if together else 1
        if firing:
            assert sum(r.heat.fold_calls for r in recs) == n_folds * per_fold
        assert fe.tenancy.stats.fold_calls == n_folds * per_fold
        readings.append(_reading(fe, recs))
        series.append(_moved(before))
    assert readings[0] == readings[1]
    assert series[0] == series[1]
    one = readings[0]
    assert one["slo"] == (sum(c["count"] for c in cuts),
                          sum(c["count"] for c in cuts[3::4]))
    assert sum(t[0] for t in one["tenants"].values()) == one["slo"][0]
    assert series[0]["batch_size"] == sum(c["device"] for c in cuts)


def test_a_fold_is_due_by_count_by_age_or_with_nothing_in_flight():
    fe = _frontend()
    rec = _snapshot("due", 1)
    cuts = _cuts(1, 40)
    # light load: the completing cut is the only one in flight, it folds
    fe._rb_inflight = 1
    _keep(fe, rec, dict(cuts[0], device=True))
    assert fe._folds == 1 and not fe._keep
    # a host-lane worker's cut is not among the cuts in flight
    _keep(fe, rec, dict(cuts[1], device=False))
    assert fe._folds == 1 and len(fe._keep) == 1
    fe._rb_inflight = 0
    _keep(fe, rec, dict(cuts[2], device=False))
    assert fe._folds == 2 and fe._keep_max == 2
    # saturation: kept until the keep holds KEEP_CUTS
    fe._rb_inflight = 6
    for cut in cuts[3:3 + nf_mod.KEEP_CUTS - 1]:
        _keep(fe, rec, cut)
    assert fe._folds == 2 and len(fe._keep) == nf_mod.KEEP_CUTS - 1
    _keep(fe, rec, cuts[20])
    assert fe._folds == 3 and fe._keep_max == nf_mod.KEEP_CUTS
    # a trickle: the oldest kept cut grows old, the next cut folds it, and
    # so does the readback loop's poll with nothing ready
    _keep(fe, rec, cuts[21])
    fe._keep[0] = fe._keep[0]._replace(
        kept_at=time.monotonic() - 2 * nf_mod.KEEP_AGE_S)
    _keep(fe, rec, cuts[22])
    assert fe._folds == 4 and fe._keep_max == nf_mod.KEEP_CUTS
    _keep(fe, rec, cuts[23])
    fe._fold_kept_if(nf_mod.KEEP_AGE_S)
    assert fe._folds == 4 and len(fe._keep) == 1
    fe._keep[0] = fe._keep[0]._replace(
        kept_at=time.monotonic() - 2 * nf_mod.KEEP_AGE_S)
    fe._fold_kept_if(nf_mod.KEEP_AGE_S)
    assert fe._folds == 5 and not fe._keep
    assert fe._folded_cuts == 3 + nf_mod.KEEP_CUTS + 3
    used = cuts[:3 + nf_mod.KEEP_CUTS - 1] + cuts[20:24]
    assert rec.heat.requests.sum() + _hybrid_oks(rec, used) == sum(
        c["count"] for c in used)


def _hybrid_oks(rec, cuts):
    """Kernel-allowed requests of hybrid configs: the pipeline counts them."""
    return sum(int((rec.heat.hybrid[c["rows"]] & (c["verdict"] != 0)).sum())
               for c in cuts)


def _child_value(family, *labels):
    return family.labels(*labels)._value.get()


@pytest.mark.parametrize("reader", ["drain", "debug_vars", "debug_tenants",
                                    "retire", "stop"])
def test_every_reader_sees_every_completed_cut_with_the_keep_half_full(reader):
    """Eight cuts kept, none folded, the cadence and the readback loop's poll
    out of the way: the drain, /debug/vars, /debug/tenants' fold, a
    snapshot's retirement and stop() each leave every cut in the arrays (and,
    where they drain, in the Prometheus children)."""
    fe = _frontend(started=True)
    fe.hist_drain_s = 3600.0
    fe._fold_kept_if = lambda age_s: None
    tag = f"half-{reader}"
    rec = _snapshot(tag, 99)
    cuts = _cuts(7, nf_mod.KEEP_CUTS // 2)
    rows = sum(c["count"] for c in cuts)
    expect = rows - _hybrid_oks(rec, cuts)
    fe.start()
    stopped = False
    try:
        fe._rb_inflight = 5
        for cut in cuts:
            _keep(fe, rec, cut)
        fe._rb_inflight = 0
        assert len(fe._keep) == len(cuts) and fe._folds == 0
        assert rec.heat.requests.sum() == 0
        if reader == "drain":
            metrics_mod.drain()
        elif reader == "debug_vars":
            post = fe.debug_vars()["post"]
            assert (post["folds"], post["folded_cuts"], post["keep_max"]) == (
                1, len(cuts), len(cuts))
        elif reader == "debug_tenants":
            metrics_mod.fold_kept()  # what the handler runs before it reads
        elif reader == "retire":
            # what the dispatch loop does on EV_SNAP_RETIRED, a cut of the
            # snapshot kept after it included
            rec.retired = True
            fe._fold_kept()
            rec.heat.flush()
            fe._rb_inflight = 5
            _keep(fe, rec, cuts[0])
            fe._rb_inflight = 0
            fe._fold_kept()
            rows += cuts[0]["count"]
            expect += cuts[0]["count"] - _hybrid_oks(rec, cuts[:1])
        else:
            fe.stop()
            stopped = True
        assert not fe._keep and fe._folded_cuts >= len(cuts)
        assert rec.heat.requests.sum() == expect
        assert fe.slo.total == rows
        seen = fe.tenancy.stats.to_json()
        assert seen["requests_total"] == rows
        if reader != "debug_tenants":
            named = sum(_child_value(metrics_mod.authconfig_total, tag,
                                     f"c{i}") for i in range(G))
            assert named == expect
    finally:
        if not stopped:
            fe.stop()
    assert fe._fold_kept not in metrics_mod.KEEP_FOLDERS


@pytest.mark.parametrize("fault", ["tenant-plane", "heat-map", "one-cut",
                                   "whole-fold"])
def test_a_fold_that_fails_loses_no_other_cuts_counts(fault, monkeypatch):
    """A fold runs inside `post` of a cut whose slot is completed: whatever
    fails in it is logged, costs at most the part that failed, and never
    reaches `_complete_device_batch`'s caller (whose answer to an exception
    is a second, fail-closed completion of the same slot)."""
    fe = _frontend()
    rec, other = _snapshot(f"fail-{fault}", 1), _snapshot(f"ok-{fault}", 2)
    cuts = _cuts(3, 6)
    good = list(cuts)
    rows = sum(c["count"] for c in cuts)

    def boom(*a, **k):
        raise RuntimeError("planted")

    if fault == "tenant-plane":
        monkeypatch.setattr(fe.tenancy, "fold_grouped", boom)
    elif fault == "heat-map":
        monkeypatch.setattr(rec.heat, "fold", boom)
    elif fault == "one-cut":
        cuts[2] = dict(cuts[2], verdict=cuts[2]["verdict"][:-1].copy()
                       if cuts[2]["count"] > 1 else np.zeros(2, np.uint8))
    else:
        real = fe._fold_cut_arrays
        monkeypatch.setattr(
            fe, "_fold_cut_arrays",
            lambda group: boom() if group[0].rec is rec else real(group))
    fe._rb_inflight = 5
    for k, cut in enumerate(cuts):
        _keep(fe, rec, cut)
        _keep(fe, other, good[(k + 1) % len(good)])
    # the cut that completes with nothing else in flight folds them all,
    # through the whole of `_complete_device_batch`
    fe._rb_inflight = 1
    cols = np.zeros((B, 8), dtype=bool)
    cols[:, 0] = True
    bt = fe.batch_stages.begin(2, 0, B)
    bt.ready()
    fe._complete_device_batch(
        other, 2, 0, B, B, 0, np.arange(B, dtype=np.int32) % G, None,
        np.packbits(cols, axis=1, bitorder="little"), time.monotonic(),
        time.time_ns(), None, 0, bt)
    assert not fe._keep and fe._folds == 1
    assert fe._folded_cuts == 2 * len(cuts) + 1
    # the other snapshot's cuts, and every cut's scalar series, are whole
    assert other.heat.requests.sum() + _hybrid_oks(other, good) + int(
        other.heat.hybrid[np.arange(B) % G].sum()) == rows + B
    assert fe.slo.total == 2 * rows + B
    lost = {"tenant-plane": 0, "heat-map": 0, "whole-fold": rows,
            "one-cut": cuts[2]["count"]}[fault]
    kept = [c for k, c in enumerate(cuts) if not (fault == "one-cut" and k == 2)]
    if fault != "whole-fold":
        assert rec.heat.requests.sum() + _hybrid_oks(rec, kept) == rows - lost
    else:
        assert rec.heat.requests.sum() == 0
    tenants = fe.tenancy.stats.total_requests
    assert tenants == (0 if fault == "tenant-plane"
                       else 2 * rows + B - lost)
    if fault == "heat-map":
        assert rec.heat._counts.sum() == 0 < other.heat._counts.sum()
    elif fault != "whole-fold":
        assert rec.heat._counts.sum() == sum(
            int((c["firing"] >= 0).sum()) for c in kept)


def test_a_tenants_first_decision_is_sampled_once_a_fold_with_its_cuts_latency():
    """The decision log's gate is a tenant a fold: a tenant first seen in the
    fifth of eight kept cuts makes one record, with that cut's latency and
    that request's rule; a tenant in all eight makes one, from the first."""
    saved = prov_mod.DECISIONS.sample_n
    prov_mod.DECISIONS.configure(sample_n=64)
    try:
        fe = _frontend()
        rec = _snapshot("first", 1)
        cuts = _cuts(5, 8, rows_from=2)
        for k, cut in enumerate(cuts):
            cut["dispatch_s"] = (k + 1) * 1e-3
            cut["rows"][0] = 0       # tenant 0: in every cut, allowed
            cut["firing"][0] = -1
        cuts[4]["rows"][3 % cuts[4]["count"]] = 1   # tenant 1: here alone
        at = 3 % cuts[4]["count"]
        cuts[4]["firing"][at] = 2
        fe._rb_inflight = 5
        total = prov_mod.DECISIONS.records_total
        for cut in cuts:
            _keep(fe, rec, cut)
        fe._fold_kept()
        distinct = len(np.unique(np.concatenate([c["rows"] for c in cuts])))
        assert fe._sampled_decisions == distinct
        assert prov_mod.DECISIONS.records_total - total == distinct
        mine = prov_mod.DECISIONS.to_json(tenant="first/c1")["records"]
        assert [(r["verdict"], r["rule"], r["latency_ms"], r["generation"])
                for r in mine] == [("deny", "2:r2", 5.0, 1)]
        every = prov_mod.DECISIONS.to_json(tenant="first/c0")["records"]
        assert [(r["verdict"], r["latency_ms"]) for r in every] == [
            ("allow", 1.0)]
        # seen, both: the next fold samples neither again
        for cut in cuts:
            _keep(fe, rec, cut)
        fe._fold_kept()
        assert fe._sampled_decisions == distinct
        # a snapshot of its own has gates of its own: first decisions again
        fresh = _snapshot("first", 2)
        _keep(fe, fresh, cuts[4])
        fe._fold_kept()
        assert fe._sampled_decisions == distinct + len(
            np.unique(cuts[4]["rows"]))
    finally:
        prov_mod.DECISIONS.configure(sample_n=saved)


@pytest.mark.parametrize("firing", [True, False], ids=["firing", "verdict"])
def test_a_cut_resolved_in_one_native_call_keeps_what_the_python_path_kept(
        firing):
    """The keep's inputs of a cut completed through `fe_resolve_cut` (a
    `_Launched` of two size classes, a plan with repeated and cached rows, a
    cache small enough to evict) equal those the Python path hands it for the
    same cut (`resolve_cut` over the one readback, then the cache's commit):
    rows, verdict, firing, and the dedup tuple with its evictions.  Only the
    native completion counts `resolved_native`."""
    from authorino_tpu.native import load_library
    from authorino_tpu.native.verdict_cache import (NativeVerdictCache,
                                                    key_segments, plan_cut)
    from authorino_tpu.ops.pattern_eval import packed_width

    lib = load_library()
    if lib is None:
        pytest.skip("native library unavailable")
    rng = np.random.default_rng(45)
    lanes = []
    for native in (True, False):
        fe = _frontend()
        if native:
            fe._mod = lib
        fe._verdict_cache = NativeVerdictCache(24)
        kept = []
        fe._post_complete_telemetry = (
            lambda *a, kept=kept, **k: kept.append((a, k)))
        lanes.append((fe, _snapshot(f"resolve-{native}", 0x5EED_0035 + native,
                                    firing=firing), kept))
    E_w = E if firing else 0
    resolved = []
    for _ in range(6):
        count = int(rng.integers(2, B + 1))
        ids = rng.integers(0, 40, count)
        rows = (ids % G).astype(np.int32)
        keys = np.ascontiguousarray(ids.astype("<u4").view(np.uint8)
                                    .reshape(count, 4))
        segs = key_segments([keys])
        tokens, eligible = np.zeros(count, np.uint64), np.ones(count, bool)
        plans = [plan_cut(fe._verdict_cache, segs, count, tokens, eligible,
                          True) for fe, _, _ in lanes]
        u = len(plans[0].unique_rows)
        packed = rng.integers(0, 256, (u, packed_width(1 + 2 * E)),
                              dtype=np.uint8)
        launched = nf_mod._Launched()
        cls = rng.integers(0, 2, u)
        for c in range(2):
            at = np.nonzero(cls == c)[0]
            if len(at):
                launched.parts.append((packed[at], at, len(at), E_w))
        before = LEDGER.snapshot("native")["resolved_native"]
        for (fe, rec, _), plan, handle in zip(lanes, plans,
                                              (launched, packed)):
            bt = fe.batch_stages.begin(rec.snap_id, 0, count)
            bt.ready()
            fe._complete_device_batch(
                rec, rec.snap_id, 0, count, B if u else 0, 0, rows, None,
                handle, time.monotonic(),
                time.time_ns(), plan, 0, bt)
        resolved.append(LEDGER.snapshot("native")["resolved_native"] - before)
    (_, _, mine), (_, _, theirs) = lanes
    assert resolved == [1] * 6 and len(mine) == len(theirs) == 6
    assert any(k["dedup"][1] for _, k in mine)       # cached rows
    assert any(k["dedup"][3] for _, k in mine)       # evictions
    for (a, k), (b, l) in zip(mine, theirs):
        assert a[1:4] == b[1:4]                      # count, pad, eff
        assert a[4].tobytes() == b[4].tobytes()      # rows
        assert a[6].tobytes() == b[6].tobytes()      # verdict
        assert (k["firing"] is None) == (l["firing"] is None) == (not firing)
        if firing:
            assert k["firing"].tobytes() == l["firing"].tobytes()
        assert k["dedup"] == l["dedup"]
        assert k["device_rows"] == l["device_rows"]
