"""Traffic replay & what-if preflight (ISSUE 13, docs/replay.md).

Covers the acceptance list: the capture ring's bounded-memory property
(byte cap honored under sustained append, drops counted), capture
round-trip bit-parity through the checksummed container (+ typed
rejection of corruption/version-skew/schema-skew), replay verdict-diff
correctness on a planted one-rule mutation (exactly the mutated rule
attributed; clean churn diffs empty), pregate rejection leaving the old
snapshot serving (zero live exposure), the engine capture hook, the
decision-record schema satellites, the bench replay timetable, and the
/debug/replay endpoint.

Deliberately import-light: collects on images without ``cryptography``;
JAX_PLATFORMS=cpu."""

from __future__ import annotations

import asyncio
import hashlib
import json
import os
import struct
import time

import numpy as np
import pytest

from authorino_tpu.compiler import ConfigRules, compile_corpus
from authorino_tpu.expressions import Operator, Pattern
from authorino_tpu.replay import capture as cap_mod
from authorino_tpu.replay.capture import (
    CAPTURE,
    CAPTURE_SCHEMA,
    CaptureFormatError,
    CaptureLog,
    read_capture,
    read_segment,
    write_segment,
)
from authorino_tpu.replay.pregate import pregate_check
from authorino_tpu.replay.replay import replay_records
from authorino_tpu.runtime import EngineEntry, PolicyEngine
from authorino_tpu.runtime import provenance as prov_mod
from authorino_tpu.runtime.change_safety import GuardThresholds
from authorino_tpu.runtime.engine import SnapshotRejected
from authorino_tpu.runtime.flight_recorder import ANOMALY_KINDS, RECORDER


def run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


def org_corpus(orgs):
    return [ConfigRules(name=n,
                        evaluators=[(None, Pattern("auth.identity.org",
                                                   Operator.EQ, org))])
            for n, org in orgs.items()]


def entries_of(cfgs):
    return [EngineEntry(id=c.name, hosts=[c.name], runtime=None, rules=c)
            for c in cfgs]


def cdoc(j, org):
    return {"request": {"host": f"h{j}", "path": f"/p{j}", "method": "GET"},
            "auth": {"identity": {"org": org}}}


def make_record(i, name="cfg-a", org="acme", allow=True):
    return {"schema": CAPTURE_SCHEMA, "t": 100.0 + i * 0.01,
            "authconfig": name, "doc": cdoc(i, org),
            "verdict": "allow" if allow else "deny",
            "rule_index": -1 if allow else 0,
            "lane": "engine", "generation": 1}


TH = GuardThresholds(min_requests=8, min_config_requests=4,
                     min_config_allows=2)


@pytest.fixture
def capture():
    """Arm the process-wide capture log (ring only) and restore it."""
    CAPTURE.configure(enabled=True, size_mb=4, sample_n=1)
    CAPTURE.clear()
    yield CAPTURE
    CAPTURE.configure(enabled=False)
    CAPTURE.directory = None
    CAPTURE.clear()


# ---------------------------------------------------------------------------
# capture: bounded memory, sampling, container round trip
# ---------------------------------------------------------------------------


def test_capture_ring_byte_cap_under_sustained_append():
    log = CaptureLog(enabled=True, size_mb=0.01)  # ~10 KB budget
    for i in range(500):
        log.offer("cfg", cdoc(i, "acme" * 10), -1, "engine", 1)
        if i % 50 == 0:
            log.flush()
    assert log.flush()
    assert log._ring_bytes <= log.size_bytes
    assert log.evicted_total > 0          # the cap actually bit
    assert log.stored_total == 500        # evictions are not drops
    assert log.dropped_total == 0
    # the ring keeps the NEWEST records (oldest evicted first)
    recs = log.ring_records()
    assert recs[-1]["doc"]["request"]["host"] == "h499"


def test_capture_queue_overflow_drops_and_counts():
    log = CaptureLog(enabled=True, queue_max=16)
    for i in range(64):  # never drained: the queue must bound itself
        log.offer("cfg", cdoc(i, "acme"), -1, "engine", 1)
    assert len(log._queue) <= 17  # bounded (±1 for the racy len check)
    assert log.dropped_total >= 47
    log.flush()
    assert log.stored_total + log.dropped_total == 64


def test_capture_disabled_is_inert():
    log = CaptureLog(enabled=False)
    log.offer("cfg", cdoc(0, "acme"), -1, "engine", 1)
    assert log.sample_indices(100) == ()
    assert not log._queue and log.stored_total == 0


def test_capture_stride_sampling():
    log = CaptureLog(enabled=True, sample_n=8)
    fired = sum(len(list(log.sample_indices(10))) for _ in range(100))
    assert 100 <= fired <= 150  # 1000 decisions at 1-in-8: ~125
    # sample_n=1 keeps everything
    log2 = CaptureLog(enabled=True, sample_n=1)
    assert list(log2.sample_indices(5)) == [0, 1, 2, 3, 4]


def test_capture_container_round_trip_bit_parity(tmp_path):
    records = [make_record(i, allow=(i % 3 != 0)) for i in range(25)]
    path = str(tmp_path / f"seg{cap_mod.SEGMENT_SUFFIX}")
    write_segment(path, records, meta={"note": "test"})
    header, rt = read_segment(path)
    assert rt == records                        # bit-parity (dict level)
    assert header["schema"] == CAPTURE_SCHEMA
    assert header["count"] == 25
    # canonical encoding parity: re-serializing the round-tripped records
    # yields byte-identical lines
    assert [cap_mod.encode_record(r) for r in rt] == \
        [cap_mod.encode_record(r) for r in records]


def test_capture_container_rejects_corruption_typed(tmp_path):
    path = str(tmp_path / f"seg{cap_mod.SEGMENT_SUFFIX}")
    write_segment(path, [make_record(0)])
    blob = bytearray(open(path, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    open(path, "wb").write(bytes(blob))
    with pytest.raises(CaptureFormatError):
        read_segment(path)
    # truncation
    open(path, "wb").write(bytes(blob[:10]))
    with pytest.raises(CaptureFormatError):
        read_segment(path)


def _skewed_container(header: dict) -> bytes:
    hb = json.dumps(header).encode()
    body = cap_mod.MAGIC + struct.pack("<Q", len(hb)) + hb
    return body + hashlib.sha256(body).digest()


def test_capture_container_rejects_version_and_schema_skew(tmp_path):
    p1 = str(tmp_path / "ver.atpucap")
    open(p1, "wb").write(_skewed_container(
        {"version": 999, "schema": CAPTURE_SCHEMA, "count": 0}))
    with pytest.raises(CaptureFormatError, match="version"):
        read_segment(p1)
    p2 = str(tmp_path / "sch.atpucap")
    open(p2, "wb").write(_skewed_container(
        {"version": cap_mod.CAPTURE_FORMAT_VERSION, "schema": 999,
         "count": 0}))
    with pytest.raises(CaptureFormatError, match="schema skew"):
        read_segment(p2)


def test_capture_directory_rotation_and_read(tmp_path):
    d = str(tmp_path / "cap")
    log = CaptureLog(enabled=True, size_mb=1.0)
    log.configure(directory=d, segment_mb=0.004)  # ~4 KB segments
    for i in range(120):
        log.offer("cfg-a", cdoc(i, "acme"), -1, "engine", 1)
    assert log.flush()
    segs = [n for n in os.listdir(d) if n.endswith(cap_mod.SEGMENT_SUFFIX)]
    assert len(segs) >= 2                 # rotation happened
    records = read_capture(d)
    assert len(records) == 120            # nothing lost across segments
    hosts = [r["doc"]["request"]["host"] for r in records]
    assert hosts == [f"h{i}" for i in range(120)]  # oldest-first order


def test_capture_directory_pruned_to_byte_budget(tmp_path):
    d = str(tmp_path / "cap")
    log = CaptureLog(enabled=True, size_mb=0.01)   # ~10 KB total budget
    log.configure(directory=d, segment_mb=0.004)
    for i in range(400):
        log.offer("cfg-a", cdoc(i, "acme" * 8), -1, "engine", 1)
    assert log.flush()
    total = sum(os.path.getsize(os.path.join(d, n))
                for n in os.listdir(d)
                if n.endswith(cap_mod.SEGMENT_SUFFIX))
    # pruned to ~the budget (the newest segment is never pruned, so allow
    # one segment of slack)
    assert total <= log.size_bytes + log.segment_bytes
    assert log.segments_pruned > 0


# ---------------------------------------------------------------------------
# replay: verdict diff on a planted mutation
# ---------------------------------------------------------------------------


def test_replay_diff_planted_one_rule_mutation():
    old = compile_corpus(org_corpus({"cfg-a": "acme", "cfg-b": "beta"}),
                         members_k=4)
    new = compile_corpus(org_corpus({"cfg-a": "nobody", "cfg-b": "beta"}),
                         members_k=4)
    records = [make_record(i, name="cfg-a", org="acme")
               for i in range(10)] + \
              [make_record(i, name="cfg-b", org="evil") for i in range(10)]
    report = replay_records(old, new, records)
    assert report["replayed"] == 20
    assert report["flips"] == {"newly_denied": 10, "newly_allowed": 0,
                               "total": 10}
    # exactly the mutated rule attributed, nothing else
    assert len(report["by_rule"]) == 1
    g = report["by_rule"][0]
    assert g["authconfig"] == "cfg-a"
    assert g["direction"] == "newly-denied"
    assert g["rule_index"] == 0 and "nobody" in g["rule"]
    assert g["count"] == 10 and g["examples"]
    assert report["per_config"]["cfg-a"]["newly_denied"] == 10
    assert report["per_config"]["cfg-b"]["newly_denied"] == 0
    assert report["load_model"] == "replay"
    assert report["platform"].startswith("host-oracle")


def test_replay_diff_clean_churn_is_empty():
    orgs = {"cfg-a": "acme", "cfg-b": "beta"}
    old = compile_corpus(org_corpus(orgs), members_k=4)
    new = compile_corpus(org_corpus(orgs), members_k=4)  # fresh objects
    records = [make_record(i, name="cfg-a", org="acme") for i in range(12)]
    report = replay_records(old, new, records)
    assert report["flips"]["total"] == 0 and report["by_rule"] == []
    assert pregate_check(report, TH) is None


def test_replay_newly_allowed_attributes_the_old_rule():
    old = compile_corpus(org_corpus({"cfg-a": "acme"}), members_k=4)
    new = compile_corpus(org_corpus({"cfg-a": "evil"}), members_k=4)
    records = [make_record(i, name="cfg-a", org="evil", allow=False)
               for i in range(10)]
    report = replay_records(old, new, records)
    assert report["flips"]["newly_allowed"] == 10
    g = report["by_rule"][0]
    assert g["direction"] == "newly-allowed"
    assert "acme" in g["rule"]  # the OLD side's rule — the one that fired


def test_replay_missing_config_and_truncation_are_reported():
    old = compile_corpus(org_corpus({"cfg-a": "acme"}), members_k=4)
    new = compile_corpus(org_corpus({"cfg-a": "acme"}), members_k=4)
    records = [make_record(0), make_record(1, name="ghost")]
    report = replay_records(old, new, records)
    assert report["replayed"] == 1
    assert report["skipped"]["missing_config"] == 1
    assert report["skipped"]["configs_missing_old"] == ["ghost"]
    # zero budget: everything past record 0 reports as truncated
    report2 = replay_records(old, new,
                             [make_record(i) for i in range(100)],
                             time_budget_s=0.0)
    assert report2["skipped"]["truncated"] > 0
    assert report2["replayed"] + report2["skipped"]["truncated"] == 100


def test_pregate_check_judges_with_guard_semantics():
    base = {"replayed": 100,
            "flips": {"newly_denied": 50, "newly_allowed": 0, "total": 50},
            "per_config": {"cfg-a": {"replayed": 50, "newly_denied": 50,
                                     "newly_allowed": 0, "old_allows": 50,
                                     "new_allows": 0}},
            "by_rule": [{"authconfig": "cfg-a", "direction": "newly-denied",
                         "rule_index": 0, "rule": "0:x", "count": 50}],
            "skipped": {"truncated": 0}}
    b = pregate_check(base, TH, changed={"cfg-a"})
    assert b is not None and "cfg-a" in b["suspects"]
    assert "replay-deny-rate" in b["guards"]
    assert b["top_flips"]
    # the changed-set restriction: an unchanged config cannot be a suspect
    b2 = pregate_check(base, TH, changed={"other"})
    assert b2 is None or "cfg-a" not in b2["suspects"]
    # below the evidence floor: no verdict at all
    small = dict(base, replayed=4)
    assert pregate_check(small, TH) is None


def test_pregate_catches_config_confined_loosening():
    """A changed config flipping ALL its denies to allows lowers every
    deny-side rate — the per-config flip-rate criterion must still name
    it (review finding: deny-side-only guards were blind to loosening)."""
    report = {"replayed": 1000,
              "flips": {"newly_denied": 0, "newly_allowed": 20,
                        "total": 20},
              "per_config": {
                  "payments": {"replayed": 20, "newly_denied": 0,
                               "newly_allowed": 20, "old_allows": 0,
                               "new_allows": 20},
                  "other": {"replayed": 980, "newly_denied": 0,
                            "newly_allowed": 0, "old_allows": 900,
                            "new_allows": 900}},
              "by_rule": [{"authconfig": "payments",
                           "direction": "newly-allowed", "rule_index": 0,
                           "rule": "0:x", "count": 20}],
              "skipped": {"truncated": 0}}
    b = pregate_check(report, TH, changed={"payments"})
    assert b is not None and b["suspects"] == ["payments"]
    assert "replay-config-deny-rate" in b["guards"]


def test_pregate_insufficient_replayed_records_skips_not_passes(capture):
    """Ring full of records the candidate cannot re-decide (every config
    renamed) must record 'skipped' — never a false 'pass' that tightens
    the canary guards on zero evidence (review finding)."""
    engine = build_engine(org_corpus({"cfg-a": "acme"}),
                          canary_fraction=0.25, canary_window_s=30.0,
                          canary_thresholds=TH, replay_pregate=True)
    run(_serve(engine, 20, names=("cfg-a",)))
    assert capture.flush()
    engine.apply_snapshot(entries_of(org_corpus({"cfg-x": "acme"})))
    phase = engine._canary
    try:
        assert engine._last_pregate["result"] == "skipped"
        assert engine._last_pregate["replayed"] == 0
        assert phase is not None
        assert phase.preflight["result"] == "skipped"
        # guards NOT tightened on absent evidence
        assert phase.guard.thresholds.deny_delta == TH.deny_delta
    finally:
        engine.canary_promote()


# ---------------------------------------------------------------------------
# engine: capture hook + pregate end to end
# ---------------------------------------------------------------------------


def build_engine(cfgs=None, **kw):
    kw.setdefault("max_batch", 8)
    kw.setdefault("lane_select", False)
    engine = PolicyEngine(members_k=4, mesh=None, **kw)
    if cfgs is not None:
        engine.apply_snapshot(entries_of(cfgs))
    return engine


async def _serve(engine, n=40, names=("cfg-a", "cfg-b")):
    for j in range(n):
        org = "acme" if j % 2 == 0 else "evil"
        name = names[j % len(names)]
        await engine.submit(cdoc(j, org), name)


def test_engine_capture_hook_records_full_fidelity(capture):
    engine = build_engine(org_corpus({"cfg-a": "acme", "cfg-b": "beta"}))
    run(_serve(engine, 20))
    assert capture.flush()
    recs = capture.ring_records()
    assert len(recs) == 20
    by_cfg = {r["authconfig"] for r in recs}
    assert by_cfg == {"cfg-a", "cfg-b"}
    r = next(r for r in recs if r["authconfig"] == "cfg-a")
    assert r["schema"] == CAPTURE_SCHEMA
    assert r["verdict"] == "allow" and r["rule_index"] == -1
    assert r["doc"]["auth"]["identity"]["org"] == "acme"
    assert r["generation"] == engine.generation
    d = next(r for r in recs if r["authconfig"] == "cfg-b")
    assert d["verdict"] == "deny" and d["rule_index"] == 0


def test_pregate_rejects_poison_with_zero_live_exposure(capture):
    engine = build_engine(org_corpus({"cfg-a": "acme", "cfg-b": "beta"}),
                          canary_fraction=0.25, canary_window_s=30.0,
                          canary_thresholds=TH, replay_pregate=True)
    run(_serve(engine))
    assert capture.flush()
    gen_before = engine.generation
    poison = org_corpus({"cfg-a": "nobody", "cfg-b": "beta"})
    events_before = RECORDER.events_total
    with pytest.raises(SnapshotRejected) as ei:
        engine.apply_snapshot(entries_of(poison))
    # the typed rejection carries the attributed diff
    assert ei.value.replay_diff["suspects"] == ["cfg-a"]
    assert any("cfg-a" in f and "newly-denied" in f
               for f in ei.value.findings)
    # zero live exposure: no canary started, generation unmoved, and the
    # OLD snapshot still answers with the OLD semantics
    assert engine._canary is None
    assert engine.generation == gen_before
    rule, _ = run(engine.submit(cdoc(0, "acme"), "cfg-a"))
    assert bool(rule[0]) is True
    assert engine._last_pregate["result"] == "breach"
    # the anomaly event rode the flight recorder ring
    assert "replay-pregate-breach" in ANOMALY_KINDS
    with RECORDER._ring_lock:
        kinds = [e["kind"] for e in RECORDER._ring]
    assert "replay-pregate-breach" in kinds
    assert RECORDER.events_total > events_before


def test_pregate_clean_churn_proceeds_to_tightened_canary(capture):
    engine = build_engine(org_corpus({"cfg-a": "acme", "cfg-b": "beta"}),
                          canary_fraction=0.25, canary_window_s=30.0,
                          canary_thresholds=TH, replay_pregate=True)
    run(_serve(engine))
    assert capture.flush()
    # benign churn: cfg-b's captured traffic was denied on both sides
    engine.apply_snapshot(entries_of(
        org_corpus({"cfg-a": "acme", "cfg-b": "gamma"})))
    phase = engine._canary
    try:
        assert phase is not None, "clean preflight must proceed to canary"
        assert phase.preflight["result"] == "pass"
        assert phase.preflight["flips_total"] == 0
        assert phase.preflight["guards_tightened"] is True
        # halved deny deltas on the phase's guard
        assert phase.guard.thresholds.deny_delta == TH.deny_delta / 2
        assert phase.guard.thresholds.config_deny_delta == \
            TH.config_deny_delta / 2
        assert phase.to_json()["preflight"]["result"] == "pass"
        assert engine._last_pregate["result"] == "pass"
    finally:
        engine.canary_promote()


def test_pregate_skips_on_empty_ring_and_swap_proceeds(capture):
    engine = build_engine(org_corpus({"cfg-a": "acme"}),
                          canary_fraction=0.0, replay_pregate=True,
                          canary_thresholds=TH)
    capture.clear()  # nothing captured
    engine.apply_snapshot(entries_of(org_corpus({"cfg-a": "other"})))
    assert engine._last_pregate["result"] == "skipped"
    assert "min_requests" in engine._last_pregate["reason"]
    # the swap landed (skipped ≠ rejected)
    rule, _ = run(engine.submit(cdoc(0, "other"), "cfg-a"))
    assert bool(rule[0]) is True


def test_pregate_without_canary_still_rejects_poison(capture):
    engine = build_engine(org_corpus({"cfg-a": "acme"}),
                          canary_fraction=0.0, replay_pregate=True,
                          canary_thresholds=TH)
    run(_serve(engine, 20, names=("cfg-a",)))
    assert capture.flush()
    with pytest.raises(SnapshotRejected):
        engine.apply_snapshot(entries_of(org_corpus({"cfg-a": "nobody"})))
    rule, _ = run(engine.submit(cdoc(0, "acme"), "cfg-a"))
    assert bool(rule[0]) is True


def test_engine_debug_vars_carries_replay_block(capture):
    engine = build_engine(org_corpus({"cfg-a": "acme"}),
                          replay_pregate=True)
    dv = engine.debug_vars()["replay"]
    assert dv["pregate"]["enabled"] is True
    assert dv["capture"]["enabled"] is True
    json.dumps(dv)  # JSON-safe


# ---------------------------------------------------------------------------
# decision-record schema satellites
# ---------------------------------------------------------------------------


def test_decision_records_are_schema_stamped():
    log = prov_mod.DecisionLog(capacity=4, sample_n=1)
    log.record(lane="engine", host="h", authconfig="c", verdict=True,
               rule=None, rule_index=-1, latency_ms=0.1, generation=1)
    rec = log.to_json()["records"][-1]
    assert rec["schema"] == prov_mod.DECISION_SCHEMA
    assert tuple(sorted(rec)) == tuple(sorted(prov_mod.DECISION_FIELDS))


def test_decision_schema_skew_rejected_typed():
    ok = {"schema": prov_mod.DECISION_SCHEMA, "records": []}
    prov_mod.check_decision_schema(ok)  # no raise
    for bad in ({"schema": 1, "records": []}, {"records": []}, []):
        with pytest.raises(prov_mod.DecisionSchemaError):
            prov_mod.check_decision_schema(bad)


def test_analysis_decisions_reader_rejects_skew(tmp_path, capsys):
    from authorino_tpu.analysis.__main__ import main as analysis_main

    p = str(tmp_path / "decisions.json")
    json.dump({"schema": 1, "records": [], "capacity": 8, "sample_n": 1,
               "records_total": 0}, open(p, "w"))
    assert analysis_main(["--decisions", p]) == 1
    assert "DecisionSchemaError" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# offline CLI
# ---------------------------------------------------------------------------


def test_analysis_replay_cli_offline(tmp_path, capsys):
    from authorino_tpu.analysis.__main__ import main as analysis_main
    from authorino_tpu.snapshots import rules_fingerprint, serialize_policy

    def blob(path, orgs, gen):
        cfgs = org_corpus(orgs)
        fps = {c.name: rules_fingerprint(c) for c in cfgs}
        b = serialize_policy(compile_corpus(cfgs, members_k=4),
                             meta={"fingerprints": fps, "certified": True,
                                   "generation": gen})
        open(path, "wb").write(b)

    old_p = str(tmp_path / "old.atpusnap")
    new_p = str(tmp_path / "new.atpusnap")
    blob(old_p, {"cfg-a": "acme", "cfg-b": "beta"}, 1)
    blob(new_p, {"cfg-a": "nobody", "cfg-b": "beta"}, 2)
    d = str(tmp_path / "cap")
    os.makedirs(d)
    write_segment(os.path.join(d, f"s{cap_mod.SEGMENT_SUFFIX}"),
                  [make_record(i) for i in range(40)])
    rc = analysis_main(["--replay", old_p, new_p, "--log", d, "--json"])
    report = json.loads(capsys.readouterr().out)
    assert rc == 1  # flips present
    assert report["flips"]["newly_denied"] == 40
    assert report["by_rule"][0]["authconfig"] == "cfg-a"
    assert report["pregate"] and "cfg-a" in report["pregate"]["suspects"]
    # clean pair exits 0
    rc2 = analysis_main(["--replay", old_p, old_p, "--log", d, "--json"])
    report2 = json.loads(capsys.readouterr().out)
    assert rc2 == 0 and report2["flips"]["total"] == 0


def test_debug_replay_endpoint(capture):
    from aiohttp.test_utils import TestClient, TestServer

    from authorino_tpu.service.http_server import build_app

    engine = build_engine(org_corpus({"cfg-a": "acme"}))
    run(_serve(engine, 4, names=("cfg-a",)))
    capture.flush()

    async def body():
        client = TestClient(TestServer(build_app(engine)))
        await client.start_server()
        try:
            resp = await client.get("/debug/replay")
            assert resp.status == 200
            payload = await resp.json()
        finally:
            await client.close()
        return payload

    payload = run(body())
    assert payload["capture"]["enabled"] is True
    assert payload["capture"]["stored_total"] >= 4
    assert payload["pregate"]["enabled"] is False
