"""PolicyEngine: the serving-time owner of the host index, the compiled rule
corpus (double-buffered, atomically swapped on reconcile) and the pipelined
micro-batch dispatcher that overlaps encode / H2D / kernel / readback across
in-flight batches.

This is the TPU-era replacement for the reference's per-request goroutine
evaluation (SURVEY.md §5 "communication backend"): the gRPC/HTTP frontend
stays on host CPU; Check() contexts are encoded and batched here; one jitted
kernel evaluates the batch against the whole corpus.  Reconcile-time
compilation is the analog of the reference's OPA precompile
(ref: pkg/evaluators/authorization/opa.go:141); the swap is the analog of
index Set (ref: controllers/auth_config_controller.go:605-636).

Dispatch is an explicit three-stage software pipeline (one global dispatcher
for all event loops; futures resolve loop-affinely):

  1. encode stage — dispatch workers (shared CPU pool) run encode_batch /
     pack_batch and build ONE fused H2D staging buffer per batch
     (ops/pattern_eval.py fuse_batch) instead of 5-7 small transfers;
  2. dispatch stream — the kernel launches WITHOUT blocking (JAX async
     dispatch + copy_to_host_async); in-flight batches are tracked as a
     bounded counter window (max_inflight_batches), not captive pool
     threads, so throughput ≈ window × batch / RTT by construction;
  3. completion stage — a shared completer thread detects each batch's
     readback arrival (jax.Array.is_ready polling) and hands it to the
     worker pool to finalize + resolve futures: completion is
     FIFO-independent, and neither a slow readback nor a fallback-heavy
     finalize convoys another batch.

Flushing is adaptive: a free window slot + a non-empty queue dispatches
immediately (light-load latency ≈ one device RTT, never a max_delay_s
stack); with the window full, requests queue and each completion cuts the
next batch — batch size grows with load instead of with a timer.

Fault tolerance (ISSUE 5, docs/robustness.md): a failed in-flight batch is
retried ONCE on a fresh dispatch, then every request is re-decided exactly
through the host expression oracle (models/policy_model.host_results — the
kernel's differential-test reference); consecutive batch failures trip a
circuit breaker (runtime/breaker.py) that routes whole batches host-side
with half-open probing; requests that cannot make their propagated Check()
deadline are shed BEFORE encode (typed DEADLINE_EXCEEDED); a completer
watchdog times out batches wedged in is_ready (--device-timeout) and feeds
them the same retry/degrade path; and SIGTERM drains the queue + in-flight
window before exit.  No request ever observes a raw exception: failures
that cannot degrade resolve as typed CheckAbort(UNAVAILABLE)."""

from __future__ import annotations

import asyncio
import logging
import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..authjson.wellknown import CheckRequestModel
from ..compiler.compile import CompiledPolicy, ConfigRules, compile_corpus
from ..compiler.encode import encode_batch
from ..evaluators.base import RuntimeAuthConfig
from ..index import HostIndex
from ..pipeline.pipeline import AuthPipeline, AuthResult
from ..utils import metrics as metrics_mod
from ..utils import tracing as tracing_mod
from ..utils.rpc import DEADLINE_EXCEEDED, NOT_FOUND, UNAVAILABLE, CheckAbort
from ..utils.verdict_cache import VerdictCache
from . import faults
from . import provenance as prov_mod
from . import change_safety as safety_mod
from ..replay.capture import CAPTURE
from .admission import AdaptiveWindow, AdmissionController
from .breaker import CircuitBreaker
from .flight_recorder import RECORDER
from . import kernel_cost as kernel_cost_mod
from .kernel_cost import LEDGER, CostModel
from .lane_select import (
    DEVICE as L_DEVICE,
    HOST as L_HOST,
    R_COST,
    R_DEADLINE,
    R_SPECULATIVE,
    LaneSelector,
    Speculation,
)
from ..tenancy.quota import R_TENANT_CONTAINED as TEN_R_CONTAINED

__all__ = ["PolicyEngine", "EngineEntry", "SnapshotRejected"]

log = logging.getLogger("authorino_tpu.engine")


class SnapshotRejected(RuntimeError):
    """A compiled snapshot failed --strict-verify tensor lint at swap time.
    The previously-serving snapshot stays live (the reconciler records
    CachingError and retries on the next resync)."""

    def __init__(self, findings):
        self.findings = findings
        super().__init__(
            f"snapshot rejected by tensor lint ({len(findings)} finding(s)): "
            + "; ".join(str(f) for f in findings[:3]))


@dataclass
class EngineEntry:
    """One AuthConfig as the control plane hands it to the engine."""

    id: str                       # e.g. "namespace/name"
    hosts: List[str]
    runtime: RuntimeAuthConfig
    rules: Optional[ConfigRules] = None  # compilable pattern surface (may be None)
    # AuthConfig metadata.annotations (ISSUE 15): the tenant QoS plane
    # resolves per-tenant weights/quotas from these at every reconcile
    # (authorino.tpu/qos-class, qos-weight, qos-quota-rps); None = the
    # default QoS class
    annotations: Optional[Dict[str, str]] = None


class _Snapshot:
    """Immutable compiled corpus + device params (double-buffered).

    With a multi-device mesh, the corpus compiles as a ShardedPolicyModel
    (rules axis tensor-parallel over 'mp', batch over 'dp') — the TPU-era
    successor of the reference's label-selector instance sharding
    (ref: controllers/label_selector.go:14-45)."""

    def __init__(self, entries: Sequence[EngineEntry], members_k: int = 16,
                 mesh=None, strict_verify: bool = False,
                 compile_cache=None, prev: "Optional[_Snapshot]" = None,
                 breaker_threshold: int = 3, breaker_reset_s: float = 5.0,
                 ovf_assist: Optional[bool] = None):
        self.by_id: Dict[str, EngineEntry] = {e.id: e for e in entries}
        rules = [e.rules for e in entries if e.rules is not None]
        self.policy: Optional[CompiledPolicy] = None
        self.params = None
        self.sharded = None
        # engine generation this snapshot serves under, set inside
        # apply_snapshot's swap lock.  In-flight batches pin their
        # snapshot, so they insert AND serve under the cache tokens (or,
        # on the mesh path, the generation) they were encoded against: a
        # swap can never let a stale verdict leak into the new snapshot's
        # lookups.
        self.generation = 0
        # set by a passing _verify(): downstream strict-verify consumers
        # (the native frontend's refresh) skip re-linting an already-vetted
        # snapshot — the lint rebuilds both lanes' host operand pytrees,
        # too heavy to repeat per swap listener
        self.lint_ok = False
        # translation-validation stats from _verify (None when strict
        # verify is off): validated / cache_hits / failed / sampled —
        # the /debug/vars evidence that the fingerprint cache is
        # actually incremental across reconciles
        self.translation: Optional[Dict[str, int]] = None
        # incremental control plane (ISSUE 8, authorino_tpu/snapshots/):
        # per-config source fingerprints, the (epoch, fingerprint) verdict-
        # cache tokens per eval row, what the incremental compile actually
        # did, the upload plan, per-phase timings, and the host operand
        # view the NEXT reconcile diffs against
        self.fingerprints: Dict[str, str] = {}
        self.cache_tokens = None         # per-row tokens (single corpus only)
        self.compile_report = None
        self.upload: Optional[Dict[str, Any]] = None
        self.phase_s: Dict[str, float] = {}
        self.host_view = None
        self.published_origin: Optional[str] = None  # set by from_published
        # change-safety provenance (ISSUE 10): set on rollback clones and
        # quarantine re-applies so the publisher manifest can carry the
        # rollback/quarantine record to replicas
        self.change_safety: Optional[Dict[str, Any]] = None
        # rule heat map (ISSUE 9): built at install time by
        # _install_snapshot (kernel rows → authconfig/rule-source labels)
        self.heat = None
        # mesh verdict-cache tokens (ISSUE 11): [shard][row] → (encoding
        # epoch, rules fingerprint), the PR 8 keying the mesh lane now
        # shares with the single corpus (generation keying retired)
        self.mesh_tokens = None
        if rules:
            if mesh is not None:
                self._compile_mesh(rules, members_k, mesh, strict_verify,
                                   prev, breaker_threshold, breaker_reset_s,
                                   ovf_assist=ovf_assist)
            else:
                self._compile_single(rules, members_k, strict_verify,
                                     compile_cache, prev,
                                     ovf_assist=ovf_assist)

    def _compile_mesh(self, rules, members_k: int, mesh,
                      strict_verify: bool,
                      prev: "Optional[_Snapshot]",
                      breaker_threshold: int = 3,
                      breaker_reset_s: float = 5.0,
                      ovf_assist: Optional[bool] = None) -> None:
        """Mesh compile → verify → delta upload, each phase timed (the
        control-plane parity half of ISSUE 11):

        - the previous mesh snapshot's INTERNER is adopted (insert-only, so
          ids are stable), which keeps each untouched shard's encoding
          epoch — and with it the verdict-cache tokens — identical across
          the swap;
        - with --strict-verify the packed shards are linted HOST-side,
          BEFORE the device upload (the PR 4 ordering caveat, fixed:
          a corrupt corpus never stages a byte);
        - the upload is a per-shard DELTA against the previous stacked host
          view: a one-config mutation ships rows only to its owning
          shard(s)."""
        from ..parallel import ShardedPolicyModel
        from ..snapshots.fingerprint import rules_fingerprint

        t0 = time.monotonic()
        prev_ok = (prev is not None and prev.sharded is not None
                   and prev.sharded.mesh is mesh)
        self.sharded = ShardedPolicyModel(
            rules, mesh, members_k=members_k,
            interner=(prev.sharded.interner if prev_ok else None),
            defer_upload=True, breaker_threshold=breaker_threshold,
            breaker_reset_s=breaker_reset_s, ovf_assist=ovf_assist)
        self.phase_s["compile"] = time.monotonic() - t0
        memo: Dict[int, str] = {}
        self.fingerprints = {c.name: rules_fingerprint(c, memo)
                             for c in rules}
        if strict_verify:
            t0 = time.monotonic()
            self._verify()
            self.phase_s["validate"] = time.monotonic() - t0
        self.mesh_tokens = self.sharded.cache_tokens(self.fingerprints)
        t0 = time.monotonic()
        self.upload = self.sharded.upload(
            prev.sharded if prev_ok else None)
        self.phase_s["upload"] = time.monotonic() - t0

    def _compile_single(self, rules, members_k: int, strict_verify: bool,
                        compile_cache, prev: "Optional[_Snapshot]",
                        ovf_assist: Optional[bool] = None) -> None:
        """Single-corpus compile → verify → diff → upload, each phase
        timed.  With a compile cache and an unchanged corpus the previous
        snapshot's CompiledPolicy AND device params are reused outright:
        zero configs compiled, zero bytes uploaded, verification skipped
        (the artifacts are byte-identical to ones already vetted)."""
        from ..snapshots.fingerprint import cache_tokens, rules_fingerprint

        t0 = time.monotonic()
        prev_ok = (prev is not None and prev.policy is not None
                   and prev.sharded is None)
        if compile_cache is not None:
            policy, report = compile_cache.compile(
                rules, members_k=members_k,
                prev_fps=(prev.fingerprints if prev_ok else None),
                prev_policy=(prev.policy if prev_ok else None),
                ovf_assist=ovf_assist)
            self.compile_report = report
            self.fingerprints = dict(report.fingerprints)
        else:
            policy = compile_corpus(rules, members_k=members_k,
                                    ovf_assist=ovf_assist)
            memo: Dict[int, str] = {}
            self.fingerprints = {c.name: rules_fingerprint(c, memo)
                                 for c in rules}
        self.policy = policy
        self.phase_s["compile"] = time.monotonic() - t0
        reused = (self.compile_report is not None
                  and self.compile_report.reused_policy)
        if reused and (prev.lint_ok or not strict_verify):
            # fingerprint-identical corpus: previous params serve as-is
            self.lint_ok = prev.lint_ok
            # the strict-verify evidence for /debug/vars: every config's
            # certificate is (trivially) served from cache — nothing was
            # re-validated, the strongest form of PR 6's zero-revalidation
            # property (the certify pass didn't even need to run)
            self.translation = (
                {"validated": 0, "cache_hits": len(prev.policy.config_ids),
                 "failed": 0, "sampled": 0, "dfa_witnesses": 0}
                if strict_verify and prev.lint_ok else prev.translation)
            self.params = prev.params
            self.host_view = prev.host_view
            self.cache_tokens = prev.cache_tokens
            self.upload = {"mode": "reuse", "upload_bytes": 0,
                           "full_bytes": 0, "arrays_reused": None,
                           "arrays_touched": []}
            return
        if strict_verify:
            # lint BEFORE the device upload: a corrupt corpus is rejected
            # host-side, never staged on the device (and never crashes
            # mid-operand-build with a raw IndexError)
            t0 = time.monotonic()
            self._verify()
            self.phase_s["validate"] = time.monotonic() - t0
        self.cache_tokens = cache_tokens(policy, self.fingerprints)
        self._upload(prev if prev_ok else None)

    def _upload(self, prev: "Optional[_Snapshot]") -> None:
        """Diff + upload phases: plan a delta against the previous host
        operand view, ship only changed rows when a structure-preserving
        delta exists, fall back to a full re-stage otherwise."""
        from ..ops.pattern_eval import to_device
        from ..snapshots.delta import apply_delta, full_upload
        from ..snapshots.diff import plan_delta

        t0 = time.monotonic()
        host_view = to_device(self.policy, host=True)
        self.host_view = host_view
        plan = None
        if (prev is not None and prev.params is not None
                and prev.host_view is not None):
            plan = plan_delta(prev.host_view, host_view)
        self.phase_s["diff"] = time.monotonic() - t0
        t0 = time.monotonic()
        if plan is not None:
            self.params, uploaded = apply_delta(prev.params, host_view, plan)
            self.upload = dict(plan.to_json(), upload_bytes=uploaded)
        else:
            self.params, uploaded = full_upload(host_view)
            self.upload = {"mode": "full", "upload_bytes": uploaded,
                           "full_bytes": uploaded, "arrays_reused": 0,
                           "arrays_touched": []}
        self.phase_s["upload"] = time.monotonic() - t0

    @classmethod
    def from_published(cls, loaded, members_k: int = 16,
                       strict_verify: bool = False,
                       prev: "Optional[_Snapshot]" = None) -> "_Snapshot":
        """Serving-replica constructor: wrap a leader-serialized corpus
        (snapshots/distribution.py LoadedSnapshot) WITHOUT compiling
        anything.  The admission gate: an uncertified snapshot is rejected
        outright; with ``strict_verify`` the replica additionally re-runs
        the full local verification (tensor lint + translation
        certification — cheap on repeats thanks to the fingerprint-keyed
        certificate cache).  Entries carry hosts only (runtime=None): a
        replica serves the compiled verdict lane, not the identity/
        metadata pipeline (docs/control_plane.md)."""
        from ..snapshots.fingerprint import cache_tokens

        if not loaded.certified:
            raise SnapshotRejected([
                "snapshot is not certified: the leader never marked it "
                "strict-verified (lint + translation certification)"])
        entries = [EngineEntry(id=cid, hosts=hosts, runtime=None, rules=None)
                   for cid, hosts in loaded.entries]
        snap = cls.__new__(cls)
        snap.by_id = {e.id: e for e in entries}
        snap.policy = loaded.policy
        snap.sharded = None
        snap.params = None
        snap.generation = 0
        snap.lint_ok = False
        snap.translation = (loaded.meta or {}).get("translation")
        snap.fingerprints = loaded.fingerprints
        snap.cache_tokens = None
        snap.mesh_tokens = None
        snap.compile_report = None
        snap.upload = None
        snap.phase_s = {}
        snap.host_view = None
        snap.change_safety = (loaded.meta or {}).get("change_safety")
        snap.heat = None
        # provenance: this snapshot was LOADED, not compiled here — the
        # publisher skips it (a replica must never republish what it
        # consumed, or a node whose source and publish dir meet — even
        # through an HTTP relay — would republish/re-apply forever)
        snap.published_origin = loaded.digest or "<loaded>"
        if strict_verify:
            t0 = time.monotonic()
            snap._verify()
            snap.phase_s["validate"] = time.monotonic() - t0
        else:
            snap.lint_ok = True  # vouched for by the leader's certificate
        prev_ok = (prev is not None and prev.policy is not None
                   and prev.sharded is None)
        if prev_ok:
            # interner continuity: every deserialize builds a FRESH
            # StringInterner (new identity serial), which would change the
            # encoding epoch and kill every cached verdict on each applied
            # generation — the exact churn cliff this subsystem removes.
            # The leader's interner is insert-only, so when the loaded
            # table prefix-extends the previous snapshot's, the ids ARE
            # the previous interner's ids: extend it in place and adopt it
            # (same object ⇒ same serial ⇒ untouched configs' entries
            # survive on replicas too).
            _adopt_interner(prev.policy.interner, loaded.policy)
        snap.cache_tokens = cache_tokens(loaded.policy, snap.fingerprints)
        snap._upload(prev if prev_ok else None)
        return snap

    def clone(self) -> "_Snapshot":
        """Shallow re-serve copy (rollback is a pointer swap, ISSUE 10):
        shares the compiled policy, device params, heat map and cache
        tokens — only the generation and change-safety record are fresh,
        so in-flight batches pinned to the ORIGINAL object keep resolving
        and inserting verdicts under their own generation/tokens."""
        c = _Snapshot.__new__(_Snapshot)
        c.__dict__.update(self.__dict__)
        c.change_safety = None
        return c

    def _verify(self) -> None:
        from ..analysis.tensor_lint import lint_snapshot

        findings = lint_snapshot(self)
        if findings:
            raise SnapshotRejected(findings)
        # translation validation (ISSUE 6): beyond structural sanity, the
        # compiled circuits/DFA tables must DECIDE identically to the host
        # expression oracle.  Per-config fingerprints + the process-wide
        # certificate cache make this incremental: an unchanged config is
        # a cache hit, never a re-validation (ROADMAP item 1).
        from ..analysis.translation_validate import (
            certify_snapshot,
            snapshot_policies,
        )

        stats = {"validated": 0, "cache_hits": 0, "failed": 0,
                 "sampled": 0, "dfa_witnesses": 0}
        failures = []
        for pol in snapshot_policies(self):
            _, fails, st = certify_snapshot(pol)
            failures += fails
            for k in stats:
                stats[k] += st.get(k, 0)
        self.translation = stats
        if failures:
            raise SnapshotRejected(failures)
        self.lint_ok = True


def _adopt_interner(prev_interner, new_policy) -> None:
    """Replica-side interner continuity (see from_published): when the
    freshly-deserialized policy's id table prefix-extends the previous
    snapshot's, graft the new entries onto the previous interner and point
    the policy at it.  Ids are positional in the insertion-ordered table,
    so a true prefix match proves every shared id means the same string;
    any mismatch (leader restarted with a fresh interner) keeps the new
    interner — a structural epoch change, exactly as safe as before."""
    old_t = prev_interner._table
    new_t = new_policy.interner._table
    if len(new_t) < len(old_t):
        return
    it = iter(new_t.items())
    for want in old_t.items():
        if next(it) != want:
            return
    for s, i in new_t.items():
        if s not in old_t:
            old_t[s] = i
    new_policy.interner = prev_interner


@dataclass
class _Pending:
    doc: Any
    config_name: str
    future: asyncio.Future
    loop: Any                     # owning event loop (loop-affine resolution)
    span: Any = None              # RequestSpan (DeviceBatch span links)
    t_enq: float = 0.0            # monotonic enqueue time (queue-wait hist)
    deadline: Optional[float] = None  # monotonic Check() deadline (shedding)
    # canary cohort flag (ISSUE 10): stamped at submit while a canary is in
    # progress — batch cuts partition by it so every launched batch rides
    # exactly ONE snapshot generation (no torn batches)
    canary: bool = False


class _Inflight:
    """One launched micro-batch riding the device window: the on-device
    result handle plus everything the completion stage needs to finalize
    and resolve it.  ``handle`` only needs is_ready() (non-blocking) and
    np.asarray-ability — tests substitute stubs for both."""

    __slots__ = ("engine", "batch", "handle", "finalize", "binfo", "waits",
                 "t_launch", "snap", "attempt", "route", "spec")

    def __init__(self, engine, batch, handle, finalize, binfo, waits,
                 snap=None, attempt=0):
        self.engine = engine
        self.batch = batch
        self.handle = handle
        self.finalize = finalize
        self.binfo = binfo
        self.waits = waits
        self.t_launch = time.monotonic()
        self.snap = snap          # pinned snapshot (retry/degrade path)
        self.attempt = attempt    # 0 = first dispatch, 1 = the one retry
        self.route = None         # mesh lane: occupied device windows
        self.spec = None          # speculative dual-dispatch token (ISSUE 12)

    def ready(self) -> bool:
        is_ready = getattr(self.handle, "is_ready", None)
        if is_ready is None:
            return True  # no readiness probe: finalize blocks (degraded)
        try:
            return bool(is_ready())
        except Exception:
            return True  # let finalize surface the real error

    def expired(self) -> bool:
        """Watchdog probe: True once this batch has been wedged in the
        in-flight window past the engine's --device-timeout."""
        t = self.engine.device_timeout_s
        return bool(t) and (time.monotonic() - self.t_launch) > t


class PolicyEngine:
    def __init__(
        self,
        max_batch: int = 256,
        max_delay_s: Optional[float] = None,
        timeout_s: Optional[float] = None,
        members_k: int = 16,
        mesh: Any = "auto",
        max_fallback_per_batch: Optional[int] = None,
        max_inflight_batches: int = 48,
        dispatch_workers: int = 4,
        verdict_cache_size: int = 32768,
        batch_dedup: bool = True,
        strict_verify: bool = False,
        analyze_policies: bool = True,
        device_timeout_s: Optional[float] = None,
        breaker_threshold: int = 5,
        breaker_reset_s: float = 5.0,
        admission_target_s: float = 0.05,
        admission_queue_cap: int = 0,
        admission_min_cap: Optional[int] = None,
        adaptive_window: bool = True,
        brownout: bool = True,
        brownout_max_batch: int = 32,
        lane_select: bool = True,
        lane_host_max_rows: int = 64,
        speculative_dispatch: bool = True,
        slo_ms: float = 0.0,
        canary_fraction: float = 0.0,
        canary_window_s: float = 30.0,
        canary_thresholds=None,
        snapshot_history: int = 4,
        replay_pregate: bool = False,
        replay_pregate_budget_s: float = 2.0,
        corpus_pregate: str = "",
        corpus_pregate_budget_s: float = 2.0,
        ovf_assist: Optional[bool] = None,
        metadata_prefetch: bool = True,
        metadata_prefetch_max_age_s: float = 300.0,
        metadata_prefetch_refresh_s: float = 60.0,
        tenant_qos: bool = True,
        tenant_default_weight: float = 1.0,
        tenant_weights: Optional[Dict[str, float]] = None,
        tenant_quota_rps: float = 0.0,
        tenant_contain_threshold: float = 3.0,
        tenant_contain_allowance_rps: float = 100.0,
        tenant_top_k: int = 16,
    ):
        """``mesh="auto"`` shards the rule corpus over all visible devices
        when more than one is present (dp × mp ShardedPolicyModel);
        ``mesh=None`` forces the single-corpus path; an explicit
        ``jax.sharding.Mesh`` pins the layout.

        ``max_fallback_per_batch`` bounds the per-batch host-oracle work for
        membership-overflow requests (an overload valve: beyond the cap,
        fallback requests are DENIED fail-closed and counted in
        auth_server_host_fallback_shed_total).  None = unbounded — safe by
        default, since the compiled-closure oracle costs ~2µs/request,
        cheaper than the reference's normal per-request path.

        ``max_delay_s`` is RETIRED (deprecated no-op since PR 2, replaced
        by the adaptive controller below): flushing is completion-driven
        (open window → immediate, full window → completion-driven) and the
        window/batch-cut are tuned by ``AdaptiveWindow``.  Passing a value
        emits a DeprecationWarning and only echoes on /debug/vars; the
        CLI's ``--batch-window-us`` still feeds the native frontend's C++
        gather window, which is a real knob there.

        ``max_inflight_batches`` is the dispatch-window depth: launched
        batches awaiting readback.  Size it so window × max_batch ≥
        device RTT × target RPS (the default 48 covers 100k RPS at 120ms
        RTT with 256-request batches); it bounds device-side memory, not
        host threads.  ``dispatch_workers`` sizes the shared encode-stage
        CPU pool (first engine in the process wins).

        ``batch_dedup`` collapses duplicate encoded rows within each
        micro-batch before dispatch (the device evaluates unique rows
        only; verdicts fan back out on completion — bit-identical by
        construction, the kernel is a pure per-row function).
        ``verdict_cache_size`` bounds the snapshot-scoped verdict LRU
        keyed by (generation, encoded-row digest); 0 disables it.  Both
        are exactness-preserving: see docs/performance.md.

        ``strict_verify`` runs the tensor-IR lint (analysis/tensor_lint.py)
        on every compiled snapshot BEFORE the generation bump: a snapshot
        with any structural finding is rejected (SnapshotRejected raised,
        auth_server_snapshot_rejected_total bumped) and the previous one
        keeps serving.  ``analyze_policies`` runs the Cedar-style semantic
        pass (analysis/policy_analysis.py) once per reconcile — advisory
        warnings on /debug/vars + metrics, never a gate.  Both are
        reconcile-path costs only; see docs/static_analysis.md.

        ``device_timeout_s`` arms the completer watchdog: an in-flight
        batch whose readback never arrives is abandoned after this long,
        counted as a circuit-breaker failure, and fed the retry/degrade
        path (None/0 = off).  ``breaker_threshold`` consecutive batch
        failures trip the device circuit breaker OPEN (whole batches
        decided host-side); after ``breaker_reset_s`` one half-open probe
        batch tests recovery.  See docs/robustness.md.

        Overload resilience (ISSUE 7, docs/robustness.md "Overload &
        brownout"): ``admission_target_s``/``admission_queue_cap``/
        ``admission_min_cap`` parameterize the CoDel-style admission gate —
        a submit that would push the queue past the wait-targeted cap is
        rejected typed RESOURCE_EXHAUSTED at admission (and one whose
        deadline lands inside the predicted wait + device RTT is shed
        DEADLINE_EXCEEDED there, before it ever queues).
        ``adaptive_window`` enables the Little's-law controller that tunes
        the live in-flight window and batch-cut inside
        [1, max_inflight_batches] / [1, max_batch] from observed arrival
        rate, queue wait and device RTT — ``max_inflight_batches`` is the
        CAP, no longer the operating point.  ``brownout`` lets saturated
        windows spill small head-of-queue batches to the exact host oracle
        (``brownout_max_batch`` rows at a time): overload degrades
        throughput, never correctness.

        Lane selection (ISSUE 12, docs/performance.md "Lane selection"):
        ``lane_select`` promotes the exact host oracle from brownout
        fallback to a FIRST-CLASS serving lane — at every batch cut a
        cost model (EWMAs of host per-row service time, device RTT, queue
        depth, window occupancy, per-lane SLO burn) decides whether the
        cut is answered host-side (light-load p50 in single-digit ms
        instead of one device RTT) or rides the device (full pads under
        load — throughput preserved by construction); the
        latency-critical head of a device cut (by propagated deadline) is
        rescued host-side instead of shed.  ``lane_host_max_rows`` caps
        what the host lane may take per cut; ``speculative_dispatch``
        dual-dispatches the breaker's half-open probe batch to BOTH lanes
        and resolves first-wins (verdicts are bit-identical by PR 6's
        certification, so the race is safe — and the device half still
        decides the breaker).

        Change safety (ISSUE 10, docs/robustness.md "Change safety"):
        with ``canary_fraction`` > 0, a reconcile that actually changes
        the compiled corpus does NOT swap at 100% — a deterministic
        hash-fraction of requests routes to the new generation for
        ``canary_window_s`` while the rest keeps serving the previous one.
        Guards (``canary_thresholds``: runtime/change_safety.py
        GuardThresholds) compare the cohorts' deny/error/SLO rates; a
        breach auto-rolls-back (pointer swap — the previous snapshot and
        its device buffers are retained) and quarantines the poison
        configs, a clean window promotes.  ``snapshot_history`` bounds how
        many previous (snapshot, index) generations are retained for
        manual rollback.

        Replay preflight (ISSUE 13, docs/replay.md): with
        ``replay_pregate``, a corpus-changing reconcile is first REPLAYED
        against the in-process capture ring (replay/capture.py CAPTURE —
        arm it with --capture) through the exact host oracle on both the
        serving and the candidate snapshot; a verdict diff breaching the
        canary guard thresholds rejects the swap as typed
        SnapshotRejected BEFORE any live request reaches the candidate
        (zero live exposure, vs the canary's ~seconds of detection
        latency), with the attributed diff frozen into a
        replay-pregate-breach flight bundle.  A clean preflight annotates
        the canary phase and HALVES its deny-delta guard thresholds (the
        change already proved behavior-preserving on yesterday's
        traffic).  ``replay_pregate_budget_s`` bounds the reconcile-path
        replay cost; records past the budget are reported as truncated,
        never silently skipped."""
        self.index: HostIndex[EngineEntry] = HostIndex()
        self.generation = 0  # bumped per apply_snapshot (gauge + /debug/vars)
        self.max_batch = max_batch
        if max_delay_s is not None:
            import warnings

            warnings.warn(
                "PolicyEngine(max_delay_s=...) is deprecated and ignored: "
                "the engine lane dispatches adaptively (AdaptiveWindow); "
                "--batch-window-us still tunes the native C++ gather window",
                DeprecationWarning, stacklevel=2)
        self.max_delay_s = max_delay_s
        self.timeout_s = timeout_s
        self.members_k = members_k
        self.max_fallback_per_batch = max_fallback_per_batch
        self.max_inflight_batches = max(1, int(max_inflight_batches))
        self.dispatch_workers = max(1, int(dispatch_workers))
        self.batch_dedup = bool(batch_dedup)
        self.strict_verify = bool(strict_verify)
        self.analyze_policies = bool(analyze_policies)
        # ISSUE 14: membership-overflow in-kernel assist (None = env
        # default AUTHORINO_TPU_OVF_ASSIST; compiler/compile.py) and the
        # metadata prefetch cache (request-independent external documents
        # pinned at reconcile cadence; relations/prefetch.py)
        self.ovf_assist = ovf_assist
        self.metadata_prefetcher = None
        if metadata_prefetch:
            from ..relations.prefetch import MetadataPrefetcher

            self.metadata_prefetcher = MetadataPrefetcher(
                max_age_s=metadata_prefetch_max_age_s,
                refresh_s=metadata_prefetch_refresh_s)
        # incremental control plane (ISSUE 8): the persistent per-config
        # compile cache (fingerprint → artifact + the cross-reconcile
        # interner/DFA memos) and the latest reconcile's phase/delta
        # evidence for /debug/vars
        from ..snapshots.compile_cache import CompileCache

        self.compile_cache = CompileCache()
        self._control_plane: Optional[Dict[str, Any]] = None
        # latest reconcile's policy-analysis report (JSON-safe; /debug/vars)
        self._analysis: Optional[Dict[str, Any]] = None
        # latest reconcile's lowerability report (ISSUE 6: fast/slow lane
        # classification per config, with reason codes; /debug/vars)
        self._lowerability: Optional[Dict[str, Any]] = None
        self._verdict_cache = (VerdictCache(verdict_cache_size)
                               if verdict_cache_size else None)
        self._mesh = mesh
        self._snapshot: Optional[_Snapshot] = None
        self._swap_lock = threading.Lock()
        # ONE global dispatcher queue for every event loop (the gRPC/HTTP
        # servers and the native frontend's slow lane may share one engine
        # from different loops): futures remember their owning loop and
        # resolve via call_soon_threadsafe, so no per-loop queue/timer state
        # exists to leak when tests/reconciles create loops freely
        self._queue: deque = deque()
        self._queue_lock = threading.Lock()
        self._inflight = 0
        self.inflight_peak = 0    # high-watermark (bench occupancy evidence)
        self._swap_listeners: List[Any] = []
        self._g_inflight = metrics_mod.inflight_batches.labels("engine")
        self._g_depth = metrics_mod.dispatch_queue_depth.labels("engine")
        # fault tolerance (ISSUE 5): device circuit breaker, completer
        # watchdog, deadline shedding headroom, graceful-drain admission
        self.device_timeout_s = (float(device_timeout_s)
                                 if device_timeout_s else None)
        self.breaker = CircuitBreaker("engine", threshold=breaker_threshold,
                                      reset_s=breaker_reset_s)
        self._draining = False
        # cumulative typed serving errors (fleet fold, ISSUE 18): requests
        # failed UNAVAILABLE after every degrade lane was exhausted.
        # Deadline sheds stay out — they are the protection working.
        self.error_total = 0
        # EWMA of the device stage (launch→readback) — the shedding
        # headroom: a request whose deadline lands inside one expected
        # device round trip cannot be answered in time
        self._device_ewma = 0.0
        # overload resilience (ISSUE 7): CoDel-style admission on the
        # submit queue + the Little's-law window/batch-cut controller +
        # host-lane brownout when the device pipeline saturates
        if admission_min_cap is None:
            # floor = one full pipeline's worth of standing work: the gate
            # must never reject a burst the window itself could absorb
            admission_min_cap = max(64, self.max_inflight_batches * max_batch)
        self.admission = AdmissionController(
            "engine", target_s=admission_target_s,
            queue_cap=admission_queue_cap, min_cap=admission_min_cap)
        self.controller = AdaptiveWindow(
            "engine", cap=self.max_inflight_batches, batch_cap=max_batch,
            enabled=adaptive_window)
        self.brownout = bool(brownout)
        self.brownout_max_batch = max(1, int(brownout_max_batch))
        # concurrent brownout batches are bounded: the host lane absorbs
        # overload, it must not become an unbounded CPU amplifier
        self._brownout_limit = max(1, self.dispatch_workers // 2)
        self._brownout_inflight = 0
        self._brownout_total = 0
        # lane selection (ISSUE 12, docs/performance.md "Lane selection"):
        # the host oracle as a FIRST-CLASS serving lane — a per-batch-cut
        # cost model decides host vs device (brownout stays the separate
        # overload spill), the latency-critical head of a device cut is
        # rescued host-side by propagated deadline, and a half-open
        # breaker probe dual-dispatches the same rows to both lanes,
        # resolving first-wins (verdicts are bit-identical by PR 6's
        # certification, so the race is safe)
        self.lanes = LaneSelector(
            "engine", enabled=lane_select,
            host_max_rows=lane_host_max_rows,
            speculative=speculative_dispatch,
            host_concurrency=max(1, self.dispatch_workers // 2))
        if lane_select:
            # predicted-wait is lane-aware at admission: a deadline only
            # the microsecond host lane can meet is no longer doomed —
            # but only while the host lane has concurrency headroom to
            # actually take it (the floor collapses to the device RTT
            # when the cap is saturated: backpressure stays honest)
            self.admission.lane_floor = self.lanes.admission_floor
        # decision observability (ISSUE 9, docs/observability.md): the SLO
        # burn-rate tracker (--slo-ms; 0 = off) and the flight-recorder
        # debug-vars provider.  The rule heat map lives on each snapshot
        # (attribution must match the corpus that evaluated the batch).
        self.slo = None
        if slo_ms:
            from ..utils.slo import SloTracker

            self.slo = SloTracker("engine", slo_ms)
        # change safety (ISSUE 10): the canary state machine, the
        # quarantine record (poison config id → fingerprints + the prior
        # entry each resync substitutes back in), the bounded generation
        # history for manual rollback, and the last-rollback evidence
        self.canary_fraction = min(max(float(canary_fraction), 0.0), 1.0)
        self.canary_window_s = float(canary_window_s)
        self.canary_thresholds = canary_thresholds
        self._canary: Optional[safety_mod.CanaryPhase] = None
        self._quarantine: Optional[Dict[str, Any]] = None
        self._quarantine_prior: Dict[str, EngineEntry] = {}
        self._history: deque = deque(maxlen=max(1, int(snapshot_history)))
        self._last_rollback: Optional[Dict[str, Any]] = None
        self._g_canary = metrics_mod.canary_state.labels("engine")
        self._g_quarantine = metrics_mod.quarantined_configs.labels("engine")
        # kernel cost observatory (ISSUE 16): per-generation modeled-cost
        # lineage — lower().compile().cost_analysis() at each reconcile,
        # >=2x per-row regression raises the cost-regression anomaly
        self._cost_model = CostModel("engine")
        # traffic replay preflight (ISSUE 13): gate state + last verdict
        self.replay_pregate = bool(replay_pregate)
        self.replay_pregate_budget_s = float(replay_pregate_budget_s)
        self._last_pregate: Optional[Dict[str, Any]] = None
        self._g_replay_flips = metrics_mod.replay_diff_flips.labels("engine")
        # corpus preflight (ISSUE 19, docs/policy_ci.md): the long-retention
        # decision corpus replayed frequency-weighted before the canary —
        # synthetic witness rows (built lazily against the serving baseline,
        # cached per generation) extend the judgment to rules live traffic
        # never exercised
        self.corpus_pregate = str(corpus_pregate or "")
        self.corpus_pregate_budget_s = float(corpus_pregate_budget_s)
        self._corpus_rows: Optional[list] = None   # loaded captured rows
        self._corpus_load_error: Optional[str] = None
        self._corpus_synth: Tuple[int, list, Dict[str, Any]] = (-1, [], {})
        self._last_corpus_pregate: Optional[Dict[str, Any]] = None
        # tenant QoS plane (ISSUE 15, docs/tenancy.md): weighted-fair batch
        # cuts over per-tenant virtual queues inside the submit queue,
        # per-tenant quotas + CoDel wait tracking + tenant-aware doomed
        # shedding at admission, the tenant axis of the provenance/SLO
        # folds, and noisy-neighbor containment (a tenant-scoped
        # brownout/shed — the global OVERLOADED latch never fires for one
        # hot tenant).  The tenant is the AuthConfig identity every
        # encoded row already carries as config_id.
        from ..tenancy import TenantPlane

        self.tenancy = TenantPlane(
            "engine", enabled=bool(tenant_qos),
            default_weight=tenant_default_weight,
            weight_overrides=tenant_weights,
            default_quota_rps=tenant_quota_rps,
            admission_target_s=self.admission.target_s,
            contain_threshold=tenant_contain_threshold,
            contain_allowance_rps=tenant_contain_allowance_rps,
            top_k=tenant_top_k,
            wait_ewma=lambda: self.admission.wait_ewma,
            wait_target_s=lambda: self.admission.target_s,
            # second pressure signal: rising GLOBAL admission rejections
            # (the wait-targeted cap clamps the queue AT the target, so
            # the wait gauge alone can read healthy while cold tenants
            # are being turned away)
            reject_count=lambda: (
                self.admission.rejected.get("overload", 0)
                + self.admission.rejected.get("queue-full", 0)
                # rising queue-share rejections = a tenant persistently
                # over-occupying the shared queue: pressure even while
                # the bound keeps the global wait healthy
                + self.admission.rejected.get("tenant-queue-share", 0)))
        RECORDER.register_provider("engine", self, "debug_vars")

    # swap listeners: the native frontend rebuilds its C++ snapshot after
    # every corpus swap (runtime/native_frontend.py refresh)
    def add_swap_listener(self, cb) -> None:
        self._swap_listeners.append(cb)

    def remove_swap_listener(self, cb) -> None:
        if cb in self._swap_listeners:
            self._swap_listeners.remove(cb)

    def notify_swap_listeners(self) -> None:
        """Fire swap listeners without a corpus swap — used by the secret
        reconciler after in-place API-key/mTLS rotation, so the native
        frontend rebuilds its credential→plan variants
        (ref controllers/secret_controller.go:40-130 mutates evaluators in
        place; the fast lane's compiled view must follow)."""
        for cb in list(self._swap_listeners):
            cb()

    # ---- control plane ---------------------------------------------------

    def _resolve_mesh(self):
        if self._mesh == "auto":
            import jax

            from ..parallel import build_mesh

            self._mesh = build_mesh() if len(jax.devices()) > 1 else None
        return self._mesh

    def apply_snapshot(self, entries: Sequence[EngineEntry], override: bool = True) -> None:
        """Compile the new corpus off the serving path, then atomically swap
        snapshot + index (double buffering: in-flight batches keep the old
        params alive until their futures resolve).

        With ``strict_verify`` the compiled snapshot is tensor-linted HERE,
        before the generation bump: a corrupt snapshot raises
        SnapshotRejected and the old snapshot/index keep serving (the
        reconciler maps the raise to CachingError + retry).

        Incremental (ISSUE 8): compilation runs through the engine's
        persistent per-config compile cache and the device upload is a
        DELTA against the previous snapshot — an unchanged corpus compiles
        zero configs and ships zero bytes; verdict-cache entries of
        untouched configs survive the swap (per-config cache tokens).

        Change safety (ISSUE 10): still-poisoned quarantined configs are
        substituted with their prior artifacts before compile, and a
        corpus-changing swap enters the canary phase instead of serving
        100% immediately (``canary_fraction`` > 0)."""
        self._apply_entries(entries, override=override, allow_canary=True)

    def _apply_entries(self, entries: Sequence[EngineEntry],
                       override: bool = True,
                       allow_canary: bool = True) -> None:
        phase = self._canary
        if phase is not None:
            # a newer reconcile supersedes an undecided canary: fall back
            # to the baseline first — the new corpus gets its own canary
            # (never stack two candidate generations)
            self._canary_rollback(phase, reason="superseded",
                                  quarantine=False)
        entries = self._substitute_quarantined(entries)
        try:
            snap = _Snapshot(entries, members_k=self.members_k,
                             mesh=self._resolve_mesh(),
                             strict_verify=self.strict_verify,
                             compile_cache=self.compile_cache,
                             prev=self._snapshot,
                             breaker_threshold=self.breaker.threshold,
                             breaker_reset_s=self.breaker.reset_s,
                             ovf_assist=self.ovf_assist)
        except SnapshotRejected as e:
            metrics_mod.snapshot_rejected.labels("engine").inc()
            RECORDER.record("snapshot-rejected", lane="engine", detail={
                "generation": self.generation,
                "findings": [str(f) for f in e.findings[:5]]})
            log.error(
                "snapshot REJECTED by tensor lint (previous generation %d "
                "keeps serving): %s", self.generation,
                "; ".join(str(f) for f in e.findings[:5]))
            raise
        q = self._quarantine
        if q is not None:
            # stamp the ACTIVE quarantine onto the outgoing snapshot BEFORE
            # install fires the swap listeners: the publisher serializes
            # this record into the blob meta + manifest, so replicas
            # converge on the quarantined state — assigning it after the
            # listeners ran would race the publish thread's read
            snap.change_safety = {"quarantine": {
                "configs": sorted(q["configs"]),
                "from_generation": q["from_generation"]}}
        # replay preflight (ISSUE 13): judge a corpus-changing swap on
        # REPLAYED captured traffic before any live request can see it —
        # a breaching diff raises SnapshotRejected here (the old snapshot
        # keeps serving, zero live exposure); quarantine/rollback
        # re-applies (allow_canary=False) skip it, they must always land
        preflight = None
        if self.replay_pregate and allow_canary and not self._draining \
                and self._comparable_change(snap):
            preflight = self._run_replay_pregate(snap)
        # corpus preflight (ISSUE 19): the same judgment over the
        # long-retention corpus + synthetic witnesses — catches a breaching
        # edit to a rule the capture ring never exercised, zero exposure
        if self.corpus_pregate and allow_canary and not self._draining \
                and self._comparable_change(snap):
            self._run_corpus_pregate(snap)
        if allow_canary and self._should_canary(snap):
            self._enter_canary(snap, entries, override=override,
                               preflight=preflight)
        else:
            self._install_snapshot(snap, entries, override=override)
        if self.analyze_policies:
            self._run_policy_analysis(entries, snap)
            self._run_lowerability(entries, snap)

    def apply_published(self, loaded) -> None:
        """Serving-replica swap path: install a leader-serialized vetted
        snapshot (snapshots/distribution.py LoadedSnapshot) without
        compiling anything.  The admission gate lives in
        _Snapshot.from_published — an uncertified or locally-failing
        snapshot raises SnapshotRejected and the previous snapshot keeps
        serving, exactly like a strict-verify reconcile rejection."""
        try:
            snap = _Snapshot.from_published(
                loaded, members_k=self.members_k,
                strict_verify=self.strict_verify, prev=self._snapshot)
        except SnapshotRejected as e:
            metrics_mod.snapshot_rejected.labels("engine").inc()
            RECORDER.record("snapshot-rejected", lane="engine", detail={
                "generation": self.generation, "published": True,
                "findings": [str(f) for f in e.findings[:5]]})
            log.error(
                "published snapshot REJECTED at admission (previous "
                "generation %d keeps serving): %s", self.generation,
                "; ".join(str(f) for f in e.findings[:5]))
            raise
        entries = list(snap.by_id.values())
        self._install_snapshot(snap, entries, override=True)

    def _install_snapshot(self, snap: "_Snapshot",
                          entries: Sequence[EngineEntry],
                          override: bool = True) -> None:
        """Shared swap tail: index build, atomic swap, telemetry, swap
        listeners."""
        new_index: HostIndex[EngineEntry] = HostIndex()
        for e in entries:
            for host in e.hosts:
                new_index.set(e.id, host, e, override=override)
        # decision provenance (ISSUE 9): the rule heat map binds kernel rows
        # to (authconfig, rule source) for THIS snapshot — attribution and
        # the dead-rule report always read the corpus that evaluated
        self._build_heat(snap)
        # tenant QoS (ISSUE 15): weights/quotas resolve from the entries'
        # AuthConfig annotations at every reconcile
        try:
            self.tenancy.bind_entries(entries)
        except Exception:
            log.exception("tenant weight rebuild failed (swap unaffected)")
        with self._swap_lock:
            self.generation += 1
            # the mesh lane's verdict cache keys on snap.generation (the
            # single-corpus lane keys on per-config cache tokens instead):
            # in-flight batches of the OLD snapshot keep inserting/serving
            # under the tokens/generation they were encoded against, so
            # the swap structurally invalidates without TTLs
            snap.generation = self.generation
            prev_snap, prev_index = self._snapshot, self.index
            self._snapshot = snap
            self.index = new_index
            metrics_mod.snapshot_generation.labels("engine").set(self.generation)
            # bounded generation history (ISSUE 10): rollback is a pointer
            # swap to a retained (snapshot, index) pair — the old device
            # buffers are double-buffer safe and the compile cache keeps
            # re-applies nearly free
            if prev_snap is not None and (prev_snap.policy is not None
                                          or prev_snap.sharded is not None):
                self._history.append((prev_snap, prev_index))
        RECORDER.record("snapshot-swap", lane="engine", detail={
            "generation": snap.generation, "configs": len(snap.by_id)})
        self._record_control_plane(snap)
        # a snapshot leaves the history some swaps after its last batch:
        # draining at each swap names what it still holds as arrays before
        # then, scraped or not
        metrics_mod.drain()
        # listeners (the native frontend rebuilding its C++ snapshot) fire
        # BEFORE the advisory analysis: a revoking reconcile must propagate
        # at swap speed, not wait out a bounded-evaluation pass
        self.notify_swap_listeners()
        # metadata prefetch (ISSUE 14): register this snapshot's request-
        # independent metadata evaluators and (asynchronously) re-pin
        # their documents — after the listeners, off the swap-speed path;
        # a registration failure never fails a reconcile
        if self.metadata_prefetcher is not None:
            try:
                self.metadata_prefetcher.reconcile(entries)
            except Exception:
                log.exception("metadata prefetch registration failed "
                              "(reconcile unaffected)")
        # relation-table footprint gauges (ISSUE 14)
        try:
            from ..analysis.translation_validate import snapshot_policies

            rows = nbytes = 0
            for pol in snapshot_policies(snap):
                if getattr(pol, "rel_bits", None) is not None:
                    rows += int(pol.rel_bits.shape[0])
                    nbytes += int(pol.rel_bits.nbytes)
            metrics_mod.relation_table_rows.set(rows)
            metrics_mod.relation_table_bytes.set(nbytes)
        except Exception:
            log.exception("relation-table telemetry failed (swap unaffected)")

    def _record_control_plane(self, snap: "_Snapshot") -> None:
        """Reconcile telemetry (ISSUE 8 satellite): phase histograms,
        compile-cache hit/miss counters, delta-upload byte counters, and
        the /debug/vars control_plane block.  Advisory — never fails a
        swap."""
        try:
            for phase, dt in snap.phase_s.items():
                metrics_mod.reconcile_phase.labels(phase).observe(dt)
            rep = snap.compile_report
            if rep is not None:
                if rep.cached:
                    metrics_mod.compile_cache_events.labels("hit").inc(
                        rep.cached)
                if rep.compiled:
                    metrics_mod.compile_cache_events.labels("miss").inc(
                        rep.compiled)
            if snap.upload is not None:
                metrics_mod.delta_upload_bytes.labels("engine").inc(
                    int(snap.upload.get("upload_bytes", 0)))
                metrics_mod.full_upload_bytes.labels("engine").inc(
                    int(snap.upload.get("full_bytes", 0)))
            RECORDER.record("reconcile", lane="engine", detail={
                "generation": snap.generation,
                "phases_ms": {k: round(v * 1e3, 3)
                              for k, v in snap.phase_s.items()}})
            self._control_plane = {
                "generation": snap.generation,
                "phases_ms": {k: round(v * 1e3, 3)
                              for k, v in snap.phase_s.items()},
                "compile": rep.to_json() if rep is not None else None,
                "upload": snap.upload,
                "compile_cache": (self.compile_cache.stats()
                                  if self.compile_cache is not None else None),
                "per_config_cache_keying": snap.cache_tokens is not None,
            }
        except Exception:
            log.exception("control-plane telemetry failed (swap unaffected)")
        # kernel cost observatory (ISSUE 16): modeled per-row FLOPs/bytes
        # of the new generation's kernel entry points, diffed against the
        # previous generation.  Advisory end to end — a >=2x per-row
        # regression raises the cost-regression flight-recorder anomaly
        # and stamps the canary phase, but NEVER rejects the swap.
        try:
            cost_rec = self._cost_model.analyze(
                snap.generation, policy=snap.policy, params=snap.params,
                sharded=snap.sharded, recorder=RECORDER)
            if isinstance(self._control_plane, dict):
                self._control_plane["kernel_cost"] = cost_rec
            phase = self._canary
            if phase is not None and phase.snap is snap:
                phase.kernel_cost = cost_rec
        except Exception:
            log.exception("kernel cost analysis failed (swap unaffected)")

    def _build_heat(self, snap: "_Snapshot") -> None:
        if snap.heat is not None:
            return
        try:
            snap.heat = prov_mod.HeatMap.for_snapshot(snap.policy,
                                                      snap.sharded)
        except Exception:
            log.exception("rule heat map build failed (swap unaffected)")
            snap.heat = None

    # ---- change safety (ISSUE 10): canary, rollback, quarantine ----------

    def _should_canary(self, snap: "_Snapshot") -> bool:
        """A swap canaries when it can (both generations on the SAME lane —
        single-corpus↔single-corpus or mesh↔mesh; cohort routing has no
        meaning across a lane change) and should (the compiled corpus
        actually changed; an identical-fingerprint resync swaps straight
        through, it has nothing to prove).  Mesh↔mesh canaries (ISSUE 11)
        work exactly like single-corpus ones: cohorts are stamped at
        submit, batch cuts partition by cohort, and the guards read the
        shard-stacked attribution columns."""
        if not (self.canary_fraction > 0.0 and self.canary_window_s > 0.0):
            return False
        if self._draining:
            return False
        return self._comparable_change(snap)

    def _comparable_change(self, snap: "_Snapshot") -> bool:
        """True when the incoming snapshot actually CHANGES the compiled
        corpus and both generations are comparable (same lane) — the
        precondition shared by the canary split and the replay pregate:
        an identical-fingerprint resync has nothing to prove, a lane
        change has nothing to compare against."""
        prev = self._snapshot
        if prev is None or (prev.policy is None and prev.sharded is None):
            return False
        if snap.policy is None and snap.sharded is None:
            return False
        if (prev.sharded is None) != (snap.sharded is None):
            return False  # lane change: swap through, nothing to compare
        return snap.fingerprints != prev.fingerprints

    def _run_replay_pregate(self, snap: "_Snapshot") -> Dict[str, Any]:
        """Replay the candidate snapshot against the live capture ring and
        judge the verdict diff (ISSUE 13, docs/replay.md "Preflight
        gate").  Returns the preflight summary on pass/skip; raises typed
        SnapshotRejected on breach — the caller's old snapshot keeps
        serving and the candidate never sees a live request.

        Runs on the reconcile path but bounded: the replay stops at
        ``replay_pregate_budget_s`` and reports what it could not cover
        (a truncated preflight is partial evidence, not full coverage)."""
        from ..replay import pregate as pregate_mod
        from ..snapshots.diff import snapshot_diff

        t0 = time.monotonic()
        baseline = self._snapshot
        thresholds = self.canary_thresholds or safety_mod.GuardThresholds()
        records = CAPTURE.ring_records()
        if len(records) < thresholds.min_requests:
            self._last_pregate = {
                "result": "skipped",
                "reason": (f"capture ring holds {len(records)} record(s) < "
                           f"min_requests {thresholds.min_requests} — not "
                           f"enough replay evidence to judge"
                           + ("" if CAPTURE.enabled else
                              " (capture is OFF: arm --capture)")),
                "replayed": 0,
            }
            metrics_mod.replay_pregate.labels("skipped").inc()
            RECORDER.record("replay-pregate", lane="engine",
                            detail=self._last_pregate)
            log.warning("replay pregate SKIPPED: %s",
                        self._last_pregate["reason"])
            return self._last_pregate
        changed = set(snapshot_diff(baseline.fingerprints or {},
                                    snap.fingerprints or {})["recompile"])
        try:
            pf = pregate_mod.preflight(
                baseline, snap, records, thresholds, changed=changed,
                time_budget_s=self.replay_pregate_budget_s)
        except Exception:
            # a pregate bug must never block the control plane: the swap
            # proceeds under its normal canary protection, loudly
            log.exception("replay pregate errored (swap proceeds under "
                          "canary protection only)")
            self._last_pregate = {"result": "skipped",
                                  "reason": "pregate error (see logs)",
                                  "replayed": 0}
            metrics_mod.replay_pregate.labels("skipped").inc()
            return self._last_pregate
        report, breach = pf["report"], pf["breach"]
        self._g_replay_flips.set(report["flips"]["total"])
        elapsed_ms = round((time.monotonic() - t0) * 1e3, 3)
        if breach is None and report["replayed"] < thresholds.min_requests:
            # the ring LOOKED big enough, but the replay itself could not
            # re-decide min_requests records (every config missing on one
            # side, or the time budget truncated almost everything) — that
            # is ABSENT evidence, not clean evidence: record skipped, so
            # the canary keeps its normal (untightened) guards
            self._last_pregate = {
                "result": "skipped",
                "reason": (f"only {report['replayed']} of "
                           f"{len(records)} record(s) re-decided "
                           f"(missing configs / time budget) < "
                           f"min_requests {thresholds.min_requests}"),
                "replayed": report["replayed"],
                "skipped_detail": report["skipped"],
                "elapsed_ms": elapsed_ms,
            }
            metrics_mod.replay_pregate.labels("skipped").inc()
            RECORDER.record("replay-pregate", lane="engine",
                            detail=self._last_pregate)
            log.warning("replay pregate SKIPPED: %s",
                        self._last_pregate["reason"])
            return self._last_pregate
        if breach is not None:
            metrics_mod.replay_pregate.labels("breach").inc()
            metrics_mod.snapshot_rejected.labels("engine").inc()
            self._last_pregate = {
                "result": "breach",
                "replayed": report["replayed"],
                "flips_total": report["flips"]["total"],
                "flips": report["flips"],
                "guards": breach["guards"],
                "suspects": breach["suspects"],
                "elapsed_ms": elapsed_ms,
            }
            # the anomaly kind auto-dumps a flight bundle with the top-N
            # attributed verdict-diff rows frozen as incident evidence
            RECORDER.record(pregate_mod.PREGATE_ANOMALY, lane="engine",
                            detail={
                                "baseline_generation": baseline.generation,
                                "breach": breach,
                                "replayed": report["replayed"],
                                "elapsed_ms": elapsed_ms,
                            })
            top = breach["top_flips"][:3]
            findings = [
                f"replay pregate breach: {', '.join(breach['guards'])} over "
                f"{report['replayed']} replayed request(s) "
                f"({report['flips']['newly_denied']} newly denied, "
                f"{report['flips']['newly_allowed']} newly allowed)"
            ] + [
                f"{g['authconfig']} rule[{g['rule_index']}] {g['rule']} "
                f"{g['direction']} {g['count']} replayed request(s)"
                for g in top
            ]
            log.error("replay pregate REJECTED the candidate snapshot "
                      "(generation %d keeps serving, zero live exposure): "
                      "%s", baseline.generation, "; ".join(findings))
            exc = SnapshotRejected(findings)
            exc.replay_diff = breach  # the full attributed evidence
            raise exc
        self._last_pregate = {
            "result": "pass",
            "replayed": report["replayed"],
            "flips_total": report["flips"]["total"],
            "flips": report["flips"],
            "truncated": report["skipped"]["truncated"],
            "elapsed_ms": elapsed_ms,
        }
        metrics_mod.replay_pregate.labels("pass").inc()
        RECORDER.record("replay-pregate", lane="engine",
                        detail=self._last_pregate)
        log.info("replay pregate PASS: %d record(s) replayed, %d flip(s), "
                 "%.0fms", report["replayed"], report["flips"]["total"],
                 elapsed_ms)
        return self._last_pregate

    def _corpus_pregate_rows(self, baseline: "_Snapshot") -> Optional[list]:
        """Captured corpus rows (loaded once from --corpus-pregate) plus
        synthetic witness rows built against the BASELINE policy (cached
        per baseline generation — synthesis is a reconcile-path cost only
        on the first swap of each generation).  None when the corpus
        source is unreadable (the pregate skips, loudly)."""
        from ..corpus import read_corpus
        from ..corpus.synthesize import augment_corpus

        if self._corpus_rows is None and self._corpus_load_error is None:
            try:
                self._corpus_rows = read_corpus(self.corpus_pregate)
            except Exception as e:
                self._corpus_load_error = str(e)
                log.error("corpus pregate: corpus unreadable at %s: %s",
                          self.corpus_pregate, e)
        if self._corpus_rows is None:
            return None
        gen, synth, _rep = self._corpus_synth
        if gen != baseline.generation:
            synth, rep = [], {}
            if baseline.policy is not None:
                try:
                    aug = augment_corpus(baseline.policy, self._corpus_rows)
                    synth, rep = aug["rows"], {
                        "reasons": aug["synthesis"]["reasons"],
                        "uncoverable": aug["synthesis"]["uncoverable"][:20],
                        "coverage_before":
                            aug["coverage_before"]["fraction"],
                        "coverage_after": aug["coverage_after"]["fraction"],
                    }
                except Exception:
                    # synthesis is additive evidence: a synthesis bug must
                    # not disarm the captured-row judgment
                    log.exception("corpus pregate: synthesis errored "
                                  "(captured rows only this generation)")
            self._corpus_synth = (baseline.generation, synth, rep)
            try:
                metrics_mod.corpus_rows.labels("captured").set(
                    len(self._corpus_rows))
                metrics_mod.corpus_rows.labels("synthetic").set(len(synth))
            except Exception:
                pass
        return self._corpus_rows + self._corpus_synth[1]

    def _run_corpus_pregate(self, snap: "_Snapshot") -> Dict[str, Any]:
        """Judge the candidate snapshot on the frequency-weighted decision
        corpus (ISSUE 19, docs/policy_ci.md "Corpus pregate") — same
        state machine as the replay pregate, but the evidence is the
        long-retention corpus plus synthetic truth-table witnesses, so a
        breaching edit to a ZERO-TRAFFIC rule is rejected here with zero
        live exposure.  Raises typed SnapshotRejected on breach."""
        from ..corpus import pregate as corpus_pregate_mod
        from ..snapshots.diff import snapshot_diff

        t0 = time.monotonic()
        baseline = self._snapshot
        thresholds = self.canary_thresholds or safety_mod.GuardThresholds()
        rows = self._corpus_pregate_rows(baseline)
        if not rows:
            self._last_corpus_pregate = {
                "result": "skipped",
                "reason": (f"corpus unreadable: {self._corpus_load_error}"
                           if self._corpus_load_error else
                           f"corpus at {self.corpus_pregate} holds no rows"),
                "replayed": 0,
            }
            metrics_mod.corpus_pregate.labels("skipped").inc()
            RECORDER.record("corpus-pregate", lane="engine",
                            detail=self._last_corpus_pregate)
            log.warning("corpus pregate SKIPPED: %s",
                        self._last_corpus_pregate["reason"])
            return self._last_corpus_pregate
        changed = set(snapshot_diff(baseline.fingerprints or {},
                                    snap.fingerprints or {})["recompile"])
        try:
            pf = corpus_pregate_mod.corpus_preflight(
                baseline, snap, rows, thresholds, changed=changed,
                time_budget_s=self.corpus_pregate_budget_s)
        except Exception:
            log.exception("corpus pregate errored (swap proceeds under "
                          "canary protection only)")
            self._last_corpus_pregate = {"result": "skipped",
                                         "reason": "pregate error (see "
                                                   "logs)",
                                         "replayed": 0}
            metrics_mod.corpus_pregate.labels("skipped").inc()
            return self._last_corpus_pregate
        report, breach = pf["report"], pf["breach"]
        elapsed_ms = round((time.monotonic() - t0) * 1e3, 3)
        if breach is None and report["replayed"] < thresholds.min_requests:
            # below the weighted evidence floor: absent evidence, recorded
            # as skipped — never a false 'pass'
            self._last_corpus_pregate = {
                "result": "skipped",
                "reason": (f"weighted corpus evidence {report['replayed']} "
                           f"< min_requests {thresholds.min_requests}"),
                "replayed": report["replayed"],
                "skipped_detail": report["skipped"],
                "elapsed_ms": elapsed_ms,
            }
            metrics_mod.corpus_pregate.labels("skipped").inc()
            RECORDER.record("corpus-pregate", lane="engine",
                            detail=self._last_corpus_pregate)
            log.warning("corpus pregate SKIPPED: %s",
                        self._last_corpus_pregate["reason"])
            return self._last_corpus_pregate
        if breach is not None:
            metrics_mod.corpus_pregate.labels("breach").inc()
            metrics_mod.snapshot_rejected.labels("engine").inc()
            self._last_corpus_pregate = {
                "result": "breach",
                "replayed": report["replayed"],
                "replayed_rows": report.get("replayed_rows", 0),
                "flips": report["flips"],
                "guards": breach["guards"],
                "suspects": breach["suspects"],
                "origins": report.get("origins", {}),
                "elapsed_ms": elapsed_ms,
            }
            RECORDER.record(corpus_pregate_mod.CORPUS_PREGATE_ANOMALY,
                            lane="engine", detail={
                                "baseline_generation": baseline.generation,
                                "breach": breach,
                                "origins": report.get("origins", {}),
                                "replayed": report["replayed"],
                                "elapsed_ms": elapsed_ms,
                            })
            top = breach["top_flips"][:3]
            findings = [
                f"corpus pregate breach: {', '.join(breach['guards'])} over "
                f"{report['replayed']} weighted corpus decision(s) "
                f"({report['flips']['newly_denied']} newly denied, "
                f"{report['flips']['newly_allowed']} newly allowed)"
            ] + [
                f"{g['authconfig']} rule[{g['rule_index']}] {g['rule']} "
                f"{g['direction']} weight {g['count']} "
                f"(origins: {', '.join(g.get('origins') or []) or 'n/a'})"
                for g in top
            ]
            log.error("corpus pregate REJECTED the candidate snapshot "
                      "(generation %d keeps serving, zero live exposure): "
                      "%s", baseline.generation, "; ".join(findings))
            exc = SnapshotRejected(findings)
            exc.corpus_diff = breach  # the full attributed evidence
            raise exc
        self._last_corpus_pregate = {
            "result": "pass",
            "replayed": report["replayed"],
            "replayed_rows": report.get("replayed_rows", 0),
            "flips": report["flips"],
            "origins": report.get("origins", {}),
            "truncated": report["skipped"]["truncated"],
            "elapsed_ms": elapsed_ms,
        }
        metrics_mod.corpus_pregate.labels("pass").inc()
        RECORDER.record("corpus-pregate", lane="engine",
                        detail=self._last_corpus_pregate)
        log.info("corpus pregate PASS: %d weighted decision(s) "
                 "(%d row(s)) replayed, %d flip(s), %.0fms",
                 report["replayed"], report.get("replayed_rows", 0),
                 report["flips"]["total"], elapsed_ms)
        return self._last_corpus_pregate

    def _enter_canary(self, snap: "_Snapshot",
                      entries: Sequence[EngineEntry],
                      override: bool = True,
                      preflight: Optional[Dict[str, Any]] = None) -> None:
        """Start the canary phase: the reconcile's host index (pipeline
        semantics) lands immediately, but the compiled VERDICT lane splits
        — the hash-fraction cohort rides the new generation, everyone else
        keeps the baseline.  Swap listeners (native frontend rebuild,
        snapshot publisher) deliberately do NOT fire here: the native fast
        lane and the replica fleet hold the baseline until promotion, so a
        breach never has to claw anything back from them."""
        new_index: HostIndex[EngineEntry] = HostIndex()
        for e in entries:
            for host in e.hosts:
                new_index.set(e.id, host, e, override=override)
        self._build_heat(snap)
        baseline = self._snapshot
        # the per-config guards watch only what this reconcile CHANGED
        # (the PR 8 fingerprint diff): unchanged configs share the
        # baseline's artifacts and can only differ by cohort selection
        # bias — see change_safety.CanaryGuard
        from ..snapshots.diff import snapshot_diff

        changed = set(snapshot_diff(baseline.fingerprints or {},
                                    snap.fingerprints or {})["recompile"])
        # preflight-tightened guards (ISSUE 13): a candidate whose replay
        # diff came back CLEAN over a real traffic window has already
        # proved itself on yesterday's requests — its canary watches with
        # halved deny-delta thresholds, so a live-only regression (a
        # metadata dependency, a traffic shift the capture window missed)
        # trips earlier.  A skipped/flipping-but-under-threshold preflight
        # keeps the operator's thresholds untouched.
        thresholds = self.canary_thresholds
        if preflight is not None and preflight.get("result") == "pass" \
                and not preflight.get("flips_total"):
            import dataclasses

            base_th = thresholds or safety_mod.GuardThresholds()
            thresholds = dataclasses.replace(
                base_th, deny_delta=base_th.deny_delta / 2,
                config_deny_delta=base_th.config_deny_delta / 2)
            preflight = dict(preflight, guards_tightened=True)
        phase = safety_mod.CanaryPhase(
            snap=snap, baseline=baseline, entries=entries,
            index=new_index, baseline_index=self.index,
            fraction=self.canary_fraction, window_s=self.canary_window_s,
            guard=safety_mod.CanaryGuard(thresholds, changed=changed),
            preflight=preflight)
        with self._swap_lock:
            self.generation += 1
            snap.generation = self.generation
            self._canary = phase
            self.index = new_index
        self._g_canary.set(1)
        RECORDER.record("canary-start", lane="engine", detail={
            "generation": snap.generation,
            "baseline_generation": baseline.generation,
            "fraction": self.canary_fraction,
            "window_s": self.canary_window_s,
            "configs": len(snap.by_id)})
        self._record_control_plane(snap)
        log.info("canary started: generation %d serving %.1f%% of traffic "
                 "for %.1fs (baseline %d serves the rest)",
                 snap.generation, self.canary_fraction * 100,
                 self.canary_window_s, baseline.generation)
        phase.start_timer(lambda: self._canary_conclude(phase))

    def _canary_conclude(self, phase) -> None:
        """Window-expiry decision (the phase timer's callback): one final
        guard evaluation (forced past the rate limit — a per-batch check
        moments earlier must not turn this into a blind promote), then
        promote or roll back."""
        if self._draining:
            return
        try:
            b = phase.guard.breach(force=True)
            if b is not None:
                self._canary_rollback(phase, reason="guard-breach",
                                      detail=b)
            else:
                self._canary_promote(phase)
        except Exception:
            log.exception("canary conclude failed")

    def _canary_guard_check(self, phase) -> None:
        """Per-feed breach/expiry check (worker threads only — promotion
        and rollback fan out to swap listeners, which must never run on a
        serving event loop)."""
        if self._canary is not phase or self._draining:
            return
        b = phase.guard.breach()
        if b is not None:
            self._canary_rollback(phase, reason="guard-breach", detail=b)
        elif phase.expired():
            self._canary_conclude(phase)

    def _canary_promote(self, phase, manual: bool = False) -> bool:
        """Clean window (or operator override): the canary generation goes
        to 100% — a pointer swap; the baseline joins the rollback history
        and the swap listeners (native rebuild, publisher) finally fire."""
        with self._swap_lock:
            if self._canary is not phase:
                return False
            self._canary = None
            self._snapshot = phase.snap
            if phase.baseline is not None and (
                    phase.baseline.policy is not None
                    or phase.baseline.sharded is not None):
                self._history.append((phase.baseline, phase.baseline_index))
            metrics_mod.snapshot_generation.labels("engine").set(
                phase.snap.generation)
        phase.cancel_timer()
        phase.guard.close()
        self._g_canary.set(0)
        RECORDER.record("canary-promote", lane="engine", detail={
            "generation": phase.snap.generation, "manual": manual,
            "age_s": round(time.monotonic() - phase.t_start, 3)})
        log.info("canary promoted to 100%%: generation %d now serves all "
                 "traffic%s", phase.snap.generation,
                 " (manual override)" if manual else "")
        self.notify_swap_listeners()
        return True

    def _canary_rollback(self, phase, reason: str,
                         detail: Optional[Dict[str, Any]] = None,
                         quarantine: bool = True,
                         manual: bool = False) -> bool:
        """Guard breach (or supersede/manual): the baseline re-serves 100%
        immediately — a pointer swap to a CLONE of the retained baseline
        (fresh generation: in-flight batches pinned to the original keep
        resolving/inserting under their own tokens), then the poison
        configs are quarantined and the rest of the reconcile re-applied."""
        t_detect = time.monotonic()
        clone = phase.baseline.clone()
        clone.change_safety = {"rollback": {
            "from_generation": phase.snap.generation, "reason": reason}}
        with self._swap_lock:
            if self._canary is not phase:
                return False
            self._canary = None
            self.generation += 1
            clone.generation = self.generation
            self._snapshot = clone
            self.index = phase.baseline_index
            metrics_mod.snapshot_generation.labels("engine").set(
                clone.generation)
        phase.cancel_timer()
        phase.guard.close()
        self._g_canary.set(0)
        metrics_mod.snapshot_rollbacks.labels(reason).inc()
        self._last_rollback = {
            "t": time.time(), "reason": reason, "manual": manual,
            "from_generation": phase.snap.generation,
            "to_generation": clone.generation,
            "detect_ms": round((t_detect - phase.t_start) * 1e3, 3),
            "detail": detail, "quarantined": [],
        }
        RECORDER.record("snapshot-rollback", lane="engine", detail={
            "reason": reason,
            "from_generation": phase.snap.generation,
            "to_generation": clone.generation,
            "guard": detail})
        log.error("canary ROLLED BACK (%s): generation %d abandoned, "
                  "baseline re-serving as generation %d%s", reason,
                  phase.snap.generation, clone.generation,
                  f" — guard: {detail}" if detail else "")
        self.notify_swap_listeners()
        if quarantine and reason == "guard-breach":
            try:
                self._quarantine_poison(phase, detail, t_detect)
            except Exception:
                log.exception("quarantine re-apply failed (rolled-back "
                              "baseline keeps serving)")
        return True

    def _quarantine_poison(self, phase, detail: Optional[Dict[str, Any]],
                           t_detect: float) -> None:
        """Post-rollback quarantine: the PR 8 fingerprint diff names what
        the reconcile changed, the guard's per-config deny deltas pin the
        spike — their intersection is the poison set (every changed config
        when the breach had no per-config attribution).  The reconcile is
        then re-applied with ONLY the poison configs reverted to their
        prior compiled artifacts; the compile cache makes that nearly
        free.  Quarantine persists across resyncs (apply_snapshot keeps
        substituting) until the operator ships a FIXED config."""
        from ..snapshots.diff import snapshot_diff

        d = snapshot_diff(phase.baseline.fingerprints or {},
                          phase.snap.fingerprints or {})
        changed = set(d["recompile"])
        suspects = [s for s in (detail or {}).get("suspects", [])
                    if s in changed]
        poison = suspects or sorted(changed)
        if not poison:
            return
        base_by_id = phase.baseline.by_id
        configs: Dict[str, Dict[str, Any]] = {}
        prior: Dict[str, EngineEntry] = {}
        for e in phase.entries:
            if e.id not in poison:
                continue
            configs[e.id] = {
                "poison": (phase.snap.fingerprints or {}).get(e.id),
                "prior": (phase.baseline.fingerprints or {}).get(e.id),
            }
            pe = base_by_id.get(e.id)
            if pe is not None:
                prior[e.id] = pe
            # pe is None → the poison config is NEW this reconcile: it has
            # no prior artifact and quarantines out entirely (the
            # substitution below drops it while keeping it quarantined)
        if not configs:
            return
        self._quarantine = {
            "since": time.time(), "reason": "guard-breach",
            "from_generation": phase.snap.generation,
            "configs": configs,
        }
        self._quarantine_prior = prior
        self._g_quarantine.set(len(configs))
        RECORDER.record("quarantine", lane="engine", detail={
            "configs": sorted(configs),
            "from_generation": phase.snap.generation})
        log.warning("quarantined %d poison config(s) %s: re-applying the "
                    "reconcile with their prior artifacts", len(configs),
                    sorted(configs))
        # re-apply the ORIGINAL entries: the quarantine is armed above, so
        # _substitute_quarantined swaps each poison entry for its prior
        # artifact (or drops a no-prior one) exactly like a control-plane
        # resync would — one substitution path, and the quarantine record
        # stays intact for configs that have no prior to serve
        self._apply_entries(phase.entries, override=True,
                            allow_canary=False)
        if self._last_rollback is not None:
            self._last_rollback["quarantined"] = sorted(configs)
            self._last_rollback["recover_ms"] = round(
                (time.monotonic() - t_detect) * 1e3, 3)

    def _substitute_quarantined(
            self, entries: Sequence[EngineEntry]) -> Sequence[EngineEntry]:
        """Resync guard: while a quarantine is active, incoming entries
        that still carry the POISON fingerprint are substituted with their
        prior artifacts (the control plane keeps resyncing the same bad
        spec — it must not re-serve it); an entry whose fingerprint
        changed (neither poison nor prior) was fixed by the operator and
        is released back to the normal (canaried) path."""
        q = self._quarantine
        if not q:
            return entries
        from ..snapshots.fingerprint import rules_fingerprint

        qc: Dict[str, Dict[str, Any]] = q["configs"]
        out: List[EngineEntry] = []
        still: Dict[str, Dict[str, Any]] = {}
        for e in entries:
            rec = qc.get(e.id)
            if rec is None:
                out.append(e)
                continue
            fp = rules_fingerprint(e.rules) if e.rules is not None else None
            if fp == rec["poison"]:
                still[e.id] = rec
                pe = self._quarantine_prior.get(e.id)
                if pe is not None:
                    out.append(EngineEntry(id=e.id, hosts=list(e.hosts),
                                           runtime=pe.runtime,
                                           rules=pe.rules))
                # no prior artifact: stays quarantined out
            elif fp == rec["prior"]:
                # already the prior artifact (our own quarantine re-apply,
                # or the operator reverting by hand): serve it, keep the
                # quarantine armed against the poison spec resyncing back
                still[e.id] = rec
                out.append(e)
            else:
                log.info("quarantine released for %s: fingerprint changed "
                         "(operator fix) — the new spec takes the normal "
                         "path", e.id)
                out.append(e)
        if still != qc:
            if still:
                self._quarantine = dict(q, configs=still)
            else:
                self.clear_quarantine(note="all poison configs changed")
            self._g_quarantine.set(len(still))
        return out

    def clear_quarantine(self, note: str = "") -> bool:
        q = self._quarantine
        if q is None:
            return False
        RECORDER.record("quarantine-clear", lane="engine", detail={
            "note": note, "configs": sorted(q["configs"])})
        log.info("quarantine cleared (%s): %s", note or "operator",
                 sorted(q["configs"]))
        self._quarantine = None
        self._quarantine_prior = {}
        self._g_quarantine.set(0)
        return True

    @property
    def quarantine_active(self) -> bool:
        return self._quarantine is not None

    def canary_promote(self) -> bool:
        """Operator override (analysis CLI --promote / /debug/canary):
        promote the in-progress canary immediately, guard unconsulted."""
        phase = self._canary
        return self._canary_promote(phase, manual=True) \
            if phase is not None else False

    def canary_rollback(self, reason: str = "manual") -> bool:
        """Operator override: roll back the in-progress canary (no
        quarantine — the operator is driving), or, with no canary active,
        pointer-swap back to the newest retained history generation."""
        phase = self._canary
        if phase is not None:
            return self._canary_rollback(phase, reason=reason,
                                         quarantine=False, manual=True)
        return self.rollback_last(reason=reason)

    def rollback_last(self, reason: str = "manual") -> bool:
        """Manual rollback outside a canary: re-serve the newest retained
        (snapshot, index) pair from the bounded generation history."""
        with self._swap_lock:
            if not self._history:
                return False
            prev_snap, prev_index = self._history.pop()
            clone = prev_snap.clone()
            from_gen = (self._snapshot.generation
                        if self._snapshot is not None else 0)
            self.generation += 1
            clone.generation = self.generation
            clone.change_safety = {"rollback": {
                "from_generation": from_gen, "reason": reason}}
            self._snapshot = clone
            self.index = prev_index
            metrics_mod.snapshot_generation.labels("engine").set(
                clone.generation)
        metrics_mod.snapshot_rollbacks.labels(reason).inc()
        self._last_rollback = {
            "t": time.time(), "reason": reason, "manual": True,
            "from_generation": from_gen,
            "to_generation": clone.generation,
            "detect_ms": None, "detail": None, "quarantined": [],
        }
        RECORDER.record("snapshot-rollback", lane="engine", detail={
            "reason": reason, "from_generation": from_gen,
            "to_generation": clone.generation})
        log.warning("manual rollback: generation %d re-serving as %d",
                    from_gen, clone.generation)
        self.notify_swap_listeners()
        return True

    def canary_observe_external(self, rows, firing, heat,
                                shards=None) -> None:
        """Baseline-cohort guard evidence from OUTSIDE the engine's own
        dispatch — the native fast lane serves the baseline during a
        canary (its C++ snapshot only rebuilds on promotion), so its
        per-batch attribution strengthens the comparison.  Breach handling
        hops to the encode pool: the caller may be a readback thread that
        must never run swap listeners."""
        phase = self._canary
        if phase is None or heat is None or firing is None:
            return
        try:
            phase.guard.observe_batch(False, rows, firing, heat,
                                      shards=shards)
            if phase.guard.breach() is not None or phase.expired():
                _encode_pool(self.dispatch_workers).submit(
                    self._canary_guard_check, phase)
        except Exception:
            log.exception("external canary guard feed failed")

    def change_safety_vars(self) -> Dict[str, Any]:
        """JSON-safe change-safety state (pure read — /debug/canary,
        /debug/vars, the native frontend's mirror, bench artifacts)."""
        phase = self._canary
        q = self._quarantine
        with self._swap_lock:
            # a reconcile thread appends to the bounded deque under this
            # lock; iterating it unguarded can raise mid-reconcile —
            # exactly when the operator is reading the debug surface
            history = [s.generation for s, _ in self._history]
        return {
            "canary_fraction": self.canary_fraction,
            "canary_window_s": self.canary_window_s,
            "canary": phase.to_json() if phase is not None else None,
            "quarantine": ({
                "since": q["since"], "reason": q["reason"],
                "from_generation": q["from_generation"],
                "configs": sorted(q["configs"]),
            } if q is not None else None),
            "history_generations": history,
            "last_rollback": self._last_rollback,
        }

    def _run_policy_analysis(self, entries: Sequence[EngineEntry],
                             snap: "_Snapshot") -> None:
        """Cedar-style semantic pass, once per reconcile (never per
        request): constant-allow/deny rules, shadowed/duplicate rules,
        duplicate-host routing.  Findings are logged ONCE here, counted in
        auth_server_policy_analysis_findings_total{kind,authconfig}, and
        kept JSON-safe for /debug/vars.  Advisory only — a failure inside
        the analyzer must never fail the reconcile."""
        try:
            from ..analysis.policy_analysis import analyze_snapshot

            findings, summary = analyze_snapshot(
                entries, snap.policy, sharded=snap.sharded)
            for f in findings:
                metrics_mod.policy_analysis_findings.labels(
                    f.kind, str(f.detail.get("config", ""))).inc()
            if findings:
                by_kind: Dict[str, int] = {}
                for f in findings:
                    by_kind[f.kind] = by_kind.get(f.kind, 0) + 1
                log.warning(
                    "policy analysis (generation %d): %d finding(s) %s — "
                    "first: %s (full list on /debug/vars)",
                    snap.generation, len(findings), by_kind,
                    findings[0])
            skipped = summary.get("skipped", [])
            for s in skipped:
                metrics_mod.policy_analysis_skipped.labels(
                    str(s.get("config", ""))).inc()
            # the per-config list is bounded (100 entries); any remainder
            # still counts, attributed to the catch-all label so the total
            # always equals skipped_wide
            extra = int(summary.get("skipped_wide", 0)) - len(skipped)
            if extra > 0:
                metrics_mod.policy_analysis_skipped.labels("").inc(extra)
            self._analysis = {
                "generation": snap.generation,
                "findings": [f.to_json() for f in findings],
                "summary": summary,
            }
        except Exception:
            log.exception("policy analysis failed (reconcile unaffected)")

    def _run_lowerability(self, entries: Sequence[EngineEntry],
                          snap: "_Snapshot") -> None:
        """Lowerability report (ISSUE 6 layer 3): classify every config as
        fast-lane or slow-lane with a reason code, once per reconcile.
        Advisory only — surfaced on /debug/vars, counted per (lane,
        reason) in auth_server_lowerability_configs_total, and never a
        reconcile failure."""
        try:
            from ..analysis.translation_validate import (
                lowerability_report,
                snapshot_policies,
            )

            # mesh snapshots compile per-shard policies; the classifier
            # reads each config's CPU-assist leaves from its owning shard
            report = lowerability_report(entries, snapshot_policies(snap))
            for lane, reason, n in report["series"]:
                metrics_mod.lowerability_configs.labels(lane, reason).inc(n)
            # would-be-fast-if-fixed rollup (ISSUE 14): gauges trend the
            # per-reason exile counts across reconciles
            for reason, b in (report.get("blocking_reasons") or {}).items():
                metrics_mod.lowerability_blocking.labels(
                    reason, "configs").set(b["configs"])
                metrics_mod.lowerability_blocking.labels(
                    reason, "sole_blocker").set(b["sole_blocker"])
            report["generation"] = snap.generation
            self._lowerability = report
        except Exception:
            log.exception("lowerability report failed (reconcile unaffected)")

    def snapshot_policy(self) -> Optional[CompiledPolicy]:
        snap = self._snapshot
        return snap.policy if snap else None

    def debug_vars(self) -> Dict[str, Any]:
        """JSON-safe live state for the /debug/vars endpoint: config
        generation, the global dispatcher's backlog + in-flight window
        occupancy, and the compiled snapshot's shape.  Read-only,
        GIL-atomic reads."""
        snap = self._snapshot
        out: Dict[str, Any] = {
            "generation": self.generation,
            "max_batch": self.max_batch,
            "max_delay_s": self.max_delay_s,
            "members_k": self.members_k,
            "queue_depth": len(self._queue),
            "inflight_batches": self._inflight,
            "inflight_peak": self.inflight_peak,
            "max_inflight_batches": self.max_inflight_batches,
            "dispatch_workers": self.dispatch_workers,
            "batch_dedup": self.batch_dedup,
            "verdict_cache": (self._verdict_cache.counts()
                              if self._verdict_cache is not None else None),
            "strict_verify": self.strict_verify,
            "control_plane": self._control_plane,
            "policy_analysis": self._analysis,
            "lowerability": self._lowerability,
            "translation_validation": (getattr(snap, "translation", None)
                                       if snap is not None else None),
            "breaker": self.breaker.to_json(),
            "draining": self._draining,
            "device_timeout_s": self.device_timeout_s,
            "device_rtt_ewma_s": self._device_ewma,
            "admission": self.admission.to_json(),
            "adaptive": self.controller.to_json(),
            "brownout": {
                "enabled": self.brownout,
                "max_batch": self.brownout_max_batch,
                "inflight": self._brownout_inflight,
                "concurrency_limit": self._brownout_limit,
                "decisions": self._brownout_total,
            },
            # lane selection (ISSUE 12): cost-model EWMAs, per-reason
            # decision counts, rows served per lane, speculative outcomes
            "lane_select": self.lanes.to_json(),
            # tenant QoS plane (ISSUE 15, docs/tenancy.md): weights, fair-
            # cut evidence, per-tenant admission/wait state, top-tenant
            # stats and containment — also served on /debug/tenants
            "tenancy": self.tenancy.to_json(),
            "faults": (faults.FAULTS.describe() if faults.ACTIVE else
                       {"armed": False}),
            # decision observability (ISSUE 9, docs/observability.md):
            # heat-map shape + fold evidence, the dead-rule cross-reference
            # against the static findings, decision-log state, the SLO
            # burn-rate windows, and the flight recorder's tail
            "provenance": {
                "expose_deny_reason": prov_mod.EXPOSE_DENY_REASON,
                "heat": (snap.heat.to_json()
                         if snap is not None and snap.heat is not None
                         else None),
                "dead_rules": prov_mod.dead_rule_report(
                    getattr(snap, "heat", None) if snap else None,
                    self._analysis),
                "decisions": {
                    "capacity": prov_mod.DECISIONS.capacity,
                    "sample_n": prov_mod.DECISIONS.sample_n,
                    "records_total": prov_mod.DECISIONS.records_total,
                },
            },
            "slo": self.slo.to_json() if self.slo is not None else None,
            # metadata prefetch cache (ISSUE 14): pinned-document counts,
            # staleness/refresh knobs, hit/miss/stale counters
            "metadata_prefetch": (self.metadata_prefetcher.to_json()
                                  if self.metadata_prefetcher is not None
                                  else None),
            "flight_recorder": RECORDER.to_json(),
            # durable local state plane (ISSUE 20, docs/robustness.md
            # "Crash recovery & warm restart"): warm-start outcome per
            # phase, live staleness, write-behind cadence.  Set by cli.py
            # when --state-dir is armed; None otherwise.
            "state_plane": (self.state_plane.to_json()
                            if getattr(self, "state_plane", None) is not None
                            else None),
            # kernel cost observatory (ISSUE 16, docs/performance.md
            # "Kernel cost model"): the process-wide structural ledger
            # (launches/bytes/pad-waste per lane), the modeled per-row
            # cost lineage, and the jit entry points the serving snapshot
            # can dispatch through (the warm-grid audit surface)
            "kernel_cost": {
                "ledger": LEDGER.to_json(),
                "modeled": self._cost_model.to_json(),
                "entry_points": kernel_cost_mod.entry_points(
                    policy=getattr(snap, "policy", None),
                    sharded=getattr(snap, "sharded", None)),
            },
            "change_safety": self.change_safety_vars(),
            # traffic replay (ISSUE 13, docs/replay.md): capture-log state
            # + the last preflight verdict (also on /debug/replay)
            "replay": {
                "capture": CAPTURE.to_json(),
                "pregate": {
                    "enabled": self.replay_pregate,
                    "budget_s": self.replay_pregate_budget_s,
                    "last": self._last_pregate,
                },
            },
            # decision corpus (ISSUE 19, docs/policy_ci.md): the pregate
            # corpus source, its row counts by origin, the synthesis
            # summary for the serving baseline, and the last verdict
            "corpus": {
                "enabled": bool(self.corpus_pregate),
                "source": self.corpus_pregate or None,
                "budget_s": self.corpus_pregate_budget_s,
                "rows_captured": (len(self._corpus_rows)
                                  if self._corpus_rows is not None else 0),
                "rows_synthetic": len(self._corpus_synth[1]),
                "synthesis": self._corpus_synth[2] or None,
                "load_error": self._corpus_load_error,
                "last": self._last_corpus_pregate,
            },
            "snapshot": None,
        }
        if snap is not None:
            policy = snap.policy
            out["snapshot"] = {
                "configs": len(snap.by_id),
                "sharded": snap.sharded is not None,
                "compiled_configs": (len(policy.config_ids)
                                     if policy is not None else 0),
                "n_attrs": int(getattr(policy, "n_attrs", 0)) if policy else 0,
                "n_leaves": int(getattr(policy, "n_leaves", 0)) if policy else 0,
            }
            if snap.sharded is not None:
                # mesh lane (ISSUE 11): per-device breaker trail, occupancy
                # windows, failover counts, and the per-shard upload bytes
                # of the serving snapshot
                try:
                    out["mesh"] = snap.sharded.mesh_vars()
                except Exception:
                    out["mesh"] = None
        return out

    # ---- request path ----------------------------------------------------

    def lookup(self, host: str) -> Optional[EngineEntry]:
        """Host lookup with :port-stripping retry
        (ref: pkg/service/auth.go:270-289)."""
        entry = self.index.get(host)
        if entry is None and ":" in host:
            entry = self.index.get(host.rsplit(":", 1)[0])
        return entry

    async def check(self, request: CheckRequestModel, span=None,
                    deadline: Optional[float] = None) -> AuthResult:
        """Full request-time flow (ref: pkg/service/auth.go:239-310).
        ``deadline`` is the propagated Envoy Check() deadline (monotonic
        seconds): it bounds the pipeline and arms deadline-aware shedding
        in the batch dispatcher."""
        entry = self.lookup(request.host())
        if entry is None:
            return AuthResult(code=NOT_FOUND, message="Service not found")
        pipeline = AuthPipeline(request, entry.runtime, timeout=self.timeout_s,
                                span=span, deadline=deadline)
        return await pipeline.evaluate()

    def admission_precheck(self, deadline: Optional[float] = None):
        """Front-door overload check for the gRPC/HTTP servers at the
        ACTUAL queue depth: a request arriving into a full hard cap, or
        doomed on arrival while the lane is OVERLOADED, is answered typed
        before a span or pipeline is built.  Deterministic — the
        submit-time gate stays the one true admission point (this never
        consumes CoDel pacing state) and never rejects anything that gate
        would accept.  Returns an AuthResult to serve, or None to
        proceed."""
        rej = self.admission.precheck(len(self._queue), deadline=deadline,
                                      rtt_s=self._device_ewma)
        if rej is None:
            return None
        code, reason = rej
        self.admission.count_reject(reason)
        if code == DEADLINE_EXCEEDED:
            metrics_mod.deadline_shed.labels("engine").inc()
            return AuthResult(code=code,
                              message="rejected at admission: deadline "
                                      "cannot be met")
        return AuthResult(code=code,
                          message=f"server overloaded ({reason})")

    # ---- micro-batching verdicts ----------------------------------------

    def provider_for(self, config_name: str):
        """BatchedVerdictProvider bound to one compiled config — handed to
        PatternMatching evaluators at translate time."""

        async def provider(pipeline, evaluator_slot: int) -> Tuple[bool, bool]:
            rule, skipped, snap = await self.submit(
                pipeline.authorization_json(), config_name, span=pipeline.span,
                deadline=getattr(pipeline, "deadline", None),
                return_snapshot=True)
            # pin the evaluating snapshot on the pipeline: a deny built
            # moments later attributes against THIS corpus, not whatever
            # a concurrent reconcile swapped in since
            pipeline.eval_snapshot = snap
            e = evaluator_slot
            return bool(rule[e]), bool(skipped[e])

        return provider

    def attribution_for(self, config_name: str):
        """Deny-attribution resolver bound to one config (ISSUE 9): handed
        to PatternMatching evaluators at translate time alongside
        provider_for.  Called ONLY on the deny path (slow lane — fast-lane
        denials are attributed per batch instead); returns the provenance
        dict for Envoy dynamic_metadata / X-Ext-Auth-Reason, or None when
        no compiled snapshot covers the config."""

        def attributor(evaluator_slot: int, snap=None):
            # prefer the snapshot that evaluated the request (pinned on
            # the pipeline by provider_for); fall back to the serving one
            # for inline/interpreter callers with no pinned snapshot
            if snap is None:
                snap = self._snapshot
            heat = getattr(snap, "heat", None) if snap is not None else None
            if heat is None:
                return None
            try:
                if snap.sharded is not None:
                    shard, row = snap.sharded.locator[config_name]
                    src = heat.source(row, evaluator_slot, shard=shard)
                else:
                    row = snap.policy.config_ids[config_name]
                    src = heat.source(row, evaluator_slot)
            except (KeyError, AttributeError):
                return None
            return prov_mod.deny_provenance(config_name, evaluator_slot,
                                            src, lane="engine")

        return attributor

    async def submit(self, doc: Any, config_name: str, span: Any = None,
                     deadline: Optional[float] = None,
                     return_snapshot: bool = False,
                     ) -> Tuple[np.ndarray, np.ndarray]:
        """Queue one request for the next micro-batch; resolves to that
        request's per-evaluator (rule_results [E], skipped [E]).  ``span``
        (the request's RequestSpan, optional) lets the batch's DeviceBatch
        span link back to this request's trace.  ``deadline`` (monotonic
        seconds, the propagated Check() deadline) arms deadline-aware
        shedding: a request that cannot make it is failed fast with a
        typed DEADLINE_EXCEEDED before encode, never a wasted kernel.

        The dispatch decision is deferred one loop iteration (call_soon):
        every submit scheduled in the same iteration — a gather, a burst of
        connection reads — lands in one batch cut, while a lone light-load
        request still dispatches immediately after its iteration, never
        waiting a delay timer."""
        if self._draining:
            # graceful drain: stop admitting — already-queued work keeps
            # flowing, but nothing new may extend the drain
            raise CheckAbort(UNAVAILABLE, "server draining")
        # admission control (ISSUE 7): doomed or beyond-the-wait-target
        # work is rejected HERE, typed, before it queues — never after an
        # encode, never as a raw exception.  A doomed-deadline rejection
        # also counts as a deadline shed (it is one, just earlier).
        # Tenant-aware doom depth (ISSUE 15): the deadline predictor sees
        # THIS tenant's fair-share effective depth, not the global queue —
        # one tenant's standing backlog cannot doom another's deadlines.
        ten = self.tenancy
        # tenant-scoped admission (ISSUE 15) runs BEFORE the global gate:
        # quota token bucket, then containment pacing.  Typed
        # RESOURCE_EXHAUSTED naming the tenant; the global OVERLOADED
        # latch and its CoDel state are untouched — every other tenant
        # keeps its full admission budget.  Ordering matters: a contained
        # hot tenant's flood must be paced HERE, or it keeps the shared
        # queue at the global cap and the global gate rejects every
        # tenant's arrivals indiscriminately — the exact collateral
        # containment exists to stop.
        if ten.enabled:
            trej = ten.admit(config_name, depth=len(self._queue),
                             effective_cap=self.admission.effective_cap())
            if trej is not None:
                code, reason = trej
                self.admission.count_reject(reason)
                ten.count_reject(config_name, reason)
                phase = self._canary
                if phase is not None:
                    # per-tenant canary guard feed (ISSUE 15): a canaried
                    # change that pushes its own tenant into tenant-scoped
                    # rejections must accumulate breach evidence
                    try:
                        in_can = phase.in_cohort(doc) or \
                            config_name not in phase.baseline.by_id
                        phase.guard.observe_tenant_rejection(
                            in_can, config_name)
                        self._canary_guard_check(phase)
                    except Exception:
                        log.exception("tenant canary feed failed")
                raise CheckAbort(
                    code, f"tenant {config_name} over its QoS budget "
                          f"({reason}): admission rejected")
        doom_depth = ten.doom_depth(config_name, len(self._queue)) \
            if ten.enabled else None
        rej = self.admission.admit(len(self._queue), deadline=deadline,
                                   rtt_s=self._device_ewma,
                                   doom_depth=doom_depth)
        if rej is not None:
            code, reason = rej
            self.admission.count_reject(reason)
            if code == DEADLINE_EXCEEDED:
                metrics_mod.deadline_shed.labels("engine").inc()
                if ten.enabled and doom_depth is not None:
                    # the tenant-aware predictor doomed it: the shed is
                    # scoped to this tenant's own standing queue — and it
                    # feeds the per-tenant canary guard like every other
                    # tenant-scoped rejection (the guard's documented
                    # attempt set includes tenant-aware doomed sheds)
                    ten.count_reject(config_name, "doomed-deadline")
                    phase = self._canary
                    if phase is not None:
                        try:
                            in_can = phase.in_cohort(doc) or \
                                config_name not in phase.baseline.by_id
                            phase.guard.observe_tenant_rejection(
                                in_can, config_name)
                            self._canary_guard_check(phase)
                        except Exception:
                            log.exception("tenant canary feed failed")
                raise CheckAbort(code, "rejected at admission: deadline "
                                       "cannot be met")
            raise CheckAbort(code, f"server overloaded ({reason}): "
                                   "admission rejected")
        loop = asyncio.get_running_loop()
        fut: asyncio.Future = loop.create_future()
        # canary cohort (ISSUE 10): stamped at submit — deterministic over
        # the request's identity, so retries/redispatches keep the cohort
        phase = self._canary
        # a config ADDED by the canaried reconcile has no baseline artifact:
        # its traffic must ride the candidate regardless of cohort (the
        # baseline snapshot cannot decide it — encoding against it would
        # hard-fail and walk the breaker open on healthy hardware)
        in_canary = phase is not None and (
            phase.in_cohort(doc)
            or config_name not in phase.baseline.by_id)
        with self._queue_lock:
            self._queue.append(_Pending(doc, config_name, fut, loop,
                                        span=span, t_enq=time.monotonic(),
                                        deadline=deadline,
                                        canary=in_canary))
            self.controller.observe_arrivals()
            ten.on_enqueue(config_name)
        loop.call_soon(self._maybe_dispatch)
        rule, skipped, snap = await fut
        if return_snapshot:
            # deny attribution (ISSUE 9): the caller gets the snapshot
            # that EVALUATED this request, so a reconcile landing between
            # verdict and deny-response build cannot relabel the rule
            return rule, skipped, snap
        return rule, skipped

    # ---- pipelined dispatch ----------------------------------------------

    def _maybe_dispatch(self) -> None:
        """Cut and launch batches while the window has free slots and the
        queue is non-empty.  Runs on event loops (post-submit) AND on the
        completion thread (post-readback) — redundant calls are cheap
        no-ops, so no timer is ever needed: a full window guarantees a
        future completion, and that completion cuts the next batch.

        The window bound is the ADAPTIVE controller's live window (≤ the
        max_inflight_batches cap); the cut stays completion-driven (grows
        with load).  With the window saturated and a standing queue forming
        (head-of-queue age past half the admission wait target), small
        head-of-queue batches spill to the exact host oracle instead —
        brownout: docs/robustness.md "Overload & brownout"."""
        while True:
            brown = False
            hostsel = None
            diverted = []
            ten = self.tenancy
            with self._queue_lock:
                depth = len(self._queue)
                if not self._queue:
                    break
                # canary phase (ISSUE 10): the cut partitions by cohort —
                # every launched batch rides exactly ONE snapshot
                # generation, so no request can ever observe a torn swap
                phase = self._canary
                if self._inflight < self.controller.window:
                    # the cut itself stays completion-driven (grow with
                    # load, bounded by max_batch): clamping it to the
                    # controller's advisory target would fragment standing
                    # queues into cold pad shapes — see AdaptiveWindow
                    n = min(depth, self.max_batch)
                    # weighted-fair cut (ISSUE 15, docs/tenancy.md): under
                    # contention (more queued than the cut takes) the cut
                    # is a deficit-round-robin selection over per-tenant
                    # virtual queues — a 10x hot tenant fills at most its
                    # weighted share of THIS batch while cold rows keep
                    # arrival order.  Uncontended cuts take everything:
                    # fairness only reorders service, it never re-decides.
                    if ten.enabled and depth > n:
                        batch = ten.cut(self._queue, n)
                    else:
                        batch = [self._queue.popleft() for _ in range(n)]
                    ten.on_dequeue(batch)
                    # noisy-neighbor containment (ISSUE 15): a contained
                    # tenant's rows peel off to the exact host-oracle lane
                    # (verdicts identical by construction) so the device
                    # window and the global brownout latch never see its
                    # overload — bounded by the host concurrency cap;
                    # past it the rows stay in the (already fair) cut.
                    if ten.has_contained():
                        keep, div = ten.split_contained(batch)
                        if div and (self.lanes.host_inflight
                                    < self.lanes.host_limit):
                            batch = keep
                            diverted = _split_cohorts(div, phase)
                            self.lanes.host_inflight += len(diverted)
                    if not batch:
                        parts = []
                    else:
                        # lane selection (ISSUE 12): the cost model decides
                        # at the cut whether these rows are answered
                        # host-side (small cut, host_cost < device_cost) or
                        # ride the device — the host lane consumes NO
                        # window slot
                        which, why = self.lanes.decide(
                            len(batch), self._inflight,
                            self.controller.window)
                        parts = _split_cohorts(batch, phase)
                        if which == L_HOST:
                            self.lanes.host_inflight += len(parts)
                            hostsel = why
                        else:
                            self._inflight += len(parts)
                            if self._inflight > self.inflight_peak:
                                self.inflight_peak = self._inflight
                            inflight = self._inflight
                elif (self.brownout
                      and self._brownout_inflight < self._brownout_limit
                      and (time.monotonic() - self._queue[0].t_enq)
                      > self.admission.target_s / 2):
                    # device pipeline saturated + a standing wait forming:
                    # the OLDEST requests (most deadline-critical) spill to
                    # the host lane — no window slot consumed
                    n = min(depth, self.brownout_max_batch)
                    batch = [self._queue.popleft() for _ in range(n)]
                    ten.on_dequeue(batch)
                    parts = _split_cohorts(batch, phase)
                    self._brownout_inflight += len(parts)
                    brown = True
                else:
                    break
            if not brown and parts:
                # ONE decision per CUT (the metric's unit), outside the
                # queue lock, even when a canary splits the cut into
                # cohort parts.  The inflight counters stay per PART —
                # each part is its own job and decrements once, so the
                # accounting balances (during a canary the host bound may
                # transiently sit one above host_limit: a throttle, not
                # an invariant)
                self.lanes.count(which, why)
            for is_canary, part in diverted:
                # contained-tenant rows: host-oracle lane, its own reason
                # label — NOT brownout (the global spill counters must not
                # read a tenant-scoped clamp as process overload)
                self.lanes.count(L_HOST, TEN_R_CONTAINED)
                _encode_pool(self.dispatch_workers).submit(
                    self._host_lane_job, self._snap_for(phase, is_canary),
                    part, None, TEN_R_CONTAINED)
            for is_canary, part in parts:
                # pinned per batch: double-buffer swap safety.  During a
                # canary the cohort picks its generation; a phase that
                # concluded since the stamp collapses to the (promoted or
                # rolled-back) serving snapshot — still one generation.
                snap = self._snap_for(phase, is_canary)
                if brown:
                    _encode_pool(self.dispatch_workers).submit(
                        self._brownout_job, snap, part)
                elif hostsel is not None:
                    _encode_pool(self.dispatch_workers).submit(
                        self._host_lane_job, snap, part, None, hostsel)
                else:
                    self._g_inflight.set(inflight)
                    _encode_pool(self.dispatch_workers).submit(
                        self._encode_launch_job, snap, part)
        self._g_depth.set(len(self._queue))

    def _snap_for(self, phase, is_canary: bool) -> "Optional[_Snapshot]":
        if phase is None:
            return self._snapshot
        return phase.snap if is_canary else phase.baseline

    def _encode_launch_job(self, snap: Optional[_Snapshot],
                           batch: List[_Pending], attempt: int = 0,
                           spec: Optional[Speculation] = None) -> None:
        """Encode stage (dispatch-worker thread): host encode + fused H2D
        staging + non-blocking kernel launch, then hand the in-flight batch
        to the completion stage.  Never blocks on the device.

        Fault-tolerant (ISSUE 5): expired-deadline requests are shed before
        encode; an open circuit breaker skips the device and decides the
        whole batch through the host oracle; any launch failure routes to
        the retry-once-then-degrade path (_batch_failed).

        Lane selection (ISSUE 12): the latency-critical head — requests
        whose propagated deadline lands inside the expected device answer
        but which the host lane can still meet — is rescued host-side
        BEFORE the shedder would fail it typed; and when this dispatch
        claims the breaker's half-open probe slot, the batch additionally
        rides the host lane speculatively, resolving first-wins (``spec``
        carries the first-wins token across the retry path)."""
        if attempt == 0 and spec is None:
            batch = self._rescue_urgent(snap, batch)
        if spec is None:
            # speculative retries skip the shedder: the host twin owns the
            # deadline story for this batch (it either already answered or
            # will shed at horizon 0 itself) — shedding here too would
            # double-count deadline_shed for rows the twin resolved
            batch = self._shed_expired(batch)
        if not batch:
            self._launch_done()
            return
        if snap is None or (snap.policy is None and snap.sharded is None):
            if spec is not None and not spec.acquire(L_DEVICE):
                self._launch_done()
                return  # the host twin answered: nothing left to fail
            self._resolve_error(batch, CheckAbort(
                UNAVAILABLE, "no compiled policy snapshot"))
            self._launch_done()
            return
        allowed, probe = self.breaker.admit_device()
        if not allowed:
            # a speculative retry arriving into a re-opened breaker must
            # ACQUIRE before degrading (the docstring contract of
            # _batch_failed): a host twin finishing mid-degrade would
            # otherwise fold provenance and count SLO/service twice
            if spec is not None and not spec.acquire(L_DEVICE):
                self.lanes.count_speculative("device-fail")
                self._launch_done()
                return
            self._degrade_batch(snap, batch, reason="breaker-open")
            self._launch_done()
            return
        if (probe and spec is None and attempt == 0
                and self.lanes.enabled and self.lanes.speculative):
            # speculative dual-dispatch: the probe batch is the one batch
            # whose device answer is in genuine doubt (the breaker just
            # half-opened) — race the exact host twin against it so the
            # clients never wait out a probe against a still-sick device.
            # The device half keeps the window slot AND the breaker
            # verdict; the host half is bounded by the host concurrency
            # cap (skipped, not queued, when the cap is taken).
            with self._queue_lock:
                if self.lanes.host_inflight < self.lanes.host_limit:
                    self.lanes.host_inflight += 1
                    spec = Speculation("engine")
            if spec is not None:
                self.lanes.count(L_HOST, R_SPECULATIVE)
                self.lanes.count_speculative("launched")
                _encode_pool(self.dispatch_workers).submit(
                    self._host_lane_job, snap, list(batch), spec,
                    R_SPECULATIVE)
        try:
            if faults.ACTIVE:
                faults.FAULTS.check("encode", "engine")
            item = self._encode_and_launch(snap, batch)
            item.snap = snap
            item.attempt = attempt
            item.spec = spec
        except Exception as e:
            self._batch_failed(snap, batch, attempt, e, spec=spec)
            return
        _completer_submit(item)

    def _shed_expired(self, batch: List[_Pending],
                      horizon_s: Optional[float] = None) -> List[_Pending]:
        """Deadline-aware admission: requests whose propagated Check()
        deadline cannot be met — it lands inside ``horizon_s`` (default:
        one expected device round trip, EWMA) — fail fast with a typed
        DEADLINE_EXCEEDED instead of riding (and wasting) a kernel launch
        whose answer arrives dead.  The brownout lane passes 0: the host
        oracle answers in microseconds, so only already-expired deadlines
        shed there."""
        if all(p.deadline is None for p in batch):
            return batch
        now = time.monotonic()
        horizon = now + (self._device_ewma if horizon_s is None
                         else horizon_s)
        live = [p for p in batch if p.deadline is None or p.deadline > horizon]
        shed = [p for p in batch if p.deadline is not None
                and p.deadline <= horizon]
        if shed:
            metrics_mod.deadline_shed.labels("engine").inc(len(shed))
            self._resolve_error(shed, CheckAbort(
                DEADLINE_EXCEEDED,
                "request shed before dispatch: deadline cannot be met"))
        return live

    def _batch_failed(self, snap: _Snapshot, batch: List[_Pending],
                      attempt: int, exc: Exception,
                      spec: Optional[Speculation] = None) -> None:
        """One launched (or launching) micro-batch failed: count it against
        the circuit breaker, retry ONCE on a fresh dispatch, then re-decide
        every request exactly through the host expression oracle.  The
        in-flight window slot stays held until the batch finally resolves
        (the retry owns it; _launch_done runs exactly once per cut).

        Speculative batches (ISSUE 12): when the host twin already WON the
        race, the clients are answered — the device half's only remaining
        job was the breaker verdict (recorded above), so the slot frees
        without a retry or a second resolution; otherwise the device path
        acquires the batch before degrading, so a host twin finishing
        mid-degrade can never double-resolve or double-fold."""
        self.breaker.record_failure()
        if spec is not None and spec.winner == L_HOST:
            self.lanes.count_speculative("device-fail")
            self._launch_done()
            return
        if attempt == 0:
            metrics_mod.batch_retries.labels("engine").inc()
            log.warning("micro-batch of %d failed (%r): retrying once on a "
                        "fresh dispatch", len(batch), exc)
            _encode_pool(self.dispatch_workers).submit(
                self._encode_launch_job, snap, batch, 1, spec)
            return
        if spec is not None and not spec.acquire(L_DEVICE):
            # the host twin answered while the retry was in flight
            self.lanes.count_speculative("device-fail")
            self._launch_done()
            return
        self._degrade_batch(snap, batch, exc=exc)
        self._launch_done()

    def _rescue_urgent(self, snap: Optional[_Snapshot],
                       batch: List[_Pending]) -> List[_Pending]:
        """Latency-critical head of a device cut (ISSUE 12): requests whose
        propagated deadline lands inside the expected device answer time —
        exactly the set the deadline shedder would fail typed — are peeled
        off and answered on the host lane instead, when its cost model says
        it can make them.  Bounded by the host concurrency cap: past it the
        batch ships whole and the shedder keeps the old behavior."""
        if (not self.lanes.enabled or snap is None
                or all(p.deadline is None for p in batch)):
            return batch
        # the device horizon is the LARGER of the cost model's estimate and
        # the shedder's own EWMA (_shed_expired's horizon): anything the
        # shedder would fail is by definition rescue-eligible, even before
        # the cost model has observed a single device batch
        host = self.lanes.cost.host_cost(1)
        dev = max(self.lanes.cost.device_cost(self._inflight,
                                              self.controller.window),
                  self._device_ewma)
        if not (dev > 0.0) or host >= dev:
            return batch
        now = time.monotonic()
        urgent = [p for p in batch
                  if p.deadline is not None
                  and p.deadline <= now + dev      # device cannot make it
                  and p.deadline > now + host]     # ... but the host can
        if not urgent:
            return batch
        # bound the rescue like any host cut (host_max_rows, tightest
        # deadlines first) and re-test against the CAPPED batch's actual
        # host cost: the oracle decides row-by-row, so admitting 500 rows
        # against host_cost(1) would blow the very deadlines the rescue
        # promised to meet
        urgent.sort(key=lambda p: p.deadline)
        urgent = urgent[:self.lanes.host_max_rows]
        bound = now + self.lanes.cost.host_cost(len(urgent))
        urgent = [p for p in urgent if p.deadline > bound]
        if not urgent:
            return batch
        with self._queue_lock:
            if self.lanes.host_inflight >= self.lanes.host_limit:
                return batch
            self.lanes.host_inflight += 1
        self.lanes.count(L_HOST, R_DEADLINE)
        _encode_pool(self.dispatch_workers).submit(
            self._host_lane_job, snap, urgent, None, R_DEADLINE)
        u = set(id(p) for p in urgent)
        return [p for p in batch if id(p) not in u]

    def _host_lane_job(self, snap: Optional[_Snapshot],
                       batch: List[_Pending],
                       spec: Optional[Speculation] = None,
                       reason: str = R_COST) -> None:
        """First-class host serving lane (ISSUE 12, encode-pool thread):
        one batch decided through the exact host oracle because the cost
        model chose it (small cut / deadline rescue / speculative twin) —
        NOT a failure and NOT overload spill (the breaker and the brownout
        counters stay untouched).  Holds no window slot; bounded by the
        lane's own concurrency counter.

        Speculative twins resolve first-wins: the twin acquires the batch
        before any request-level effect (resolution, SLO burn, admission
        service count, provenance fold), so whichever lane loses the race
        contributes nothing but its own cost-model observation."""
        released = False

        def release_slot() -> None:
            # the concurrency slot bounds oracle CPU, not resolution
            # fan-out: release it as soon as the decisions are computed,
            # so a caller awaiting one of these futures can land its next
            # small cut back on the host lane instead of racing the pool
            # thread to the slot and spilling to the device as host-busy
            nonlocal released
            if released:
                return
            released = True
            with self._queue_lock:
                self.lanes.host_inflight -= 1

        try:
            # host lane horizon 0: the oracle answers in microseconds, so
            # only already-expired deadlines shed here
            live = self._shed_expired(batch, horizon_s=0.0)
            if not live:
                return
            if snap is None or (snap.policy is None and snap.sharded is None):
                if spec is None or spec.acquire(L_HOST):
                    self._resolve_error(live, CheckAbort(
                        UNAVAILABLE, "no compiled policy snapshot"))
                return
            by_loop, failed, n_ok, results = self._host_decide_batch(
                snap, live, fold=False)
            if spec is not None:
                if failed:
                    # exactness first: a partially-failed host twin never
                    # claims — the device half owns the whole batch
                    self.lanes.count_speculative("host-fail")
                    return
                if not spec.acquire(L_HOST):
                    return  # the device answered first: confirmation only
                self.lanes.count_speculative("host-win")
            # request-level effects — exactly once per batch, winner-only
            self._fold_host_provenance(snap, live, results,
                                       lane="engine-host")
            if n_ok:
                self.lanes.count_rows(L_HOST, n_ok)
                self.admission.observe_service(n_ok)
                n_bad = 0
                if self.slo is not None:
                    now = time.monotonic()
                    n_bad = min(n_ok, sum(
                        1 for p in live
                        if p.t_enq and now - p.t_enq > self.slo.slo_s))
                    self.slo.observe(n_ok, n_bad)
                self.lanes.cost.observe_slo(L_HOST, n_ok, n_bad)
            release_slot()
            self._resolve_host_decisions(by_loop, failed)
        except Exception:
            log.exception("host-lane batch failed")
            if spec is not None:
                self.lanes.count_speculative("host-fail")
            else:
                self._resolve_error(batch, CheckAbort(
                    UNAVAILABLE, "policy evaluation unavailable"))
        finally:
            release_slot()
            self._maybe_dispatch()

    def _host_decide_batch(self, snap: _Snapshot, batch: List[_Pending],
                           fold: bool = True, lane: str = "engine"):
        """Row-by-row exact host decisions for one batch (the oracle is the
        kernel's differential-test reference, membership overflow
        included).  Returns (resolutions-by-loop, failed-futures-by-loop,
        n_ok, results); rows whose oracle run itself failed land in
        ``failed`` and resolve typed UNAVAILABLE, fail closed.
        ``fold=False`` defers the provenance fold to the caller — the
        speculative host twin must not fold until it WINS the race
        (exactly one fold per batch, whoever resolves).

        Attribution (ISSUE 9): the oracle's (rule, skipped) columns fold
        into the SAME heat map / decision log as the device lane — a
        degraded or brownout decision attributes identically to the kernel
        decision it replaced (the oracle is the kernel's reference)."""
        from ..models.policy_model import host_results

        t0 = time.monotonic()
        by_loop: Dict[Any, list] = {}
        failed: Dict[Any, list] = {}
        n_ok = 0
        if snap.sharded is not None:
            results = snap.sharded.host_decide_many(
                [p.config_name for p in batch], [p.doc for p in batch])
        else:
            results = []
            for p in batch:
                try:
                    row = snap.policy.config_ids[p.config_name]
                    _, rule, skipped = host_results(snap.policy, p.doc, row)
                    results.append((rule, skipped))
                except Exception:
                    log.exception("host oracle failed for config %r "
                                  "(fail-closed UNAVAILABLE)", p.config_name)
                    results.append(None)
        for p, res in zip(batch, results):
            if res is None:
                failed.setdefault(p.loop, []).append(p.future)
            else:
                n_ok += 1
                by_loop.setdefault(p.loop, []).append(
                    (p.future,) + tuple(res) + (snap,))
        # cost-model feed (ISSUE 12): EVERY host-oracle batch teaches the
        # per-row service EWMA — lane-selected, brownout and degrade alike
        # (an engine that spent its warm-up degrading must not enter lane
        # selection with the optimistic cold-start estimate)
        if batch:
            self.lanes.cost.observe_host(time.monotonic() - t0, len(batch))
            # structural cost fold (ISSUE 16): every host-oracle batch —
            # lane-selected, brownout, degrade — counts ZERO device
            # launches and zero H2D/D2H bytes, exactly
            LEDGER.observe("host", rows=len(batch))
        if fold:
            self._fold_host_provenance(snap, batch, results, lane=lane)
        return by_loop, failed, n_ok, results

    def _fold_host_provenance(self, snap: _Snapshot, batch: List[_Pending],
                              results, lane: str = "engine") -> None:
        """Heat-map/decision-log fold for the host-oracle lanes (degrade +
        brownout): stack the per-row (rule, skipped) columns and run the
        same per-batch fold the device completion uses."""
        try:
            heat = getattr(snap, "heat", None)
            if heat is None:
                return
            pendings, rows, shards, rules, skips = [], [], [], [], []
            for p, res in zip(batch, results):
                if res is None:
                    continue
                if snap.sharded is not None:
                    s, r = snap.sharded.locator[p.config_name]
                    shards.append(s)
                    rows.append(r)
                else:
                    rows.append(snap.policy.config_ids[p.config_name])
                pendings.append(p)
                rules.append(np.asarray(res[0], dtype=bool))
                skips.append(np.asarray(res[1], dtype=bool))
            if not rows:
                return
            self._observe_provenance(
                snap, pendings, np.asarray(rows), np.stack(rules),
                np.stack(skips),
                shards=(np.asarray(shards) if snap.sharded is not None
                        else None), lane=lane)
        except Exception:
            log.exception("host-lane provenance fold failed "
                          "(decision unaffected)")

    def _observe_provenance(self, snap: _Snapshot, pendings: List[_Pending],
                            rows, own_rule, own_skipped, shards=None,
                            lane: str = "engine", waits=None):
        """Per-batch decision-observability fold: which-rule-fired columns →
        the snapshot's heat map (vectorized composite-key bincount), plus at
        most ONE head-sampled decision record.  Never raises — a telemetry
        bug must not re-dispatch a decided batch."""
        phase = self._canary
        try:
            heat = getattr(snap, "heat", None)
            if heat is None:
                return None
            from ..ops.pattern_eval import firing_columns

            firing = firing_columns(own_rule, own_skipped)
            p = pendings[0] if pendings else None
            now_m = time.monotonic()
            prov_mod.fold_and_sample(
                heat, rows, firing, len(pendings), lane=lane, shards=shards,
                host=_doc_host(p.doc) if p is not None else "",
                latency_ms=((now_m - p.t_enq) * 1e3
                            if p is not None and p.t_enq else 0.0),
                generation=snap.generation,
                # stratified sampling (ISSUE 15): each sampled TENANT's
                # record carries ITS OWN request's host/latency, not the
                # batch head's — called only for sampled tenants, bounded
                host_of=lambda i: _doc_host(pendings[i].doc),
                latency_of=lambda i: ((now_m - pendings[i].t_enq) * 1e3
                                      if pendings[i].t_enq else 0.0))
            # tenant axis (ISSUE 15): the SAME per-batch seam feeds the
            # per-tenant request/deny counters, wait EWMAs and SLO burn —
            # and because EVERY lane's completion funnels through here
            # (device finalize, host lane, brownout spill, host-oracle
            # degrade), contained and degraded traffic burns the right
            # tenant's accounting too (the old gap the parity test pins).
            # Two clocks, deliberately distinct: ``waits`` (queue waits,
            # captured at the CUT by the device path; sojourn on the
            # host-oracle lanes where service is microseconds) feed the
            # per-tenant CoDel wait signal, while the SLO bad mask reads
            # the full SOJOURN at completion — end-to-end latency is what
            # the --slo-ms budget is about.
            if self.tenancy.enabled:
                sojourn = np.asarray([(now_m - q.t_enq) if q.t_enq else 0.0
                                      for q in pendings])
                self.tenancy.fold(
                    heat, rows, firing=firing, shards=shards,
                    waits=(waits if waits is not None else sojourn),
                    bad_mask=(sojourn > self.slo.slo_s
                              if self.slo is not None else None),
                    lane=lane)
            # traffic capture (ISSUE 13): the full-fidelity sampled request
            # log rides the same per-batch seam as the decision sampler —
            # one enabled check per batch when off; when on, each sampled
            # decision's raw (authconfig, doc, verdict) tuple is queued for
            # the capture log's own drain thread (encode/persist happen
            # there, never here)
            if CAPTURE.enabled:
                pf = self.metadata_prefetcher
                md_digests: Dict[str, Optional[str]] = {}
                for i in CAPTURE.sample_indices(len(pendings)):
                    pi = pendings[i]
                    # metadata reproducibility (ISSUE 14): stamp which
                    # pinned prefetched documents this config's decision
                    # evaluated under (None: nothing pinned)
                    md = None
                    if pf is not None:
                        if pi.config_name not in md_digests:
                            md_digests[pi.config_name] = pf.digest_for(
                                pi.config_name)
                        md = md_digests[pi.config_name]
                    CAPTURE.offer(pi.config_name, pi.doc, int(firing[i]),
                                  lane, snap.generation,
                                  metadata_doc_digest=md)
            # canary guards (ISSUE 10): the SAME attribution columns feed
            # the per-cohort deny-rate comparison — batches are cohort-
            # homogeneous, so the evaluating snapshot names the cohort
            if phase is not None and \
                    (snap is phase.snap or snap is phase.baseline):
                phase.guard.observe_batch(snap is phase.snap, rows, firing,
                                          heat, shards=shards)
        except Exception:
            log.exception("provenance fold failed (decision unaffected)")
            return None
        if phase is not None:
            try:
                self._canary_guard_check(phase)
            except Exception:
                log.exception("canary guard check failed")
        return firing

    @staticmethod
    def _resolve_host_decisions(by_loop, failed) -> None:
        for loop, resolutions in by_loop.items():
            try:
                loop.call_soon_threadsafe(_resolve_many, resolutions)
            except RuntimeError:
                pass  # loop closed since submit: its futures are moot
        for loop, futs in failed.items():
            try:
                loop.call_soon_threadsafe(_fail_many, futs, CheckAbort(
                    UNAVAILABLE, "policy evaluation unavailable"))
            except RuntimeError:
                pass

    def _degrade_batch(self, snap: _Snapshot, batch: List[_Pending],
                       exc: Optional[Exception] = None,
                       reason: str = "device-failure") -> None:
        """Final fallback lane: every request re-decided row-by-row through
        the host expression oracle.  Fail-closed typed UNAVAILABLE ONLY for
        rows where the oracle itself fails."""
        by_loop, failed, n_ok, _ = self._host_decide_batch(snap, batch)
        if n_ok:
            metrics_mod.degraded_decisions.labels("engine").inc(n_ok)
            self.admission.observe_service(n_ok)
            if self.slo is not None:
                now = time.monotonic()
                n_bad = sum(1 for p in batch if p.t_enq
                            and now - p.t_enq > self.slo.slo_s)
                self.slo.observe(n_ok, min(n_bad, n_ok))
            if exc is not None:
                log.warning("micro-batch of %d re-decided host-side after "
                            "device failure (%r)", len(batch), exc)
        n_failed = sum(len(futs) for futs in failed.values())
        self.error_total += n_failed
        phase = self._canary
        if n_failed and phase is not None and batch:
            # typed-error guard feed (ISSUE 10): rows the degrade oracle
            # itself fails are serving errors too — a canary artifact
            # broken on BOTH lanes must still accumulate breach evidence
            try:
                phase.guard.observe_errors(bool(batch[0].canary), n_failed)
                self._canary_guard_check(phase)
            except Exception:
                log.exception("canary error feed failed")
        self._resolve_host_decisions(by_loop, failed)

    def _brownout_job(self, snap: Optional[_Snapshot],
                      batch: List[_Pending]) -> None:
        """Brownout lane (encode-pool thread): a small head-of-queue batch
        decided through the exact host oracle while the device window is
        saturated.  Identical verdicts to the device by construction (the
        oracle is the kernel's reference); throughput degrades, correctness
        never.  No window slot is held — brownout concurrency is bounded by
        its own counter."""
        try:
            # horizon 0: the host oracle answers in microseconds — a
            # deadline the DEVICE's inflated RTT could not meet is exactly
            # what this lane exists to rescue
            batch = self._shed_expired(batch, horizon_s=0.0)
            if not batch:
                return
            if snap is None or (snap.policy is None and snap.sharded is None):
                self._resolve_error(batch, CheckAbort(
                    UNAVAILABLE, "no compiled policy snapshot"))
                return
            by_loop, failed, n_ok, _ = self._host_decide_batch(snap, batch)
            if n_ok:
                metrics_mod.brownout_decisions.labels("engine").inc(n_ok)
                metrics_mod.brownout_batches.labels("engine").inc()
                self._brownout_total += n_ok
                self.admission.observe_service(n_ok)
                if self.slo is not None:
                    now = time.monotonic()
                    n_bad = sum(1 for p in batch if p.t_enq
                                and now - p.t_enq > self.slo.slo_s)
                    self.slo.observe(n_ok, min(n_bad, n_ok))
            self._resolve_host_decisions(by_loop, failed)
        except Exception:
            # a brownout bug must fail its own batch typed, never leak or
            # wedge the queue
            log.exception("brownout batch failed")
            self._resolve_error(batch, CheckAbort(
                UNAVAILABLE, "policy evaluation unavailable"))
        finally:
            with self._queue_lock:
                self._brownout_inflight -= 1
            self._maybe_dispatch()

    @staticmethod
    def _route_done(item: "_Inflight", ok: bool) -> None:
        """Terminal mesh-route accounting for one in-flight batch:
        per-device breaker verdicts + occupancy release (idempotent; no-op
        on the single-corpus lane)."""
        route = item.route
        if route is None:
            return
        item.route = None
        try:
            sharded = getattr(item.snap, "sharded", None) \
                if item.snap is not None else None
            if sharded is not None:
                sharded.complete_route(route, ok, lane="engine")
            else:
                route.release()
        except Exception:
            log.exception("mesh route accounting failed (batch unaffected)")

    def _watchdog_fire(self, item: "_Inflight") -> None:
        """Completer watchdog hand-off: an in-flight batch wedged past
        --device-timeout is abandoned (its readback may still arrive — the
        handle is simply dropped) and fed the retry/degrade path as a
        breaker-counted failure."""
        self._route_done(item, ok=False)
        metrics_mod.watchdog_timeouts.labels("engine").inc()
        RECORDER.record("watchdog-timeout", lane="engine", detail={
            "requests": len(item.batch), "attempt": item.attempt,
            "device_timeout_s": self.device_timeout_s})
        log.warning("device batch (%d requests, attempt %d) wedged past "
                    "--device-timeout %.3fs: abandoning the handle",
                    len(item.batch), item.attempt, self.device_timeout_s)
        self._batch_failed(item.snap, item.batch, item.attempt,
                           TimeoutError("device readback watchdog timeout"),
                           spec=item.spec)

    # ---- graceful drain --------------------------------------------------

    @property
    def draining(self) -> bool:
        return self._draining

    def begin_drain(self) -> None:
        """Stop admitting new requests (submit fails fast with a typed
        UNAVAILABLE; /readyz flips to 503 so the LB stops routing here).
        Queued and in-flight work keeps flowing to completion."""
        if not self._draining:
            self._draining = True
            phase = self._canary
            if phase is not None:
                # a mid-drain window expiry must not promote/rollback into
                # a tearing-down process (swap listeners would rebuild a
                # stopped native frontend); the canary stays undecided and
                # cohort routing keeps serving until exit
                phase.cancel_timer()
            if self.metadata_prefetcher is not None:
                # the refresher must not re-pin into a tearing-down
                # process; stale pins only ever fall through to the live
                # fetch, so stopping early is always safe
                self.metadata_prefetcher.stop(timeout_s=0.5)
            RECORDER.record("drain", lane="engine", detail={
                "queue": len(self._queue), "inflight": self._inflight})
            log.info("engine draining: admission stopped "
                     "(queue=%d, inflight=%d)", len(self._queue),
                     self._inflight)

    def drain(self, timeout_s: float = 10.0) -> bool:
        """Block until every queued request and in-flight batch has
        resolved (or the timeout expires — False).  Call from a worker
        thread (the CLI's SIGTERM path runs it via run_in_executor);
        begin_drain() is implied."""
        self.begin_drain()
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            with self._queue_lock:
                idle = (not self._queue and self._inflight == 0
                        and self._brownout_inflight == 0
                        and self.lanes.host_inflight == 0)
            if idle:
                return True
            time.sleep(0.01)
        with self._queue_lock:
            log.warning("engine drain timed out after %.1fs "
                        "(queue=%d, inflight=%d, brownout=%d)", timeout_s,
                        len(self._queue), self._inflight,
                        self._brownout_inflight)
        return False

    # ---- fleet plane (ISSUE 18) ------------------------------------------

    def fleet_health(self) -> Dict[str, Any]:
        """The fleet router's per-replica health dict — exactly the
        /readyz + admission + breaker evidence (service/http_server.py
        readyz; runtime/admission.py health_signal), so in-process
        replicas and process replicas polled over HTTP publish one shape.
        Every read here is GIL-atomic: safe from the router's decision
        path under load."""
        h = self.admission.health_signal(len(self._queue))
        h["ready"] = self._snapshot is not None and not self._draining
        h["draining"] = self._draining
        h["breaker_open"] = self.breaker.state != "closed"
        h["generation"] = self.generation
        return h

    def fleet_fold(self) -> Dict[str, Any]:
        """One replica's fold for the fleet aggregator (fleet/aggregate.py):
        health + CUMULATIVE counters (the aggregator differences
        consecutive folds into deltas; cumulatives survive a missed
        publish) + the per-tenant rate EWMAs whose fleet-wide sum is the
        global tenant share.  Small and cadence-published — never anything
        per-request."""
        fold = self.fleet_health()
        fold["errors"] = self.error_total
        if self.slo is not None:
            fold["slo_total"] = self.slo.total
            fold["slo_bad"] = self.slo.bad_total
        else:
            fold["slo_total"] = fold["slo_bad"] = 0
        ten = self.tenancy
        if ten.enabled:
            fold["tenants"] = ten.stats.export_fold()
            fold["tenant_rejects"] = {
                t: sum(r.values())
                for t, r in list(ten.admission.rejected.items())}
        else:
            fold["tenants"] = {}
            fold["tenant_rejects"] = {}
        # fleet-pressure gate for the GLOBAL containment check: this
        # replica's wait is hot or its admission gate left HEALTHY
        fold["wait_hot"] = bool(
            self.admission.wait_ewma > self.admission.target_s
            or self.admission.overloaded)
        fold["admission_state"] = ("OVERLOADED" if self.admission.overloaded
                                   else "HEALTHY")
        return fold

    def _cache_keys(self, keys, n, snap, rows=None):
        """Full verdict-cache keys for one batch.  Single-corpus snapshots
        key per config: (encoding epoch, config source fingerprint, row
        bytes) — entries for configs a swap did NOT touch stay reachable
        across the swap (ISSUE 8: the verdict cache survives churn).  Mesh
        snapshots carry the same tokens per (shard, row)
        (snap.mesh_tokens, built in _encode_and_launch_sharded); the
        generation fallback here only serves snapshots with no tokens at
        all (loaded replicas)."""
        if keys is None or self._verdict_cache is None:
            return None
        tokens = snap.cache_tokens
        if tokens is not None and rows is not None:
            return [(tokens[rows[r]], keys[r]) for r in range(n)]
        gen = snap.generation
        return [(gen, keys[r]) for r in range(n)]

    def _dedup_plan(self, keys, ckeys, n, eligible):
        """Shared cache-lookup + within-batch-collapse plan for one
        micro-batch.  ``eligible(r)`` gates verdict-cache participation
        (cacheable config AND not a lossy host-fallback row — the
        fallback flag itself already rides the row keys).  ``ckeys`` are
        the full cache keys (per-config tokens folded in; None = cache
        off).  Returns (cached {row: value}, miss_rows, unique_rows,
        inverse, eligible_misses)."""
        from ..compiler.pack import dedup_rows

        cache = self._verdict_cache
        cached: Dict[int, Any] = {}
        eligible_misses = 0
        if cache is not None and ckeys is not None:
            miss_rows: List[int] = []
            for r in range(n):
                if eligible(r):
                    v = cache.get(ckeys[r])
                    if v is not None:
                        cached[r] = v
                        continue
                    eligible_misses += 1
                miss_rows.append(r)
        else:
            miss_rows = list(range(n))
        if self.batch_dedup and keys is not None:
            unique_rows, inverse = dedup_rows(keys, miss_rows)
        else:
            unique_rows, inverse = miss_rows, np.arange(len(miss_rows))
        return cached, miss_rows, unique_rows, inverse, eligible_misses

    def _cache_insert(self, ckeys, unique_rows, eligible,
                      own_rule, own_skipped) -> int:
        """Insert freshly-evaluated unique rows under their full cache
        keys (captured from the batch's PINNED snapshot at encode time —
        a swap admitted mid-dispatch can never relabel in-flight work);
        returns the eviction delta for this batch's metrics fold."""
        cache = self._verdict_cache
        if cache is None or ckeys is None:
            return 0
        evict0 = cache.evictions
        for r in unique_rows:
            if eligible(r):
                cache.put(ckeys[r],
                          (own_rule[r].copy(), own_skipped[r].copy()))
        return cache.evictions - evict0

    def _encode_and_launch(self, snap: _Snapshot,
                           batch: List[_Pending]) -> _Inflight:
        """Encode + launch one micro-batch; returns the in-flight handle.
        The finalize closure runs on the completion stage with the readback
        as numpy and applies the host-fallback oracle there.

        Between encode and launch sit the two hot-path cuts of ISSUE 3:
        rows whose (generation, row-digest) verdict is cached resolve
        WITHOUT the device, and the remaining rows collapse to unique rows
        only — the fused H2D buffer carries unique work, verdicts fan back
        out through the inverse map on completion (bit-identical: the
        kernel is a pure per-row function of the operand bytes)."""
        n = len(batch)
        pad = _bucket(n)
        t0 = time.monotonic()
        waits = np.array([(t0 - p.t_enq) if p.t_enq else 0.0 for p in batch])
        # the CoDel signal rides the batch cut: the cut's MINIMUM wait is
        # the standing-queue indicator the admission state flips on.  A
        # RETRIED batch re-feeds waits measured from the original enqueue,
        # so the signal is total sojourn (queue + failed attempts) by
        # design: a device so flaky that work is stuck re-dispatching is
        # overload from the client's seat, whatever the queue depth says
        self.admission.observe_waits(waits, now=t0)
        binfo = {"batch_size": n, "pad": pad, "eff": 0,
                 "start_ns": time.time_ns(), "duration_s": 0.0}
        docs = [p.doc for p in batch]
        names = [p.config_name for p in batch]
        if snap.sharded is not None:
            return self._encode_and_launch_sharded(
                snap, batch, docs, names, n, pad, t0, binfo, waits)
        from ..compiler.pack import batch_row_keys, pack_batch, select_rows
        from ..ops.pattern_eval import (dispatch_fused, packed_width,
                                        staged_h2d_bytes, unpack_verdicts)

        policy = snap.policy
        rows = [policy.config_ids[name] for name in names]
        enc = encode_batch(policy, docs, rows, batch_pad=pad)
        db = pack_batch(policy, enc)
        has_dfa = policy.n_byte_attrs > 0
        cacheable = policy.config_cacheable
        keys = (batch_row_keys(db, n)
                if n and (self.batch_dedup or self._verdict_cache is not None)
                else None)
        ckeys = self._cache_keys(keys, n, snap, rows=rows)

        def eligible(r: int) -> bool:
            return bool(cacheable[rows[r]]) and not bool(db.host_fallback[r])

        cached, miss_rows, unique_rows, inverse, elig_miss = self._dedup_plan(
            keys, ckeys, n, eligible)
        u = len(unique_rows)
        if u == n:
            db_u, pad_u = db, pad  # nothing collapsed: ship the batch as-is
        elif u:
            pad_u = _bucket(u)
            db_u = select_rows(db, unique_rows, batch_pad=pad_u)
        else:
            db_u, pad_u = None, 0  # every row cache-resolved: no dispatch
        binfo["pad"] = pad_u
        binfo["device_rows"] = u
        binfo["eff"] = (int(db_u.attr_bytes.shape[-1])
                        if has_dfa and db_u is not None else 0)
        metrics_mod.observe_pipeline_stage(
            "engine", "encode", time.monotonic() - t0)
        # span window opens at the launch: encode/pack are host work
        t1 = time.monotonic()
        binfo["start_ns"] = time.time_ns()
        if db_u is not None:
            if faults.ACTIVE:
                faults.FAULTS.check("h2d", "engine")
                faults.FAULTS.check("kernel", "engine")
            handle = dispatch_fused(snap.params, db_u)
            if faults.ACTIVE:
                handle = faults.FAULTS.wrap_handle(handle, "engine")
        else:
            handle = np.zeros((0, 1), dtype=np.uint8)  # completes instantly
        metrics_mod.observe_pipeline_stage(
            "engine", "launch", time.monotonic() - t1)
        E = int(policy.eval_rule.shape[1])
        # structural cost fold (ISSUE 16): ONE launch per well-formed cut;
        # a fully cache/dedup-resolved cut counts zero launches and zero
        # bytes.  H2D = the fused staging buffer bytes, D2H = the bitpacked
        # [pad_u, W] readback
        LEDGER.observe(
            "engine", rows=n, device_rows=u,
            launches=1 if db_u is not None else 0,
            h2d_bytes=staged_h2d_bytes(db_u) if db_u is not None else 0,
            d2h_bytes=pad_u * packed_width(1 + 2 * E) if db_u is not None else 0,
            pad_rows=pad_u,
            dedup_avoided_rows=len(miss_rows) - u,
            cache_avoided_rows=len(cached))
        max_fallback = self.max_fallback_per_batch

        def finalize(packed):
            # padded eval columns are TRUE_SLOT/False — same tail semantics
            # as the kernel's own padded rows
            own_rule = np.ones((n, E), dtype=bool)
            own_skipped = np.zeros((n, E), dtype=bool)
            if u:
                unpacked = unpack_verdicts(packed, 1 + 2 * E)
                mr = np.asarray(miss_rows)
                own_rule[mr] = unpacked[inverse, 1:1 + E]
                own_skipped[mr] = unpacked[inverse, 1 + E:1 + 2 * E]
            for r, (c_rule, c_skip) in cached.items():
                own_rule[r] = c_rule
                own_skipped[r] = c_skip
            n_fallback = int(np.count_nonzero(db.host_fallback[:n]))
            if n_fallback:
                # compact payload was lossy for these rows (membership
                # overflow): exact re-decision on host via the expression
                # oracle, bounded by the fallback cap (beyond it: deny
                # fail-closed + counter)
                from ..models.policy_model import apply_host_fallback, host_results

                apply_host_fallback(
                    lambda r: host_results(policy, docs[r], rows[r])[1:],
                    np.nonzero(db.host_fallback[:n])[0],
                    own_rule, own_skipped, max_fallback,
                )
            evict_d = self._cache_insert(ckeys, unique_rows, eligible,
                                         own_rule, own_skipped)
            metrics_mod.observe_dedup("engine", n, u, len(cached),
                                      elig_miss, evict_d)
            # attribution (ISSUE 9): one per-batch fold over the FINAL
            # columns — cache hits, dedup fan-out and fallback rows are
            # already folded back in, so every path attributes identically.
            # ``waits`` are the cut-time QUEUE waits (the tenant wait
            # signal must not absorb the device round trip)
            self._observe_provenance(snap, batch, rows, own_rule,
                                     own_skipped, waits=waits)
            return own_rule, own_skipped, n_fallback

        return _Inflight(self, batch, handle, finalize, binfo, waits)

    def _encode_and_launch_sharded(self, snap, batch, docs, names, n, pad,
                                   t0, binfo, waits) -> _Inflight:
        """Mesh-sharded mirror of the dedup/cache encode stage: the row key
        additionally folds in shard_of/row_of (config identity on the
        mesh), and the unique sub-batch re-pads to the dp-aligned bucket."""
        from ..ops.pattern_eval import unpack_verdicts

        sharded = snap.sharded
        enc = sharded.encode(docs, names, batch_pad=pad)
        keys = (sharded.row_keys(enc, n)
                if n and (self.batch_dedup or self._verdict_cache is not None)
                else None)
        # mesh verdict-cache keying (ISSUE 11, PR 8 parity): (encoding
        # epoch of the owning shard, config source fingerprint) tokens —
        # entries of configs a reconcile did not touch survive the swap;
        # generation keying remains only as the loaded-snapshot fallback
        tokens = getattr(snap, "mesh_tokens", None)
        if keys is not None and self._verdict_cache is not None \
                and tokens is not None:
            ckeys = [(tokens[enc.shard_of[r]][enc.row_of[r]], keys[r])
                     for r in range(n)]
        else:
            ckeys = self._cache_keys(keys, n, snap)

        def eligible(r: int) -> bool:
            return (bool(sharded.config_cacheable[enc.shard_of[r],
                                                  enc.row_of[r]])
                    and not bool(enc.host_fallback[r]))

        cached, miss_rows, unique_rows, inverse, elig_miss = self._dedup_plan(
            keys, ckeys, n, eligible)
        u = len(unique_rows)
        binfo["device_rows"] = u
        if u == n:
            enc_u = enc
            binfo["pad"] = int(enc.attrs_val.shape[0])
        elif u:
            enc_u = sharded.select_rows(enc, unique_rows, batch_pad=_bucket(u))
            binfo["pad"] = int(enc_u.attrs_val.shape[0])
        else:
            enc_u = None
            binfo["pad"] = 0
        metrics_mod.observe_pipeline_stage(
            "engine", "encode", time.monotonic() - t0)
        t1 = time.monotonic()
        binfo["start_ns"] = time.time_ns()
        route = None
        if enc_u is not None:
            if faults.ACTIVE:
                faults.FAULTS.check("h2d", "engine")
                faults.FAULTS.check("kernel", "engine")
            # breaker-aware routed launch (ISSUE 11): full-mesh shard_map
            # when every device is healthy; a device that fails its probe
            # records on ITS breaker and the batch fails over to the
            # healthy device with the emptiest in-flight window.
            # MeshUnavailable (all devices down) propagates into the
            # existing retry-once-then-degrade path — host-oracle decisions
            # begin only past that point.
            handle, route = sharded.dispatch_routed(enc_u, lane="engine")
            if faults.ACTIVE:
                handle = faults.FAULTS.wrap_handle(handle, "engine")
        else:
            handle = np.zeros((0, 1), dtype=np.uint8)
        metrics_mod.observe_pipeline_stage(
            "engine", "launch", time.monotonic() - t1)
        # structural cost fold (ISSUE 16), mesh lane: the shard-step
        # launch + bytes were counted at the dispatch site (one collective
        # launch per step, failovers included); this fold adds the
        # batch-level story — real rows, dedup/cache cuts, pad waste
        LEDGER.observe(
            "mesh", rows=n, device_rows=u, pad_rows=binfo["pad"],
            dedup_avoided_rows=len(miss_rows) - u,
            cache_avoided_rows=len(cached))
        E = int(sharded.shards[0].eval_rule.shape[1])
        max_fallback = self.max_fallback_per_batch

        def finalize(packed):
            own_rule = np.ones((n, E), dtype=bool)
            own_skipped = np.zeros((n, E), dtype=bool)
            if u:
                unpacked = unpack_verdicts(np.asarray(packed), 1 + 2 * E)
                mr = np.asarray(miss_rows)
                own_rule[mr] = unpacked[inverse, 1:1 + E]
                own_skipped[mr] = unpacked[inverse, 1 + E:1 + 2 * E]
            for r, (c_rule, c_skip) in cached.items():
                own_rule[r] = c_rule
                own_skipped[r] = c_skip
            sharded.apply_fallback(enc.host_fallback, docs, names,
                                   own_rule, own_skipped, max_fallback)
            evict_d = self._cache_insert(ckeys, unique_rows, eligible,
                                         own_rule, own_skipped)
            metrics_mod.observe_dedup("engine", n, u, len(cached),
                                      elig_miss, evict_d)
            self._observe_provenance(snap, batch, enc.row_of[:n], own_rule,
                                     own_skipped, shards=enc.shard_of[:n],
                                     waits=waits)
            return own_rule, own_skipped, None

        item = _Inflight(self, batch, handle, finalize, binfo, waits)
        item.route = route
        return item

    def _complete(self, item: _Inflight) -> None:
        """Completion stage (worker pool, handed off by the completer once
        the readback arrived): finalize → loop-affine future resolution →
        free the window slot.  A readback/finalize failure is a DEVICE
        failure and rides the retry-once-then-degrade path (which owns the
        slot until the batch resolves); anything that fails AFTER the
        device provably answered — telemetry, tracing, resolution — is a
        host-side bug and must never feed the breaker or re-dispatch a
        succeeded batch."""
        try:
            t_done = time.monotonic()
            if faults.ACTIVE:
                faults.FAULTS.check("readback", "engine")
            packed = np.asarray(item.handle)
            # speculative first-wins (ISSUE 12): acquire BEFORE finalize —
            # a batch the host twin already resolved skips finalize (and
            # with it the provenance fold + cache insert) entirely; the
            # device readback was confirmation + the breaker's probe
            # verdict.  acquire() is idempotent for the device lane, so
            # the finalize-failure path below keeps ownership.
            spec_won = item.spec is None or item.spec.acquire(L_DEVICE)
            if spec_won:
                own_rule, own_skipped, fallback_n = item.finalize(packed)
        except Exception as e:
            # device/readback failure: per-device breaker attribution +
            # occupancy release for a routed mesh batch, then retry once
            # (the fresh dispatch routes around the sick device), then
            # host-oracle degrade
            self._route_done(item, ok=False)
            self._batch_failed(item.snap, item.batch, item.attempt, e,
                               spec=item.spec)
            return
        # the mesh devices answered: per-device breaker success + window
        # release, before any telemetry that could fail host-side
        self._route_done(item, ok=True)
        slo_counted = False
        try:
            # the device answered: clear the breaker's consecutive-failure
            # count (and close a half-open probe) BEFORE resolution work.
            # A fully cache-resolved batch (zero device rows) proves
            # nothing about the device — it only releases a claimed probe.
            if item.binfo.get("device_rows", 1) == 0:
                self.breaker.release_probe()
            else:
                self.breaker.record_success()
            dur = t_done - item.t_launch
            self._device_ewma = (dur if not self._device_ewma
                                 else 0.8 * self._device_ewma + 0.2 * dur)
            # lane-selection cost model (ISSUE 12): every device completion
            # feeds the RTT/congestion EWMAs the next cut decides on —
            # EXCEPT fully cache-resolved batches (zero device rows): they
            # never touched the link, and their ~100µs turnaround would
            # read as a fast device and pin small cuts device-side under
            # cache-hit-heavy traffic (the exact regression this lane
            # removes; the native lane has the same guard)
            if item.binfo.get("device_rows", 1) != 0:
                self.lanes.cost.observe_device(
                    dur, item.binfo["batch_size"], len(self._queue),
                    self._inflight, self.controller.window)
            sharded = (getattr(item.snap, "sharded", None)
                       if item.snap is not None else None)
            if sharded is not None:
                # mesh lane cost feed (ISSUE 12): a partially-down mesh
                # concentrates load on the survivors — the device cost the
                # selector compares against rises accordingly
                try:
                    self.lanes.cost.mesh_penalty = sharded.cost_feed()
                except Exception:
                    pass
            # overload controllers: the batch's device round trip + size
            # steps the adaptive window/cut; completed rows feed the
            # admission gate's service-rate estimate
            self.controller.observe_batch(dur, item.binfo["batch_size"],
                                          len(self._queue), now=t_done)
            if not spec_won:
                # the host twin already answered the clients: request-level
                # accounting (admission service, SLO, spans, resolution)
                # happened exactly once on the host side
                return
            if item.spec is not None:
                self.lanes.count_speculative("device-win")
            self.lanes.count_rows(L_DEVICE, item.binfo["batch_size"])
            self.admission.observe_service(item.binfo["batch_size"],
                                           now=t_done)
            if self.slo is not None:
                # per-request latency ≈ queue wait + this batch's device
                # stage — one vectorized compare per batch (ISSUE 9)
                lat = np.asarray(item.waits) + dur
                n_bad = int(np.count_nonzero(lat > self.slo.slo_s))
                self.slo.observe(len(item.batch), n_bad)
                slo_counted = True
                # per-lane burn bias feed (ISSUE 12): selection leans
                # toward the lane that is NOT burning budget
                self.lanes.cost.observe_slo(L_DEVICE, len(item.batch),
                                            n_bad)
                # SLO-delta canary guard feed (ISSUE 10): per-cohort bad
                # fractions ride the same per-batch counts
                phase = self._canary
                if phase is not None and \
                        item.snap in (phase.snap, phase.baseline):
                    phase.guard.observe_slo(item.snap is phase.snap,
                                            len(item.batch), n_bad)
            binfo = item.binfo
            binfo["duration_s"] = t_done - item.t_launch
            metrics_mod.observe_pipeline_stage("engine", "device",
                                               binfo["duration_s"])
            metrics_mod.observe_batch(
                "engine", binfo["batch_size"], binfo["pad"],
                item.waits, binfo["duration_s"], fallback_n,
                device_rows=binfo.get("device_rows"))
            if tracing_mod.tracing_active():
                # one DeviceBatch span per kernel launch, span-linked to
                # every constituent request's trace (export only: a link
                # list build per batch, nothing per request)
                links = [(p.span.trace_id, p.span.span_id)
                         for p in item.batch if p.span is not None
                         and getattr(p.span, "sampled", True)]
                if links:
                    tracing_mod.export_device_batch_span(
                        binfo["batch_size"], binfo["pad"], binfo["eff"],
                        links, binfo["start_ns"], binfo["duration_s"])
            by_loop: Dict[Any, list] = {}
            for i, p in enumerate(item.batch):
                by_loop.setdefault(p.loop, []).append(
                    (p.future, own_rule[i], own_skipped[i], item.snap))
            for loop, resolutions in by_loop.items():
                try:
                    loop.call_soon_threadsafe(_resolve_many, resolutions)
                except RuntimeError:
                    pass  # loop closed since submit: its futures are moot
            metrics_mod.observe_pipeline_stage("engine", "resolve",
                                               time.monotonic() - t_done)
        except Exception as e:
            # post-device-success host bug (telemetry exporter, metrics
            # label, resolution plumbing): fail any still-unresolved
            # futures typed — already-resolved ones keep their verdicts —
            # and free the slot.  Retrying here would re-run a healthy
            # device and could walk the breaker open off exporter noise.
            log.exception("post-completion work failed (batch verdicts "
                          "already computed)")
            if spec_won:
                self._resolve_error(item.batch, e, slo_counted=slo_counted)
        finally:
            self._launch_done()

    def _resolve_error(self, batch: List[_Pending], exc: Exception,
                       slo_counted: bool = False) -> None:
        """Fail unresolved requests with a TYPED CheckAbort — never the raw
        exception, whose repr would otherwise serve as a deny reason
        string through the gRPC/HTTP layer (ISSUE 5 satellite).  Raw causes
        are logged here; callers with a degrade path never reach this."""
        if not isinstance(exc, CheckAbort):
            log.error("batch of %d failed without a degrade path: %r",
                      len(batch), exc)
            exc = CheckAbort(UNAVAILABLE, "policy evaluation unavailable")
        if self.slo is not None and not slo_counted and \
                exc.code != DEADLINE_EXCEEDED:
            # serving errors burn the SLO budget; deadline sheds are the
            # protection mechanism working and stay out of it.  slo_counted:
            # a post-completion telemetry failure arrives here AFTER the
            # success path already observed the batch — don't double-burn
            self.slo.observe_errors(len(batch))
        if exc.code != DEADLINE_EXCEEDED:
            self.error_total += len(batch)
        phase = self._canary
        if phase is not None and batch and exc.code != DEADLINE_EXCEEDED:
            # typed-error guard (ISSUE 10): a canary generation whose
            # batches keep failing (encode raises on a bad artifact, say)
            # must breach even when it never produces a deny column.
            # Batches are cohort-homogeneous post-partition.
            try:
                phase.guard.observe_errors(bool(batch[0].canary),
                                           len(batch))
                self._canary_guard_check(phase)
            except Exception:
                log.exception("canary error feed failed")
        by_loop: Dict[Any, list] = {}
        for p in batch:
            by_loop.setdefault(p.loop, []).append(p.future)
        for loop, futs in by_loop.items():
            try:
                loop.call_soon_threadsafe(_fail_many, futs, exc)
            except RuntimeError:
                pass

    def _launch_done(self) -> None:
        with self._queue_lock:
            self._inflight -= 1
            inflight = self._inflight
        self._g_inflight.set(inflight)
        self._maybe_dispatch()


def _doc_host(doc) -> str:
    """Best-effort host of one authorization JSON (decision-log records)."""
    try:
        return str((doc.get("request") or {}).get("host", ""))
    except Exception:
        return ""


def _split_cohorts(batch, phase):
    """Partition one cut by canary cohort: [(is_canary, items), ...] with
    empties dropped.  With no canary in progress the cut ships whole."""
    if phase is None:
        return [(False, batch)]
    base = [p for p in batch if not p.canary]
    can = [p for p in batch if p.canary]
    parts = []
    if base:
        parts.append((False, base))
    if can:
        parts.append((True, can))
    return parts or [(False, batch)]


def _resolve_many(resolutions) -> None:
    for fut, rule, skipped, snap in resolutions:
        if not fut.done():
            fut.set_result((rule, skipped, snap))


def _fail_many(futs, exc) -> None:
    for fut in futs:
        if not fut.done():
            fut.set_exception(exc)


# ---------------------------------------------------------------------------
# shared pipeline stages.  Both are process-wide singletons: engines are
# created freely (tests, reconciles) and per-engine threads with no shutdown
# path would leak.
#
#   encode pool   — CPU workers for the encode stage AND per-batch finalize;
#                   its size bounds host parallelism only, NOT the in-flight
#                   device window (that is each engine's max_inflight_batches
#                   counter)
#   completer     — one thread that ONLY polls in-flight readbacks
#                   (is_ready) and hands each arrived batch to the pool the
#                   moment it lands — arrival order, not launch order, and
#                   no finalize work that could convoy other arrivals
# ---------------------------------------------------------------------------

_ENCODE_POOL = None
_ENCODE_POOL_LOCK = threading.Lock()


def _encode_pool(workers: int = 4):
    global _ENCODE_POOL
    if _ENCODE_POOL is None:
        from concurrent.futures import ThreadPoolExecutor

        with _ENCODE_POOL_LOCK:
            if _ENCODE_POOL is None:
                _ENCODE_POOL = ThreadPoolExecutor(
                    max_workers=max(1, workers),
                    thread_name_prefix="atpu-engine-encode")
    return _ENCODE_POOL


_COMPLETER: Optional[threading.Thread] = None
_COMPLETER_LOCK = threading.Lock()
_COMPLETER_ITEMS: deque = deque()
_COMPLETER_EVT = threading.Event()


def _completer_submit(item: _Inflight) -> None:
    _ensure_completer()
    _COMPLETER_ITEMS.append(item)
    _COMPLETER_EVT.set()


def _ensure_completer() -> None:
    global _COMPLETER
    if _COMPLETER is None or not _COMPLETER.is_alive():
        with _COMPLETER_LOCK:
            if _COMPLETER is None or not _COMPLETER.is_alive():
                t = threading.Thread(target=_completer_loop,
                                     name="atpu-engine-completer", daemon=True)
                t.start()
                _COMPLETER = t


def _completer_loop() -> None:
    log = logging.getLogger("authorino_tpu.engine")
    pending: List[_Inflight] = []
    while True:
        while _COMPLETER_ITEMS:
            try:
                pending.append(_COMPLETER_ITEMS.popleft())
            except IndexError:
                break
        if not pending:
            _COMPLETER_EVT.wait()
            _COMPLETER_EVT.clear()
            continue
        progressed = False
        for item in list(pending):
            if item.ready():
                pending.remove(item)
                progressed = True
                try:
                    # finalize on the worker pool, NOT here: the host-
                    # fallback oracle can be O(batch) work, and one heavy
                    # batch must not convoy the resolution of other already-
                    # arrived batches.  _complete handles its own failures
                    # and releases the window slot exactly once.
                    _encode_pool(item.engine.dispatch_workers).submit(
                        item.engine._complete, item)
                except Exception:
                    log.exception("batch completion handoff failed")
            elif item.expired():
                # watchdog: the readback is wedged past --device-timeout —
                # abandon the handle and feed the batch the retry/degrade
                # path (a breaker-counted failure).  A late arrival on the
                # dropped handle is harmless: nothing materializes it.
                pending.remove(item)
                progressed = True
                try:
                    _encode_pool(item.engine.dispatch_workers).submit(
                        item.engine._watchdog_fire, item)
                except Exception:
                    log.exception("watchdog handoff failed")
        if not progressed:
            # nothing ready: sub-ms poll — noise against the link RTT each
            # in-flight batch is waiting out, and it keeps resolution
            # FIFO-independent (no blocking on the oldest launch)
            _COMPLETER_EVT.wait(0.0005)
            _COMPLETER_EVT.clear()


from ..utils import bucket_pow2 as _bucket  # noqa: E402 — shared bucketing policy
