"""Decision provenance (ISSUE 9): which-rule-fired attribution, the runtime
rule heat map, and the head-sampled decision log.

The PR 3 bitpacked readback already ships per-rule result/skip columns
alongside every verdict (``ops/pattern_eval.py eval_verdicts`` →
``rule_results``); until this layer they were decoded to one verdict and
thrown away.  Here they become:

- **attribution**: the first evaluator column that evaluated false and was
  not condition-skipped is *the* rule that denied the request
  (``ops.pattern_eval.firing_columns`` — the reference pipeline's
  short-circuit order).  Both lanes decode it per BATCH, and the fan-out
  paths (within-batch dedup, verdict-cache hits, brownout, host-oracle
  degrade) attribute identically because they all reproduce the same
  (rule, skipped) columns;
- **rule heat map**: ``auth_server_rule_fired_total{authconfig,rule}``,
  folded per batch into a dense count array indexed by the composite
  (config row, firing column) key: O(1) Python per batch.  The same heat
  map keeps the native lane's per-AuthConfig request counters and the
  decision log's sampling gate as arrays indexed by config row; the named
  side (Prometheus children) is pushed by the drain
  (``utils.metrics.drain``), never by the thread that completes batches.
  The never-fired set cross-references the static constant/shadowed
  findings (PR 4 policy analysis) in the dead-rule report on
  ``/debug/vars``;
- **decision log**: a bounded ring of head-sampled structured decision
  records (host, authconfig, verdict, firing rule, lane, latency, snapshot
  generation) served on ``/debug/decisions`` and pretty-printed by
  ``python -m authorino_tpu.analysis --decisions``.  Sampling is 1-in-N
  *decisions* per tenant, gated by two arrays on the heat map: a batch pays
  one vector compare, and a dict build only for the tenants that fire.

Privacy: rule SOURCE strings reach clients (X-Ext-Auth-Reason) only behind
``--expose-deny-reason`` (module flag ``EXPOSE_DENY_REASON``); Envoy
``dynamic_metadata`` provenance and the operator surfaces (/metrics,
/debug/*) always carry them — they are mesh-internal."""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import (Any, Dict, List, NamedTuple, Optional, Sequence,
                    Tuple)

import numpy as np

from ..utils import metrics as metrics_mod

__all__ = ["EXPOSE_DENY_REASON", "RULE_LABEL_MAX", "HeatMap", "DecisionLog",
           "DECISIONS", "DecisionSchemaError", "check_decision_schema",
           "rule_label", "deny_provenance", "deny_reason",
           "dead_rule_report", "fired_pairs", "fold_and_sample"]

# --expose-deny-reason: when False (default), deny responses keep the
# generic "Unauthorized" reason and attribution rides only dynamic_metadata
# + operator surfaces.  Set by the CLI; module-level so the evaluator seam
# (evaluators/authorization/pattern_matching.py) needs no plumbing.
EXPOSE_DENY_REASON = False

# rule-source label truncation: heat-map label values must stay bounded
# (Prometheus label cardinality is per distinct VALUE, and sources are
# operator-authored — truncation only shortens, never merges rules, because
# the evaluator index prefixes the label)
RULE_LABEL_MAX = 120


def rule_label(col: int, source: str) -> str:
    src = source if len(source) <= RULE_LABEL_MAX else \
        source[:RULE_LABEL_MAX - 1] + "…"
    return f"{col}:{src}"


# process-wide fired set, merged across lanes and snapshot generations:
# (authconfig, evaluator column) pairs that have attributed at least one
# denial since process start.  The dead-rule report subtracts it from the
# serving snapshot's registered rules.
_FIRED: set = set()
_FIRED_LOCK = threading.Lock()


def fired_pairs() -> set:
    with _FIRED_LOCK:
        return set(_FIRED)


def _reset_fired_for_tests() -> None:
    with _FIRED_LOCK:
        _FIRED.clear()


class RowGroups(NamedTuple):
    """The rows of one or many batches grouped by flat config row, by ONE
    sort (``HeatMap.group``): what the sampling gate, the tenant fold and
    the request counters each grouped for themselves once a batch."""
    flat: np.ndarray     # [n] the flat config row of every request
    uniq: np.ndarray     # [U] the distinct rows, ascending
    first: np.ndarray    # [U] index in ``flat`` of each row's first request
    inverse: np.ndarray  # [n] index in ``uniq`` of every request's row
    counts: np.ndarray   # [U] requests a row

    def per_row(self, mask) -> np.ndarray:
        """[U] how many of each row's requests ``mask`` marks."""
        return np.bincount(self.inverse[mask], minlength=self.uniq.size)


class HeatMap:
    """Per-snapshot attribution folder: kernel config rows → (authconfig
    name, per-evaluator rule sources).  Everything a batch's completion
    records per config lives here as dense arrays indexed by the flat
    config row (``shard * configs_per_shard + row`` on a mesh corpus):

    - rule-fired counts, by (row, firing column): ``fold``;
    - the native lane's per-AuthConfig request counters: ``fold_requests``;
    - the decision log's per-tenant sampling gate: ``sample_gate``.

    Each is a few vector operations a batch.  ``flush()`` pushes what moved
    since the last flush into cached Prometheus children; the drain
    (``utils.metrics.drain``) calls it on the native frontend's
    housekeeping cadence (``hist_drain_s``) and before every read, so
    counters may lag a cadence inside the process and are exact wherever
    they are read."""

    def __init__(self, names_by_row: Sequence[str],
                 sources_by_row: Sequence[Sequence[str]], n_evaluators: int,
                 configs_per_shard: Optional[int] = None):
        self.names_by_row = list(names_by_row)
        self.sources_by_row = [list(s) for s in sources_by_row]
        self.E = int(n_evaluators)
        # mesh corpora: rows arrive (shard, row) and flatten as
        # shard * configs_per_shard + row; None = single corpus
        self.configs_per_shard = configs_per_shard
        self._children: Dict[int, Any] = {}   # composite key -> counter child
        self._lock = threading.Lock()
        G = max(1, len(self.names_by_row))
        self._counts = np.zeros(G * (self.E + 1), dtype=np.int64)
        self._flushed = np.zeros_like(self._counts)
        # per-AuthConfig request counters: requests and OKs by row, the
        # (namespace, name) labels and the hybrid mask bound by the native
        # frontend at snapshot build, children minted on a row's first flush
        self.requests = np.zeros(G, dtype=np.int64)
        self.ok = np.zeros(G, dtype=np.int64)
        self._requests_flushed = np.zeros(G, dtype=np.int64)
        self._ok_flushed = np.zeros(G, dtype=np.int64)
        self.hybrid = np.zeros(G, dtype=bool)
        self._authconfig_labels: List[Tuple[str, str]] = [("", "")] * G
        self._authconfig_children: Dict[int, list] = {}
        # the sampling gate: decisions seen by row, and the count at which
        # the row next samples (1: a row's first decision always does)
        self.seen = np.zeros(G, dtype=np.int64)
        self.next_fire = np.ones(G, dtype=np.int64)
        self._gate_epoch = 0
        self.fold_calls = 0       # per-batch evidence for the perf guard
        self.fold_seconds = 0.0   # cumulative fold cost (bench overhead delta)
        metrics_mod.register_drainable(self)

    # -- construction ------------------------------------------------------

    @classmethod
    def from_policy(cls, policy) -> "HeatMap":
        names = [""] * policy.n_configs
        for name, row in policy.config_ids.items():
            names[row] = name
        return cls(names, policy.rule_sources(),
                   int(policy.eval_rule.shape[1]))

    @classmethod
    def from_sharded(cls, sharded) -> "HeatMap":
        """Mesh corpora: rows flatten as shard * G + row (the same flat key
        native _post_complete_telemetry already bins by)."""
        G = sharded.configs_per_shard
        names = [""] * (sharded.n_shards * G)
        sources: List[List[str]] = [[] for _ in range(sharded.n_shards * G)]
        for s, pol in enumerate(sharded.shards):
            srcs = pol.rule_sources()
            for name, row in pol.config_ids.items():
                names[s * G + row] = name
                sources[s * G + row] = srcs[row]
        return cls(names, sources, int(sharded.shards[0].eval_rule.shape[1]),
                   configs_per_shard=G)

    @classmethod
    def for_snapshot(cls, policy=None, sharded=None) -> "Optional[HeatMap]":
        if sharded is not None:
            return cls.from_sharded(sharded)
        if policy is not None:
            return cls.from_policy(policy)
        return None

    # -- folding -----------------------------------------------------------

    def flat_rows(self, rows, shards=None) -> np.ndarray:
        rows = np.asarray(rows, dtype=np.int64)
        if shards is not None and self.configs_per_shard:
            return np.asarray(shards, dtype=np.int64) * \
                self.configs_per_shard + rows
        return rows

    def group(self, rows, shards=None) -> RowGroups:
        """``np.unique(flat, return_index=True, return_inverse=True,
        return_counts=True)`` by a sort of VALUES: row x n + position is
        distinct a request, so one in-place sort orders the requests by row
        and, inside a row, by position (a third of the stable argsort's time
        at 4,096 requests)."""
        flat = self.flat_rows(rows, shards)
        n = flat.size
        if not n:
            none = np.zeros(0, dtype=np.int64)
            return RowGroups(flat, none, none, none, none)
        key = flat * n
        key += np.arange(n)
        key.sort()
        by_row, at = np.divmod(key, n)
        new = np.empty(n, dtype=bool)
        new[0] = True
        np.not_equal(by_row[1:], by_row[:-1], out=new[1:])
        starts = np.flatnonzero(new)
        inverse = np.empty(n, dtype=np.int64)
        inverse[at] = np.cumsum(new) - 1
        counts = np.empty(starts.size, dtype=np.int64)
        counts[:-1] = starts[1:] - starts[:-1]
        counts[-1] = n - starts[-1]
        return RowGroups(flat, by_row[starts], at[starts], inverse, counts)

    def fold(self, rows, firing, shards=None, flat=None) -> None:
        """Fold one batch's attribution into the heat map: ONE vectorized
        np.add.at into the composite-key count array — Python work is O(1)
        per batch, independent of batch size AND of the number of distinct
        rules.  ``flat``: the rows' flat form where the caller has it
        (``RowGroups.flat``).

        fold_seconds meters THREAD CPU time, not wall: on a saturated box
        the encode-pool thread gets preempted mid-fold, and a wall meter
        would bill those descheduled gaps to the fold (observed ~100x
        inflation on the CPU-only bench image, where the 'device' kernel
        competes for the same cores)."""
        t0 = time.thread_time()
        rows = self.flat_rows(rows, shards) if flat is None else flat
        firing = np.asarray(firing, dtype=np.int64)
        self.fold_calls += 1
        denied = firing >= 0
        if denied.any():
            comp = rows[denied] * (self.E + 1) + firing[denied]
            with self._lock:
                np.add.at(self._counts, comp, 1)
        self.fold_seconds += time.thread_time() - t0

    def bind_authconfigs(self, labels: Dict[Any, Tuple[str, str]],
                         hybrid=()) -> None:
        """Snapshot build: the (namespace, name) labels of the kernel rows
        the native lane answers, and which of them are HYBRID configs (a
        kernel-allowed hybrid request continues into the pipeline, which
        observes it itself: only its native denials count here).  Keys are
        rows, or (shard, row) on a mesh corpus."""
        mask = np.zeros_like(self.hybrid)
        for key in hybrid:
            mask[self._flat_key(key)] = True
        for key, label in labels.items():
            self._authconfig_labels[self._flat_key(key)] = label
        self.hybrid = mask

    def _flat_key(self, key) -> int:
        if isinstance(key, tuple):
            return key[0] * self.configs_per_shard + key[1]
        return key

    def fold_requests(self, rows, verdict, shards=None,
                      groups: Optional[RowGroups] = None) -> None:
        """Per-AuthConfig request counters (the counters and labels the
        pipeline bumps, ref pkg/service/auth_pipeline.go:26-36) of one
        batch, or of the many that ``groups`` holds: one bincount and two
        adds over the distinct rows, none over the corpus.  A hybrid
        config's kernel-allowed request is the pipeline's to count."""
        g = self.group(rows, shards) if groups is None else groups
        ok = g.per_row(np.asarray(verdict) != 0)
        hybrid = self.hybrid[g.uniq]
        with self._lock:
            self.requests[g.uniq] += np.where(hybrid, g.counts - ok, g.counts)
            self.ok[g.uniq] += np.where(hybrid, 0, ok)

    def sample_gate(self, groups: RowGroups, sample_n: int, epoch: int):
        """The decision log's stratified 1-in-N gate, as arrays: advance
        each distinct row's decision count by its requests in ``groups`` (one
        batch, or the many a caller folds at once); a row fires on its first
        decision in this snapshot and then once every ``sample_n`` decisions.
        Returns (rows that fire, index in ``groups.flat`` of each one's first
        request).  ``epoch`` re-arms every row when the log is
        reconfigured."""
        uniq, first, counts = groups.uniq, groups.first, groups.counts
        with self._lock:
            if epoch != self._gate_epoch:
                self._gate_epoch = epoch
                self.seen[:] = 0
                self.next_fire[:] = 1
            seen = self.seen[uniq] + counts
            self.seen[uniq] = seen
            hit = seen >= self.next_fire[uniq]
            self.next_fire[uniq[hit]] = seen[hit] + sample_n
        return uniq[hit], first[hit]

    def flush(self) -> int:
        """Push what moved since the last flush into the Prometheus
        children (and the process-wide fired set); returns the children
        touched.  Cost is bounded by the distinct (config, rule) pairs and
        configs that moved — paid by the drain, never per batch."""
        with self._lock:
            delta = self._counts - self._flushed
            fired = np.nonzero(delta)[0]
            fired_n = delta[fired]
            np.copyto(self._flushed, self._counts)
            # an OK is a request, so a row whose OKs moved is among these
            delta = self.requests - self._requests_flushed
            rows = np.nonzero(delta)[0]
            rows_n = delta[rows]
            rows_ok = (self.ok - self._ok_flushed)[rows]
            np.copyto(self._requests_flushed, self.requests)
            np.copyto(self._ok_flushed, self.ok)
        for key, n in zip(fired.tolist(), fired_n.tolist()):
            self._bump(key, n)
        children = int(fired.size)
        for row, n, n_ok in zip(rows.tolist(), rows_n.tolist(),
                                rows_ok.tolist()):
            children += self._bump_authconfig(row, n, n_ok)
        return children

    def _bump(self, comp_key: int, n: int) -> None:
        child = self._children.get(comp_key)
        if child is None:
            row, col = divmod(comp_key, self.E + 1)
            if row >= len(self.names_by_row):
                return  # padded/unknown row: nothing to attribute
            name = self.names_by_row[row]
            sources = self.sources_by_row[row] if row < len(
                self.sources_by_row) else []
            src = sources[col] if col < len(sources) else "<padded>"
            with self._lock:
                child = self._children.get(comp_key)
                if child is None:
                    child = metrics_mod.rule_fired.labels(
                        name, rule_label(col, src))
                    self._children[comp_key] = child
            with _FIRED_LOCK:
                _FIRED.add((name, col))
        child.inc(n)

    def _bump_authconfig(self, row: int, n: int, n_ok: int) -> int:
        """A series appears with its first count, as where the pipeline
        bumps the same families: children are minted lazily, each once."""
        children = self._authconfig_children.get(row)
        if children is None:
            children = self._authconfig_children[row] = [None, None, None]
        touched = 0
        for k, amount in enumerate((n, n_ok, n - n_ok)):
            if amount:
                child = children[k]
                if child is None:
                    child = children[k] = self._mint_authconfig(row, k)
                child.inc(amount)
                touched += 1
        return touched

    def _mint_authconfig(self, row: int, k: int):
        ns, name = self._authconfig_labels[row]
        if k == 0:
            return metrics_mod.authconfig_total.labels(ns, name)
        return metrics_mod.authconfig_response_status.labels(
            ns, name, "OK" if k == 1 else "PERMISSION_DENIED")

    # -- attribution lookups ----------------------------------------------

    def source(self, row: int, col: int, shard: Optional[int] = None) -> str:
        if shard is not None and self.configs_per_shard:
            row = shard * self.configs_per_shard + row
        sources = self.sources_by_row[row] if 0 <= row < len(
            self.sources_by_row) else []
        return sources[col] if 0 <= col < len(sources) else ""

    def name(self, row: int, shard: Optional[int] = None) -> str:
        if shard is not None and self.configs_per_shard:
            row = shard * self.configs_per_shard + row
        return self.names_by_row[row] if 0 <= row < len(
            self.names_by_row) else ""

    # -- reporting ---------------------------------------------------------

    def registered_rules(self):
        """Every real (authconfig, column, source) rule in this snapshot."""
        for row, sources in enumerate(self.sources_by_row):
            name = self.names_by_row[row]
            if not name:
                continue  # padded config row
            for col, src in enumerate(sources):
                yield name, col, src

    def to_json(self) -> Dict[str, Any]:
        self.flush()
        return {
            "configs": sum(1 for n in self.names_by_row if n),
            "rules": sum(len(s) for r, s in enumerate(self.sources_by_row)
                         if self.names_by_row[r]),
            "fold_calls": self.fold_calls,
            "fold_seconds": round(self.fold_seconds, 6),
        }


def dead_rule_report(heat: Optional[HeatMap],
                     analysis: Optional[Dict[str, Any]],
                     limit: int = 100) -> Optional[Dict[str, Any]]:
    """Cross-reference the heat map's never-fired set against the static
    policy-analysis findings (PR 4): a rule that static analysis already
    called constant-allow CANNOT fire (it never denies) — expected-dead;
    a never-fired rule with no static explanation is runtime-dead policy
    surface worth pruning.  /debug/vars ``engine.provenance.dead_rules``."""
    if heat is None:
        return None
    heat.flush()  # the fired set must reflect every folded batch
    # keyed (config, evaluator index): a constant-allow finding on
    # evaluator 0 must not "explain" evaluator 1's silence — per-config
    # keying would mark live-but-quiet rules as safe to prune
    static_by_rule: Dict[Any, List[str]] = {}
    for f in (analysis or {}).get("findings", []):
        kind = f.get("kind", "")
        if kind in ("constant-allow", "shadowed-rule", "duplicate-rule"):
            d = f.get("detail") or {}
            cfg = str(d.get("config", ""))
            ev = d.get("evaluator")
            key = (cfg, int(ev)) if ev is not None else cfg
            static_by_rule.setdefault(key, []).append(kind)
    fired = fired_pairs()
    never: List[Dict[str, Any]] = []
    total = fired_n = 0
    for name, col, src in heat.registered_rules():
        total += 1
        if (name, col) in fired:
            fired_n += 1
            continue
        if len(never) < limit:
            never.append({
                "authconfig": name,
                "rule": rule_label(col, src),
                # evaluator-keyed findings first; config-wide ones (no
                # evaluator in the finding detail) apply to every column
                "static_findings": (static_by_rule.get((name, col), []) +
                                    static_by_rule.get(name, [])),
            })
    return {
        "rules_total": total,
        "rules_fired": fired_n,
        "never_fired_count": total - fired_n,
        "never_fired": never,
        "statically_explained": sum(1 for d in never if d["static_findings"]),
    }


# ---------------------------------------------------------------------------
# decision log: bounded ring of head-sampled structured decision records
# ---------------------------------------------------------------------------

# pinned record schema (tests/test_provenance.py): every record carries
# exactly these keys, so downstream log pipelines can rely on the shape.
# Schema 2 (ISSUE 13 satellite): each RECORD is stamped with the schema it
# was written under — a saved /debug/decisions JSON (or a capture segment
# embedding decision fields) names its own version, so offline readers
# reject skew with the typed DecisionSchemaError instead of misparsing.
DECISION_SCHEMA = 2
DECISION_FIELDS = ("schema", "t", "lane", "host", "authconfig", "verdict",
                   "rule", "rule_index", "latency_ms", "generation")


class DecisionSchemaError(ValueError):
    """A decision-log payload was written under a different schema version
    than this reader understands.  Typed so offline tooling (analysis
    --decisions, replay readers) fails loudly instead of misparsing."""


def check_decision_schema(payload: Any) -> None:
    """Raise :class:`DecisionSchemaError` when ``payload`` (a
    /debug/decisions-shaped dict) names a schema this reader does not
    speak.  A payload without a schema field predates versioning and is
    rejected too — silence is exactly the misparse this gate exists to
    stop."""
    got = payload.get("schema") if isinstance(payload, dict) else None
    if got != DECISION_SCHEMA:
        raise DecisionSchemaError(
            f"decision-log schema skew: payload schema {got!r} != reader "
            f"schema {DECISION_SCHEMA} (refusing to misparse; re-save the "
            f"log with a matching build)")


class DecisionLog:
    """Head-sampled decision ring, sampled STRATIFIED per tenant (ISSUE 15
    satellite).  The sampler used to be one global 1-in-N counter with at
    most one record per batch — under a zipf-headed workload the hot
    tenant's batches won essentially every fire AND its records evicted
    every cold-tenant record from the bounded ring, so /debug/decisions
    showed exactly one tenant.  Now:

    - every tenant has its own 1-in-N counter, and ``fold_and_sample``
      steps it once per distinct tenant in the batch: at most one record
      per tenant per batch.  The counters of a snapshot's tenants are two
      arrays on its heat map (``HeatMap.sample_gate``: bounded by the
      corpus, per snapshot; Python runs only for the tenants that fire);
      ``should_sample_tenant(tenant, n)`` is the same gate by name, over a
      bounded LRU table, for callers that have no heat map;
    - alongside the global ring, each tenant keeps a small per-tenant
      sub-ring (``tenant_capacity`` newest records, LRU-bounded tenants),
      so a hot tenant filling the global ring can never evict a cold
      tenant's last records — ``/debug/decisions?tenant=NAME`` serves
      them.

    ``should_sample(n)`` (the legacy global gate) remains for callers with
    no tenant axis."""

    MAX_TENANTS = 512

    def __init__(self, capacity: int = 1024, sample_n: int = 64,
                 tenant_capacity: int = 4):
        self.capacity = max(1, int(capacity))
        self.sample_n = max(1, int(sample_n))
        self.tenant_capacity = max(1, int(tenant_capacity))
        self._ring: deque = deque(maxlen=self.capacity)
        # guards ring append vs snapshot: both lanes record concurrently
        # while /debug/decisions lists the ring, and iterating a deque
        # that another thread appends to raises RuntimeError
        self._lock = threading.Lock()
        self._seen = 0
        self._next_fire = 1  # first decision samples (head of the stream)
        # tenant -> [seen, next_fire]; insertion order is the LRU axis
        self._tenant_gate: Dict[str, list] = {}
        # bumped when the rate changes: every heat map's gate re-arms
        self.gate_epoch = 0
        # tenant -> deque(maxlen=tenant_capacity) of its newest records
        self._tenant_ring: Dict[str, deque] = {}
        self.records_total = 0
        self._lane_children: Dict[str, Any] = {}

    def configure(self, capacity: Optional[int] = None,
                  sample_n: Optional[int] = None) -> None:
        if capacity is not None and int(capacity) != self.capacity:
            self.capacity = max(1, int(capacity))
            with self._lock:
                self._ring = deque(self._ring, maxlen=self.capacity)
        if sample_n is not None:
            self.sample_n = max(1, int(sample_n))
            # re-arm from here: a tighter rate must not wait out the fire
            # point the old (possibly much larger) rate scheduled
            self._next_fire = self._seen + self.sample_n
            with self._lock:
                self._tenant_gate.clear()
                self.gate_epoch += 1

    def should_sample(self, n_decisions: int) -> bool:
        """Advance the decision counter by this batch's size; True when the
        1-in-N sampler fires inside the batch — at most one record per
        batch, O(1) per batch (a racing add under free threading can only
        lose a sample, never add per-request work)."""
        if n_decisions <= 0:
            return False
        seen = self._seen = self._seen + n_decisions
        if seen >= self._next_fire:
            self._next_fire = seen + self.sample_n
            return True
        return False

    def should_sample_tenant(self, tenant: str, n_decisions: int) -> bool:
        """The stratified gate: this TENANT's own 1-in-N counter, advanced
        by its decision count within the batch.  The first decision a
        tenant ever shows always samples (cold tenants become visible on
        their first batch, not after N of them)."""
        if n_decisions <= 0:
            return False
        gate = self._tenant_gate.get(tenant)
        if gate is None:
            if len(self._tenant_gate) >= self.MAX_TENANTS:
                with self._lock:
                    # LRU-ish bound: drop the oldest-inserted third
                    for t in list(self._tenant_gate)[:self.MAX_TENANTS // 3]:
                        self._tenant_gate.pop(t, None)
            gate = self._tenant_gate[tenant] = [0, 1]
        gate[0] += n_decisions
        if gate[0] >= gate[1]:
            gate[1] = gate[0] + self.sample_n
            return True
        return False

    def record(self, lane: str, host: str, authconfig: str, verdict: bool,
               rule: Optional[str], rule_index: int, latency_ms: float,
               generation: Any) -> None:
        rec = {
            "schema": DECISION_SCHEMA,
            "t": time.time(),
            "lane": lane,
            "host": host,
            "authconfig": authconfig,
            "verdict": "allow" if verdict else "deny",
            "rule": rule,
            "rule_index": rule_index,
            "latency_ms": round(float(latency_ms), 3),
            "generation": generation,
        }
        with self._lock:
            self._ring.append(rec)
            self.records_total += 1
            if authconfig:
                sub = self._tenant_ring.get(authconfig)
                if sub is None:
                    if len(self._tenant_ring) >= self.MAX_TENANTS:
                        for t in list(self._tenant_ring)[
                                :self.MAX_TENANTS // 3]:
                            self._tenant_ring.pop(t, None)
                    sub = self._tenant_ring[authconfig] = deque(
                        maxlen=self.tenant_capacity)
                sub.append(rec)
        child = self._lane_children.get(lane)
        if child is None:
            child = self._lane_children[lane] = \
                metrics_mod.decision_records.labels(lane)
        child.inc()

    def to_json(self, n: Optional[int] = None,
                tenant: Optional[str] = None) -> Dict[str, Any]:
        with self._lock:
            if tenant is not None:
                records = list(self._tenant_ring.get(tenant, ()))
            else:
                records = list(self._ring)
            tenants_tracked = len(self._tenant_ring)
        if n is not None:
            n = max(0, int(n))
            records = records[-n:] if n else []
        out = {
            "schema": DECISION_SCHEMA,
            "capacity": self.capacity,
            "sample_n": self.sample_n,
            "records_total": self.records_total,
            "records": records,
            "stratified": {
                "tenants_tracked": tenants_tracked,
                "per_tenant_capacity": self.tenant_capacity,
            },
        }
        if tenant is not None:
            out["tenant"] = tenant
        return out


# one ring per process: both lanes sample into it, the analysis CLI and
# /debug/decisions read it
DECISIONS = DecisionLog()


def fold_and_sample(heat: HeatMap, rows, firing, n: int, *, lane: str,
                    shards=None, host: str = "", latency_ms: float = 0.0,
                    generation: Any = None, host_of=None,
                    latency_of=None,
                    groups: Optional[RowGroups] = None) -> int:
    """The one per-batch observability sequence every lane's completion
    runs: fold the batch's attribution into the heat map, then sample
    decision records STRATIFIED per tenant — at most one record per
    distinct tenant (authconfig) per batch, each tenant gated by its own
    1-in-N counter, so a zipf-hot tenant can neither win every sample nor
    evict the cold tenants' records (ISSUE 15 satellite).  The gate is a
    vector compare on the heat map's arrays; Python runs only for the
    tenants that fire: a tenant's first decision in a snapshot, then one in
    ``sample_n``.  Returns the records made.  Keeping it here means a schema
    or sampling change lands once, not once per lane.

    ``groups`` (``heat.group(rows, shards)``): the caller grouped the rows
    already, the rows of many batches perhaps (the native lane's fold over
    its kept cuts), and the gate reads that grouping: "a batch" above is
    then what the caller folds at once."""
    if groups is None:
        groups = heat.group(rows, shards)
    heat.fold(rows, firing, flat=groups.flat)
    if not n:
        return 0
    hit_rows, hit_first = heat.sample_gate(groups, DECISIONS.sample_n,
                                           DECISIONS.gate_epoch)
    for u, i in zip(hit_rows.tolist(), hit_first.tolist()):
        col = int(firing[i])
        shard_i = int(shards[i]) if shards is not None else None
        # per-record resolvers (``host_of``/``latency_of``, called only
        # for SAMPLED tenants): each tenant's record carries ITS OWN
        # request's host/latency — the batch head's values belong to a
        # different tenant in a mixed batch, which is exactly the wrong
        # evidence in the per-tenant sub-rings
        DECISIONS.record(
            lane=lane,
            host=(host_of(i) if host_of is not None else host),
            authconfig=heat.name(u),
            verdict=col < 0,
            rule=(rule_label(col, heat.source(int(rows[i]), col,
                                              shard=shard_i))
                  if col >= 0 else None),
            rule_index=col,
            latency_ms=(latency_of(i) if latency_of is not None
                        else latency_ms),
            generation=generation)
    return len(hit_rows)


# ---------------------------------------------------------------------------
# deny-response attribution (the X-Ext-Auth-Reason / dynamic_metadata seam)
# ---------------------------------------------------------------------------


def deny_provenance(authconfig: str, rule_index: int, source: str,
                    lane: str = "engine") -> Dict[str, Any]:
    """The JSON-safe provenance object a denied response carries in Envoy
    dynamic_metadata (always) and X-Ext-Auth-Reason (knob-gated)."""
    return {
        "authconfig": authconfig,
        "rule_index": int(rule_index),
        "rule": source,
        "lane": lane,
    }


def deny_reason(prov: Optional[Dict[str, Any]]) -> str:
    """The deny message: attributed behind --expose-deny-reason, the
    reference's generic 'Unauthorized' otherwise."""
    if prov and EXPOSE_DENY_REASON:
        return (f"denied by {prov['authconfig']} "
                f"rule[{prov['rule_index']}]: {prov['rule']}")
    return "Unauthorized"
