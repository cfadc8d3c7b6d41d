"""Per-tenant observability folds (ISSUE 15): the tenant axis of PR 9's
vectorized provenance/SLO folds.

One call per micro-batch (never per request), and no Python per tenant: the
plane keeps every tenant's state in arrays indexed by a slot (``name ->
slot``, minted on the tenant's first row), a heat map's ``row -> slot``
vector is resolved once a row, and ``fold`` is a gather, a few bincounts and
the EWMA steps under masks.  It accumulates per-tenant requests, denies,
queue-wait means, SLO bad counts and burn, and a served-rate EWMA (the
noisy-neighbor detector's share signal).

Prometheus exposition is bounded-cardinality by construction: the flush
(run by the drain: ``utils.metrics.drain``, on the housekeeping cadence and
before every read) assigns real tenant label values only to the top-K
tenants by cumulative request volume and folds everyone else into the
reserved ``other`` bucket, summing the deltas by label before it touches a
child.  K is clamped to the family's declared hard bound in
``utils.metrics.TENANT_LABEL_BOUNDS`` — the table the metrics-catalog
cardinality lint enforces."""

from __future__ import annotations

import threading
import time
import weakref
from typing import Any, Dict, List, Optional

import numpy as np

from ..utils import metrics as metrics_mod

__all__ = ["TenantStats"]

# per-slot state, one array each.  Counts are int64; clocks and EWMAs
# float64.  The burn columns are one coarse sliding window a tenant (the
# tenant axis of utils.slo's burn-rate fold): two half-window buckets,
# current (burn_total, burn_bad, since burn_t) and previous (burn_prev_*),
# rotated in place, so burn reads the sum of both.
_INT_COLUMNS = ("requests", "denies", "slo_bad", "rate_pend", "burn_total",
                "burn_bad", "burn_prev_total", "burn_prev_bad", "label")
_FLOAT_COLUMNS = ("rate_t", "rate_ewma", "wait_ewma", "last_seen", "burn_t")


class _RowSlots:
    """One heat map's row -> slot vector (-2: row not met yet, -1: a padded
    row with no name), the slots its rows have met, and whether two rows
    met the same one (a config named on two shards)."""
    __slots__ = ("vec", "slots", "shared")

    def __init__(self, vec: np.ndarray):
        self.vec = vec
        self.slots: set = set()
        self.shared = False


class TenantStats:
    def __init__(self, lane: str, top_k: int = 16, max_tenants: int = 8192,
                 burn_window_s: float = 60.0, gc_idle_s: float = 600.0):
        self.lane = lane
        bound = min(metrics_mod.TENANT_LABEL_BOUNDS.get(
            "auth_server_tenant_requests_total", 32), 32)
        self.top_k = max(1, min(int(top_k), bound))
        self.max_tenants = int(max_tenants)
        self.gc_idle_s = float(gc_idle_s)
        self.burn_window_s = float(burn_window_s)
        self.burn_budget = 1.0 - 0.999  # the error budget of a 99.9 % SLO
        self._burn_swept = 0.0
        self._lock = threading.Lock()
        self._slot: Dict[str, int] = {}
        self._names: List[str] = []
        # Prometheus deltas by the FOLD's lane, [requests, denies, slo_bad]
        # x slot (the plane is shared across engine + native; the slot
        # arrays serve shares/waits, but exported counters must say which
        # lane served)
        self._lane_delta: Dict[str, np.ndarray] = {}
        self._grow(64)
        # heat map -> its _RowSlots; cleared when slots are compacted
        self._row_slots: "weakref.WeakKeyDictionary" = \
            weakref.WeakKeyDictionary()
        self._label_of: Dict[str, str] = {}  # tenant -> prometheus label
        self._label_names: List[str] = [metrics_mod.TENANT_OTHER]
        self._children: Dict[Any, Any] = {}
        self.fold_calls = 0
        self.total_requests = 0
        # wait-observation sink (TenantAdmission.observe_waits), attached
        # by the plane so the per-tenant CoDel signal rides this same fold
        self.wait_sink = None
        metrics_mod.register_drainable(self)

    def _grow(self, capacity: int) -> None:
        for columns, dtype in ((_INT_COLUMNS, np.int64),
                               (_FLOAT_COLUMNS, np.float64)):
            for column in columns:
                grown = np.zeros(capacity, dtype=dtype)
                old = getattr(self, column, None)
                if old is not None:
                    grown[:old.size] = old
                setattr(self, column, grown)
        for lane, delta in self._lane_delta.items():
            grown = np.zeros((3, capacity), dtype=np.int64)
            grown[:, :delta.shape[1]] = delta
            self._lane_delta[lane] = grown

    def _mint(self, name: str, now: float) -> int:
        slot = self._slot.get(name)
        if slot is None:
            slot = self._slot[name] = len(self._names)
            self._names.append(name)
            if slot >= self.requests.size:
                self._grow(2 * self.requests.size)
            self.rate_t[slot] = self.last_seen[slot] = now
            if name in self._label_of:  # dropped idle, and back
                self.label[slot] = self._label_names.index(name)
        return slot

    def _slots_of(self, heat, flat: np.ndarray, now: float):
        """(the rows as tenant slots, whether two rows of this heat map have
        met one tenant).  A row costs Python once a snapshot: its name is
        looked up, and minted, the first time it appears."""
        met = self._row_slots.get(heat)
        top = int(flat.max()) + 1
        if met is None or met.vec.size < top:
            grown = np.full(max(top, len(getattr(heat, "names_by_row", ()))),
                            -2, dtype=np.int64)
            if met is None:
                met = self._row_slots[heat] = _RowSlots(grown)
            else:
                grown[:met.vec.size] = met.vec
                met.vec = grown
        vec = met.vec
        slots = vec[flat]
        unmet = slots == -2
        if unmet.any():
            for row in np.unique(flat[unmet]).tolist():
                name = heat.name(row)
                if not name:
                    vec[row] = -1
                    continue
                slot = vec[row] = self._mint(name, now)
                if slot in met.slots:
                    met.shared = True
                met.slots.add(slot)
            slots = vec[flat]
        return slots, met.shared

    # -- folding (one call per batch) ---------------------------------------

    def fold(self, heat, rows, firing=None, shards=None, waits=None,
             bad_mask=None, denied_mask=None, lane: Optional[str] = None,
             now: Optional[float] = None) -> None:
        """Fold one batch's tenant axis.  ``heat`` resolves kernel rows to
        tenant names (the snapshot's HeatMap — attribution and tenancy
        read identical evidence); ``firing`` (or ``denied_mask``) marks
        denials; ``waits`` (seconds, per row, optional) are QUEUE waits —
        they feed the per-tenant wait EWMAs and the per-tenant CoDel sink
        (pass None on lanes without a per-request queue clock);
        ``bad_mask`` (bool per row, optional) marks SLO-budget burns
        (callers decide the SLI — sojourn vs batch round trip); ``lane``
        labels the Prometheus deltas (defaults to the plane's lane)."""
        if heat is None:
            return
        rows = np.asarray(rows, dtype=np.int64)
        n = int(rows.size)
        if not n:
            return
        flat = rows
        cps = getattr(heat, "configs_per_shard", None)
        if shards is not None and cps:
            flat = np.asarray(shards, dtype=np.int64) * cps + rows
        if denied_mask is None and firing is not None:
            denied_mask = np.asarray(firing, dtype=np.int64) >= 0
        uniq, inv, k = np.unique(flat, return_inverse=True,
                                 return_counts=True)

        def per_row(mask):
            if mask is None:
                return None
            return np.bincount(inv[np.asarray(mask, dtype=bool)],
                               minlength=uniq.size)

        wait_sum = wait_least = None
        if waits is not None:
            waits = np.asarray(waits, dtype=np.float64)
            if waits.size == n:
                wait_sum = np.bincount(inv, weights=waits,
                                       minlength=uniq.size)
                wait_least = np.full(uniq.size, np.inf)
                np.minimum.at(wait_least, inv, waits)
        self.fold_grouped(heat, uniq, k, per_row(denied_mask),
                          per_row(bad_mask), wait_sum=wait_sum,
                          wait_least=wait_least, lane=lane, now=now)

    def fold_grouped(self, heat, uniq, k, den=None, bad=None, wait_sum=None,
                     wait_least=None, lane: Optional[str] = None,
                     now: Optional[float] = None) -> None:
        """``fold`` for a caller that grouped its requests by flat config
        row already (``HeatMap.group``, over one batch or many): ``uniq`` the
        distinct rows, and a row each ``k`` its requests, ``den`` its
        denials, ``bad`` its SLO-budget burns, ``wait_sum`` and
        ``wait_least`` the sum and the least of its queue waits.  No sort
        here: a row is a tenant, but for a config named on two shards."""
        now = time.monotonic() if now is None else now
        lane = lane or self.lane
        with self._lock:
            self.fold_calls += 1
            self.total_requests += int(k.sum())
            u, shared = self._slots_of(heat, uniq, now)
            columns = [k, den, bad, wait_sum, wait_least]
            named = u >= 0
            if not named.all():
                u = u[named]
                columns = [c if c is None else c[named] for c in columns]
            if not u.size:
                return
            if shared:
                u, inv = np.unique(u, return_inverse=True)
                least = columns.pop()
                columns = [c if c is None else np.bincount(
                    inv, weights=c, minlength=u.size).astype(c.dtype)
                    for c in columns]
                if least is not None:
                    rows_least, least = least, np.full(u.size, np.inf)
                    np.minimum.at(least, inv, rows_least)
                columns.append(least)
            k, den, bad, wait_sum, wait_least = columns
            self.requests[u] += k
            if den is not None:
                self.denies[u] += den
            if bad is not None:
                self.slo_bad[u] += bad
            self.last_seen[u] = now
            delta = self._lane_delta.get(lane)
            if delta is None:
                delta = self._lane_delta[lane] = np.zeros(
                    (3, self.requests.size), dtype=np.int64)
            delta[0, u] += k
            if den is not None:
                delta[1, u] += den
            if bad is not None:
                delta[2, u] += bad
            # served-rate EWMA: rows accumulate across folds inside the
            # 50ms window, then the whole window's rows divide the elapsed
            # dt (never just the last batch's: batches land far faster than
            # the window, and that would undercount exactly the hot tenants
            # the detector's share signal exists for)
            self.rate_pend[u] += k
            dt = now - self.rate_t[u]
            due = dt > 0.05
            if due.any():
                s = u[due]
                inst = self.rate_pend[s] / dt[due]
                old = self.rate_ewma[s]
                self.rate_ewma[s] = np.where(old == 0.0, inst,
                                             0.7 * old + 0.3 * inst)
                self.rate_t[s] = now
                self.rate_pend[s] = 0
            if wait_sum is not None:
                mean = wait_sum / k
                old = self.wait_ewma[u]
                self.wait_ewma[u] = np.where(old == 0.0, mean,
                                             0.8 * old + 0.2 * mean)
                if self.wait_sink is not None:
                    # the per-tenant CoDel signal: the one call a tenant
                    # left, on the lanes that clock a queue (the engine's)
                    for slot, m, w in zip(u.tolist(), mean.tolist(),
                                          wait_least.tolist()):
                        self.wait_sink(self._names[slot], m, w, now)
            if bad is not None:
                self._fold_burn(u, k, bad, now)

    def _fold_burn(self, u, k, bad, now: float) -> None:
        age = now - self.burn_t[u]
        turn = age >= self.burn_window_s / 2.0
        if turn.any():
            s = u[turn]
            # past a whole window both halves are stale
            fresh = age[turn] < self.burn_window_s
            self.burn_prev_total[s] = np.where(fresh, self.burn_total[s], 0)
            self.burn_prev_bad[s] = np.where(fresh, self.burn_bad[s], 0)
            self.burn_total[s] = self.burn_bad[s] = 0
            self.burn_t[s] = now
        self.burn_total[u] += k
        self.burn_bad[u] += bad
        if now - self._burn_swept > self.burn_window_s:
            # once a window: a tenant idle for a whole one reads nothing
            self._burn_swept = now
            idle = now - self.burn_t > self.burn_window_s
            for column in ("burn_total", "burn_bad", "burn_prev_total",
                           "burn_prev_bad", "burn_t"):
                getattr(self, column)[idle] = 0

    # -- shares (the detector's signal) -------------------------------------

    def share(self, tenant: str) -> float:
        """This tenant's share of the lane's recently-served rows (rate
        EWMAs — decays as traffic shifts)."""
        with self._lock:
            slot = self._slot.get(tenant)
            if slot is None or not self.rate_ewma[slot]:
                return 0.0
            total = float(self.rate_ewma.sum())
            return float(self.rate_ewma[slot]) / total if total > 0 else 0.0

    def shares(self) -> Dict[str, float]:
        with self._lock:
            total = float(self.rate_ewma.sum())
            if total <= 0:
                return {}
            live = np.nonzero(self.rate_ewma > 0)[0]
            return dict(zip((self._names[s] for s in live.tolist()),
                            (self.rate_ewma[live] / total).tolist()))

    def rate(self, tenant: str) -> float:
        with self._lock:
            slot = self._slot.get(tenant)
            return float(self.rate_ewma[slot]) if slot is not None else 0.0

    def export_fold(self) -> Dict[str, Dict[str, float]]:
        """Raw per-tenant counters for the fleet fold publisher (ISSUE 18):
        cumulative requests/denies/slo_bad plus the live served-rate EWMA.
        Cumulative counts let the aggregator difference consecutive folds
        into deltas; the rate EWMAs are what global tenant share sums —
        per-replica SHARES cannot be averaged (consistent-hash routing
        concentrates tenants, so a fleet-hot tenant can look locally
        entitled on every replica at once — the exact blindness the global
        fold exists to remove)."""
        with self._lock:
            n = len(self._names)
            return {name: {"requests": r, "denies": d, "slo_bad": b,
                           "rate": e}
                    for name, r, d, b, e in zip(
                        self._names, self.requests[:n].tolist(),
                        self.denies[:n].tolist(), self.slo_bad[:n].tolist(),
                        self.rate_ewma[:n].tolist())}

    # -- prometheus flush (top-K + other) -----------------------------------

    def _labels(self) -> None:
        """Mint label values: the top-K tenants by cumulative volume get
        their own, everyone else folds into `other` (label index 0).  A
        tenant that falls OUT of the top-K keeps its minted label
        (monotonic counters must not teleport into `other`); the hard bound
        holds because minted labels only grow to the bound and then
        stop."""
        n = len(self._names)
        bound = min(metrics_mod.TENANT_LABEL_BOUNDS.get(
            "auth_server_tenant_requests_total", 32), 32)
        ranked = np.argsort(-self.requests[:n], kind="stable")[:self.top_k]
        for slot in ranked.tolist():
            name = self._names[slot]
            if name not in self._label_of and len(self._label_of) < bound:
                self._label_of[name] = name
                self.label[slot] = len(self._label_names)
                self._label_names.append(name)

    def _child(self, family, *labels):
        child = self._children.get((family, labels))
        if child is None:
            child = self._children[(family, labels)] = family.labels(*labels)
        return child

    def flush(self, now: Optional[float] = None) -> int:
        """Push the deltas since the last flush into the Prometheus
        children, summed by label first: a child is touched once a flush,
        however many tenants share its label.  Returns the children
        touched."""
        now = time.monotonic() if now is None else now
        families = (metrics_mod.tenant_requests, metrics_mod.tenant_denied,
                    metrics_mod.tenant_slo_bad)
        with self._lock:
            n = len(self._names)
            self._labels()
            n_labels = len(self._label_names)
            moved = []
            for lane, delta in self._lane_delta.items():
                for family, row in zip(families, delta):
                    if not row.any():
                        continue
                    by_label = np.bincount(self.label[:n], weights=row[:n],
                                           minlength=n_labels)
                    for at in np.nonzero(by_label)[0].tolist():
                        moved.append((family, lane, self._label_names[at],
                                      int(by_label[at])))
                delta[:] = 0
            labelled = np.nonzero(self.label[:n])[0]
            gauges = [(self._names[s], w) for s, w in zip(
                labelled.tolist(), self.wait_ewma[labelled].tolist())]
            if n > self.max_tenants:
                self._drop_idle(now)
        for family, lane, label, amount in moved:
            self._child(family, lane, label).inc(amount)
        for label, w in gauges:
            self._child(metrics_mod.tenant_queue_wait, label).set(
                round(w, 6))
        return len(moved) + len(gauges)

    def _drop_idle(self, now: float) -> None:
        """Past ``max_tenants``: forget tenants idle for ``gc_idle_s`` and
        compact the slots (the deltas were just taken, so none is lost;
        every heat map resolves its rows again)."""
        n = len(self._names)
        keep = np.nonzero(now - self.last_seen[:n] <= self.gc_idle_s)[0]
        if keep.size == n:
            return
        for column in _INT_COLUMNS + _FLOAT_COLUMNS:
            old = getattr(self, column)
            kept = np.zeros_like(old)
            kept[:keep.size] = old[keep]
            setattr(self, column, kept)
        self._names = [self._names[s] for s in keep.tolist()]
        self._slot = {name: s for s, name in enumerate(self._names)}
        self._row_slots.clear()

    def count_reject(self, tenant: str, reason: str) -> None:
        with self._lock:
            label = self._label_of.get(tenant, metrics_mod.TENANT_OTHER)
        metrics_mod.tenant_rejected.labels(label, reason).inc()

    # -- introspection -------------------------------------------------------

    def _burn_json(self, top: int) -> Dict[str, Any]:
        n = len(self._names)
        total = self.burn_total[:n] + self.burn_prev_total[:n]
        bad = self.burn_bad[:n] + self.burn_prev_bad[:n]
        seen = np.nonzero(total)[0]
        rate = bad[seen] / total[seen] / self.burn_budget
        order = np.argsort(-rate, kind="stable")[:top]
        return {
            "window_s": self.burn_window_s,
            "keys": int(seen.size),
            "top_burn": [{"key": self._names[s], "burn_rate": round(r, 4),
                          "total": t, "bad": d}
                         for s, r, t, d in zip(
                             seen[order].tolist(), rate[order].tolist(),
                             total[seen][order].tolist(),
                             bad[seen][order].tolist())],
        }

    def to_json(self, top: int = 16) -> Dict[str, Any]:
        with self._lock:
            n = len(self._names)
            ranked = np.argsort(-self.requests[:n], kind="stable")[:top]
            total_rate = float(self.rate_ewma.sum()) or 1.0
            rows = [{
                "tenant": self._names[s],
                "requests": int(self.requests[s]),
                "denies": int(self.denies[s]),
                "slo_bad": int(self.slo_bad[s]),
                "queue_wait_ewma_ms": round(float(self.wait_ewma[s]) * 1e3,
                                            3),
                "share": round(float(self.rate_ewma[s]) / total_rate, 4),
            } for s in ranked.tolist()]
            burn = self._burn_json(top=8)
        return {
            "lane": self.lane,
            "tenants_seen": n,
            "top_k": self.top_k,
            "fold_calls": self.fold_calls,
            "requests_total": self.total_requests,
            "top": rows,
            "slo_burn": burn,
        }
