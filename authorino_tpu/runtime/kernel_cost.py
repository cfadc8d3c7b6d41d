"""Kernel cost observatory (ISSUE 16): the measurement contract the fused-
kernel work (ROADMAP item 2) is accepted against.

Two planes:

1. **CostLedger** — per-batch STRUCTURAL device-cost counters folded O(1)
   per micro-batch from every dispatch site (engine single-corpus, engine
   sharded, native ``_dispatch``, mesh shard-steps, and the host/brownout/
   degrade CPU evals).  Counts the things wall clock cannot swing:
   device-computation launches (the number item 2 must drive to 1 per
   batch), H2D bytes (the fused staging buffer's size —
   snapshot upload traffic stays on the PR 8 ``delta/full_upload_bytes``
   counters so the two planes compose instead of double-counting), D2H
   bytes (the PR 3 bitpacked ``[pad, W]`` readback), pad waste (padded −
   real rows, plus eff-column slack), and the dedup/cache-avoided rows
   that never shipped.  The ledger is PROCESS-WIDE like /metrics: every
   engine and frontend in the process folds into the same lanes
   ("engine", "host", "mesh", "native").

2. **CostModel** — per-component static analysis at reconcile: at each
   snapshot swap, ``lower().compile().cost_analysis()`` of the serving
   kernel entry points at a representative (pad, eff) shape → modeled
   FLOPs / bytes-accessed per padded row, recorded per generation.  A
   reconcile whose modeled per-row cost regresses ≥2× vs the previous
   generation raises a ``cost-regression`` flight-recorder anomaly —
   ADVISORY, never rejects the swap (modeled cost compares generations,
   not wall clock; see docs/performance.md "Kernel cost model").
   Analyses are memoized process-wide by (entry, shape, params
   fingerprint): an unchanged-shape reconcile pays a dict hit, not an
   XLA compile.
"""

from __future__ import annotations

import logging
import threading
from typing import Any, Dict, List, Optional, Tuple

from ..utils import metrics as metrics_mod

log = logging.getLogger("authorino-tpu.kernel-cost")

LANES = ("engine", "host", "mesh", "native")

# modeled per-row cost must grow by this factor generation-over-generation
# to count as a regression (2x: a pad-bucket step or an added operand lane
# never doubles per-row FLOPs by itself — a kernel-structure change does)
REGRESSION_FACTOR = 2.0

_FIELDS = (
    "batches", "launches", "zero_launch_batches", "rows", "device_rows",
    "h2d_transfers", "h2d_bytes", "d2h_bytes", "pad_rows", "pad_waste_rows",
    "eff_slack_cols", "dedup_avoided_rows", "cache_avoided_rows",
    "dfa_ovf_rows", "own_dfa_slots", "own_dfa_rows", "telemetry_folds",
    "eff_cols", "dfa_dev_bytes", "dfa_host_bytes", "resolved_native",
)


class _LaneCost:
    __slots__ = _FIELDS

    def __init__(self) -> None:
        for f in _FIELDS:
            setattr(self, f, 0)

    def to_json(self) -> Dict[str, Any]:
        d: Dict[str, Any] = {f: int(getattr(self, f)) for f in _FIELDS}
        if self.batches:
            d["launches_per_batch"] = round(self.launches / self.batches, 4)
        if self.device_rows:
            d["h2d_bytes_per_device_row"] = round(
                self.h2d_bytes / self.device_rows, 2)
        if self.pad_rows:
            d["d2h_bytes_per_pad_row"] = round(
                self.d2h_bytes / self.pad_rows, 2)
            d["pad_occupancy"] = round(self.device_rows / self.pad_rows, 4)
        return d


class CostLedger:
    """Process-wide structural device-cost counters, one fold per batch."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._lanes: Dict[str, _LaneCost] = {}

    def observe(self, lane: str, *, rows: int, device_rows: int = 0,
                launches: int = 0, h2d_transfers: int = 0,
                h2d_bytes: int = 0, d2h_bytes: int = 0,
                pad_rows: int = 0, eff_slack_cols: int = 0,
                dedup_avoided_rows: int = 0,
                cache_avoided_rows: int = 0,
                dfa_ovf_rows: int = 0, own_dfa_slots: int = 0,
                own_dfa_rows: int = 0, eff_cols: int = 0,
                dfa_dev_bytes: int = 0, dfa_host_bytes: int = 0) -> None:
        """Fold one batch: ``rows`` real requests in the cut, of which
        ``device_rows`` actually shipped (``pad_rows`` after padding) in
        ``launches`` device calls, their request operands handed to the
        runtime in ``h2d_transfers`` host-to-device transfers (1 when the
        launch staged one buffer, one an operand otherwise);
        ``dfa_ovf_rows`` of the cut's rows carried a value past their
        config's size class's byte width, whose DFAs the encoder scanned on
        the host (the native lane counts them); ``own_dfa_slots`` DFA rows were
        scanned by the cut's launches (pad rows x the launch's size class's
        D), of which ``own_dfa_rows`` are DFA rows the launched rows' own
        configs have: the rest is padding to the class's largest member
        (the native lane counts both); ``eff_cols`` is the launches' byte
        bucket ``eff``, summed (over ``launches``: the scan length a launch
        ran); ``dfa_dev_bytes`` the value bytes the launched rows' DFAs read
        on the device (a DFA row a value byte, the row's own config's) and
        ``dfa_host_bytes`` the value bytes the encoder's overflow scan was
        handed for the cut's rows (a DFA a value byte, before any early
        exit).  Host/degrade
        evals and fully cache/dedup-resolved cuts fold with launches=0 and
        zero byte counts.
        The mesh lane folds its batch here with launches=0 and counts the
        actual shard-step launches at the dispatch site instead
        (``observe_launch``) — failover re-dispatches then show up as
        launches_per_batch > 1 rather than vanishing."""
        pad_waste = max(0, pad_rows - device_rows)
        with self._lock:
            lc = self._lanes.get(lane)
            if lc is None:
                lc = self._lanes[lane] = _LaneCost()
            lc.batches += 1
            lc.launches += launches
            if launches == 0 and device_rows == 0:
                lc.zero_launch_batches += 1
            lc.rows += rows
            lc.device_rows += device_rows
            lc.h2d_transfers += h2d_transfers
            lc.h2d_bytes += h2d_bytes
            lc.d2h_bytes += d2h_bytes
            lc.pad_rows += pad_rows
            lc.pad_waste_rows += pad_waste
            lc.eff_slack_cols += eff_slack_cols
            lc.dedup_avoided_rows += dedup_avoided_rows
            lc.cache_avoided_rows += cache_avoided_rows
            lc.dfa_ovf_rows += dfa_ovf_rows
            lc.own_dfa_slots += own_dfa_slots
            lc.own_dfa_rows += own_dfa_rows
            lc.eff_cols += eff_cols
            lc.dfa_dev_bytes += dfa_dev_bytes
            lc.dfa_host_bytes += dfa_host_bytes
        metrics_mod.observe_kernel_cost(
            lane, launches, h2d_bytes, d2h_bytes, pad_waste)

    def observe_launch(self, lane: str, launches: int = 1,
                       h2d_bytes: int = 0, d2h_bytes: int = 0) -> None:
        """Count device launches + bytes at the dispatch site WITHOUT
        folding a batch (the mesh shard-step hook: the batch itself folds
        once at the cut via ``observe``)."""
        with self._lock:
            lc = self._lanes.get(lane)
            if lc is None:
                lc = self._lanes[lane] = _LaneCost()
            lc.launches += launches
            lc.h2d_bytes += h2d_bytes
            lc.d2h_bytes += d2h_bytes
        metrics_mod.observe_kernel_cost(lane, launches, h2d_bytes,
                                        d2h_bytes, 0)

    def observe_telemetry_fold(self, lane: str) -> None:
        """One fold of the lane's kept cuts into the heat map, the tenant
        plane and the batch series (the native lane folds the telemetry of
        many completed cuts at once): ``batches`` / ``telemetry_folds`` is
        the cuts a fold."""
        with self._lock:
            lc = self._lanes.get(lane)
            if lc is None:
                lc = self._lanes[lane] = _LaneCost()
            lc.telemetry_folds += 1

    def observe_resolved(self, lane: str) -> None:
        """One cut completed by the native lane's one-call resolve
        (``fe_resolve_cut``: decode, fan-out, completion and the cache's
        commit outside the interpreter lock): ``resolved_native`` /
        ``batches`` is the share of cuts that took it; the mesh step,
        degraded, brownout and host-lane cuts do not."""
        with self._lock:
            lc = self._lanes.get(lane)
            if lc is None:
                lc = self._lanes[lane] = _LaneCost()
            lc.resolved_native += 1

    def snapshot(self, lane: str) -> Dict[str, Any]:
        """One lane's raw counters (zeros if the lane never folded) —
        tests delta two snapshots around a dispatch to pin exact counts."""
        with self._lock:
            lc = self._lanes.get(lane)
            return lc.to_json() if lc is not None else _LaneCost().to_json()

    def to_json(self) -> Dict[str, Any]:
        with self._lock:
            return {lane: lc.to_json()
                    for lane, lc in sorted(self._lanes.items())}

    def reset_for_tests(self) -> None:
        with self._lock:
            self._lanes.clear()


LEDGER = CostLedger()


# ---------------------------------------------------------------------------
# Static cost analysis at reconcile.
# ---------------------------------------------------------------------------

# (entry, pad, eff, params fingerprint) -> (flops, bytes_accessed).
# Process-wide on purpose: jax's AOT lowering cache makes a repeat
# lower().compile() ~1ms, but the memo keeps even that (and the throwaway
# operand build) off the reconcile path for unchanged shapes.
_ANALYSIS_MEMO: Dict[tuple, Tuple[float, float]] = {}


def params_fingerprint(params: Any) -> tuple:
    """Hashable (shape, dtype) tree fingerprint of a params pytree — the
    memo key axis that changes exactly when the compiled corpus's operand
    shapes change (recompiles that keep shapes hit the memo)."""
    import jax

    leaves = jax.tree_util.tree_leaves(params)
    return tuple(
        (tuple(getattr(a, "shape", ())), str(getattr(a, "dtype", type(a))))
        for a in leaves)


def _cost_numbers(compiled) -> Tuple[float, float]:
    """(flops, bytes accessed) out of a Compiled.cost_analysis() result,
    tolerant of the backend returning a dict OR a list of per-module
    dicts, with missing keys reading 0 (CPU backends fill both today)."""
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    if not hasattr(ca, "get"):
        return 0.0, 0.0
    return (float(ca.get("flops", 0.0) or 0.0),
            float(ca.get("bytes accessed", 0.0) or 0.0))


def launch_temp_bytes(fn, *args) -> int:
    """Bytes of temporaries one launch of jitted ``fn`` allocates at these
    operands, from the compiled entry's ``memory_analysis()``; 0 where the
    backend gives none.  The lowering it compiles is the one the jitted
    call then finds compiled."""
    try:
        analysis = fn.lower(*args).compile().memory_analysis()
        return int(getattr(analysis, "temp_size_in_bytes", 0) or 0)
    except Exception as e:  # pragma: no cover - backend-dependent
        log.debug("memory_analysis unavailable: %r", e)
        return 0


def modeled_entry_cost(entry: str, fn, args: tuple, pad: int,
                       fingerprint: tuple,
                       eff: int = 0) -> Optional[Dict[str, Any]]:
    """XLA-modeled cost of one jit entry point at one (pad, eff) shape:
    {flops, bytes_accessed, flops_per_row, bytes_per_row, pad, eff}.
    Memoized by (entry, pad, eff, fingerprint); returns None when the
    backend cannot lower/analyze (advisory plane — never raises)."""
    key = (entry, pad, eff, fingerprint)
    hit = _ANALYSIS_MEMO.get(key)
    if hit is None:
        try:
            flops, bytes_acc = _cost_numbers(fn.lower(*args).compile())
        except Exception as e:  # pragma: no cover - backend-dependent
            log.debug("cost_analysis unavailable for %s: %r", entry, e)
            return None
        _ANALYSIS_MEMO[key] = hit = (flops, bytes_acc)
    flops, bytes_acc = hit
    return {
        "entry": entry, "pad": pad, "eff": eff,
        "flops": flops, "bytes_accessed": bytes_acc,
        "flops_per_row": round(flops / pad, 2) if pad else 0.0,
        "bytes_per_row": round(bytes_acc / pad, 2) if pad else 0.0,
    }


def _bitpacked_zero_args(policy, params, pad: int, eff: int) -> tuple:
    """Throwaway zero operands for eval_bitpacked_jit at one (pad, eff)
    bucket, shapes only (PR 14 operand tail rides on the params'
    structural Nones): what the cost model lowers it with, and what the
    native lane's host twin warms its CPU variants with."""
    import jax.numpy as jnp
    import numpy as np

    from ..compiler.intern import PAD
    from ..compiler.pack import wire_dtype

    dt = wire_dtype(policy)
    A, M, K = policy.n_attrs, policy.n_member_attrs, policy.members_k
    C = policy.n_own_cpu
    NB = max(policy.n_byte_attrs, 1)
    return (
        params,
        jnp.asarray(np.zeros((pad, A), dtype=dt)),
        jnp.asarray(np.full((pad, M, K), PAD, dtype=dt)),
        jnp.asarray(np.zeros((pad, C), dtype=bool)),
        jnp.asarray(np.zeros((pad,), dtype=np.int32)),
        jnp.asarray(np.zeros((pad, NB, eff), dtype=np.uint8)) if eff else None,
        jnp.asarray(np.zeros((pad, NB), dtype=bool)) if eff else None,
    )


def entry_points(policy=None, sharded=None) -> List[Dict[str, Any]]:
    """Enumerate the jit entry points a serving snapshot can dispatch
    through, with the operand lanes each one stages — the warm-grid audit
    surface (ISSUE 16 satellite: PR 1's grid predates the bitpacked/fused
    readback and the PR 14 relations operands; this list is what tests
    pin so the surface cannot drift again)."""
    base = ["attrs_val", "members_c", "cpu_dense", "config_id"]

    def _operands(pol) -> List[str]:
        ops = list(base)
        if pol is not None:
            if getattr(pol, "n_byte_attrs", 0):
                ops += ["attr_bytes", "byte_ovf"]  # device regex (DFA) lane
            if getattr(pol, "n_num_attrs", 0):
                ops += ["attrs_num", "num_valid"]  # PR 14 numeric lane
            if getattr(pol, "rel_bits", None) is not None:
                ops += ["rel_rows"]                # PR 14 relation lane
            if getattr(pol, "ovf_assist", False):
                ops += ["member_ovf"]              # PR 14 overflow assist
        return ops

    out: List[Dict[str, Any]] = []
    if sharded is not None:
        p0 = sharded.shards[0]
        out.append({
            "entry": "sharded_step",
            "kind": "collective (one launch per shard-step, psum-merged)",
            "operands": _operands(p0),
            "n_shards": int(sharded.n_shards),
        })
    elif policy is not None:
        ops = _operands(policy)
        out.append({
            "entry": "eval_bitpacked",
            "kind": "single-corpus bitpacked readback [pad, W] uint8",
            "operands": ops,
        })
        out.append({
            "entry": "eval_fused",
            "kind": "the engine lane's entry: one fused H2D staging "
                    "buffer (same compute as eval_bitpacked)",
            "operands": ops,
        })
        out.append({
            "entry": "eval_bitpacked_staged",
            "kind": "the native lane's served entry: one staged uint8 "
                    "buffer decoded on the device (same compute as "
                    "eval_bitpacked, the host twin's entry)",
            "operands": ops,
        })
    return out


class CostModel:
    """Per-component (engine / native frontend) modeled-cost lineage:
    one record per snapshot generation, compared against the previous
    one at reconcile time."""

    HISTORY = 8

    def __init__(self, component: str) -> None:
        self.component = component
        self._lock = threading.Lock()
        self._history: List[Dict[str, Any]] = []

    # -- recording ------------------------------------------------------
    def analyze(self, generation: int, *, policy=None, params=None,
                sharded=None, pad: int = 16, recorder=None) -> Dict[str, Any]:
        """Model the serving snapshot's kernel cost and diff it against
        the previous generation.  Advisory end to end: any failure
        degrades to an empty record, never blocks the swap."""
        with self._lock:
            if self._history and \
                    self._history[-1]["generation"] == int(generation):
                # canary promote re-installs the same generation: one
                # record per generation, not one per install
                return self._history[-1]
        entries: Dict[str, Dict[str, Any]] = {}
        try:
            entries = self._model_entries(policy=policy, params=params,
                                          sharded=sharded, pad=pad)
        except Exception:  # pragma: no cover - advisory plane
            log.exception("kernel cost analysis failed (advisory)")
        rec: Dict[str, Any] = {"generation": int(generation),
                               "entries": entries, "regressions": []}
        with self._lock:
            prev = self._history[-1] if self._history else None
            if prev is not None:
                rec["regressions"] = self._diff(prev, rec)
            self._history.append(rec)
            del self._history[:-self.HISTORY]
        for name, e in entries.items():
            metrics_mod.kernel_modeled_flops_per_row.labels(name).set(
                e["flops_per_row"])
        if rec["regressions"] and recorder is not None:
            try:
                recorder.record(
                    "cost-regression", lane=self.component,
                    detail={"generation": int(generation),
                            "regressions": rec["regressions"]},
                    anomaly=True)
            except Exception:  # pragma: no cover
                log.exception("cost-regression record failed")
        return rec

    def _model_entries(self, *, policy, params, sharded,
                       pad: int) -> Dict[str, Dict[str, Any]]:
        if sharded is not None:
            # the mesh step's shard_map lowering is mesh-bound state; model
            # the per-shard compute via the stacked single-device kernel
            # shapes instead (same per-row compute, collective excluded)
            return {}
        if policy is None or params is None:
            return {}
        from ..ops.pattern_eval import eval_bitpacked_jit, has_dfa

        # the widest byte bucket a launch of this corpus can take
        eff = policy.byte_width if has_dfa(params) else 0
        fp = params_fingerprint(params)
        args = _bitpacked_zero_args(policy, params, pad, eff)
        cost = modeled_entry_cost("eval_bitpacked", eval_bitpacked_jit,
                                  args, pad, fp, eff=eff)
        return {"eval_bitpacked": cost} if cost is not None else {}

    @staticmethod
    def _diff(prev: Dict[str, Any], cur: Dict[str, Any]) -> List[Dict[str, Any]]:
        out: List[Dict[str, Any]] = []
        for name, e in cur["entries"].items():
            pe = prev["entries"].get(name)
            if pe is None:
                continue
            for axis in ("flops_per_row", "bytes_per_row"):
                base, now = pe.get(axis, 0.0), e.get(axis, 0.0)
                if base > 0 and now >= REGRESSION_FACTOR * base:
                    out.append({
                        "entry": name, "axis": axis,
                        "previous": base, "current": now,
                        "ratio": round(now / base, 2),
                        "previous_generation": prev["generation"],
                    })
        return out

    # -- surfaces -------------------------------------------------------
    def to_json(self) -> Dict[str, Any]:
        with self._lock:
            cur = self._history[-1] if self._history else None
            regressed = [r for rec in self._history
                         for r in rec["regressions"]]
            return {
                "component": self.component,
                "generations_analyzed": len(self._history),
                "current": cur,
                "regressions_seen": len(regressed),
                "last_regression": regressed[-1] if regressed else None,
            }
