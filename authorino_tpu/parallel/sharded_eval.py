"""Tensor-parallel (rules-axis) + data-parallel (batch-axis) policy
evaluation over a jax.sharding.Mesh.

The reference scales horizontally by label-selector sharding of AuthConfigs
across replicas (ref: controllers/label_selector.go:14-45,
docs/user-guides/sharding.md).  The TPU-era equivalent (SURVEY.md §2 P3):
partition the *config axis* of the rule corpus across mesh shards — each
shard holds the full boolean circuit of its configs, so the tree reduction
stays shard-local and the only cross-shard communication is the final
verdict gather, which XLA lays onto ICI.

Layout:
  - configs are round-robined into ``mp`` groups; each group compiles as its
    own sub-corpus against a shared interner, with ShapeTargets forcing
    identical operand shapes (incl. DFA row/state/byte-slot axes, so the
    device regex lane rides the mesh too); arrays stack on a leading [S] axis
  - mesh ('dp', 'mp'): batch is sharded over dp, the [S] corpus axis over mp
  - shard_map evaluates each (dp, mp) block locally → verdict [B, S*G] plus
    per-evaluator rule/skipped [B, S*G, E] — the same outputs as the
    single-corpus ``eval_full_jit``, so PolicyEngine can serve from a
    sharded snapshot when more than one device is present

Mesh as the first-class lane (ISSUE 11):

  - **grid relief**: each mp shard compiles only its sub-corpus, so its
    member-attr grid M is ~1/mp of the monolithic corpus — the per-device
    membership payload budget (M × K) supports a proportionally LARGER
    compact K.  ``members_k`` is boosted to ``min(members_k * mp,
    max(members_k, MEMBERS_K_RELIEF_CAP))``: requests whose role lists
    overflowed the single-corpus K (the ``cpu-grid-overflow`` host-oracle
    rows) ride the kernel when the corpus is rule-sharded across ≥2
    devices.
  - **two-phase staging**: ``defer_upload=True`` compiles and stacks the
    operands HOST-side only; ``upload()`` stages them onto the mesh.  The
    engine's --strict-verify lints the packed shards between the two — a
    corrupt corpus is rejected before any byte touches a device, matching
    the single-corpus ordering (the PR 4 caveat, fixed).
  - **per-shard delta uploads**: ``upload(prev=...)`` diffs the stacked
    host views; the leading axis of every stacked leaf IS the shard axis,
    so ``plan_delta``'s changed-leading-rows mode ships bytes only to the
    shard(s) a mutation touched (measured per shard in
    auth_server_mesh_shard_upload_bytes).
  - **per-device failover**: ``dispatch_routed`` probes the fault plane per
    device, keeps per-DEVICE circuit breakers (runtime/breaker.py
    DeviceBreakerSet, process-wide per mesh so state survives reconciles),
    and re-dispatches a batch that failed on one device to the healthy
    device with the emptiest in-flight window (occupancy-aware routing) —
    host-oracle degrade only begins once EVERY device is down
    (MeshUnavailable).
"""

from __future__ import annotations

import logging
import threading
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..compiler.compile import (
    CompiledPolicy,
    ConfigRules,
    ShapeTargets,
    compile_corpus,
)
from ..compiler.encode import encode_batch
from ..compiler.intern import StringInterner
from ..ops.pattern_eval import (
    _bitpack_rows,
    eval_verdicts,
    packed_width,
    to_device,
    unpack_verdicts,
)
from ..runtime.kernel_cost import LEDGER

__all__ = ["ShardedPolicyModel", "build_mesh", "MeshUnavailable",
           "MEMBERS_K_RELIEF_CAP", "flat_config_rows"]


def flat_config_rows(shards, rows, configs_per_shard):
    """Flatten mesh (shard, row) config coordinates to the single flat row
    key the heat map, the per-authconfig telemetry bins and the tenant QoS
    folds (ISSUE 15) all share: ``shard * configs_per_shard + row``.  One
    vectorized expression — callers pass whole batch arrays."""
    import numpy as _np

    return (_np.asarray(shards, dtype=_np.int64) * int(configs_per_shard)
            + _np.asarray(rows, dtype=_np.int64))

log = logging.getLogger("authorino_tpu.sharded_eval")

# grid relief ceiling: rule-sharding shrinks each shard's member-attr grid
# ~1/mp, so the compact membership K can grow ~mp× inside the same
# per-device payload budget.  64 covers any operationally plausible role
# list; beyond it the host-fallback lane remains (exactness is never K's
# job — K only decides which rows ride the kernel).
MEMBERS_K_RELIEF_CAP = 64


class MeshUnavailable(RuntimeError):
    """Every mesh device is down (breakers open / probes failing): the
    caller's host-oracle degrade path is the only lane left."""


# jitted sharded steps cached per (mesh, lane flags, n_levels):
# reconcile-time apply_snapshot builds a fresh ShardedPolicyModel, and a
# per-model jax.jit(shard_map(...)) closure would force a full XLA recompile
# on every snapshot even at unchanged shapes — the sharded analog of the
# module-level eval_packed_jit cache on the single-corpus path.  The flags
# pin the params/specs pytree STRUCTURE (lane presence changes it), so a
# gather-lane model can never reuse a matmul-traced step.  ``extras`` is
# the (has_num, has_rel, has_ovf) tuple of the ISSUE 14 operand lanes.
_STEP_CACHE: Dict[Tuple[Mesh, bool, bool, int, tuple], Any] = {}


def _sharded_step(mesh: Mesh, has_dfa: bool, has_matmul: bool, n_levels: int,
                  specs,
                  extras: tuple = (False, False, False)):
    """Own-config evaluation step over the mesh: each mp shard evaluates its
    sub-corpus, selects the rows of requests whose config it owns, and the
    tiny [B], [B, E] results combine with one psum over 'mp' — so the
    device→host readback is own-rows only, never the [B, S*G(, E)] matrices
    (the sharded analog of eval_packed_jit's one-small-readback contract).
    ``specs`` mirrors the stacked-params structure (P('mp') on every leaf);
    the cache key's flags pin that structure."""
    has_num, has_rel, has_ovf = extras
    key = (mesh, has_dfa, has_matmul, n_levels, extras)
    step = _STEP_CACHE.get(key)
    if step is not None:
        return step

    def local_eval(params, attrs_val, members_c, cpu_dense,
                   attr_bytes, byte_ovf, attrs_num, num_valid, rel_rows,
                   member_ovf, shard_of, row_of):
        # params leading axis is the local S slice (size 1 per mp shard)
        sq = jax.tree_util.tree_map(lambda a: a[0], params)
        mp_idx = jax.lax.axis_index("mp")
        verdict, (rule, skipped) = eval_verdicts(
            sq,
            attrs_val[:, 0],
            members_c[:, 0],
            cpu_dense[:, 0],
            # whose CPU columns the slice carries: the owning shard's row,
            # nobody's elsewhere (the slice is the empty encoding there)
            jnp.where(shard_of == mp_idx, row_of, -1),
            attr_bytes[:, 0] if has_dfa else None,
            byte_ovf[:, 0] if has_dfa else None,
            attrs_num[:, 0] if has_num else None,
            num_valid[:, 0] if has_num else None,
            rel_rows[:, 0] if has_rel else None,
            member_ovf[:, 0] if has_ovf else None,
        )
        # own-config one-hot rows local to this shard (other shards see all-
        # False masks for the request); psum over mp merges the disjoint parts
        G = verdict.shape[1]
        mask = (shard_of == mp_idx)[:, None] & (
            row_of[:, None] == jnp.arange(G, dtype=row_of.dtype)[None, :]
        )                                                        # [B_l, G]
        own = jnp.any(verdict & mask, axis=1)
        own_rule = jnp.any(rule & mask[:, :, None], axis=1)      # [B_l, E]
        own_skip = jnp.any(skipped & mask[:, :, None], axis=1)
        merged = jax.lax.psum(
            jnp.concatenate(
                [own[:, None], own_rule, own_skip], axis=1
            ).astype(jnp.int32),
            "mp",
        )
        return merged > 0                                        # [B_l, 1+2E]

    byte_specs = (
        (P("dp", "mp", None, None), P("dp", "mp", None))
        if has_dfa
        else (None, None)
    )
    num_specs = ((P("dp", "mp", None), P("dp", "mp", None))
                 if has_num else (None, None))
    rel_specs = ((P("dp", "mp", None),) if has_rel else (None,))
    ovf_specs = ((P("dp", "mp", None),) if has_ovf else (None,))
    mapped = jax.shard_map(
        local_eval,
        mesh=mesh,
        in_specs=(
            specs,
            P("dp", "mp", None),
            P("dp", "mp", None, None),
            P("dp", "mp", None),
        ) + byte_specs + num_specs + rel_specs + ovf_specs
        + (P("dp"), P("dp")),
        out_specs=P("dp"),
    )

    def bitpacked_step(params, *operands):
        # D2H readback rides the link as a u8 bitmask (8 verdicts/byte —
        # decode host-side with ops.pattern_eval.unpack_verdicts), same
        # packed-readback contract as the single-corpus eval_fused_jit
        return _bitpack_rows(mapped(params, *operands))

    step = jax.jit(bitpacked_step)
    _STEP_CACHE[key] = step
    return step


def _eval_stacked(params, attrs_val, members_c, cpu_dense,
                  attr_bytes, byte_ovf, attrs_num, num_valid, rel_rows,
                  member_ovf, shard_of, row_of):
    """Single-DEVICE evaluation of the whole stacked corpus — the failover
    twin of the shard_map step: vmap over the [S] shard axis replaces the
    mesh partition, the own-config mask-reduce replaces the psum.  Same
    operands, same bit-packed [B, ceil((1+2E)/8)] readback, bit-identical
    verdicts (the kernel is a pure per-row function and vmap is exact)."""
    def per_shard(sq, av, mc, cd, cfg, ab, bo, an, nv, rr, mo):
        verdict, (rule, skipped) = eval_verdicts(
            sq, av, mc, cd, cfg, ab, bo, an, nv, rr, mo)
        return verdict, rule, skipped

    n_shards = attrs_val.shape[1]
    # whose CPU columns each shard's slice carries (see local_eval)
    cfg = jnp.where(
        shard_of[None, :] == jnp.arange(n_shards, dtype=shard_of.dtype)[:, None],
        row_of[None, :], -1)                                     # [S, B]
    ops = [jnp.moveaxis(attrs_val, 1, 0), jnp.moveaxis(members_c, 1, 0),
           jnp.moveaxis(cpu_dense, 1, 0), cfg]
    axes = [0, 0, 0, 0, 0]
    for a in (attr_bytes, byte_ovf, attrs_num, num_valid, rel_rows,
              member_ovf):
        if a is not None:
            ops.append(jnp.moveaxis(a, 1, 0))
            axes.append(0)
        else:
            ops.append(None)
            axes.append(None)
    verdict, rule, skipped = jax.vmap(per_shard, in_axes=tuple(axes))(
        params, *ops)
    S, _, G = verdict.shape
    own_mask = (
        (shard_of[None, :, None]
         == jnp.arange(S, dtype=shard_of.dtype)[:, None, None])
        & (row_of[None, :, None]
           == jnp.arange(G, dtype=row_of.dtype)[None, None, :])
    )                                                            # [S, B, G]
    own = jnp.any(verdict & own_mask, axis=(0, 2))
    own_rule = jnp.any(rule & own_mask[..., None], axis=(0, 2))
    own_skip = jnp.any(skipped & own_mask[..., None], axis=(0, 2))
    return _bitpack_rows(
        jnp.concatenate([own[:, None], own_rule, own_skip], axis=1))


_EVAL_STACKED_JIT = jax.jit(_eval_stacked)


def build_mesh(n_devices: Optional[int] = None, dp: Optional[int] = None) -> Mesh:
    devices = np.asarray(jax.devices()[: n_devices or len(jax.devices())])
    n = devices.size
    if dp is None:
        dp = 2 if n % 2 == 0 and n > 1 else 1
    mp = n // dp
    return Mesh(devices[: dp * mp].reshape(dp, mp), ("dp", "mp"))


# ---------------------------------------------------------------------------
# per-mesh routing state: device breakers, occupancy, failover evidence.
# Process-wide keyed by the Mesh object (the engine resolves ONE mesh and
# reuses it across reconciles), so device health and in-flight occupancy
# survive snapshot swaps — a device is sick or busy, not a snapshot.
# ---------------------------------------------------------------------------

_MESH_STATE: Dict[Mesh, "MeshState"] = {}
_MESH_STATE_LOCK = threading.Lock()


class MeshState:
    def __init__(self, mesh: Mesh, threshold: int = 3, reset_s: float = 5.0):
        from ..runtime.breaker import DeviceBreakerSet

        self.device_ids = [int(d.id) for d in mesh.devices.flat]
        self.breakers = DeviceBreakerSet("mesh", self.device_ids,
                                         threshold=threshold, reset_s=reset_s)
        self.lock = threading.Lock()
        # Serializes the ENQUEUE of collective-bearing (psum) programs:
        # concurrent shard_map launches from different dispatcher threads
        # can interleave the per-device execution queues in inconsistent
        # order, deadlocking the cross-device rendezvous (observed as stuck
        # AllReduce participants on forced-host CPU devices; the same
        # cross-thread enqueue race exists on real chips).  Only the
        # dispatch call is held — execution and readback stay async, so
        # pipelining is unaffected.
        self.launch_lock = threading.Lock()
        self.occupancy: Dict[int, int] = {d: 0 for d in self.device_ids}
        self.occupancy_peak: Dict[int, int] = {d: 0 for d in self.device_ids}
        self.launches: Dict[int, int] = {d: 0 for d in self.device_ids}
        self.failovers: Dict[int, int] = {d: 0 for d in self.device_ids}

    def acquire(self, model: "ShardedPolicyModel", devices: List[int]
                ) -> "MeshRoute":
        from ..utils import metrics as metrics_mod

        with self.lock:
            for d in devices:
                n = self.occupancy[d] = self.occupancy.get(d, 0) + 1
                if n > self.occupancy_peak.get(d, 0):
                    self.occupancy_peak[d] = n
                metrics_mod.mesh_shard_occupancy.labels(str(d)).set(n)
        return MeshRoute(self, devices)

    def count_launch(self, devices: List[int]) -> None:
        """One serving launch that ran on ``devices`` (counted where the
        launch happens, so every lane that dispatches shows up — the
        engine's routed launches and the native frontend's alike)."""
        with self.lock:
            for d in devices:
                self.launches[d] = self.launches.get(d, 0) + 1

    def release(self, devices: List[int]) -> None:
        from ..utils import metrics as metrics_mod

        with self.lock:
            for d in devices:
                n = self.occupancy[d] = max(0, self.occupancy.get(d, 0) - 1)
                metrics_mod.mesh_shard_occupancy.labels(str(d)).set(n)

    def device_failed(self, device_id: int, lane: str,
                      failover: bool = True) -> None:
        """Breaker + evidence fold for one attributed device failure.
        ``failover=True`` (dispatch-time: the batch re-dispatches elsewhere
        right now) also counts auth_server_device_failover_total; readback/
        watchdog failures reported via ``complete_route`` pass False — they
        feed the breaker, but whether the RETRY resolves on a device or
        degrades is the engine's story, not this counter's."""
        from ..runtime.flight_recorder import RECORDER
        from ..utils import metrics as metrics_mod

        self.breakers.record_failure(device_id)
        if failover:
            metrics_mod.device_failover.labels(str(device_id)).inc()
            with self.lock:
                self.failovers[device_id] = self.failovers.get(device_id, 0) + 1
        RECORDER.record("device-failover", lane=lane,
                        detail={"device": device_id})

    def to_json(self) -> Dict[str, Any]:
        with self.lock:
            return {
                "devices": list(self.device_ids),
                "occupancy": {str(d): n for d, n in self.occupancy.items()},
                "occupancy_peak": {str(d): n
                                   for d, n in self.occupancy_peak.items()},
                "launches": {str(d): n for d, n in self.launches.items()},
                "failovers": {str(d): n for d, n in self.failovers.items()},
                "breakers": self.breakers.to_json(),
            }


def _mesh_state(mesh: Mesh, threshold: int = 3,
                reset_s: float = 5.0) -> MeshState:
    """One MeshState per mesh, process-wide.  The breaker knobs apply only
    at CREATION (device health outlives snapshots by design, so the first
    engine to touch a mesh fixes its per-device breaker tuning)."""
    state = _MESH_STATE.get(mesh)
    if state is None:
        with _MESH_STATE_LOCK:
            state = _MESH_STATE.get(mesh)
            if state is None:
                state = _MESH_STATE[mesh] = MeshState(
                    mesh, threshold=threshold, reset_s=reset_s)
    return state


def _reset_mesh_state_for_tests() -> None:
    """Drop all per-mesh routing state (breakers, occupancy, failover
    evidence).  Tests only: equal meshes share one MeshState by design (a
    device's health outlives snapshots), so a fault-injection test must not
    leak open breakers into its neighbours."""
    with _MESH_STATE_LOCK:
        _MESH_STATE.clear()


class MeshRoute:
    """One launched batch's claim on its device windows: which devices it
    occupies and the idempotent release.  The engine releases on terminal
    completion (success, degrade, watchdog) and records the per-device
    breaker outcome via ``ShardedPolicyModel.complete_route``."""

    __slots__ = ("state", "devices", "_done")

    def __init__(self, state: MeshState, devices: List[int]):
        self.state = state
        self.devices = list(devices)
        self._done = False

    def release(self) -> None:
        if not self._done:
            self._done = True
            self.state.release(self.devices)


@dataclass
class _ShardedEncoded:
    attrs_val: np.ndarray      # [B, S, A]
    members_c: np.ndarray      # [B, S, M, K] — compact membership rows
    cpu_dense: np.ndarray      # [B, S, c_own] — own config's CPU-lane columns
    attr_bytes: Optional[np.ndarray]  # [B, S, NB, LB] uint8 (None: no DFA lane)
    byte_ovf: Optional[np.ndarray]    # [B, S, NB] bool
    shard_of: np.ndarray       # [B] which shard owns the request's config
    row_of: np.ndarray         # [B] row within that shard
    host_fallback: np.ndarray  # [B] bool — exact re-decision on host
    # ISSUE 14 lanes (None when the stacked corpus lacks them)
    attrs_num: Optional[np.ndarray] = None   # [B, S, NN] int32
    num_valid: Optional[np.ndarray] = None   # [B, S, NN] bool
    rel_rows: Optional[np.ndarray] = None    # [B, S, NR] int32
    member_ovf: Optional[np.ndarray] = None  # [B, S, M] bool

class ShardedPolicyModel:
    """Rule corpus partitioned over the 'mp' mesh axis; batch over 'dp'.

    Two-phase: the constructor compiles the shards and stacks every operand
    HOST-side (``host_view``); ``upload()`` stages them onto the mesh (one
    mesh-sharded device_put per leaf, or a per-shard delta against a
    previous model).  ``defer_upload=True`` stops after the host phase so a
    strict-verify lint can gate the upload (ISSUE 11 satellite — the
    single-corpus path's lint-before-upload ordering, restored here)."""

    def __init__(self, configs: Sequence[ConfigRules], mesh: Mesh,
                 members_k: int = 16, interner: Optional[StringInterner] = None,
                 defer_upload: bool = False, grid_relief: bool = True,
                 breaker_threshold: int = 3, breaker_reset_s: float = 5.0,
                 ovf_assist: Optional[bool] = None):
        self.mesh = mesh
        S = mesh.shape["mp"]
        self.n_shards = S
        self.members_k = members_k  # requested (single-corpus-equivalent) K
        # grid relief (ISSUE 11): each shard's member grid is ~1/mp of the
        # monolithic corpus, so the same per-device payload budget funds a
        # ~mp× larger compact K — single-corpus membership-overflow rows
        # (the cpu-grid-overflow host-oracle caveat) ride the kernel here
        if grid_relief and S > 1:
            self.members_k_eff = min(members_k * S,
                                     max(members_k, MEMBERS_K_RELIEF_CAP))
        else:
            self.members_k_eff = members_k
        self.interner = interner if interner is not None else StringInterner()
        groups: List[List[ConfigRules]] = [[] for _ in range(S)]
        self.locator: Dict[str, Tuple[int, int]] = {}
        for i, cfg in enumerate(configs):
            shard = i % S
            self.locator[cfg.name] = (shard, len(groups[shard]))
            groups[shard].append(cfg)

        # two-pass compile: natural shapes → union targets → final compile.
        # The union carries the DFA row/state/byte axes, so shards with
        # regexes stack their device-DFA tables and regex-free shards carry
        # a dummy lane of the same shape.  One dfa_cache spans both passes
        # and all shards: each distinct regex determinizes exactly once.
        dfa_cache: Dict[str, Any] = {}
        k = self.members_k_eff
        first = [
            compile_corpus(g, members_k=k, interner=self.interner,
                           dfa_cache=dfa_cache, ovf_assist=ovf_assist)
            for g in groups
        ]
        targets = ShapeTargets.union([p.shape_targets() for p in first])
        self.shards: List[CompiledPolicy] = [
            compile_corpus(g, members_k=k, interner=self.interner,
                           targets=targets, dfa_cache=dfa_cache,
                           ovf_assist=ovf_assist)
            for g in groups
        ]
        self.has_dfa = self.shards[0].n_byte_attrs > 0
        # ISSUE 14 lane flags: structural across shards (ShapeTargets union)
        self.has_num = int(getattr(self.shards[0], "n_num_attrs", 0)) > 0
        self.has_rel = int(getattr(self.shards[0], "n_rel_slots", 0)) > 0
        self.has_ovf = bool(getattr(self.shards[0], "ovf_assist", False))
        # targets unified every operand shape (incl. eval-table rows), so
        # the whole per-shard device pytree — gather lane, matmul lane, DFA
        # lane — stacks on a leading [S] axis with one tree.map
        self.configs_per_shard = self.shards[0].n_configs
        # [S, G] verdict-cache eligibility, indexed (shard_of, row_of) by
        # the engine's dedup/cache encode stage
        self.config_cacheable = np.stack(
            [p.config_cacheable for p in self.shards])
        # host-side staging: stack numpy operands; upload() ships each
        # shard's slice straight to its devices via ONE mesh-sharded
        # device_put per leaf (no transient 2-3x corpus copy on device 0).
        # The stacked view is retained: the next reconcile diffs against it
        # for the per-shard delta upload, and the failover path device_puts
        # it onto a single healthy device.
        per_shard_params = [to_device(p, host=True, dense=True)
                            for p in self.shards]
        self.host_view = jax.tree.map(
            lambda *xs: np.stack(xs), *per_shard_params
        )
        self.has_matmul = self.host_view.get("matmul") is not None
        self.params = None            # set by upload()
        self.upload_report: Optional[Dict[str, Any]] = None
        self._step = None
        # routing state is per-MESH (process-wide): device health and
        # occupancy survive reconciles (first creator's breaker knobs win)
        self.state = _mesh_state(mesh, threshold=breaker_threshold,
                                 reset_s=breaker_reset_s)
        self._dev_by_id = {int(d.id): d for d in mesh.devices.flat}
        self._device_params: Dict[int, Any] = {}  # failover staging cache
        self._device_params_lock = threading.Lock()
        if not defer_upload:
            self.upload()

    # ---- staging ----------------------------------------------------------

    def upload(self, prev: "Optional[ShardedPolicyModel]" = None
               ) -> Dict[str, Any]:
        """Stage the stacked host operands onto the mesh.  With ``prev`` (a
        previously-uploaded model on the SAME mesh) a delta plan is
        computed between the stacked host views: the leading axis of every
        stacked leaf is the shard axis, so ``plan_delta``'s changed-rows
        mode ships bytes only to the shard(s) a reconcile touched —
        unchanged shards receive zero bytes (per-shard delta uploads,
        measured in auth_server_mesh_shard_upload_bytes{shard}).  Returns
        the upload report (also retained as ``self.upload_report``)."""
        from ..snapshots.diff import plan_delta
        from ..utils import metrics as metrics_mod

        specs = jax.tree.map(lambda _: P("mp"), self.host_view)
        sharding = jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), specs)
        plan = None
        if (prev is not None and prev.mesh is self.mesh
                and prev.params is not None and prev.host_view is not None):
            # rows_win_factor=1.0: the leading axis is the shard axis, so
            # any strict row subset confines traffic to the owning shards
            plan = plan_delta(prev.host_view, self.host_view,
                              rows_win_factor=1.0)
        S = self.n_shards
        per_shard = [0] * S
        if plan is None:
            self.params = jax.tree.map(
                lambda a, sh: jax.device_put(a, sh),
                self.host_view, sharding)
            total = 0

            def _count(a):
                nonlocal total
                arr = np.asarray(a)
                total += arr.nbytes
                for s in range(min(S, arr.shape[0] if arr.ndim else 0)):
                    per_shard[s] += arr[s].nbytes

            jax.tree.map(_count, self.host_view)
            report = {"mode": "full", "upload_bytes": total,
                      "full_bytes": total, "arrays_reused": 0,
                      "arrays_touched": []}
        else:
            by_name = {e.name: e for e in plan.entries}
            uploaded = 0

            def leaf(name, new_h, prev_d, sh):
                nonlocal uploaded
                e = by_name.get(name)
                new_h = np.asarray(new_h)
                if e is None or prev_d is None or e.mode == "full":
                    uploaded += new_h.nbytes
                    for s in range(min(S, new_h.shape[0])):
                        per_shard[s] += new_h[s].nbytes
                    return jax.device_put(new_h, sh)
                if e.mode == "reuse":
                    return prev_d
                # rows mode: the leading axis is the SHARD axis, so the
                # changed rows name exactly the shards whose slice this
                # reconcile rewrote.  Functional scatter: the previous
                # device buffers stay intact for in-flight batches; the
                # H2D traffic is the changed shard slices + indices.
                idx = e.rows
                uploaded += int(e.upload_bytes)
                for s in idx.tolist():
                    if s < S:
                        per_shard[s] += new_h[s].nbytes
                out = prev_d.at[jnp.asarray(idx)].set(
                    jnp.asarray(new_h[idx]))
                return jax.device_put(out, sh)

            def rebuild(prefix, new_v, prev_v, sh):
                if new_v is None:
                    return None
                if isinstance(new_v, dict):
                    pd = prev_v if isinstance(prev_v, dict) else {}
                    sd = sh if isinstance(sh, dict) else {}
                    return {k: rebuild(f"{prefix}.{k}" if prefix else str(k),
                                       new_v[k], pd.get(k), sd.get(k))
                            for k in new_v}
                if isinstance(new_v, (tuple, list)):
                    pt = prev_v if isinstance(prev_v, (tuple, list)) else ()
                    st = sh if isinstance(sh, (tuple, list)) else ()
                    return tuple(
                        rebuild(f"{prefix}.{i}", x,
                                pt[i] if i < len(pt) else None,
                                st[i] if i < len(st) else None)
                        for i, x in enumerate(new_v))
                return leaf(prefix, new_v, prev_v, sh)

            self.params = rebuild("", self.host_view, prev.params, sharding)
            report = dict(plan.to_json(), upload_bytes=uploaded)
        report["per_shard_bytes"] = {str(s): int(b)
                                     for s, b in enumerate(per_shard)}
        for s, b in enumerate(per_shard):
            if b:
                metrics_mod.mesh_shard_upload_bytes.labels(str(s)).inc(b)
        self.upload_report = report
        n_levels = len(self.shards[0].levels)
        self._step = _sharded_step(
            self.mesh, self.has_dfa, self.has_matmul, n_levels, specs,
            extras=(self.has_num, self.has_rel, self.has_ovf),
        )
        return report

    def cache_tokens(self, fingerprints: Dict[str, str]):
        """Per-shard per-row verdict-cache tokens: (encoding_epoch of the
        OWNING shard's compiled layout, the config's source fingerprint) —
        the mesh twin of the single-corpus snapshot tokens (ISSUE 11
        satellite: PR 8 parity).  Indexed [shard_of][row_of] by the
        engine's dedup/cache stage; entries of configs a reconcile did not
        touch keep their tokens (same interner ⇒ same epoch) and SURVIVE
        the swap."""
        from ..snapshots.fingerprint import cache_tokens as _tokens

        return [_tokens(p, fingerprints) for p in self.shards]

    # ------------------------------------------------------------------

    def encode(self, docs: Sequence[Any], config_names: Sequence[str], batch_pad: int = 0) -> _ShardedEncoded:
        from ..compiler.intern import EMPTY_ID, PAD
        from ..compiler.pack import pack_batch

        B = max(len(docs), 1)
        if batch_pad and batch_pad > B:
            B = batch_pad
        dp = self.mesh.shape["dp"]
        if B % dp:
            B += dp - B % dp
        S = self.n_shards
        p0 = self.shards[0]
        A, K = p0.n_attrs, p0.members_k
        M, C = p0.n_member_attrs, p0.n_own_cpu
        attrs_val = np.full((B, S, A), EMPTY_ID, dtype=np.int32)
        members_c = np.full((B, S, M, K), PAD, dtype=np.int32)
        cpu_dense = np.zeros((B, S, C), dtype=bool)
        if self.has_dfa:
            NB = p0.n_byte_attrs
            # shards compile under ShapeTargets: one class each at the floor
            # width, so this is DFA_VALUE_BYTES on every mesh corpus today
            attr_bytes = np.zeros(
                (B, S, NB, max(p.byte_width for p in self.shards)),
                dtype=np.uint8)
            byte_ovf = np.zeros((B, S, NB), dtype=bool)
        else:
            attr_bytes = byte_ovf = None
        if self.has_num:
            NN = p0.n_num_attrs
            attrs_num = np.zeros((B, S, NN), dtype=np.int32)
            num_valid = np.zeros((B, S, NN), dtype=bool)
        else:
            attrs_num = num_valid = None
        rel_rows = (np.zeros((B, S, p0.n_rel_slots), dtype=np.int32)
                    if self.has_rel else None)
        member_ovf = (np.zeros((B, S, M), dtype=bool)
                      if self.has_ovf else None)
        shard_of = np.zeros((B,), dtype=np.int32)
        row_of = np.zeros((B,), dtype=np.int32)
        host_fallback = np.zeros((B,), dtype=bool)
        # group requests by owning shard and encode each group in ONE
        # batched call (per-request encode_batch would dominate the hot path)
        by_shard: Dict[int, List[int]] = {}
        for r, (doc, name) in enumerate(zip(docs, config_names)):
            shard, row = self.locator[name]
            shard_of[r], row_of[r] = shard, row
            by_shard.setdefault(shard, []).append(r)
        for shard, rs in by_shard.items():
            enc = encode_batch(
                self.shards[shard],
                [docs[r] for r in rs],
                [int(row_of[r]) for r in rs],
            )
            db = pack_batch(self.shards[shard], enc, trim_bytes=False)
            attrs_val[rs, shard] = db.attrs_val[: len(rs)]
            members_c[rs, shard] = db.members_c[: len(rs)]
            cpu_dense[rs, shard] = db.cpu_dense[: len(rs)]
            if self.has_dfa:
                # per-shard batches may be byte-trimmed (pack._trim_bytes);
                # assign into the prefix, then trim the assembled tensor once
                lb = db.attr_bytes.shape[-1]
                attr_bytes[rs, shard, :, :lb] = db.attr_bytes[: len(rs)]
                byte_ovf[rs, shard] = db.byte_ovf[: len(rs)]
            if self.has_num:
                attrs_num[rs, shard] = db.attrs_num[: len(rs)]
                num_valid[rs, shard] = db.num_valid[: len(rs)]
            if self.has_rel:
                rel_rows[rs, shard] = db.rel_rows[: len(rs)]
            if self.has_ovf:
                member_ovf[rs, shard] = db.member_ovf[: len(rs)]
            host_fallback[rs] = db.host_fallback[: len(rs)]
        if self.has_dfa:
            from ..compiler.pack import _trim_bytes

            attr_bytes = _trim_bytes(attr_bytes)
        return _ShardedEncoded(
            attrs_val, members_c, cpu_dense, attr_bytes, byte_ovf,
            shard_of, row_of, host_fallback,
            attrs_num=attrs_num, num_valid=num_valid,
            rel_rows=rel_rows, member_ovf=member_ovf,
        )

    def row_keys(self, encoded: _ShardedEncoded, n: int):
        """Canonical per-row keys for dedup + the verdict cache: the full
        operand bytes plus shard_of/row_of (config identity on the mesh)
        and the lossy-row flag (compiler/pack.py row_key_bytes doc)."""
        from ..compiler.pack import row_key_bytes

        return row_key_bytes(
            [encoded.shard_of, encoded.row_of, encoded.attrs_val,
             encoded.members_c, encoded.cpu_dense, encoded.attr_bytes,
             encoded.byte_ovf, encoded.host_fallback, encoded.attrs_num,
             encoded.num_valid, encoded.rel_rows, encoded.member_ovf], n)

    def select_rows(self, encoded: _ShardedEncoded, rows: Sequence[int],
                    batch_pad: int = 0) -> _ShardedEncoded:
        """Row-subset view for dedup dispatch: the unique rows re-padded to
        ``batch_pad`` (dp-aligned like encode) by repeating the first row —
        padding rows' verdicts are discarded by the inverse fan-out."""
        u = len(rows)
        B = max(u, 1, batch_pad)
        dp = self.mesh.shape["dp"]
        if B % dp:
            B += dp - B % dp
        fill = rows[0] if u else 0
        idx = np.asarray(list(rows) + [fill] * (B - u))

        def take(a):
            return a[idx] if a is not None else None

        return _ShardedEncoded(
            take(encoded.attrs_val), take(encoded.members_c),
            take(encoded.cpu_dense), take(encoded.attr_bytes),
            take(encoded.byte_ovf), take(encoded.shard_of),
            take(encoded.row_of), take(encoded.host_fallback),
            attrs_num=take(encoded.attrs_num),
            num_valid=take(encoded.num_valid),
            rel_rows=take(encoded.rel_rows),
            member_ovf=take(encoded.member_ovf),
        )

    def launch(self, encoded: _ShardedEncoded):
        """Enqueue the shard_map step for one encoded batch and return the
        on-device bit-packed result — THE operand list of the step (absent
        lanes pass None, matching its in_specs), with no accounting: warm-up
        calls it with zero operands, ``dispatch_full`` wraps it for serving."""
        if self._step is None:
            raise RuntimeError(
                "ShardedPolicyModel not staged: call upload() after the "
                "deferred (strict-verify) construction")
        # launch_lock: enqueue-order consistency for the psum collective
        # (see MeshState) — held for the async dispatch only
        with self.state.launch_lock:
            packed = self._step(
                self.params,
                jnp.asarray(encoded.attrs_val),
                jnp.asarray(encoded.members_c),
                jnp.asarray(encoded.cpu_dense),
                jnp.asarray(encoded.attr_bytes) if self.has_dfa else None,
                jnp.asarray(encoded.byte_ovf) if self.has_dfa else None,
                jnp.asarray(encoded.attrs_num) if self.has_num else None,
                jnp.asarray(encoded.num_valid) if self.has_num else None,
                jnp.asarray(encoded.rel_rows) if self.has_rel else None,
                jnp.asarray(encoded.member_ovf) if self.has_ovf else None,
                jnp.asarray(encoded.shard_of),
                jnp.asarray(encoded.row_of),
            )
        return packed

    def dispatch_full(self, encoded: _ShardedEncoded):
        """Non-blocking serving launch: returns the ON-DEVICE packed
        own-rows result [B, 1+2E] (readback copy started eagerly), so the
        caller can keep further batches in flight while this one rides the
        link — the sharded mirror of the engine's pipelined dispatch
        window."""
        packed = self.launch(encoded)
        try:
            packed.copy_to_host_async()
        except Exception:
            pass  # readback degrades to a blocking copy at np.asarray time
        # ISSUE 16: ONE collective launch per shard-step — the psum merge
        # is part of the same program, so a 2x4 mesh still counts 1 here
        LEDGER.observe_launch("mesh", 1,
                              h2d_bytes=self._encoded_h2d_bytes(encoded),
                              d2h_bytes=self._d2h_bytes(encoded))
        self.state.count_launch(self.state.device_ids)
        return packed

    def _encoded_h2d_bytes(self, encoded: _ShardedEncoded) -> int:
        """Request-operand bytes one launch of ``encoded`` stages (every
        present operand incl. the shard_of/row_of routing rows) — pure
        shape arithmetic for the kernel-cost ledger."""
        total = 0
        for name in ("attrs_val", "members_c", "cpu_dense", "attr_bytes",
                     "byte_ovf", "attrs_num", "num_valid", "rel_rows",
                     "member_ovf", "shard_of", "row_of"):
            arr = getattr(encoded, name, None)
            if arr is not None:
                total += arr.nbytes
        return total

    def _d2h_bytes(self, encoded: _ShardedEncoded) -> int:
        """Readback bytes of one launch: the bitpacked [B, W] uint8
        own-rows result."""
        E = int(self.shards[0].eval_rule.shape[1])
        return int(encoded.attrs_val.shape[0]) * packed_width(1 + 2 * E)

    # ---- per-device failover (ISSUE 11) ----------------------------------

    def device_params(self, device_id: int):
        """The stacked corpus staged onto ONE device (failover lane),
        cached per device — built lazily the first time a device serves a
        failover batch, reused for the rest of the incident."""
        params = self._device_params.get(device_id)
        if params is None:
            with self._device_params_lock:
                params = self._device_params.get(device_id)
                if params is None:
                    device = self._dev_by_id[device_id]
                    params = jax.tree.map(
                        lambda a: jax.device_put(a, device), self.host_view)
                    self._device_params[device_id] = params
        return params

    def dispatch_on_device(self, encoded: _ShardedEncoded, device_id: int):
        """Single-device launch of one batch against the WHOLE stacked
        corpus (vmap over the shard axis replaces the mesh partition) —
        the failover lane when part of the mesh is down.  Same bit-packed
        own-rows readback as ``dispatch_full``."""
        device = self._dev_by_id[device_id]

        def put(a):
            return jax.device_put(np.asarray(a), device) if a is not None \
                else None

        packed = _EVAL_STACKED_JIT(
            self.device_params(device_id),
            put(encoded.attrs_val),
            put(encoded.members_c),
            put(encoded.cpu_dense),
            put(encoded.attr_bytes) if self.has_dfa else None,
            put(encoded.byte_ovf) if self.has_dfa else None,
            put(encoded.attrs_num) if self.has_num else None,
            put(encoded.num_valid) if self.has_num else None,
            put(encoded.rel_rows) if self.has_rel else None,
            put(encoded.member_ovf) if self.has_ovf else None,
            put(encoded.shard_of),
            put(encoded.row_of),
        )
        try:
            packed.copy_to_host_async()
        except Exception:
            pass
        # failover lane: a re-dispatch is a REAL extra launch — the ledger
        # shows it as launches_per_batch > 1 instead of hiding it
        LEDGER.observe_launch("mesh", 1,
                              h2d_bytes=self._encoded_h2d_bytes(encoded),
                              d2h_bytes=self._d2h_bytes(encoded))
        self.state.count_launch([device_id])
        return packed

    def dispatch_routed(self, encoded: _ShardedEncoded, lane: str = "engine"
                        ) -> Tuple[Any, MeshRoute]:
        """Breaker- and occupancy-aware launch (the engine's mesh entry):

        1. every device healthy → the full-mesh shard_map launch;
        2. a device fails its fault probe / launch → its per-device breaker
           records the failure and the batch re-dispatches to the healthy
           device with the EMPTIEST in-flight window (occupancy-aware
           routing) — before any host-oracle involvement;
        3. no device left → MeshUnavailable (the caller's host-oracle
           degrade is the only lane past this point).

        Returns (on-device packed handle, MeshRoute).  The route carries
        the occupied device windows; the caller releases it at terminal
        completion via ``complete_route``."""
        from ..runtime import faults

        state = self.state
        tried: set = set()
        full_mesh_eligible = state.breakers.all_closed()
        while True:
            if full_mesh_eligible:
                full_mesh_eligible = False
                try:
                    if faults.ACTIVE:
                        for d in state.device_ids:
                            faults.FAULTS.check("kernel", lane, device=d)
                    handle = self.dispatch_full(encoded)
                    return handle, state.acquire(self, list(state.device_ids))
                except MeshUnavailable:
                    raise
                except Exception as e:
                    dev = getattr(e, "device_id", None)
                    if dev is None:
                        raise  # unattributed: the engine's retry/degrade owns it
                    state.device_failed(int(dev), lane)
                    tried.add(int(dev))
                    log.warning(
                        "mesh device %d failed a full-mesh launch probe: "
                        "failing the batch over to a healthy device", dev)
                    continue
            cands = [d for d in state.breakers.candidates() if d not in tried]
            if not cands:
                raise MeshUnavailable(
                    f"no healthy mesh device left (excluded {sorted(tried)})")
            # DUE PROBES first: an open-past-cooldown device only recovers
            # if some batch actually probes it, and the breaker's single
            # probe slot (allow_device) keeps every other batch on healthy
            # devices while the probe is in flight — closed-first ordering
            # would starve the probe and strand the mesh in single-device
            # dispatch forever.  Within each class, emptiest in-flight
            # window first (the occupancy-aware cut).
            from ..runtime.breaker import CLOSED

            with state.lock:
                cands.sort(key=lambda d: (
                    state.breakers.get(d).state == CLOSED,
                    state.occupancy.get(d, 0)))
            dev = cands[0]
            if not state.breakers.get(dev).allow_device():
                tried.add(dev)
                continue
            try:
                if faults.ACTIVE:
                    faults.FAULTS.check("kernel", lane, device=dev)
                handle = self.dispatch_on_device(encoded, dev)
                return handle, state.acquire(self, [dev])
            except Exception:
                state.device_failed(dev, lane)
                tried.add(dev)
                continue

    def complete_route(self, route: Optional[MeshRoute], ok: bool,
                       lane: str = "engine") -> None:
        """Terminal accounting for one routed batch: per-device breaker
        verdicts (a single-device route's failure is attributable; a
        full-mesh readback failure is not — the lane-global breaker owns
        those) and the idempotent occupancy release."""
        if route is None:
            return
        try:
            if ok:
                self.state.breakers.record_success(route.devices)
            elif len(route.devices) == 1:
                self.state.device_failed(route.devices[0], lane,
                                         failover=False)
        finally:
            route.release()

    def cost_feed(self) -> float:
        """Mesh-lane cost multiplier for the lane-selection cost model
        (ISSUE 12, runtime/lane_select.py): ≥ 1.0, rising as devices trip
        their breakers — a partially-down mesh concentrates the same load
        on the survivors, so a device dispatch is expected to cost
        proportionally more than the healthy-mesh RTT EWMA says.  All
        devices down returns the full device count (the selector then
        prefers the host lane for everything it is allowed to take, which
        is exactly the degrade behavior the breaker enforces anyway)."""
        from ..runtime.breaker import CLOSED

        breakers = self.state.breakers.breakers
        total = len(breakers)
        if not total:
            return 1.0
        healthy = sum(1 for b in breakers.values() if b.state == CLOSED)
        return float(total) / float(max(1, healthy))

    def mesh_vars(self) -> Dict[str, Any]:
        """JSON-safe mesh-lane state for /debug/vars + bench artifacts."""
        out = self.state.to_json()
        out.update({
            "dp": int(self.mesh.shape["dp"]),
            "mp": int(self.mesh.shape["mp"]),
            "members_k": self.members_k,
            "members_k_eff": self.members_k_eff,
            "configs_per_shard": self.configs_per_shard,
            "upload": self.upload_report,
        })
        return out

    # ------------------------------------------------------------------

    def _run_step(self, encoded: _ShardedEncoded) -> np.ndarray:
        """Own-rows result [B, 1+2E] bool, decoded from the bit-packed
        readback — one small (u8 bitmask) transfer per batch (own-config
        selection happens on device, inside the shard_map)."""
        E = int(self.shards[0].eval_rule.shape[1])
        return unpack_verdicts(
            np.asarray(self.dispatch_full(encoded)), 1 + 2 * E)

    def apply(self, encoded: _ShardedEncoded) -> np.ndarray:
        return self._run_step(encoded)[:, 0]

    def apply_full(self, encoded: _ShardedEncoded) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Own-config (verdict [B], rule results [B, E], skipped [B, E]) —
        the same contract as the single-corpus ``eval_full_jit``."""
        packed = self._run_step(encoded)
        E = int(self.shards[0].eval_rule.shape[1])
        own = packed[:, 0]
        own_rule = packed[:, 1:1 + E].copy()      # writable: host fallback
        own_skipped = packed[:, 1 + E:1 + 2 * E].copy()
        return own, own_rule, own_skipped

    def host_decide(self, config_name: str, doc: Any) -> Tuple[np.ndarray, np.ndarray]:
        """Exact host-oracle decision for ONE request of this mesh corpus:
        (rule_results [E], skipped [E]) with the kernel's padding/tail
        semantics.  The engine's degraded lane (runtime/engine.py
        _degrade_batch) re-decides whole batches through this when the
        device path fails or the circuit breaker is open — the sharded
        mirror of host_results on the single corpus."""
        from ..models.policy_model import host_results

        shard, row = self.locator[config_name]
        return host_results(self.shards[shard], doc, int(row))[1:]

    def host_decide_many(self, config_names: Sequence[str],
                         docs: Sequence[Any]) -> List[Optional[Tuple[np.ndarray, np.ndarray]]]:
        """Batch form of host_decide for the engine's degraded and brownout
        lanes: one (rule_results [E], skipped [E]) per request, or None for
        a row whose oracle run itself failed (the caller resolves those
        typed UNAVAILABLE, fail closed — one bad row never fails its
        batchmates)."""
        out: List[Optional[Tuple[np.ndarray, np.ndarray]]] = []
        for name, doc in zip(config_names, docs):
            try:
                out.append(self.host_decide(name, doc))
            except Exception:
                log.exception("host oracle failed for config %r", name)
                out.append(None)
        return out

    def apply_fallback(self, host_fallback: np.ndarray, docs: Sequence[Any],
                       config_names: Sequence[str], own_rule: np.ndarray,
                       own_skipped: np.ndarray,
                       max_fallback: Optional[int] = None) -> None:
        """Host-oracle completion for membership-overflow rows — the ONE
        definition shared by finalize_full and the engine's pipelined
        (dedup-aware) finalize, so fallback semantics can't drift between
        the blocking and serving paths.  Mutates own_rule/own_skipped in
        place; at most ``max_fallback`` rows re-decide (beyond the cap:
        fail-closed deny + auth_server_host_fallback_shed_total)."""
        from ..models.policy_model import apply_host_fallback, host_results
        from ..utils import metrics as metrics_mod

        def decide(r: int):
            shard, row = self.locator[config_names[r]]
            return host_results(self.shards[shard], docs[r], int(row))[1:]

        fallback_rows = np.nonzero(host_fallback[: len(docs)])[0]
        metrics_mod.batch_host_fallback.labels("engine").observe(
            len(fallback_rows))
        apply_host_fallback(
            decide, fallback_rows,
            own_rule, own_skipped, max_fallback,
        )

    def finalize_full(
        self, packed, enc: _ShardedEncoded, docs: Sequence[Any],
        config_names: Sequence[str], max_fallback: Optional[int] = None,
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Completion half of run_full: takes the (device or already-numpy)
        packed result of ``dispatch_full(enc)`` and applies the host-oracle
        fallback.  Runs on the engine's completion stage under pipelining."""
        packed = np.asarray(packed)
        E = int(self.shards[0].eval_rule.shape[1])
        if packed.dtype == np.uint8:
            packed = unpack_verdicts(packed, 1 + 2 * E)  # bit-packed readback
        own_rule = packed[:, 1:1 + E].copy()
        own_skipped = packed[:, 1 + E:1 + 2 * E].copy()
        self.apply_fallback(enc.host_fallback, docs, config_names,
                            own_rule, own_skipped, max_fallback)
        return own_rule, own_skipped

    def run_full(
        self, docs: Sequence[Any], config_names: Sequence[str], batch_pad: int = 0,
        max_fallback: Optional[int] = None,
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Serving entry (PolicyEngine batch contract): per-request
        per-evaluator (rule_results [B, E], skipped [B, E]).  Blocking
        convenience composition of encode → dispatch_full → finalize_full;
        the engine's pipeline calls the three stages separately so batch
        N+1 encodes while batch N is still on the wire."""
        enc = self.encode(docs, config_names, batch_pad=batch_pad)
        return self.finalize_full(self.dispatch_full(enc), enc, docs,
                                  config_names, max_fallback=max_fallback)

    def decide(self, docs: Sequence[Any], config_names: Sequence[str]) -> List[bool]:
        from ..models.policy_model import host_results

        enc = self.encode(docs, config_names)
        own = self.apply(enc)
        out = [bool(b) for b in own[: len(docs)]]
        for r in np.nonzero(enc.host_fallback[: len(docs)])[0]:
            shard, row = self.locator[config_names[r]]
            out[r], _, _ = host_results(self.shards[shard], docs[r], int(row))
        return out
