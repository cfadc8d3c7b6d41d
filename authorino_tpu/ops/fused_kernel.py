"""Fused mega-kernel: the whole hot path in ONE launch (ISSUE 17).

The serving snapshot's batch today crosses several jitted calls on the
unfused path (leaf compares, the DFA byte scan, the value lanes, the
circuit, the bitpack) and the DFA lane gathers through the compile-order
row map.  This module is the paper's "one vmapped (requests x rules)
kernel" taken seriously:

  - ``_eval_verdicts_fused`` is the gather lane re-plumbed onto the fused
    operand layout: op codes travel int8 (all codes < 2^7, see
    compiler/compile.py OP_*), and the DFA transition arrays are re-keyed
    by ``CompiledPolicy.dfa_row_perm`` — rows grouped by owning table
    (``dfa_table_of_row`` nondecreasing after the permutation) so per-byte
    transition gathers walk the deduped table axis sequentially instead of
    hopping through compile order.
  - ``_fused_packed`` finishes the batch IN-KERNEL: own-config selection,
    the [B, 1+2E] attribution concat, and the little-endian bitpack are
    inlined (no separate ``_bitpack_rows`` launch) so the kernel's only
    output is the [B, W] uint8 readback.
  - ``dispatch_megakernel`` wraps the whole thing in ONE ``pl.pallas_call``:
    compiled by Mosaic on a TPU backend, ``interpret=True`` on a CPU
    (bit-exact, so tier-1 pins parity).  A body Mosaic refuses raises at
    the call — there is no quiet switch to another body under the same
    lane label.  The PR 16 ledger sees ``launches_per_batch == 1.0``.
  - ``dispatch_staged`` is the honest UNFUSED baseline: the same math cut
    into per-stage jits (leaves / DFA / value lanes / circuit / bitpack),
    each its own launch, bit-exact with the fused result — what
    ``bench_micro --kernel-cost-grid``'s fused-vs-unfused column and the
    perf_guard launch-count proof compare against.
  - ``occupancy_pad`` shapes the mesh batch pad from per-shard occupancy
    (the PR 11 grid's dp replication) instead of the global cut size.

Lane selection: ``to_device(..., lane="fused")`` or the
``AUTHORINO_TPU_KERNEL_LANE`` env mirror of ``--kernel-lane``; ``auto``
never arms it (docs/performance.md "Fused mega-kernel").
"""

from __future__ import annotations

import os
from functools import partial
from types import SimpleNamespace
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import pattern_eval as pe
from ..compiler.compile import DFA_VALUE_BYTES, CompiledPolicy

__all__ = [
    "fused_operands", "eval_fused_kernel", "dispatch_megakernel",
    "dispatch_staged", "staged_launches", "prewarm_fused", "occupancy_pad",
]


def _kernel_lane() -> str:
    """Env mirror of ``--kernel-lane`` (cli.py): fused|gather|matmul|auto."""
    return os.environ.get("AUTHORINO_TPU_KERNEL_LANE", "auto")


# ---------------------------------------------------------------------------
# fused operand layout (int8 ops, table-grouped DFA rows)
# ---------------------------------------------------------------------------


def fused_operands(policy: CompiledPolicy, dfa_byte_slot: np.ndarray) -> dict:
    """The ``params["fused"]`` subtree, host-side numpy (``to_device``
    applies its own ``put``).  Grouped arrays are the gather lane's DFA
    operands composed with ``policy.dfa_row_perm``; ``leaf_dfa_pos`` is the
    leaf's row position AFTER grouping (inverse permutation composed with
    ``leaf_dfa_row``) so leaf gathers land on the re-keyed axis."""
    fz = {"leaf_op_i8": np.asarray(policy.leaf_op_i8)}
    if policy.n_byte_attrs:
        perm = np.asarray(policy.dfa_row_perm)
        inv = np.empty_like(perm)
        inv[perm] = np.arange(perm.shape[0], dtype=np.int32)
        fz["dfa_table_of_row_g"] = policy.dfa_table_of_row[perm]
        fz["dfa_byte_slot_g"] = dfa_byte_slot.astype(np.int32)[perm]
        fz["leaf_dfa_pos"] = inv[policy.leaf_dfa_row].astype(np.int32)
    return fz


def _eval_verdicts_fused(params, attrs_val, members_c, cpu_dense, config_id,
                         attr_bytes=None, byte_ovf=None, attrs_num=None,
                         num_valid=None, rel_rows=None, member_ovf=None):
    """Gather-lane semantics on the fused layout.  Differences from
    ``pe._eval_verdicts_gather`` are exactly the layout: int8 op codes
    (upcast once on device), table-grouped DFA row arrays, and a
    ``fori_loop`` byte scan (the loop form Pallas kernels lower best)."""
    fz = params["fused"]
    if attrs_val.dtype != jnp.int32:
        attrs_val = attrs_val.astype(jnp.int32)
    if members_c.dtype != jnp.int32:
        members_c = members_c.astype(jnp.int32)
    leaf_op = fz["leaf_op_i8"].astype(jnp.int32)
    leaf_const = params["leaf_const"]
    B = attrs_val.shape[0]

    val = jnp.take(attrs_val, params["leaf_attr"], axis=1)          # [B, L]
    eq = val == leaf_const[None, :]
    memb = jnp.take(members_c, params["member_slot_of_leaf"], axis=1)
    incl = jnp.any(memb == leaf_const[None, :, None], axis=-1)
    cpu_lane = pe._cpu_full(params, cpu_dense, config_id)

    if params["dfa_tables"] is not None and attr_bytes is not None:
        tables = params["dfa_tables"]            # [T, S, 256] uint8 (deduped)
        # grouped layout: tab_idx nondecreasing, so each scan step's table
        # gathers are sequential along the deduped table axis
        tab_idx = fz["dfa_table_of_row_g"][None, :]                  # [1, R]
        row_bytes = jnp.take(attr_bytes, fz["dfa_byte_slot_g"], axis=1)
        LB = row_bytes.shape[2]
        # init derived from a varying input (zero-multiplied) so its
        # manual-mesh "varying" type matches inside shard_map
        init = (row_bytes[:, :, 0] * 0).astype(jnp.int32)

        def dfa_step(i, states):
            byte_col = jax.lax.dynamic_index_in_dim(
                row_bytes, i, axis=2, keepdims=False)
            return tables[tab_idx, states, byte_col.astype(jnp.int32)].astype(
                jnp.int32)

        final = jax.lax.fori_loop(0, LB, dfa_step, init)
        dfa_row_res = params["dfa_accept"][tab_idx, final]           # [B, R]
        leaf_dfa = jnp.take(dfa_row_res, fz["leaf_dfa_pos"], axis=1)
        leaf_slot = jnp.take(fz["dfa_byte_slot_g"], fz["leaf_dfa_pos"])
        leaf_bovf = jnp.take(byte_ovf, leaf_slot, axis=1)
        dfa_leaf_val = jnp.where(leaf_bovf, cpu_lane, leaf_dfa)
    else:
        dfa_leaf_val = cpu_lane  # regexes ride the CPU lane entirely

    num_cmp = None
    if params.get("leaf_num_slot") is not None and attrs_num is not None:
        lv = jnp.take(attrs_num, params["leaf_num_slot"], axis=1)
        lok = jnp.take(num_valid, params["leaf_num_slot"], axis=1)
        ic = leaf_const[None, :]
        num_cmp = (lok & (lv > ic), lok & (lv >= ic),
                   lok & (lv < ic), lok & (lv <= ic))

    rel_res = None
    if params.get("rel_bits") is not None and rel_rows is not None:
        rows_l = jnp.take(rel_rows, params["leaf_rel_slot"], axis=1)
        col = params["leaf_rel_col"]
        byte = params["rel_bits"][rows_l, (col >> 3)[None, :]].astype(
            jnp.int32)
        rel_res = ((byte >> (col & 7)[None, :]) & 1) != 0

    leaf_movf = None
    if member_ovf is not None:
        leaf_movf = jnp.take(member_ovf, params["member_slot_of_leaf"],
                             axis=1)

    res = pe._leaf_op_cascade(leaf_op, eq, incl, dfa_leaf_val, cpu_lane,
                              num_cmp, rel_res, leaf_movf)

    true_col = jnp.ones((B, 1), dtype=bool)
    false_col = jnp.zeros((B, 1), dtype=bool)
    buffer = jnp.concatenate([true_col, false_col, res], axis=1)
    for children, is_and in params["levels"]:
        ch = jnp.take(buffer, children.reshape(-1), axis=1)
        ch = ch.reshape(B, children.shape[0], children.shape[1])
        node = jnp.where(is_and[None, :], jnp.all(ch, axis=-1),
                         jnp.any(ch, axis=-1))
        buffer = jnp.concatenate([buffer, node], axis=1)

    cond = jnp.take(buffer, params["eval_cond"].reshape(-1), axis=1)
    rule = jnp.take(buffer, params["eval_rule"].reshape(-1), axis=1)
    G, E = params["eval_rule"].shape
    return pe._verdict_from_tables(
        params, cond.reshape(B, G, E), rule.reshape(B, G, E))


def _fused_packed(params, ops: dict):
    """The whole batch in one traced body: verdicts + attribution + the
    IN-KERNEL bitpack.  ``ops`` is the operand dict a ``pe._defuse`` (or
    the per-operand staging) produces; absent lanes are absent keys."""
    verdict, (rule, skipped) = _eval_verdicts_fused(
        params, ops["attrs_val"], ops["members_c"], ops["cpu_dense"],
        ops["config_id"], ops.get("attr_bytes"), ops.get("byte_ovf"),
        ops.get("attrs_num"),
        ops.get("num_valid"), ops.get("rel_rows"), ops.get("member_ovf"))
    own_mask = pe._select_own(ops["config_id"], verdict.shape[1])
    own = jnp.any(verdict & own_mask, axis=1)
    own_rule = jnp.any(rule & own_mask[:, :, None], axis=1)
    own_skipped = jnp.any(skipped & own_mask[:, :, None], axis=1)
    cols = jnp.concatenate([own[:, None], own_rule, own_skipped], axis=1)
    # inline little-endian bitpack — same contract as pe._bitpack_rows, but
    # produced inside the one launch so the kernel's only output is the
    # [B, W] uint8 readback (W == CompiledPolicy.fused_pack_w)
    B, C = cols.shape
    W = pe.packed_width(C)
    padded = jnp.zeros((B, W * 8), dtype=bool).at[:, :C].set(cols)
    weights = (1 << jnp.arange(8, dtype=jnp.int32))[None, None, :]
    return (padded.reshape(B, W, 8).astype(jnp.int32) * weights).sum(
        axis=-1).astype(jnp.uint8)


# ---------------------------------------------------------------------------
# the one launch: Pallas kernel (interpret mode off-TPU)
# ---------------------------------------------------------------------------


def _pallas_wrap(params, ops: dict, extra_flat=None, defuse_layout=None):
    """Run ``_fused_packed`` as ONE ``pl.pallas_call``.  Params + operands
    tree-flatten into the kernel's refs (bool leaves cross as uint8 — Pallas
    I/O is numeric — and are restored inside); with ``defuse_layout`` the
    LAST input is the fused staging buffer and the operand decode happens
    inside the kernel too, so the launch consumes the raw H2D bytes."""
    from jax.experimental import pallas as pl

    tree = (params, ops)
    flat, treedef = jax.tree_util.tree_flatten(tree)
    bool_ix = frozenset(
        i for i, a in enumerate(flat)
        if np.dtype(a.dtype) == np.dtype(bool))
    cast = [a.astype(jnp.uint8) if i in bool_ix else a
            for i, a in enumerate(flat)]
    tail = list(extra_flat) if extra_flat is not None else []
    if defuse_layout is not None:
        B = next(s[0] for n, d, s, o, z in defuse_layout if n == "attrs_val")
    else:
        B = ops["attrs_val"].shape[0]
    W = pe.packed_width(1 + 2 * params["eval_rule"].shape[1])

    def kernel(*refs):
        *in_refs, out_ref = refs
        vals = [r[...] for r in in_refs]
        leaves = [(v != 0) if i in bool_ix else v
                  for i, v in enumerate(vals[:len(flat)])]
        p, o = jax.tree_util.tree_unflatten(treedef, leaves)
        if defuse_layout is not None:
            o = pe._defuse(vals[-1], defuse_layout)
        out_ref[...] = _fused_packed(p, o)

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((B, W), jnp.uint8),
        interpret=jax.default_backend() != "tpu",
    )(*cast, *tail)


@partial(jax.jit, static_argnames=("layout",))
def _fused_buf_jit(params, buf, layout):
    """ONE launch over the fused H2D staging buffer: operand decode, every
    lane, the circuit, and the bitpack in a single executable."""
    with jax.named_scope("fused_kernel"):
        return _pallas_wrap(params, {}, extra_flat=(buf,),
                            defuse_layout=layout)


@jax.jit
def _fused_ops_jit(params, attrs_val, members_c, cpu_dense, config_id,
                   attr_bytes, byte_ovf, attrs_num, num_valid, rel_rows,
                   member_ovf):
    """Per-operand-transfer variant of the one launch (big-endian hosts
    where the fused H2D bitcast probe fails, the native lane whose operands
    are already separate arrays, and the zero-operand warm)."""
    ops = {"attrs_val": attrs_val, "members_c": members_c,
           "cpu_dense": cpu_dense, "config_id": config_id}
    for name, a in (("attr_bytes", attr_bytes), ("byte_ovf", byte_ovf),
                    ("attrs_num", attrs_num), ("num_valid", num_valid),
                    ("rel_rows", rel_rows), ("member_ovf", member_ovf)):
        if a is not None:
            ops[name] = a
    with jax.named_scope("fused_kernel"):
        return _pallas_wrap(params, ops)


def eval_fused_kernel(params, db) -> "jax.Array":
    """One compact batch through the mega-kernel; returns the on-device
    [B, W] uint8 bitpacked readback (decode with ``pe.unpack_verdicts``)."""
    if pe.fused_h2d_supported():
        buf, layout = pe.fuse_batch(db)
        return _fused_buf_jit(params, jnp.asarray(buf), layout)
    has_dfa = params["dfa_tables"] is not None
    return _fused_ops_jit(
        params,
        jnp.asarray(db.attrs_val),
        jnp.asarray(db.members_c),
        jnp.asarray(db.cpu_dense),
        jnp.asarray(db.config_id),
        jnp.asarray(db.attr_bytes) if has_dfa else None,
        jnp.asarray(db.byte_ovf) if has_dfa else None,
        *pe._extra_operands(db),
    )


def dispatch_megakernel(params, db) -> "jax.Array":
    """Non-blocking mega-kernel launch (the fused-lane twin of
    ``pe.dispatch_fused``'s unfused body): eager D2H copy start, one launch
    on the ledger either way."""
    out = eval_fused_kernel(params, db)
    try:
        out.copy_to_host_async()
    except Exception:
        pass  # readback degrades to a blocking copy at np.asarray time
    return out


# ---------------------------------------------------------------------------
# staged baseline: the same math cut into per-stage launches
# ---------------------------------------------------------------------------
#
# The honest pre-fusion shape of the hot path for the ledger/bench
# comparison: each stage is its own jit (its own launch + inter-stage
# device round trips stay on device, but the LAUNCH count is real).
# Bit-exact with the fused result — tests pin it.


@jax.jit
def _stage_leaves(params, attrs_val, members_c, cpu_dense, config_id):
    if attrs_val.dtype != jnp.int32:
        attrs_val = attrs_val.astype(jnp.int32)
    if members_c.dtype != jnp.int32:
        members_c = members_c.astype(jnp.int32)
    val = jnp.take(attrs_val, params["leaf_attr"], axis=1)
    eq = val == params["leaf_const"][None, :]
    memb = jnp.take(members_c, params["member_slot_of_leaf"], axis=1)
    incl = jnp.any(memb == params["leaf_const"][None, :, None], axis=-1)
    return eq, incl, pe._cpu_full(params, cpu_dense, config_id)


@jax.jit
def _stage_dfa(params, attr_bytes, byte_ovf, cpu_lane):
    # the UNgrouped compile-order gather layout — the pre-fusion hot path
    tables = params["dfa_tables"]
    tab_idx = params["dfa_table_of_row"][None, :]
    row_bytes = jnp.take(attr_bytes, params["dfa_byte_slot"], axis=1)

    def dfa_step(states, byte_col):
        nxt = tables[tab_idx, states, byte_col.astype(jnp.int32)]
        return nxt.astype(jnp.int32), None

    init = (row_bytes[:, :, 0] * 0).astype(jnp.int32)
    final, _ = jax.lax.scan(dfa_step, init,
                            jnp.transpose(row_bytes, (2, 0, 1)))
    dfa_row_res = params["dfa_accept"][tab_idx, final]
    leaf_dfa = jnp.take(dfa_row_res, params["leaf_dfa_row"], axis=1)
    leaf_slot = jnp.take(params["dfa_byte_slot"], params["leaf_dfa_row"])
    leaf_bovf = jnp.take(byte_ovf, leaf_slot, axis=1)
    return jnp.where(leaf_bovf, cpu_lane, leaf_dfa)


@jax.jit
def _stage_value_lanes(params, attrs_num, num_valid, rel_rows, member_ovf):
    num_cmp = None
    if params.get("leaf_num_slot") is not None and attrs_num is not None:
        lv = jnp.take(attrs_num, params["leaf_num_slot"], axis=1)
        lok = jnp.take(num_valid, params["leaf_num_slot"], axis=1)
        ic = params["leaf_const"][None, :]
        num_cmp = (lok & (lv > ic), lok & (lv >= ic),
                   lok & (lv < ic), lok & (lv <= ic))
    rel_res = None
    if params.get("rel_bits") is not None and rel_rows is not None:
        rows_l = jnp.take(rel_rows, params["leaf_rel_slot"], axis=1)
        col = params["leaf_rel_col"]
        byte = params["rel_bits"][rows_l, (col >> 3)[None, :]].astype(
            jnp.int32)
        rel_res = ((byte >> (col & 7)[None, :]) & 1) != 0
    leaf_movf = None
    if member_ovf is not None:
        leaf_movf = jnp.take(member_ovf, params["member_slot_of_leaf"],
                             axis=1)
    return num_cmp, rel_res, leaf_movf


@jax.jit
def _stage_circuit(params, config_id, eq, incl, dfa_leaf_val, cpu_lane,
                   num_cmp, rel_res, leaf_movf):
    res = pe._leaf_op_cascade(params["leaf_op"], eq, incl, dfa_leaf_val,
                              cpu_lane, num_cmp, rel_res, leaf_movf)
    B = res.shape[0]
    buffer = jnp.concatenate(
        [jnp.ones((B, 1), dtype=bool), jnp.zeros((B, 1), dtype=bool), res],
        axis=1)
    for children, is_and in params["levels"]:
        ch = jnp.take(buffer, children.reshape(-1), axis=1)
        ch = ch.reshape(B, children.shape[0], children.shape[1])
        node = jnp.where(is_and[None, :], jnp.all(ch, axis=-1),
                         jnp.any(ch, axis=-1))
        buffer = jnp.concatenate([buffer, node], axis=1)
    cond = jnp.take(buffer, params["eval_cond"].reshape(-1), axis=1)
    rule = jnp.take(buffer, params["eval_rule"].reshape(-1), axis=1)
    G, E = params["eval_rule"].shape
    verdict, (rule_r, skipped) = pe._verdict_from_tables(
        params, cond.reshape(B, G, E), rule.reshape(B, G, E))
    own_mask = pe._select_own(config_id, verdict.shape[1])
    own = jnp.any(verdict & own_mask, axis=1)
    own_rule = jnp.any(rule_r & own_mask[:, :, None], axis=1)
    own_skipped = jnp.any(skipped & own_mask[:, :, None], axis=1)
    return jnp.concatenate([own[:, None], own_rule, own_skipped], axis=1)


_stage_pack = jax.jit(pe._bitpack_rows)


def staged_launches(params, db) -> int:
    """How many launches ``dispatch_staged`` will make for this batch —
    pure structure arithmetic (leaves + circuit + pack, plus DFA and
    value-lane stages when those operands ride)."""
    n = 3
    if params["dfa_tables"] is not None and db.attr_bytes is not None:
        n += 1
    if any(a is not None
           for a in (db.attrs_num, db.num_valid, db.rel_rows,
                     db.member_ovf)):
        n += 1
    return n


def dispatch_staged(params, db, ledger_lane: Optional[str] = None):
    """The unfused baseline: same batch, same bit-exact [B, W] uint8
    readback, one launch PER STAGE (recorded on the PR 16 ledger when
    ``ledger_lane`` is given).  Intermediate arrays stay on device."""
    def obs():
        if ledger_lane is not None:
            from ..runtime.kernel_cost import LEDGER
            LEDGER.observe_launch(ledger_lane)

    eq, incl, cpu_lane = _stage_leaves(
        params, jnp.asarray(db.attrs_val), jnp.asarray(db.members_c),
        jnp.asarray(db.cpu_dense), jnp.asarray(db.config_id))
    obs()
    if params["dfa_tables"] is not None and db.attr_bytes is not None:
        dfa_leaf_val = _stage_dfa(params, jnp.asarray(db.attr_bytes),
                                  jnp.asarray(db.byte_ovf), cpu_lane)
        obs()
    else:
        dfa_leaf_val = cpu_lane
    extras = pe._extra_operands(db)
    if any(a is not None for a in extras):
        num_cmp, rel_res, leaf_movf = _stage_value_lanes(params, *extras)
        obs()
    else:
        num_cmp = rel_res = leaf_movf = None
    cols = _stage_circuit(params, jnp.asarray(db.config_id), eq, incl,
                          dfa_leaf_val, cpu_lane, num_cmp, rel_res,
                          leaf_movf)
    obs()
    out = _stage_pack(cols)
    obs()
    return out


# ---------------------------------------------------------------------------
# pre-warm + mesh occupancy shaping
# ---------------------------------------------------------------------------


def _zero_db(policy: CompiledPolicy, pad: int, eff: int):
    """Throwaway zero DeviceBatch-shaped namespace at one (pad, eff)
    bucket — the fused twin of kernel_cost._bitpacked_zero_args, carrying
    the PR 14 operand tail so the warmed executable matches serving."""
    from ..compiler.intern import PAD
    from ..compiler.pack import wire_dtype

    dt = wire_dtype(policy)
    A, M, K = policy.n_attrs, policy.n_member_attrs, policy.members_k
    C, NB = policy.n_own_cpu, max(policy.n_byte_attrs, 1)
    NN = getattr(policy, "n_num_attrs", 0)
    NR = getattr(policy, "n_rel_slots", 0)
    return SimpleNamespace(
        attrs_val=np.zeros((pad, A), dtype=dt),
        members_c=np.full((pad, M, K), PAD, dtype=dt),
        cpu_dense=np.zeros((pad, C), dtype=bool),
        config_id=np.zeros((pad,), dtype=np.int32),
        attr_bytes=np.zeros((pad, NB, eff), dtype=np.uint8) if eff else None,
        byte_ovf=np.zeros((pad, NB), dtype=bool) if eff else None,
        attrs_num=np.zeros((pad, NN), dtype=np.int32) if NN else None,
        num_valid=np.zeros((pad, NN), dtype=bool) if NN else None,
        rel_rows=np.zeros((pad, NR), dtype=np.int32) if NR else None,
        member_ovf=np.zeros((pad, M), dtype=bool)
        if getattr(policy, "ovf_assist", False) else None,
    )


def prewarm_fused(policy: CompiledPolicy, params, pad: int = 16,
                  eff: Optional[int] = None) -> bool:
    """Compile the mega-kernel entry at one warm-grid (pad, eff) bucket so
    the first post-reconcile batch pays no XLA (or Pallas lowering) compile.
    No-op (False) unless the snapshot's params carry the fused subtree."""
    if params is None or params.get("fused") is None:
        return False
    if eff is None:
        eff = DFA_VALUE_BYTES if policy.n_byte_attrs else 0
    out = eval_fused_kernel(params, _zero_db(policy, pad, eff))
    jax.block_until_ready(out)
    return True


def occupancy_pad(shard_counts, dp: int, n_rows: int,
                  floor: int = 16, cap: Optional[int] = None) -> int:
    """Per-shard occupancy-shaped batch pad for the mesh lane (ISSUE 17):
    the stacked [B, S, ...] operands pad to the pow2 bucket of the BUSIEST
    shard's row count replicated across the PR 11 grid's dp axis — a batch
    concentrated on one shard pads to that shard's occupancy, never below
    the real row count, snapped to the same pow2 grid as the single-corpus
    warm buckets (so it adds no jit variants beyond that grid)."""
    occ = max((int(c) for c in shard_counts), default=0)
    need = max(int(n_rows), occ * max(int(dp), 1), 1)
    pad = max(int(floor), 1)
    while pad < need:
        pad *= 2
    if cap is not None:
        pad = min(pad, max(int(cap), need))
    return pad
