"""Batched policy-evaluation kernel (pure JAX/XLA; static shapes, no
data-dependent control flow).

One call evaluates a micro-batch of requests against the compiled rule
corpus: every config's verdict for every request (the dense bodies), or each
request's own config's alone (``eval_own``, what serves).  This replaces
the reference's per-request goroutine fan-out + per-pattern gjson walk
(ref: pkg/service/auth_pipeline.go:150-182, pkg/jsonexp/expressions.go:59):
equal-priority rules across all configs fuse into one kernel launch
(SURVEY.md §2 P1/P2 mapping).

Inputs are the *compact* device payload (compiler/pack.py): [B, A] attr ids,
[B, M, K] membership rows for incl/excl attrs only, a [B, c_own] CPU lane
(the request's own config's true-CPU + DFA leaves, not the leaf axis), and
the DFA byte tensors.  The wire format carries only what the kernel reads, and results
return as one packed matrix: every byte crosses the host↔device link
(PCIe), whose share of a batch has not been measured on the current code.

Three bodies, one selection:

  - ``eval_own`` is what serves.  Every entry that reads only each row's
    own config (``eval_full_jit`` and, through it, ``eval_packed_jit``,
    ``eval_bitpacked_jit`` — the host twin's — and the two staged entries,
    ``eval_bitpacked_staged_jit``, what the native lane serves, and
    ``eval_fused_jit``, the engine lane's) runs it: ONE body that
    gathers the request's config's row of its SIZE CLASS's per-config
    tables (compiler/compile.py ``SizeClass``: the configs cut into classes
    of like size, each with tables of its own widths, so that a row pays
    for its own config's size and not for the corpus's largest) and
    evaluates the request's own [B, l_own] leaves, [B, n_own] circuit nodes
    a level and [B, E] evaluators, at the class's l_own, n_own, E, D and S.
    The native lane launches a cut's rows one class at a time
    (``class_view``); handed the whole operands, every class evaluates every
    row at its own widths, answers False for rows of no member, and the
    answers are OR-ed.  Its DFA scan covers the class's
    ``config_dfa_rows[row]``, the [B, D] rows the config reaches: their
    [S, 256] tables are gathered once a launch from the class's
    deduplicated ``dfa_tables``, one batched matmul with
    the byte one-hots gives every byte position's S -> S map, the
    ``lax.scan`` carries [D, B] (the batch on the lanes), and the accepts
    feed the own leaves directly.  Reads inside a row (attribute of a leaf,
    child of a node) are one-hot mask-reduces over the small own axes —
    integer-exact, no gather.
    Nothing in it grows with the corpus but the tables it gathers one row
    from, nor with the corpus's largest config but the largest class's
    launches.
  - ``_eval_verdicts_matmul`` is the dense body of the callers that want
    every config's column (``to_device(dense=True)``: ``forward`` /
    ``_eval_jit`` / ``eval_batch_jit`` through models/policy_model.py, the
    mesh step in parallel/sharded_eval.py).  Gathers are pathological on
    TPU (they lower to scalar-unit loops), so every gather is reformulated
    as a one-hot matmul on the MXU:
      * leaf operand selection rides ``attrs @ attr_onehot`` with
        ``Precision.HIGHEST`` — XLA's 3-pass bf16 decomposition makes the
        f32 product exact, and selecting through an exact 0/1 one-hot
        reassembles interner ids < 2^24 bit-exactly;
      * the boolean circuit becomes per-level *count* matmuls over the
        result buffer (AND ≡ count==width since And-padding children point
        at the constant-TRUE slot; OR ≡ count>0 with FALSE-slot padding);
      * per-config rule/condition extraction and own-config selection are
        one-hot matmuls/masked reductions;
      * the regex-DFA byte scan keeps its ``lax.scan`` skeleton but each
        step's transition lookup becomes a batched
        (byte-one-hot × transition-table) matmul — values ≤ 255 are exact
        in bf16.
  - ``_eval_verdicts_gather`` is the direct jnp.take formulation of the
    dense body — the semantic reference the differential tests compare
    against, and the dense body of a corpus whose interner outgrows
    exact-f32 range (ids ≥ 2^24).

The selection is structural and taken from the input alone: ``to_device``
builds the ``matmul`` subtree unless the interner is past 2^24 strings (or a
test or the lint's per-lane loop names the reference body with ``lane=``),
``dense=True`` adds the G x L one-hot operands to it, and ``eval_verdicts``
branches on their presence at trace time, so the bodies jit-cache
independently.  No flag or environment variable names a body.

The row payload's CPU lane is own-config too: ``cpu_dense`` is [B, c_own],
column j the answer of leaf ``own.cpu_leaves[config_id, j]`` (a config's
columns are the first of its row, so a launch of one size class stages the
class's c_own and the host encoders fill the corpus's); the dense bodies
spread it onto the leaf axis with ``_cpu_full``.

Membership overflow (arrays longer than K) and DFA byte overflow cannot be
answered from the compact payload per-leaf; overflowed *requests* are flagged
host_fallback by pack_batch and re-decided on host by the expression oracle
(models/policy_model.py) — the kernel result for those rows is ignored.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..compiler.compile import (
    FALSE_SLOT,
    NUMERIC_OPS,
    OWN_ATTR,
    OWN_BYTE,
    OWN_CONST,
    OWN_CPU,
    OWN_DFA,
    OWN_FIELDS,
    OWN_MEMBER,
    OWN_NUM,
    OWN_OP,
    OWN_REL_COL,
    OWN_REL_SLOT,
    OP_CPU,
    OP_EQ,
    OP_ERROR,
    OP_EXCL,
    OP_INCL,
    OP_NEQ,
    OP_NUM_GE,
    OP_NUM_GT,
    OP_NUM_LE,
    OP_NUM_LT,
    OP_REGEX_DFA,
    OP_RELATION,
    OP_TREE_CPU,
    TRUE_SLOT,
    CompiledPolicy,
)

__all__ = ["DevicePolicy", "to_device", "eval_verdicts", "eval_own",
           "eval_batch_jit", "kernel_widths", "class_widths", "class_view",
           "has_dfa", "operand_bytes",
           "fuse_layout", "fuse_bytes", "fuse_batch", "eval_fused_jit",
           "dispatch_fused", "fused_h2d_supported", "require_fused_h2d",
           "eval_bitpacked_jit",
           "eval_bitpacked_staged_jit", "unpack_verdicts",
           "packed_width", "firing_columns", "unpack_attribution",
           "kernel_lane_of"]

# exact integer range of f32 accumulation — larger interners must use the
# gather lane
_F32_EXACT = 1 << 24

_HIGH = jax.lax.Precision.HIGHEST


def kernel_lane_of(params) -> str:
    """Which lane ``to_device`` built a params pytree for — structural,
    mirroring eval_verdicts' trace-time branch."""
    return "matmul" if params.get("matmul") is not None else "gather"


def _mm_dtype(device=None):
    """bf16 on MXU-bearing backends; f32 on CPU (whose dot kernels lack
    BF16×BF16→F32 — and where f32 one-hot matmuls are exact natively).
    Derived from the *target* device's platform when one is given."""
    platform = device.platform if device is not None else jax.default_backend()
    return jnp.float32 if platform == "cpu" else jnp.bfloat16


def _matmul_operands(policy: CompiledPolicy, row_slot: np.ndarray, device=None) -> dict:
    """One-hot / count matrices of the DENSE MXU body (see module doc): they
    grow with G x L, so ``to_device`` builds them only on request.
    ``row_slot`` is the per-DFA-row byte-tensor slot (shared with the gather
    lane's ``dfa_byte_slot`` so the two lanes can never disagree on which
    byte tensor a row scans)."""
    L = policy.n_leaves
    A = policy.n_attrs
    M = policy.n_member_attrs
    cdt = _mm_dtype(device)
    attr_onehot = np.zeros((A, L), dtype=np.float32)
    attr_onehot[policy.leaf_attr, np.arange(L)] = 1.0

    # compact-membership one-hot: member slot of each incl/excl leaf's attr
    memb_onehot = np.zeros((M, L), dtype=np.float32)
    is_memb = policy.leaf_is_membership
    if is_memb.any():
        slots = policy.member_attr_slot[policy.leaf_attr[is_memb]]
        memb_onehot[slots, np.nonzero(is_memb)[0]] = 1.0

    # per-level count matrices over the buffer prefix visible to that level
    # (the count threshold — the level's child width — is recovered at trace
    # time from params["levels"]; keeping it out of the operands leaves the
    # pytree all-array, so the sharded model can stack it on a mesh axis)
    level_mats = []
    cursor = 2 + L  # TRUE/FALSE slots + leaf block
    for children, is_and in policy.levels:
        rows, width = children.shape
        m = np.zeros((rows, cursor), dtype=np.float32)
        np.add.at(m, (np.repeat(np.arange(rows), width), children.reshape(-1)), 1.0)
        level_mats.append(m.astype(cdt))
        cursor += rows

    # eval-table one-hots over the full buffer
    G, E = policy.eval_rule.shape
    rule_m = np.zeros((G * E, cursor), dtype=np.float32)
    rule_m[np.arange(G * E), policy.eval_rule.reshape(-1)] = 1.0
    cond_m = np.zeros((G * E, cursor), dtype=np.float32)
    cond_m[np.arange(G * E), policy.eval_cond.reshape(-1)] = 1.0

    out = {
        "attr_onehot": attr_onehot,  # f32: exact selection via HIGHEST
        "memb_onehot": memb_onehot,  # f32: exact selection via HIGHEST
        "level_mats": tuple(level_mats),
        "rule_m": rule_m.astype(cdt),
        "cond_m": cond_m.astype(cdt),
    }

    # numeric lane (ISSUE 14): int32 compares happen slot-wise (exact, no
    # f32 round-trip for the values); this bool mask spreads each slot's
    # verdict onto its leaves via a masked any-reduce — gather-free
    if getattr(policy, "n_num_attrs", 0):
        NN = policy.n_num_attrs
        num_mask = np.zeros((NN, L), dtype=bool)
        is_num = np.isin(policy.leaf_op, NUMERIC_OPS)
        if is_num.any():
            slots = np.maximum(
                policy.num_attr_slot[policy.leaf_attr[is_num]], 0)
            num_mask[slots, np.nonzero(is_num)[0]] = True
        out["num_slot_leaf_mask"] = num_mask

    # relation lane (ISSUE 14): per (entity row, leaf) bit matrix — each
    # relation leaf's column unpacked onto its leaf slot, selected by an
    # exact one-hot over the row axis (0/1 products: exact in bf16)
    if getattr(policy, "n_rel_slots", 0):
        Rp = int(policy.rel_bits.shape[0])
        NR = policy.n_rel_slots
        is_rel = policy.leaf_op == OP_RELATION
        rel_leaf_mat = np.zeros((Rp, L), dtype=np.float32)
        rel_slot_leaf = np.zeros((NR, L), dtype=np.float32)
        for l in np.nonzero(is_rel)[0]:
            c = int(policy.leaf_rel_col[l])
            rel_leaf_mat[:, l] = (policy.rel_bits[:, c >> 3]
                                  >> np.uint8(c & 7)) & 1
            rel_slot_leaf[int(policy.leaf_rel_slot[l]), l] = 1.0
        out["rel_leaf_mat"] = rel_leaf_mat.astype(cdt)
        out["rel_slot_leaf_oh"] = rel_slot_leaf.astype(cdt)

    # device regex lane: matmul-form transition tables + spread one-hots.
    # The compiled tables are table-deduped ([T, S, 256] + row→table map);
    # the matmul lane's einsum contracts over the row axis, so the tables
    # expand back to per-row here (host-side — the one-hot spread matrices
    # dominate this lane's operand footprint anyway)
    if policy.n_byte_attrs:
        R = policy.dfa_table_of_row.shape[0]
        NB = policy.n_byte_attrs
        slot_row_oh = np.zeros((NB, R), dtype=np.float32)
        slot_row_oh[row_slot, np.arange(R)] = 1.0
        is_dfa_leaf = policy.leaf_op == OP_REGEX_DFA
        row_leaf_oh = np.zeros((R, L), dtype=np.float32)
        row_leaf_oh[policy.leaf_dfa_row[is_dfa_leaf], np.nonzero(is_dfa_leaf)[0]] = 1.0
        slot_leaf_oh = np.zeros((NB, L), dtype=np.float32)
        leaf_slot = row_slot[policy.leaf_dfa_row[is_dfa_leaf]]
        slot_leaf_oh[leaf_slot, np.nonzero(is_dfa_leaf)[0]] = 1.0
        # next-state values <= 255 and state count <= 256: exact in bf16; a
        # u16 store (ids past 255) travels in f32 and its matmul runs at
        # HIGHEST precision (_eval_verdicts_matmul), exact to 2**24
        tdt = cdt if policy.dfa_tables.dtype == np.uint8 else np.float32
        out.update(
            {
                "dfa_tables_f": policy.dfa_tables_by_row.astype(tdt),
                "dfa_accept_f": policy.dfa_accept_by_row.astype(cdt),
                "slot_row_oh": slot_row_oh.astype(cdt),
                "row_leaf_oh": row_leaf_oh.astype(cdt),
                "slot_leaf_oh": slot_leaf_oh.astype(cdt),
            }
        )
    return out


def to_device(policy: CompiledPolicy, device=None, lane: Optional[str] = None,
              host: bool = False, dense: bool = False) -> dict:
    """Upload a compiled corpus's operands as a pytree of device arrays.
    The engine double-buffers these and swaps atomically on reconcile
    (SURVEY.md §3.4: rule-tensor compile + device upload on index Set).
    ``lane`` names the reference body (``gather``) for the callers that
    compare against it; left None it is ``matmul``, or ``gather`` once the
    interner is past exact-f32 range.  ``host=True`` keeps the
    operands as host numpy arrays — the sharded model stacks per-shard
    pytrees host-side and transfers each shard's slice exactly once via a
    mesh-sharded device_put, instead of staging everything on device 0.
    ``dense=True`` adds the matmul lane's G x L one-hot operands, for
    callers of the dense body (``eval_verdicts``); without them that body
    runs the gather formulation, and ``eval_own`` needs neither."""
    if host:
        put = np.asarray
    else:
        put = partial(jax.device_put, device=device) if device is not None else jax.device_put
    if lane is None:
        lane = "matmul"
    if lane == "matmul" and len(policy.interner) + 4 >= _F32_EXACT:
        lane = "gather"  # ids no longer exact in f32 accumulation
    # per-dfa-row byte-tensor slot (attr → slot mapping folded in here);
    # shared by all lanes
    dfa_byte_slot = np.maximum(policy.attr_byte_slot[policy.dfa_leaf_attr], 0)
    mm = None
    if lane == "matmul":
        # "mxu": the lane's compute dtype for the target device, as a
        # zero-length operand (a traced body cannot ask for the platform)
        mm = {"mxu": put(np.zeros((0,), dtype=_mm_dtype(device)))}
        if dense:
            mm.update(jax.tree.map(
                put, _matmul_operands(policy, dfa_byte_slot, device=device)))
    # gather-lane helpers for the compact payload
    L = policy.n_leaves
    member_slot_of_leaf = np.maximum(
        policy.member_attr_slot[policy.leaf_attr], 0
    ).astype(np.int32)
    own = policy.own
    # scatter targets of the row payload's CPU columns, per config; padding
    # columns land in a dump slot at L (sliced off) so they can never
    # clobber a real leaf
    own_cpu_leaf = np.where(own.cpu_leaves >= 0, own.cpu_leaves, L).astype(np.int32)
    has_dfa_lane = bool(policy.n_byte_attrs)
    # the dense gather body scans every class's DFA store and reads a leaf's
    # accept from the stores laid end to end: leaf -> position there (its
    # first store's; a row shared by two classes reads the same either way)
    leaf_dfa_pos = np.zeros((L,), dtype=np.int32)
    if has_dfa_lane:
        store_rows = np.concatenate([c.dfa_rows for c in policy.classes])
        pos_of_row = np.zeros((policy.dfa_table_of_row.shape[0],), dtype=np.int32)
        # last to first, so that a shared row keeps its first store's place
        pos_of_row[store_rows[::-1]] = np.arange(store_rows.shape[0])[::-1]
        is_dfa = policy.leaf_op == OP_REGEX_DFA
        leaf_dfa_pos[is_dfa] = pos_of_row[policy.leaf_dfa_row[is_dfa]]

    def class_operands(cls) -> dict:
        """One size class's tables (compiler/compile.py SizeClass): what
        ``eval_own`` gathers a member's row from.  One ROW a config: the
        device pads the two minor axes of an array to its tiles, so
        [G, 10, 10] would take 20 times its bytes and be copied whole by
        every launch's gather."""
        o = cls.own
        G_c = o.leaves.shape[0]
        dfa = has_dfa_lane and bool(cls.dfa_rows.size)
        return {
            # config row -> row of this class's tables (-1: another class's)
            "cfg_local": put(cls.cfg_local),
            "own": {
                "leaf": put(o.leaf_tab.reshape(G_c, -1)),           # [G_c, l_own * F]
                "levels": tuple((put(c.reshape(G_c, -1)), put(a))   # [G_c, n * w], [G_c, n]
                                for c, a in o.levels),
                "evals": put(o.evals.reshape(G_c, -1)),             # [G_c, 3 * E_c]
            },
            # device regex lane of the class; None (a static pytree node,
            # not a traced leaf) when its members reach no DFA row, so the
            # kernel's python-level `is None` check specializes at trace
            # time.  Tables travel DEDUPED ([T_c, S_c, 256] + the store's
            # row -> table map): identical regexes across the class's
            # AuthConfigs upload exactly one transition table.
            "dfa_tables": put(cls.dfa_tables) if dfa else None,
            "dfa_accept": put(cls.dfa_accept) if dfa else None,
            "dfa_table_of_row": put(cls.dfa_table_of_row) if dfa else None,
            "dfa_byte_slot": put(dfa_byte_slot[cls.dfa_rows].astype(np.int32))
            if dfa else None,
            # own-row scan: per member, the store rows its circuits reach
            "config_dfa_rows": put(cls.config_dfa_rows) if dfa else None,
        }

    # operands are numpy throughout: `put` is the ONLY device transfer (or a
    # no-op for host=True), so nothing ever stages on the default device
    return {
        "matmul": mm,
        "leaf_op": put(policy.leaf_op),
        "leaf_attr": put(policy.leaf_attr),
        "leaf_const": put(policy.leaf_const),
        "member_slot_of_leaf": put(member_slot_of_leaf),
        "own_cpu_leaf": put(own_cpu_leaf),
        # the size classes' tables: eval_own evaluates a row at its own
        # config's class's widths (``class_view`` hands a launch one class)
        "classes": tuple(class_operands(cls) for cls in policy.classes),
        "levels": tuple(
            (put(children), put(is_and))
            for children, is_and in policy.levels
        ),
        "eval_cond": put(policy.eval_cond),
        "eval_rule": put(policy.eval_rule),
        "eval_has_cond": put(policy.eval_has_cond),
        # dense gather body: leaf -> row of the classes' DFA stores laid end
        # to end; None when the corpus has no DFA-compilable regexes
        "leaf_dfa_row": put(leaf_dfa_pos) if has_dfa_lane else None,
        # numeric comparator lane (ISSUE 14): leaf → compact value slot;
        # the constants ride leaf_const (folded int32 at compile time)
        "leaf_num_slot": put(np.maximum(
            policy.num_attr_slot[policy.leaf_attr], 0).astype(np.int32))
        if getattr(policy, "n_num_attrs", 0) else None,
        # relation lane (ISSUE 14): the per-snapshot closure bitmatrix +
        # leaf → (entity-row slot, group column) bindings
        "rel_bits": put(policy.rel_bits)
        if getattr(policy, "n_rel_slots", 0) else None,
        "leaf_rel_slot": put(policy.leaf_rel_slot)
        if getattr(policy, "n_rel_slots", 0) else None,
        "leaf_rel_col": put(policy.leaf_rel_col)
        if getattr(policy, "n_rel_slots", 0) else None,
    }


def has_dfa(params) -> bool:
    """Whether the operands carry a device regex lane (structural)."""
    return params.get("leaf_dfa_row") is not None or any(
        c["dfa_tables"] is not None for c in params["classes"])


# what eval_own reads beside a class's tables
_VIEW_KEYS = ("matmul", "leaf_num_slot", "rel_bits")


def class_view(params, c: int) -> dict:
    """The operands of ONE size class, for a launch whose rows are all that
    class's: the served entries run it at the class's widths and return the
    class's [B, 1 + 2 * E_c] columns.  Config ids stay the corpus's."""
    view = {k: params.get(k) for k in _VIEW_KEYS}
    if view["matmul"] is not None:
        view["matmul"] = {"mxu": view["matmul"]["mxu"]}
    view["classes"] = (params["classes"][c],)
    return view


DevicePolicy = dict


def _cpu_full(params, cpu_dense, config_id):
    """Spread the row payload's [B, c_own] CPU columns onto the [B, L] leaf
    axis: column j of row b is leaf ``own_cpu_leaf[config_id[b], j]``.  A
    config id outside [0, G) owns no column."""
    B = cpu_dense.shape[0]
    L = params["leaf_op"].shape[0]
    table = params["own_cpu_leaf"]                           # [G, c_own]
    G = table.shape[0]
    in_range = (config_id >= 0) & (config_id < G)
    idx = jnp.where(in_range[:, None],
                    jnp.take(table, jnp.clip(config_id, 0, G - 1), axis=0), L)
    buf = jnp.zeros((B, L + 1), dtype=bool)
    buf = buf.at[jnp.arange(B)[:, None], idx].set(cpu_dense)
    return buf[:, :L]


def _leaf_op_cascade(leaf_op, eq, incl, dfa_leaf_val, cpu_lane,
                     num_cmp=None, rel_res=None, leaf_movf=None):
    """Shared op-code dispatch: per-leaf boolean results from the lane's
    primitive comparisons (identical semantics in every body).  ``leaf_op``
    is the corpus's [L] or a batch's own [B, l_own].

    ``num_cmp`` is the numeric lane's (gt, ge, lt, le) [B, L] quadruple
    (None: no numeric leaves); ``rel_res`` the relation lane's [B, L]
    bitmask-gather result; ``leaf_movf`` the membership-overflow mask
    spread to the leaf axis (ovf_assist): overflowed incl/excl leaves read
    their exact precomputed answer from the dense CPU columns — note the
    EXCL branch reads ``cpu_lane`` directly (the encoder stores the final
    excl answer, not the membership bit)."""
    op = leaf_op[None, :] if leaf_op.ndim == 1 else leaf_op
    if leaf_movf is None:
        incl_eff, excl_eff = incl, ~incl
    else:
        incl_eff = jnp.where(leaf_movf, cpu_lane, incl)
        excl_eff = jnp.where(leaf_movf, cpu_lane, ~incl)
    if num_cmp is None:
        num_res = False
    else:
        gt, ge, lt, le = num_cmp
        num_res = jnp.where(
            op == OP_NUM_GT, gt,
            jnp.where(op == OP_NUM_GE, ge,
                      jnp.where(op == OP_NUM_LT, lt, le)))
    tail = jnp.where(
        (op == OP_CPU) | (op == OP_TREE_CPU), cpu_lane,
        jnp.where(op >= OP_NUM_GT,
                  jnp.where(op == OP_RELATION,
                            rel_res if rel_res is not None else False,
                            num_res),
                  False))  # OP_ERROR → False
    return jnp.where(
        op == OP_EQ, eq,
        jnp.where(
            op == OP_NEQ, ~eq,
            jnp.where(
                op == OP_INCL, incl_eff,
                jnp.where(
                    op == OP_EXCL, excl_eff,
                    jnp.where(op == OP_REGEX_DFA, dfa_leaf_val, tail),
                ),
            ),
        ),
    )


def _verdict_from_tables(params, cond, rule):
    """Shared tail: per-config verdicts ∧ over evaluators of (¬cond ∨ rule)."""
    skipped = params["eval_has_cond"][None, :, :] & ~cond
    contrib = jnp.where(skipped, True, rule)
    verdict = jnp.all(contrib, axis=-1)  # [B, G]
    return verdict, (rule, skipped)


def class_widths(cp) -> dict:
    """One class's widths, off its operands (``to_device``'s class dict)."""
    own = cp["own"]
    dfa = cp["dfa_tables"] is not None
    return {
        "configs": int(own["leaf"].shape[-2]),
        "leaf_cols_per_row": int(own["leaf"].shape[-1]) // OWN_FIELDS,
        "dfa_rows_per_row": int(cp["config_dfa_rows"].shape[-1]) if dfa else 0,
        "dfa_states": int(cp["dfa_tables"].shape[-2]) if dfa else 0,
        "evaluators": int(own["evals"].shape[-1]) // 3,
        "operand_bytes": operand_bytes(cp),
    }


def kernel_widths(params, own: bool = True) -> dict:
    """What /debug/vars reports of one served row's work: ``dfa_rows_total``
    is the rows of the DFA stores and ``dfa_rows_per_row`` what one request
    row has scanned (its class's D on ``eval_own``, R on a dense body; both
    0 without a device DFA lane); ``leaf_cols_per_row`` is the leaf columns
    evaluated for it (its class's l_own, or L while dense); ``dfa_states``
    is S, the state axis of its class's table store (every table of a class
    padded to the class's largest DFA's count, in whole tiles of 8).  With
    more than one size class the scalars read the LARGEST class (what the
    costliest row pays; one class: the corpus's) and ``classes`` lists every
    class's own.  ``own``: an entry that returns own-config results (False:
    the mesh step, which is dense)."""
    classes = [class_widths(cp) for cp in params["classes"]]
    R = sum(int(cp["dfa_table_of_row"].shape[-1]) for cp in params["classes"]
            if cp["dfa_tables"] is not None)
    out = {"dfa_rows_per_row": 0, "dfa_rows_total": R,
           "dfa_states": max(c["dfa_states"] for c in classes),
           "leaf_cols_per_row": int(params["leaf_op"].shape[-1])}
    if own:
        out.update(
            leaf_cols_per_row=max(c["leaf_cols_per_row"] for c in classes),
            dfa_rows_per_row=max(c["dfa_rows_per_row"] for c in classes),
            classes=classes)
    elif R:
        out["dfa_rows_per_row"] = R
    return out


def operand_bytes(params) -> int:
    """Bytes of an operand pytree, summed over its arrays."""
    return int(sum(a.nbytes for a in jax.tree_util.tree_leaves(params)))


def _pick(x, idx):
    """``x[b, idx[b, j]]`` for x [B, N, ...] and idx [B, J] -> [B, J, ...];
    zero / False where idx is outside [0, N).  A one-hot mask-reduce over
    the (small, own-config) axis N: exact in any dtype and gather-free
    (gathers serialize on TPU)."""
    hit = idx[:, :, None] == jnp.arange(x.shape[1], dtype=idx.dtype)  # [B, J, N]
    hit = hit.reshape(hit.shape + (1,) * (x.ndim - 2))
    if x.dtype == jnp.bool_:
        return jnp.any(hit & x[:, None], axis=2)
    return jnp.sum(jnp.where(hit, x[:, None], 0), axis=2, dtype=x.dtype)


def is_wide(tables) -> bool:
    """Whether a table store holds state ids past 255 (u16 next states,
    compiler/compile.py dfa_state_dtype): its scan is the wide one."""
    return tables.dtype != jnp.uint8


def _wide_step_maps(own_tables, byte_oh):
    """[LB, D, S, B] f32 step maps of u16 tables: each next state as two
    base-256 digits, each a whole number under 256 and so exact in bf16, one
    bf16 matmul a digit with an f32 result (one non-zero term a sum), then
    hi * 256 + lo in f32, exact to 2**24.  A bf16 map or carry would round a
    state id past 256 to its neighbour (bf16 holds 8 bits of mantissa)."""
    oh = byte_oh.astype(jnp.bfloat16)

    def digit(t):
        return jnp.einsum("bdsc,bdlc->ldsb", t.astype(jnp.bfloat16), oh,
                          preferred_element_type=jnp.float32)

    return digit(own_tables >> 8) * 256.0 + digit(own_tables & 0xFF)


def _own_dfa_row_res(params, cfg, in_range, attr_bytes, cdt):
    """Own-row DFA scan of one size class (``params``: its operands):
    evaluates only the DFA rows ``config_dfa_rows[cfg]`` names and returns
    their accepts [B, D], False on the -1 padding and on rows of no member
    of the class (``cfg`` is the clipped table row).

    A store of u8 tables (S <= 256) scans in ``cdt`` (bf16 on the chip: ids
    up to 255 are exact there).  A u16 store (S past 256) scans in f32 from
    ``_wide_step_maps``, whatever ``cdt`` is.

    The sequential part keeps the batch on the minor axis: its carry is
    [D, B] and a step reads [D, S, B], so B fills the lanes and S (whole
    tiles of 8, compiler/compile.py) the sublanes with no padding, and the
    per-step reduce over S stays inside a lane.  With [B, D, S] a step's
    two minor axes (18 x 72) were padded to 24 x 128 and reduced across
    lanes: 2.9 ms a launch of 256 rows at D 18, S 72 against 0.8 ms (chip
    micro-run, PERF.md section 6, PR 32)."""
    tables = params["dfa_tables"]                            # [T, S, 256] u8 / u16
    S = tables.shape[1]
    own = jnp.where(in_range[:, None],
                    jnp.take(params["config_dfa_rows"], cfg, axis=0), -1)  # [B, D]
    row = jnp.maximum(own, 0)
    tab = jnp.take(params["dfa_table_of_row"], row)          # [B, D]
    slot = jnp.take(params["dfa_byte_slot"], row)            # [B, D]
    own_bytes = jnp.take_along_axis(
        attr_bytes, slot[:, :, None], axis=1)                # [B, D, LB] u8
    # whole [S, 256] tables fetched once a launch, from the deduped axis
    own_tables = jnp.take(tables, tab, axis=0)               # [B, D, S, 256]
    # every byte position's S -> S transition map at once (next-state values
    # <= 255 and 0/1 one-hots: one non-zero term a sum, exact in bf16, maps
    # and carry alike), so the sequential part below carries [D, B] and
    # touches [D, S, B] a step
    byte_oh = own_bytes[..., None] == jnp.arange(256, dtype=own_bytes.dtype)
    if is_wide(tables):
        sdt = jnp.float32
        step_maps = _wide_step_maps(own_tables, byte_oh)     # [LB, D, S, B]
    else:
        sdt = cdt
        step_maps = jnp.einsum(
            "bdsc,bdlc->ldsb", own_tables.astype(cdt), byte_oh.astype(cdt),
            preferred_element_type=cdt)                      # [LB, D, S, B]
    iota_s = jnp.arange(S, dtype=sdt)

    def dfa_step(state, step_map):  # state [D, B]; step_map [D, S, B]
        nxt = jnp.sum(jnp.where(
            state[:, None, :] == iota_s[:, None], step_map, 0), axis=1)
        return nxt.astype(sdt), None

    # init carry derived from a varying input (zero-multiplied) so its
    # manual-mesh "varying" type matches inside shard_map
    init = (own_bytes[:, :, 0].astype(sdt) * 0).T
    final, _ = jax.lax.scan(dfa_step, init, step_maps)
    accept = jnp.take(params["dfa_accept"], tab, axis=0)     # [B, D, S] bool
    return (own >= 0) & jnp.any(
        accept & (final.T[..., None] == iota_s), axis=-1)    # [B, D]


def eval_own(params, attrs_val, members_c, cpu_dense, config_id,
             attr_bytes=None, byte_ovf=None, attrs_num=None, num_valid=None,
             rel_rows=None, member_ovf=None):
    """Each request against its OWN config only (see module doc): returns
    (own verdict [B], own rule results [B, E], own skipped flags [B, E]),
    bit for bit what selecting row ``config_id`` of the dense body's
    results gives; a config id outside [0, G) reads False throughout.

    One class at a time: every class of ``params["classes"]`` evaluates the
    batch at its own widths and answers False for rows of no member, and
    the answers are OR-ed.  The served launches hand it ``class_view``s (one
    class, that class's rows, E = the class's); whole operands evaluate
    every row at every class's widths and return the corpus's E columns."""
    if attrs_val.dtype != jnp.int32:
        attrs_val = attrs_val.astype(jnp.int32)
    if members_c.dtype != jnp.int32:
        members_c = members_c.astype(jnp.int32)
    mm = params.get("matmul")
    cdt = mm["mxu"].dtype if mm is not None else jnp.float32
    operands = (attrs_val, members_c, cpu_dense, config_id, attr_bytes,
                byte_ovf, attrs_num, num_valid, rel_rows, member_ovf)
    parts = [_eval_own_class(params, cp, cdt, *operands)
             for cp in params["classes"]]
    E = max(rule.shape[1] for _, rule, _, _ in parts)
    if params.get("eval_rule") is not None:
        E = params["eval_rule"].shape[-1]
    out = None
    for verdict, rule, skipped, member in parts:
        extra = E - rule.shape[1]
        if extra:
            # evaluator columns past the class's read TRUE_SLOT in the
            # corpus-wide layout: rule True, never skipped
            B = rule.shape[0]
            rule = jnp.concatenate(
                [rule, jnp.broadcast_to(member[:, None], (B, extra))], axis=1)
            skipped = jnp.concatenate(
                [skipped, jnp.zeros((B, extra), dtype=bool)], axis=1)
        out = (verdict, rule, skipped) if out is None else (
            out[0] | verdict, out[1] | rule, out[2] | skipped)
    return out


def _eval_own_class(params, cp, cdt, attrs_val, members_c, cpu_dense,
                    config_id, attr_bytes, byte_ovf, attrs_num, num_valid,
                    rel_rows, member_ovf):
    """``eval_own`` at one class's widths: (verdict, rule [B, E_c], skipped
    [B, E_c], member [B]), all False on rows whose config is no member."""
    own = cp["own"]
    B = attrs_val.shape[0]
    local = cp["cfg_local"]                                  # [G]
    G = local.shape[0]
    row = jnp.take(local, jnp.clip(config_id, 0, G - 1))     # [B] table row
    in_range = (config_id >= 0) & (config_id < G) & (row >= 0)
    cfg = jnp.maximum(row, 0)

    with jax.named_scope("own_gather"):
        tab = jnp.take(own["leaf"], cfg, axis=0).reshape(B, -1, OWN_FIELDS)
        levels = []
        for children, is_and in own["levels"]:
            node_and = jnp.take(is_and, cfg, axis=0)         # [B, n]
            levels.append((jnp.take(children, cfg, axis=0).reshape(
                node_and.shape + (-1,)), node_and))          # [B, n, w]
        evals = jnp.take(own["evals"], cfg, axis=0).reshape(B, 3, -1)
    const = tab[..., OWN_CONST]                              # [B, l_own]

    with jax.named_scope("own_leaf_compares"):
        eq = _pick(attrs_val, tab[..., OWN_ATTR]) == const
        cpu_lane = _pick(cpu_dense, tab[..., OWN_CPU])
    with jax.named_scope("membership"):
        memb = _pick(members_c, tab[..., OWN_MEMBER])        # [B, l_own, K]
        incl = jnp.any(memb == const[..., None], axis=-1)
        leaf_movf = None
        if member_ovf is not None:
            leaf_movf = _pick(member_ovf, tab[..., OWN_MEMBER])
    wide = cp["dfa_tables"] is not None and is_wide(cp["dfa_tables"])
    with jax.named_scope("dfa_scan_wide" if wide else "dfa_scan"):
        if cp["dfa_tables"] is not None and attr_bytes is not None:
            own_res = _own_dfa_row_res(cp, cfg, in_range, attr_bytes, cdt)
            # overflowed values: exact answer precomputed into the CPU lane
            dfa_leaf_val = jnp.where(_pick(byte_ovf, tab[..., OWN_BYTE]),
                                     cpu_lane, _pick(own_res, tab[..., OWN_DFA]))
        else:
            dfa_leaf_val = cpu_lane  # regexes ride the CPU lane entirely

    num_cmp = None
    if params.get("leaf_num_slot") is not None and attrs_num is not None:
        lv = _pick(attrs_num, tab[..., OWN_NUM])
        lok = _pick(num_valid, tab[..., OWN_NUM])
        num_cmp = (lok & (lv > const), lok & (lv >= const),
                   lok & (lv < const), lok & (lv <= const))
    rel_res = None
    if params.get("rel_bits") is not None and rel_rows is not None:
        col = tab[..., OWN_REL_COL]
        byte = params["rel_bits"][_pick(rel_rows, tab[..., OWN_REL_SLOT]),
                                  col >> 3].astype(jnp.int32)
        rel_res = ((byte >> (col & 7)) & 1) != 0

    with jax.named_scope("own_circuit"):
        res = _leaf_op_cascade(tab[..., OWN_OP], eq, incl, dfa_leaf_val,
                               cpu_lane, num_cmp, rel_res, leaf_movf)
        buffer = jnp.concatenate(
            [jnp.ones((B, 1), dtype=bool), jnp.zeros((B, 1), dtype=bool), res],
            axis=1)
        for children, is_and in levels:                      # [B, n, w], [B, n]
            ch = _pick(buffer, children.reshape(B, -1)).reshape(children.shape)
            node = jnp.where(is_and, jnp.all(ch, axis=-1), jnp.any(ch, axis=-1))
            buffer = jnp.concatenate([buffer, node], axis=1)
        rule = _pick(buffer, evals[:, 0])                    # [B, E]
        skipped = (evals[:, 2] != 0) & ~_pick(buffer, evals[:, 1])
        verdict = jnp.all(skipped | rule, axis=-1)
        return (verdict & in_range, rule & in_range[:, None],
                skipped & in_range[:, None], in_range)


# ---------------------------------------------------------------------------
# matmul lane (MXU)
# ---------------------------------------------------------------------------


def _eval_verdicts_matmul(params, attrs_val, members_c, cpu_dense, config_id,
                          attr_bytes, byte_ovf, attrs_num=None,
                          num_valid=None, rel_rows=None, member_ovf=None):
    mm = params["matmul"]
    f32 = jnp.float32
    cdt = mm["rule_m"].dtype
    B = attrs_val.shape[0]
    attr_oh = mm["attr_onehot"]                              # [A, L] f32
    const = params["leaf_const"].astype(f32)                 # [L]

    # ---- leaf selection: one-hot matmuls, exact in f32 -------------------
    with jax.named_scope("leaf_compares"):
        val = jnp.matmul(attrs_val.astype(f32), attr_oh, precision=_HIGH)  # [B, L]
        eq = val == const[None, :]
    with jax.named_scope("membership"):
        memb = jnp.einsum(
            "bmk,ml->bkl", members_c.astype(f32), mm["memb_onehot"], precision=_HIGH
        )                                                        # [B, K, L]
        incl = jnp.any(memb == const[None, None, :], axis=1)     # [B, L]

    cpu_lane = _cpu_full(params, cpu_dense, config_id)       # [B, L]

    # ---- device regex lane: DFA scan, transitions as batched matmuls -----
    with jax.named_scope("dfa_scan"):
        if "dfa_tables_f" in mm and attr_bytes is not None:
            tables = mm["dfa_tables_f"]           # [R, S, 256] cdt, f32 if wide
            R, S = tables.shape[0], tables.shape[1]
            tdt = tables.dtype
            prec = _HIGH if tdt == f32 else None
            # spread each row's attr bytes from its slot: [B, NB, LB] → [B, R, LB]
            row_bytes = jnp.einsum(
                "bnl,nr->brl", attr_bytes.astype(cdt), mm["slot_row_oh"],
                preferred_element_type=f32,
            )
            iota_s = jnp.arange(S, dtype=f32)
            iota_c = jnp.arange(256, dtype=f32)

            def dfa_step(state, byte_col):  # state [B,R] f32; byte_col [B,R] f32
                byte_oh = (byte_col[..., None] == iota_c).astype(tdt)   # [B,R,256]
                # per-state next-state given this byte: [R,S,256] × [B,R,256]
                nxt_by_state = jnp.einsum(
                    "rsc,brc->brs", tables, byte_oh, preferred_element_type=f32,
                    precision=prec)
                st_oh = (state[..., None] == iota_s).astype(f32)
                nxt = jnp.sum(st_oh * nxt_by_state, axis=-1)
                return nxt, None

            # derive the scan's init carry from a varying input (zero-multiplied)
            # so its manual-mesh "varying" type matches inside shard_map
            init = row_bytes[:, :, 0] * 0.0
            final, _ = jax.lax.scan(dfa_step, init, jnp.transpose(row_bytes, (2, 0, 1)))
            final_oh = (final[..., None] == iota_s).astype(cdt)
            dfa_row_res = jnp.einsum(
                "brs,rs->br", final_oh, mm["dfa_accept_f"], preferred_element_type=f32
            ) > 0.5                                              # [B, R]
            leaf_dfa = jnp.einsum(
                "br,rl->bl", dfa_row_res.astype(cdt), mm["row_leaf_oh"],
                preferred_element_type=f32,
            ) > 0.5
            leaf_bovf = jnp.einsum(
                "bn,nl->bl", byte_ovf.astype(cdt), mm["slot_leaf_oh"],
                preferred_element_type=f32,
            ) > 0.5
            # overflowed values: exact answer precomputed into the CPU lane
            dfa_leaf_val = jnp.where(leaf_bovf, cpu_lane, leaf_dfa)
        else:
            dfa_leaf_val = cpu_lane  # regexes ride the CPU lane entirely

    # ---- numeric lane: slot-wise int32 compares, mask-spread (no gather,
    # no f32 round-trip of the values — exactness by construction) --------
    num_cmp = None
    if params.get("leaf_num_slot") is not None and attrs_num is not None:
        num_mask = mm["num_slot_leaf_mask"]                  # [NN, L] bool
        iconst = params["leaf_const"][None, None, :]         # [1, 1, L] i32
        v = attrs_num[:, :, None]                            # [B, NN, 1]
        lane_ok = num_valid[:, :, None] & num_mask[None]     # [B, NN, L]
        num_cmp = (
            jnp.any(lane_ok & (v > iconst), axis=1),
            jnp.any(lane_ok & (v >= iconst), axis=1),
            jnp.any(lane_ok & (v < iconst), axis=1),
            jnp.any(lane_ok & (v <= iconst), axis=1),
        )

    # ---- relation lane: exact one-hot row selection per slot over the
    # unpacked per-leaf column matrix (0/1 products: exact) ---------------
    rel_res = None
    if params.get("rel_bits") is not None and rel_rows is not None:
        rel_mat = mm["rel_leaf_mat"]                         # [Rp, L]
        Rp = rel_mat.shape[0]
        iota_r = jnp.arange(Rp, dtype=f32)
        acc = jnp.zeros((B, rel_mat.shape[1]), dtype=f32)
        for n_i in range(mm["rel_slot_leaf_oh"].shape[0]):   # static, small
            oh = (rel_rows[:, n_i].astype(f32)[:, None]
                  == iota_r[None, :]).astype(cdt)            # [B, Rp]
            vals = jnp.matmul(oh, rel_mat, preferred_element_type=f32)
            acc = acc + vals * mm["rel_slot_leaf_oh"][n_i][None, :].astype(f32)
        rel_res = acc > 0.5

    # ---- membership-overflow assist: spread the [B, M] mask to leaves ---
    leaf_movf = None
    if member_ovf is not None:
        leaf_movf = jnp.matmul(
            member_ovf.astype(cdt), mm["memb_onehot"].astype(cdt),
            preferred_element_type=f32) > 0.5                # [B, L]

    with jax.named_scope("circuit"):
        res = _leaf_op_cascade(params["leaf_op"], eq, incl, dfa_leaf_val,
                               cpu_lane, num_cmp, rel_res, leaf_movf)

        # ---- boolean circuit: per-level count matmuls ------------------------
        true_col = jnp.ones((B, 1), dtype=bool)
        false_col = jnp.zeros((B, 1), dtype=bool)
        buffer = jnp.concatenate([true_col, false_col, res], axis=1)
        for m, (children, is_and) in zip(mm["level_mats"], params["levels"]):
            width = children.shape[1]  # static: the level's padded child count
            counts = jnp.matmul(
                buffer.astype(cdt), m.T, preferred_element_type=f32
            )                                                    # [B, rows]
            # And-padding children point at TRUE (count includes them); Or-padding
            # at FALSE (adds 0) — so count==width ≡ all, count>0 ≡ any
            node = jnp.where(is_and[None, :], counts >= width - 0.5, counts > 0.5)
            buffer = jnp.concatenate([buffer, node], axis=1)

        # ---- per-config rule/cond extraction: one-hot matmuls ----------------
        buf16 = buffer.astype(cdt)
        G, E = params["eval_rule"].shape
        rule = (jnp.matmul(buf16, mm["rule_m"].T, preferred_element_type=f32) > 0.5)
        cond = (jnp.matmul(buf16, mm["cond_m"].T, preferred_element_type=f32) > 0.5)
        return _verdict_from_tables(params, cond.reshape(B, G, E), rule.reshape(B, G, E))


# ---------------------------------------------------------------------------
# gather lane (semantic reference / large-interner fallback)
# ---------------------------------------------------------------------------


def _eval_verdicts_gather(params, attrs_val, members_c, cpu_dense, config_id,
                          attr_bytes, byte_ovf, attrs_num=None,
                          num_valid=None, rel_rows=None, member_ovf=None):
    leaf_op = params["leaf_op"]          # [L]
    leaf_attr = params["leaf_attr"]      # [L]
    leaf_const = params["leaf_const"]    # [L]

    B = attrs_val.shape[0]

    # ---- leaf evaluation -------------------------------------------------
    with jax.named_scope("leaf_compares"):
        val = jnp.take(attrs_val, leaf_attr, axis=1)            # [B, L]
        eq = val == leaf_const[None, :]
    with jax.named_scope("membership"):
        memb = jnp.take(members_c, params["member_slot_of_leaf"], axis=1)  # [B, L, K]
        incl = jnp.any(memb == leaf_const[None, :, None], axis=-1)

    cpu_lane = _cpu_full(params, cpu_dense, config_id)      # [B, L]

    # ---- device regex lane: DFA scan over value bytes --------------------
    with jax.named_scope("dfa_scan"):
        if params["leaf_dfa_row"] is not None and attr_bytes is not None:
            # every size class's store in turn, their rows laid end to end
            # (``leaf_dfa_row`` indexes that axis)
            row_res, row_slot = [], []
            for cp in params["classes"]:
                if cp["dfa_tables"] is None:
                    continue
                tables = cp["dfa_tables"]       # [T_c, S_c, 256] uint8 (deduped)
                # per-row table index: rows sharing an automaton share one table
                tab_idx = cp["dfa_table_of_row"][None, :]        # [1, R_c]
                row_bytes = jnp.take(attr_bytes, cp["dfa_byte_slot"], axis=1)  # [B, R_c, LB]

                def dfa_step(states, byte_col, tables=tables, tab_idx=tab_idx):
                    # states [B,R_c] i32, byte_col [B,R_c] u8
                    nxt = tables[tab_idx, states, byte_col.astype(jnp.int32)]
                    return nxt.astype(jnp.int32), None

                # init carry derived from a varying input (zero-multiplied) so
                # its manual-mesh "varying" type matches inside shard_map
                init = (row_bytes[:, :, 0] * 0).astype(jnp.int32)
                final, _ = jax.lax.scan(dfa_step, init, jnp.transpose(row_bytes, (2, 0, 1)))
                row_res.append(cp["dfa_accept"][tab_idx, final])  # [B, R_c]
                row_slot.append(cp["dfa_byte_slot"])
            dfa_row_res = jnp.concatenate(row_res, axis=1)
            leaf_dfa = jnp.take(dfa_row_res, params["leaf_dfa_row"], axis=1)  # [B, L]
            leaf_slot = jnp.take(jnp.concatenate(row_slot), params["leaf_dfa_row"])
            leaf_bovf = jnp.take(byte_ovf, leaf_slot, axis=1)    # [B, L]
            dfa_leaf_val = jnp.where(leaf_bovf, cpu_lane, leaf_dfa)
        else:
            dfa_leaf_val = cpu_lane  # regexes ride the CPU lane entirely

    # ---- numeric lane: gather each leaf's slot value, compare int32 ------
    num_cmp = None
    if params.get("leaf_num_slot") is not None and attrs_num is not None:
        lv = jnp.take(attrs_num, params["leaf_num_slot"], axis=1)    # [B, L]
        lok = jnp.take(num_valid, params["leaf_num_slot"], axis=1)
        ic = leaf_const[None, :]
        num_cmp = (lok & (lv > ic), lok & (lv >= ic),
                   lok & (lv < ic), lok & (lv <= ic))

    # ---- relation lane: bitmask gather through (entity row, group col) ---
    rel_res = None
    if params.get("rel_bits") is not None and rel_rows is not None:
        rows_l = jnp.take(rel_rows, params["leaf_rel_slot"], axis=1)  # [B, L]
        col = params["leaf_rel_col"]                                  # [L]
        byte = params["rel_bits"][rows_l, (col >> 3)[None, :]].astype(
            jnp.int32)                                                # [B, L]
        rel_res = ((byte >> (col & 7)[None, :]) & 1) != 0

    # ---- membership-overflow assist ---------------------------------------
    leaf_movf = None
    if member_ovf is not None:
        leaf_movf = jnp.take(member_ovf, params["member_slot_of_leaf"],
                             axis=1)                                  # [B, L]

    with jax.named_scope("circuit"):
        res = _leaf_op_cascade(leaf_op, eq, incl, dfa_leaf_val, cpu_lane,
                               num_cmp, rel_res, leaf_movf)

        # ---- boolean-circuit reduction, level by level -----------------------
        true_col = jnp.ones((B, 1), dtype=bool)
        false_col = jnp.zeros((B, 1), dtype=bool)
        buffer = jnp.concatenate([true_col, false_col, res], axis=1)
        for children, is_and in params["levels"]:
            ch = jnp.take(buffer, children.reshape(-1), axis=1)
            ch = ch.reshape(B, children.shape[0], children.shape[1])
            node = jnp.where(is_and[None, :], jnp.all(ch, axis=-1), jnp.any(ch, axis=-1))
            buffer = jnp.concatenate([buffer, node], axis=1)

        # ---- per-config verdicts ---------------------------------------------
        cond = jnp.take(buffer, params["eval_cond"].reshape(-1), axis=1)
        rule = jnp.take(buffer, params["eval_rule"].reshape(-1), axis=1)
        G, E = params["eval_rule"].shape
        return _verdict_from_tables(
            params, cond.reshape(B, G, E), rule.reshape(B, G, E)
        )


def eval_verdicts(
    params: DevicePolicy,
    attrs_val: jnp.ndarray,      # [B, A] int32
    members_c: jnp.ndarray,      # [B, M, K] int32 (compact membership)
    cpu_dense: jnp.ndarray,      # [B, c_own] bool (own config's CPU columns)
    config_id: jnp.ndarray,      # [B] int32: whose columns cpu_dense carries
    attr_bytes: Optional[jnp.ndarray] = None,  # [B, NB, LB] uint8
    byte_ovf: Optional[jnp.ndarray] = None,    # [B, NB] bool
    attrs_num: Optional[jnp.ndarray] = None,   # [B, NN] int32 (numeric lane)
    num_valid: Optional[jnp.ndarray] = None,   # [B, NN] bool
    rel_rows: Optional[jnp.ndarray] = None,    # [B, NR] int32 (relation lane)
    member_ovf: Optional[jnp.ndarray] = None,  # [B, M] bool (ovf_assist)
) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, jnp.ndarray]]:
    """The dense body: (verdict [B, G] bool, (rule_results [B, G, E],
    skipped [B, G, E])), every config's column exact.  Runs the matmul
    formulation where ``to_device(dense=True)`` built its operands, the
    gather formulation otherwise."""
    # ids travel as int16 when the interner fits (compiler/pack.py
    # wire_dtype); upcast on device AFTER the transfer
    if attrs_val.dtype != jnp.int32:
        attrs_val = attrs_val.astype(jnp.int32)
    if members_c.dtype != jnp.int32:
        members_c = members_c.astype(jnp.int32)
    operands = (params, attrs_val, members_c, cpu_dense, config_id, attr_bytes,
                byte_ovf, attrs_num, num_valid, rel_rows, member_ovf)
    mm = params.get("matmul")
    if mm is not None and "rule_m" in mm:
        return _eval_verdicts_matmul(*operands)
    return _eval_verdicts_gather(*operands)


def _select_own(config_id: jnp.ndarray, n_configs: int) -> jnp.ndarray:
    """[B, G] one-hot row mask of each request's own config (mask-reduce
    instead of take_along_axis: gathers serialize on TPU)."""
    return config_id[:, None] == jnp.arange(n_configs, dtype=config_id.dtype)[None, :]


def forward(params, attrs_val, members_c, cpu_dense, config_id,
            attr_bytes=None, byte_ovf=None, attrs_num=None, num_valid=None,
            rel_rows=None, member_ovf=None):
    """Canonical forward step: encoded micro-batch → (own verdicts [B],
    full verdict matrix [B, G]).  The single source of truth for
    verdict-selection logic (PolicyModel and the engine both use it)."""
    verdict, _ = eval_verdicts(
        params, attrs_val, members_c, cpu_dense, config_id, attr_bytes,
        byte_ovf, attrs_num, num_valid, rel_rows, member_ovf
    )
    own_mask = _select_own(config_id, verdict.shape[1])
    own = jnp.any(verdict & own_mask, axis=1)
    return own, verdict


_eval_jit = jax.jit(forward)


@partial(jax.jit, static_argnames=())
def eval_full_jit(params, attrs_val, members_c, cpu_dense, config_id,
                  attr_bytes=None, byte_ovf=None, attrs_num=None,
                  num_valid=None, rel_rows=None, member_ovf=None):
    """Like _eval_jit but returns only each request's own verdict and
    per-evaluator rule results + skipped flags [B, E] — what the pipeline's
    batched pattern-matching evaluators consume (runtime/engine.py): the
    request's own config alone (``eval_own``)."""
    return eval_own(params, attrs_val, members_c, cpu_dense, config_id,
                    attr_bytes, byte_ovf, attrs_num, num_valid, rel_rows,
                    member_ovf)


@partial(jax.jit, static_argnames=())
def eval_packed_jit(params, attrs_val, members_c, cpu_dense, config_id,
                    attr_bytes=None, byte_ovf=None, attrs_num=None,
                    num_valid=None, rel_rows=None, member_ovf=None):
    """Hot-path variant: one packed [B, 1+2E] bool result (own verdict,
    own rule results, own skipped) so the device→host read is a single
    small transfer — the link's round-trip latency dominates the batch
    budget, so one readback per batch is the contract."""
    own, own_rule, own_skipped = eval_full_jit(
        params, attrs_val, members_c, cpu_dense, config_id, attr_bytes,
        byte_ovf, attrs_num, num_valid, rel_rows, member_ovf
    )
    return jnp.concatenate([own[:, None], own_rule, own_skipped], axis=1)


# ---------------------------------------------------------------------------
# packed u8 bitmask readback: 8 verdicts per byte on the D2H link
# ---------------------------------------------------------------------------
#
# The packed [B, 1+2E] bool result still crosses the link as one byte per
# element (JAX bools are 1-byte).  The serving dispatchers read back a
# [B, W] uint8 bitmask instead (W = ceil((1+2E)/8)): ~8x fewer D2H bytes
# per batch.  Bit order is LITTLE (bit j of byte k = column k*8+j), matching
# np.unpackbits(bitorder="little") for the host-side decode — round-trip
# bit-exactness is pinned by tests/test_eval_lanes.py.

def packed_width(n_cols: int) -> int:
    """Bitmask bytes per row for an ``n_cols``-wide packed bool result."""
    return (n_cols + 7) // 8


def _bitpack_rows(mat):
    """Traced [B, C] bool → [B, ceil(C/8)] uint8 (little bit order)."""
    B, C = mat.shape
    W = packed_width(C)
    padded = jnp.zeros((B, W * 8), dtype=bool).at[:, :C].set(mat)
    weights = (1 << jnp.arange(8, dtype=jnp.int32))[None, None, :]
    return (padded.reshape(B, W, 8).astype(jnp.int32) * weights).sum(
        axis=-1).astype(jnp.uint8)


def unpack_verdicts(arr, n_cols: int) -> np.ndarray:
    """Host-side decode of a [B, W] uint8 bitmask readback back to the
    [B, n_cols] bool matrix eval_packed_jit would have returned."""
    a = np.asarray(arr)
    return np.unpackbits(a, axis=1, bitorder="little")[:, :n_cols].astype(bool)


def firing_columns(own_rule: np.ndarray, own_skipped: np.ndarray) -> np.ndarray:
    """Which-rule-fired attribution (ISSUE 9): the FIRST evaluator column
    that evaluated false and was not condition-skipped, per row — the same
    short-circuit order the reference pipeline denies in — or -1 for
    allowed rows (verdict ≡ all(skipped | rule), so a row is denied iff a
    firing column exists).  Pure vectorized numpy: one call per BATCH, the
    zero-per-request-Python contract of the native fast lane.

    Padded evaluator columns read TRUE_SLOT (rule=True) and can never
    fire.  Host-fallback rows past the fallback cap are denied fail-closed
    with rule[:]=False — they attribute to column 0, a synthetic denial
    documented in docs/observability.md."""
    fired = ~np.asarray(own_skipped, dtype=bool) & ~np.asarray(
        own_rule, dtype=bool)                                  # [B, E]
    first = fired.argmax(axis=1).astype(np.int32)              # [B]
    first[~fired.any(axis=1)] = -1
    return first


def unpack_attribution(packed, n_evaluators: int):
    """Per-batch decode of a bitpacked [B, W] uint8 readback into
    (verdict [B] uint8, firing [B] int32) — the native lane's one-shot
    column fold (bit 0 = own verdict, bits 1..E = rule results,
    E+1..2E = skipped)."""
    E = n_evaluators
    cols = unpack_verdicts(packed, 1 + 2 * E)
    verdict = cols[:, 0].astype(np.uint8)
    firing = firing_columns(cols[:, 1:1 + E], cols[:, 1 + E:1 + 2 * E])
    return verdict, firing


@partial(jax.jit, static_argnames=())
def eval_bitpacked_jit(params, attrs_val, members_c, cpu_dense, config_id,
                       attr_bytes=None, byte_ovf=None, attrs_num=None,
                       num_valid=None, rel_rows=None, member_ovf=None):
    """eval_packed_jit with the result bit-packed on device: the D2H
    readback is [B, ceil((1+2E)/8)] uint8 instead of [B, 1+2E] bool.
    The phases carry named scopes (own_gather, own_leaf_compares,
    membership, dfa_scan, own_circuit, bitpack under pattern_eval), so a
    device trace can be grouped by phase whatever the compiler calls its
    fusions."""
    with jax.named_scope("pattern_eval"):
        packed = eval_packed_jit(
            params, attrs_val, members_c, cpu_dense, config_id,
            attr_bytes, byte_ovf, attrs_num, num_valid, rel_rows, member_ovf)
        with jax.named_scope("bitpack"):
            return _bitpack_rows(packed)


def _extra_operands(db) -> tuple:
    """The ISSUE 14 operand tail of one DeviceBatch, as jnp arrays (None
    entries stay None — structural, like the DFA lane)."""
    return tuple(
        jnp.asarray(a) if a is not None else None
        for a in (db.attrs_num, db.num_valid, db.rel_rows, db.member_ovf))


# ---------------------------------------------------------------------------
# fused H2D staging: ONE host→device transfer per micro-batch
# ---------------------------------------------------------------------------
#
# The compact payload is 5-7 small tensors; each jnp.asarray is its own
# host→device transfer, and per-transfer latency (PCIe doorbells) stacks.
# The fused
# path concatenates every operand's bytes into one contiguous uint8 staging
# buffer on host, ships it in a single transfer, and bitcast-decodes the
# operands back out INSIDE the jitted kernel (static layout → static slices;
# the decode is free relative to the transfer it replaces).
#
# Bitcast byte order must match numpy's little-endian view;
# fused_h2d_supported verifies the round trip once per process, and where
# the backend disagrees (a big-endian host) neither lane launches at all.

_FUSED_FIELDS = ("attrs_val", "members_c", "cpu_dense", "config_id",
                 "attr_bytes", "byte_ovf", "attrs_num", "num_valid",
                 "rel_rows", "member_ovf")


def fuse_layout(fields) -> tuple:
    """The static layout of one staging buffer: (field, dtype, shape,
    offset, nbytes) per operand, laid end to end in the order given
    (``fields`` yields (name, dtype, shape)).  Hashable, and a function of
    the operand shapes alone, so one jit variant per (pad, eff) bucket as
    without it."""
    layout = []
    off = 0
    for name, dtype, shape in fields:
        shape = tuple(int(n) for n in shape)
        size = int(np.prod(shape, dtype=np.int64)) * np.dtype(dtype).itemsize
        layout.append((name, str(np.dtype(dtype)), shape, off, size))
        off += size
    return tuple(layout)


def fuse_bytes(arrays) -> np.ndarray:
    """The staging buffer itself: the (C-contiguous) operands' bytes end to
    end in one uint8 array, as ``fuse_layout`` of their shapes lays them."""
    return np.concatenate([a.view(np.uint8).reshape(-1) for a in arrays])


def fuse_batch(db) -> Tuple[np.ndarray, tuple]:
    """(staging buffer [N] uint8, static layout) for one DeviceBatch."""
    arrs = [(name, np.ascontiguousarray(getattr(db, name)))
            for name in _FUSED_FIELDS if getattr(db, name) is not None]
    layout = fuse_layout((name, a.dtype, a.shape) for name, a in arrs)
    return fuse_bytes(a for _, a in arrs), layout


def staged_h2d_bytes(db) -> int:
    """Exact request-operand bytes one launch of ``db`` stages host-to-
    device — the fused staging buffer size (sum of nbytes over the present
    _FUSED_FIELDS).  Pure shape arithmetic for the kernel-cost ledger: no
    copy, no fuse."""
    total = 0
    for name in _FUSED_FIELDS:
        arr = getattr(db, name)
        if arr is not None:
            total += arr.nbytes
    return total


def _defuse(buf, layout):
    """Decode the staged operands out of the fused buffer (traced: static
    slices + bitcasts, no data movement beyond the one transfer)."""
    out = {}
    for name, dt, shape, off, size in layout:
        seg = jax.lax.slice_in_dim(buf, off, off + size)
        if dt == "bool":
            out[name] = seg.reshape(shape) != 0
        elif dt == "uint8":
            out[name] = seg.reshape(shape)
        else:
            # bytes -> ids as a flat [n, itemsize] -> [n], then the shape:
            # with the byte axis behind the operand's own axes the TPU pads
            # every row of 2 or 4 bytes to a whole tile, and the decode
            # takes twice as long (PERF.md section 6, PR 31)
            npdt = np.dtype(dt)
            out[name] = jax.lax.bitcast_convert_type(
                seg.reshape(-1, npdt.itemsize), npdt).reshape(shape)
    return out


def _eval_staged(params, buf, layout):
    """Decode the staged operands and evaluate them: the body of both
    single-staging-buffer entries, under the kernel's named scopes."""
    with jax.named_scope("pattern_eval"):
        with jax.named_scope("defuse"):
            ops = _defuse(buf, layout)
        packed = eval_packed_jit(
            params, *(ops.get(name) for name in _FUSED_FIELDS))
        with jax.named_scope("bitpack"):
            return _bitpack_rows(packed)


@partial(jax.jit, static_argnames=("layout",))
def eval_fused_jit(params, buf, layout):
    """eval over a fused staging buffer: ONE H2D transfer in, one
    bit-packed [B, ceil((1+2E)/8)] uint8 readback out (decode host-side
    with ``unpack_verdicts``).  The engine lane's entry."""
    return _eval_staged(params, buf, layout)


@partial(jax.jit, static_argnames=("layout",))
def eval_bitpacked_staged_jit(params, buf, layout):
    """What the native lane serves: ``eval_bitpacked_jit`` with its
    operands decoded on the device out of ONE staged uint8 buffer (layout
    from ``fuse_layout``, static per (pad, eff)).  A function of its own
    so that a device trace names the served module."""
    return _eval_staged(params, buf, layout)


_FUSED_OK: Optional[bool] = None


@partial(jax.jit, static_argnames=("layout",))
def _defuse_probe(buf, layout):
    return tuple(_defuse(buf, layout).values())


def fused_h2d_supported() -> bool:
    """One-time probe that the backend's bitcast byte order matches numpy's
    view (little-endian), which ``_defuse`` decodes a staged buffer by."""
    global _FUSED_OK
    if _FUSED_OK is None:
        try:
            a16 = np.array([-7, 0, 1, 30000], dtype=np.int16)
            a32 = np.array([1, -2, 1 << 20], dtype=np.int32)
            buf = np.concatenate([a16.view(np.uint8).reshape(-1),
                                  a32.view(np.uint8).reshape(-1)])
            layout = (("attrs_val", "int16", (4,), 0, 8),
                      ("config_id", "int32", (3,), 8, 12))
            got16, got32 = _defuse_probe(jnp.asarray(buf), layout)
            _FUSED_OK = (np.array_equal(np.asarray(got16), a16)
                         and np.array_equal(np.asarray(got32), a32))
        except Exception:
            _FUSED_OK = False
    return _FUSED_OK


def require_fused_h2d() -> None:
    """Refuse to launch where the probe failed: a staged buffer would decode
    to wrong operands, and no other launch format serves."""
    if not fused_h2d_supported():
        raise RuntimeError(
            "the backend's bitcast byte order is not numpy's little-endian "
            "view, which a staged launch buffer is decoded by")


def dispatch_fused(params, db) -> "jax.Array":
    """Non-blocking launch of one compact batch with a single fused H2D
    transfer; raises where the backend's bitcast byte order failed the
    probe.  The result is the BIT-PACKED [B, W] uint8 readback (decode with
    ``unpack_verdicts``); the device→host copy starts eagerly so a later
    np.asarray only waits, never initiates."""
    require_fused_h2d()
    buf, layout = fuse_batch(db)
    out = eval_fused_jit(params, jnp.asarray(buf), layout)
    try:
        out.copy_to_host_async()
    except Exception:
        pass  # readback degrades to a blocking copy at np.asarray time
    return out


def eval_batch_jit(params, db) -> Tuple[np.ndarray, np.ndarray]:
    """Convenience wrapper: compact batch (compiler/pack.py DeviceBatch) →
    (own verdicts [B], full verdict matrix [B, G]) as numpy."""
    dfa = has_dfa(params)
    own, verdict = _eval_jit(
        params,
        jnp.asarray(db.attrs_val),
        jnp.asarray(db.members_c),
        jnp.asarray(db.cpu_dense),
        jnp.asarray(db.config_id),
        jnp.asarray(db.attr_bytes) if dfa else None,
        jnp.asarray(db.byte_ovf) if dfa else None,
        *_extra_operands(db),
    )
    return np.asarray(own), np.asarray(verdict)
