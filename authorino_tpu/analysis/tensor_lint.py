"""Tensor-IR lint: pure-host structural verification of a compiled snapshot.

Every transform between ``compile_corpus`` and the kernels (packing, dedup,
lane operand builds) preserves exactness only if the compiled artifacts obey
invariants the device code silently assumes — a ``dfa_table_of_row`` entry
past the table axis, a circuit child referencing a *later* buffer slot, or a
scatter map that is not an exact cover each produce silently wrong verdicts,
not crashes.  This module states those invariants once, as checks a host can
run in milliseconds, so a malformed snapshot is caught at reconcile time
(``--strict-verify``) or in CI, never as a wrong verdict under load.

Checks and their finding kinds (catalogue: docs/static_analysis.md):

  dfa-table-index    every dfa_table_of_row entry < n_dfa_tables (and >= 0)
  dfa-next-state     transition tables are [T, S, 256] with next-states < S,
                     of a dtype that can name S states (u16 past 256)
  circuit-order      And/Or children reference strictly earlier buffer slots
                     (acyclic + topologically ordered by construction)
  operand-range      eval tables / leaf attrs / slot maps inside their grids
  lane-contract      dtype + shape contracts of the gather and matmul lane
                     operand pytrees (to_device host build); the matmul
                     lane's tables are f32 past 256 states
  scatter-cover      a dedup plan's fan-out reproduces the batch exactly
  pack-grid          packed DeviceBatch axes match the policy's padded grid
  shard-stack        every mesh shard's padded grid matches shard 0's — the
                     stacked [S]-axis device pytree silently truncates or
                     misaligns operands if the ShapeTargets union missed an
                     axis (mesh lane, ISSUE 11)
  own-dfa-rows       config_dfa_rows[g] is exactly the set of DFA rows
                     reachable from config g's evaluators (ascending, -1
                     padded) — the own-row scan evaluates nothing else, so a
                     missing row reads False where the regex would match
  own-layout         every position of the own-config tables (OwnLayout)
                     maps back to the corpus slot it stands for: evaluator
                     references, each node's children and kind, each leaf's
                     fields, the row payload's CPU columns — the served
                     entry evaluates these tables and nothing else.  Held
                     for the corpus-wide layout AND for every size class's
                     (ISSUE 34): each config is in exactly one class, every
                     own position of every class maps back to its corpus
                     slot, a class's DFA rows are its members' and its
                     table store holds their tables, cut to states no
                     transition leaves, u8 at 256 states or fewer and u16
                     past (the dtype the served scan's arithmetic follows)
"""

from __future__ import annotations

from typing import Any, List, Optional, Sequence

import numpy as np

from ..compiler.compile import (
    NUMERIC_OPS,
    OP_CPU,
    OP_EQ,
    OP_ERROR,
    OP_EXCL,
    OP_INCL,
    OP_NEQ,
    OP_REGEX_DFA,
    OP_RELATION,
    OP_TREE_CPU,
    CompiledPolicy,
    dfa_state_dtype,
)
from . import Finding

__all__ = ["tensor_lint", "lint_snapshot", "lint_scatter_plan",
           "lint_device_batch", "lint_sharded_stack"]

_LAYER = "tensor_lint"
_KNOWN_OPS = (OP_EQ, OP_NEQ, OP_INCL, OP_EXCL, OP_CPU, OP_ERROR,
              OP_TREE_CPU, OP_REGEX_DFA) + NUMERIC_OPS + (OP_RELATION,)


def _err(kind: str, message: str, location: str = "", **detail) -> Finding:
    return Finding(kind=kind, message=message, layer=_LAYER,
                   severity="error", location=location, detail=detail)


def _leaf_base() -> int:
    return 2  # TRUE_SLOT, FALSE_SLOT precede the leaf block


def _check_dfa(policy: CompiledPolicy, out: List[Finding]) -> None:
    tables = policy.dfa_tables
    if tables.ndim != 3 or tables.shape[2] != 256:
        out.append(_err(
            "dfa-next-state",
            f"transition tables must be [T, S, 256], got {tables.shape}",
            "dfa_tables"))
        return
    T, S = int(tables.shape[0]), int(tables.shape[1])
    if tables.dtype.kind == "u" and S > 1 << (8 * tables.dtype.itemsize):
        out.append(_err(
            "dfa-next-state",
            f"{tables.dtype} tables cannot name {S} states (u16 past 256: "
            "compiler/compile.py dfa_state_dtype)", "dfa_tables"))
    # uint8 tables can't go negative, but the lint must not trust the
    # dtype it is auditing — a corrupt artifact may arrive signed
    if tables.size and (int(tables.min()) < 0 or int(tables.max()) >= S):
        bad = np.argwhere((tables < 0) | (tables >= S))[0]
        out.append(_err(
            "dfa-next-state",
            f"next-state {int(tables[tuple(bad)])} out of range [0, S={S}) "
            f"at table {int(bad[0])}, state {int(bad[1])}, byte {int(bad[2])}",
            "dfa_tables"))
    if policy.dfa_accept.shape != (T, S):
        out.append(_err(
            "dfa-next-state",
            f"accept mask shape {policy.dfa_accept.shape} != tables' ({T}, {S})",
            "dfa_accept"))
    rows = policy.dfa_table_of_row
    if rows.size and (int(rows.min()) < 0 or int(rows.max()) >= T):
        r = int(np.argmax((rows < 0) | (rows >= T)))
        out.append(_err(
            "dfa-table-index",
            f"dfa_table_of_row[{r}] = {int(rows[r])} outside [0, "
            f"n_dfa_tables={T})", "dfa_table_of_row"))
    R = int(rows.shape[0])
    ldr = policy.leaf_dfa_row
    if ldr.size and (int(ldr.min()) < 0 or int(ldr.max()) >= max(R, 1)):
        out.append(_err(
            "operand-range",
            f"leaf_dfa_row max {int(ldr.max())} outside [0, R={R})",
            "leaf_dfa_row"))
    A = policy.n_attrs
    dla = policy.dfa_leaf_attr
    if dla.size and (int(dla.min()) < 0 or int(dla.max()) >= A):
        out.append(_err(
            "operand-range",
            f"dfa_leaf_attr max {int(dla.max())} outside [0, A={A})",
            "dfa_leaf_attr"))
    abs_ = policy.attr_byte_slot
    if abs_.size and (int(abs_.min()) < -1
                      or int(abs_.max()) >= max(policy.n_byte_attrs, 1)):
        out.append(_err(
            "operand-range",
            f"attr_byte_slot outside [-1, n_byte_attrs="
            f"{policy.n_byte_attrs})", "attr_byte_slot"))


def _check_circuit(policy: CompiledPolicy, out: List[Finding]) -> None:
    """Children must reference strictly earlier buffer slots: the kernels
    evaluate level-by-level over a growing prefix, so a forward (or self)
    reference is either a cycle or a read of an undefined slot — both
    produce garbage verdicts, silently."""
    cursor = _leaf_base() + policy.n_leaves
    for l, (children, is_and) in enumerate(policy.levels):
        if children.ndim != 2 or is_and.shape != (children.shape[0],):
            out.append(_err(
                "circuit-order",
                f"level {l}: children {children.shape} / is_and "
                f"{is_and.shape} malformed", f"levels[{l}]"))
            return
        if children.size:
            lo, hi = int(children.min()), int(children.max())
            if lo < 0 or hi >= cursor:
                r, c = np.unravel_index(
                    int(np.argmax((children < 0) | (children >= cursor))),
                    children.shape)
                out.append(_err(
                    "circuit-order",
                    f"level {l} node {int(r)} child {int(c)} references "
                    f"buffer slot {int(children[r, c])}, but only slots "
                    f"[0, {cursor}) are defined at this level (forward "
                    f"reference = cycle or undefined read)",
                    f"levels[{l}]"))
        cursor += int(children.shape[0])
    # cursor is now buffer_size; eval tables must stay inside it
    if cursor != policy.buffer_size:
        out.append(_err(
            "operand-range",
            f"level rows sum to buffer size {cursor} != "
            f"policy.buffer_size {policy.buffer_size}", "levels"))


def _check_operands(policy: CompiledPolicy, out: List[Finding]) -> None:
    L, A, B = policy.n_leaves, policy.n_attrs, policy.buffer_size
    for name in ("eval_cond", "eval_rule"):
        t = getattr(policy, name)
        if t.shape != policy.eval_rule.shape:
            out.append(_err("operand-range",
                            f"{name} shape {t.shape} != eval_rule "
                            f"{policy.eval_rule.shape}", name))
            continue
        if t.size and (int(t.min()) < 0 or int(t.max()) >= B):
            g, e = np.unravel_index(
                int(np.argmax((t < 0) | (t >= B))), t.shape)
            out.append(_err(
                "operand-range",
                f"{name}[{int(g)}, {int(e)}] = {int(t[g, e])} outside the "
                f"padded result buffer [0, {B})", name))
    la = policy.leaf_attr
    if la.shape != (L,):
        out.append(_err("operand-range",
                        f"leaf_attr shape {la.shape} != [L={L}]", "leaf_attr"))
    elif la.size and (int(la.min()) < 0 or int(la.max()) >= A):
        out.append(_err(
            "operand-range",
            f"leaf_attr max {int(la.max())} outside [0, A={A})", "leaf_attr"))
    lo = policy.leaf_op
    if lo.size and not np.isin(lo, _KNOWN_OPS).all():
        i = int(np.argmax(~np.isin(lo, _KNOWN_OPS)))
        out.append(_err("operand-range",
                        f"leaf_op[{i}] = {int(lo[i])} is not a known op code",
                        "leaf_op"))
    mas = policy.member_attr_slot
    M = policy.n_member_attrs
    if mas.size and (int(mas.min()) < -1 or int(mas.max()) >= M):
        out.append(_err(
            "operand-range",
            f"member_attr_slot outside [-1, M={M})", "member_attr_slot"))
    ma = policy.member_attrs
    if ma.size and (int(ma.min()) < 0 or int(ma.max()) >= A):
        out.append(_err("operand-range",
                        f"member_attrs outside [0, A={A})", "member_attrs"))
    cll = policy.cpu_leaf_list
    if cll.size and (int(cll.min()) < 0 or int(cll.max()) >= L):
        out.append(_err("operand-range",
                        f"cpu_leaf_list outside [0, L={L})", "cpu_leaf_list"))
    if cll.shape[0] > policy.n_cpu_leaves:
        out.append(_err(
            "operand-range",
            f"{cll.shape[0]} CPU-lane leaves exceed the padded grid "
            f"C={policy.n_cpu_leaves}", "cpu_leaf_list"))
    if ma.shape[0] > M:
        out.append(_err(
            "operand-range",
            f"{ma.shape[0]} member attrs exceed the padded grid M={M}",
            "member_attrs"))
    # numeric lane (ISSUE 14)
    NN = int(getattr(policy, "n_num_attrs", 0) or 0)
    nas = getattr(policy, "num_attr_slot", None)
    if nas is not None and nas.size and (
            int(nas.min()) < -1 or int(nas.max()) >= max(NN, 1)):
        out.append(_err(
            "operand-range",
            f"num_attr_slot outside [-1, NN={NN})", "num_attr_slot"))
    if np.isin(lo, NUMERIC_OPS).any() and NN == 0:
        out.append(_err(
            "operand-range",
            "numeric leaves present but n_num_attrs == 0 (no value lane)",
            "num_attr_slot"))
    # relation lane (ISSUE 14)
    NR = int(getattr(policy, "n_rel_slots", 0) or 0)
    rb = getattr(policy, "rel_bits", None)
    has_rel_leaf = bool((lo == OP_RELATION).any()) if lo.size else False
    if has_rel_leaf and (NR == 0 or rb is None):
        out.append(_err(
            "operand-range",
            "relation leaves present but the relation lane is absent",
            "rel_bits"))
    if rb is not None:
        if rb.ndim != 2 or rb.dtype != np.uint8:
            out.append(_err(
                "operand-range",
                f"rel_bits must be a [Rp, W] uint8 bitmatrix, got "
                f"{rb.dtype} {rb.shape}", "rel_bits"))
        elif rb.shape[0] and rb[0].any():
            out.append(_err(
                "operand-range",
                "rel_bits row 0 (the reserved unknown-entity row) has set "
                "bits: unknown principals would gain memberships",
                "rel_bits"))
        lrs = getattr(policy, "leaf_rel_slot", None)
        if lrs is not None and lrs.size and (
                int(lrs.min()) < 0 or int(lrs.max()) >= max(NR, 1)):
            out.append(_err(
                "operand-range",
                f"leaf_rel_slot outside [0, NR={NR})", "leaf_rel_slot"))
        lrc = getattr(policy, "leaf_rel_col", None)
        if lrc is not None and rb.ndim == 2 and lrc.size and (
                int(lrc.min()) < 0 or int(lrc.max()) >= rb.shape[1] * 8):
            out.append(_err(
                "operand-range",
                f"leaf_rel_col outside the bitmatrix width "
                f"[0, {rb.shape[1] * 8})", "leaf_rel_col"))


_INT_DTYPES = (np.int32, np.int64)


def _check_own_rows(policy: CompiledPolicy, out: List[Finding]) -> None:
    """ISSUE 26 own-row scan layout, audited against its SOURCES by a walk of
    its own (top-down from each config's evaluator references; the compiler
    derives the table bottom-up).  Runs after circuit-order passed, so the
    recursion is bounded by the level count."""
    table = getattr(policy, "config_dfa_rows", None)
    G = int(policy.eval_rule.shape[0])
    if table is None or table.ndim != 2 or table.shape[0] != G \
            or table.shape[1] < 1:
        out.append(_err(
            "own-dfa-rows",
            f"config_dfa_rows must be [G={G}, D>=1], got "
            f"{None if table is None else table.shape}", "config_dfa_rows"))
        return
    base, L = _leaf_base(), policy.n_leaves
    is_dfa = policy.leaf_op == OP_REGEX_DFA
    starts = [base + L]
    for children, _ in policy.levels:
        starts.append(starts[-1] + int(children.shape[0]))
    memo: dict = {}

    def reach(buf: int) -> frozenset:
        if buf < base:
            return frozenset()
        if buf < base + L:
            return frozenset((int(policy.leaf_dfa_row[buf - base]),)) \
                if is_dfa[buf - base] else frozenset()
        hit = memo.get(buf)
        if hit is None:
            level = int(np.searchsorted(starts, buf, side="right")) - 1
            kids = policy.levels[level][0][buf - starts[level]]
            hit = memo[buf] = frozenset().union(
                *(reach(int(k)) for k in set(kids.tolist())))
        return hit

    for g in range(G):
        refs = set(policy.eval_cond[g].tolist()) | set(policy.eval_rule[g].tolist())
        want = sorted(frozenset().union(*(reach(r) for r in refs)))
        got = table[g].tolist()
        if got != want + [-1] * (len(got) - len(want)):
            out.append(_err(
                "own-dfa-rows",
                f"config_dfa_rows[{g}] = {got} but config {g}'s evaluators "
                f"reach DFA rows {want} (ascending, -1 padded)",
                "config_dfa_rows", config=g))
            return


def _check_own_layout(policy: CompiledPolicy, out: List[Finding],
                      own=None, cfgs=None, dfa_rows=None,
                      where: str = "own") -> None:
    """(``own``, ``cfgs``, ``dfa_rows``: one size class's tables, its member
    config rows and their [G_c, D_c] corpus DFA rows; left None, the
    corpus-wide layout of every config.)

    ISSUE 28 own-config layout, audited against its SOURCES without a
    walk: every own position is mapped back to the corpus's buffer slot and
    (1) each evaluator reference, (2) each own node's children and kind,
    (3) each own leaf's fields must equal the corpus's.  By induction over
    the levels the own circuit then computes the corpus circuit's value for
    every evaluator; padding maps back to -1 and so can be referenced by
    nothing.  The served entry evaluates only these tables, and the row
    payload's CPU columns mean what ``cpu_leaves`` says."""
    from ..compiler.compile import (OP_ERROR, OP_RELATION, OWN_ATTR, OWN_BYTE,
                                    OWN_CONST, OWN_CPU, OWN_DFA, OWN_MEMBER,
                                    OWN_NUM, OWN_OP, OWN_REL_COL,
                                    OWN_REL_SLOT)

    if own is None:
        own = getattr(policy, "own", None)
    if cfgs is None:
        cfgs = np.arange(policy.eval_rule.shape[0])
    if dfa_rows is None:
        dfa_rows = policy.config_dfa_rows
    G = int(cfgs.shape[0])
    base, L = _leaf_base(), policy.n_leaves

    def bad(msg: str, config: Optional[int] = None) -> None:
        out.append(_err("own-layout", msg, where, **(
            {} if config is None else {"config": int(cfgs[config])})))

    n_levels = len(policy.levels)
    E = int(own.evals.shape[2]) if own is not None and own.evals.ndim == 3 \
        else 0
    eval_rule, eval_cond = policy.eval_rule[cfgs], policy.eval_cond[cfgs]
    eval_has_cond = policy.eval_has_cond[cfgs]
    if not 1 <= E <= eval_rule.shape[1] or (
            eval_rule[:, E:] != 0).any() or eval_has_cond[:, E:].any():
        # an evaluator column the tables lack must be the padding's: rule
        # TRUE_SLOT (0) and no condition
        bad("own evaluator columns do not cover the configs' evaluators")
        return
    eval_rule, eval_cond = eval_rule[:, :E], eval_cond[:, :E]
    eval_has_cond = eval_has_cond[:, :E]
    if own is None or own.leaves.ndim != 2 or own.leaves.shape[0] != G \
            or len(own.levels) != n_levels or len(own.nodes) != n_levels \
            or own.evals.shape != (G, 3, E) \
            or own.leaf_tab.shape[:2] != own.leaves.shape \
            or own.cpu_leaves.ndim != 2 or own.cpu_leaves.shape[0] != G:
        bad("own-config tables do not match the corpus's [G, E] and levels")
        return
    # own position -> corpus buffer slot, -1 on padding
    parts = [np.tile(np.asarray([0, 1], dtype=np.int64), (G, 1)),
             np.where(own.leaves >= 0, own.leaves.astype(np.int64) + base, -1)]
    start = base + L
    for (children, _), rows in zip(policy.levels, own.nodes):
        if rows.size and int(rows.max()) >= children.shape[0]:
            bad("own node row outside its level")
            return
        parts.append(np.where(rows >= 0, rows.astype(np.int64) + start, -1))
        start += int(children.shape[0])
    to_corpus = np.concatenate(parts, axis=1)                    # [G, P]
    P = to_corpus.shape[1]

    def back(pos: np.ndarray) -> np.ndarray:
        """[G, ...] own positions -> corpus slots (-2 when out of range)."""
        flat = pos.reshape(G, -1).astype(np.int64)
        ok = (flat >= 0) & (flat < P)
        got = np.take_along_axis(to_corpus, np.clip(flat, 0, P - 1), axis=1)
        return np.where(ok, got, -2).reshape(pos.shape)

    def first_bad(mask: np.ndarray) -> int:
        return int(np.nonzero(mask.reshape(G, -1).any(axis=1))[0][0])

    wrong = (back(own.evals[:, 0]) != eval_rule) \
        | (back(own.evals[:, 1]) != eval_cond) \
        | ((own.evals[:, 2] != 0) != eval_has_cond)
    if wrong.any():
        g = first_bad(wrong)
        bad(f"own evaluator references of config {g} do not map back to "
            "eval_rule / eval_cond / eval_has_cond", g)
        return
    for k, ((children, is_and), rows, (own_ch, own_and)) in enumerate(
            zip(policy.levels, own.nodes, own.levels)):
        if own_ch.shape != rows.shape + (children.shape[1],) \
                or own_and.shape != rows.shape:
            bad(f"own level {k} tables are not [G, n_own, width]")
            return
        real = rows >= 0
        want = children[np.maximum(rows, 0)]                     # [G, n, w]
        wrong = real & ((back(own_ch) != want).any(axis=-1)
                        | (own_and != is_and[np.maximum(rows, 0)]))
        if wrong.any():
            g = first_bad(wrong)
            bad(f"own level {k} node of config {g} does not map back to its "
                "corpus node's children / kind", g)
            return
    has = own.leaves >= 0
    lf = np.maximum(own.leaves, 0)
    tab = own.leaf_tab
    attr = policy.leaf_attr[lf]
    op = policy.leaf_op[lf]
    is_dfa = has & (op == OP_REGEX_DFA)
    dfa_row = np.take_along_axis(
        dfa_rows, np.clip(tab[..., OWN_DFA], 0, dfa_rows.shape[1] - 1), axis=1)
    cpu_leaf = np.take_along_axis(
        own.cpu_leaves, np.clip(tab[..., OWN_CPU], 0, None), axis=1)
    in_cpu = np.isin(own.leaves, policy.cpu_leaf_list) & has
    checks = [
        ("op", tab[..., OWN_OP] != np.where(has, op, OP_ERROR)),
        ("attr", has & (tab[..., OWN_ATTR] != attr)),
        ("const", has & (tab[..., OWN_CONST] != policy.leaf_const[lf])),
        ("member slot", has & (tab[..., OWN_MEMBER]
                               != policy.member_attr_slot[attr])),
        ("dfa row", is_dfa & ((tab[..., OWN_DFA] < 0)
                              | (dfa_row != policy.leaf_dfa_row[lf]))),
        ("byte slot", is_dfa & (tab[..., OWN_BYTE] != np.maximum(
            policy.attr_byte_slot[attr], 0))),
        ("cpu column", (in_cpu & ((tab[..., OWN_CPU] < 0)
                                  | (cpu_leaf != own.leaves)))
         | (~in_cpu & (tab[..., OWN_CPU] >= 0))),
    ]
    if policy.n_num_attrs:
        checks.append(("numeric slot", has & (
            tab[..., OWN_NUM] != policy.num_attr_slot[attr])))
    if policy.n_rel_slots:
        is_rel = has & (op == OP_RELATION)
        checks.append(("relation binding", is_rel & (
            (tab[..., OWN_REL_SLOT] != policy.leaf_rel_slot[lf])
            | (tab[..., OWN_REL_COL] != policy.leaf_rel_col[lf]))))
    for what, wrong in checks:
        if wrong.any():
            g = first_bad(wrong)
            bad(f"own leaf table of config {g}: {what} differs from the "
                "corpus leaf it stands for", g)
            return
    cl = own.cpu_leaves
    if (np.sort(np.where(cl >= 0, cl, L), axis=1)
            != np.sort(np.where(in_cpu, own.leaves, L), axis=1)[:, :cl.shape[1]]
            ).any() or in_cpu.sum(axis=1).max(initial=0) > cl.shape[1]:
        bad("own CPU columns are not the config's own CPU-lane leaves")


def _check_classes(policy: CompiledPolicy, out: List[Finding]) -> None:
    """ISSUE 34 size classes, audited against the corpus arrays: the
    classes partition the configs; each class's own-config tables pass the
    own-layout audit over its members (so every own position of every
    class maps back to its corpus slot, at whatever widths the class has);
    its DFA rows are exactly its members' and its store's tables are the
    corpus's, cut to a state axis no transition leaves.  The served entry
    gathers a request's row from these tables and no other."""
    classes = getattr(policy, "classes", None)
    G = int(policy.eval_rule.shape[0])

    def bad(msg: str, c: int) -> None:
        out.append(_err("own-layout", msg, f"classes[{c}]"))

    if not classes:
        out.append(_err("own-layout", "the corpus has no size class",
                        "classes"))
        return
    seen = np.concatenate([np.asarray(c.configs) for c in classes])
    if sorted(seen.tolist()) != list(range(G)):
        out.append(_err("own-layout", "the size classes do not hold every "
                        "config exactly once", "classes"))
        return
    has_dfa = bool(policy.n_byte_attrs) and bool(policy.dfa_tables.size)
    for c, cls in enumerate(classes):
        cfgs = np.asarray(cls.configs)
        local = np.full((G,), -1, dtype=np.int64)
        local[cfgs] = np.arange(cfgs.shape[0])
        if cls.cfg_local.shape != (G,) or (cls.cfg_local != local).any():
            bad("cfg_local is not the class's config row -> table row map", c)
            return
        rows, store = cls.config_dfa_rows, np.asarray(cls.dfa_rows)
        if rows.ndim != 2 or rows.shape[0] != cfgs.shape[0] or rows.shape[1] < 1 \
                or (rows.size and int(rows.max()) >= max(store.shape[0], 1)):
            bad("config_dfa_rows is not [G_c, D_c] over the class's store", c)
            return
        corpus_rows = np.where(rows >= 0, store[np.maximum(rows, 0)]
                               if store.size else -1, -1)
        want = policy.config_dfa_rows[cfgs]
        D = rows.shape[1]
        if D > want.shape[1] or (corpus_rows != want[:, :D]).any() \
                or (want[:, D:] >= 0).any():
            bad("the class's DFA rows are not its members' own rows", c)
            return
        if has_dfa and store.size:
            tab = cls.dfa_table_of_row
            src = policy.dfa_table_of_row[store]
            S = cls.dfa_tables.shape[1]
            if int(src.min()) < 0 or int(src.max()) >= policy.dfa_tables.shape[0] \
                    or tab.shape != store.shape \
                    or int(tab.max()) >= cls.dfa_tables.shape[0] \
                    or S > policy.dfa_tables.shape[1] \
                    or (cls.dfa_tables[tab] != policy.dfa_tables[src][:, :S]).any() \
                    or (cls.dfa_accept[tab] != policy.dfa_accept[src][:, :S]).any() \
                    or int(cls.dfa_tables[tab].max(initial=0)) >= S:
                bad("the class's DFA table store does not hold its rows' "
                    "tables, or cuts a state a transition reaches", c)
                return
            if cls.dfa_tables.dtype != dfa_state_dtype(S):
                # the served scan picks its arithmetic by this dtype: u8
                # (bf16 on the chip) only where every id is under 256
                bad(f"the class's {S}-state store is {cls.dfa_tables.dtype}, "
                    f"not {dfa_state_dtype(S)}", c)
                return
        before = len(out)
        _check_own_layout(policy, out, own=cls.own, cfgs=cfgs,
                          dfa_rows=corpus_rows, where=f"classes[{c}].own")
        if len(out) > before:
            return


def _check_lanes(policy: CompiledPolicy, out: List[Finding]) -> None:
    """Dtype/shape contracts of the device operand pytrees, for ALL lanes.
    Host-only build (to_device(host=True)): no device, no transfer."""
    from ..ops.pattern_eval import to_device

    L, A, B = policy.n_leaves, policy.n_attrs, policy.buffer_size
    G, E = policy.eval_rule.shape
    for lane in ("gather", "matmul"):
        try:
            params = to_device(policy, host=True, lane=lane, dense=True)
        except Exception as e:
            out.append(_err("lane-contract",
                            f"{lane} lane operand build failed: {e!r}",
                            f"to_device[{lane}]"))
            continue
        loc = f"params[{lane}]"
        if params["leaf_op"].dtype not in _INT_DTYPES or \
                params["leaf_op"].shape != (L,):
            out.append(_err("lane-contract",
                            f"leaf_op must be int32 [L={L}], got "
                            f"{params['leaf_op'].dtype} "
                            f"{params['leaf_op'].shape}", loc))
        csi = params["own_cpu_leaf"]
        # padding columns target the dump slot at L (sliced off on device);
        # anything past it clobbers memory the kernel never wrote
        if csi.shape != (G, policy.n_own_cpu) or (csi.size and (
                int(csi.min()) < 0 or int(csi.max()) > L)):
            out.append(_err("lane-contract",
                            f"own_cpu_leaf must index [0, L={L}] over "
                            f"[G={G}, c_own={policy.n_own_cpu}]", loc))
        msl = params["member_slot_of_leaf"]
        if msl.shape != (L,) or (msl.size and (
                int(msl.min()) < 0
                or int(msl.max()) >= policy.n_member_attrs)):
            out.append(_err("lane-contract",
                            f"member_slot_of_leaf must index [0, M="
                            f"{policy.n_member_attrs}) over [L={L}]", loc))
        mm = params.get("matmul")
        if lane == "matmul" and mm is None:
            # large interners legitimately force the gather lane; only a
            # silent None on a small corpus is a contract break
            from ..ops.pattern_eval import _F32_EXACT

            if len(policy.interner) + 4 < _F32_EXACT:
                out.append(_err("lane-contract",
                                "matmul lane requested but operands missing",
                                loc))
            continue
        if mm is None:
            continue
        expect = {
            "attr_onehot": (A, L),
            "memb_onehot": (policy.n_member_attrs, L),
            "rule_m": (G * E, B),
            "cond_m": (G * E, B),
        }
        for name, shape in expect.items():
            if mm[name].shape != shape:
                out.append(_err(
                    "lane-contract",
                    f"matmul operand {name} shape {mm[name].shape} != "
                    f"{shape}", loc))
        # selection matrices must be exact one-hots: a doubled or missing
        # entry silently selects the wrong operand (or none)
        for name, axis in (("attr_onehot", 0), ("rule_m", 1), ("cond_m", 1)):
            sums = mm[name].astype(np.float64).sum(axis=axis)
            if sums.size and not np.allclose(sums, 1.0):
                out.append(_err(
                    "lane-contract",
                    f"matmul operand {name} is not an exact one-hot "
                    f"(per-{'column' if axis == 0 else 'row'} sum != 1)",
                    loc))
        cursor = _leaf_base() + L
        for l, m in enumerate(mm["level_mats"]):
            rows = int(policy.levels[l][0].shape[0])
            if m.shape != (rows, cursor):
                out.append(_err(
                    "lane-contract",
                    f"level_mats[{l}] shape {m.shape} != ({rows}, {cursor}) "
                    f"(count matrix must cover exactly the buffer prefix "
                    f"visible to its level)", loc))
            cursor += rows
        if policy.n_byte_attrs:
            R = int(policy.dfa_table_of_row.shape[0])
            S = int(policy.dfa_tables.shape[1])
            if mm["dfa_tables_f"].shape != (R, S, 256):
                out.append(_err(
                    "lane-contract",
                    f"dfa_tables_f shape {mm['dfa_tables_f'].shape} != "
                    f"({R}, {S}, 256) (matmul lane expands per-row)", loc))
            if S > 256 and mm["dfa_tables_f"].dtype != np.float32:
                out.append(_err(
                    "lane-contract",
                    f"dfa_tables_f is {mm['dfa_tables_f'].dtype} at {S} "
                    "states: ids past 256 round there (f32 keeps them)", loc))


def lint_scatter_plan(keys: Sequence[bytes], rows: Sequence[int],
                      unique_rows: Sequence[int],
                      inverse: np.ndarray) -> List[Finding]:
    """Verify a dedup plan (compiler/pack.py dedup_rows output) is an exact
    cover: fanning the unique rows' verdicts back out through ``inverse``
    must reproduce every original row's verdict.  Exact because the kernel
    is a pure per-row function of the canonical key bytes — so cover ≡
    key equality, checkable without evaluating anything."""
    out: List[Finding] = []
    inv = np.asarray(inverse)
    if inv.shape != (len(rows),):
        out.append(_err("scatter-cover",
                        f"inverse length {inv.shape} != rows {len(rows)}",
                        "dedup_rows"))
        return out
    u = len(unique_rows)
    if inv.size and (int(inv.min()) < 0 or int(inv.max()) >= u):
        out.append(_err("scatter-cover",
                        f"inverse references unique slot {int(inv.max())} "
                        f"outside [0, {u})", "dedup_rows"))
        return out
    seen = set()
    for i, ur in enumerate(unique_rows):
        k = keys[ur]
        if k in seen:
            out.append(_err("scatter-cover",
                            f"unique_rows[{i}] duplicates an earlier key "
                            "(the collapse is not minimal, so the plan "
                            "disagrees with the cache keying)",
                            "dedup_rows"))
            return out
        seen.add(k)
    for j, r in enumerate(rows):
        if keys[unique_rows[int(inv[j])]] != keys[r]:
            out.append(_err(
                "scatter-cover",
                f"row {r} fans out from unique row "
                f"{unique_rows[int(inv[j])]} whose key differs — the "
                "scatter map is not a cover (verdict would be wrong)",
                "dedup_rows"))
            return out
    return out


def lint_device_batch(policy: CompiledPolicy, db: Any) -> List[Finding]:
    """Packed-artifact check: one DeviceBatch's axes against the policy's
    padded grid (compiler/pack.py pack_batch contract)."""
    out: List[Finding] = []
    B = int(db.attrs_val.shape[0])
    grid = {
        "attrs_val": (B, policy.n_attrs),
        "members_c": (B, policy.n_member_attrs, policy.members_k),
        "cpu_dense": (B, policy.n_own_cpu),
        "config_id": (B,),
        "host_fallback": (B,),
    }
    for name, shape in grid.items():
        arr = getattr(db, name)
        if arr.shape != shape:
            out.append(_err("pack-grid",
                            f"{name} shape {arr.shape} != padded grid "
                            f"{shape}", name))
    cid = np.asarray(db.config_id)
    G = policy.n_configs
    if cid.size and (int(cid.min()) < 0 or int(cid.max()) >= G):
        out.append(_err("pack-grid",
                        f"config_id outside [0, G={G})", "config_id"))
    if db.attr_bytes is not None:
        NB = max(policy.n_byte_attrs, 1)
        if db.attr_bytes.shape[0] != B or db.attr_bytes.shape[1] != NB:
            out.append(_err("pack-grid",
                            f"attr_bytes shape {db.attr_bytes.shape} != "
                            f"[B={B}, NB={NB}, ...]", "attr_bytes"))
    NN = int(getattr(policy, "n_num_attrs", 0) or 0)
    for name, want in (
        ("attrs_num", (B, NN)),
        ("num_valid", (B, NN)),
        ("rel_rows", (B, int(getattr(policy, "n_rel_slots", 0) or 0))),
        ("member_ovf", (B, policy.n_member_attrs)),
    ):
        arr = getattr(db, name, None)
        if arr is not None and arr.shape != want:
            out.append(_err("pack-grid",
                            f"{name} shape {arr.shape} != padded grid "
                            f"{want}", name))
    rr = getattr(db, "rel_rows", None)
    rb = getattr(policy, "rel_bits", None)
    if rr is not None and rb is not None and rr.size and (
            int(rr.min()) < 0 or int(rr.max()) >= rb.shape[0]):
        out.append(_err("pack-grid",
                        f"rel_rows outside the bitmatrix row axis "
                        f"[0, {rb.shape[0]})", "rel_rows"))
    return out


def tensor_lint(policy: CompiledPolicy,
                check_lanes: bool = True) -> List[Finding]:
    """All structural checks over one compiled corpus.  Pure host, no
    device contact; ~ms even at 1k configs."""
    out: List[Finding] = []
    _check_operands(policy, out)
    _check_circuit(policy, out)
    _check_dfa(policy, out)
    if not out:
        _check_own_rows(policy, out)
    if not out:
        _check_own_layout(policy, out)
    if not out:
        _check_classes(policy, out)
    if check_lanes and not out:
        # lane builds index through the arrays checked above; skip when the
        # base layout is already broken (they would raise, not report)
        _check_lanes(policy, out)
    return out


def _shard_grid_sig(p: CompiledPolicy) -> tuple:
    """The padded-grid signature every mesh shard must share for the
    stacked [S]-axis pytree to be well-formed (one np.stack per leaf)."""
    return (
        p.n_attrs, p.n_leaves, p.n_member_attrs, p.members_k,
        p.n_cpu_leaves, p.n_byte_attrs, p.buffer_size,
        tuple(p.eval_rule.shape), tuple(p.config_dfa_rows.shape),
        p.own.shape_key(), tuple(c.shape_key() for c in p.classes),
        tuple((tuple(children.shape), int(is_and.shape[0]))
              for children, is_and in p.levels),
        int(getattr(p, "n_num_attrs", 0) or 0),
        int(getattr(p, "n_rel_slots", 0) or 0),
        tuple(p.rel_bits.shape) if getattr(p, "rel_bits", None) is not None
        else (),
        bool(getattr(p, "ovf_assist", False)),
    )


def lint_sharded_stack(sharded: Any) -> List[Finding]:
    """Mesh stacking invariant (ISSUE 11): every shard compiled against the
    same ShapeTargets union, so every operand's padded grid is identical
    across shards — a mismatched shard would make the [S]-axis stack (and
    with it every launch) silently wrong or impossible.  Host-only, runs
    BEFORE the upload on the strict-verify path."""
    out: List[Finding] = []
    shards = list(getattr(sharded, "shards", ()))
    if len(shards) < 2:
        return out
    ref = _shard_grid_sig(shards[0])
    for i, p in enumerate(shards[1:], 1):
        sig = _shard_grid_sig(p)
        if sig != ref:
            out.append(_err(
                "shard-stack",
                f"shard {i} padded grid {sig} != shard 0 {ref} — the "
                "ShapeTargets union did not cover every axis; the stacked "
                "device pytree would misalign",
                f"shard[{i}]"))
    return out


def lint_snapshot(snap: Any, check_lanes: bool = True) -> List[Finding]:
    """Lint an engine snapshot: the single compiled corpus, or every shard
    of a mesh-sharded one (runtime/engine.py _Snapshot duck type) plus the
    cross-shard stacking invariant."""
    policy = getattr(snap, "policy", None)
    sharded = getattr(snap, "sharded", None)
    if policy is None and sharded is None and isinstance(
            snap, CompiledPolicy):
        policy = snap
    out: List[Finding] = []
    if policy is not None:
        out += tensor_lint(policy, check_lanes=check_lanes)
    if sharded is not None:
        for i, shard in enumerate(getattr(sharded, "shards", ())):
            for f in tensor_lint(shard, check_lanes=check_lanes):
                f.location = f"shard[{i}].{f.location}" if f.location \
                    else f"shard[{i}]"
                out.append(f)
        out += lint_sharded_stack(sharded)
    return out
