"""Rule compiler: lower pattern-expression trees across all AuthConfigs into
dense tensor operands for the batched TPU kernel.

This is the TPU-era analog of the reference's reconcile-time OPA precompile
(ref: pkg/evaluators/authorization/opa.go:141): all compilation cost is paid
once per corpus change, never per request.

Lowering model
--------------
All expressions from all configs share one flat *result buffer* per request:

  slot 0           constant TRUE   (empty And — ref pkg/jsonexp/expressions.go:111)
  slot 1           constant FALSE  (empty Or  — ref :136)
  slots 2..2+L     leaf pattern results (deduped globally by (attr, op, const))
  slots 2+L..      internal And/Or nodes, grouped by tree depth

Each And/Or node stores child *buffer indices*; children always live at
earlier buffer positions, so the kernel evaluates level-by-level with static
shapes.  And-rows pad with slot 0 (identity of ∧), Or-rows with slot 1.

Per config, each authorization evaluator contributes a (condition, rule)
pair of buffer indices; the verdict is

  verdict[cfg] = ∧ over evaluators of (¬cond ∨ rule)       # skipped ⇒ pass
                                            (ref: pkg/service/auth_pipeline.go:120-125,
                                             307-318 — all-must-pass, conditions gate)

Regex (`matches`) leaves and incl/excl membership overflow are routed through
a CPU lane: the encoder supplies exact per-(request, leaf) booleans and the
kernel selects them by op code / overflow mask (SURVEY.md §7 "hard parts").
"""

from __future__ import annotations

import os
import re
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..expressions.ast import (
    NUMERIC_OPERATORS,
    And,
    Expression,
    InGroup,
    Operator,
    Or,
    Pattern,
)
from ..relations.closure import RelationClosure
from .intern import PAD, StringInterner

__all__ = [
    "OP_EQ", "OP_NEQ", "OP_INCL", "OP_EXCL", "OP_CPU", "OP_ERROR", "OP_TREE_CPU",
    "OP_REGEX_DFA", "OP_NUM_GT", "OP_NUM_GE", "OP_NUM_LT", "OP_NUM_LE",
    "OP_RELATION", "NUMERIC_OPS",
    "ConfigRules", "CompiledPolicy", "ShapeTargets", "OwnLayout", "SizeClass",
    "compile_corpus", "derive_layouts", "CLASS_RATIO", "CLASS_FLOOR_BYTES",
    "TRUE_SLOT", "FALSE_SLOT", "DFA_VALUE_BYTES", "DFA_WIDTHS",
    "DFA_SCAN_BUDGET", "class_device_width", "dfa_state_dtype",
    "cpu_regex_leaves",
]

OP_EQ, OP_NEQ, OP_INCL, OP_EXCL, OP_CPU, OP_ERROR, OP_TREE_CPU, OP_REGEX_DFA = (
    0, 1, 2, 3, 4, 5, 6, 7,
)
# numeric comparator lane + compiled relation tables (ISSUE 14)
OP_NUM_GT, OP_NUM_GE, OP_NUM_LT, OP_NUM_LE, OP_RELATION = 8, 9, 10, 11, 12

NUMERIC_OPS = (OP_NUM_GT, OP_NUM_GE, OP_NUM_LT, OP_NUM_LE)

_NUM_OP_OF = {
    Operator.GT: OP_NUM_GT,
    Operator.GE: OP_NUM_GE,
    Operator.LT: OP_NUM_LT,
    Operator.LE: OP_NUM_LE,
}

# The device regex lane's byte tensor is [B, NB, W]: a value of up to W bytes
# is scanned on the device, a longer one (or one containing NUL) falls back to
# an exact host scan per request, so W is purely a transfer/compute vs
# fallback-rate dial.  W is a property of a config's SIZE CLASS
# (``SizeClass.device_width``, ``class_device_width``), not of the corpus:
# 64 covers URL paths and short headers and is the floor every class gets
# (DFA_VALUE_BYTES); it does NOT cover what an Envoy edge forwards of a
# browser (a user-agent is 105-135 bytes, a referer 45-200, a cookie 40-380),
# so a class whose rows are cheap to scan takes 128 or 256 (DFA_WIDTHS).
#
# The width rule (ISSUE 38).  A row's scan costs D x S x W state-steps
# (ops/pattern_eval.py _own_dfa_row_res: step maps [W, D, S, B], one-hot of
# the bytes [B, D, W, 256]): the class's own size (the bytes of tables a row
# gathers, D x S x 256) times W / 256.  A class takes the widest W of
# DFA_WIDTHS with D x S x W <= DFA_SCAN_BUDGET, never under the floor.  The
# budget is what the chip showed a launch can afford while the host still
# paces the cell: at 82,944 state-steps a row (routes-1k: D 18, S 72, W 64)
# a launch of 256 rows is 0.76 ms (PERF.md section 5, PR 37) against the
# ~2.1 ms the front end takes to fill the cut at 120k Check()/s, the chip a
# third busy; at 599,040 (mixed-tenants-1k's large class) the chip paces the
# cell already, so doubling its W would halve the rate.  65,536 keeps a class
# at the budget under routes-1k's cost a row.  Read from no flag and no
# environment variable (AUTHORINO_TPU_DFA_VALUE_BYTES is gone: one width for
# the whole corpus is what this rule replaces).
DFA_VALUE_BYTES = 64
DFA_WIDTHS = (64, 128, 256)
DFA_SCAN_BUDGET = 65536


def dfa_state_dtype(n_states: int) -> np.dtype:
    """The dtype of a table store's next states at a state axis of
    ``n_states``: u8 while every id fits it, u16 past 256 (compiler/redfa.py
    MAX_STATES).  A store of u8 tables scans as it always has; a u16 store
    takes the scan that is exact past 256 (ops/pattern_eval.py
    _own_dfa_row_res), and the host's overflow scan loads two bytes a
    state."""
    return np.dtype(np.uint8 if n_states <= 256 else np.uint16)


def cpu_regex_leaves(policy: "CompiledPolicy") -> int:
    """The `matches` leaves of a compiled corpus that compiler/redfa.py could
    not take (outside its subset): the CPU regex lane decides them, and a
    config that has one gets no native fast-lane plan."""
    return sum(1 for op, rx in zip(policy.leaf_op.tolist(), policy.leaf_regex)
               if op == OP_CPU and rx is not None)


def class_device_width(n_dfa_rows: int, n_states: int) -> int:
    """The byte-lane width of a size class of D = ``n_dfa_rows`` DFA rows a
    request row and a state axis S = ``n_states`` (the width rule above)."""
    fit = [w for w in DFA_WIDTHS
           if n_dfa_rows * n_states * w <= DFA_SCAN_BUDGET]
    return max(fit, default=DFA_VALUE_BYTES)


TRUE_SLOT = 0
FALSE_SLOT = 1
_LEAF_BASE = 2
_DFA_MISS = object()

# Selectors whose value is unique per request or time-dependent: rows of
# configs referencing them (almost) never repeat on the wire, so caching
# their verdicts only evicts useful entries from the snapshot-scoped verdict
# cache.  Correctness NEVER depends on this bit — the cache key is the full
# encoded operand digest (runtime/engine.py, runtime/native_frontend.py);
# this is purely a cache-pollution dial.
_UNCACHEABLE_SELECTOR_PREFIXES = (
    "request.id",
    "request.time",
    "context.request.time",
    "context.request.http.id",
)


def _selector_uncacheable(selector_str: str) -> bool:
    head = selector_str.split("|", 1)[0].split("#", 1)[0].strip()
    return any(head == p or head.startswith(p + ".")
               for p in _UNCACHEABLE_SELECTOR_PREFIXES)


@dataclass
class ShapeTargets:
    """Forced operand shapes so independently-compiled sub-corpora (one per
    tensor-parallel shard) stack into a single leading-axis array with
    identical buffer layouts (parallel/sharded_eval.py)."""

    n_leaves: int                      # padded L
    n_attrs: int                       # padded A
    max_e: int                         # evaluator columns
    levels: Tuple[Tuple[int, int], ...]  # per level: (rows, children width)
    n_member_attrs: int = 1            # compact membership rows (M)
    n_cpu_leaves: int = 1              # dense CPU-lane columns (C)
    # device regex lane: DFA row/state/byte-slot axes must also stack across
    # shards.  n_byte_attrs > 0 in the union forces every shard to carry a
    # (possibly dummy) DFA lane so the stacked param structure is uniform.
    n_dfa_rows: int = 1
    n_dfa_states: int = 1
    n_byte_attrs: int = 0
    # unique DFA transition tables (rows sharing a determinized automaton
    # point at one table through dfa_table_of_row — rule-tensor compaction)
    n_dfa_tables: int = 1
    # most DFA rows any one config's circuit reaches (config_dfa_rows' D)
    n_own_dfa_rows: int = 1
    # own-config layout (OwnLayout): most leaves, CPU columns and per-level
    # nodes any one config's circuit reaches
    n_own_leaves: int = 1
    n_own_cpu: int = 1
    own_level_rows: Tuple[int, ...] = ()
    # eval-table rows (configs per shard) — unified so per-shard device
    # pytrees (incl. the matmul lane's [G*E, cursor] one-hots) stack
    n_configs: int = 1
    # numeric comparator lane (ISSUE 14): compact [B, NN] int32 value slots.
    # 0 = no lane anywhere in the union (structural, like n_byte_attrs)
    n_num_attrs: int = 0
    # compiled relation tables (ISSUE 14): [Rp, W] bitmatrix rows/width and
    # the [B, NR] entity-row operand slots.  n_rel_slots == 0 = no lane
    n_rel_slots: int = 0
    n_rel_rows: int = 1
    n_rel_width: int = 1

    @staticmethod
    def union(shapes: Sequence["ShapeTargets"]) -> "ShapeTargets":
        n_levels = max((len(s.levels) for s in shapes), default=0)
        levels = []
        for l in range(n_levels):
            rows = max((s.levels[l][0] for s in shapes if l < len(s.levels)), default=1)
            width = max((s.levels[l][1] for s in shapes if l < len(s.levels)), default=1)
            levels.append((rows, width))
        return ShapeTargets(
            n_leaves=max(s.n_leaves for s in shapes),
            n_attrs=max(s.n_attrs for s in shapes),
            max_e=max(s.max_e for s in shapes),
            levels=tuple(levels),
            n_member_attrs=max(s.n_member_attrs for s in shapes),
            n_cpu_leaves=max(s.n_cpu_leaves for s in shapes),
            n_dfa_rows=max(s.n_dfa_rows for s in shapes),
            n_dfa_states=max(s.n_dfa_states for s in shapes),
            n_byte_attrs=max(s.n_byte_attrs for s in shapes),
            n_dfa_tables=max(s.n_dfa_tables for s in shapes),
            n_own_dfa_rows=max(s.n_own_dfa_rows for s in shapes),
            n_own_leaves=max(s.n_own_leaves for s in shapes),
            n_own_cpu=max(s.n_own_cpu for s in shapes),
            own_level_rows=tuple(
                max((s.own_level_rows[l] for s in shapes
                     if l < len(s.own_level_rows)), default=1)
                for l in range(n_levels)),
            n_configs=max(s.n_configs for s in shapes),
            n_num_attrs=max(s.n_num_attrs for s in shapes),
            n_rel_slots=max(s.n_rel_slots for s in shapes),
            n_rel_rows=max(s.n_rel_rows for s in shapes),
            n_rel_width=max(s.n_rel_width for s in shapes),
        )


@dataclass
class ConfigRules:
    """One AuthConfig's compilable authorization surface: a list of
    (conditions, rules) expression pairs — one per pattern-matching
    authorization evaluator (conditions may be None)."""

    name: str
    evaluators: List[Tuple[Optional[Expression], Expression]] = field(default_factory=list)


@dataclass
class _Leaf:
    op: int
    attr: int
    const: int
    regex: Optional[str] = None  # for CPU lane
    tree: Optional[Expression] = None  # for OP_TREE_CPU whole-tree fallback
    rel: Optional[RelationClosure] = None  # for OP_RELATION
    group: Optional[str] = None            # for OP_RELATION


def _has_invalid_regex(expr: Expression) -> bool:
    """A leaf whose evaluation can only ERROR (invalid regex, unfoldable
    numeric constant): the containing tree keeps the reference's error
    short-circuit semantics via the whole-tree CPU fallback.  The name
    predates the numeric lane; it now covers every invalid-leaf kind."""
    if isinstance(expr, Pattern):
        if expr.operator is Operator.MATCHES:
            return getattr(expr, "_regex", None) is None
        if expr.operator in NUMERIC_OPERATORS:
            return getattr(expr, "_num_const", None) is None
        return False
    if isinstance(expr, InGroup):
        return False
    return any(_has_invalid_regex(c) for c in expr.children)


@dataclass
class CompiledPolicy:
    """Dense device operands + CPU-side metadata for one compiled corpus."""

    # --- device operands (numpy here; moved to device by the engine) ---
    leaf_op: np.ndarray        # [L] int32
    leaf_attr: np.ndarray      # [L] int32
    leaf_const: np.ndarray     # [L] int32
    levels: Tuple[Tuple[np.ndarray, np.ndarray], ...]  # per level: (children [N,C] i32, is_and [N] bool)
    eval_cond: np.ndarray      # [G, E] int32 buffer idx (TRUE_SLOT when absent)
    eval_rule: np.ndarray      # [G, E] int32 buffer idx
    eval_has_cond: np.ndarray  # [G, E] bool

    # --- device regex lane (empty arrays when no DFA-compilable regexes) ---
    # transition tables are stored DEDUPED: rows whose regexes determinize to
    # the same automaton (same pattern on different attrs, or structurally
    # identical patterns across AuthConfigs) share one [S, 256] table and
    # point at it through dfa_table_of_row — rule-tensor compaction that
    # shrinks both the device corpus upload and per-snapshot host memory
    dfa_tables: np.ndarray     # [T, S, 256] dfa_state_dtype(S) — UNIQUE transition tables
    dfa_accept: np.ndarray     # [T, S] bool
    dfa_table_of_row: np.ndarray  # [R] int32 — dfa row → unique table
    dfa_leaf_attr: np.ndarray  # [R] int32 — attr idx of each dfa row
    leaf_dfa_row: np.ndarray   # [L] int32 — leaf → dfa row (0 for others)
    attr_byte_slot: np.ndarray  # [A] int32 — attr → byte-tensor slot (-1 none)
    n_byte_attrs: int

    # --- CPU-side metadata ---
    interner: StringInterner
    attr_selectors: List[str]            # attr idx -> selector string
    config_ids: Dict[str, int]           # config name -> row in eval_* tables
    config_attrs: List[List[int]]        # per config: attr idxs to resolve
    config_cpu_leaves: List[List[int]]   # per config: leaf idxs needing CPU lane
    leaf_regex: List[Optional["re.Pattern"]]  # per leaf: compiled regex or None
    leaf_tree: List[Optional[Expression]]     # per leaf: whole-tree CPU fallback
    leaf_is_membership: np.ndarray       # [L] bool — incl/excl (overflow-capable)
    members_k: int                       # K: membership vector width

    # --- transfer-compaction metadata (see compiler/pack.py) ---
    # attr → row in the compact [B, M, K] membership tensor (-1: attr has no
    # incl/excl leaf and its members are never read by the kernel)
    member_attr_slot: np.ndarray         # [A] int32
    member_attrs: np.ndarray             # [M_real] int32 (attrs with slot >= 0)
    n_member_attrs: int                  # M (padded >= 1)
    # leaves whose value rides the dense CPU lane: op CPU/TREE_CPU always,
    # plus REGEX_DFA (column read only under byte-overflow)
    cpu_leaf_list: np.ndarray            # [C_real] int32 leaf idxs
    n_cpu_leaves: int                    # C (padded >= 1)
    # original expressions per config evaluator — the host-fallback oracle
    # for requests the compact encoding cannot represent (membership overflow)
    config_exprs: List[List[Tuple[Optional[Expression], Expression]]]

    # per-config verdict-cache eligibility: False for configs whose rules
    # reference request-unique/time-dependent selectors (their rows never
    # repeat, so caching them only evicts useful entries).  Correctness
    # never depends on it — cache keys are full encoded-row digests.
    config_cacheable: np.ndarray = None  # [G] bool

    # --- numeric comparator lane (ISSUE 14; empty when no numeric leaf) ---
    # attr → compact numeric-value slot (-1: attr has no numeric leaf)
    num_attr_slot: np.ndarray = None     # [A] int32
    num_attrs: np.ndarray = None         # [NN_real] int32
    n_num_attrs: int = 0                 # NN (padded; 0 = no lane)

    # --- compiled relation tables (ISSUE 14; empty when no InGroup leaf) --
    # the per-snapshot ancestor-closure bitmatrix: row = (relation
    # instance, entity), col = (relation instance, queried group); row 0 is
    # the reserved all-zero row unknown entities resolve to.  Bit order is
    # LITTLE within each byte (bit j of byte k = column k*8+j).
    rel_bits: np.ndarray = None          # [Rp, W] uint8
    leaf_rel_slot: np.ndarray = None     # [L] int32 (slot in rel_rows; 0 dflt)
    leaf_rel_col: np.ndarray = None      # [L] int32 (column; 0 default)
    rel_slot_attr: np.ndarray = None     # [NRp] int32 (attr of each slot)
    n_rel_slots: int = 0                 # NR (padded; 0 = no lane)
    # host metadata: closure instances (deduped by digest), per-instance
    # entity → global row map, per-slot (attr, instance), per-col
    # (instance, group) — the encoder's and certifier's view of the lane
    rel_instances: List[RelationClosure] = None
    rel_entity_rows: List[Dict[str, int]] = None
    rel_slots: List[Tuple[int, int]] = None
    rel_col_names: List[Tuple[int, str]] = None

    # membership-overflow in-kernel assist (ISSUE 14): when True the
    # encoder's exact per-leaf overflow answers ride dense CPU-lane columns
    # and the kernel selects them under the [B, M] member_ovf mask —
    # overflow rows stay on the device lane instead of host_fallback
    ovf_assist: bool = False

    # --- own-row DFA scan layout (ISSUE 26) ---
    # per config row, the DFA rows its evaluators' circuits reach, ascending,
    # padded with -1 to D = the most any one config reaches (>= 1).  Leaves
    # are deduplicated across configs, so a DFA row may appear under several
    # configs: a per-config table, not a partition.  Derived
    # deterministically in __post_init__ (so deserialized snapshots rebuild
    # byte-identical layouts without a format bump) but STORED as a field:
    # the operand build consumes it directly, and the tensor lint + the
    # translation certifier audit it against its sources — a corrupted
    # layout is a real miscompile, not a stale cache.  Only
    # ShapeTargets.n_own_dfa_rows widens it, so shards stack.
    config_dfa_rows: np.ndarray = None   # [G, D] int32 (-1 = no row)

    # --- own-config layout (ISSUE 28) ---
    # per config row, the leaves, circuit nodes, evaluator references and
    # CPU-lane columns its evaluators reach, in a buffer of the config's
    # own: what the served entry evaluates for a request and what the row
    # payload's CPU columns mean.  Derived like config_dfa_rows.
    own: "OwnLayout" = None

    # --- size classes (ISSUE 34) ---
    # the configs cut into classes of like size, each with tables of its own
    # widths ([G_c, ...], its own l_own, c_own, n_own a level, E, D and DFA
    # state axis S): what the served entry gathers a request's row from, so
    # that a row pays for its own config's size and not for the corpus's
    # largest.  ``own`` and ``config_dfa_rows`` above stay the corpus-wide
    # padded form on the host (the encoders' CPU columns, the dense bodies,
    # the single layout the classes are cut from).  Derived like them.
    classes: Tuple["SizeClass", ...] = None

    def __post_init__(self) -> None:
        if self.eval_rule is not None and self.leaf_dfa_row is not None \
                and (self.config_dfa_rows is None or self.own is None
                     or self.classes is None):
            derive_layouts(self, keep=True)

    def rule_sources(self) -> List[List[str]]:
        """Decision provenance (ISSUE 9): per config row, the source string
        of each evaluator's rule expression — the rule-index → (authconfig,
        rule-source) map the observability layer attributes denials with.
        Derived from ``config_exprs`` (which the snapshot serializer
        round-trips, so replicas attribute identically to the compiling
        leader); memoized on first use — one walk per compiled corpus,
        never per request."""
        memo = getattr(self, "_rule_sources", None)
        if memo is None:
            memo = [[str(rule) for _cond, rule in evs]
                    for evs in self.config_exprs]
            object.__setattr__(self, "_rule_sources", memo)
        return memo

    def provenance_map(self) -> Dict[str, Dict[str, Any]]:
        """JSON-safe {config name: {"row", "rules": [source, ...]}} view of
        rule_sources (the /debug/vars + analysis-CLI shape)."""
        srcs = self.rule_sources()
        return {name: {"row": row, "rules": list(srcs[row])}
                for name, row in self.config_ids.items()}

    @property
    def dfa_tables_by_row(self) -> np.ndarray:
        """Transition tables expanded back to the per-row axis [R, S, 256]
        (consumers that index by dfa row host-side: the matmul-lane operand
        build and the native C++ encoder)."""
        return self.dfa_tables[self.dfa_table_of_row]

    @property
    def dfa_accept_by_row(self) -> np.ndarray:
        return self.dfa_accept[self.dfa_table_of_row]

    @property
    def dfa_flags_by_row(self) -> np.ndarray:
        """State flags per dfa row [R, S] uint8 (dfa_state_flags), for the
        native encoder's host scan of values past DFA_VALUE_BYTES."""
        return dfa_state_flags(self.dfa_tables,
                               self.dfa_accept)[self.dfa_table_of_row]

    @property
    def config_byte_width(self) -> np.ndarray:
        """[G] int32: the byte-lane width of each config's size class; a
        value longer than its config's overflows to the host scan."""
        memo = getattr(self, "_config_byte_width", None)
        if memo is None or memo[0] is not self.classes:
            out = np.full((self.n_configs,), DFA_VALUE_BYTES, dtype=np.int32)
            for cls in self.classes:
                out[cls.configs] = cls.device_width
            memo = (self.classes, out)
            object.__setattr__(self, "_config_byte_width", memo)
        return memo[1]

    @property
    def byte_width(self) -> int:
        """The corpus's widest byte lane: the last axis of the encoders'
        ``attr_bytes`` [B, NB, W]."""
        return max((int(c.device_width) for c in self.classes),
                   default=DFA_VALUE_BYTES)

    @property
    def n_leaves(self) -> int:
        return int(self.leaf_op.shape[0])

    @property
    def n_attrs(self) -> int:
        return len(self.attr_selectors)

    @property
    def n_configs(self) -> int:
        return int(self.eval_rule.shape[0])

    @property
    def n_own_cpu(self) -> int:
        """Columns of the row payload's CPU lane (c_own)."""
        return int(self.own.cpu_leaves.shape[1])

    @property
    def buffer_size(self) -> int:
        return _LEAF_BASE + self.n_leaves + sum(lv[0].shape[0] for lv in self.levels)

    def shape_key(self) -> tuple:
        """Everything jit specializes on — used to bound recompiles."""
        return (
            self.n_leaves,
            self.n_attrs,
            self.members_k,
            self.n_member_attrs,
            self.n_cpu_leaves,
            tuple((lv[0].shape, ) for lv in self.levels),
            self.eval_rule.shape,
            self.n_num_attrs,
            self.n_rel_slots,
            tuple(self.rel_bits.shape) if self.rel_bits is not None else (),
            bool(self.ovf_assist),
            int(self.config_dfa_rows.shape[1]),
            self.own.shape_key(),
            tuple(c.shape_key() for c in self.classes),
        )

    def shape_targets(self) -> ShapeTargets:
        return ShapeTargets(
            n_leaves=self.n_leaves,
            n_attrs=len(self.attr_selectors),
            max_e=int(self.eval_rule.shape[1]),
            levels=tuple((int(c.shape[0]), int(c.shape[1])) for c, _ in self.levels),
            n_member_attrs=self.n_member_attrs,
            n_cpu_leaves=self.n_cpu_leaves,
            n_dfa_rows=int(self.dfa_table_of_row.shape[0]),
            n_dfa_states=int(self.dfa_tables.shape[1]),
            n_byte_attrs=self.n_byte_attrs,
            n_dfa_tables=int(self.dfa_tables.shape[0]),
            n_own_dfa_rows=int(self.config_dfa_rows.shape[1]),
            n_own_leaves=int(self.own.leaves.shape[1]),
            n_own_cpu=self.n_own_cpu,
            own_level_rows=tuple(int(n.shape[1]) for n in self.own.nodes),
            n_configs=self.n_configs,
            n_num_attrs=self.n_num_attrs,
            n_rel_slots=self.n_rel_slots,
            n_rel_rows=int(self.rel_bits.shape[0])
            if self.rel_bits is not None else 1,
            n_rel_width=int(self.rel_bits.shape[1])
            if self.rel_bits is not None else 1,
        )


# fields of OwnLayout.leaf_tab's last axis
(OWN_OP, OWN_ATTR, OWN_CONST, OWN_MEMBER, OWN_DFA, OWN_BYTE, OWN_CPU,
 OWN_NUM, OWN_REL_SLOT, OWN_REL_COL) = range(10)
OWN_FIELDS = 10


@dataclass
class OwnLayout:
    """One config's slice of the corpus, for every config of the table: the
    served entry gathers a request's config's row of each table and
    evaluates [B, l_own] leaves, [B, n_own] nodes a level and [B, E]
    evaluators in a buffer of the config's own: TRUE, FALSE, its leaves
    (ascending global index), then its nodes level by level (ascending
    global row).  Leaves and nodes are shared across configs, so these are
    per-config tables, not a partition.  Two forms: ``CompiledPolicy.own``
    covers every config, every axis the natural maximum over the corpus
    (only ShapeTargets widens it, so shards stack); ``SizeClass.own`` covers
    a class's members at the class's maxima and is what serves.  Padding:
    leaves read OP_ERROR (False), nodes are an Or of FALSE, and nothing
    references either."""

    leaves: np.ndarray      # [G, l_own] int32 global leaf idx (-1 pad)
    nodes: Tuple[np.ndarray, ...]  # per level [G, n_own] int32 global row (-1 pad)
    # per own leaf: op, attr, const, member slot, position in
    # config_dfa_rows[g], byte slot, column of the row's CPU payload,
    # numeric slot, relation slot, relation column (-1: the leaf has none)
    leaf_tab: np.ndarray    # [G, l_own, OWN_FIELDS] int32
    # per level (children [G, n_own, width] int32, is_and [G, n_own] bool),
    # children in own-buffer coordinates
    levels: Tuple[Tuple[np.ndarray, np.ndarray], ...]
    evals: np.ndarray       # [G, 3, E] int32: rule, cond (own coords), has_cond
    cpu_leaves: np.ndarray  # [G, c_own] int32 global leaf idx (-1 pad)

    def shape_key(self) -> tuple:
        return (self.leaf_tab.shape, self.cpu_leaves.shape,
                tuple(c.shape for c, _ in self.levels))


def _level_starts(policy: "CompiledPolicy") -> List[int]:
    """Buffer slot of each level's first node (and one past the last)."""
    starts = [_LEAF_BASE + policy.n_leaves]
    for children, _ in policy.levels:
        starts.append(starts[-1] + int(children.shape[0]))
    return starts


def own_reach(policy: "CompiledPolicy") -> List[Tuple[List[int], List[List[int]]]]:
    """Per config row: (leaves, nodes per level), ascending, reachable from
    its ``eval_cond`` / ``eval_rule`` references through ``levels``.
    Children reference strictly earlier buffer slots (tensor_lint
    circuit-order), so the walk ends."""
    from bisect import bisect_right

    starts = _level_starts(policy)
    first_node = starts[0]
    kids = [children.tolist() for children, _ in policy.levels]
    refs = np.concatenate([policy.eval_cond, policy.eval_rule], axis=1).tolist()
    out = []
    for row in refs:
        leaves: set = set()
        nodes: List[set] = [set() for _ in kids]
        stack = [r for r in set(row) if r >= _LEAF_BASE]
        while stack:
            slot = stack.pop()
            if slot < first_node:
                leaves.add(slot - _LEAF_BASE)
                continue
            level = bisect_right(starts, slot) - 1
            r = slot - starts[level]
            if r not in nodes[level]:
                nodes[level].add(r)
                stack.extend(k for k in kids[level][r] if k >= _LEAF_BASE)
        out.append((sorted(leaves), [sorted(n) for n in nodes]))
    return out


def _padded(lists: Sequence[Sequence[int]], width: int) -> np.ndarray:
    out = np.full((len(lists), width), -1, dtype=np.int32)
    for g, l in enumerate(lists):
        out[g, : len(l)] = l
    return out


def derive_config_dfa_rows(policy: "CompiledPolicy", reach=None) -> np.ndarray:
    """[G, D] int32: for each config row the DFA rows of the
    ``OP_REGEX_DFA`` leaves it reaches, ascending, padded with -1.  D is the
    natural maximum over the corpus (at least 1); the served scan runs a
    class's own D (``SizeClass.config_dfa_rows``)."""
    reach = own_reach(policy) if reach is None else reach
    is_dfa = policy.leaf_op == OP_REGEX_DFA
    own = [sorted({int(policy.leaf_dfa_row[l]) for l in leaves if is_dfa[l]})
           for leaves, _ in reach]
    return _padded(own, max(max((len(o) for o in own), default=0), 1))


def derive_own_layout(policy: "CompiledPolicy", reach=None,
                      targets: Optional[ShapeTargets] = None) -> OwnLayout:
    """The per-config tables of ``OwnLayout`` from the corpus's own arrays
    (needs ``config_dfa_rows``).  ``targets`` widens the axes."""
    reach = own_reach(policy) if reach is None else reach
    G, E = policy.eval_rule.shape
    n_levels = len(policy.levels)
    cpu_set = set(policy.cpu_leaf_list.tolist())
    own_cpu = [[l for l in leaves if l in cpu_set] for leaves, _ in reach]
    l_own = max(max((len(lv) for lv, _ in reach), default=0), 1)
    c_own = max(max((len(c) for c in own_cpu), default=0), 1)
    n_own = [max(max((len(nd[k]) for _, nd in reach), default=0), 1)
             for k in range(n_levels)]
    if targets is not None:
        assert targets.n_own_leaves >= l_own and targets.n_own_cpu >= c_own \
            and all(t >= n for t, n in zip(targets.own_level_rows, n_own)), \
            "targets' own-config axes too small"
        l_own, c_own = targets.n_own_leaves, targets.n_own_cpu
        n_own = list(targets.own_level_rows[:n_levels])
    leaves = _padded([lv for lv, _ in reach], l_own)
    nodes = tuple(_padded([nd[k] for _, nd in reach], n_own[k])
                  for k in range(n_levels))
    cpu_leaves = _padded(own_cpu, c_own)

    # per-leaf fields, gathered from the corpus arrays
    has = leaves >= 0
    lf = np.maximum(leaves, 0)
    attr = policy.leaf_attr[lf]
    op = policy.leaf_op[lf]
    tab = np.full((G, l_own, OWN_FIELDS), -1, dtype=np.int32)
    tab[..., OWN_OP] = np.where(has, op, OP_ERROR)
    tab[..., OWN_ATTR] = np.where(has, attr, -1)
    tab[..., OWN_CONST] = np.where(has, policy.leaf_const[lf], 0)
    tab[..., OWN_MEMBER] = np.where(has, policy.member_attr_slot[attr], -1)
    is_dfa = has & (op == OP_REGEX_DFA)
    if is_dfa.any():
        row = policy.leaf_dfa_row[lf]
        pos = (policy.config_dfa_rows[:, None, :] == row[:, :, None]).argmax(-1)
        tab[..., OWN_DFA] = np.where(is_dfa, pos, -1)
        tab[..., OWN_BYTE] = np.where(
            is_dfa, np.maximum(policy.attr_byte_slot[attr], 0), -1)
    cpu_pos = (cpu_leaves[:, None, :] == leaves[:, :, None])
    tab[..., OWN_CPU] = np.where(has & cpu_pos.any(-1), cpu_pos.argmax(-1), -1)
    if policy.n_num_attrs:
        tab[..., OWN_NUM] = np.where(has, policy.num_attr_slot[attr], -1)
    if policy.n_rel_slots:
        is_rel = has & (op == OP_RELATION)
        tab[..., OWN_REL_SLOT] = np.where(is_rel, policy.leaf_rel_slot[lf], -1)
        tab[..., OWN_REL_COL] = np.where(is_rel, policy.leaf_rel_col[lf], 0)

    # global buffer slot -> own-buffer position, one config at a time
    starts = _level_starts(policy)
    bases = [_LEAF_BASE + l_own]
    for n in n_own:
        bases.append(bases[-1] + n)
    own_levels = [
        (np.full((G, n_own[k], int(policy.levels[k][0].shape[1])), FALSE_SLOT,
                 dtype=np.int32), np.zeros((G, n_own[k]), dtype=bool))
        for k in range(n_levels)]
    evals = np.empty((G, 3, E), dtype=np.int32)
    evals[:, 2] = policy.eval_has_cond
    for g, (lv, nd) in enumerate(reach):
        pos = {TRUE_SLOT: TRUE_SLOT, FALSE_SLOT: FALSE_SLOT}
        pos.update((_LEAF_BASE + l, _LEAF_BASE + j) for j, l in enumerate(lv))
        for k in range(n_levels):
            pos.update((starts[k] + r, bases[k] + j)
                       for j, r in enumerate(nd[k]))
        for k in range(n_levels):
            children, is_and = policy.levels[k]
            for j, r in enumerate(nd[k]):
                own_levels[k][0][g, j] = [pos[c] for c in children[r].tolist()]
                own_levels[k][1][g, j] = is_and[r]
        evals[g, 0] = [pos[r] for r in policy.eval_rule[g].tolist()]
        evals[g, 1] = [pos[r] for r in policy.eval_cond[g].tolist()]
    return OwnLayout(leaves=leaves, nodes=nodes, leaf_tab=tab,
                     levels=tuple(own_levels), evals=evals,
                     cpu_leaves=cpu_leaves)


# The class rule (ISSUE 34).  A config's size is the bytes of corpus tables
# one request row of it gathers: its own leaf, node, evaluator and DFA-row
# table rows and the DFA transition tables it scans.  Configs are taken in
# ascending size and a class is closed where the next config is CLASS_RATIO
# times the class's smallest or more, so no row is evaluated at more than
# that many times its own config's size.  Sizes under CLASS_FLOOR_BYTES
# count as the floor: below it a launch costs its fixed overhead, not its
# bytes, and a class of its own (one more launch a cut, one more warm grid)
# buys nothing.  Both are properties of the rule, read from no flag and no
# environment variable; a corpus of one size is one class.
CLASS_RATIO = 4
CLASS_FLOOR_BYTES = 64 * 1024


@dataclass
class SizeClass:
    """The configs of one size class and their tables, at the class's own
    widths (the natural maximum over its members on every axis): what
    ``ops/pattern_eval.py`` ``eval_own`` gathers a member's row from.  A
    config is in exactly one class; leaves, nodes and DFA rows are shared
    across configs, so a DFA row may sit in several classes' stores."""

    configs: np.ndarray           # [G_c] int32 config rows, ascending
    cfg_local: np.ndarray         # [G] int32 config row -> table row (-1: not a member)
    own: OwnLayout                # [G_c, ...] tables; evals [G_c, 3, E_c]
    dfa_rows: np.ndarray          # [R_c] int32 corpus DFA rows of the store, ascending
    config_dfa_rows: np.ndarray   # [G_c, D_c] int32 positions in dfa_rows (-1 pad)
    dfa_tables: np.ndarray        # [T_c, S_c, 256] dfa_state_dtype(S_c)
    dfa_accept: np.ndarray        # [T_c, S_c] bool
    dfa_table_of_row: np.ndarray  # [R_c] int32 store row -> table of the store
    # bytes of a value the device scans for a member (the width rule at
    # DFA_VALUE_BYTES): a longer value overflows to the host scan
    device_width: int = DFA_VALUE_BYTES

    def shape_key(self) -> tuple:
        return (self.own.shape_key(), self.own.evals.shape,
                self.config_dfa_rows.shape, self.dfa_tables.shape,
                self.device_width)

    def widths(self) -> Dict[str, int]:
        """What /debug/vars lists of a class."""
        has_dfa = bool(self.dfa_rows.size)
        return {
            "configs": int(self.configs.shape[0]),
            "leaf_cols_per_row": int(self.own.leaves.shape[1]),
            "dfa_rows_per_row": int(self.config_dfa_rows.shape[1]) if has_dfa else 0,
            "dfa_states": int(self.dfa_tables.shape[1]) if has_dfa else 0,
            "device_width": int(self.device_width) if has_dfa else 0,
            "cpu_cols": int(self.own.cpu_leaves.shape[1]),
            "evaluators": int(self.own.evals.shape[2]),
        }


def dfa_table_states(policy: "CompiledPolicy") -> np.ndarray:
    """[T] int: each table's own state count, found from the table (padding
    states self-loop and nothing real reaches them): 1 + the largest state
    reachable from state 0."""
    tables = policy.dfa_tables
    T = tables.shape[0]
    reach_max = np.maximum.accumulate(
        tables.max(axis=2).astype(np.int64), axis=1)            # [T, S]
    n = np.ones((T,), dtype=np.int64)
    while True:
        nxt = np.maximum(n, reach_max[np.arange(T), n - 1] + 1)
        if (nxt == n).all():
            return n
        n = nxt


DFA_ACCEPTS, DFA_ABSORBS = 1, 2


def dfa_state_flags(tables: np.ndarray, accept: np.ndarray) -> np.ndarray:
    """[T, S] uint8 read off the tables themselves, never off the regex:
    DFA_ACCEPTS where the state accepts, DFA_ABSORBS where every one of its
    256 transitions returns to it (the empty subset of an anchored pattern,
    the accept of an unanchored one: compiler/redfa.py), so that no further
    byte can change the verdict of a DFA that has reached it.  A padding
    state self-loops too and is marked; nothing real reaches it."""
    own = np.arange(tables.shape[1], dtype=tables.dtype)[None, :]
    # the least and the largest target are the state itself: two reductions
    # and no [T, S, 256] temporary (a sixth of the compare's time at 332 MB)
    absorbs = (tables.min(axis=2) == own) & (tables.max(axis=2) == own)
    return (accept.astype(np.uint8) * DFA_ACCEPTS
            | absorbs.astype(np.uint8) * DFA_ABSORBS)


def _natural_sizes(policy: "CompiledPolicy") -> Dict[str, Any]:
    """Per config, what its own circuit takes on every axis of the own
    layout, read off the corpus-wide padded tables (and each DFA table's
    own state count, ``table_states``)."""
    own = policy.own
    trivial = (own.evals[:, 0] == TRUE_SLOT) & (own.evals[:, 2] == 0)  # [G, E]
    E = trivial.shape[1]
    last = np.where(trivial, 0, np.arange(1, E + 1)).max(axis=1, initial=0)
    rows = policy.config_dfa_rows
    table_states = dfa_table_states(policy)
    states = np.zeros(rows.shape, dtype=np.int64)
    if policy.n_byte_attrs and policy.dfa_tables.size:
        per_row = table_states[policy.dfa_table_of_row]
        states = np.where(rows >= 0, per_row[np.maximum(rows, 0)], 0)
    return {
        "table_states": table_states,
        "leaves": (own.leaves >= 0).sum(axis=1),
        "cpu": (own.cpu_leaves >= 0).sum(axis=1),
        "nodes": [(n >= 0).sum(axis=1) for n in own.nodes],
        "evals": np.maximum(last, 1),
        "dfa": (rows >= 0).sum(axis=1),
        "states": states.max(axis=1, initial=0),
    }


def _tile8(n) -> Any:
    """The DFA state axis in whole device tiles (see compile_corpus)."""
    return -(-np.maximum(n, 1) // 8) * 8


def config_row_bytes(policy: "CompiledPolicy", sizes=None) -> np.ndarray:
    """[G] int64: the class rule's size of each config (see CLASS_RATIO)."""
    sizes = _natural_sizes(policy) if sizes is None else sizes
    out = sizes["leaves"] * (OWN_FIELDS * 4) + sizes["evals"] * 12 \
        + sizes["dfa"] * (4 + _tile8(sizes["states"]) * 256)
    for n, (children, _) in zip(sizes["nodes"], policy.own.levels):
        out = out + n * (int(children.shape[2]) * 4 + 1)
    return out.astype(np.int64)


def split_classes(row_bytes: np.ndarray) -> np.ndarray:
    """[G] int32 class of each config by the class rule, classes numbered by
    ascending size."""
    w = np.maximum(np.asarray(row_bytes, dtype=np.int64), CLASS_FLOOR_BYTES)
    order = np.argsort(w, kind="stable")
    ws = w[order]
    class_of = np.zeros(w.shape, dtype=np.int32)
    i, c = 0, 0
    while i < ws.shape[0]:
        j = int(np.searchsorted(ws, CLASS_RATIO * ws[i], side="left"))
        class_of[order[i:j]] = c
        i, c = j, c + 1
    return class_of


def _class_of(policy: "CompiledPolicy", cfgs: np.ndarray, sizes,
              natural: bool) -> SizeClass:
    """Cut one class out of the corpus-wide padded layout: the member rows,
    every axis narrowed to the members' natural maximum (``natural`` False:
    left as the corpus's, which ShapeTargets forced so that shards stack),
    own-buffer positions moved to the narrower buffer, and a DFA table store
    of the rows the members reach, at their own state axis."""
    own = policy.own
    G = own.leaves.shape[0]
    E = own.evals.shape[2]
    wide_n = [int(n.shape[1]) for n in own.nodes]
    l_w, c_w = int(own.leaves.shape[1]), int(own.cpu_leaves.shape[1])
    D_w = int(policy.config_dfa_rows.shape[1])
    if natural:
        def most(a):
            return max(int(a[cfgs].max(initial=0)), 1)

        l_c, c_c, D_c = most(sizes["leaves"]), most(sizes["cpu"]), most(sizes["dfa"])
        n_c = [most(n) for n in sizes["nodes"]]
        E_c = min(_round_up(most(sizes["evals"]), minimum=2), E)
    else:
        l_c, c_c, D_c, n_c, E_c = l_w, c_w, D_w, wide_n, E
    # corpus-wide own-buffer position -> the class's (a real reference names
    # a real position, and those are a prefix of every region)
    lut = np.full((_LEAF_BASE + l_w + sum(wide_n),), FALSE_SLOT, dtype=np.int32)
    lut[TRUE_SLOT], lut[FALSE_SLOT] = TRUE_SLOT, FALSE_SLOT
    lut[_LEAF_BASE:_LEAF_BASE + l_c] = np.arange(_LEAF_BASE, _LEAF_BASE + l_c)
    wide_base, base = _LEAF_BASE + l_w, _LEAF_BASE + l_c
    for n_wide, n in zip(wide_n, n_c):
        lut[wide_base:wide_base + n] = np.arange(base, base + n)
        wide_base, base = wide_base + n_wide, base + n
    evals = own.evals[cfgs][:, :, :E_c].copy()
    evals[:, :2] = lut[evals[:, :2]]
    layout = OwnLayout(
        leaves=np.ascontiguousarray(own.leaves[cfgs][:, :l_c]),
        nodes=tuple(np.ascontiguousarray(n[cfgs][:, :k])
                    for n, k in zip(own.nodes, n_c)),
        leaf_tab=np.ascontiguousarray(own.leaf_tab[cfgs][:, :l_c]),
        levels=tuple((lut[ch[cfgs][:, :k]], np.ascontiguousarray(a[cfgs][:, :k]))
                     for (ch, a), k in zip(own.levels, n_c)),
        evals=evals,
        cpu_leaves=np.ascontiguousarray(own.cpu_leaves[cfgs][:, :c_c]))
    cfg_local = np.full((G,), -1, dtype=np.int32)
    cfg_local[cfgs] = np.arange(cfgs.shape[0], dtype=np.int32)

    # the class's DFA store: the rows its members reach, the tables those
    # rows name, the state axis of the largest of them
    rows = policy.config_dfa_rows[cfgs][:, :D_c]
    if natural:
        dfa_rows = np.unique(rows[rows >= 0]).astype(np.int32)
    else:
        dfa_rows = np.arange(policy.dfa_table_of_row.shape[0], dtype=np.int32)
    local_rows = np.where(
        rows >= 0, np.searchsorted(dfa_rows, np.maximum(rows, 0)), -1
    ).astype(np.int32)
    if natural:
        tabs, table_of_row = np.unique(policy.dfa_table_of_row[dfa_rows],
                                       return_inverse=True)
        S_c = int(_tile8(sizes["table_states"][tabs].max(initial=1))) \
            if tabs.size else int(policy.dfa_tables.shape[1])
        if not tabs.size:
            tabs = np.zeros((1,), dtype=np.int64)   # no DFA row: never read
    else:
        tabs = np.arange(policy.dfa_tables.shape[0])
        table_of_row = policy.dfa_table_of_row
        S_c = int(policy.dfa_tables.shape[1])
    return SizeClass(
        configs=cfgs.astype(np.int32), cfg_local=cfg_local, own=layout,
        dfa_rows=dfa_rows, config_dfa_rows=local_rows,
        # a class of 256 states or fewer keeps u8 tables whatever the
        # corpus's widest table is
        dfa_tables=np.ascontiguousarray(policy.dfa_tables[tabs][:, :S_c],
                                        dtype=dfa_state_dtype(S_c)),
        dfa_accept=np.ascontiguousarray(policy.dfa_accept[tabs][:, :S_c]),
        dfa_table_of_row=np.asarray(table_of_row, dtype=np.int32).reshape(-1),
        # forced widths (shards stack on one byte tensor): the floor
        device_width=class_device_width(D_c, S_c)
        if natural and dfa_rows.size else DFA_VALUE_BYTES)


def derive_classes(policy: "CompiledPolicy",
                   natural: bool = True) -> Tuple[SizeClass, ...]:
    """The corpus's size classes, by the class rule, from ``policy.own`` and
    ``policy.config_dfa_rows``.  ``natural`` False (a compile under
    ShapeTargets): one class at the forced widths."""
    sizes = _natural_sizes(policy)
    G = policy.own.leaves.shape[0]
    class_of = split_classes(config_row_bytes(policy, sizes)) if natural \
        else np.zeros((G,), dtype=np.int32)
    return tuple(
        _class_of(policy, np.nonzero(class_of == c)[0], sizes, natural)
        for c in range(int(class_of.max(initial=0)) + 1))


def derive_layouts(policy: "CompiledPolicy", keep: bool = False,
                   targets: Optional[ShapeTargets] = None) -> None:
    """(Re)build everything the compiler derives from the corpus arrays:
    ``config_dfa_rows``, the own-config layout and the size classes.
    ``keep`` leaves a table that is already there as it is (a deserialized
    or hand-built policy); ``targets`` widens the own axes and yields one
    class."""
    reach = None
    if policy.config_dfa_rows is None or not keep:
        reach = own_reach(policy)
        rows = derive_config_dfa_rows(policy, reach)
        if targets is not None:
            assert targets.n_own_dfa_rows >= rows.shape[1], \
                "targets.n_own_dfa_rows too small"
            rows = np.pad(
                rows, ((0, 0), (0, targets.n_own_dfa_rows - rows.shape[1])),
                constant_values=-1)
        policy.config_dfa_rows = rows
    if policy.own is None or not keep:
        policy.own = derive_own_layout(
            policy, own_reach(policy) if reach is None else reach,
            targets=targets)
    if policy.classes is None or not keep:
        policy.classes = derive_classes(policy, natural=targets is None)


def _round_up(n: int, multiple: int = 8, minimum: int = 8) -> int:
    """Pad to the next power-of-two-ish bucket so shape changes (and thus XLA
    recompiles) are logarithmic in corpus growth (SURVEY.md §7 bucketing)."""
    n = max(n, minimum)
    bucket = minimum
    while bucket < n:
        bucket *= 2
    return bucket


class _Lowerer:
    def __init__(self, interner: StringInterner, members_k: int, enable_dfa: bool = True,
                 dfa_cache: Optional[Dict[str, Optional["object"]]] = None):
        self.interner = interner
        self.members_k = members_k
        self.enable_dfa = enable_dfa
        self.attrs: Dict[str, int] = {}
        self.leaves: List[_Leaf] = []
        self.leaf_dedupe: Dict[Tuple[int, int, int, Optional[str]], int] = {}
        # nodes: (depth, is_and, children buffer idxs)
        self.nodes: List[Tuple[int, bool, List[int]]] = []
        self.depth_of: Dict[int, int] = {TRUE_SLOT: 0, FALSE_SLOT: 0}
        self.tree_leaf_by_expr: Dict[int, int] = {}
        # structural And/Or node dedup across ALL configs: two configs
        # lowering the identical subtree share one node row (and thus one
        # result-buffer slot), shrinking the per-level matrices and the
        # whole padded buffer — rule-tensor compaction at the circuit level
        self.node_dedupe: Dict[Tuple[bool, Tuple[int, ...]], int] = {}
        # regex determinization is the most expensive part of compilation;
        # a caller-shared cache lets the sharded model's two-pass compile
        # (and all its shards) determinize each distinct regex once
        self._dfa_cache: Dict[str, Optional["object"]] = (
            dfa_cache if dfa_cache is not None else {}
        )

    def _dfa_for(self, pattern: str):
        hit = self._dfa_cache.get(pattern, _DFA_MISS)
        if hit is not _DFA_MISS:
            return hit
        from .redfa import compile_regex_dfa, reserve_memo

        # the process-wide memo keeps room for the corpus being lowered
        reserve_memo(len(self._dfa_cache) + 1)
        dfa = compile_regex_dfa(pattern)
        self._dfa_cache[pattern] = dfa
        return dfa

    def attr_idx(self, selector: str) -> int:
        i = self.attrs.get(selector)
        if i is None:
            i = len(self.attrs)
            self.attrs[selector] = i
        return i

    def lower_relation_leaf(self, g: InGroup) -> int:
        """Hierarchical-membership leaf: one atom per (selector, closure,
        group), deduped across configs — configs declaring identical edge
        sets share one compiled relation table (closure digest identity)."""
        attr = self.attr_idx(g.selector)
        key = (OP_RELATION, attr, 0, f"{g.relation.digest}:{g.group}")
        idx = self.leaf_dedupe.get(key)
        if idx is None:
            idx = len(self.leaves)
            self.leaves.append(_Leaf(op=OP_RELATION, attr=attr, const=0,
                                     rel=g.relation, group=g.group))
            self.leaf_dedupe[key] = idx
        buf = _LEAF_BASE + idx
        self.depth_of[buf] = 0
        return buf

    def lower_leaf(self, p: Pattern) -> int:
        attr = self.attr_idx(p.selector)
        if p.operator in NUMERIC_OPERATORS:
            # constant folded + int32-bounded at Pattern construction;
            # unfoldable constants never reach here (_has_invalid_regex
            # routes the whole tree to the CPU oracle)
            key = (_NUM_OP_OF[p.operator], attr,
                   int(p._num_const), None)  # type: ignore[attr-defined]
            idx = self.leaf_dedupe.get(key)
            if idx is None:
                idx = len(self.leaves)
                self.leaves.append(
                    _Leaf(op=key[0], attr=attr, const=key[2]))
                self.leaf_dedupe[key] = idx
            buf = _LEAF_BASE + idx
            self.depth_of[buf] = 0
            return buf
        if p.operator is Operator.MATCHES:
            rx = getattr(p, "_regex", None)
            if rx is None:
                # invalid regex: evaluation errors deny in the reference
                # (error return from Pattern.Matches → deny); constant-false
                key = (OP_ERROR, attr, 0, p.value)
            elif self.enable_dfa and self._dfa_for(p.value) is not None:
                key = (OP_REGEX_DFA, attr, 0, p.value)
            else:
                key = (OP_CPU, attr, 0, p.value)
        else:
            op = {
                Operator.EQ: OP_EQ,
                Operator.NEQ: OP_NEQ,
                Operator.INCL: OP_INCL,
                Operator.EXCL: OP_EXCL,
            }[p.operator]
            key = (op, attr, self.interner.intern(p.value), None)
        idx = self.leaf_dedupe.get(key)
        if idx is None:
            idx = len(self.leaves)
            self.leaves.append(_Leaf(op=key[0], attr=key[1], const=key[2], regex=key[3]))
            self.leaf_dedupe[key] = idx
        buf = _LEAF_BASE + idx
        self.depth_of[buf] = 0
        return buf

    def lower_tree_cpu(self, expr: Expression) -> int:
        """Whole-tree CPU-fallback leaf: used when a tree contains an invalid
        regex, whose error must propagate with the reference's left-to-right
        short-circuit semantics (error ⇒ deny for rules, ⇒ skip for
        conditions; both read as False at the tree root —
        ref pkg/jsonexp/expressions.go:87-91,111-154).  Un-tensorizable, so
        the encoder evaluates the expression with the CPU oracle."""
        idx = len(self.leaves)
        self.leaves.append(_Leaf(op=OP_TREE_CPU, attr=0, const=0, tree=expr))
        self.tree_leaf_by_expr[id(expr)] = idx
        buf = _LEAF_BASE + idx
        self.depth_of[buf] = 0
        return buf

    def lower(self, expr: Expression) -> int:
        """Return the buffer index holding this expression's result."""
        if _has_invalid_regex(expr):
            return self.lower_tree_cpu(expr)
        if isinstance(expr, Pattern):
            return self.lower_leaf(expr)
        if isinstance(expr, InGroup):
            return self.lower_relation_leaf(expr)
        is_and = isinstance(expr, And)
        children = [self.lower(c) for c in expr.children]
        if not children:
            return TRUE_SLOT if is_and else FALSE_SLOT
        if len(children) == 1:
            return children[0]
        dedupe_key = (is_and, tuple(children))
        hit = self.node_dedupe.get(dedupe_key)
        if hit is not None:
            return hit
        depth = 1 + max(self.depth_of[c] for c in children)
        node_id = len(self.nodes)
        self.nodes.append((depth, is_and, children))
        # buffer position assigned later (after level grouping); use a
        # placeholder key: negative ids -(node_id+1)
        self.depth_of[-(node_id + 1)] = depth
        self.node_dedupe[dedupe_key] = -(node_id + 1)
        return -(node_id + 1)


def compile_corpus(
    configs: Sequence[ConfigRules],
    members_k: int = 16,
    pad: bool = True,
    targets: Optional[ShapeTargets] = None,
    interner: Optional[StringInterner] = None,
    enable_dfa: bool = True,
    dfa_cache: Optional[Dict[str, Any]] = None,
    ovf_assist: Optional[bool] = None,
) -> CompiledPolicy:
    """Compile all configs' pattern rules into one CompiledPolicy.

    ``targets`` forces final operand shapes — including the DFA row/state/
    byte axes, so tensor-parallel shards stack uniformly (must dominate the
    natural shapes); ``interner`` lets shards share one global string table;
    ``enable_dfa=False`` routes all regexes to the CPU lane (tests and manual
    fallback — the sharded model rides the device DFA lane by default).

    ``ovf_assist`` (ISSUE 14; default off, env AUTHORINO_TPU_OVF_ASSIST=1)
    keeps membership-overflow rows on the device lane: incl/excl leaves gain
    dense CPU-assist columns carrying the encoder's exact per-leaf overflow
    answers and the kernel selects them under the [B, M] overflow mask —
    the cpu-grid-overflow lowerability caveat drops for assisted corpora.
    Off by default so the host-fallback lane (the degrade backstop) keeps
    its full test surface."""
    if ovf_assist is None:
        ovf_assist = os.environ.get(
            "AUTHORINO_TPU_OVF_ASSIST", "") in ("1", "true", "yes")
    interner = interner if interner is not None else StringInterner()
    lw = _Lowerer(interner, members_k, enable_dfa=enable_dfa, dfa_cache=dfa_cache)

    # 1. lower every expression; remember (cond_ref, rule_ref) per evaluator
    per_config: List[Tuple[str, List[Tuple[Optional[int], int]]]] = []
    for cfg in configs:
        pairs: List[Tuple[Optional[int], int]] = []
        for cond, rule in cfg.evaluators:
            cond_ref = lw.lower(cond) if cond is not None else None
            rule_ref = lw.lower(rule)
            pairs.append((cond_ref, rule_ref))
        per_config.append((cfg.name, pairs))

    # 2. assign buffer positions: leaves first, then nodes grouped by depth.
    # Node positions must account for leaf AND level-row PADDING — the
    # kernel's result buffer holds the padded leaf block, then each padded
    # level's rows, in order.
    n_leaves = len(lw.leaves)
    Lp = _round_up(n_leaves) if pad else max(n_leaves, 1)
    if targets is not None:
        assert targets.n_leaves >= n_leaves, "targets.n_leaves too small"
        Lp = targets.n_leaves
    by_depth: Dict[int, List[int]] = {}
    for node_id, (depth, _, _) in enumerate(lw.nodes):
        by_depth.setdefault(depth, []).append(node_id)
    levels_raw: List[List[int]] = [by_depth[d] for d in sorted(by_depth)]
    n_levels = len(levels_raw)
    if targets is not None:
        assert len(targets.levels) >= n_levels, "targets.levels too shallow"
        n_levels = len(targets.levels)
        levels_raw += [[] for _ in range(n_levels - len(levels_raw))]

    def level_rows(l: int) -> int:
        natural = len(levels_raw[l])
        if targets is not None:
            assert targets.levels[l][0] >= natural, "targets level rows too small"
            return targets.levels[l][0]
        return natural

    node_pos: Dict[int, int] = {}
    cursor = _LEAF_BASE + Lp
    for l, level_nodes in enumerate(levels_raw):
        for row, node_id in enumerate(level_nodes):
            node_pos[node_id] = cursor + row
        cursor += level_rows(l)

    def ref_to_buf(ref: int) -> int:
        # negative refs encode node placeholders -(node_id+1); others are
        # already buffer positions (TRUE/FALSE slots or leaves)
        if ref < 0:
            return node_pos[-ref - 1]
        return ref

    # 3. build level tensors (padded rows evaluate And() ≡ True, harmless)
    levels: List[Tuple[np.ndarray, np.ndarray]] = []
    for l, level_nodes in enumerate(levels_raw):
        max_c = max((len(lw.nodes[nid][2]) for nid in level_nodes), default=1)
        if targets is not None:
            assert targets.levels[l][1] >= max_c, "targets level width too small"
            max_c = targets.levels[l][1]
        rows = level_rows(l)
        children = np.full((rows, max_c), TRUE_SLOT, dtype=np.int32)
        is_and = np.ones((rows,), dtype=bool)
        for row, nid in enumerate(level_nodes):
            _, node_is_and, kids = lw.nodes[nid]
            is_and[row] = node_is_and
            padv = TRUE_SLOT if node_is_and else FALSE_SLOT
            buf_kids = [ref_to_buf(k) for k in kids]
            children[row, : len(buf_kids)] = buf_kids
            children[row, len(buf_kids):] = padv
        levels.append((children, is_and))

    # 4. per-config evaluator tables.  Targets pad the row count so shards
    # stack; padded rows are all-TRUE_SLOT — trivially-allow configs that no
    # request can ever select (row ids only cover the real configs).
    n_configs = len(per_config)
    Gp = n_configs
    if targets is not None:
        assert targets.n_configs >= n_configs, "targets.n_configs too small"
        Gp = targets.n_configs
    max_e = max((len(p[1]) for p in per_config), default=1) or 1
    if targets is not None:
        assert targets.max_e >= max_e, "targets.max_e too small"
        max_e = targets.max_e
    elif pad:
        max_e = _round_up(max_e, minimum=2)
    eval_cond = np.full((Gp, max_e), TRUE_SLOT, dtype=np.int32)
    eval_rule = np.full((Gp, max_e), TRUE_SLOT, dtype=np.int32)
    eval_has_cond = np.zeros((Gp, max_e), dtype=bool)
    config_ids: Dict[str, int] = {}
    for row, (name, pairs) in enumerate(per_config):
        config_ids[name] = row
        for col, (cond_ref, rule_ref) in enumerate(pairs):
            if cond_ref is not None:
                eval_cond[row, col] = ref_to_buf(cond_ref)
                eval_has_cond[row, col] = True
            eval_rule[row, col] = ref_to_buf(rule_ref)

    # 5. leaf tensors (padded to the bucket chosen in step 2)
    leaf_op = np.full((Lp,), OP_EQ, dtype=np.int32)
    leaf_attr = np.zeros((Lp,), dtype=np.int32)
    leaf_const = np.full((Lp,), PAD, dtype=np.int32)  # PAD const: matches nothing
    leaf_regex: List[Optional[re.Pattern]] = [None] * Lp
    leaf_tree: List[Optional[Expression]] = [None] * Lp
    leaf_is_membership = np.zeros((Lp,), dtype=bool)
    leaf_dfa_row = np.zeros((Lp,), dtype=np.int32)
    dfa_rows: List[Tuple[int, Any]] = []  # (attr, DFA) per device-regex leaf
    # relation lane registry (ISSUE 14): closure instances deduped by
    # digest, (attr, instance) operand slots, (instance, group) columns
    leaf_rel_slot = np.zeros((Lp,), dtype=np.int32)
    leaf_rel_col = np.zeros((Lp,), dtype=np.int32)
    rel_instances: List[RelationClosure] = []
    rel_inst_idx: Dict[str, int] = {}
    rel_slot_idx: Dict[Tuple[int, int], int] = {}
    rel_slots_list: List[Tuple[int, int]] = []
    rel_col_idx: Dict[Tuple[int, str], int] = {}
    rel_col_names_list: List[Tuple[int, str]] = []
    for i, leaf in enumerate(lw.leaves):
        leaf_op[i] = leaf.op
        leaf_attr[i] = leaf.attr
        leaf_const[i] = leaf.const
        leaf_is_membership[i] = leaf.op in (OP_INCL, OP_EXCL)
        if leaf.op in (OP_CPU, OP_REGEX_DFA) and leaf.regex is not None:
            leaf_regex[i] = re.compile(leaf.regex)  # CPU lane / overflow fallback
        if leaf.op == OP_REGEX_DFA:
            leaf_dfa_row[i] = len(dfa_rows)
            dfa_rows.append((leaf.attr, lw._dfa_for(leaf.regex)))
        if leaf.op == OP_TREE_CPU:
            leaf_tree[i] = leaf.tree
        if leaf.op == OP_RELATION:
            inst = rel_inst_idx.get(leaf.rel.digest)
            if inst is None:
                inst = rel_inst_idx[leaf.rel.digest] = len(rel_instances)
                rel_instances.append(leaf.rel)
            slot = rel_slot_idx.get((leaf.attr, inst))
            if slot is None:
                slot = rel_slot_idx[(leaf.attr, inst)] = len(rel_slots_list)
                rel_slots_list.append((leaf.attr, inst))
            col = rel_col_idx.get((inst, leaf.group))
            if col is None:
                col = rel_col_idx[(inst, leaf.group)] = len(rel_col_names_list)
                rel_col_names_list.append((inst, leaf.group))
            leaf_rel_slot[i] = slot
            leaf_rel_col[i] = col

    n_attrs = len(lw.attrs)
    Ap = _round_up(n_attrs) if pad else max(n_attrs, 1)
    if targets is not None:
        assert targets.n_attrs >= n_attrs, "targets.n_attrs too small"
        Ap = targets.n_attrs

    # device regex lane tables (states padded to max).  Rows whose regexes
    # determinized to the same automaton — the same pattern on different
    # attrs, or byte-identical tables across AuthConfigs — share ONE
    # [S, 256] table; rows reach it through dfa_table_of_row (rule-tensor
    # compaction).  Targets force R/S/NB/T so independently-compiled shards
    # stack (padded rows/tables are never referenced; padded states
    # self-loop).
    R = len(dfa_rows)
    # the state axis in whole device tiles (8 sublanes): the served entry
    # gathers [S, 256] tables by row, and a store whose S is not a multiple
    # of 8 is copied whole into a padded layout on every launch (87 MB a
    # launch at 20,000 tables of 17 states: PERF.md, PR 28)
    S = -(-max((d.n_states for _, d in dfa_rows), default=1) // 8) * 8
    Rp = max(R, 1)
    if targets is not None:
        assert targets.n_dfa_rows >= Rp, "targets.n_dfa_rows too small"
        assert targets.n_dfa_states >= S, "targets.n_dfa_states too small"
        Rp, S = targets.n_dfa_rows, targets.n_dfa_states
    dfa_table_of_row = np.zeros((Rp,), dtype=np.int32)
    dfa_leaf_attr = np.zeros((Rp,), dtype=np.int32)
    attr_byte_slot = np.full((Ap,), -1, dtype=np.int32)
    n_byte_attrs = 0
    table_idx: Dict[Any, int] = {}
    table_dfas: List[Any] = []
    for r_i, (attr, dfa) in enumerate(dfa_rows):
        tkey = (dfa.trans.tobytes(), dfa.accept.tobytes())
        t_i = table_idx.get(tkey)
        if t_i is None:
            t_i = table_idx[tkey] = len(table_dfas)
            table_dfas.append(dfa)
        dfa_table_of_row[r_i] = t_i
        dfa_leaf_attr[r_i] = attr
        if attr_byte_slot[attr] < 0:
            attr_byte_slot[attr] = n_byte_attrs
            n_byte_attrs += 1
    T = len(table_dfas)
    Tp = max(T, 1)
    if targets is not None:
        assert targets.n_dfa_tables >= Tp, "targets.n_dfa_tables too small"
        Tp = targets.n_dfa_tables
    dfa_tables = np.zeros((Tp, S, 256), dtype=dfa_state_dtype(S))
    dfa_accept = np.zeros((Tp, S), dtype=bool)
    for t_i, dfa in enumerate(table_dfas):
        s = dfa.n_states
        dfa_tables[t_i, :s] = dfa.trans
        # padded states self-loop so they can never be reached anyway
        dfa_tables[t_i, s:] = np.arange(s, S)[:, None]
        dfa_accept[t_i, :s] = dfa.accept
    for t_i in range(T, Tp):
        # padded tables (mesh targets): self-loop everywhere, never referenced
        dfa_tables[t_i] = np.arange(S, dtype=dfa_tables.dtype)[:, None]
    if targets is not None:
        assert targets.n_byte_attrs >= n_byte_attrs, "targets.n_byte_attrs too small"
        # force a uniform (possibly dummy) byte-tensor axis so shards whose
        # sub-corpus has fewer (or no) regexes still stack with the others
        n_byte_attrs = targets.n_byte_attrs
    attr_selectors = [""] * Ap
    for sel, idx in lw.attrs.items():
        attr_selectors[idx] = sel

    # 5b. numeric comparator lane: attrs with numeric leaves get compact
    # [B, NN] value slots (the encoder parses the rendered value once per
    # attr; the kernel compares int32 against the folded constants)
    num_attr_slot = np.full((Ap,), -1, dtype=np.int32)
    num_attrs_list: List[int] = []
    for i in range(n_leaves):
        if leaf_op[i] in NUMERIC_OPS:
            a_i = int(leaf_attr[i])
            if num_attr_slot[a_i] < 0:
                num_attr_slot[a_i] = len(num_attrs_list)
                num_attrs_list.append(a_i)
    NN_real = len(num_attrs_list)
    NN = NN_real
    if targets is not None:
        assert targets.n_num_attrs >= NN_real, "targets.n_num_attrs too small"
        NN = targets.n_num_attrs
    elif pad and NN_real:
        NN = _round_up(NN_real, minimum=2)

    # 5c. relation tables: close every instance's edges into the bitmatrix.
    # Row 0 is the reserved all-zero row (unknown entities); each
    # instance's entities occupy a contiguous row block.  Columns exist
    # only for QUERIED (instance, group) pairs, so W tracks the policy
    # surface, not the hierarchy size.
    rel_entity_rows: List[Dict[str, int]] = []
    next_row = 1
    for rel in rel_instances:
        rel_entity_rows.append(
            {e: next_row + j for j, e in enumerate(rel.entities)})
        next_row += len(rel.entities)
    NR_real = len(rel_slots_list)
    n_rel_cols = len(rel_col_names_list)
    R_real = next_row
    Rp = _round_up(R_real) if pad else max(R_real, 1)
    W = max((n_rel_cols + 7) // 8, 1)
    NRp = NR_real
    if targets is not None:
        assert targets.n_rel_slots >= NR_real, "targets.n_rel_slots too small"
        assert targets.n_rel_rows >= R_real, "targets.n_rel_rows too small"
        assert targets.n_rel_width >= W or not NR_real, \
            "targets.n_rel_width too small"
        NRp, Rp = targets.n_rel_slots, targets.n_rel_rows
        W = max(W, targets.n_rel_width)
    has_rel = NRp > 0
    if has_rel:
        rel_bits = np.zeros((Rp, W), dtype=np.uint8)
        for c, (inst, group) in enumerate(rel_col_names_list):
            closure = rel_instances[inst]
            for entity, row in rel_entity_rows[inst].items():
                if closure.contains(entity, group):
                    rel_bits[row, c >> 3] |= np.uint8(1 << (c & 7))
        rel_slot_attr = np.zeros((max(NRp, 1),), dtype=np.int32)
        for s, (attr, _inst) in enumerate(rel_slots_list):
            rel_slot_attr[s] = attr
    else:
        rel_bits = None
        rel_slot_attr = np.zeros((1,), dtype=np.int32)

    # 6. per-config CPU metadata
    config_attrs: List[List[int]] = []
    config_cpu_leaves: List[List[int]] = []
    # which leaves belong to which config: walk expressions again via dedupe map
    leaf_of_attr: Dict[int, List[int]] = {}
    for i, leaf in enumerate(lw.leaves):
        leaf_of_attr.setdefault(leaf.attr, []).append(i)

    def collect_attrs(expr: Expression, acc_attrs: set, acc_cpu: set):
        if _has_invalid_regex(expr):
            # whole tree rode the CPU-fallback leaf; no attrs were lowered
            acc_cpu.add(lw.tree_leaf_by_expr[id(expr)])
            return
        if isinstance(expr, InGroup):
            acc_attrs.add(lw.attrs[expr.selector])
            return
        if isinstance(expr, Pattern):
            attr = lw.attrs[expr.selector]
            acc_attrs.add(attr)
            if expr.operator is Operator.MATCHES:
                rx = getattr(expr, "_regex", None)
                for op in (OP_ERROR, OP_REGEX_DFA, OP_CPU):
                    key = (op, attr, 0, expr.value)
                    if key in lw.leaf_dedupe:
                        acc_cpu.add(lw.leaf_dedupe[key])
                        break
            elif expr.operator in (Operator.INCL, Operator.EXCL):
                op = OP_INCL if expr.operator is Operator.INCL else OP_EXCL
                key = (op, attr, interner.intern(expr.value), None)
                acc_cpu.add(lw.leaf_dedupe[key])  # overflow lane candidates
        else:
            for c in expr.children:
                collect_attrs(c, acc_attrs, acc_cpu)

    for cfg in configs:
        a: set = set()
        cl: set = set()
        for cond, rule in cfg.evaluators:
            if cond is not None:
                collect_attrs(cond, a, cl)
            collect_attrs(rule, a, cl)
        config_attrs.append(sorted(a))
        config_cpu_leaves.append(sorted(cl))
    # per-config metadata padded alongside the eval-table rows (Gp): padded
    # configs resolve nothing and evaluate vacuously true, and no request
    # ever maps to them
    config_attrs += [[] for _ in range(Gp - n_configs)]
    config_cpu_leaves += [[] for _ in range(Gp - n_configs)]

    # verdict-cache eligibility: a config referencing any request-unique /
    # time-dependent selector produces rows that never repeat — exclude it
    # from the snapshot-scoped verdict cache (pollution dial, not a
    # correctness gate: the cache key is the full encoded-row digest)
    attr_uncacheable = np.zeros((Ap,), dtype=bool)
    for sel_str, a_idx in lw.attrs.items():
        attr_uncacheable[a_idx] = _selector_uncacheable(sel_str)
    config_cacheable = np.ones((Gp,), dtype=bool)
    for row, attrs_l in enumerate(config_attrs):
        if any(attr_uncacheable[a_i] for a_i in attrs_l):
            config_cacheable[row] = False

    # 7. transfer-compaction metadata: which attrs' membership vectors the
    # kernel can ever read (incl/excl leaves), and which leaves ride the
    # dense CPU lane (true-CPU regex/tree leaves; DFA leaves' columns are
    # read only under byte-overflow)
    member_attr_slot = np.full((Ap,), -1, dtype=np.int32)
    member_attrs_list: List[int] = []
    for i in range(n_leaves):
        if leaf_is_membership[i]:
            a_i = int(leaf_attr[i])
            if member_attr_slot[a_i] < 0:
                member_attr_slot[a_i] = len(member_attrs_list)
                member_attrs_list.append(a_i)
    M = targets.n_member_attrs if targets is not None else max(len(member_attrs_list), 1)
    assert M >= max(len(member_attrs_list), 1), "targets.n_member_attrs too small"

    # membership leaves join the dense assist columns under ovf_assist:
    # their exact overflow answers (already computed by the encoder) travel
    # to the device and the kernel selects them under the overflow mask
    cpu_leaf_list_: List[int] = [
        i for i in range(n_leaves)
        if leaf_op[i] in (OP_CPU, OP_TREE_CPU, OP_REGEX_DFA)
        or (ovf_assist and leaf_op[i] in (OP_INCL, OP_EXCL))
    ]
    C = targets.n_cpu_leaves if targets is not None else max(len(cpu_leaf_list_), 1)
    assert C >= max(len(cpu_leaf_list_), 1), "targets.n_cpu_leaves too small"

    policy = CompiledPolicy(
        leaf_op=leaf_op,
        leaf_attr=leaf_attr,
        leaf_const=leaf_const,
        levels=tuple((c.astype(np.int32), a) for c, a in levels),
        eval_cond=eval_cond,
        eval_rule=eval_rule,
        eval_has_cond=eval_has_cond,
        dfa_tables=dfa_tables,
        dfa_accept=dfa_accept,
        dfa_table_of_row=dfa_table_of_row,
        dfa_leaf_attr=dfa_leaf_attr,
        leaf_dfa_row=leaf_dfa_row,
        attr_byte_slot=attr_byte_slot,
        n_byte_attrs=n_byte_attrs,
        interner=interner,
        attr_selectors=attr_selectors,
        config_ids=config_ids,
        config_attrs=config_attrs,
        config_cpu_leaves=config_cpu_leaves,
        leaf_regex=leaf_regex,
        leaf_tree=leaf_tree,
        leaf_is_membership=leaf_is_membership,
        members_k=members_k,
        member_attr_slot=member_attr_slot,
        member_attrs=np.asarray(member_attrs_list, dtype=np.int32),
        n_member_attrs=M,
        cpu_leaf_list=np.asarray(cpu_leaf_list_, dtype=np.int32),
        n_cpu_leaves=C,
        config_exprs=[list(cfg.evaluators) for cfg in configs]
        + [[] for _ in range(Gp - n_configs)],
        config_cacheable=config_cacheable,
        num_attr_slot=num_attr_slot,
        num_attrs=np.asarray(num_attrs_list, dtype=np.int32),
        n_num_attrs=NN,
        rel_bits=rel_bits,
        leaf_rel_slot=leaf_rel_slot,
        leaf_rel_col=leaf_rel_col,
        rel_slot_attr=rel_slot_attr,
        n_rel_slots=NRp,
        rel_instances=rel_instances,
        rel_entity_rows=rel_entity_rows,
        rel_slots=rel_slots_list,
        rel_col_names=rel_col_names_list,
        ovf_assist=bool(ovf_assist),
    )
    if targets is not None:
        derive_layouts(policy, targets=targets)
    return policy
