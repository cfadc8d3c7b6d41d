"""PolicyModel — the framework's flagship "model": a compiled rule corpus
plus its batched evaluation function.

The analog of a forward pass here is one micro-batched policy evaluation:
(requests × rules) int32 compares + boolean-circuit reduction → per-request
allow verdicts (SURVEY.md north star; replaces the per-request Go hot loop at
ref: pkg/service/auth_pipeline.go:287-322 + pkg/jsonexp/expressions.go:59).
There is no gradient training in this domain; the "training-step analog" is
corpus compilation (reconcile-time) + this evaluation step (request-time).

Requests whose membership arrays overflow the compact payload (K elements)
are re-decided on host by the expression oracle — `host_results` implements
the exact reference semantics (errors ⇒ False at the root;
ref: pkg/jsonexp/expressions.go:59-100) and is also the differential-test
oracle for the kernel.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..compiler.compile import CompiledPolicy, ConfigRules, compile_corpus
from ..compiler.encode import EncodedBatch, encode_batch
from ..compiler.pack import DeviceBatch, pack_batch
from ..ops.pattern_eval import _eval_jit, forward, to_device

__all__ = ["PolicyModel", "host_results", "apply_host_fallback"]


def apply_host_fallback(decide, fb, own_rule, own_skipped, cap) -> None:
    """Shared fallback policy for BOTH serving paths (single-corpus engine +
    mesh ShardedPolicyModel — TestServingPathBitParity holds them identical):
    re-decide up to ``cap`` membership-overflow rows via ``decide(r) ->
    (rule_row, skipped_row)``; rows beyond the cap are denied fail-closed.
    Meters auth_server_host_fallback_{total,shed_total}."""
    from ..utils import metrics as metrics_mod

    decided = fb if cap is None else fb[:cap]
    shed = fb[len(decided):]
    for r in decided:
        own_rule[r], own_skipped[r] = decide(int(r))
    for r in shed:
        own_rule[r] = False
        own_skipped[r] = False
    if len(fb):
        metrics_mod.host_fallback_total.inc(len(decided))
        if len(shed):
            metrics_mod.host_fallback_shed_total.inc(len(shed))


def host_results(
    policy: CompiledPolicy, doc: Any, row: int
) -> Tuple[bool, np.ndarray, np.ndarray]:
    """Exact host-side decision for one request via the expression oracle:
    (own verdict, per-evaluator rule results [E], skipped [E]) with the
    same padding/tail semantics as the kernel's eval_full_jit."""
    E = policy.eval_rule.shape[1]
    rule_res = np.ones((E,), dtype=bool)       # padded cols: TRUE_SLOT
    skipped = np.zeros((E,), dtype=bool)
    for e, (cond, rule) in enumerate(policy.config_exprs[row]):
        if cond is not None:
            try:
                cond_ok = bool(cond.matches(doc))
            except Exception:
                cond_ok = False
            if not cond_ok:
                skipped[e] = True
                continue
        try:
            rule_res[e] = bool(rule.matches(doc))
        except Exception:
            rule_res[e] = False
    own = bool(np.all(skipped | rule_res))
    return own, rule_res, skipped


class PolicyModel:
    """Single-corpus model: replicated params, batch (data) parallel only.
    For the rules-axis-sharded variant see parallel/sharded_eval.py."""

    def __init__(self, policy: CompiledPolicy, device=None):
        self.policy = policy
        self.params = to_device(policy, device=device, dense=True)
        # module-level jit: identical-shape models share one trace cache
        self._apply = _eval_jit

    @classmethod
    def from_configs(cls, configs: Sequence[ConfigRules], members_k: int = 16, device=None) -> "PolicyModel":
        return cls(compile_corpus(configs, members_k=members_k), device=device)

    # ---- request path ----------------------------------------------------

    def encode(self, docs: Sequence[Any], config_rows: Sequence[int], batch_pad: int = 0) -> DeviceBatch:
        enc = encode_batch(self.policy, docs, config_rows, batch_pad=batch_pad)
        return pack_batch(self.policy, enc)

    def encode_json(self, parts: Sequence[bytes], config_rows: Sequence[int],
                    batch_pad: int = 0) -> DeviceBatch:
        """GIL-free encode from raw authorization-JSON bytes (one UTF-8 blob
        per request — what a wire frontend already holds).  Falls back to
        the Python encoder via json.loads when the native module is
        unavailable."""
        from ..native import get_native_encoder

        nat = get_native_encoder(self.policy)
        if nat is not None:
            enc = nat.encode_json_parts(parts, config_rows, batch_pad)
            if enc is not None:
                return pack_batch(self.policy, enc)
        import json

        return self.encode([json.loads(pt) for pt in parts], config_rows, batch_pad)

    def apply(self, db: DeviceBatch) -> Tuple[np.ndarray, np.ndarray]:
        from ..ops.pattern_eval import _extra_operands

        has_dfa = self.policy.n_byte_attrs > 0
        own, verdict = self._apply(
            self.params,
            jnp.asarray(db.attrs_val),
            jnp.asarray(db.members_c),
            jnp.asarray(db.cpu_dense),
            jnp.asarray(db.config_id),
            jnp.asarray(db.attr_bytes) if has_dfa else None,
            jnp.asarray(db.byte_ovf) if has_dfa else None,
            *_extra_operands(db),
        )
        return np.asarray(own), np.asarray(verdict)

    def decide(self, docs: Sequence[Any], config_names: Sequence[str]) -> List[bool]:
        return self.decide_rows(docs, [self.policy.config_ids[n] for n in config_names])

    def decide_rows(self, docs: Sequence[Any], rows: Sequence[int]) -> List[bool]:
        db = self.encode(docs, rows)
        own, _ = self.apply(db)
        out = [bool(b) for b in own[: len(docs)]]
        if db.host_fallback.any():
            for r in np.nonzero(db.host_fallback[: len(docs)])[0]:
                out[r], _, _ = host_results(self.policy, docs[r], rows[r])
        return out

    # ---- graft-entry support --------------------------------------------

    def forward_fn_and_args(self, batch: int = 64):
        """A jittable forward fn + realistic example args (for compile checks)."""
        db = self.encode([], [], batch_pad=batch)
        has_dfa = self.policy.n_byte_attrs > 0
        attr_bytes = db.attr_bytes
        if has_dfa:
            # re-pad to the full byte budget: an empty batch trims to the
            # minimum width, but the compile check must cover the widest
            # DFA-scan variant production values can trigger
            full = np.zeros(attr_bytes.shape[:-1] + (self.policy.byte_width,),
                            dtype=np.uint8)
            full[..., : attr_bytes.shape[-1]] = attr_bytes
            attr_bytes = full
        from ..ops.pattern_eval import _extra_operands

        args = (
            self.params,
            jnp.asarray(db.attrs_val),
            jnp.asarray(db.members_c),
            jnp.asarray(db.cpu_dense),
            jnp.asarray(db.config_id),
            jnp.asarray(attr_bytes) if has_dfa else None,
            jnp.asarray(db.byte_ovf) if has_dfa else None,
            *_extra_operands(db),
        )
        return forward, args
