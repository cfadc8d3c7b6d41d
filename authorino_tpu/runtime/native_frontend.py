"""Python half of the native device-owner gRPC frontend (native/frontend.cpp).

One process owns the TPU; the wire runs in C++.  This module decides, per
AuthConfig, whether its FULL pipeline semantics reduce to a native
decision — the *fast lane*:

  - compiled pattern-matching authorization (`when` conditions included):
    packed column 0 is exactly the pipeline's decision
    (ops/pattern_eval.py eval_verdicts), single-corpus or mesh-sharded;
  - identity as an ordered OR of sources: anonymous, API keys (per-key
    plan variants resolved at refresh), and OIDC/JWT + mTLS through a
    verified-credential cache registered by the slow lane (TTL-bounded by
    exp/notAfter; JWKS/CA rotation swaps the cache away);
  - auth.*-only identity extensions and DynamicJSON/Plain response
    templates, precomputed per identity outcome (OK bytes per variant);
  - static denyWith templates, all-sources-failed answers per
    static-credential-presence bitmask.

It builds the C++ encode plans + byte-exact response templates (with the
same pb2 code as service/grpc_server.py so fast-lane responses match the
Python server bit for bit), and runs two kinds of Python threads:

  - dispatchers: one JAX dispatch per micro-batch (the only per-batch Python)
  - slow lane: full AuthPipeline for everything else (unknown/expired
    credentials, metadata fetches, Rego, templated denyWith, sampled
    traces, …) with continuous admission and graceful-drain shutdown

Reference parity: main.go:437-488 (one-process gRPC server),
pkg/service/auth.go:239-310 (Check flow incl. host override + port strip).
"""

from __future__ import annotations

import logging
import os
import threading
import time
from bisect import bisect_right
from dataclasses import dataclass, field
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import numpy as np

from ..authjson import selector as sel
from ..compiler.compile import (
    DFA_VALUE_BYTES,
    OP_CPU,
    OP_REGEX_DFA,
    OP_TREE_CPU,
    CompiledPolicy,
    cpu_regex_leaves,
)
from ..compiler.intern import PAD
from ..compiler.pack import _trim_bytes, wire_dtype
from ..evaluators import credentials as cred_mod
from ..evaluators.base import DenyWithValues, RuntimeAuthConfig
from ..evaluators.authorization import OPA as OPAEval
from ..evaluators.authorization import PatternMatching
from ..evaluators.identity import APIKey, KubernetesAuth, MTLS, Noop, OAuth2
from ..evaluators.identity.api_key import INVALID_API_KEY_MSG
from ..evaluators.identity.oidc import OIDC
from ..native.verdict_cache import (NativeVerdictCache, key_segments,
                                    plan_cut)
from ..pipeline.pipeline import AuthPipeline, AuthResult
from ..utils import bucket_pow2
from ..utils import metrics as metrics_mod
from ..utils import tracing as tracing_mod
from ..utils.rpc import (
    INVALID_ARGUMENT,
    NOT_FOUND,
    OK,
    PERMISSION_DENIED,
    UNAUTHENTICATED,
)
from . import faults
from . import provenance as prov_mod
from .admission import AdmissionController
from .batch_stages import StageClock
from .breaker import CircuitBreaker
from .flight_recorder import RECORDER
from . import kernel_cost as kernel_cost_mod
from .kernel_cost import (LEDGER, CostModel, _bitpacked_zero_args,
                          launch_temp_bytes)
from .lane_select import DEVICE as L_DEVICE, HOST as L_HOST, LaneSelector

log = logging.getLogger("authorino_tpu.native_frontend")

__all__ = ["NativeFrontend", "fast_lane_eligible", "FastLaneSpec"]

# plan kinds — must match native/frontend.cpp PlanKind
K_CONST, K_METHOD, K_PATH, K_URL_PATH, K_QUERY, K_HOST, K_SCHEME = range(7)
K_PROTOCOL, K_SIZE, K_FRAGMENT, K_HEADER, K_CTX_EXT = range(7, 12)

EV_TIMEOUT, EV_BATCH, EV_SNAP_RETIRED, EV_STOPPED = 0, 1, 3, 4

# the keep (`_post_complete_telemetry`, `_fold_kept`): how many completed
# cuts `post` keeps before one fold takes them all, and how old the oldest
# kept cut may grow while the readback thread has others to complete
KEEP_CUTS = 16
KEEP_AGE_S = 0.05

_SIMPLE = {
    ("request", "method"): (K_METHOD, ""),
    ("request", "path"): (K_PATH, ""),
    ("request", "url_path"): (K_URL_PATH, ""),
    ("request", "query"): (K_QUERY, ""),
    ("request", "host"): (K_HOST, ""),
    ("request", "scheme"): (K_SCHEME, ""),
    ("request", "protocol"): (K_PROTOCOL, ""),
    ("request", "size"): (K_SIZE, ""),
    ("request", "fragment"): (K_FRAGMENT, ""),
    ("request", "referer"): (K_HEADER, "referer"),
    ("request", "user_agent"): (K_HEADER, "user-agent"),
}


def _cfg_dfa_refs(policy: CompiledPolicy,
                  row_base: int = 0) -> List[List[Tuple[int, int, int]]]:
    """Per config row, its DFA leaves as (attr, dfa row, column of the row's
    own cpu_dense payload): what the C++ encoder evaluates exactly on the
    host when a value overflows the byte tensor."""
    from ..compiler.compile import OWN_ATTR, OWN_CPU, OWN_OP

    own = policy.own
    tab = own.leaf_tab
    out: List[List[Tuple[int, int, int]]] = [[] for _ in range(tab.shape[0])]
    g, j = np.nonzero((tab[..., OWN_OP] == OP_REGEX_DFA) & (tab[..., OWN_CPU] >= 0))
    rows = policy.leaf_dfa_row[own.leaves[g, j]] + row_base
    for gi, attr, row, col in zip(g.tolist(), tab[g, j, OWN_ATTR].tolist(),
                                  rows.tolist(), tab[g, j, OWN_CPU].tolist()):
        out[gi].append((attr, row, col))
    return out


def _classify_selector(selector_str: str):
    """("req", kind, key) for a request-derived attr, ("auth",) for one that
    resolves over the identity-dependent ``auth.*`` subtree (constant per
    identity outcome), or None when the fast lane cannot encode it."""
    if not selector_str or selector_str[0] in "{[":
        return None
    try:
        segs = sel._parse_path(selector_str)
    except Exception:
        return None
    if not all(s.kind == "key" for s in segs):
        # gjson-extended selectors over the auth tree still resolve
        # constantly per identity; anything touching the request needs the
        # full engine
        keys0 = selector_str.split(".", 1)[0].split("|", 1)[0]
        if keys0 == "auth":
            return ("auth",)
        return None
    keys = tuple(s.key for s in segs)
    if keys in _SIMPLE:
        kind, key = _SIMPLE[keys]
        return ("req", kind, key)
    if len(keys) == 3 and keys[:2] == ("request", "headers"):
        return ("req", K_HEADER, keys[2])
    if len(keys) == 3 and keys[:2] == ("request", "context_extensions"):
        return ("req", K_CTX_EXT, keys[2])
    # legacy context.* mirrors that share exact semantics with the wellknown
    # forms (context_dict filters ""-valued scalar fields, so only the
    # unfiltered maps are plannable)
    if len(keys) == 5 and keys[:4] == ("context", "request", "http", "headers"):
        return ("req", K_HEADER, keys[4])
    if len(keys) == 3 and keys[:2] == ("context", "context_extensions"):
        return ("req", K_CTX_EXT, keys[2])
    if keys[0] == "auth":
        return ("auth",)
    return None


def _const_plan(policy: CompiledPolicy, attr: int, const_doc: Dict[str, Any],
                row: Optional[int] = None):
    """K_CONST plan tuple for `attr` resolved against a constant auth doc,
    or None when the compact device payload can't hold the value (membership
    overflow, a NUL in a DFA operand) — which disqualifies the config.  A DFA
    operand past the byte width of config ``row``'s size class (None: the
    floor, DFA_VALUE_BYTES) is flagged (the tuple's last field): the C++
    encoder scans it on the host as it does a request's long value."""
    from ..compiler.encode import _MISSING, _render

    res = sel.get(const_doc, policy.attr_selectors[attr])
    K = policy.members_k
    v = res.value if res.exists else _MISSING
    rendered = _render(v)
    vid = policy.interner.lookup(rendered)
    missing = v is _MISSING or v is None
    members: List[int] = []
    if isinstance(v, list):
        if len(v) > K:
            return None  # const membership overflow: host oracle only
        members = [policy.interner.lookup(_render(e)) for e in v]
    elif not missing:
        members = [vid]
    raw = rendered.encode("utf-8")
    dfa_operand = int(policy.attr_byte_slot[attr]) >= 0
    if dfa_operand and 0 in raw:
        return None  # byte 0 is the DFA's pad identity: host oracle only
    width = (DFA_VALUE_BYTES if row is None
             else int(policy.config_byte_width[row]))
    return (int(attr), K_CONST, "", int(vid), missing, members, raw,
            dfa_operand and len(raw) > width)


def _const_doc(identity_obj) -> Dict[str, Any]:
    """The constant auth.* subtree of a fast-lane request: identity as
    resolved, everything else empty — the authorization phase reads the doc
    BEFORE its own results are stored, and fast-lane configs have no
    metadata/callbacks."""
    return {
        "auth": {
            "identity": identity_obj,
            "metadata": {},
            "authorization": {},
            "response": {},
            "callbacks": {},
        }
    }


_ANON_IDENTITY = {"anonymous": True}


def _static_value(v) -> bool:
    return v is None or not getattr(v, "pattern", "")


# auth.* subtrees that are constant per identity outcome in BOTH lanes at
# every fast-lane resolve point.  auth.authorization is NOT: the pipeline
# stores authorization results before the response phase (and before
# later-priority authorization buckets), while the fast lane's const doc
# holds {} — and a bare `auth` selector includes it
_CONST_AUTH_ROOTS = ("identity", "metadata", "response", "callbacks")


def _auth_subroot_ok(s: str) -> bool:
    parts = s.split(".")
    if len(parts) < 2:
        return False
    sub = parts[1].split("|")[0].split("#")[0].split("@")[0]
    return sub in _CONST_AUTH_ROOTS


def _auth_only_value(v) -> bool:
    """True when a JSONValue resolves constantly per identity outcome:
    static, or selectors/templates rooted entirely in the constant parts
    of the auth.* subtree."""
    from ..authjson.value import is_template, template_selectors

    if not getattr(v, "pattern", ""):
        return True
    sels = (template_selectors(v.pattern) if is_template(v.pattern)
            else [v.pattern])
    return all(_classify_selector(s) == ("auth",) and _auth_subroot_ok(s)
               for s in sels)


def _extend_identity(idc, obj):
    """Mirror IdentityConfig.resolve_extended_properties against a CONSTANT
    identity outcome: extensions read the raw identity through the doc
    (auth.identity stays raw during the loop, exactly like the pipeline's
    _sync_auth-then-extend ordering) while mutating the extended copy."""
    if not idc.extended_properties:
        return obj
    if not isinstance(obj, dict):
        raise ValueError("cannot extend non-object identity")
    doc = _const_doc(obj)
    extended = dict(obj)
    for prop in idc.extended_properties:
        extended[prop.name] = prop.resolve_for(extended, doc)
    return extended


def _response_templates_eligible(rt: RuntimeAuthConfig) -> bool:
    """Response evaluators whose outputs are constant per identity outcome
    (DynamicJSON / Plain over auth.*-only values) can precompute their OK
    CheckResponse bytes per credential variant — the 'inject an identity
    header' pattern stays on the fast lane.  Anything per-request
    (request.* selectors, Wristbands: per-request iat/exp signatures)
    disqualifies."""
    from ..evaluators.response import DynamicJSON, Plain

    for conf in rt.response:
        if conf.conditions is not None or conf.cache is not None or conf.metrics:
            return False
        ev = conf.evaluator
        if isinstance(ev, DynamicJSON):
            vals = [p.value for p in ev.properties]
        elif isinstance(ev, Plain):
            vals = [ev.value]
        else:
            return False
        if not all(_auth_only_value(v) for v in vals):
            return False
    return True


def _deny_with_static(dw: Optional[DenyWithValues]) -> bool:
    if dw is None:
        return True
    if not _static_value(dw.message) or not _static_value(dw.body):
        return False
    return all(_static_value(h.value) for h in dw.headers)


def _deny_with_const(dw: Optional[DenyWithValues]) -> bool:
    """True when every denyWith value is constant per identity outcome:
    static, or templated over the constant auth.* subtrees — then the
    denial bytes precompute per credential variant (identity-failure
    templates resolve against the empty doc, where auth-only selectors are
    constantly missing, exactly like the pipeline's identity-None doc)."""
    if dw is None:
        return True
    vals = [dw.message, dw.body] + [h.value for h in dw.headers]
    return all(v is None or _static_value(v) or _auth_only_value(v)
               for v in vals)


# AuthCredentials location → C++ CredKind (native/frontend.cpp)
_CRED_KINDS = {
    cred_mod.LOCATION_AUTH_HEADER: 1,
    cred_mod.LOCATION_CUSTOM_HEADER: 2,
    cred_mod.LOCATION_COOKIE: 3,
    cred_mod.LOCATION_QUERY: 4,
}
# mTLS: the forwarded client certificate is the credential
_CRED_KIND_CERT = 5
MISSING_CERT_MSG = "client certificate is missing"


@dataclass
class SourceSpec:
    """One identity source of a fast-lane config, in the pipeline's
    priority-then-declaration order (identity is an OR,
    ref pkg/service/auth_pipeline.go:203-258)."""

    name: str                     # IdentityConfig name (all-fail error keys)
    cred_kind: int = 0
    cred_key: str = ""
    dyn: bool = False             # OIDC/mTLS: verified-credential cache
    # static (API key): per-key plan variants resolved at refresh time
    variants: List[Tuple[bytes, List[tuple]]] = field(default_factory=list)
    idc: Any = None               # the IdentityConfig (dyn registration)
    missing_msg: str = ""         # per-source failure when credential absent
    invalid_msg: str = ""         # static: failure when the key is unknown
    # dyn: extra TTL bound from the user's own cache opt-in (OAuth2
    # introspection / K8s TokenReview)
    ttl_cap: Optional[float] = None


def _kernel_covered(conf) -> bool:
    """True when this authorization evaluator's verdict is decided by the
    compiled kernel corpus: pattern-matching evaluators with a batched
    provider, and OPA evaluators whose decidable Rego was lowered into a
    ConfigRules slot at translate time (rego_lower)."""
    if conf.cache is not None or conf.metrics:
        return False
    ev_c = conf.evaluator
    if isinstance(ev_c, PatternMatching):
        return ev_c.batched_provider is not None and conf.conditions is None
    if isinstance(ev_c, OPAEval):
        # wrapper conditions are fine: translate compiles the same gate
        # into the kernel slot AND keeps it on the pipeline
        return ev_c.kernel_slot is not None
    return False


@dataclass
class FastLaneSpec:
    """Everything the C++ frontend needs to serve one AuthConfig natively.

    ``has_batch`` configs evaluate pattern authorization through the kernel;
    configs without authorization (identity-only) decide entirely in C++.
    ``sources`` lists the config's identity sources (empty = anonymous):
    API-key sources (ref pkg/evaluators/identity/api_key.go:72-93) carry
    per-key plan variants — each known key's ``auth.identity.*`` operands
    resolved to constants at refresh time; dyn sources (OIDC/JWT,
    ref oidc.go:41-103; mTLS, ref mtls.go:23-189) use the variant map as a
    verified-credential cache registered at runtime by the slow lane, TTL
    = min(exp/notAfter, dyn_ttl).  ``auth_attrs`` carries the attr rows a
    registration must resolve per credential.  Multi-identity configs are
    an OR: the first source (priority order) whose credential resolves a
    variant wins; all-fail answers come from static templates indexed by
    which static credentials were present."""

    plans: List[tuple] = field(default_factory=list)
    has_batch: bool = False
    sources: List[SourceSpec] = field(default_factory=list)
    auth_attrs: List[int] = field(default_factory=list)
    # anonymous configs: the (possibly extended) constant identity object —
    # response templates resolve against it at swap time
    const_identity: Any = None
    # unauthorized denyWith carries identity-templated values → per-variant
    # DENY bytes must be built (else the config-default static deny serves)
    deny_templated: bool = False
    # hybrid lane: the kernel covers only part of the authorization phase —
    # a kernel DENY answers natively, a kernel PASS hands the raw request
    # to the slow lane for the full pipeline (procedural Rego/SAR/SpiceDB
    # evaluators, arbitrary responses)
    hybrid: bool = False


# bounds on the identity-source fan-out the C++ lane carries: the all-fail
# template table is 2^n_static entries, and every extra source is a per-
# request extraction attempt
_MAX_SOURCES = 4
_MAX_STATIC_SOURCES = 3


def fast_lane_eligible(entry, policy: Optional[CompiledPolicy]) -> Optional[FastLaneSpec]:
    """Returns a FastLaneSpec when `entry`'s pipeline reduces to a native
    decision (kernel verdict and/or credential map lookup), else None.
    Mirrors pipeline.evaluate() phase by phase
    (ref pkg/service/auth_pipeline.go:451-502): every feature that would
    need per-request Python work disqualifies."""
    rt: Optional[RuntimeAuthConfig] = entry.runtime
    if rt is None:
        return None
    if rt.conditions is not None:
        return None
    if rt.metadata or rt.callbacks:
        return None
    covered = [c for c in rt.authorization if _kernel_covered(c)]
    uncovered = [c for c in rt.authorization if not _kernel_covered(c)]
    # hybrid: kernel pre-filters denials, the pipeline finishes the allows —
    # so responses (which only run on OK) need no template eligibility
    hybrid = bool(covered) and bool(uncovered)
    if rt.response and not hybrid and not _response_templates_eligible(rt):
        return None
    if not rt.identity or len(rt.identity) > _MAX_SOURCES:
        return None
    for idc in rt.identity:
        if idc.conditions is not None:
            return None
        # per-evaluator TTL caches run in the pipeline — except for the
        # revocable-credential identities (OAuth2 introspection, K8s
        # TokenReview), whose opt-in caches the dyn lane honors itself
        # (checked in the source builder)
        if idc.cache is not None and not isinstance(
                idc.evaluator, (OAuth2, KubernetesAuth)):
            return None
        if idc.metrics or metrics_mod.DEEP_METRICS_ENABLED:
            return None  # deep per-evaluator series need the pipeline
        # identity extensions are constant per identity outcome when their
        # values resolve over auth.* only (ref pkg/evaluators/
        # identity_extension.go) — applied at variant-build time
        if idc.extended_properties and not all(
                _auth_only_value(e.value) for e in idc.extended_properties):
            return None
    is_noop = len(rt.identity) == 1 and isinstance(rt.identity[0].evaluator, Noop)
    sources: List[SourceSpec] = []
    if not is_noop:
        # identity sources in the pipeline's priority-then-declaration
        # order (ascending priority buckets; within a bucket the pipeline
        # RACES — the reference's outcome there is scheduling-dependent, so
        # any single winner is within its semantics)
        ordered = sorted(enumerate(rt.identity), key=lambda p: (p[1].priority, p[0]))
        for _, idc in ordered:
            ident = idc.evaluator
            if isinstance(ident, APIKey):
                kind = _CRED_KINDS.get(ident.credentials.location, 0)
                if kind == 0:
                    return None
                key_sel = ident.credentials.key_selector
                src = SourceSpec(
                    name=idc.name, cred_kind=kind,
                    cred_key=key_sel.lower() if kind == 2 else key_sel,
                    idc=idc, missing_msg="credential not found",
                    invalid_msg=INVALID_API_KEY_MSG)
            elif isinstance(ident, OIDC):
                kind = _CRED_KINDS.get(ident.credentials.location, 0)
                if kind == 0:
                    return None
                key_sel = ident.credentials.key_selector
                src = SourceSpec(
                    name=idc.name, cred_kind=kind,
                    cred_key=key_sel.lower() if kind == 2 else key_sel,
                    dyn=True, idc=idc, missing_msg="credential not found")
            elif isinstance(ident, MTLS):
                src = SourceSpec(name=idc.name, cred_kind=_CRED_KIND_CERT,
                                 dyn=True, idc=idc,
                                 missing_msg=MISSING_CERT_MSG)
            elif isinstance(ident, (OAuth2, KubernetesAuth)):
                # revocable credentials: the AS/apiserver check IS the
                # revocation check — cacheable ONLY when the user
                # explicitly opted in via a `cache` spec keyed by the
                # credential header (the reference's own TTL-cache
                # semantics, ref pkg/evaluators/cache.go:16-89); the dyn
                # entry is then bounded by that TTL (and a response exp)
                if idc.cache is None:
                    return None
                if isinstance(ident, KubernetesAuth) and not ident.audiences:
                    return None  # default audience is the REQUEST host
                kind = _CRED_KINDS.get(ident.credentials.location, 0)
                if kind not in (1, 2):
                    return None  # header credentials map 1:1 to cache keys
                key_sel = ident.credentials.key_selector
                hdr = ("authorization" if kind == 1 else key_sel.lower())
                if idc.cache.key_pattern not in (
                        f"request.headers.{hdr}",
                        f"context.request.http.headers.{hdr}"):
                    return None
                src = SourceSpec(
                    name=idc.name, cred_kind=kind,
                    cred_key=key_sel.lower() if kind == 2 else key_sel,
                    dyn=True, idc=idc, missing_msg="credential not found",
                    ttl_cap=float(idc.cache.ttl))
            else:
                return None  # incl. Noop mixed into a multi-identity OR
            sources.append(src)
        if sum(1 for s in sources if not s.dyn) > _MAX_STATIC_SOURCES:
            return None
        # all-fail answers come from constant templates — the identity-
        # failure denyWith must resolve without a request doc (auth-only
        # values are constantly missing there, like the pipeline's)
        if not _deny_with_const(rt.deny_with.unauthenticated):
            return None

    plans: List[tuple] = []
    auth_attrs: List[int] = []
    has_batch = False
    row = None
    if rt.authorization:
        if entry.rules is None or policy is None:
            return None
        row = policy.config_ids.get(entry.rules.name)
        if row is None:
            return None
        if not covered or len(covered) != len(entry.rules.evaluators):
            return None
        if uncovered:
            # a kernel pre-deny must not preempt an uncovered evaluator the
            # pipeline would have FAILED in an earlier priority bucket
            # (its denial could differ); same-bucket outcomes race in the
            # reference (ref pkg/service/auth_pipeline.go:160-199), so any
            # single winner there is within its semantics
            if max(c.priority for c in covered) > min(
                    u.priority for u in uncovered):
                return None
        if not _deny_with_const(rt.deny_with.unauthorized):
            return None
        # per-request regex/tree oracles cannot run in C++: a `matches`
        # outside compiler/redfa.py's subset (lookaround, flags, inner
        # anchors), never a regex's size, which the device tables hold
        for leaf in policy.config_cpu_leaves[row]:
            if int(policy.leaf_op[leaf]) in (OP_CPU, OP_TREE_CPU):
                return None
        has_batch = True
        for attr in policy.config_attrs[row]:
            sel_str = policy.attr_selectors[attr]
            c = _classify_selector(sel_str)
            if c is None:
                return None
            if c[0] == "req":
                plans.append((int(attr), c[1], c[2], 0, False, [], b"", False))
            else:
                # auth.authorization-rooted pattern operands would see the
                # pipeline's earlier-bucket results but the const doc's {} —
                # only the truly constant subtrees are plannable
                if not _auth_subroot_ok(sel_str):
                    return None
                auth_attrs.append(int(attr))
    elif entry.rules is not None and entry.rules.evaluators:
        return None  # compiled rules without runtime authz configs: engine bug

    spec = FastLaneSpec(plans=plans, has_batch=has_batch, sources=sources,
                        auth_attrs=auth_attrs, hybrid=hybrid,
                        deny_templated=has_batch and not _deny_with_static(
                            rt.deny_with.unauthorized))
    if is_noop:
        try:
            spec.const_identity = _extend_identity(rt.identity[0],
                                                   dict(_ANON_IDENTITY))
        except ValueError:
            return None
        doc = _const_doc(spec.const_identity)
        for attr in auth_attrs:
            p = _const_plan(policy, attr, doc, row)
            if p is None:
                return None
            spec.plans.append(p)
        return spec
    # API-key sources: resolve each known key's auth.* operands to
    # constants (the fast-lane analog of precompile-at-reconcile,
    # ref pkg/evaluators/authorization/opa.go:141); dyn sources register
    # their variants at runtime (NativeFrontend._register_dyn)
    for src in sources:
        if src.dyn:
            continue
        for key, secret in src.idc.evaluator.snapshot_secrets().items():
            try:
                ident_obj = _extend_identity(src.idc,
                                             secret.to_identity_object())
            except ValueError:
                return None
            vplans: List[tuple] = []
            if auth_attrs:
                doc = _const_doc(ident_obj)
                for attr in auth_attrs:
                    p = _const_plan(policy, attr, doc, row)
                    if p is None:
                        return None
                    vplans.append(p)
            # the identity object rides along so refresh can precompute the
            # per-key OK/DENY bytes for response/denyWith-template configs
            # (hybrid OKs are answered by the pipeline, which runs the
            # response phase itself — no per-key OK bytes there)
            src.variants.append((
                key.encode("utf-8"), vplans,
                ident_obj if ((rt.response and not hybrid)
                              or spec.deny_templated) else None))
    return spec


class _Launched:
    """The device launches of one single-corpus cut, one a size class
    present (none: the cache answered the whole cut): ``parts`` holds
    (result handle [pad, W_c] uint8, positions among the cut's launched
    rows or None = all of them in order, rows launched, the class's
    evaluator columns E_c).  ``fe_resolve_cut`` completes it in one call."""

    __slots__ = ("parts",)

    def __init__(self) -> None:
        self.parts: List[tuple] = []

    def is_ready(self) -> bool:
        for handle, _, _, _ in self.parts:
            ready = getattr(handle, "is_ready", None)
            if ready is not None and not ready():
                return False
        return True


def resolve_cut(parts, plan, count: int, attribute: bool):
    """(verdict [count] uint8, firing [count] int32 or None) of a completed
    cut, in numpy: each of ``parts`` (as ``_Launched.parts``, results read
    back) decoded at its class's width (``unpack_attribution``) and put back
    at its positions among the launched rows, then fanned out through
    ``plan`` (a ``CutPlan``; None: the launched rows are the cut's) with the
    cached rows' values written last.  A row nothing reaches reads 0 / -1.
    What ``fe_resolve_cut`` computes outside the interpreter lock; the mesh
    step completes through this, and the tests hold the native call to it."""
    from ..ops.pattern_eval import unpack_attribution

    u = count if plan is None else len(plan.unique_rows)
    uniq_v = np.zeros((u,), dtype=np.uint8)
    uniq_f = np.full((u,), -1, dtype=np.int32) if attribute else None
    for packed, at, n, E in parts:
        packed = np.asarray(packed)[:n]
        at = slice(n) if at is None else at
        if attribute:
            uniq_v[at], uniq_f[at] = unpack_attribution(packed, E)
        else:
            uniq_v[at] = packed[:, 0] & 1
    if plan is None:
        return uniq_v, uniq_f
    verdict = np.zeros((count,), dtype=np.uint8)
    firing = np.full((count,), -1, dtype=np.int32) if attribute else None
    verdict[plan.miss_rows] = uniq_v[plan.inverse]
    # cached value = (verdict, firing): a cache hit attributes identically
    # to the device evaluation it memoized
    verdict[plan.cached_rows] = plan.cached_verdict
    if attribute:
        firing[plan.miss_rows] = uniq_f[plan.inverse]
        firing[plan.cached_rows] = plan.cached_firing
    return verdict, firing


class _KeptCut(NamedTuple):
    """A completed cut as the keep holds it: the cut's own arrays and the
    scalars its telemetry takes, folded with the other kept cuts'."""
    rec: "_SnapRec"
    rows: np.ndarray
    verdict: np.ndarray
    firing: Optional[np.ndarray]
    shards: Optional[np.ndarray]
    count: int
    pad: int
    eff: int
    device_rows: Optional[int]
    dispatch_s: float
    t0_ns: int
    device: bool
    inflight: int             # `_rb_inflight` as the completion read it
    dedup: Optional[tuple]    # observe_dedup's (device rows, hits, misses,
    #                           evictions); None: no cache or dedup was asked
    kept_at: float            # time.monotonic() at the append


@dataclass
class _SnapRec:
    snap_id: int
    policy: CompiledPolicy
    params: Any
    encoder: Any                       # NativeEncoder (owns the Policy capsule)
    sharded: Any = None                # ShardedPolicyModel (mesh corpora)
    arrays: List[Dict[str, np.ndarray]] = field(default_factory=list)
    keepalive: List[np.ndarray] = field(default_factory=list)
    fc_rows: Optional[np.ndarray] = None
    row_labels: Dict[int, Tuple[str, str]] = field(default_factory=dict)
    # jit bucket variants already compiled for this snapshot's params, for
    # EVERY size class: (batch_pad, byte_eff) pairs; 0 byte_eff = no DFA
    # lane (a class whose members reach no DFA row stages no bytes at any
    # byte_eff).  _dispatch only
    # uses warmed shapes (rounding up) so XLA compiles never land on live
    # requests (the precompile-at-reconcile discipline,
    # ref pkg/evaluators/authorization/opa.go:141)
    warm: set = field(default_factory=set)
    warm_done: threading.Event = field(default_factory=threading.Event)
    # temporaries one launch of the largest warm variant allocates, from the
    # compiled entry's memory_analysis() at the swap gate (0: not given)
    launch_temp_bytes: int = 0
    # (size class, batch_pad, byte_eff) -> the static layout of that
    # variant's staging buffer (_stage_layout): built once, read by the warm
    # grid and by every launch, so both name the same jit variant
    layouts: Dict[Tuple[int, int, int], tuple] = field(default_factory=dict)
    # size classes (compiler/compile.py SizeClass), single corpus: a launch
    # carries the rows of ONE class and runs that class's operands
    # (``views[c]``, ops/pattern_eval.py class_view) at its widths.
    # class_of: config row -> class; classes: each class's widths
    # (SizeClass.widths(): `cpu_cols` the CPU columns staged a row,
    # `dfa_rows_per_row` the own-row scan's D, 0 = its members reach no DFA
    # row, `evaluators` the evaluator columns read back); cfg_dfa_n: config
    # row -> DFA rows its own circuit reaches
    views: List[Any] = field(default_factory=list)
    class_of: Optional[np.ndarray] = None
    classes: List[Dict[str, int]] = field(default_factory=list)
    # the slot arrays' byte-lane width: the widest class's device_width (the
    # warm grid's byte axis runs to it); 0 = no DFA row in the corpus
    byte_width: int = 0
    cfg_dfa_n: Optional[np.ndarray] = None
    # first kernel lowering/compile failure of this snapshot's warm grid
    # (swap gate or background rest): surfaced on /debug/vars and /readyz,
    # never only in the log — a kernel that cannot compile must not look
    # healthy while the degrade path does the serving
    warm_error: Optional[str] = None
    # configs with dyn sources: entry.id → (fc_idx, auth_attrs, policy,
    # {id(IdentityConfig): (source idx, ttl cap)}, hybrid) — the slow lane
    # registers verified-credential plan variants against this snapshot
    # (policy = the entry's OWN compile: its shard's on a mesh; hybrid
    # suppresses per-credential OK bytes — the pipeline answers those)
    dyn_regs: Dict[str, Tuple[int, List[int], Any,
                              Dict[int, Tuple[int, Optional[float]]],
                              bool]] = field(default_factory=dict)
    # kernel rows of HYBRID configs (same key type as row_labels): dispatch
    # attribution must count only their native denials — kernel-allowed
    # requests continue into the pipeline, which observes them itself
    hybrid_rows: set = field(default_factory=set)
    # configs of the snapshot that got no native plan (fast_lane_eligible
    # None): every request to one of their hosts takes the Python pipeline
    slow_configs: int = 0
    # verdict-cache eligibility per kernel row: [G] bool (single corpus) or
    # [S, G] (mesh) — compiler/compile.py config_cacheable
    cacheable: Optional[np.ndarray] = None
    # what `plan` hands the native cache, built once a snapshot
    # (_bind_cache_keys).  tok_ids: the verdict-cache key token of each
    # kernel row as a u64 — the engine snapshot's per-config (encoding
    # epoch, source fingerprint) tokens (ISSUE 8), interned by the frontend,
    # so entries of configs a reconcile did NOT touch stay reachable across
    # fe snapshots; mesh corpora and pre-fingerprint snapshots give every
    # row one snapshot-wide token (PR 3's snap_id keying).  key_segs: the
    # key descriptor of each batch slot, parallel to ``arrays``.
    tok_ids: Optional[np.ndarray] = None
    key_segs: List[bytes] = field(default_factory=list)
    # host (numpy) operand pytree for the host serving lane (ISSUE 12) and
    # the degraded lane: the same kernel on the CPU backend.  Built eagerly
    # by the pre-warm thread at snapshot swap (lazily as a fallback), so
    # the first host-lane decision after a reconcile is not a CPU
    # jit-compile latency spike.
    host_params: Any = None
    # CPU-backend jit variants already compiled against host_params:
    # (batch_pad, byte_eff) pairs — _host_eval rounds up into this set
    host_warm: set = field(default_factory=set)
    # decision provenance (ISSUE 9): the rule heat map binding this
    # snapshot's kernel rows to (authconfig, rule source) — shared with the
    # engine snapshot's instance when one exists, so both lanes fold into
    # one label-children cache
    heat: Any = None
    # the C++ side has no slot of this snapshot left (EV_SNAP_RETIRED): a
    # fold of its last kept cuts names what its heat map holds at once, no
    # drain will find the map again
    retired: bool = False


class NativeFrontend:
    """Owns the C++ server lifecycle + the dispatcher/slow-lane threads."""

    def __init__(self, engine, port: int = 0, max_batch: int = 1024,
                 window_us: int = 2000, slots: int = 16, slow_cap: int = 65536,
                 dispatch_threads: int = 6, bind_all: bool = False,
                 dyn_ttl_s: float = 600.0, trace_sample_n: int = 128,
                 verdict_cache_size: int = 32768, batch_dedup: bool = True,
                 strict_verify: bool = False,
                 device_timeout_s: Optional[float] = None,
                 breaker_threshold: int = 5, breaker_reset_s: float = 5.0,
                 admission_target_s: float = 0.05,
                 brownout: bool = True, brownout_max_rows: int = 64,
                 lane_select: bool = True, lane_host_max_rows: int = 64,
                 slo_ms: float = 0.0):
        self.engine = engine
        # fault tolerance (ISSUE 5, docs/robustness.md): a failed device
        # batch retries once, then degrades to the SAME kernel on the CPU
        # backend (fail-closed deny only if that fails too); consecutive
        # failures trip the breaker and whole batches skip the device; the
        # readback watchdog abandons batches wedged past --device-timeout
        self.breaker = CircuitBreaker("native", threshold=breaker_threshold,
                                      reset_s=breaker_reset_s)
        self.device_timeout_s = (float(device_timeout_s)
                                 if device_timeout_s else None)
        # --strict-verify: tensor-lint every snapshot in refresh() BEFORE
        # fe_swap — a corrupt corpus never becomes the serving C++ snapshot
        # (the old one keeps serving; auth_server_snapshot_rejected_total)
        self.strict_verify = bool(strict_verify)
        # batch row dedup + snapshot-scoped verdict cache, mirroring the
        # engine lane (runtime/engine.py): the device evaluates unique rows
        # only, and cached (snap_id, row-digest) verdicts skip it entirely.
        # Cache hits/misses/adds are folded into the frontend's dyn_hit/
        # dyn_miss/dyn_add stats keys (see stats()).
        self.batch_dedup = bool(batch_dedup)
        self._verdict_cache = (NativeVerdictCache(verdict_cache_size)
                               if verdict_cache_size else None)
        # (encoding epoch, config fingerprint) -> u64, for the frontend's
        # life: a config a reconcile did not touch keeps its id, so its
        # cache entries stay reachable across snapshots (ISSUE 8)
        self._cache_token_ids: Dict[Any, int] = {}
        # verified-token cache entries live at most this long (and never
        # past the token's own exp claim)
        self.dyn_ttl_s = float(dyn_ttl_s)
        # with tracing active, 1-in-N requests take the slow lane with full
        # span export; the rest serve natively.  AUTHORINO_TPU_TRACE_ALL=1
        # restores the reference's every-request tracing (at slow-lane
        # throughput — the reference traces in-process,
        # ref pkg/trace/trace.go:20-27)
        if os.environ.get("AUTHORINO_TPU_TRACE_ALL", "").lower() in (
                "1", "true", "yes"):
            trace_sample_n = 1
        self.trace_sample_n = max(1, int(trace_sample_n))
        self._trace_mode_logged = False
        self.port = port
        self.bind_all = bind_all
        self.max_batch = int(max_batch)
        self.window_us = int(window_us)
        self.slots = int(slots)
        self.slow_cap = int(slow_cap)
        # dispatchers only ENCODE + LAUNCH (readback rides the dedicated
        # readback thread), so a couple of threads saturate the C++ batch
        # queue; the in-flight window is the slot count, not this number
        self.dispatch_threads = int(dispatch_threads)
        self._mod = None
        self._snaps: Dict[int, _SnapRec] = {}
        self._next_snap_id = 1
        # kernel-cost observatory (ISSUE 16): XLA-modeled per-row cost per
        # snapshot generation; >=2x per-row regressions raise an advisory
        # cost-regression anomaly (the refresh swap is never blocked)
        self._cost_model = CostModel("native_frontend")
        self._running = False
        self._threads: List[threading.Thread] = []
        self._lock = threading.Lock()
        # newest snapshot record — the slow lane registers verified-token
        # variants against it (GIL-atomic pointer read)
        self._cur_rec: Optional[_SnapRec] = None
        # duration/stage histogram drain cadence + accumulated stage counts
        self.hist_drain_s = 2.0
        self._last_hist_drain = 0.0
        self.stage_totals: Dict[str, Any] = {}
        # fe_stats() → Prometheus delta drain, owned by the periodic drain
        # thread (single owner: delta state is unsynchronized by design)
        self._stats_drain = metrics_mod.NativeStatsDrain()
        self._drain_wake = threading.Event()
        self._drain_lock = threading.Lock()
        # cached label children for the per-(pad,eff) warm-cache counters
        self._warm_children: Dict[Tuple[int, int, str], Any] = {}
        # live pre-warm/refresh helper threads (joined on stop); own lock —
        # trackers run both under _lock (refresh) and without it (notifier)
        self._prewarm_threads: List[threading.Thread] = []
        self._thread_lock = threading.Lock()
        # evaluators this instance registered _on_oidc_change on —
        # unregistered in stop() so a replaced frontend isn't kept alive
        # (and re-fired) by long-lived evaluators
        self._change_wired: set = set()
        # slow-lane responses buffer here; a dedicated completer thread
        # lands them in C++ in batches (per-response fe_complete_slow was
        # ~35µs of contended wall on the asyncio thread)
        from collections import deque as _deque

        self._done_buf = _deque()
        self._done_evt = threading.Event()
        # pipelined readback: dispatchers launch kernels WITHOUT blocking on
        # the device→host copy and park the in-flight batch here; a readback
        # thread completes each batch as its result arrives (is_ready), so
        # the in-flight window is bounded by the C++ slot count, not by how
        # many Python threads are captive in np.asarray
        self._rb_q = _deque()
        self._rb_evt = threading.Event()
        self._rb_lock = threading.Lock()
        self._rb_inflight = 0
        self.rb_inflight_peak = 0
        self._fe_stopped = False  # set just before fe_stop(): readback must
        # never complete a batch into the torn-down C++ server
        self._g_native_inflight = metrics_mod.inflight_batches.labels("native")
        # overload resilience (ISSUE 7): the C++ side already bounds its
        # queues (slots for the device lane, slow_cap for the slow lane);
        # the Python side adds (a) a CoDel admission state fed by the slow
        # lane's estimated queue wait, paced-rejecting slow requests typed
        # RESOURCE_EXHAUSTED while a standing queue persists, and (b)
        # host-lane brownout: with nearly every device slot in flight, a
        # small batch is answered by the SAME kernel on the CPU backend
        # (exact; docs/robustness.md "Overload & brownout")
        # the CoDel interval must exceed the wait-feed cadence (the drain
        # loop, hist_drain_s): with a shorter interval the idle-reset
        # would mistake the gap BETWEEN feeds for vanished load and flap
        # the OVERLOADED state under genuinely sustained saturation
        self.admission = AdmissionController(
            "native", target_s=admission_target_s,
            interval_s=max(1.0, 2 * self.hist_drain_s))
        self.brownout = bool(brownout)
        self.brownout_max_rows = max(1, int(brownout_max_rows))
        self._brownout_threshold = max(1, self.slots - 2)
        self._brownout_total = 0
        self._brownout_batches = 0
        # live brownout worker threads (under _rb_lock): stop()'s drain
        # must wait these out like in-flight device batches — a spill
        # mid-_host_eval completing into a torn-down C++ server would be
        # a native use-after-stop
        self._brownout_live = 0
        # lane selection (ISSUE 12, docs/performance.md "Lane selection"):
        # slot-level lane choice — a small gathered slot whose host-twin
        # cost beats the device round trip is answered on the CPU-backend
        # kernel even when the window is NOT saturated (brownout keeps its
        # distinct overload trigger and counters).  Speculative dual-
        # dispatch stays an engine-lane feature: a C++ slot completes via
        # fe_complete_batch exactly once, so racing two completions against
        # one slot has no safe first-wins seam here.
        self.lanes = LaneSelector(
            "native", enabled=lane_select,
            host_max_rows=min(int(lane_host_max_rows), self.max_batch),
            speculative=False, host_concurrency=2)
        # persistent workers for cost-model-selected host slots: this is
        # the LIGHT-LOAD latency path, so thread-per-slot churn (the
        # brownout pattern, fine under rare saturation spills) would eat
        # a measurable slice of the very p50 the lane buys down
        from concurrent.futures import ThreadPoolExecutor

        self._host_pool = ThreadPoolExecutor(
            max_workers=self.lanes.host_limit,
            thread_name_prefix="atpu-fe-lane-host")
        # slow-lane service-rate estimator state (owned by the drain loop)
        self._slow_last: Dict[str, float] = {"slow": 0.0, "t": 0.0}
        # decision observability (ISSUE 9): per-lane SLO burn-rate tracker
        # (--slo-ms; 0 = off — the native SLI is the batch's device round
        # trip, folded per batch) and the flight-recorder provider
        self.slo = None
        if slo_ms:
            from ..utils.slo import SloTracker

            self.slo = SloTracker("native", slo_ms)
        # tenant QoS (ISSUE 15): the native lane SHARES the engine's tenant
        # plane — the C++ gather owns its own slot cut (no Python-side
        # reorder seam), but every completed slot folds its tenant axis
        # (config_id rows) into the same per-tenant request/deny/SLO
        # counters the engine lane feeds (queue waits stay C++-clocked and
        # out of the per-tenant CoDel signal), so detection, weights and
        # the /debug/tenants view see one multi-lane truth; containment
        # ENFORCEMENT lands at the engine/slow-lane admission
        # (docs/tenancy.md names the fast-lane caveat).
        self.tenancy = getattr(engine, "tenancy", None)
        RECORDER.register_provider("native_frontend", self, "debug_vars")
        # the batch stage clock (runtime/batch_stages.py): always on; its
        # ring of batch timelines rides every flight bundle, so a dump on
        # admission-overloaded or watchdog-timeout holds what led to it
        self.batch_stages = StageClock("native")
        RECORDER.register_provider("native_batches", self.batch_stages,
                                   "to_json")
        # how often the named side of `post` still runs: decision records
        # its sampling gate let through, and Prometheus children the drain
        # touched (utils.metrics.drain: `post` itself only adds into arrays)
        self._sampled_decisions = 0
        self._drained_children = 0
        # the keep: completed cuts whose telemetry no fold has taken yet
        # (appended by the readback thread and the host-lane workers under
        # _post_counts_lock), the folds that took them, and the lock that
        # makes a fold one at a time: a reader that folds before it reads
        # waits for a fold under way (re-entrant: a fold may end in a flight
        # dump, whose providers drain)
        self._keep: List[_KeptCut] = []
        self._folds = 0
        self._folded_cuts = 0
        self._keep_max = 0
        self._fold_lock = threading.RLock()
        self._post_counts_lock = threading.Lock()

    # ------------------------------------------------------------------
    def start(self) -> int:
        from ..native import load_library

        mod = load_library()
        if mod is None:
            raise RuntimeError("native library unavailable")
        self._mod = mod
        rc = mod.fe_start(self.port, self.max_batch, self.slots, self.window_us,
                          self.slow_cap, self._health_bytes(),
                          1 if self.bind_all else 0)
        if rc != 0:
            raise RuntimeError(f"native frontend failed to start (rc={rc}: "
                               "-2 no socket, -3 bind or listen refused)")
        self._running = True
        self.bound_port = mod.fe_port()
        self._threads = [
            threading.Thread(target=self._dispatch_loop,
                             name=f"atpu-fe-dispatch-{i}", daemon=True)
            for i in range(self.dispatch_threads)
        ]
        self._threads.append(
            threading.Thread(target=self._readback_loop,
                             name="atpu-fe-readback", daemon=True))
        self._threads.append(
            threading.Thread(target=self._slow_loop, name="atpu-fe-slow", daemon=True))
        self._threads.append(
            threading.Thread(target=self._completer_loop,
                             name="atpu-fe-completer", daemon=True))
        self._threads.append(
            threading.Thread(target=self._metrics_drain_loop,
                             name="atpu-fe-metrics-drain", daemon=True))
        metrics_mod.DRAIN_OBSERVERS.append(self._observe_drain)
        metrics_mod.KEEP_FOLDERS.append(self._fold_kept)
        for t in self._threads:
            t.start()
        self.refresh()
        self.engine.add_swap_listener(self.refresh)
        return self.bound_port

    def stop(self, drain_s: float = 10.0) -> None:
        if self._mod is not None and self._running:
            # graceful: already-accepted slow-lane work flushes to the wire
            # while the listener is still alive — a cancelled handler would
            # leave its client hanging to the gRPC deadline.  Bounded:
            # steady incoming traffic degrades to the old abrupt stop.
            deadline = time.monotonic() + drain_s
            while time.monotonic() < deadline:
                s = self._mod.fe_stats()
                if not s or (s.get("slow_pending", 0) == 0
                             and s.get("slow_queued", 0) == 0):
                    break
                time.sleep(0.05)
            # in-flight device batches AND live brownout spills must land
            # (fe_complete_batch) while the C++ server is still alive
            deadline = time.monotonic() + drain_s
            while ((self._rb_inflight or self._brownout_live)
                   and time.monotonic() < deadline):
                time.sleep(0.02)
        self._running = False
        self._rb_evt.set()
        if self._mod is not None:
            self.engine.remove_swap_listener(self.refresh)
        # unwire AFTER the swap listener is gone and under _lock, so a
        # concurrent refresh() can't re-register listeners mid-unwire
        with self._lock:
            for ev in self._change_wired:
                remove = getattr(ev, "remove_change_listener", None)
                if remove is not None:
                    remove(self._on_oidc_change)
            self._change_wired.clear()
        if self._mod is not None:
            try:
                self._fold_fc_counts()
                self.drain_histograms()  # final fold: short runs lose nothing
                self.drain_native_stats()
                metrics_mod.drain()
            except Exception:
                log.exception("final metric drain failed")
            self._fe_stopped = True
            self._mod.fe_stop()
        self._drain_wake.set()
        # host-lane pool: its tasks are counted in _brownout_live, which
        # the drain above already waited out — shutdown is bookkeeping
        try:
            self._host_pool.shutdown(wait=False)
        except Exception:
            pass
        for t in self._threads:
            t.join(timeout=5)
        if self._observe_drain in metrics_mod.DRAIN_OBSERVERS:
            metrics_mod.DRAIN_OBSERVERS.remove(self._observe_drain)
        if self._fold_kept in metrics_mod.KEEP_FOLDERS:
            self._fold_kept()  # a cut a late worker kept after the last drain
            metrics_mod.KEEP_FOLDERS.remove(self._fold_kept)
        # pre-warm compiles can't be interrupted mid-XLA; they bail between
        # variants (self._running) — wait them out so interpreter teardown
        # never force-unwinds a thread inside native code
        with self._thread_lock:
            helpers = list(self._prewarm_threads)
        for t in helpers:
            t.join(timeout=300)

    def stats(self) -> Dict[str, int]:
        """fe_stats() plus the Python-side verdict-cache counters.  The
        verdict cache's hit/miss/add traffic is FOLDED into the dyn_hit/
        dyn_miss/dyn_add keys (the credential cache's counters — one
        combined 'cached decision' story on /metrics), and additionally
        exported under its own vdict_* keys so the two caches stay
        distinguishable; the periodic drain turns every key into a
        labelled auth_server_native_frontend_events_total series."""
        s = dict(self._mod.fe_stats()) if self._mod else {}
        vc = self._verdict_cache
        if s and vc is not None:
            counts = vc.counts()
            s["dyn_hit"] = s.get("dyn_hit", 0) + counts["hits"]
            s["dyn_miss"] = s.get("dyn_miss", 0) + counts["misses"]
            s["dyn_add"] = s.get("dyn_add", 0) + counts["adds"]
            s["vdict_hit"] = counts["hits"]
            s["vdict_miss"] = counts["misses"]
            s["vdict_add"] = counts["adds"]
            s["vdict_evict"] = counts["evictions"]
        return s

    def drain_native_stats(self) -> None:
        """Fold the C++ fe_stats() counters into Prometheus as deltas
        (auth_server_native_frontend_events_total / _queue_depth).  Locked:
        the periodic drain thread, stop()'s final fold, and on-demand
        callers (bench, /debug scrapes) must not interleave delta reads."""
        with self._drain_lock:
            self._stats_drain.fold(self.stats())
            if self._mod is not None:
                self._stats_drain.fold_loop_clock(self._mod.fe_loop_clock())

    def _observe_drain(self, dur_ns: int, children: int) -> None:
        self.batch_stages.record_drain(dur_ns)
        with self._post_counts_lock:
            self._drained_children += children

    def _metrics_drain_loop(self) -> None:
        """Housekeeping, independent of traffic (the dispatch loop only
        drains when batch events wake it): fe_stats() deltas → Prometheus,
        and the cadence drain of what `post` keeps as arrays (per-AuthConfig
        counters, rule heat map, tenant plane) into their children, so the
        thread that completes batches never does it."""
        while self._running:
            self._drain_wake.wait(self.hist_drain_s)
            if not self._running:
                return
            try:
                self.drain_native_stats()
                self._feed_admission()
                metrics_mod.drain()
            except Exception:
                log.exception("native stats drain failed")

    def _feed_admission(self) -> None:
        """Estimate the slow lane's standing queue wait from fe_stats()
        (Little's law: queued / observed completion rate) and feed it to
        the CoDel admission state.  The per-request waits live in C++; this
        coarse estimate on the drain cadence is what the Python side can
        see without putting itself back on the per-request path."""
        s = self.stats()
        if not s:
            return
        now = time.monotonic()
        last_t = self._slow_last["t"]
        done = float(s.get("slow", 0))
        queued = float(s.get("slow_queued", 0)) + float(s.get("slow_pending", 0))
        if last_t:
            dt = now - last_t
            delta = done - self._slow_last["slow"]
            if dt > 0 and delta >= 0:
                self.admission.observe_service(int(delta), now=now)
                rate = delta / dt
                est_wait = queued / max(rate, 1.0)
                self.admission.observe_waits((est_wait,), now=now)
        self._slow_last["slow"] = done
        self._slow_last["t"] = now

    def debug_vars(self) -> Dict[str, Any]:
        """JSON-safe live state for /debug/vars: raw fe_stats counters and
        backlog gauges, the serving snapshot id, its warmed jit grid, and
        the frontend's batching knobs."""
        rec = self._cur_rec
        from ..native import loaded_digest

        metrics_mod.drain()  # a read: the counters are exact from here
        out: Dict[str, Any] = {
            "running": self._running,
            # sha256 of the C++ sources the loaded extension was built from
            "source_digest": loaded_digest(),
            "stats": {k: int(v) for k, v in self.stats().items()},
            "max_batch": self.max_batch,
            "window_us": self.window_us,
            "slots": self.slots,
            "dispatch_threads": self.dispatch_threads,
            # {stage: {count, sum_ns, max_ns}}, cumulative: a reader takes
            # the difference of two scrapes (runtime/batch_stages.py)
            "stages": self.batch_stages.totals(),
            # the C++ front end's loop clock (native/frontend.cpp "The loop
            # clock"): where its one epoll thread's time goes, cumulative
            "front": self._mod.fe_loop_clock() if self._mod else {},
            # the drain above folded the keep: `folded_cuts` is every cut
            # `post` has kept, `keep_max` the most one fold took
            "post": {"sampled_decisions": self._sampled_decisions,
                     "drained_children": self._drained_children,
                     "folds": self._folds,
                     "folded_cuts": self._folded_cuts,
                     "keep_max": self._keep_max},
            "inflight_batches": self._rb_inflight,
            "inflight_peak": self.rb_inflight_peak,
            "trace_sample_n": self.trace_sample_n,
            "batch_dedup": self.batch_dedup,
            "strict_verify": self.strict_verify,
            "verdict_cache": (self._verdict_cache.counts()
                              if self._verdict_cache is not None else None),
            "breaker": self.breaker.to_json(),
            "device_timeout_s": self.device_timeout_s,
            "admission": self.admission.to_json(),
            "brownout": {
                "enabled": self.brownout,
                "max_rows": self.brownout_max_rows,
                "slot_threshold": self._brownout_threshold,
                "decisions": self._brownout_total,
                "batches": self._brownout_batches,
            },
            # lane selection (ISSUE 12): slot-level cost-model decisions,
            # rows served per lane, cost EWMAs, warmed host shapes
            # tuple() first: the pre-warm thread and host-eval workers
            # add() concurrently — iterating the live set can raise
            "lane_select": dict(
                self.lanes.to_json(),
                host_warm_shapes=(sorted(list(s)
                                         for s in tuple(rec.host_warm))
                                  if rec is not None else [])),
            "provenance": {
                "heat": (rec.heat.to_json()
                         if rec is not None and rec.heat is not None
                         else None),
            },
            # tenant QoS (ISSUE 15): the shared plane's view — the native
            # lane feeds the same per-tenant folds the engine lane reads
            "tenancy": (self.tenancy.to_json()
                        if self.tenancy is not None else None),
            "slo": self.slo.to_json() if self.slo is not None else None,
            # change-safety mirror (ISSUE 10): the native lane holds the
            # baseline through a canary window (refresh fires on
            # promotion/rollback only) — operators reading this lane's
            # vars see the same canary/quarantine state the engine owns
            "change_safety": (self.engine.change_safety_vars()
                              if hasattr(self.engine, "change_safety_vars")
                              else None),
            # kernel cost observatory (ISSUE 16): the process-wide ledger
            # plus this lane's modeled-cost lineage and the jit entry
            # points the serving snapshot can dispatch through
            "kernel_cost": {
                "ledger": LEDGER.to_json(),
                "modeled": self._cost_model.to_json(),
                "entry_points": kernel_cost_mod.entry_points(
                    policy=rec.policy if rec is not None else None,
                    sharded=rec.sharded if rec is not None else None),
            },
            "snapshot": None,
        }
        if rec is not None:
            out["snapshot"] = {
                "snap_id": rec.snap_id,
                "warm": sorted([list(pe) for pe in rec.warm]),
                "warm_done": (rec.warm_done.is_set()
                              and rec.warm_error is None),
                "warm_error": rec.warm_error,
                # what a device dispatch of this snapshot runs: the jitted
                # entry, the operand lane and the widths of one row's work
                "kernel": self._kernel_of(rec),
                "fast_configs": len(rec.row_labels),
                "slow_configs": rec.slow_configs,
                "hybrid_configs": len(rec.hybrid_rows),
                "dyn_registrations": len(rec.dyn_regs),
            }
        return out

    @staticmethod
    def _kernel_of(rec: _SnapRec) -> Optional[Dict[str, Any]]:
        from ..ops.pattern_eval import (kernel_lane_of, kernel_widths,
                                        operand_bytes)

        if rec.sharded is not None:
            # the mesh step wants every config's column: the dense body
            view = rec.sharded.host_view
            return {"lane": kernel_lane_of(view), "entry": "sharded_step",
                    "operand_bytes": operand_bytes(view),
                    "dfa_cpu_leaves": sum(cpu_regex_leaves(p)
                                          for p in rec.sharded.shards),
                    **kernel_widths(view, own=False)}
        if rec.params is None:
            return None
        out = {"lane": kernel_lane_of(rec.params),
               # the jitted function a launch runs, which a device trace
               # finds the served XLA module by
               "entry": "eval_bitpacked_staged",
               # bytes of the serving snapshot's device operands, summed
               # over the uploaded pytree
               "operand_bytes": operand_bytes(rec.params),
               # temporaries of one launch of the largest warm variant of
               # the largest size class
               "launch_temp_bytes": rec.launch_temp_bytes,
               # what the served entry evaluates for ONE request row (its
               # own config's class's leaves and DFA rows) against the
               # corpus's, and the state axis of the class's DFA table
               # store: the scalars read the largest class, `classes`
               # lists each
               **kernel_widths(rec.params)}
        for widths, mine, cp in zip(out["classes"], rec.classes,
                                    rec.params["classes"]):
            widths["cpu_cols"] = mine["cpu_cols"]
            # bytes of a value the device scans for the class's members (0:
            # no DFA row); a longer value is the host's overflow scan's
            widths["device_width"] = mine["device_width"]
            # bytes of a next state in the class's tables: 1 (u8, the bf16
            # scan), 2 past 256 states (u16, the exact wide scan); 0: no DFA
            widths["state_bytes"] = (int(cp["dfa_tables"].dtype.itemsize)
                                     if cp["dfa_tables"] is not None else 0)
        # `matches` leaves the compiler left to the CPU regex lane (outside
        # compiler/redfa.py's subset): a config that has one gets no native
        # plan
        out["dfa_cpu_leaves"] = cpu_regex_leaves(rec.policy)
        return out

    @property
    def warm_error(self) -> Optional[str]:
        """The serving snapshot's kernel warm failure, if any (/readyz)."""
        rec = self._cur_rec
        return rec.warm_error if rec is not None else None

    # ------------------------------------------------------------------
    @staticmethod
    def _health_bytes() -> bytes:
        from .. import protos

        return protos.health_pb2.HealthCheckResponse(
            status=protos.health_pb2.HealthCheckResponse.SERVING
        ).SerializeToString()

    @staticmethod
    def _result_bytes(result: AuthResult) -> bytes:
        from ..service.grpc_server import check_response_from_result

        return check_response_from_result(result).SerializeToString()

    @staticmethod
    def _static_deny(code: int, message: str, headers: List[Dict[str, str]],
                     deny: Optional[DenyWithValues],
                     doc: Optional[Dict[str, Any]] = None) -> AuthResult:
        """Constant mirror of pipeline._customize_deny_with
        (ref pkg/service/auth_pipeline.go:581-608): the denyWith values are
        pre-checked constant for ``doc`` (static, or auth-only against a
        const identity doc; the default empty doc serves identity-failure
        templates, where auth-only selectors are constantly missing)."""
        from ..authjson.value import stringify_json

        doc = doc or {}
        result = AuthResult(code=code, message=message, headers=headers)
        if deny is not None:
            if deny.code:
                result.status = deny.code
            if deny.message is not None:
                result.message = stringify_json(deny.message.resolve_for(doc))
            if deny.body is not None:
                result.body = stringify_json(deny.body.resolve_for(doc))
            if deny.headers:
                result.headers = [
                    {h.name: stringify_json(h.value.resolve_for(doc))}
                    for h in deny.headers
                ]
        return result

    def _deny_result(self, rt: RuntimeAuthConfig,
                     identity_obj: Any = None) -> AuthResult:
        """Authorization-failure template, optionally resolved against a
        constant identity (ref pkg/service/auth_pipeline.go:478-481)."""
        return self._static_deny(
            PERMISSION_DENIED, "Unauthorized", [], rt.deny_with.unauthorized,
            doc=_const_doc(identity_obj) if identity_obj is not None else None)

    def _unauth_result(self, rt: RuntimeAuthConfig, message: str) -> AuthResult:
        """Identity-failure template: UNAUTHENTICATED + WWW-Authenticate
        challenges + static denyWith.unauthenticated
        (ref pkg/service/auth_pipeline.go:468-472)."""
        return self._static_deny(
            UNAUTHENTICATED, message, rt.challenge_headers(),
            rt.deny_with.unauthenticated)

    def _ok_bytes_for(self, rt: RuntimeAuthConfig, identity_obj) -> bytes:
        """Success CheckResponse bytes for a CONSTANT identity outcome:
        response evaluators resolved bucket by bucket against the const
        doc — mirrors pipeline._evaluate_response (per-bucket _sync_auth:
        later buckets see earlier outputs under auth.response.*) +
        wrap_responses + the success assembly in _evaluate_phases
        (ref pkg/service/auth_pipeline.go:487-491)."""
        from ..evaluators.base import wrap_responses
        from ..evaluators.response import DynamicJSON

        doc = _const_doc(identity_obj)
        results: Dict[Any, Any] = {}
        grouped: Dict[int, list] = {}
        for c in rt.response:
            grouped.setdefault(c.priority, []).append(c)
        for bucket in (grouped[p] for p in sorted(grouped)):
            for conf in bucket:
                ev = conf.evaluator
                if isinstance(ev, DynamicJSON):
                    results[conf] = {p.name: p.value.resolve_for(doc)
                                     for p in ev.properties}
                else:
                    results[conf] = ev.value.resolve_for(doc)
            doc["auth"]["response"] = {c.name: o for c, o in results.items()}
        headers, metadata = wrap_responses(results)
        return self._result_bytes(
            AuthResult(code=OK, headers=[headers], metadata=metadata))

    def _unauth_templates(self, rt: RuntimeAuthConfig,
                          sources: List[SourceSpec]) -> List[bytes]:
        """All-sources-failed CheckResponse templates, indexed by the
        bitmask of which STATIC sources' credentials were present (present
        ⇒ key unknown; absent ⇒ missing; dyn sources hitting this path are
        always missing — extractable dyn credentials go to the slow lane).
        Byte-exact with the pipeline: one source returns its bare error,
        several return the sorted JSON error dict
        (pipeline._evaluate_identity, ref auth_pipeline.go:203-258)."""
        if not sources:
            return []
        import json as _json

        statics = [s for s in sources if not s.dyn]
        out: List[bytes] = []
        for mask in range(1 << len(statics)):
            if len(sources) == 1:
                s = sources[0]
                msg = s.invalid_msg if (not s.dyn and mask & 1) else s.missing_msg
            else:
                errors: Dict[str, str] = {}
                si = 0
                for s in sources:
                    if s.dyn:
                        errors[s.name] = s.missing_msg
                    else:
                        errors[s.name] = (s.invalid_msg if (mask >> si) & 1
                                          else s.missing_msg)
                        si += 1
                msg = _json.dumps(errors, separators=(",", ":"), sort_keys=True)
            out.append(self._result_bytes(self._unauth_result(rt, msg)))
        return out

    # ---- jit pre-warm (compiles must never land on live requests) ----

    def _bucket_grid(self, rec: _SnapRec) -> List[Tuple[int, int]]:
        """Every (batch_pad, byte_eff) jit variant _dispatch can produce,
        largest first (the largest combo is the universal round-up target)."""
        pads: List[int] = []
        p = min(bucket_pow2(self.max_batch), self.max_batch)
        while p >= 16:
            pads.append(p)
            p //= 2
        if not pads:  # max_batch < 16: one pad, or refresh would warm nothing
            pads.append(min(bucket_pow2(self.max_batch), self.max_batch))
        # the byte axis (_byte_bucket): powers of two up to the floor width,
        # as it was, and past it the corpus's widest class's width alone; a
        # class compiles the buckets up to its own (_warm_one).  No bucket
        # between: each costs a class 0.4-0.7 s of every boot, pad by pad,
        # even from the compile cache (PERF.md section 6, PR 38), and a cut
        # whose longest value is 65-128 bytes runs 256 steps on a chip that
        # is idle most of the time
        effs: List[int] = [0]
        if rec.byte_width:
            effs = []
            e = 16
            while e < DFA_VALUE_BYTES:
                effs.append(e)
                e *= 2
            effs.append(DFA_VALUE_BYTES)
            if rec.byte_width > DFA_VALUE_BYTES:
                effs.append(rec.byte_width)
            effs.reverse()
        return [(p, e) for p in pads for e in effs]

    @staticmethod
    def _byte_bucket(used: int, width: int) -> int:
        """The byte bucket of a launch whose rows' longest value is ``used``
        bytes, in a class ``width`` wide: the warm grid's byte axis."""
        bucket = bucket_pow2(max(used, 1))
        return bucket if bucket <= DFA_VALUE_BYTES else width

    def _warm_one(self, rec: _SnapRec, pad: int, eff: int) -> None:
        """Compile (and cache) the jit variant for one bucket shape using
        throwaway zero operands.  The snapshot's first (the swap gate's: the
        largest variant) also records what one launch of it allocates in
        temporaries (``rec.launch_temp_bytes``)."""
        import jax
        import jax.numpy as jnp

        from ..ops.pattern_eval import eval_bitpacked_staged_jit

        if rec.sharded is not None:
            from ..parallel.sharded_eval import _ShardedEncoded

            sh = rec.sharded
            p0 = sh.shards[0]
            S, A, M, K = sh.n_shards, p0.n_attrs, p0.n_member_attrs, p0.members_k
            C, NB = p0.n_own_cpu, max(p0.n_byte_attrs, 1)
            out = sh.launch(_ShardedEncoded(
                attrs_val=np.zeros((pad, S, A), dtype=np.int32),
                members_c=np.full((pad, S, M, K), PAD, dtype=np.int32),
                cpu_dense=np.zeros((pad, S, C), dtype=bool),
                attr_bytes=np.zeros((pad, S, NB, eff), dtype=np.uint8)
                if eff else None,
                byte_ovf=np.zeros((pad, S, NB), dtype=bool) if eff else None,
                shard_of=np.zeros((pad,), dtype=np.int32),
                row_of=np.zeros((pad,), dtype=np.int32),
                host_fallback=np.zeros((pad,), dtype=bool)))
            jax.block_until_ready(out)
            rec.warm.add((pad, eff))
            return
        first = not rec.warm
        for c, view in enumerate(rec.views):
            # every size class's variant of the bucket: a cut's launch of
            # any class then finds its shape compiled.  A class's rows hold
            # no value past its own width (0: no DFA row), so its launches
            # run a bucket past it at that width (_launch_classes): (pad,
            # eff) warm means every class compiled at (pad, min(eff, its
            # width)); a shape met again costs one launch of zeros
            eff_c = min(eff, rec.classes[c]["device_width"])
            layout = self._stage_layout(rec, c, pad, eff_c)
            size = layout[-1][3] + layout[-1][4]
            args = (view, jnp.asarray(np.zeros(size, dtype=np.uint8)), layout)
            if first:
                rec.launch_temp_bytes = max(
                    rec.launch_temp_bytes,
                    launch_temp_bytes(eval_bitpacked_staged_jit, *args))
            out = eval_bitpacked_staged_jit(*args)
            jax.block_until_ready(out)
        rec.warm.add((pad, eff))

    @staticmethod
    def _operand_views(a: Dict[str, np.ndarray], rows, eff: int,
                       n_cpu: int) -> list:
        """The request operands of one single-corpus launch as host arrays,
        in the order the jitted entries take them: rows ``rows`` of the slot
        arrays ``a`` (``slice(pad)`` for a full cut: views, stale pad rows
        and all; row indices after dedup or for one size class of a cut:
        copies, since the slot refills once the batch completes),
        ``cpu_dense`` cut to the launch's class's ``n_cpu`` columns (a
        config's CPU columns are the first of its row, whatever the
        corpus's widest) and ``attr_bytes`` to ``eff`` columns (``eff`` 0 =
        no DFA operands)."""
        views = [a["attrs_val"][rows], a["members"][rows],
                 a["cpu_dense"][rows, :n_cpu].view(bool), a["config_id"][rows]]
        if eff:
            views += [np.ascontiguousarray(a["attr_bytes"][rows, :, :eff]),
                      a["byte_ovf"][rows].view(bool)]
        return views

    @staticmethod
    def _stage_layout(rec: _SnapRec, c: int, pad: int, eff: int) -> tuple:
        """The static layout of the staging buffer of one single-corpus
        launch of size class ``c`` at bucket (pad, eff): the slot arrays'
        operands, [:pad] rows each, ``cpu_dense`` cut to the class's columns
        and ``attr_bytes`` to ``eff``, end to end in the order the served
        entry decodes them (``eff`` 0 = no DFA operands).  A function of the
        snapshot's operand shapes and wire dtype alone, built once per
        snapshot, class and bucket.  Raises where the backend's byte order
        failed the one-time probe: the swap gate's warm then fails, and so
        does any launch."""
        from ..ops.pattern_eval import (_FUSED_FIELDS, fuse_layout,
                                        require_fused_h2d)

        layout = rec.layouts.get((c, pad, eff))
        if layout is None:
            require_fused_h2d()
            # the operands' own dtypes and row shapes, under the names the
            # entry decodes them by (its first four, or six, in order)
            views = NativeFrontend._operand_views(
                rec.arrays[0], slice(0), eff, rec.classes[c]["cpu_cols"])
            layout = rec.layouts[(c, pad, eff)] = fuse_layout(
                (name, v.dtype, (pad,) + v.shape[1:])
                for name, v in zip(_FUSED_FIELDS, views))
        return layout

    def _prewarm_rest(self, rec: _SnapRec, grid: List[Tuple[int, int]]) -> None:
        try:
            # host-lane jit first (ISSUE 12 satellite): with lane selection
            # on, the very next light-load slot after this swap will ride
            # the CPU-backend twin — its small pad shapes must be warm
            # before the long tail of device variants compiles (the same
            # latency-spike class as the brownout worker-thread fix)
            if self.lanes.enabled:
                self._warm_host(rec)
            for pad, eff in grid:
                # bail once superseded: a draining snapshot never sees new
                # shapes, and its compiles would contend with the successor's
                # swap-gate compile for the single core
                if (not self._running or rec.snap_id not in self._snaps
                        or rec.snap_id != self._next_snap_id - 1):
                    return
                if (pad, eff) in rec.warm:
                    continue
                self._warm_one(rec, pad, eff)
        except Exception as e:
            log.exception("kernel warm grid failed for snapshot %d",
                          rec.snap_id)
            rec.warm_error = rec.warm_error or f"{type(e).__name__}: {e}"
        finally:
            rec.warm_done.set()

    def _warm_host(self, rec: _SnapRec) -> None:
        """Compile the CPU-backend (host-lane) jit variants for the common
        SMALL pad shapes — the shapes cost-model-selected slots and the
        degrade path actually produce under light load.  Large pads stay
        cold on purpose: the cost model never routes a large cut host-side
        (R_BATCH), so warming them would burn reconcile-time CPU for
        shapes that only the saturated-brownout edge could ever hit."""
        if rec.sharded is not None or rec.policy is None:
            return
        effs = [rec.byte_width] if rec.policy.n_byte_attrs else [0]
        for pad in (16, 32):
            if pad > self.max_batch:
                break
            for eff in effs:
                if (not self._running or rec.snap_id not in self._snaps
                        or rec.snap_id != self._next_snap_id - 1):
                    return
                if (pad, eff) not in rec.host_warm:
                    self._warm_host_one(rec, pad, eff)

    @staticmethod
    def _host_twin(rec: _SnapRec):
        """The CPU device the host twin runs on, with ``rec.host_params``
        built (once per snapshot) FOR that device: in a TPU process the
        default backend's bf16 matmul operands are what the CPU backend
        cannot multiply."""
        import jax

        from ..ops.pattern_eval import to_device

        cpu = jax.devices("cpu")[0]
        if rec.host_params is None:
            rec.host_params = to_device(rec.policy, device=cpu, host=True)
        return cpu

    def _warm_host_one(self, rec: _SnapRec, pad: int, eff: int) -> None:
        """Compile (and cache) the CPU-backend jit variant for one bucket
        shape using throwaway zero operands — the host-lane mirror of
        _warm_one.  Also builds rec.host_params eagerly, so the first real
        host-lane slot pays neither the operand-pytree build nor the XLA
        compile."""
        import jax

        from ..ops.pattern_eval import eval_bitpacked_jit

        cpu = self._host_twin(rec)
        with jax.default_device(cpu):
            out = eval_bitpacked_jit(*_bitpacked_zero_args(
                rec.policy, rec.host_params, pad, eff))
            jax.block_until_ready(out)
        rec.host_warm.add((pad, eff))

    def _pick_warm_shape(self, rec: _SnapRec, count: int, eff: int) -> Tuple[int, int]:
        """Smallest warmed (pad ≥ count, eff' ≥ eff); falls back to the
        exact bucket shape (inline compile) only when nothing fits — i.e.
        cold start before the first variant finished compiling.  Each
        consultation is counted per served (pad, eff) variant: hit = exact
        shape warm, rounded = a larger warm shape absorbed the batch,
        miss = inline XLA compile on a live batch."""
        pad = min(bucket_pow2(count), self.max_batch)
        if (pad, eff) in rec.warm:
            self._count_warm(pad, eff, "hit")
            return pad, eff
        best: Optional[Tuple[int, int]] = None
        for p, e in tuple(rec.warm):  # snapshot: the prewarm thread appends
            if p >= count and e >= eff and (best is None or (p, e) < best):
                best = (p, e)
        if best is not None:
            self._count_warm(best[0], best[1], "rounded")
            return best
        self._count_warm(pad, eff, "miss")
        return pad, eff

    def _count_warm(self, pad: int, eff: int, outcome: str) -> None:
        ch = self._warm_children.get((pad, eff, outcome))
        if ch is None:
            ch = self._warm_children[(pad, eff, outcome)] = (
                metrics_mod.jit_warm_cache.labels(str(pad), str(eff), outcome))
        ch.inc()

    def wait_warm(self, timeout_s: float = 600.0) -> bool:
        """Block until the newest snapshot's warm grid has finished; True
        when every jit bucket variant compiled, False on a timeout or a
        kernel that failed to compile (``warm_error`` says which).  The CLI
        and the benches call this after start(), so the server is not
        called ready while XLA compiles can still land on live traffic."""
        with self._lock:
            rec = self._snaps.get(self._next_snap_id - 1)
        if rec is None:
            return True
        return rec.warm_done.wait(timeout_s) and rec.warm_error is None

    # ------------------------------------------------------------------
    def refresh(self) -> None:
        """Rebuild the C++ snapshot from the engine's current one (called
        after every engine.apply_snapshot — the reconcile-time swap).
        Serialized end-to-end under _lock: concurrent reconciles must not
        mint duplicate ids OR install their C++ snapshots out of order
        (fe_swap sets the serving snapshot unconditionally — a late older
        swap would leave a stale corpus serving).

        Change safety (ISSUE 10): during an engine canary window the swap
        listeners do not fire, and ``engine._snapshot`` IS the baseline —
        so this lane holds the previous generation until promotion (the
        C++ batcher gathers per-snapshot and cannot split one gathered
        batch across two compiled corpora; its canary evidence instead
        feeds the guard's baseline cohort via canary_observe_external).
        Promotion and rollback both fire the listeners, converging this
        lane in one atomic fe_swap."""
        with self._lock:
            self._refresh_locked()

    def _refresh_locked(self) -> None:
        # a refresh already blocked on _lock when stop() ran would re-wire
        # change listeners (re-leaking this instance) and fe_swap onto a
        # torn-down module — the lock alone doesn't order it before stop()'s
        # unwire, so bail once stopped (start() sets _running before the
        # first refresh)
        if not self._running:
            return
        engine = self.engine
        snap = engine._snapshot
        policy = snap.policy if snap is not None else None
        sharded = snap.sharded if snap is not None else None
        mod = self._mod

        # ISSUE 14 lanes: the C++ encoder/kernel predate the numeric and
        # relation operands and the overflow assist — a corpus using them
        # must NOT become a C++ fast-lane snapshot (it would silently
        # mis-evaluate the new leaves).  The Python engine lane serves it
        # exactly; the native port is tracked work (docs/architecture.md).
        def _uses_new_lanes(p) -> bool:
            return (int(getattr(p, "n_num_attrs", 0) or 0) > 0
                    or int(getattr(p, "n_rel_slots", 0) or 0) > 0
                    or bool(getattr(p, "ovf_assist", False)))

        pols = ([policy] if policy is not None else
                list(getattr(sharded, "shards", None) or ()))
        if any(_uses_new_lanes(p) for p in pols):
            log.warning(
                "native fast lane DISABLED for this snapshot: the corpus "
                "uses numeric/relation/ovf-assist lanes the C++ encoder "
                "does not implement yet — the engine lane serves it")
            policy = None
            sharded = None

        if self.strict_verify and snap is not None and (
                policy is not None or sharded is not None) and not getattr(
                snap, "lint_ok", False):
            # lint_ok marks snapshots the engine's own strict-verify already
            # vetted at compile time: re-linting here (under _lock, per
            # refresh) would rebuild both lanes' operand pytrees for zero
            # added protection.  This path fires only when the frontend is
            # strict but the engine is not.
            from ..analysis.tensor_lint import lint_snapshot
            from ..analysis.translation_validate import (
                certify_snapshot,
                snapshot_policies,
            )

            findings = lint_snapshot(snap)
            if not findings:
                # lint-clean: certify the compiled artifacts decide like
                # the host oracle (same gate the engine's strict path
                # runs; the fingerprint cache makes repeats free)
                for pol in snapshot_policies(snap):
                    _, fails, _ = certify_snapshot(pol)
                    findings += fails
            if findings:
                # no snap_id minted, no fe_swap: the previous C++ snapshot
                # (and its credential variants) keeps serving untouched
                metrics_mod.snapshot_rejected.labels("native_frontend").inc()
                log.error(
                    "native snapshot REJECTED by tensor lint/translation "
                    "validation (snapshot %d keeps serving): %s",
                    self._cur_rec.snap_id if self._cur_rec else 0,
                    "; ".join(str(f) for f in findings[:5]))
                return

        snap_id = self._next_snap_id
        self._next_snap_id += 1

        spec: Dict[str, Any] = {
            "snap_id": snap_id,
            "policy": None,
            "A": 0, "M": 0, "K": 0, "C": 0, "NB": 0, "DVB": DFA_VALUE_BYTES,
            "elem16": 0,
            "fcs": [], "hosts": [], "slots": [],
            "attr_dfas": [],
            "dfa_R": 0, "dfa_S": 0,
            "invalid": self._result_bytes(
                AuthResult(code=INVALID_ARGUMENT, message="Invalid request")),
            "notfound": self._result_bytes(
                AuthResult(code=NOT_FOUND, message="Service not found")),
            "health": self._health_bytes(),
        }
        rec = _SnapRec(snap_id=snap_id, policy=policy, params=None, encoder=None)
        # attribution (ISSUE 9): reuse the engine snapshot's heat map when
        # it exists (same policy object → same rows), else build one
        try:
            rec.heat = getattr(snap, "heat", None) if snap is not None \
                else None
            if rec.heat is None:
                rec.heat = prov_mod.HeatMap.for_snapshot(policy, sharded)
        except Exception:
            log.exception("native heat-map build failed (refresh unaffected)")
            rec.heat = None

        entries = list(snap.by_id.values()) if snap is not None else []
        fcs: List[dict] = []
        # exact hosts AND "*.suffix" wildcard keys — the C++ side replicates
        # the index's wildcard walk-up, so misses resolve to NOT_FOUND
        # natively (ref pkg/index/index.go:153-174)
        hosts: List[Tuple[str, int]] = []
        ok_bytes = self._result_bytes(AuthResult(code=OK, headers=[{}]))

        # active span export needs a per-request Python span (W3C inject into
        # outbound calls + Check span export, ref pkg/trace/trace.go:20-27);
        # the fast lane never touches Python per request, so with tracing on
        # it head-samples: every Nth request takes the slow lane with full
        # spans, the rest stay native (counted in stats trace_sampled —
        # enabling observability must not cost ~8x throughput wholesale)
        spec["trace_every"] = (self.trace_sample_n
                               if tracing_mod.tracing_active() else 0)
        if spec["trace_every"] > 1 and not self._trace_mode_logged:
            self._trace_mode_logged = True
            log.info(
                "tracing active: head-sampling 1-in-%d requests to the slow "
                "lane for span export (the rest serve natively, untraced); "
                "set AUTHORINO_TPU_TRACE_ALL=1 for every-request tracing",
                spec["trace_every"])

        enc = None
        if policy is not None:
            from ..native.encoder import get_native_encoder
            from ..ops.pattern_eval import class_view, to_device

            enc = get_native_encoder(policy)
            if enc is not None:
                rec.encoder = enc
                rec.params = (snap.params if snap.params is not None
                              else to_device(policy))
                classes = policy.classes
                rec.views = [class_view(rec.params, c)
                             for c in range(len(classes))]
                rec.class_of = np.zeros((policy.n_configs,), dtype=np.int8)
                for c, cls in enumerate(classes):
                    rec.class_of[cls.configs] = c
                rec.classes = [cls.widths() for cls in classes]
                rec.byte_width = max(w["device_width"] for w in rec.classes)
                rec.cfg_dfa_n = (policy.config_dfa_rows >= 0).sum(
                    axis=1).astype(np.int64)
                spec["policy"] = enc._handle
                dt = wire_dtype(policy)
                A, M, K = policy.n_attrs, policy.n_member_attrs, policy.members_k
                C, NB = policy.n_own_cpu, max(policy.n_byte_attrs, 1)
                # the slots are as wide as the widest class (at least the
                # floor: a corpus without a DFA row still has the arrays)
                DVB = max(rec.byte_width, DFA_VALUE_BYTES)
                spec.update(A=A, M=M, K=K, C=C, NB=NB, DVB=DVB,
                            elem16=1 if dt == np.int16 else 0)
                ams = np.ascontiguousarray(policy.member_attr_slot, dtype=np.int32)
                abs_v = np.ascontiguousarray(policy.attr_byte_slot, dtype=np.int32)
                rec.keepalive += [ams, abs_v]
                spec["attr_member_slot_addr"] = ams.ctypes.data
                spec["attr_byte_slot_addr"] = abs_v.ctypes.data
                rec.cacheable = policy.config_cacheable
                if policy.n_byte_attrs > 0 and policy.dfa_tables.size:
                    # C++ indexes transition tables BY ROW: expand the
                    # compiler's deduped [T, S, 256] store through
                    # dfa_table_of_row for the native encoder, at the
                    # store's own next-state width (u16 past 256 states)
                    dt_tr = np.ascontiguousarray(policy.dfa_tables_by_row)
                    dt_fl = np.ascontiguousarray(policy.dfa_flags_by_row,
                                                 dtype=np.uint8)
                    rec.keepalive += [dt_tr, dt_fl]
                    spec.update(dfa_R=int(dt_tr.shape[0]), dfa_S=int(dt_tr.shape[1]),
                                dfa_state_bytes=int(dt_tr.dtype.itemsize),
                                dfa_trans_addr=dt_tr.ctypes.data,
                                dfa_flags_addr=dt_fl.ctypes.data)
                spec["G"] = policy.n_configs
                spec["cfg_dfas"] = _cfg_dfa_refs(policy)

                # batch slots (numpy-owned; freed on SNAP_RETIRED)
                B = self.max_batch
                for _ in range(self.slots):
                    a = {
                        "attrs_val": np.zeros((B, A), dtype=dt),
                        "members": np.full((B, M, K), PAD, dtype=dt),
                        "cpu_dense": np.zeros((B, C), dtype=np.uint8),
                        "config_id": np.zeros((B,), dtype=np.int32),
                        "attr_bytes": np.zeros((B, NB, DVB), dtype=np.uint8),
                        "byte_ovf": np.zeros((B, NB), dtype=np.uint8),
                        # written by the C++ encoder a row (Slot::byte_used,
                        # Slot::dfa_bytes): the longest value in the row's
                        # attr_bytes; the value bytes its DFAs read on the
                        # device [:, 0] and in the host's overflow scan [:, 1]
                        "byte_used": np.zeros((B,), dtype=np.uint16),
                        "dfa_bytes": np.zeros((B, 2), dtype=np.uint32),
                    }
                    rec.arrays.append(a)
                    spec["slots"].append({k: v.ctypes.data for k, v in a.items()})
                self._bind_cache_keys(rec, getattr(snap, "cache_tokens", None))
            else:
                policy = None  # no native encoder → kernel fast lane off
        elif sharded is not None:
            # mesh-sharded corpus: the shards share ONE interner and
            # ShapeTargets-unified operand shapes, so the C++ encoder writes
            # each request into its owning shard's [B, S, ...] slice and the
            # dispatcher feeds the shard_map step directly — multi-device
            # scaling and the native frontend compose (the reference's
            # sharding composes with its full server,
            # ref controllers/label_selector.go:14-45)
            from ..native.encoder import get_native_encoder

            enc = get_native_encoder(sharded.shards[0])
            if enc is not None:
                rec.encoder = enc
                rec.sharded = sharded
                spec["policy"] = enc._handle
                p0 = sharded.shards[0]
                S_sh = sharded.n_shards
                A, M, K = p0.n_attrs, p0.n_member_attrs, p0.members_k
                C, NB = p0.n_own_cpu, max(p0.n_byte_attrs, 1)
                # the sharded step takes int32 operands (parallel/sharded_eval
                # encode contract), so elem16 stays off
                # shards compile under ShapeTargets: one class each, at the
                # floor width
                DVB = max(p.byte_width for p in sharded.shards)
                rec.byte_width = DVB if sharded.has_dfa else 0
                spec.update(A=A, M=M, K=K, C=C, NB=NB, S=S_sh, DVB=DVB,
                            elem16=0)
                ams = np.ascontiguousarray(
                    np.stack([p.member_attr_slot for p in sharded.shards]),
                    dtype=np.int32)
                abs_v = np.ascontiguousarray(
                    np.stack([p.attr_byte_slot for p in sharded.shards]),
                    dtype=np.int32)
                rec.keepalive += [ams, abs_v]
                spec["attr_member_slot_addr"] = ams.ctypes.data
                spec["attr_byte_slot_addr"] = abs_v.ctypes.data
                # per-shard DFA tables stack on the row axis (targets unify
                # R and the state count); attr_dfas rows are globalized
                rec.cacheable = np.stack(
                    [p.config_cacheable for p in sharded.shards])
                cfg_dfas: List[List[Tuple[int, int, int]]] = []
                if p0.n_byte_attrs > 0 and p0.dfa_tables.size:
                    # per-row expansion of the deduped table store, stacked
                    # on the (shard-globalized) row axis for C++
                    R = int(p0.dfa_table_of_row.shape[0])
                    dt_tr = np.ascontiguousarray(
                        np.concatenate([p.dfa_tables_by_row
                                        for p in sharded.shards]))
                    dt_fl = np.ascontiguousarray(
                        np.concatenate([p.dfa_flags_by_row
                                        for p in sharded.shards]),
                        dtype=np.uint8)
                    rec.keepalive += [dt_tr, dt_fl]
                    spec.update(dfa_R=int(dt_tr.shape[0]),
                                dfa_S=int(dt_tr.shape[1]),
                                dfa_state_bytes=int(dt_tr.dtype.itemsize),
                                dfa_trans_addr=dt_tr.ctypes.data,
                                dfa_flags_addr=dt_fl.ctypes.data)
                    for s, p in enumerate(sharded.shards):
                        cfg_dfas += _cfg_dfa_refs(p, row_base=s * R)
                spec["G"] = p0.n_configs
                spec["cfg_dfas"] = cfg_dfas

                B = self.max_batch
                for _ in range(self.slots):
                    a = {
                        "attrs_val": np.zeros((B, S_sh, A), dtype=np.int32),
                        "members": np.full((B, S_sh, M, K), PAD, dtype=np.int32),
                        "cpu_dense": np.zeros((B, S_sh, C), dtype=np.uint8),
                        "config_id": np.zeros((B,), dtype=np.int32),
                        "shard_of": np.zeros((B,), dtype=np.int32),
                        "attr_bytes": np.zeros((B, S_sh, NB, DVB),
                                               dtype=np.uint8),
                        "byte_ovf": np.zeros((B, S_sh, NB), dtype=np.uint8),
                        "byte_used": np.zeros((B,), dtype=np.uint16),
                        "dfa_bytes": np.zeros((B, 2), dtype=np.uint32),
                    }
                    rec.arrays.append(a)
                    spec["slots"].append({k: v.ctypes.data for k, v in a.items()})
                self._bind_cache_keys(rec, None)
            else:
                sharded = None  # no native encoder → kernel fast lane off

        fast_ids = set()
        fc_rows: List[int] = []
        for entry in entries:
            # each entry is judged against its OWN compile: the single
            # corpus, or its owning shard's sub-corpus on a mesh
            policy_for = policy
            if sharded is not None:
                policy_for = None
                if entry.rules is not None:
                    loc = sharded.locator.get(entry.rules.name)
                    if loc is not None:
                        policy_for = sharded.shards[loc[0]]
            spec_fl = fast_lane_eligible(entry, policy_for)
            if spec_fl is None:
                continue
            fast_ids.add(id(entry))
            fc_idx = len(fcs)
            # per-authconfig metric labels — EXACTLY the pipeline's
            # scheme (ref pkg/service/auth_pipeline.go:26-36; translate
            # injects namespace/name into runtime labels), so a
            # config's fast- and slow-lane traffic lands on one series
            lbl = entry.runtime.labels or {}
            ns_l, nm_l = lbl.get("namespace", ""), lbl.get("name", "")
            rt_e = entry.runtime
            # response/denyWith-template configs: OK and DENY bytes are per
            # identity outcome (anonymous at swap; per-key at swap; per-
            # credential at dyn registration) — empty bytes in a variant =
            # the config default
            fc_ok = (self._ok_bytes_for(rt_e, spec_fl.const_identity)
                     if rt_e.response and not spec_fl.sources
                     and not spec_fl.hybrid else ok_bytes)
            fc_deny = self._result_bytes(self._deny_result(
                rt_e,
                spec_fl.const_identity
                if spec_fl.deny_templated and not spec_fl.sources else None))
            fc = {
                "row": 0,
                "has_batch": 1 if spec_fl.has_batch else 0,
                "hybrid": 1 if spec_fl.hybrid else 0,
                "ok": fc_ok,
                "deny": fc_deny,
                "plans": spec_fl.plans,
                "sources": [
                    {
                        "cred_kind": s.cred_kind,
                        "cred_key": s.cred_key,
                        "dyn": 1 if s.dyn else 0,
                        "variants": [
                            (key, vplans,
                             self._ok_bytes_for(rt_e, ident_obj)
                             if ident_obj is not None and rt_e.response
                             and not spec_fl.hybrid
                             else b"",
                             self._result_bytes(
                                 self._deny_result(rt_e, ident_obj))
                             if ident_obj is not None
                             and spec_fl.deny_templated else b"")
                            for key, vplans, ident_obj in s.variants
                        ],
                    }
                    for s in spec_fl.sources
                ],
                "unauth_msgs": self._unauth_templates(rt_e, spec_fl.sources),
                "ns": ns_l,
                "name": nm_l,
            }
            dyn_map = {id(s.idc): (i, s.ttl_cap)
                       for i, s in enumerate(spec_fl.sources) if s.dyn}
            if dyn_map:
                rec.dyn_regs[entry.id] = (fc_idx, spec_fl.auth_attrs,
                                          policy_for, dyn_map,
                                          spec_fl.hybrid)
                # a JWKS rotation invalidates every cached token: swap
                # in a fresh snapshot (empty variant map) when the
                # provider's key set actually changes (add_change_listener
                # dedups, so re-wiring on every refresh is safe — and a
                # reconcile-minted evaluator gets wired the first time)
                for s in spec_fl.sources:
                    if not s.dyn:
                        continue
                    add_listener = getattr(s.idc.evaluator,
                                           "add_change_listener", None)
                    if add_listener is not None:
                        add_listener(self._on_oidc_change)
                        # unregistered in stop(): evaluators outlive
                        # frontend instances (reconcile re-creates the
                        # frontend, not the evaluator graph)
                        self._change_wired.add(s.idc.evaluator)
            if spec_fl.has_batch:
                if sharded is not None:
                    shard, row = sharded.locator[entry.rules.name]
                    fc["row"], fc["shard"] = int(row), int(shard)
                    fc["dvb"] = int(
                        sharded.shards[shard].config_byte_width[row])
                    row_key: Any = (int(shard), int(row))
                else:
                    row = policy.config_ids[entry.rules.name]
                    fc["row"] = int(row)
                    fc["dvb"] = int(policy.config_byte_width[row])
                    fc_rows.append(int(row))
                    row_key = int(row)
                rec.row_labels[row_key] = (ns_l, nm_l)
                if spec_fl.hybrid:
                    rec.hybrid_rows.add(row_key)
            fcs.append(fc)
            for host in entry.hosts:
                hosts.append((host, fc_idx))
        rec.fc_rows = np.asarray(fc_rows or [0], dtype=np.int64)
        rec.slow_configs = len(entries) - len(fast_ids)
        if rec.heat is not None:
            rec.heat.bind_authconfigs(rec.row_labels, rec.hybrid_rows)

        # non-fast hosts route to the Python pipeline (slow lane)
        fast_hosts = {h for h, _ in hosts}
        for entry in entries:
            if id(entry) in fast_ids:
                continue
            for host in entry.hosts:
                if host not in fast_hosts:
                    hosts.append((host, -1))
        spec["fcs"] = fcs
        spec["hosts"] = hosts

        self._snaps[snap_id] = rec  # caller holds _lock
        self._cur_rec = rec
        grid: List[Tuple[int, int]] = []
        if (rec.params is not None or rec.sharded is not None) and rec.arrays:
            grid = self._bucket_grid(rec)
            try:
                # the largest combo compiles BEFORE the swap goes live: the
                # previous snapshot keeps serving meanwhile, and once this
                # one is current every batch shape can round up to it
                self._warm_one(rec, *grid[0])
            except Exception as e:
                log.exception("kernel failed to compile at the swap gate "
                              "(snapshot %d, shape %s)", snap_id, grid[0])
                rec.warm_error = f"{type(e).__name__}: {e}"
        mod.fe_swap(spec)
        metrics_mod.snapshot_generation.labels("native_frontend").set(snap_id)
        try:
            # kernel-cost analysis (ISSUE 16) — advisory, after the swap is
            # live; the process-wide shape memo makes engine/native overlap
            # for the same snapshot essentially free
            self._cost_model.analyze(snap_id, policy=rec.policy,
                                     params=rec.params, sharded=rec.sharded,
                                     recorder=RECORDER)
        except Exception:
            log.exception("kernel cost analysis failed (swap unaffected)")
        if grid and rec.warm_error is None:
            # NON-daemon and tracked: a daemon thread mid-XLA-compile at
            # interpreter exit force-unwinds through native code and aborts
            # the process ("FATAL: exception not rethrown"); stop() joins
            # these, and _prewarm_rest bails between variants once stopped
            self._track_thread(threading.Thread(
                target=self._prewarm_rest, args=(rec, grid),
                name="atpu-fe-prewarm"))
        else:
            rec.warm_done.set()
        log.info("native frontend snapshot %d: %d fast configs, %d host keys",
                 snap_id, len(fcs), len(hosts))

    def _on_oidc_change(self) -> None:
        """JWKS rotation: rebuild the C++ snapshot (fresh, empty variant
        map) so tokens verified under retired keys stop being served fast.
        Runs on its own thread — the notifier is an asyncio worker and
        refresh() blocks on the swap-gate jit compile."""
        if not self._running:
            return
        self._track_thread(threading.Thread(target=self._refresh_if_running,
                                            name="atpu-fe-oidc-refresh"))

    def _refresh_if_running(self) -> None:
        if self._running:
            self.refresh()

    def _track_thread(self, t: threading.Thread) -> None:
        """Register-then-start a compile-bearing helper thread under its
        own lock (callers run both with and without _lock) — a dropped
        entry would escape stop()'s join and race interpreter teardown."""
        with self._thread_lock:
            self._prewarm_threads = [
                p for p in self._prewarm_threads if p.is_alive()] + [t]
        t.start()

    def _register_dyn(self, rec, entry, pipeline, model) -> None:
        """After a slow-lane pipeline run: if the config is dyn-eligible and
        identity resolved, cache this token's plan variant in C++ so the
        next request with it never touches Python (the fast-lane analog of
        the reference's per-evaluator TTL cache keyed by access token,
        ref pkg/evaluators/evaluator.go caching + opa.go:141 precompile).

        ``rec`` is the snapshot record captured BEFORE the pipeline ran: a
        JWKS rotation that rebuilds the snapshot mid-verification makes the
        registration land on the superseded (no longer serving) snapshot
        instead of re-caching a retired-key token into the fresh one."""
        if rec is None or rec is not self._cur_rec:
            return
        reg = rec.dyn_regs.get(entry.id)
        if reg is None:
            return
        fc_idx, auth_attrs, reg_policy, src_map, reg_hybrid = reg
        conf, obj = pipeline.resolved_identity()
        if obj is None:
            return
        reg_src = src_map.get(id(conf))
        if reg_src is None:
            return  # the winning identity is not a dyn source
        src_idx, ttl_cap = reg_src
        idc = conf
        import time as _time

        now = _time.time()
        ttl = self.dyn_ttl_s
        if ttl_cap is not None:
            # the opted-in window is anchored at the LAST REAL check: a
            # registration off a pipeline-cache hit must not restart the
            # clock (revocation would slip past cache.ttl otherwise)
            ttl = min(ttl, ttl_cap)
            if idc.cache is not None:
                try:
                    rem = idc.cache.remaining(idc.cache.resolve_key_for(
                        pipeline.authorization_json()))
                except Exception:
                    rem = None
                if rem is not None:
                    ttl = min(ttl, rem)
        deadline = now + ttl
        if isinstance(idc.evaluator, MTLS):
            # the raw forwarded PEM is the cache key (exactly the bytes the
            # C++ side extracts); the cert's own notAfter bounds the entry
            token = model.source.certificate or ""
            if not token:
                return
            try:
                import urllib.parse

                from cryptography import x509

                cert = x509.load_pem_x509_certificate(
                    urllib.parse.unquote(token).encode())
                deadline = min(deadline,
                               cert.not_valid_after_utc.timestamp())
            except Exception:
                return
        else:
            try:
                token = idc.evaluator.credentials.extract(model.http)
            except Exception:
                return
        exp = obj.get("exp") if isinstance(obj, dict) else None
        if isinstance(exp, (int, float)) and not isinstance(exp, bool):
            deadline = min(deadline, float(exp))
        if deadline <= now:
            return
        vplans: List[tuple] = []
        if auth_attrs:
            if reg_policy is None:
                return
            doc = _const_doc(obj)
            reg_row = reg_policy.config_ids.get(entry.rules.name) \
                if entry.rules is not None else None
            for attr in auth_attrs:
                p = _const_plan(reg_policy, attr, doc, reg_row)
                if p is None:
                    return  # this token's values don't fit the compact payload
                vplans.append(p)
        rt_e = entry.runtime
        ok_bytes = b""
        deny_bytes = b""
        try:
            if rt_e.response and not reg_hybrid:
                # hybrid OKs are answered by the pipeline (response phase
                # runs there) — no per-credential OK bytes
                ok_bytes = self._ok_bytes_for(rt_e, obj)
            if rt_e.authorization and not _deny_with_static(
                    rt_e.deny_with.unauthorized):
                deny_bytes = self._result_bytes(self._deny_result(rt_e, obj))
        except Exception:
            return  # this credential's templates don't resolve: stay slow
        self._mod.fe_add_variant(rec.snap_id, fc_idx, src_idx,
                                 token.encode("utf-8"), vplans, ok_bytes,
                                 deny_bytes, int(deadline * 1e9))

    # ------------------------------------------------------------------
    def _fold_fc_counts(self) -> None:
        """Fold C++-side direct decisions (identity-only OKs, credential
        denials) into the same per-authconfig Prometheus series the pipeline
        bumps (ref pkg/service/auth_pipeline.go:26-36)."""
        for ns, name, ok, missing, invalid in self._mod.fe_drain_fc_counts():
            metrics_mod.authconfig_total.labels(ns, name).inc(ok + missing + invalid)
            if ok:
                metrics_mod.authconfig_response_status.labels(ns, name, "OK").inc(ok)
            if missing or invalid:
                metrics_mod.authconfig_response_status.labels(
                    ns, name, "UNAUTHENTICATED").inc(missing + invalid)
        # duration + stage histograms drain on a coarser cadence — each
        # drain walks every fc × bucket atomic, too wide for per-batch
        now = time.monotonic()
        if now - self._last_hist_drain >= self.hist_drain_s:
            self._last_hist_drain = now
            self.drain_histograms()

    def drain_histograms(self) -> None:
        """Fold the C++-recorded duration/stage histograms into Prometheus:
        auth_server_authconfig_duration_seconds per authconfig (metric
        parity with ref pkg/service/auth_pipeline.go:26-36 on the fast
        lane) and auth_server_frontend_stage_duration_seconds per on-box
        stage.  Also accumulates raw stage counts in self.stage_totals for
        the bench's on-box latency artifact."""
        for ns, name, buckets, sum_ns in self._mod.fe_drain_durations():
            metrics_mod.observe_bucketed(
                metrics_mod.authconfig_duration.labels(ns, name),
                buckets, sum_ns / 1e9)
        stages = self._mod.fe_stage_hist()
        if not stages:
            return  # server already stopped (fe_stop raced this drain)
        for stage in ("wait", "exec", "respond"):
            counts = stages[stage]
            acc = self.stage_totals.setdefault(stage, [0] * len(counts))
            for i, n in enumerate(counts):
                acc[i] += n
            # the sum is exact: C++ keeps it beside the buckets
            metrics_mod.observe_bucketed(
                metrics_mod.frontend_stage_duration.labels(stage),
                counts, stages["sum_ns"][stage] / 1e9)
        self.stage_totals["bounds_ns"] = stages["bounds_ns"]

    def _dispatch_loop(self) -> None:
        mod = self._mod
        while self._running:
            kind, a, b, c, flush_ns, ovf_rows, first_ns = \
                mod.fe_wait_batch(200)
            self._fold_fc_counts()
            if kind == EV_BATCH:
                try:
                    self._dispatch(int(a), int(b), int(c),
                                   flush_ns=int(flush_ns),
                                   ovf_rows=int(ovf_rows),
                                   first_ns=int(first_ns))
                except Exception as e:
                    log.exception("native batch dispatch failed")
                    # retry once, then degrade (CPU-backend kernel) — fail
                    # closed deny only when the degraded lane fails too
                    try:
                        self._native_batch_failed(int(a), int(b), int(c), 0, e)
                    except Exception:
                        log.exception("native batch failure handling failed")
            elif kind == EV_SNAP_RETIRED:
                # GIL-atomic pop, deliberately NOT under _lock: refresh holds
                # _lock across its swap-gate jit compile, and blocking here
                # would stall every batch completion queued behind this event
                retired = self._snaps.pop(int(a), None)
                if retired is not None and retired.heat is not None:
                    # no batch of it is left in flight: fold what the keep
                    # holds of it and name what its heat map holds as
                    # arrays before the last reference goes (a cut of it
                    # kept after this is named by its own fold)
                    retired.retired = True
                    self._fold_kept()
                    retired.heat.flush()
            elif kind == EV_STOPPED:
                break

    def _bind_cache_keys(self, rec: _SnapRec,
                         cache_tokens: Optional[list]) -> None:
        """Once a snapshot: each kernel row's cache token as a u64 and each
        slot's key descriptor.  The key is the row's encoded operand bytes,
        ``shard_of`` first on a mesh corpus, then ``config_id`` and the
        operands in this order (compiler/pack.py batch_row_keys's), the
        byte lane as far as the row's longest value reaches."""
        if cache_tokens is not None:
            ids = self._cache_token_ids
            rec.tok_ids = np.fromiter(
                (ids.setdefault(t, len(ids)) for t in cache_tokens),
                dtype=np.uint64, count=len(cache_tokens))
        else:
            # the top bit keeps snapshot-wide tokens off the interned ids
            rec.tok_ids = np.full(rec.cacheable.shape[-1],
                                  (1 << 63) | rec.snap_id, dtype=np.uint64)
        order = ["config_id", "attrs_val", "members", "cpu_dense",
                 "attr_bytes", "byte_ovf"]
        if rec.sharded is not None:
            order.insert(0, "shard_of")
        # a row's byte lane is keyed as far as its longest value reaches
        # (every byte past it is zero), not as wide as the slot
        at = order.index("attr_bytes")
        rec.key_segs = [key_segments([a[k] for k in order],
                                     used={at: a["byte_used"]})
                        for a in rec.arrays]

    def _dedup_plan(self, rec: _SnapRec, slot: int, count: int,
                    rows: np.ndarray, shards_arr: Optional[np.ndarray]):
        """Cache lookup + within-batch row collapse for one C++-encoded
        slot: one native call, outside the interpreter lock
        (native/verdict_cache.cpp).  Keys are the raw encoded operand bytes
        of each row (exact: the kernel is a pure per-row function; the
        native path has no lossy host-fallback rows) under the row's token
        (``_SnapRec.tok_ids``).  Returns a ``CutPlan`` — or None when both
        features are off."""
        cache = self._verdict_cache
        if not self.batch_dedup and cache is None:
            return None
        if rec.cacheable is None:
            eligible = np.zeros((count,), dtype=bool)
        elif shards_arr is not None:
            eligible = rec.cacheable[shards_arr, rows]
        else:
            eligible = rec.cacheable[rows]
        return plan_cut(cache, rec.key_segs[slot], count, rec.tok_ids[rows],
                        eligible, self.batch_dedup)

    @staticmethod
    def _row_h2d_bytes(a: Dict[str, np.ndarray], eff: int, n_cpu: int) -> int:
        """Per-row operand bytes one single-corpus launch stages from this
        slot's arrays at byte-width ``eff`` (0 = no DFA operands) and
        ``n_cpu`` CPU columns (pure shape arithmetic — numpy basic indexing
        views, no copies): multiply by the pad bucket for the ledger's
        exact H2D count."""
        per = (a["attrs_val"][0].nbytes + a["members"][0].nbytes
               + n_cpu * a["cpu_dense"].dtype.itemsize
               + a["config_id"].dtype.itemsize)
        if eff:
            per += (a["attr_bytes"][0][..., :eff].nbytes
                    + a["byte_ovf"][0].nbytes)
        return int(per)

    def _dispatch(self, snap_id: int, slot: int, count: int,
                  attempt: int = 0, spill: bool = True,
                  flush_ns: int = 0, ovf_rows: int = 0,
                  first_ns: int = 0) -> None:
        """Launch stage: non-blocking kernel dispatch for one C++-encoded
        slot, then park the in-flight batch on the readback queue.  The
        dispatcher thread is immediately free to launch the next slot, so
        the in-flight window is the C++ slot count — batches overlap on the
        link instead of serializing per thread.

        Before the launch, cached (snap_id, row-digest) verdicts resolve
        without the device and the remaining rows collapse to UNIQUE rows
        (ISSUE 3): the H2D payload carries only unique work, and the
        readback thread fans verdicts back out through the inverse map.
        The readback itself is the bit-packed u8 bitmask (8 verdicts/
        byte), so D2H bytes shrink ~8x on the RTT-bound link too.

        ``attempt`` is the retry generation (0 = first dispatch, 1 = the
        one retry after a device failure); an OPEN circuit breaker skips
        the device entirely and decides the slot on the CPU backend.
        ``flush_ns`` is when the C++ front end cut the slot and ``first_ns``
        when the slot's first row arrived (0 on a retry): the first two
        stamps of the batch's stage clock (runtime/batch_stages.py);
        ``ovf_rows`` how many of the cut's rows carried a value past
        DFA_VALUE_BYTES, as the encoder counted them (0 on a retry: the
        ledger's ``dfa_ovf_rows`` counts a cut once)."""
        rec = self._snaps[snap_id]
        bt = self.batch_stages.begin(snap_id, slot, count, flush_ns, first_ns)
        with bt.stage("plan"):
            allowed, probe = self.breaker.admit_device()
            if not allowed:
                self._degrade_slot(rec, snap_id, slot, count)
                return
            # a claimed half-open PROBE must reach the device: routing it
            # host-side (lane choice or brownout) would strand
            # _probe_inflight forever — no breaker verdict ever lands, every
            # later slot skips the device, and a transiently-sick device
            # becomes a permanent host-only degrade.  (The engine lane turns
            # probes into speculative dual-dispatch instead; this lane has
            # no first-wins seam, so the probe simply rides the device
            # alone.)
            if (spill and not probe and self.lanes.enabled
                    and rec.sharded is None and rec.policy is not None
                    and count <= self.lanes.host_max_rows):
                # slot-level lane choice (ISSUE 12): a small gathered slot
                # the cost model says the CPU-backend twin answers FASTER
                # than a device round trip rides the host lane — light-load
                # latency stops paying the H2D/D2H trip.  Same worker-thread
                # + live-counter discipline as brownout (stop() waits these
                # out), but its own trigger and counters: this is a latency
                # choice, not an overload spill.
                which, why = self.lanes.decide(count, self._rb_inflight,
                                               self.slots)
                if which == L_HOST:
                    taken = False
                    with self._rb_lock:
                        if self.lanes.host_inflight < self.lanes.host_limit:
                            self.lanes.host_inflight += 1
                            self._brownout_live += 1
                            taken = True
                    if taken:
                        self.lanes.count(L_HOST, why)
                        self._host_pool.submit(self._brownout_slot, rec,
                                               snap_id, slot, count, why=why)
                        return
                    # a concurrent host worker filled the cap between
                    # decide() and the under-lock re-check: the slot rides
                    # the device — record THAT, or dispatched slots stop
                    # summing up
                    which, why = L_DEVICE, "host-busy"
                self.lanes.count(L_DEVICE, why)
            if (spill and not probe and self.brownout
                    and count <= self.brownout_max_rows
                    and self._rb_inflight >= self._brownout_threshold
                    and rec.sharded is None and rec.policy is not None):
                # device pipeline saturated (nearly every slot in flight)
                # and this batch is small: answer it on the CPU-backend
                # kernel instead of queueing it behind a full window — exact
                # verdicts, bounded latency (brownout, docs/robustness.md).
                # On its OWN worker thread: the first CPU eval of a new
                # (pad, eff) shape jit-compiles, and that must never stall a
                # dispatcher thread mid-saturation (mirrors _fail_async — at
                # most one live thread per C++ slot, since a slot cannot
                # re-fire until fe_complete_batch refills it).  Counted in
                # _brownout_live so stop()'s drain waits the spill out
                # before fe_stop.
                with self._rb_lock:
                    self._brownout_live += 1
                threading.Thread(target=self._brownout_slot,
                                 args=(rec, snap_id, slot, count),
                                 name="atpu-fe-brownout", daemon=True).start()
                return
            a = rec.arrays[slot]
            # copy attribution rows BEFORE the slot can complete: once
            # fe_complete_batch runs, the C++ encoder may refill them
            rows = a["config_id"][:count].copy()
            shards_arr = (a["shard_of"][:count].copy()
                          if rec.sharded is not None else None)
            fan = self._dedup_plan(rec, slot, count, rows, shards_arr)
            if fan is not None:
                unique_rows = fan.unique_rows
                u = len(unique_rows)
            else:
                unique_rows, u = None, count

        cost_lane = "native" if rec.sharded is None else "mesh"
        avoided = dict(
            dedup_avoided_rows=(len(fan.miss_rows) - u
                                if fan is not None else 0),
            cache_avoided_rows=(len(fan.cached_rows)
                                if fan is not None else 0),
            dfa_ovf_rows=ovf_rows,
            # the value bytes the C++ encoder's overflow scan was handed for
            # this cut's rows, a DFA a byte (counted once a cut, as the rows)
            dfa_host_bytes=(int(a["dfa_bytes"][:count, 1].sum())
                            if ovf_rows else 0))
        if u == 0:
            # every row cache-resolved: complete through the readback queue
            # with no device work at all
            pad = eff = 0
            packed = _Launched()
            t0 = time.monotonic()
            t0_ns = time.time_ns()
            # structural cost fold (ISSUE 16): ZERO launches, zero bytes —
            # the parity the perf_guard tests pin exactly
            LEDGER.observe(cost_lane, rows=count, **avoided)
        elif rec.sharded is not None:
            # one shard_map dispatch per micro-batch: the C++ encoder
            # already laid each request into its owning shard's [B, S, ...]
            # slice (packed bit 0 = own verdict, psum-merged over 'mp')
            sh = rec.sharded
            has_dfa = sh.has_dfa
            with bt.stage("encode"):
                eff_need = (_trim_bytes(a["attr_bytes"][:count] if u == count
                                        else a["attr_bytes"][unique_rows]
                                        ).shape[-1]
                            if has_dfa else 0)
                # round the batch/byte buckets up to an already-compiled
                # variant so XLA compiles never land on live requests (rows
                # past the unique count carry stale/repeated operands;
                # results discarded)
                pad, eff = self._pick_warm_shape(rec, u, eff_need)
                idx = None
                if u != count:
                    idx = np.full((pad,), unique_rows[0], dtype=np.int32)
                    idx[:u] = unique_rows

                def sel(name):
                    """Unique-row operand view: the slot arrays sliced
                    [:pad] when nothing collapsed (stale pad rows discarded,
                    as before), else fancy-indexed unique rows padded by
                    repeating the first (a copy — the slot refills once the
                    batch completes)."""
                    return a[name][:pad] if u == count else a[name][idx]

                t0 = time.monotonic()
                t0_ns = time.time_ns()
                if faults.ACTIVE:
                    faults.FAULTS.check("h2d", "native")
                    faults.FAULTS.check("kernel", "native")
                from ..parallel.sharded_eval import _ShardedEncoded

                operands = _ShardedEncoded(
                    attrs_val=sel("attrs_val"),
                    members_c=sel("members"),
                    cpu_dense=sel("cpu_dense").view(bool),
                    attr_bytes=np.ascontiguousarray(
                        sel("attr_bytes")[..., :eff]) if has_dfa else None,
                    byte_ovf=(sel("byte_ovf").view(bool)
                              if has_dfa else None),
                    shard_of=sel("shard_of"),
                    row_of=sel("config_id"),
                    host_fallback=np.zeros((pad,), dtype=bool))
            with bt.stage("launch"):
                # dispatch_full owns the step's operand list, the mesh
                # ledger launch (+ exact operand bytes) and the per-device
                # launch counts
                packed = sh.dispatch_full(operands)
                if faults.ACTIVE:
                    packed = faults.FAULTS.wrap_handle(packed, "native")
                try:
                    packed.copy_to_host_async()
                except Exception:
                    pass
                # sharded slots fold the batch here; their collective launch
                # and its bytes were counted on the mesh lane by
                # dispatch_full.  eff-column slack is the warm-shape
                # round-up (eff - eff_need)
                LEDGER.observe("mesh", rows=count, device_rows=u,
                               pad_rows=pad, eff_slack_cols=eff - eff_need,
                               **avoided)
        else:
            t0 = time.monotonic()
            t0_ns = time.time_ns()
            if faults.ACTIVE:
                faults.FAULTS.check("h2d", "native")
                faults.FAULTS.check("kernel", "native")
            packed, pad, eff = self._launch_classes(
                rec, a, bt, count, rows, unique_rows if u != count else None,
                avoided)
        with self._rb_lock:
            self._rb_inflight += 1
            if self._rb_inflight > self.rb_inflight_peak:
                self.rb_inflight_peak = self._rb_inflight
            inflight = self._rb_inflight
        self._g_native_inflight.set(inflight)
        bt.device_rows, bt.pad, bt.eff, bt.inflight = u, pad, eff, inflight
        self._rb_q.append((rec, snap_id, slot, count, pad, eff, rows,
                           shards_arr, packed, t0, t0_ns, fan, attempt, bt))
        self._rb_evt.set()

    def _launch_classes(self, rec: _SnapRec, a: Dict[str, np.ndarray], bt,
                        count: int, rows: np.ndarray,
                        unique_rows: Optional[np.ndarray],
                        avoided: Dict[str, int]):
        """The device launches of one single-corpus cut: its rows to launch
        (all ``count``, or ``unique_rows`` after dedup and cache) split by
        their config's size class, ONE launch a class present, each the
        class's operands (``rec.views``) over a staging buffer of the
        class's rows at the class's widths.  A cut of one class is one
        launch of every row, views and no copy, as a corpus of one class
        always is.  `encode` and `launch` run once a launch; the ledger
        folds the cut once, in its last launch.  Returns (the launches, the
        pad rows launched in all, the widest byte bucket)."""
        import jax.numpy as jnp

        from ..ops.pattern_eval import eval_bitpacked_staged_jit, fuse_bytes

        cfg = rows if unique_rows is None else rows[unique_rows]
        u = cfg.shape[0]
        cls = rec.class_of[cfg]
        present = []
        for c in range(len(rec.views)):
            mine = cls == c
            n = int(np.count_nonzero(mine))
            if n:
                present.append((c, n, mine))
        out = _Launched()
        tally = dict(h2d_transfers=0, h2d_bytes=0, d2h_bytes=0, pad_rows=0,
                     eff_slack_cols=0, own_dfa_slots=0, eff_cols=0)
        eff_max = 0
        for c, n, mine in present:
            with bt.stage("encode"):
                if n == u:
                    at, src = None, unique_rows   # the whole selection
                else:
                    at = np.nonzero(mine)[0]
                    src = at if unique_rows is None else unique_rows[at]
                width = rec.classes[c]
                n_dfa = width["dfa_rows_per_row"]
                # the byte bucket of the launch's own rows: the longest
                # value the encoder wrote into any of them (what trimming
                # the all-zero columns of their attr_bytes gives), never
                # past the class's width, past which a value overflowed
                eff_need = 0
                if n_dfa:
                    used = a["byte_used"][slice(count) if src is None else src]
                    eff_need = self._byte_bucket(int(used.max()),
                                                 width["device_width"])
                # round the batch/byte buckets up to an already-compiled
                # variant so XLA compiles never land on live requests (rows
                # past the launch's carry stale/repeated operands; results
                # discarded); a bucket past the class's width runs at the
                # width (0 where the class has no DFA row: _warm_one)
                pad, eff = self._pick_warm_shape(rec, n, eff_need)
                eff = min(eff, width["device_width"])
                if src is None:
                    idx = slice(pad)
                else:
                    idx = np.full((pad,), src[0], dtype=np.int32)
                    idx[:n] = src
                # the operands as ONE staged buffer (the served entry
                # decodes them on the device), handed to the runtime here,
                # in one transfer, so that `launch` times the jitted call
                # alone
                layout = self._stage_layout(rec, c, pad, eff)
                staged = jnp.asarray(fuse_bytes(self._operand_views(
                    a, idx, eff, width["cpu_cols"])))
            with bt.stage("launch"):
                packed = eval_bitpacked_staged_jit(rec.views[c], staged, layout)
                if faults.ACTIVE:
                    packed = faults.FAULTS.wrap_handle(packed, "native")
                try:
                    packed.copy_to_host_async()
                except Exception:
                    pass
                out.parts.append((packed, at, n, width["evaluators"]))
                # structural cost (ISSUE 16): the exact H2D operand bytes
                # this (class, pad, eff) variant staged and the bitpacked
                # [pad, W] readback; eff-column slack is the warm-shape
                # round-up (eff - eff_need); what the launch scanned: pad
                # rows x the class's D
                tally["h2d_transfers"] += 1
                tally["h2d_bytes"] += pad * self._row_h2d_bytes(
                    a, eff, width["cpu_cols"])
                tally["d2h_bytes"] += int(packed.shape[0]) * int(packed.shape[1])
                tally["pad_rows"] += pad
                tally["eff_slack_cols"] += eff - eff_need
                tally["eff_cols"] += eff
                tally["own_dfa_slots"] += pad * n_dfa
                eff_max = max(eff_max, eff)
                if c == present[-1][0]:
                    LEDGER.observe(
                        "native", rows=count, device_rows=u,
                        launches=len(present),
                        own_dfa_rows=int(rec.cfg_dfa_n[cfg].sum()),
                        # value bytes the launched rows' DFAs read here, a
                        # DFA a byte (the encoder counted them a row)
                        dfa_dev_bytes=int(a["dfa_bytes"][
                            slice(count) if unique_rows is None
                            else unique_rows, 0].sum()),
                        **tally, **avoided)
        return out, tally["pad_rows"], eff_max

    def _brownout_slot(self, rec: _SnapRec, snap_id: int, slot: int,
                       count: int, why: str = "brownout") -> None:
        """Answer one small slot on the CPU-backend kernel (worker thread —
        see _dispatch).  Two distinct triggers share this execution path:
        ``why="brownout"`` = the device window is saturated (overload
        spill, PR 7 counters); any other ``why`` = the ISSUE 12 cost model
        simply chose the host lane as FASTER (counted in
        auth_server_lane_decisions_total instead).  If the host eval
        itself fails, the slot falls back to a normal device dispatch
        (spill=False so it cannot loop back here).  Exactness: same
        kernel, same encoded operands — only the execution backend
        differs."""
        lane_sel = why != "brownout"
        try:
            t0 = time.monotonic()
            t0_ns = time.time_ns()
            rows = rec.arrays[slot]["config_id"][:count].copy()
            try:
                verdict, firing = self._host_eval(rec, slot, count)
            except Exception:
                log.exception("native host-lane eval failed; batch rides "
                              "the device instead")
                try:
                    self._dispatch(snap_id, slot, count, spill=False)
                except Exception as e:
                    log.exception("post-host-lane device dispatch failed")
                    try:
                        self._native_batch_failed(snap_id, slot, count, 0, e)
                    except Exception:
                        log.exception("native batch failure handling failed")
                return
            dur = time.monotonic() - t0
            self.lanes.cost.observe_host(dur, count)
            # kernel-cost ledger (ISSUE 16): a host-lane batch performs
            # ZERO device launches and moves zero device bytes — exactly
            LEDGER.observe("host", rows=count)
            if lane_sel:
                self.lanes.count_rows(L_HOST, count)
            else:
                metrics_mod.brownout_decisions.labels("native").inc(count)
                metrics_mod.brownout_batches.labels("native").inc()
                self._brownout_total += count
                self._brownout_batches += 1
            if not self._fe_stopped:
                self._mod.fe_complete_batch(snap_id, slot, verdict.ctypes.data)
            try:
                # pad/eff 0 + device_rows 0: per-authconfig counters stay
                # exact, while the device-occupancy series never sees a
                # batch that deliberately skipped the device
                self._post_complete_telemetry(rec, count, 0, 0, rows, None,
                                              verdict, dur, t0_ns,
                                              device_rows=0, device=False,
                                              firing=firing)
            except Exception:
                log.exception("host-lane telemetry failed")
        finally:
            with self._rb_lock:
                self._brownout_live -= 1
                if lane_sel:
                    self.lanes.host_inflight -= 1

    def _readback_loop(self) -> None:
        """Completion stage: finalize in-flight batches as their readbacks
        arrive (is_ready polling — a slow batch never convoys a fast one),
        completing each into C++ and folding its telemetry."""
        pending: List[tuple] = []
        while True:
            while self._rb_q:
                try:
                    pending.append(self._rb_q.popleft())
                except IndexError:
                    break
            if not pending:
                if not self._running:
                    return
                # nothing in flight: whatever a failed or abandoned cut left
                # in the keep folds now
                self._fold_kept_if(0.0)
                self._rb_evt.wait(0.2)
                self._rb_evt.clear()
                continue
            progressed = False
            for item in list(pending):
                is_ready = getattr(item[8], "is_ready", None)
                try:
                    ready = is_ready is None or bool(is_ready())
                except Exception:
                    ready = True  # surface the real error in completion
                if not ready:
                    t = self.device_timeout_s
                    if t and time.monotonic() - item[9] > t:
                        # watchdog: readback wedged past --device-timeout —
                        # abandon the handle, count a breaker failure, and
                        # feed the slot the retry/degrade path
                        pending.remove(item)
                        progressed = True
                        metrics_mod.watchdog_timeouts.labels("native").inc()
                        RECORDER.record("watchdog-timeout", lane="native",
                                        detail={"slot": item[2],
                                                "requests": item[3],
                                                "attempt": item[12]})
                        log.warning(
                            "native batch (slot %d, %d requests, attempt %d)"
                            " wedged past --device-timeout %.3fs",
                            item[2], item[3], item[12], t)
                        try:
                            self._fail_async(
                                item[1], item[2], item[3], item[12],
                                TimeoutError("device readback watchdog "
                                             "timeout"))
                        except Exception:
                            log.exception("native watchdog handling failed")
                        finally:
                            with self._rb_lock:
                                self._rb_inflight -= 1
                                inflight = self._rb_inflight
                            self._g_native_inflight.set(inflight)
                    continue
                pending.remove(item)
                progressed = True
                item[13].ready()
                try:
                    self._complete_device_batch(*item)
                except Exception as e:
                    log.exception("native batch completion failed")
                    try:
                        # retry once, then degrade on the CPU backend (deny
                        # fail-closed only when that fails too; never into
                        # a stopped server — see _complete_device_batch)
                        self._fail_async(item[1], item[2], item[3],
                                         item[12], e)
                    except Exception:
                        log.exception("native batch failure handling failed")
                finally:
                    with self._rb_lock:
                        self._rb_inflight -= 1
                        inflight = self._rb_inflight
                    self._g_native_inflight.set(inflight)
            if not progressed:
                # a trickle of traffic must not hold a kept cut back
                self._fold_kept_if(KEEP_AGE_S)
                # sub-ms poll while results ride the link (noise vs RTT)
                self._rb_evt.wait(0.0005)
                self._rb_evt.clear()

    def _complete_device_batch(self, rec: _SnapRec, snap_id: int, slot: int,
                               count: int, pad: int, eff: int,
                               rows: np.ndarray,
                               shards_arr: Optional[np.ndarray],
                               packed, t0: float, t0_ns: int,
                               fan, attempt: int, bt) -> None:
        if self._fe_stopped:
            # stop()'s drain deadline expired with this batch still on the
            # wire and fe_stop has run: completing into the torn-down C++
            # server would be a native use-after-stop
            return
        with bt.stage("resolve"):
            if faults.ACTIVE:
                faults.FAULTS.check("readback", "native")
            launched = packed if isinstance(packed, _Launched) else None
            if launched is None:
                packed = np.asarray(packed)
            else:
                parts = [(np.asarray(h), at, n, e)
                         for h, at, n, e in launched.parts]
            if pad:
                # the device answered (cache-only batches with pad == 0
                # never touched it): clear the breaker's consecutive-failure
                # count
                self.breaker.record_success()
            else:
                # a cache-only batch proves nothing about the device — just
                # release a half-open probe slot it may have claimed
                self.breaker.release_probe()
            dispatch_s = time.monotonic() - t0
            # attribution (ISSUE 9): the packed readback already carries the
            # per-rule result/skip columns, so the firing column comes back
            # beside the verdict bit with no per-request Python (pinned by
            # tests/test_provenance.py)
            heat = rec.heat
            E = heat.E if heat is not None else 0
            u = count if fan is None else len(fan.unique_rows)
            cached_n = len(fan.cached_rows) if fan is not None else 0
            elig_miss_n = fan.eligible_misses if fan is not None else 0
            if launched is not None:
                # a single corpus's launches (none: the cache answered the
                # cut): ONE native call outside the interpreter lock decodes
                # them, fans them out through the plan, completes the slot
                # and commits the plan's ticket (native/verdict_cache.cpp
                # resolve; `resolve_cut` is its reference).  It checks every
                # input before it touches the slot
                verdict = np.empty((count,), dtype=np.uint8)
                firing = np.empty((count,), dtype=np.int32) if E else None
                evict_d = self._mod.fe_resolve_cut(
                    parts, fan, count, snap_id, slot, verdict, firing)
            else:
                # the mesh step's one result: own verdict = bit 0 of byte 0
                verdict, firing = resolve_cut([(packed, None, u, E)], fan,
                                              count, bool(E))
                self._mod.fe_complete_batch(snap_id, slot, verdict.ctypes.data)
                evict_d = 0
        # the slot is COMPLETED from here on: an exception below must not
        # propagate to the readback loop's fail-closed deny, which would
        # fe_complete_batch the same slot twice — by then possibly refilled
        # with a fresh live batch
        try:
            with bt.stage("post"):
                cache = self._verdict_cache
                if launched is not None:
                    LEDGER.observe_resolved(
                        "native" if rec.sharded is None else "mesh")
                elif fan is not None and cache is not None and u:
                    # the mesh step's commit (the call above made the
                    # others'): unique rows are freshly evaluated, the
                    # cacheable ones go in at once, under the keys (token
                    # and row bytes) the ticket copied at plan time: the
                    # slot may have been refilled since
                    evict_d = cache.commit(fan.ticket, verdict, firing)
                # the commit has a reader waiting (a later cut's plan); the
                # rest of the cut's telemetry has none inside the cut
                self._post_complete_telemetry(
                    rec, count, pad, eff, rows, shards_arr, verdict,
                    dispatch_s, t0_ns, device_rows=u, firing=firing,
                    dedup=(u, cached_n, elig_miss_n, evict_d))
        except Exception:
            log.exception("post-completion telemetry failed")

    def _fail_async(self, snap_id: int, slot: int, count: int,
                    attempt: int, exc: Exception) -> None:
        """Hand a failed batch to its own worker thread: the retry dispatch
        and the CPU-backend degrade (whose first use jit-compiles) must not
        stall the single readback thread that completes every other
        in-flight batch.  Bounded: at most one live thread per C++ slot —
        a slot cannot fail again until fe_complete_batch refills it."""
        threading.Thread(target=self._native_batch_failed,
                         args=(snap_id, slot, count, attempt, exc),
                         name="atpu-fe-degrade", daemon=True).start()

    def _native_batch_failed(self, snap_id: int, slot: int, count: int,
                             attempt: int, exc: Exception) -> None:
        """One device batch failed (launch, readback, or watchdog): count a
        breaker failure, retry ONCE on a fresh dispatch from the same slot
        (the C++ operands are intact until fe_complete_batch), then decide
        the slot on the degraded lane — the native mirror of the engine's
        _batch_failed."""
        self.breaker.record_failure()
        rec = self._snaps.get(snap_id)
        if attempt == 0 and rec is not None:
            metrics_mod.batch_retries.labels("native").inc()
            log.warning("native batch (slot %d, %d requests) failed (%r): "
                        "retrying once on a fresh dispatch", slot, count, exc)
            try:
                self._dispatch(snap_id, slot, count, attempt=1)
                return
            except Exception as e2:
                log.exception("native batch retry dispatch failed")
                self.breaker.record_failure()
                exc = e2
        self._degrade_slot(rec, snap_id, slot, count, exc=exc)

    def _degrade_slot(self, rec: Optional[_SnapRec], snap_id: int, slot: int,
                      count: int, exc: Optional[Exception] = None) -> None:
        """Degraded lane: evaluate the slot's already-encoded operands with
        the SAME kernel on the CPU backend (exactness preserved — the
        kernel is a pure per-row function; only the execution device
        changes).  Deny fail-closed ONLY when the degraded evaluation
        itself is impossible (no CPU backend, mesh-sharded corpus, retired
        snapshot)."""
        if rec is None:
            # the C++ side already retired this snapshot (EV_SNAP_RETIRED
            # raced the failure): its slots are gone — completing into it
            # would be a native use-after-retire, so drop the batch
            log.warning("native batch failure for retired snapshot %d "
                        "(slot %d, %d requests): dropped", snap_id, slot,
                        count)
            return
        verdict: Optional[np.ndarray] = None
        firing: Optional[np.ndarray] = None
        rows: Optional[np.ndarray] = None
        if rec.sharded is None and rec.policy is not None:
            try:
                # attribution rows copied BEFORE completion: the C++
                # encoder may refill the slot once fe_complete_batch runs
                rows = rec.arrays[slot]["config_id"][:count].copy()
                t0 = time.monotonic()
                verdict, firing = self._host_eval(rec, slot, count)
                # degraded host evals teach the cost model too (ISSUE 12):
                # a frontend that spent its warm-up degrading must not
                # enter lane selection on the cold-start estimate
                self.lanes.cost.observe_host(time.monotonic() - t0, count)
                # kernel-cost ledger (ISSUE 16): degrade = host lane, zero
                # device launches
                LEDGER.observe("host", rows=count)
            except Exception:
                log.exception("native host degrade failed (fail-closed deny)")
        if verdict is not None:
            metrics_mod.degraded_decisions.labels("native").inc(count)
            if exc is not None:
                log.warning("native batch (slot %d, %d requests) decided on "
                            "the CPU backend after device failure (%r)",
                            slot, count, exc)
        else:
            verdict = np.zeros(count, dtype=np.uint8)
        if not self._fe_stopped:
            self._mod.fe_complete_batch(snap_id, slot, verdict.ctypes.data)
        if firing is not None and rows is not None and rec.heat is not None:
            try:
                # degraded decisions attribute like the device decisions
                # they replaced (same kernel, CPU backend) — heat fold +
                # head sample only; the per-authconfig counters keep their
                # established healthy-path-only semantics
                prov_mod.fold_and_sample(rec.heat, rows, firing, count,
                                         lane="native",
                                         generation=rec.snap_id)
                # tenant parity (ISSUE 15 satellite): degraded slots used
                # to bypass per-tenant accounting entirely — a contained
                # or degraded tenant's traffic must still burn ITS
                # requests/denies, not vanish from the tenant plane
                ten = self.tenancy
                if ten is not None and ten.enabled:
                    ten.fold(rec.heat, rows, firing=firing, lane="native")
            except Exception:
                log.exception("degrade provenance fold failed")

    def _host_eval(self, rec: _SnapRec, slot: int,
                   count: int) -> Tuple[np.ndarray, Optional[np.ndarray]]:
        """CPU-backend kernel evaluation of one C++-encoded slot → (own
        verdicts [count] uint8, firing columns [count] int32 or None) —
        the SAME packed columns the device returns, so degraded/brownout
        decisions attribute identically.  The host operand pytree is built
        lazily once per snapshot; each (pad, eff) shape compiles on first
        use — a degraded-mode cost, never on the healthy path."""
        import jax
        import jax.numpy as jnp

        from ..ops.pattern_eval import eval_bitpacked_jit, unpack_attribution

        a = rec.arrays[slot]
        cpu = self._host_twin(rec)
        has_dfa = rec.policy.n_byte_attrs > 0
        pad = min(bucket_pow2(count), self.max_batch)
        eff = (_trim_bytes(a["attr_bytes"][:count]).shape[-1]
               if has_dfa else 0)
        # round up into an already-warmed CPU variant (ISSUE 12 satellite:
        # the pre-warm thread compiles the small shapes at snapshot swap,
        # so a live host-lane slot pays no inline XLA compile; rows past
        # the count carry stale operands and their results are discarded —
        # the same discipline as the device lane's _pick_warm_shape)
        if (pad, eff) not in rec.host_warm:
            best = None
            for p, e in tuple(rec.host_warm):
                if p >= count and e >= eff and (best is None or (p, e) < best):
                    best = (p, e)
            if best is not None:
                pad, eff = best
        cpu = jax.devices("cpu")[0]
        with jax.default_device(cpu):
            packed = eval_bitpacked_jit(
                rec.host_params,
                *(jnp.asarray(v) for v in self._operand_views(
                    a, slice(pad), eff, rec.policy.n_own_cpu)))
            out = np.asarray(packed)
        rec.host_warm.add((pad, eff))  # compiled now, warm from here on
        E = rec.heat.E if rec.heat is not None else 0
        if E:
            verdict, firing = unpack_attribution(out[:count], E)
            return np.ascontiguousarray(verdict), firing
        return (np.ascontiguousarray(out[:count, 0] & 1).astype(np.uint8),
                None)

    def _post_complete_telemetry(self, rec: _SnapRec, count: int, pad: int,
                                 eff: int, rows: np.ndarray,
                                 shards_arr: Optional[np.ndarray],
                                 verdict: np.ndarray, dispatch_s: float,
                                 t0_ns: int,
                                 device_rows: Optional[int] = None,
                                 device: bool = True,
                                 firing: Optional[np.ndarray] = None,
                                 dedup: Optional[tuple] = None) -> None:
        """Per-batch telemetry AFTER completion: responses are already on
        their way to the wire (queue wait is C++-clocked — stage hists), and
        nothing it feeds has a reader inside the cut, so the cut is KEPT
        (one append) and `_fold_kept` folds the kept cuts together: when
        this one fills the keep to KEEP_CUTS, when it leaves no cut in
        flight (light load: every cut folds at once), or when the oldest
        kept cut is older than KEEP_AGE_S; a drain folds the rest.
        ``device=False`` (host lane, brownout spill: their workers call
        this too) keeps the per-authconfig counters but stays out of the
        device-lane batch/RTT series — a sub-ms host eval must not read as
        a fast device round trip — and is not among the cuts in flight."""
        now = time.monotonic()
        inflight = self._rb_inflight
        with self._post_counts_lock:
            keep = self._keep
            keep.append(_KeptCut(rec, rows, verdict, firing, shards_arr,
                                 count, pad, eff, device_rows, dispatch_s,
                                 t0_ns, device, inflight, dedup, now))
            # the readback thread's own cut still counts as in flight
            due = (len(keep) >= KEEP_CUTS or inflight <= int(device)
                   or now - keep[0].kept_at > KEEP_AGE_S)
        if due:
            self._fold_kept()

    def _fold_kept_if(self, age_s: float) -> None:
        """The readback loop, with nothing ready: fold the keep if its oldest
        cut is older than ``age_s``."""
        keep = self._keep
        try:
            if keep and time.monotonic() - keep[0].kept_at >= age_s:
                self._fold_kept()
        except IndexError:
            pass  # another thread's fold took the keep in between

    def _fold_kept(self) -> None:
        """Fold the telemetry of every kept cut, at once: the arrays of the
        cuts of one snapshot concatenated and grouped by config row by ONE
        sort, which the heat map's sampling gate, the tenant plane and the
        per-AuthConfig request counters share; the scalars a cut in a plain
        loop.  Whoever runs it (the thread that kept the cut that was due,
        the readback loop's poll, a drain, a snapshot's retirement) holds
        `_fold_lock` from taking the keep to the last add: a reader that
        folds first finds every cut kept before its call in the arrays.
        Every count is a sum and does not depend on how cuts were grouped.
        Never raises: a cut's answers left long ago."""
        with self._fold_lock:
            with self._post_counts_lock:
                kept, self._keep = self._keep, []
            if not kept:
                return
            LEDGER.observe_telemetry_fold("native")
            by_snapshot: Dict[tuple, List[_KeptCut]] = {}
            for cut in kept:
                if cut.rec.heat is not None and cut.count:
                    by_snapshot.setdefault(
                        (id(cut.rec), cut.firing is None, cut.shards is None),
                        []).append(cut)
            sampled = 0
            for cuts in by_snapshot.values():
                try:
                    sampled += self._fold_cut_arrays(cuts)
                    if cuts[0].rec.retired:
                        cuts[0].rec.heat.flush()
                except Exception:
                    log.exception("kept cuts not folded (telemetry only)")
            for cut in kept:
                try:
                    self._fold_cut_scalars(cut)
                except Exception:
                    log.exception("batch telemetry failed (one cut's series)")
            with self._post_counts_lock:
                self._sampled_decisions += sampled
                self._folds += 1
                self._folded_cuts += len(kept)
                self._keep_max = max(self._keep_max, len(kept))

    def _fold_cut_arrays(self, cuts: List[_KeptCut]) -> int:
        """The array side of one snapshot's kept cuts (all with or all
        without ``firing`` and ``shards``); returns the decision records
        made.  What can fail on a malformed cut runs before the first add:
        then the cuts fold one by one, and only that cut's counts are lost."""
        rec = cuts[0].rec
        heat = rec.heat
        try:
            def joined(field):
                parts = [getattr(cut, field) for cut in cuts]
                if parts[0] is None:
                    return None
                return parts[0] if len(parts) == 1 else np.concatenate(parts)

            rows, verdict = joined("rows"), joined("verdict")
            firing, shards = joined("firing"), joined("shards")
            counts = [cut.count for cut in cuts]
            for column in (rows, verdict, firing, shards):
                if column is not None and column.shape != (sum(counts),):
                    raise ValueError(
                        f"kept cut's arrays are {column.shape}, its rows "
                        f"{sum(counts)}")
            groups = heat.group(rows, shards)
            # which-rule-fired attribution (ISSUE 9) marks the denials;
            # without it the verdict does
            den = groups.per_row(firing >= 0 if firing is not None
                                 else verdict == 0)
            # the SLO bad mask keeps the lane's established SLI: the cut's
            # on-box round trip, shared by every member of the cut
            slo_s = self.slo.slo_s if self.slo is not None else 0.0
            bad = None
            if slo_s:
                late = np.array([cut.dispatch_s > slo_s for cut in cuts])
                bad = (groups.per_row(np.repeat(late, counts)) if late.any()
                       else np.zeros(groups.uniq.size, dtype=np.int64))
        except Exception:
            if len(cuts) == 1:
                log.exception("kept cut not folded (its telemetry is lost)")
                return 0
            return sum(self._fold_cut_arrays([cut]) for cut in cuts)
        sampled = 0
        if firing is not None:
            # one composite-key add into the rule heat map, and at most one
            # head-sampled decision record a tenant a fold, each with its
            # own cut's latency — never per-request Python
            ends = np.cumsum(counts).tolist()
            try:
                sampled = prov_mod.fold_and_sample(
                    heat, rows, firing, rows.size, lane="native",
                    shards=shards, generation=rec.snap_id, groups=groups,
                    latency_of=lambda i: cuts[bisect_right(
                        ends, i)].dispatch_s * 1e3)
            except Exception:
                log.exception("provenance fold failed (telemetry only)")
        # tenant axis (ISSUE 15): every completed slot — device, lane-
        # selected host AND brownout spill alike — folds per-tenant
        # requests/denies/SLO into the shared plane, so fast-lane traffic
        # is never invisible to the noisy-neighbor detector or the
        # per-tenant burn trackers.  No waits: the native lane's per-request
        # queue waits are C++-clocked — feeding the batch ROUND TRIP as a
        # "queue wait" would latch every tenant overloaded on normal device
        # latency.
        ten = self.tenancy
        if ten is not None and ten.enabled:
            try:
                ten.fold_grouped(heat, groups.uniq, groups.counts, den, bad,
                                 lane="native")
            except Exception:
                log.exception("tenant fold failed (telemetry only)")
            # change safety (ISSUE 10): during an engine canary the native
            # fast lane serves the BASELINE (its C++ snapshot only
            # rebuilds on promotion — swap listeners are deferred), so its
            # attribution strengthens the guard's baseline cohort
            if getattr(self.engine, "_canary", None) is not None:
                self.engine.canary_observe_external(rows, firing, heat,
                                                    shards=shards)
        # per-authconfig request metrics, same counters + labels the
        # pipeline bumps (ref pkg/service/auth_pipeline.go:26-36), into the
        # heat map's arrays: the drain names them
        try:
            heat.fold_requests(rows, verdict, groups=groups)
        except Exception:
            log.exception("request counters not folded (telemetry only)")
        return sampled

    def _fold_cut_scalars(self, cut: _KeptCut) -> None:
        """The series that take one sample a cut."""
        count = cut.count
        if cut.dedup is not None:
            metrics_mod.observe_dedup("native", count, *cut.dedup)
        if self.slo is not None and count:
            # the native SLI is the batch's on-box round trip (per-request
            # waits are C++-clocked): every member shares the batch verdict
            n_bad = count if cut.dispatch_s > self.slo.slo_s else 0
            self.slo.observe(count, n_bad)
            # per-lane burn bias feed (ISSUE 12): selection leans toward
            # the lane that is not burning budget
            self.lanes.cost.observe_slo(L_DEVICE if cut.device else L_HOST,
                                        count, n_bad)
        if not cut.device:
            return
        if cut.device_rows is None or cut.device_rows > 0:
            # lane-selection cost model: every device completion feeds
            # the RTT/occupancy EWMAs the next slot decision compares
            # against (cache-only batches skip it — they never touched
            # the link, and their sub-ms turnaround would read as a
            # fast device)
            self.lanes.cost.observe_device(cut.dispatch_s, count, 0,
                                           cut.inflight, self.slots)
        self.lanes.count_rows(L_DEVICE, count)
        metrics_mod.observe_batch("native", count, cut.pad, None,
                                  cut.dispatch_s, device_rows=cut.device_rows)
        if tracing_mod.tracing_active():
            # fast-lane requests have no Python spans to link (only sampled
            # slow-lane ones do) — the DeviceBatch span still carries the
            # launch's batch_size/pad/eff for pad-waste attribution
            tracing_mod.export_device_batch_span(count, cut.pad, cut.eff, [],
                                                 cut.t0_ns, cut.dispatch_s)

    # ------------------------------------------------------------------
    def _completer_loop(self) -> None:
        """Drain buffered slow-lane responses into C++ in batches: two lock
        rounds + at most one epoll wake per batch instead of per response.
        Runs until stop() AND the buffer is flushed (stop()'s drain loop
        waits for slow_pending to clear, which needs these flushes)."""
        mod = self._mod
        buf = self._done_buf
        evt = self._done_evt
        while True:
            if not buf:
                # only sleep when the buffer is empty: a burst past the
                # batch cap must flush immediately, not after the timeout
                evt.wait(0.2)
                evt.clear()
            items = []
            while buf and len(items) < 1024:
                try:
                    items.append(buf.popleft())
                except IndexError:
                    break
            if items:
                try:
                    mod.fe_complete_slow_many(items)
                except Exception:
                    log.exception("batch completion failed")
            elif not self._running:
                return

    # ------------------------------------------------------------------
    def _slow_loop(self) -> None:
        import asyncio

        from .. import protos
        from ..service.grpc_server import (
            check_response_from_result,
            request_model_from_proto,
        )

        mod = self._mod
        engine = self.engine
        external_auth_pb2 = protos.external_auth_pb2

        from ..utils.tracing import RequestSpan

        done_buf = self._done_buf
        done_evt = self._done_evt

        def complete(req_id: int, payload: bytes, status: int) -> None:
            done_buf.append((req_id, payload, status))
            done_evt.set()

        from ..utils.rpc import RESOURCE_EXHAUSTED

        overload_bytes = check_response_from_result(AuthResult(
            code=RESOURCE_EXHAUSTED,
            message="server overloaded: slow lane shedding",
        )).SerializeToString()

        async def handle(req_id: int, raw: bytes) -> None:
            # CoDel admission (ISSUE 7): while the slow lane's estimated
            # standing wait has stayed above target for a full interval,
            # paced arrivals are answered typed RESOURCE_EXHAUSTED before
            # any parse/pipeline work — the C++ slow_cap bounds the queue,
            # this bounds the WAIT of what the queue holds
            if self.admission.drop_now():
                self.admission.count_reject("overload")
                complete(req_id, overload_bytes, 0)
                return
            try:
                req = external_auth_pb2.CheckRequest.FromString(raw)
                model = request_model_from_proto(req)
                if model is None:
                    result = AuthResult(code=INVALID_ARGUMENT, message="Invalid request")
                else:
                    # same flow as engine.check (host lookup + pipeline),
                    # inlined so the pipeline object is reachable for
                    # verified-token registration; same span lifecycle as
                    # the Python gRPC server (service/grpc_server.py check)
                    span = RequestSpan.from_headers(model.http.headers, model.http.id)
                    try:
                        entry = engine.lookup(model.host())
                        if entry is None:
                            result = AuthResult(code=NOT_FOUND,
                                                message="Service not found")
                        else:
                            # snapshot BEFORE verification: registration is
                            # dropped when a JWKS rotation swaps it mid-run
                            rec = self._cur_rec
                            pipeline = AuthPipeline(model, entry.runtime,
                                                    timeout=engine.timeout_s,
                                                    span=span)
                            result = await pipeline.evaluate()
                            # register BEFORE completing: once the client
                            # sees this response, a repeat of the same
                            # token must already be servable fast
                            self._register_dyn(rec, entry, pipeline, model)
                    finally:
                        span.end()
                complete(req_id,
                         check_response_from_result(result).SerializeToString(), 0)
            except Exception:
                log.exception("slow-lane request failed")
                complete(req_id, b"", 13)  # INTERNAL

        async def main() -> None:
            # continuous admission, NOT batch-gather convoys: a straggler
            # (an OIDC discovery fetch, a slow metadata backend) must not
            # block unrelated requests queued behind it — each completion
            # frees an admission slot immediately (the asyncio analog of the
            # reference's per-request goroutines, ref main.go:437-488)
            loop = asyncio.get_running_loop()
            # deep enough to hide the device link RTT under the slow lane's
            # own micro-batches (in-flight ≈ throughput × RTT)
            sem = asyncio.Semaphore(2048)
            # strong refs: asyncio holds tasks weakly — an unreferenced
            # task can be garbage-collected mid-execution
            tasks: set = set()

            def _done(t):
                tasks.discard(t)
                sem.release()

            while self._running:
                batch = await loop.run_in_executor(None, mod.fe_take_slow, 200, 256)
                for i, raw in batch:
                    await sem.acquire()
                    t = loop.create_task(handle(i, raw))
                    tasks.add(t)
                    t.add_done_callback(_done)
            # drain in-flight work before the loop closes: every request
            # taken from the C++ queue MUST complete (asyncio.run would
            # otherwise cancel these tasks and their clients would hang
            # until their gRPC deadlines)
            if tasks:
                await asyncio.gather(*tuple(tasks), return_exceptions=True)

        asyncio.run(main())
