"""The host's overflow scan (ISSUE 37; native/frontend.cpp `scan_overflow`):
a value past its config's size class's byte width (64, 128 or 256: ISSUE 38)
on a regex attribute has every DFA of the row's
config that reads the attribute advanced abreast, one byte at a time, and a
DFA leaves the pass in a state that absorbs.

Held here through the served front end over gRPC, on the CPU: for configs of
1, 2, 17 and 130 DFAs on one attribute (anchored routes, unanchored
substrings, anchored suffixes, end-anchored patterns and byte counts mixed), values of 65,
96, 300 and 4,096 bytes of six kinds, the value of a missing attribute and a
constant that overflows, every DFA's `cpu_dense` verdict equals Python's
`re` on the same value and the verdict of the same value cut to fit the
device's byte tensor, which the kernel scans; the state flags say "absorbs"
of exactly the states whose 256 transitions return to them, on the
benchmark's own generators; and the two counts beside the loop clock read
loads a DFA well under the value's length on a route corpus and equal to it
where no DFA absorbs."""

from __future__ import annotations

import asyncio
import os
import re
import random
import sys

import grpc
import numpy as np
import pytest

from authorino_tpu.compiler import ConfigRules, compile_corpus
from authorino_tpu.compiler import compile as cc
from authorino_tpu.compiler.compile import DFA_VALUE_BYTES
from authorino_tpu.compiler.redfa import compile_regex_dfa
from authorino_tpu.controllers.translate import translate_auth_config
from authorino_tpu.evaluators import (AuthorizationConfig, IdentityConfig,
                                      RuntimeAuthConfig)
from authorino_tpu.evaluators.authorization import PatternMatching
from authorino_tpu.evaluators.credentials import AuthCredentials
from authorino_tpu.evaluators.identity import APIKey, Noop
from authorino_tpu.expressions import Operator, Pattern
from authorino_tpu.k8s.client import LabelSelector, Secret
from authorino_tpu.runtime import EngineEntry, PolicyEngine
from authorino_tpu.runtime.native_frontend import (NativeFrontend,
                                                   fast_lane_eligible)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.join(ROOT, "benchmark"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from corpora import route_rules, tenant_rules  # noqa: E402

from test_front_clock import check_stub  # noqa: E402
from test_native_frontend import _native_available, make_req  # noqa: E402

needs_native = pytest.mark.skipif(
    not _native_available(), reason="native frontend unavailable")

COUNTS = (1, 2, 17, 130)
# either side of each width a size class can take (64, 128, 256), and far past
LENGTHS = (65, 96, 129, 257, 300, 4096)
FITS = 48  # the same value cut to fit the device's byte tensor
OK, DENIED = 0, 7
HEAD = "/api/v1/t0/"


def patterns(n):
    """n regexes on one attribute, five families in turn: an anchored route
    (dies where the route differs), an unanchored substring (its accept
    absorbs), an anchored suffix (alive on every byte of its class), an
    end-anchored substring (no state of it absorbs) and a count of the
    value's bytes modulo 2 to 5 (a byte read twice or not at all flips it)."""
    family = (lambda j: f"^/api/v[0-9]+/t0/r{j}/[a-z0-9]*$",
              lambda j: f"k{j}z",
              lambda j: f"^/api/v[0-9]+/t0/[a-z0-9/]*s{j}$",
              lambda j: f"e{j}$",
              lambda j: f"^(.{{{2 + j // 5 % 4}}})*(y{j})?$")
    return [family[j % 5](j) for j in range(n)]


def _fill(head, tail, length):
    return head + "x" * (length - len(head) - len(tail)) + tail


# a kind builds its value at any length; but for the byte counts, the
# verdicts are the same at each
KINDS = {
    # route 0's DFA matches, the substring and the suffix are found at the end
    "one-route": lambda n: _fill(HEAD + "r0/", "k1zs2", n),
    # every anchored DFA dies at byte 1; the unanchored ones read on
    "dies-at-byte-1": lambda n: _fill("#", "e3", n),
    # alive to the last byte, which no class holds
    "dies-on-last-byte": lambda n: _fill(HEAD + "r0/", "!", n),
    # inside `v[0-9]+` to the end: no DFA dies, none matches
    "all-alive": lambda n: "/api/v" + "1" * (n - len("/api/v")),
    # an unanchored DFA's accept absorbs at byte 14, a suffix matches at the end
    "found-early": lambda n: _fill(HEAD + "k1z/", "s2", n),
}
CONST_KEYS = {  # api key -> (kind, length) of its identity's annotation
    "key-96": ("one-route", 96), "key-300": ("dies-on-last-byte", 300),
    "key-4096": ("found-early", 4096), "key-fits": ("one-route", FITS)}


def _entry(engine, cfg_id, host, selector, regexes, identity):
    rules = [Pattern("request.headers.x-skip", Operator.NEQ, f"p{j}")
             for j in range(len(regexes))]
    conds = [Pattern(selector, Operator.MATCHES, rx) for rx in regexes]
    authz = [AuthorizationConfig(f"e{j}", PatternMatching(
        rule, batched_provider=engine.provider_for(cfg_id), evaluator_slot=j))
        for j, rule in enumerate(rules)]
    return EngineEntry(
        id=cfg_id, hosts=[host],
        runtime=RuntimeAuthConfig(labels={"namespace": "ns", "name": cfg_id[3:]},
                                  identity=[identity], authorization=authz),
        rules=ConfigRules(name=cfg_id, evaluators=list(zip(conds, rules))))


def _keyed_identity(n):
    creds = AuthCredentials(key_selector="X-API-KEY", location="custom_header")
    keys = APIKey(f"keys-{n}", LabelSelector.from_spec(
        {"matchLabels": {"g": f"c{n}"}}), credentials=creds)
    for key, (kind, length) in CONST_KEYS.items():
        keys.add_k8s_secret_based_identity(Secret(
            namespace="ns", name=f"c{n}-{key}", labels={"g": f"c{n}"},
            annotations={"path": KINDS[kind](length)},
            data={"api_key": f"{key}-{n}".encode()}))
    return IdentityConfig(f"keys-{n}", keys, credentials=creds)


ROUTE_REGEXES = [route_rules.route_regex(0, k)
                 for k in range(len(route_rules.ROUTES))]
NEVER_ABSORB = [f"e{j}$" for j in range(5)]


@pytest.fixture(scope="module")
def served():
    engine = PolicyEngine(max_batch=64, mesh=None)
    anon = IdentityConfig("anon", Noop())
    entries = []
    for n in COUNTS:
        entries.append(_entry(engine, f"ns/head-{n}", f"head-{n}.test",
                              "request.headers.x-path", patterns(n), anon))
        entries.append(_entry(engine, f"ns/const-{n}", f"const-{n}.test",
                              "auth.identity.metadata.annotations.path",
                              patterns(n), _keyed_identity(n)))
    entries.append(_entry(engine, "ns/routes", "routes.test",
                          "request.url_path", ROUTE_REGEXES, anon))
    entries.append(_entry(engine, "ns/never", "never.test",
                          "request.url_path", NEVER_ABSORB, anon))
    engine.apply_snapshot(entries)
    policy = engine._snapshot.policy
    assert all(fast_lane_eligible(e, policy) is not None for e in entries)
    # lane selection and brownout off: the kernel reads every row's
    # cpu_dense; no verdict cache: every row is encoded and launched
    fe = NativeFrontend(engine, port=0, max_batch=64, window_us=2000,
                        lane_select=False, brownout=False,
                        verdict_cache_size=0)
    port = fe.start()
    assert fe.wait_warm(900.0) and fe.warm_error is None
    try:
        with grpc.insecure_channel(
                f"127.0.0.1:{port}",
                options=[("grpc.max_send_message_length", -1)]) as ch:
            yield fe, check_stub(ch), policy
    finally:
        fe.stop()


def _codes(call, reqs, window=256):
    out = []
    for lo in range(0, len(reqs), window):
        futures = [call.future(r, timeout=120) for r in reqs[lo:lo + window]]
        out += [f.result().status.code for f in futures]
    return out


def _verdicts(call, host, n, headers):
    """Every DFA's verdict on the row, read one request a DFA: evaluator j
    holds unless its `when` (DFA j) matches and `x-skip` names it."""
    codes = _codes(call, [make_req(host, headers=dict(headers, **{"x-skip": f"p{j}"}))
                          for j in range(n)])
    assert set(codes) <= {OK, DENIED}, codes
    return [c == DENIED for c in codes]


def _expected(n, value):
    return [re.search(rx, value) is not None for rx in patterns(n)]


def _scans(fe):
    return fe._mod.fe_loop_clock()["phases"]["ovf_scan"]["count"]


def _width(policy, cfg_id):
    """The byte width of the config's size class: a value past it is the
    host's, one inside it the device's (ISSUE 38)."""
    return int(policy.config_byte_width[policy.config_ids[cfg_id]])


@needs_native
@pytest.mark.parametrize("kind", sorted(KINDS))
@pytest.mark.parametrize("n", COUNTS)
def test_every_dfas_verdict_equals_re_and_the_device_lanes(served, n, kind):
    fe, call, policy = served
    host = f"head-{n}.test"
    width = _width(policy, f"ns/head-{n}")
    assert width in (64, 128, 256)
    fits = KINDS[kind](FITS)
    scans = _scans(fe)
    on_device = _verdicts(call, host, n, {"x-path": fits})
    assert _scans(fe) == scans  # it fits: the kernel scanned it
    assert on_device == _expected(n, fits)
    for length in LENGTHS:
        value = KINDS[kind](length)
        assert len(value) == length > DFA_VALUE_BYTES
        scans = _scans(fe)
        on_host = _verdicts(call, host, n, {"x-path": value})
        # past the row's own class's width the host scanned every row;
        # inside it the kernel did, whatever another class's width is
        assert _scans(fe) == scans + (n if length > width else 0)
        assert on_host == _expected(n, value), (n, kind, length)
        assert all(h == d for j, (h, d) in enumerate(zip(on_host, on_device))
                   if j % 5 != 4), (n, kind, length)
    if n > 3:  # the kind is what its name says, not nothing
        named = [v for j, v in enumerate(on_device) if j % 5 != 4]
        assert any(named) == (kind in ("one-route", "dies-at-byte-1",
                                       "found-early"))


@needs_native
@pytest.mark.parametrize("n", COUNTS)
def test_a_missing_attribute_reads_the_empty_value(served, n):
    fe, call, _ = served
    assert _verdicts(call, f"head-{n}.test", n, {}) == _expected(n, "")
    assert _verdicts(call, f"head-{n}.test", n, {"x-path": ""}) == _expected(n, "")


@needs_native
@pytest.mark.parametrize("n", COUNTS)
def test_a_constant_that_overflows_takes_the_same_pass(served, n):
    fe, call, policy = served
    host = f"const-{n}.test"
    width = _width(policy, f"ns/const-{n}")
    got = {}
    for key, (kind, length) in CONST_KEYS.items():
        scans = _scans(fe)
        got[key] = _verdicts(call, host, n, {"x-api-key": f"{key}-{n}"})
        assert _scans(fe) - scans == (n if length > width else 0)
        assert got[key] == _expected(n, KINDS[kind](length)), (n, key)
    assert all(a == b for j, (a, b) in enumerate(zip(
        got["key-96"], got["key-fits"])) if j % 5 != 4)


def _naive_absorbing(tables):
    """A state absorbs if and only if all 256 transitions return to it."""
    own = np.arange(tables.shape[1])[None, :, None]
    return (tables == own).all(axis=2)


def _translated(module, params):
    return [asyncio.run(translate_auth_config(
        m["metadata"]["name"], module.NAMESPACE, m["spec"])).rules
        for m in module.manifests(params)]


@pytest.mark.parametrize("generator", ["route_rules", "tenant_rules"])
def test_a_state_is_marked_absorbing_iff_every_transition_returns_to_it(generator):
    module = {"route_rules": route_rules, "tenant_rules": tenant_rules}[generator]
    policy = compile_corpus(_translated(module, {"n_configs": 6}))
    tables, accept = policy.dfa_tables, policy.dfa_accept
    flags = cc.dfa_state_flags(tables, accept)
    assert flags.dtype == np.uint8 and flags.shape == accept.shape
    np.testing.assert_array_equal(flags & cc.DFA_ACCEPTS != 0, accept)
    np.testing.assert_array_equal(flags & cc.DFA_ABSORBS != 0,
                                  _naive_absorbing(tables))
    assert not (flags & ~np.uint8(cc.DFA_ACCEPTS | cc.DFA_ABSORBS)).any()
    # what the front end is handed is these flags, a dfa row each
    by_row = policy.dfa_flags_by_row
    np.testing.assert_array_equal(by_row, flags[policy.dfa_table_of_row])
    np.testing.assert_array_equal(by_row & cc.DFA_ACCEPTS != 0,
                                  policy.dfa_accept_by_row)
    # every anchored table has its dead state among them; state 0 never is
    # one (each pattern here reads at least a byte before it settles)
    states = cc.dfa_table_states(policy)
    real = np.arange(tables.shape[1])[None, :] < states[:, None]
    marked = (flags & cc.DFA_ABSORBS != 0) & real
    assert marked.any(axis=1).all() and not marked[:, 0].any()
    # from a marked state no byte changes the verdict: every walk that
    # enters one stays, so its accept bit is the answer
    t, s = np.nonzero(marked)
    assert (tables[t, s] == s[:, None]).all()


def test_absorbing_states_are_the_two_redfa_builds():
    """compiler/redfa.py: the empty subset of an anchored pattern, the
    accept of an unanchored one; an end-anchored search has neither."""
    for rx, n_absorbing, accepting in ((r"^/a/[0-9]+$", 1, 0), (r"k1z", 1, 1),
                                       (r"e1$", 0, 0), (r"^/a/", 2, 1)):
        dfa = compile_regex_dfa(rx)
        flags = cc.dfa_state_flags(dfa.trans[None].astype(np.uint8),
                                   dfa.accept[None])[0]
        absorbing = flags & cc.DFA_ABSORBS != 0
        assert absorbing.sum() == n_absorbing, rx
        assert (absorbing & dfa.accept).sum() == accepting, rx


def _ovf_counts(fe):
    rows = fe._mod.fe_loop_clock()["rows"]
    assert rows["ovf_dfas"]["sum_ns"] == rows["ovf_loads"]["sum_ns"] == 0
    return rows["ovf_dfas"]["count"], rows["ovf_loads"]["count"]


@needs_native
def test_the_scans_counts_say_where_the_dfas_settled(served):
    fe, call, policy = served
    # sixteen routes of up to 72 states: the class keeps the floor width
    assert _width(policy, "ns/routes") == DFA_VALUE_BYTES
    route = route_rules.ROUTES[route_rules.LONG_ROUTES[0]]
    paths = [HEAD + route[4](random.Random(k), 96 - len(HEAD)) for k in range(8)]
    assert all(len(p) == 96 and re.search(ROUTE_REGEXES[
        route_rules.LONG_ROUTES[0]], p) for p in paths)
    dfas0, loads0 = _ovf_counts(fe)
    codes = _codes(call, [make_req("routes.test", path=p) for p in paths])
    dfas1, loads1 = _ovf_counts(fe)
    assert codes == [OK] * len(paths)
    n = len(ROUTE_REGEXES)
    assert dfas1 - dfas0 == n * len(paths)
    # one route reads its path to the end, fifteen die inside the first
    # segment after the prefix: far under the value's length a DFA
    per_dfa = (loads1 - loads0) / (dfas1 - dfas0)
    assert 96 / n < per_dfa < 96 / 4, per_dfa
    assert loads1 - loads0 >= 96 * len(paths)
    # a corpus whose DFAs never absorb reads every byte with every DFA
    past = [n for n in LENGTHS if n > _width(policy, "ns/never")]
    assert past and len(past) < len(LENGTHS)  # its class is wider than 64
    long_paths = ["/" + "x" * (length - 1) for length in LENGTHS]
    codes = _codes(call, [make_req("never.test", path=p) for p in long_paths])
    dfas2, loads2 = _ovf_counts(fe)
    assert codes == [OK] * len(long_paths)
    assert dfas2 - dfas1 == len(NEVER_ABSORB) * len(past)
    assert loads2 - loads1 == len(NEVER_ABSORB) * sum(past)
    # a value that fits enters no DFA here
    _codes(call, [make_req("routes.test", path=HEAD + "health")])
    assert _ovf_counts(fe) == (dfas2, loads2)
    # the phase's own count is the rows, as before
    assert fe.debug_vars()["front"]["rows"]["ovf_dfas"]["count"] == dfas2
