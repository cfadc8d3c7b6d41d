"""Differential suite for the native C++ gRPC frontend (native/frontend.cpp +
runtime/native_frontend.py): every response must match the Python grpc.aio
server (service/grpc_server.py) field for field — same corpus, same
requests, fast lane and slow lane both.

The engine here is built with mesh=None so the single-corpus fast lane
engages (the suite-wide conftest forces an 8-device virtual mesh, which
routes everything to the slow lane — covered by its own test below)."""

from __future__ import annotations

import asyncio
import threading
import time

import grpc
import pytest

from authorino_tpu import protos
from authorino_tpu.compiler import ConfigRules
from authorino_tpu.evaluators import (
    AuthorizationConfig,
    DenyWith,
    DenyWithValues,
    IdentityConfig,
    RuntimeAuthConfig,
)
from authorino_tpu.authjson.value import JSONProperty, JSONValue
from authorino_tpu.evaluators.authorization import OPA, PatternMatching
from authorino_tpu.evaluators.credentials import AuthCredentials
from authorino_tpu.evaluators.identity import APIKey, Noop
from authorino_tpu.expressions import All, Any_, Operator, Pattern
from authorino_tpu.k8s.client import LabelSelector, Secret
from authorino_tpu.runtime import EngineEntry, PolicyEngine
from authorino_tpu.runtime.native_frontend import NativeFrontend, fast_lane_eligible

pb = protos.external_auth_pb2


def _native_available() -> bool:
    from authorino_tpu.native import load_library

    mod = load_library()
    return mod is not None and hasattr(mod, "fe_start")


pytestmark = pytest.mark.skipif(
    not _native_available(), reason="native frontend unavailable (no g++ to build it?)")


# ---------------------------------------------------------------------------
# corpus: a mix that exercises fast lane, slow lane, DFA, denyWith
# ---------------------------------------------------------------------------

def make_pattern_entry(engine, cfg_id, hosts, rule, cond=None, deny_with=None):
    pm = PatternMatching(rule, batched_provider=engine.provider_for(cfg_id),
                         evaluator_slot=0)
    ns, _, nm = cfg_id.partition("/")
    runtime = RuntimeAuthConfig(
        labels={"namespace": ns, "name": nm},  # like translate injects
        identity=[IdentityConfig("anon", Noop())],
        authorization=[AuthorizationConfig("rules", pm)],
        deny_with=deny_with or DenyWith(),
    )
    return EngineEntry(id=cfg_id, hosts=hosts, runtime=runtime,
                       rules=ConfigRules(name=cfg_id, evaluators=[(cond, rule)]))


def build_engine() -> PolicyEngine:
    engine = PolicyEngine(max_batch=64, mesh=None)

    def pattern_entry(i, cfg_id, hosts, rule, cond=None, deny_with=None):
        return make_pattern_entry(engine, cfg_id, hosts, rule, cond, deny_with)

    entries = []
    # fast: plain eq/neq/incl over request attrs
    entries.append(pattern_entry(
        0, "ns/fast-eq", ["fast-eq.test"],
        All(Pattern("request.method", Operator.EQ, "GET"),
            Pattern("request.headers.x-org", Operator.EQ, "acme"))))
    # fast: compiled evaluator conditions (skipped ⇒ allow)
    entries.append(pattern_entry(
        1, "ns/fast-cond", ["fast-cond.test"],
        Pattern("request.headers.x-role", Operator.EQ, "admin"),
        cond=Pattern("request.method", Operator.EQ, "POST")))
    # fast: device-DFA regex over url_path
    entries.append(pattern_entry(
        2, "ns/fast-rx", ["fast-rx.test"],
        Pattern("request.url_path", Operator.MATCHES, r"^/api/v[0-9]+/ok")))
    # fast: static denyWith customization
    entries.append(pattern_entry(
        3, "ns/fast-deny", ["fast-deny.test"],
        Pattern("request.headers.x-pass", Operator.EQ, "yes"),
        deny_with=DenyWith(unauthorized=DenyWithValues(
            code=302,
            message=JSONValue(static="moved"),
            headers=[JSONProperty("Location", JSONValue(static="http://login.test"))],
        ))))
    # fast (round 4): API-key identity-only — credential map lookup, pure
    # C++ decision, no kernel involvement
    api_key = APIKey("friends", LabelSelector.from_spec({"matchLabels": {"g": "t"}}),
                     credentials=AuthCredentials(key_selector="APIKEY"))
    api_key.add_k8s_secret_based_identity(
        Secret(namespace="ns", name="k1", labels={"g": "t"}, data={"api_key": b"sekret"}))
    entries.append(EngineEntry(
        id="ns/fast-keyonly", hosts=["slow-key.test"],
        runtime=RuntimeAuthConfig(
            labels={"namespace": "ns", "name": "fast-keyonly"},
            identity=[IdentityConfig("friends", api_key,
                                     credentials=AuthCredentials(key_selector="APIKEY"))]),
        rules=None))
    # fast (round 4): API-key identity + patterns over auth.identity.* —
    # per-key plan variants resolved at refresh time
    api_key2 = APIKey(
        "team", LabelSelector.from_spec({"matchLabels": {"g": "t2"}}),
        credentials=AuthCredentials(key_selector="X-API-KEY", location="custom_header"))
    api_key2.add_k8s_secret_based_identity(Secret(
        namespace="ns", name="adm", labels={"g": "t2"},
        annotations={"role": "admin"}, data={"api_key": b"adminkey"}))
    api_key2.add_k8s_secret_based_identity(Secret(
        namespace="ns", name="usr", labels={"g": "t2"},
        annotations={"role": "user"}, data={"api_key": b"userkey"}))
    rule_role = Pattern("auth.identity.metadata.annotations.role", Operator.EQ, "admin")
    pm_role = PatternMatching(rule_role, batched_provider=engine.provider_for("ns/fast-key"),
                              evaluator_slot=0)
    entries.append(EngineEntry(
        id="ns/fast-key", hosts=["fast-key.test"],
        runtime=RuntimeAuthConfig(
            labels={"namespace": "ns", "name": "fast-key"},
            identity=[IdentityConfig(
                "team", api_key2,
                credentials=AuthCredentials(key_selector="X-API-KEY",
                                            location="custom_header"))],
            authorization=[AuthorizationConfig("rules", pm_role)]),
        rules=ConfigRules(name="ns/fast-key", evaluators=[(None, rule_role)])))
    # fast (round 4): remaining credential locations (cookie / query)
    for host, loc, sel in (("cookie-key.test", "cookie", "ses"),
                           ("query-key.test", "query", "tok")):
        ak = APIKey(f"k-{loc}", LabelSelector.from_spec({"matchLabels": {"g": loc}}),
                    credentials=AuthCredentials(key_selector=sel, location=loc))
        ak.add_k8s_secret_based_identity(Secret(
            namespace="ns", name=f"s-{loc}", labels={"g": loc},
            data={"api_key": b"c0ffee"}))
        entries.append(EngineEntry(
            id=f"ns/fast-{loc}", hosts=[host],
            runtime=RuntimeAuthConfig(
                labels={"namespace": "ns", "name": f"fast-{loc}"},
                identity=[IdentityConfig(
                    f"k-{loc}", ak,
                    credentials=AuthCredentials(key_selector=sel, location=loc))]),
            rules=None))
    # slow: templated denyWith needs per-request resolution
    entries.append(pattern_entry(
        7, "ns/slow-tmpl", ["slow-tmpl.test"],
        Pattern("request.method", Operator.EQ, "GET"),
        deny_with=DenyWith(unauthorized=DenyWithValues(
            message=JSONValue(pattern="request.path")))))
    # wildcard host: pattern-only, so it rides the FAST lane — the C++
    # side replicates the index's wildcard walk-up
    entries.append(pattern_entry(
        5, "ns/fast-wild", ["*.wild.test"],
        Pattern("request.method", Operator.NEQ, "DELETE")))
    # fast (round 5): patternMatching + decidable inline Rego in ONE config —
    # the Rego verdict lowers into a kernel slot (rego_lower) so the mixed
    # config keeps the fast lane (the reference runs OPA inline at full
    # server speed, ref pkg/evaluators/authorization/opa.go:86-117)
    opa = OPA("ns/fast-rego/rego", inline_rego=(
        'allow { input.request.method == "GET" }\n'
        'allow { input.request.headers["x-root"] == "true" }'))
    rule_tier = Pattern("request.headers.x-tier", Operator.EQ, "gold")
    pm_tier = PatternMatching(rule_tier,
                              batched_provider=engine.provider_for("ns/fast-rego"),
                              evaluator_slot=0)
    lowered = opa.lowered_verdict()
    assert lowered is not None
    opa.kernel_slot = 1
    entries.append(EngineEntry(
        id="ns/fast-rego", hosts=["fast-rego.test"],
        runtime=RuntimeAuthConfig(
            labels={"namespace": "ns", "name": "fast-rego"},
            identity=[IdentityConfig("anon", Noop())],
            authorization=[AuthorizationConfig("rules", pm_tier),
                           AuthorizationConfig("rego", opa)]),
        rules=ConfigRules(name="ns/fast-rego",
                          evaluators=[(None, rule_tier), (None, lowered)])))
    engine.apply_snapshot(entries)
    return engine


def make_req(host, method="GET", path="/", headers=None, ctx=None):
    req = pb.CheckRequest()
    http = req.attributes.request.http
    http.method = method
    http.path = path
    http.host = host
    for k, v in (headers or {}).items():
        http.headers[k] = v
    for k, v in (ctx or {}).items():
        req.attributes.context_extensions[k] = v
    return req


REQUESTS = [
    make_req("fast-eq.test", headers={"x-org": "acme"}),
    make_req("fast-eq.test", headers={"x-org": "evil"}),
    make_req("fast-eq.test", method="POST", headers={"x-org": "acme"}),
    make_req("fast-eq.test"),                                    # header missing
    make_req("fast-cond.test"),                                  # cond unmatched → allow
    make_req("fast-cond.test", method="POST"),                   # cond matched → deny
    make_req("fast-cond.test", method="POST", headers={"x-role": "admin"}),
    make_req("fast-rx.test", path="/api/v2/ok?x=1"),
    make_req("fast-rx.test", path="/api/nope"),
    make_req("fast-rx.test", path="/api/v9/ok" + "a" * 100),     # > DFA_VALUE_BYTES
    make_req("fast-rx.test", path="/api/v9/ok" + "a" * 300),     # > every class's width
    make_req("fast-deny.test", headers={"x-pass": "yes"}),
    make_req("fast-deny.test", headers={"x-pass": "no"}),        # custom 302 deny
    make_req("slow-key.test", headers={"authorization": "APIKEY sekret"}),
    make_req("slow-key.test", headers={"authorization": "APIKEY wrong"}),
    make_req("slow-key.test"),                                   # credential missing
    make_req("slow-key.test", headers={"authorization": "Bearer sekret"}),  # wrong scheme
    make_req("fast-key.test", headers={"x-api-key": "adminkey"}),  # identity const allows
    make_req("fast-key.test", headers={"x-api-key": "userkey"}),   # identity const denies
    make_req("fast-key.test", headers={"x-api-key": "nope"}),      # unknown key
    make_req("fast-key.test"),                                     # header missing
    make_req("slow-tmpl.test", method="POST", path="/here"),       # templated deny → slow
    make_req("cookie-key.test", headers={"cookie": "a=1; ses=c0ffee; b=2"}),
    make_req("cookie-key.test", headers={"cookie": "ses=wrong"}),
    make_req("cookie-key.test", headers={"cookie": "other=1"}),    # cred missing
    make_req("query-key.test", path="/hello?x=1&tok=c0ffee&y=2"),
    make_req("query-key.test", path="/hello?tok=bad"),
    make_req("query-key.test", path="/hello"),                     # cred missing
    make_req("a.wild.test"),
    make_req("a.wild.test", method="DELETE"),
    make_req("deep.a.wild.test"),            # wildcard matches any depth
    make_req("wild.test"),                   # walk-up matches the base itself
    make_req("a.wild.test:8443"),            # port strip before wildcard
    make_req("unknown.test"),                # exact+wildcard miss → 404
    make_req("fast-eq.test:8080", headers={"x-org": "acme"}),    # port strip
    make_req("other.test", headers={"x-org": "acme"}, ctx={"host": "fast-eq.test"}),
    # mixed pattern + lowered-Rego config: both evaluators kernel-decided
    make_req("fast-rego.test", headers={"x-tier": "gold"}),              # GET → allow
    make_req("fast-rego.test", method="DELETE", headers={"x-tier": "gold"}),  # rego deny
    make_req("fast-rego.test", method="DELETE",
             headers={"x-tier": "gold", "x-root": "true"}),              # 2nd rego body
    make_req("fast-rego.test", headers={"x-tier": "wood"}),              # pattern deny
    make_req("fast-rego.test", method="DELETE", headers={"x-root": "TRUE"}),  # both deny
]


def wait_for_snap_retire(fe, timeout_s: float = 30.0) -> None:
    """Poll until every superseded snapshot drained and retired."""
    deadline = time.monotonic() + timeout_s
    while len(fe._snaps) > 1 and time.monotonic() < deadline:
        time.sleep(0.1)
    assert len(fe._snaps) == 1


def response_key(resp: pb.CheckResponse):
    kind = resp.WhichOneof("http_response")
    headers = []
    body = ""
    status = 0
    if kind == "denied_response":
        d = resp.denied_response
        status = d.status.code
        headers = sorted((h.header.key, h.header.value) for h in d.headers)
        body = d.body
    elif kind == "ok_response":
        headers = sorted((h.header.key, h.header.value) for h in resp.ok_response.headers)
    return (resp.status.code, kind, status, headers, body)


def grpc_call(port, req, path="/envoy.service.auth.v3.Authorization/Check"):
    with grpc.insecure_channel(f"127.0.0.1:{port}") as ch:
        call = ch.unary_unary(path,
                              request_serializer=pb.CheckRequest.SerializeToString,
                              response_deserializer=pb.CheckResponse.FromString)
        return call(req, timeout=10)


def run_python_server(engine):
    """The grpc.aio reference server on a background loop thread."""
    from authorino_tpu.service.grpc_server import build_server

    started = threading.Event()
    holder = {}

    def runner():
        async def main():
            server = build_server(engine, address="127.0.0.1:0")
            await server.start()
            holder["port"] = server.bound_port
            holder["loop"] = asyncio.get_running_loop()
            started.set()
            await holder["stop"].wait()
            await server.stop(0.2)

        holder["stop"] = None

        async def boot():
            holder["stop"] = asyncio.Event()
            await main()

        asyncio.run(boot())

    t = threading.Thread(target=runner, daemon=True)
    t.start()
    started.wait(30)
    return holder, t


def test_sharded_engine_serves_fast_lane():
    """A mesh-sharded corpus must ride the fast lane too (round 4): the C++
    encoder lays each request into its owning shard's [B, S, ...] slice and
    one shard_map dispatch serves the batch — multi-device scaling composes
    with the native frontend instead of disabling it.  Differential against
    the Python server on the same sharded engine.  (Runs FIRST: the C++
    server is one-per-process, so this test must finish before the
    module-scoped stack fixture starts its own.)"""
    import jax

    if len(jax.devices()) < 2:
        pytest.skip("needs the virtual multi-device mesh")
    engine = PolicyEngine(max_batch=16, mesh="auto")
    entries = []
    # enough configs to land on several mp shards, incl. a device-DFA regex
    for i in range(10):
        entries.append(make_pattern_entry(
            engine, f"ns/shard-{i}", [f"shard-{i}.test"],
            All(Pattern("request.headers.x-org", Operator.EQ, f"org-{i}"),
                Pattern("request.method", Operator.NEQ, "DELETE"))))
    entries.append(make_pattern_entry(
        engine, "ns/shard-rx", ["shard-rx.test"],
        Pattern("request.url_path", Operator.MATCHES, r"^/v[0-9]+/ok")))
    # credential variants × sharding: per-key auth.* constants must resolve
    # against the OWNING SHARD's compile
    aks = APIKey("sh-keys", LabelSelector.from_spec({"matchLabels": {"g": "sh"}}),
                 credentials=AuthCredentials(key_selector="APIKEY"))
    aks.add_k8s_secret_based_identity(Secret(
        namespace="ns", name="sh-adm", labels={"g": "sh"},
        annotations={"role": "admin"}, data={"api_key": b"sh-admin"}))
    aks.add_k8s_secret_based_identity(Secret(
        namespace="ns", name="sh-usr", labels={"g": "sh"},
        annotations={"role": "user"}, data={"api_key": b"sh-user"}))
    rule_sh = Pattern("auth.identity.metadata.annotations.role", Operator.EQ,
                      "admin")
    pm_sh = PatternMatching(rule_sh,
                            batched_provider=engine.provider_for("ns/shard-key"),
                            evaluator_slot=0)
    entries.append(EngineEntry(
        id="ns/shard-key", hosts=["shard-key.test"],
        runtime=RuntimeAuthConfig(
            labels={"namespace": "ns", "name": "shard-key"},
            identity=[IdentityConfig("sh-keys", aks,
                                     credentials=AuthCredentials(
                                         key_selector="APIKEY"))],
            authorization=[AuthorizationConfig("rules", pm_sh)]),
        rules=ConfigRules(name="ns/shard-key", evaluators=[(None, rule_sh)])))
    engine.apply_snapshot(entries)
    assert engine._snapshot.sharded is not None, "mesh path not engaged"
    fe = NativeFrontend(engine, port=0, max_batch=16, window_us=500)
    port = fe.start()
    holder, t = run_python_server(engine)
    try:
        reqs = []
        for i in range(10):
            reqs.append(make_req(f"shard-{i}.test", headers={"x-org": f"org-{i}"}))
            reqs.append(make_req(f"shard-{i}.test", headers={"x-org": "evil"}))
            reqs.append(make_req(f"shard-{i}.test", method="DELETE",
                                 headers={"x-org": f"org-{i}"}))
        reqs.append(make_req("shard-rx.test", path="/v2/ok"))
        reqs.append(make_req("shard-rx.test", path="/nope"))
        reqs.append(make_req("shard-rx.test", path="/v2/ok" + "x" * 200))  # ovf
        reqs.append(make_req("shard-key.test",
                             headers={"authorization": "APIKEY sh-admin"}))
        reqs.append(make_req("shard-key.test",
                             headers={"authorization": "APIKEY sh-user"}))
        reqs.append(make_req("shard-key.test",
                             headers={"authorization": "APIKEY nope"}))
        reqs.append(make_req("shard-key.test"))
        reqs.append(make_req("unknown.test"))
        for i, req in enumerate(reqs):
            native = response_key(grpc_call(port, req))
            python = response_key(grpc_call(holder["port"], req))
            assert native == python, f"sharded req #{i}: {native} vs {python}"
        stats = fe.stats()
        assert stats["fast"] > 0, f"sharded fast lane never engaged: {stats}"
        assert stats["fast"] >= len(reqs) - 1  # all but the 404 ride fast
        # seeded random sweep across shards, credentials, regex/overflow
        rng = __import__("random").Random(8)
        mism = []
        for i in range(120):
            host = rng.choice([f"shard-{rng.randrange(10)}.test",
                               "shard-rx.test", "shard-key.test",
                               "nope.test"])
            headers = {}
            if rng.random() < 0.6:
                headers["x-org"] = rng.choice(
                    [f"org-{rng.randrange(10)}", "evil", ""])
            if rng.random() < 0.5:
                headers["authorization"] = rng.choice(
                    ["APIKEY sh-admin", "APIKEY sh-user", "APIKEY zz", ""])
            req = make_req(host, method=rng.choice(["GET", "DELETE"]),
                           path=rng.choice(["/v2/ok", "/no",
                                            "/v1/ok" + "y" * 180]),
                           headers=headers)
            nk = response_key(grpc_call(port, req))
            pk = response_key(grpc_call(holder["port"], req))
            if nk != pk:
                mism.append((i, nk, pk))
        assert not mism, f"{len(mism)} diverged on the mesh, first: {mism[0]}"
    finally:
        holder["loop"].call_soon_threadsafe(holder["stop"].set)
        t.join(timeout=10)
        fe.stop()


# ---------------------------------------------------------------------------
# OIDC/JWT fast lane: the C++ variant map as a verified-token cache
# (round 4; ref pkg/evaluators/identity/oidc.go:41-103 verifies per request —
# here verification runs once in the slow lane and repeats serve natively)
# ---------------------------------------------------------------------------

def run_fake_idp():
    """FakeIdP (test_evaluators) on its own loop thread, alive while the
    frontend's slow lane and the Python server both fetch discovery/JWKS."""
    from test_evaluators import FakeIdP

    started = threading.Event()
    holder = {}

    def runner():
        async def main():
            from aiohttp.test_utils import TestServer

            idp = FakeIdP()
            server = TestServer(idp.app())
            await server.start_server()
            idp.issuer = str(server.make_url("")).rstrip("/")
            holder["idp"] = idp
            holder["loop"] = asyncio.get_running_loop()
            holder["stop"] = asyncio.Event()
            started.set()
            await holder["stop"].wait()
            await server.close()

        asyncio.run(main())

    t = threading.Thread(target=runner, daemon=True)
    t.start()
    started.wait(30)
    return holder, t


def _oidc_engine(idp):
    from authorino_tpu.evaluators.identity import OIDC

    engine = PolicyEngine(max_batch=32, mesh=None)
    oidc = OIDC("kc", idp.issuer)
    rule = Pattern("auth.identity.realm_access.roles", Operator.INCL, "admin")
    pm = PatternMatching(rule, batched_provider=engine.provider_for("ns/oidc"),
                         evaluator_slot=0)
    entries = [
        EngineEntry(
            id="ns/oidc", hosts=["oidc.test"],
            runtime=RuntimeAuthConfig(
                labels={"namespace": "ns", "name": "oidc"},
                identity=[IdentityConfig("kc", oidc)],
                authorization=[AuthorizationConfig("rules", pm)]),
            rules=ConfigRules(name="ns/oidc", evaluators=[(None, rule)])),
        # identity-only: token validity IS the decision (pure C++ on hits)
        EngineEntry(
            id="ns/oidc-only", hosts=["oidc-only.test"],
            runtime=RuntimeAuthConfig(
                labels={"namespace": "ns", "name": "oidc-only"},
                identity=[IdentityConfig("kc", oidc)]),
            rules=None),
    ]
    engine.apply_snapshot(entries)
    return engine, oidc


def test_oidc_fast_lane_token_cache():
    holder, t = run_fake_idp()
    idp = holder["idp"]
    try:
        engine, oidc = _oidc_engine(idp)
        # eligibility: dyn spec with the claim attr rows for registration
        snap = engine._snapshot
        spec = fast_lane_eligible(snap.by_id["ns/oidc"], snap.policy)
        assert spec is not None and len(spec.sources) == 1
        assert spec.sources[0].dyn and spec.sources[0].cred_kind == 1
        assert spec.sources[0].cred_key == "Bearer" and spec.auth_attrs

        fe = NativeFrontend(engine, port=0, max_batch=32, window_us=500)
        port = fe.start()
        pyholder, pyt = run_python_server(engine)
        try:
            bearer = lambda tok: {"authorization": f"Bearer {tok}"}
            admin = idp.token()  # realm_access.roles = [admin]
            user = idp.token({"realm_access": {"roles": ["user"]}})

            # first sight of a token: slow lane verifies AND registers
            r1 = grpc_call(port, make_req("oidc.test", headers=bearer(admin)))
            assert r1.status.code == 0
            assert fe.stats()["dyn_add"] >= 1
            # repeats ride the fast lane (claims resolved from the cache)
            r2 = grpc_call(port, make_req("oidc.test", headers=bearer(admin)))
            assert r2.status.code == 0
            assert fe.stats()["dyn_hit"] >= 1
            # a cached token with the wrong role denies through the kernel
            d1 = grpc_call(port, make_req("oidc.test", headers=bearer(user)))
            d2 = grpc_call(port, make_req("oidc.test", headers=bearer(user)))
            assert d1.status.code == 7 and d2.status.code == 7
            assert fe.stats()["dyn_hit"] >= 2
            # identity-only config: cached token → direct C++ OK
            before_ok = fe.stats()["direct_ok"]
            grpc_call(port, make_req("oidc-only.test", headers=bearer(admin)))
            o2 = grpc_call(port, make_req("oidc-only.test", headers=bearer(admin)))
            assert o2.status.code == 0
            assert fe.stats()["direct_ok"] > before_ok

            # differential vs the Python server, hits and misses both
            matrix = [
                make_req("oidc.test", headers=bearer(admin)),
                make_req("oidc.test", headers=bearer(user)),
                make_req("oidc.test", headers=bearer("not-a-token")),
                make_req("oidc.test", headers={"authorization": "Basic zzz"}),
                make_req("oidc.test"),
                make_req("oidc-only.test", headers=bearer(admin)),
                make_req("oidc-only.test"),
            ]
            for i, rq in enumerate(matrix):
                native = response_key(grpc_call(port, rq))
                python = response_key(grpc_call(pyholder["port"], rq))
                assert native == python, f"oidc req #{i}: {native} vs {python}"

            # expiry is enforced in C++: past its exp the token stops being
            # served from the cache.  jose honors a 30s clock-skew leeway,
            # so the slow lane still answers OK here — the point is the
            # route: post-exp requests must MISS the cache (and a dead
            # deadline must not re-register)
            short = idp.token({"exp": int(time.time()) + 1})
            a = grpc_call(port, make_req("oidc.test", headers=bearer(short)))
            assert a.status.code == 0
            time.sleep(1.3)
            miss_before = fe.stats()["dyn_miss"]
            b = grpc_call(port, make_req("oidc.test", headers=bearer(short)))
            assert b.status.code == 0  # within leeway: pipeline parity
            assert fe.stats()["dyn_miss"] > miss_before
            c = grpc_call(port, make_req("oidc.test", headers=bearer(short)))
            assert fe.stats()["dyn_miss"] > miss_before + 1  # stayed slow
        finally:
            pyholder["loop"].call_soon_threadsafe(pyholder["stop"].set)
            pyt.join(timeout=10)
            fe.stop()
    finally:
        holder["loop"].call_soon_threadsafe(holder["stop"].set)
        t.join(timeout=10)


def test_oidc_jwks_rotation_drops_token_cache():
    """Key rotation at the provider must invalidate every cached token:
    the OIDC change listener swaps in a fresh C++ snapshot (empty variant
    map), so old-key tokens fall back to the slow lane and fail
    verification against the new JWKS."""
    from cryptography.hazmat.primitives.asymmetric import rsa

    holder, t = run_fake_idp()
    idp = holder["idp"]
    try:
        engine, oidc = _oidc_engine(idp)
        fe = NativeFrontend(engine, port=0, max_batch=32, window_us=500)
        port = fe.start()
        try:
            bearer = lambda tok: {"authorization": f"Bearer {tok}"}
            old_tok = idp.token()
            r1 = grpc_call(port, make_req("oidc.test", headers=bearer(old_tok)))
            r2 = grpc_call(port, make_req("oidc.test", headers=bearer(old_tok)))
            assert r1.status.code == 0 and r2.status.code == 0
            assert fe.stats()["dyn_hit"] >= 1

            idp.key = rsa.generate_private_key(public_exponent=65537, key_size=2048)
            # refresh discovery+JWKS (any loop works; the change listener
            # fires from here and rebuilds the frontend snapshot)
            fut = asyncio.run_coroutine_threadsafe(oidc.refresh(), holder["loop"])
            fut.result(30)

            deadline = time.time() + 60
            code = 0
            while time.time() < deadline:
                code = grpc_call(port, make_req(
                    "oidc.test", headers=bearer(old_tok))).status.code
                if code == 16:
                    break
                time.sleep(0.2)
            assert code == 16, "old-key token still served after rotation"
            new_tok = idp.token()
            rn = grpc_call(port, make_req("oidc.test", headers=bearer(new_tok)))
            assert rn.status.code == 0
        finally:
            fe.stop()
    finally:
        holder["loop"].call_soon_threadsafe(holder["stop"].set)
        t.join(timeout=10)


def test_multi_identity_or_fast_lane():
    """API key OR JWT in one AuthConfig (the canonical Authorino pairing):
    both identity sources ride the fast lane — static per-key variants for
    the API key, the verified-token cache for OIDC — and the all-sources-
    failed answers come from per-bitmask static templates, byte-exact with
    the pipeline's aggregated JSON error (round 4)."""
    holder, t = run_fake_idp()
    idp = holder["idp"]
    try:
        from authorino_tpu.evaluators.identity import OIDC

        engine = PolicyEngine(max_batch=32, mesh=None)
        ak = APIKey("api-users", LabelSelector.from_spec(
            {"matchLabels": {"g": "multi"}}),
            credentials=AuthCredentials(key_selector="APIKEY"))
        ak.add_k8s_secret_based_identity(Secret(
            namespace="ns", name="svc-key", labels={"g": "multi"},
            annotations={"role": "admin"}, data={"api_key": b"svc-secret"}))
        oidc = OIDC("kc", idp.issuer)
        rule = Any_(
            Pattern("auth.identity.metadata.annotations.role", Operator.EQ,
                    "admin"),
            Pattern("auth.identity.realm_access.roles", Operator.INCL,
                    "admin"))
        cfg_id = "ns/multi"
        pm = PatternMatching(rule, batched_provider=engine.provider_for(cfg_id),
                             evaluator_slot=0)
        engine.apply_snapshot([EngineEntry(
            id=cfg_id, hosts=["multi.test"],
            runtime=RuntimeAuthConfig(
                labels={"namespace": "ns", "name": "multi"},
                # distinct priorities: deterministic order in BOTH servers
                identity=[
                    IdentityConfig("api-users", ak, priority=0,
                                   credentials=AuthCredentials(
                                       key_selector="APIKEY")),
                    IdentityConfig("kc", oidc, priority=1),
                ],
                authorization=[AuthorizationConfig("rules", pm)]),
            rules=ConfigRules(name=cfg_id, evaluators=[(None, rule)]))])
        spec = fast_lane_eligible(engine._snapshot.by_id[cfg_id],
                                  engine._snapshot.policy)
        assert spec is not None and len(spec.sources) == 2
        assert not spec.sources[0].dyn and spec.sources[1].dyn

        fe = NativeFrontend(engine, port=0, max_batch=32, window_us=500)
        port = fe.start()
        pyholder, pyt = run_python_server(engine)
        try:
            admin = idp.token()  # realm_access.roles = [admin]
            viewer = idp.token({"realm_access": {"roles": ["viewer"]}})

            # API-key path: pure static variant, no slow lane at all
            r = grpc_call(port, make_req("multi.test",
                                         headers={"authorization": "APIKEY svc-secret"}))
            assert r.status.code == 0
            assert fe.stats()["slow"] == 0
            # JWT path: first sight slow, repeat fast
            r1 = grpc_call(port, make_req("multi.test",
                                          headers={"authorization": f"Bearer {admin}"}))
            r2 = grpc_call(port, make_req("multi.test",
                                          headers={"authorization": f"Bearer {admin}"}))
            assert r1.status.code == 0 and r2.status.code == 0
            assert fe.stats()["dyn_hit"] >= 1

            matrix = [
                make_req("multi.test",
                         headers={"authorization": "APIKEY svc-secret"}),
                make_req("multi.test",
                         headers={"authorization": f"Bearer {admin}"}),
                make_req("multi.test",
                         headers={"authorization": f"Bearer {viewer}"}),  # deny
                make_req("multi.test"),                       # both missing
                make_req("multi.test",
                         headers={"authorization": "APIKEY nope"}),  # invalid+missing
                make_req("multi.test",
                         headers={"authorization": "Bearer junk"}),  # slow verify
            ]
            for i, rq in enumerate(matrix):
                native = response_key(grpc_call(port, rq))
                python = response_key(grpc_call(pyholder["port"], rq))
                assert native == python, f"multi req #{i}: {native} vs {python}"
            # the all-fail answers above were native template decisions
            assert fe.stats()["unauth"] >= 2
        finally:
            pyholder["loop"].call_soon_threadsafe(pyholder["stop"].set)
            pyt.join(timeout=10)
            fe.stop()
    finally:
        holder["loop"].call_soon_threadsafe(holder["stop"].set)
        t.join(timeout=10)


def test_response_templates_ride_fast_lane():
    """Response evaluators whose outputs are constant per identity outcome
    (DynamicJSON/Plain over auth.*) keep the fast lane: OK bytes are
    precomputed per credential variant — the 'inject an identity header'
    pattern (round 4).  Differential against the Python server, headers
    AND dynamic metadata."""
    from google.protobuf.json_format import MessageToDict

    from authorino_tpu.evaluators import ResponseConfig
    from authorino_tpu.evaluators.response import DynamicJSON, Plain

    holder, t = run_fake_idp()
    idp = holder["idp"]
    try:
        from authorino_tpu.evaluators.identity import OIDC

        engine = PolicyEngine(max_batch=32, mesh=None)
        ak = APIKey("keys", LabelSelector.from_spec({"matchLabels": {"g": "rt"}}),
                    credentials=AuthCredentials(key_selector="APIKEY"))
        ak.add_k8s_secret_based_identity(Secret(
            namespace="ns", name="alice-key", labels={"g": "rt"},
            annotations={"role": "admin"}, data={"api_key": b"alice-secret"}))
        oidc = OIDC("kc", idp.issuer)
        entries = []
        # anonymous + static/template response headers
        rule = Pattern("request.method", Operator.NEQ, "DELETE")
        pm = PatternMatching(rule, batched_provider=engine.provider_for("ns/r-anon"),
                             evaluator_slot=0)
        entries.append(EngineEntry(
            id="ns/r-anon", hosts=["r-anon.test"],
            runtime=RuntimeAuthConfig(
                labels={"namespace": "ns", "name": "r-anon"},
                identity=[IdentityConfig("anon", Noop())],
                authorization=[AuthorizationConfig("rules", pm)],
                response=[
                    ResponseConfig("x-static", Plain(JSONValue(static="on"))),
                    ResponseConfig("x-anon", DynamicJSON([JSONProperty(
                        "anon", JSONValue(pattern="auth.identity.anonymous"))])),
                ]),
            rules=ConfigRules(name="ns/r-anon", evaluators=[(None, rule)])))
        # API key + per-key identity header (template) + dynamic metadata
        entries.append(EngineEntry(
            id="ns/r-key", hosts=["r-key.test"],
            runtime=RuntimeAuthConfig(
                labels={"namespace": "ns", "name": "r-key"},
                identity=[IdentityConfig("keys", ak,
                                         credentials=AuthCredentials(
                                             key_selector="APIKEY"))],
                response=[
                    ResponseConfig("x-user", Plain(JSONValue(
                        pattern="secret {auth.identity.metadata.name} "
                                "is {auth.identity.metadata.annotations.role}"))),
                    ResponseConfig("ident", DynamicJSON([JSONProperty(
                        "name",
                        JSONValue(pattern="auth.identity.metadata.name"))]),
                        wrapper="envoyDynamicMetadata"),
                ]),
            rules=None))
        # OIDC + claim-derived header (registered with the token variant)
        entries.append(EngineEntry(
            id="ns/r-jwt", hosts=["r-jwt.test"],
            runtime=RuntimeAuthConfig(
                labels={"namespace": "ns", "name": "r-jwt"},
                identity=[IdentityConfig("kc", oidc)],
                response=[ResponseConfig("x-sub", Plain(JSONValue(
                    pattern="auth.identity.sub")))]),
            rules=None))
        engine.apply_snapshot(entries)
        for cfg in ("ns/r-anon", "ns/r-key", "ns/r-jwt"):
            assert fast_lane_eligible(engine._snapshot.by_id[cfg],
                                      engine._snapshot.policy) is not None, cfg

        fe = NativeFrontend(engine, port=0, max_batch=32, window_us=500)
        port = fe.start()
        pyholder, pyt = run_python_server(engine)
        try:
            tok = idp.token({"sub": "john"})
            reqs = [
                make_req("r-anon.test"),
                make_req("r-key.test",
                         headers={"authorization": "APIKEY alice-secret"}),
                make_req("r-jwt.test",
                         headers={"authorization": f"Bearer {tok}"}),
                make_req("r-jwt.test",
                         headers={"authorization": f"Bearer {tok}"}),  # cached
            ]
            for i, rq in enumerate(reqs):
                native = grpc_call(port, rq)
                python = grpc_call(pyholder["port"], rq)
                assert MessageToDict(native) == MessageToDict(python), (
                    f"response req #{i}: {MessageToDict(native)} "
                    f"vs {MessageToDict(python)}")
            # spot-check the injected values themselves
            r = grpc_call(port, reqs[1])
            hdrs = {h.header.key: h.header.value for h in r.ok_response.headers}
            assert hdrs["x-user"] == "secret alice-key is admin"
            assert r.dynamic_metadata.fields["ident"].struct_value.fields[
                "name"].string_value == "alice-key"
            # the repeats were native, not pipeline
            stats = fe.stats()
            assert stats["fast"] >= 4
        finally:
            pyholder["loop"].call_soon_threadsafe(pyholder["stop"].set)
            pyt.join(timeout=10)
            fe.stop()
    finally:
        holder["loop"].call_soon_threadsafe(holder["stop"].set)
        t.join(timeout=10)


def test_identity_extensions_ride_fast_lane():
    """auth.*-only identity extensions resolve constantly per credential —
    applied at variant-build time, visible to both the kernel's auth.*
    patterns and the response templates (round 4)."""
    from google.protobuf.json_format import MessageToDict

    from authorino_tpu.evaluators import ResponseConfig
    from authorino_tpu.evaluators.base import IdentityExtension
    from authorino_tpu.evaluators.response import Plain

    engine = PolicyEngine(max_batch=16, mesh=None)
    ak = APIKey("keys", LabelSelector.from_spec({"matchLabels": {"g": "ext"}}),
                credentials=AuthCredentials(key_selector="APIKEY"))
    ak.add_k8s_secret_based_identity(Secret(
        namespace="ns", name="bob-key", labels={"g": "ext"},
        annotations={"level": "9"}, data={"api_key": b"bob-secret"}))
    exts = [
        IdentityExtension("tier", JSONValue(
            pattern="auth.identity.metadata.annotations.level")),
        IdentityExtension("source", JSONValue(static="api-key")),
    ]
    rule = Pattern("auth.identity.tier", Operator.EQ, "9")
    pm = PatternMatching(rule, batched_provider=engine.provider_for("ns/ext"),
                         evaluator_slot=0)
    engine.apply_snapshot([EngineEntry(
        id="ns/ext", hosts=["ext.test"],
        runtime=RuntimeAuthConfig(
            labels={"namespace": "ns", "name": "ext"},
            identity=[IdentityConfig(
                "keys", ak, extended_properties=exts,
                credentials=AuthCredentials(key_selector="APIKEY"))],
            authorization=[AuthorizationConfig("rules", pm)],
            response=[ResponseConfig("x-src", Plain(JSONValue(
                pattern="auth.identity.source")))]),
        rules=ConfigRules(name="ns/ext", evaluators=[(None, rule)]))])
    assert fast_lane_eligible(engine._snapshot.by_id["ns/ext"],
                              engine._snapshot.policy) is not None

    fe = NativeFrontend(engine, port=0, max_batch=16, window_us=500)
    port = fe.start()
    holder, t = run_python_server(engine)
    try:
        req = make_req("ext.test", headers={"authorization": "APIKEY bob-secret"})
        native = grpc_call(port, req)
        python = grpc_call(holder["port"], req)
        assert MessageToDict(native) == MessageToDict(python)
        assert native.status.code == 0  # pattern over the EXTENDED tier
        hdrs = {h.header.key: h.header.value for h in native.ok_response.headers}
        assert hdrs["x-src"] == "api-key"
        assert fe.stats()["fast"] >= 1 and fe.stats()["slow"] == 0
    finally:
        holder["loop"].call_soon_threadsafe(holder["stop"].set)
        t.join(timeout=10)
        fe.stop()


def test_oidc_cache_survives_reconcile_storm():
    """Reconcile swaps drop the verified-token cache (by design: fresh
    snapshot, empty variant maps).  Under a storm of swaps with live OIDC
    traffic, every response must stay correct — misses re-verify and
    re-register, hits serve natively, nothing errors (round 4)."""
    import concurrent.futures

    holder, t = run_fake_idp()
    idp = holder["idp"]
    try:
        engine, oidc = _oidc_engine(idp)
        base_entries = list(engine._snapshot.by_id.values())
        fe = NativeFrontend(engine, port=0, max_batch=32, window_us=500)
        port = fe.start()
        try:
            bearer = {"authorization": f"Bearer {idp.token()}"}
            grpc_call(port, make_req("oidc.test", headers=bearer))  # prime

            stop = threading.Event()
            codes = []

            def loader():
                with grpc.insecure_channel(f"127.0.0.1:{port}") as ch:
                    call = ch.unary_unary(
                        "/envoy.service.auth.v3.Authorization/Check",
                        request_serializer=pb.CheckRequest.SerializeToString,
                        response_deserializer=pb.CheckResponse.FromString)
                    req = make_req("oidc.test", headers=bearer)
                    while not stop.is_set():
                        codes.append(call(req, timeout=30).status.code)

            with concurrent.futures.ThreadPoolExecutor(2) as pool:
                futs = [pool.submit(loader) for _ in range(2)]
                adds_seen = [fe.stats()["dyn_add"]]
                for i in range(5):
                    # a real reconcile: new snapshot, cache dropped
                    extra = make_pattern_entry(
                        engine, f"ns/storm-{i}", [f"storm-{i}.test"],
                        Pattern("request.method", Operator.NEQ, "DELETE"))
                    engine.apply_snapshot(base_entries + [extra])
                    time.sleep(0.4)
                    adds_seen.append(fe.stats()["dyn_add"])
                stop.set()
                for f in futs:
                    f.result(timeout=30)
            assert codes and all(c == 0 for c in codes), (
                f"{sum(1 for c in codes if c)} non-OK of {len(codes)}")
            # each swap forced at least one re-registration
            assert adds_seen[-1] >= adds_seen[0] + 3, adds_seen
            assert fe.stats()["dyn_hit"] > 0
        finally:
            fe.stop()
    finally:
        holder["loop"].call_soon_threadsafe(holder["stop"].set)
        t.join(timeout=10)


def test_per_request_features_stay_slow():
    """Negative eligibility: anything genuinely per-request must keep the
    slow lane — response templates over request.*, identity extensions
    over request.*, wristbands (per-request signatures)."""
    from authorino_tpu.evaluators import ResponseConfig
    from authorino_tpu.evaluators.base import IdentityExtension
    from authorino_tpu.evaluators.response import Plain

    engine = PolicyEngine(max_batch=8, mesh=None)

    def entry_with(response=None, exts=None):
        rule = Pattern("request.method", Operator.NEQ, "DELETE")
        cfg_id = f"ns/neg-{len(response or [])}-{len(exts or [])}"
        pm = PatternMatching(rule, batched_provider=engine.provider_for(cfg_id),
                             evaluator_slot=0)
        return EngineEntry(
            id=cfg_id, hosts=[f"{cfg_id.split('/')[1]}.test"],
            runtime=RuntimeAuthConfig(
                identity=[IdentityConfig("anon", Noop(),
                                         extended_properties=exts or [])],
                authorization=[AuthorizationConfig("rules", pm)],
                response=response or []),
            rules=ConfigRules(name=cfg_id, evaluators=[(None, rule)]))

    # request.*-templated response → slow
    e1 = entry_with(response=[ResponseConfig(
        "x-path", Plain(JSONValue(pattern="request.path")))])
    # request.*-templated identity extension → slow
    e2 = entry_with(exts=[IdentityExtension(
        "path", JSONValue(pattern="request.path"))])
    # auth.*-only versions of both → fast
    e3 = entry_with(
        response=[ResponseConfig("x-anon", Plain(JSONValue(
            pattern="auth.identity.anonymous")))],
        exts=[IdentityExtension("src", JSONValue(static="anon"))])
    engine.apply_snapshot([e1, e2, e3])
    policy = engine._snapshot.policy
    assert fast_lane_eligible(e1, policy) is None
    assert fast_lane_eligible(e2, policy) is None
    assert fast_lane_eligible(e3, policy) is not None


def test_oauth2_cache_opt_in_rides_fast_lane():
    """OAuth2 introspection identities stay slow by default (introspection
    IS the revocation check) — but an explicit `cache` opt-in keyed by the
    credential header makes the dyn lane honor the user's own TTL
    semantics (round 4): hits serve natively, entries expire at cache.ttl,
    and post-TTL revocation is enforced."""
    from authorino_tpu.evaluators.cache import EvaluatorCache
    from authorino_tpu.evaluators.identity import OAuth2

    holder, t = run_fake_idp()
    idp = holder["idp"]
    try:
        engine = PolicyEngine(max_batch=16, mesh=None)
        url = f"{idp.issuer}/introspect"
        no_cache = OAuth2("oa", url, "cid", "csec")
        cached = OAuth2("oa", url, "cid", "csec")
        entries = [
            EngineEntry(
                id="ns/oauth-nocache", hosts=["oauth-nocache.test"],
                runtime=RuntimeAuthConfig(
                    labels={"namespace": "ns", "name": "oauth-nocache"},
                    identity=[IdentityConfig("oa", no_cache)]),
                rules=None),
            EngineEntry(
                id="ns/oauth", hosts=["oauth.test"],
                runtime=RuntimeAuthConfig(
                    labels={"namespace": "ns", "name": "oauth"},
                    identity=[IdentityConfig(
                        "oa", cached,
                        cache=EvaluatorCache(JSONValue(
                            pattern="request.headers.authorization"), 1))]),
                rules=None),
        ]
        engine.apply_snapshot(entries)
        snap = engine._snapshot
        assert fast_lane_eligible(snap.by_id["ns/oauth-nocache"],
                                  snap.policy) is None
        spec = fast_lane_eligible(snap.by_id["ns/oauth"], snap.policy)
        assert spec is not None and spec.sources[0].dyn
        assert spec.sources[0].ttl_cap == 1.0

        fe = NativeFrontend(engine, port=0, max_batch=16, window_us=500)
        port = fe.start()
        try:
            hdr = {"authorization": "Bearer opaque-token-1"}
            r1 = grpc_call(port, make_req("oauth.test", headers=hdr))
            t_reg = time.monotonic()
            assert r1.status.code == 0  # slow: introspected + registered
            r2 = grpc_call(port, make_req("oauth.test", headers=hdr))
            assert r2.status.code == 0
            assert fe.stats()["dyn_hit"] >= 1
            # the no-cache config always introspects (slow lane)
            slow_before = fe.stats()["slow"]
            n1 = grpc_call(port, make_req("oauth-nocache.test", headers=hdr))
            n2 = grpc_call(port, make_req("oauth-nocache.test", headers=hdr))
            assert n1.status.code == 0 and n2.status.code == 0
            assert fe.stats()["slow"] >= slow_before + 2

            # revocation takes effect once the user's TTL lapses: the dyn
            # entry AND the pipeline cache both expire at cache.ttl = 1s
            idp.active_tokens["opaque-token-1"] = {"active": False}
            t_revoked = time.monotonic()
            r3 = grpc_call(port, make_req("oauth.test", headers=hdr))
            if time.monotonic() - t_reg < 0.8:
                # still inside the opted-in window (guard: a slow CI stall
                # past the 1s TTL would legitimately re-introspect)
                assert r3.status.code == 0
            time.sleep(max(0.0, 1.3 - (time.monotonic() - t_revoked)))
            r4 = grpc_call(port, make_req("oauth.test", headers=hdr))
            assert r4.status.code == 16  # re-introspected: revoked
        finally:
            fe.stop()
    finally:
        holder["loop"].call_soon_threadsafe(holder["stop"].set)
        t.join(timeout=10)


def test_k8s_tokenreview_cache_opt_in_rides_fast_lane():
    """K8s TokenReview under an explicit cache opt-in (and explicit
    audiences — the default audience is the request host, which would vary
    per request): first review slow, repeats native, patterns over the
    reviewed user resolve from the cached identity."""
    from authorino_tpu.evaluators.cache import EvaluatorCache
    from authorino_tpu.evaluators.identity import KubernetesAuth
    from authorino_tpu.k8s import InMemoryCluster

    cluster = InMemoryCluster()
    cluster.token_reviews["sa-token"] = {"status": {
        "authenticated": True,
        "user": {"username": "system:serviceaccount:ns:app",
                 "groups": ["system:authenticated"]}}}
    engine = PolicyEngine(max_batch=16, mesh=None)
    ka = KubernetesAuth("k8s", audiences=["talker-api"], cluster=cluster)
    rule = Pattern("auth.identity.username", Operator.EQ,
                   "system:serviceaccount:ns:app")
    pm = PatternMatching(rule, batched_provider=engine.provider_for("ns/k8s"),
                         evaluator_slot=0)
    entries = [
        EngineEntry(
            id="ns/k8s", hosts=["k8s.test"],
            runtime=RuntimeAuthConfig(
                labels={"namespace": "ns", "name": "k8s"},
                identity=[IdentityConfig(
                    "k8s", ka,
                    cache=EvaluatorCache(JSONValue(
                        pattern="request.headers.authorization"), 60))],
                authorization=[AuthorizationConfig("rules", pm)]),
            rules=ConfigRules(name="ns/k8s", evaluators=[(None, rule)])),
        # no explicit audiences → host-dependent review → ineligible
        EngineEntry(
            id="ns/k8s-hostaud", hosts=["k8s-hostaud.test"],
            runtime=RuntimeAuthConfig(
                labels={"namespace": "ns", "name": "k8s-hostaud"},
                identity=[IdentityConfig(
                    "k8s", KubernetesAuth("k8s", cluster=cluster),
                    cache=EvaluatorCache(JSONValue(
                        pattern="request.headers.authorization"), 60))]),
            rules=None),
    ]
    engine.apply_snapshot(entries)
    snap = engine._snapshot
    assert fast_lane_eligible(snap.by_id["ns/k8s"], snap.policy) is not None
    assert fast_lane_eligible(snap.by_id["ns/k8s-hostaud"], snap.policy) is None

    fe = NativeFrontend(engine, port=0, max_batch=16, window_us=500)
    port = fe.start()
    holder, t = run_python_server(engine)
    try:
        hdr = {"authorization": "Bearer sa-token"}
        r1 = grpc_call(port, make_req("k8s.test", headers=hdr))
        r2 = grpc_call(port, make_req("k8s.test", headers=hdr))
        assert r1.status.code == 0 and r2.status.code == 0
        assert fe.stats()["dyn_hit"] >= 1
        for rq in (make_req("k8s.test", headers=hdr),
                   make_req("k8s.test", headers={"authorization": "Bearer bad"}),
                   make_req("k8s.test")):
            native = response_key(grpc_call(port, rq))
            python = response_key(grpc_call(holder["port"], rq))
            assert native == python, (native, python)
    finally:
        holder["loop"].call_soon_threadsafe(holder["stop"].set)
        t.join(timeout=10)
        fe.stop()


def test_identity_templated_deny_rides_fast_lane():
    """denyWith.unauthorized templated over the identity precomputes per
    credential variant (round 4): denial messages naming the caller serve
    natively, byte-exact with the pipeline; request.*-templated denials
    still route slow."""
    from google.protobuf.json_format import MessageToDict

    engine = PolicyEngine(max_batch=16, mesh=None)
    ak = APIKey("keys", LabelSelector.from_spec({"matchLabels": {"g": "dt"}}),
                credentials=AuthCredentials(key_selector="APIKEY"))
    ak.add_k8s_secret_based_identity(Secret(
        namespace="ns", name="eve-key", labels={"g": "dt"},
        annotations={"role": "viewer"}, data={"api_key": b"eve-secret"}))
    rule = Pattern("auth.identity.metadata.annotations.role", Operator.EQ,
                   "admin")

    def entry(cfg_id, host, deny_pattern):
        pm = PatternMatching(rule, batched_provider=engine.provider_for(cfg_id),
                             evaluator_slot=0)
        return EngineEntry(
            id=cfg_id, hosts=[host],
            runtime=RuntimeAuthConfig(
                labels={"namespace": "ns", "name": cfg_id.split("/")[1]},
                identity=[IdentityConfig("keys", ak,
                                         credentials=AuthCredentials(
                                             key_selector="APIKEY"))],
                authorization=[AuthorizationConfig("rules", pm)],
                deny_with=DenyWith(unauthorized=DenyWithValues(
                    code=403,
                    message=JSONValue(pattern=deny_pattern),
                    headers=[JSONProperty("x-denied-user", JSONValue(
                        pattern="auth.identity.metadata.name"))]))),
            rules=ConfigRules(name=cfg_id, evaluators=[(None, rule)]))

    e_auth = entry("ns/deny-tmpl", "deny-tmpl.test",
                   "role {auth.identity.metadata.annotations.role} "
                   "may not pass")
    e_req = entry("ns/deny-req", "deny-req.test", "request.path")
    engine.apply_snapshot([e_auth, e_req])
    policy = engine._snapshot.policy
    assert fast_lane_eligible(e_auth, policy) is not None
    assert fast_lane_eligible(e_req, policy) is None  # request-templated

    fe = NativeFrontend(engine, port=0, max_batch=16, window_us=500)
    port = fe.start()
    holder, t = run_python_server(engine)
    try:
        hdr = {"authorization": "APIKEY eve-secret"}
        native = grpc_call(port, make_req("deny-tmpl.test", headers=hdr))
        python = grpc_call(holder["port"], make_req("deny-tmpl.test", headers=hdr))
        assert MessageToDict(native) == MessageToDict(python)
        assert native.status.code == 7
        assert native.denied_response.status.code == 403
        assert native.denied_response.body == ""
        hdrs = {h.header.key: h.header.value
                for h in native.denied_response.headers}
        assert hdrs["x-denied-user"] == "eve-key"
        # the denial itself was a native fast-lane decision
        assert fe.stats()["fast"] >= 1 and fe.stats()["slow"] == 0
        # missing credential: all-fail template still byte-exact
        n2 = grpc_call(port, make_req("deny-tmpl.test"))
        p2 = grpc_call(holder["port"], make_req("deny-tmpl.test"))
        assert MessageToDict(n2) == MessageToDict(p2)
    finally:
        holder["loop"].call_soon_threadsafe(holder["stop"].set)
        t.join(timeout=10)
        fe.stop()


def test_hybrid_lane_procedural_rego():
    """A config mixing kernel patterns with PROCEDURAL (non-lowerable) Rego
    rides the hybrid lane (round 5): kernel denials answer natively, kernel
    passes hand the raw request to the slow pipeline — which re-runs the
    full phase (∧-verdict, so re-deciding covered patterns is correct).
    The reference evaluates OPA inline in the same server
    (ref pkg/evaluators/authorization/opa.go:86-117)."""
    engine = PolicyEngine(max_batch=16, mesh=None)
    rule = Pattern("request.headers.x-tier", Operator.EQ, "gold")
    pm = PatternMatching(rule, batched_provider=engine.provider_for("ns/hyb"),
                         evaluator_slot=0)
    opa = OPA("ns/hyb/rego",
              inline_rego='allow { count(input.request.path) > 5 }')
    assert opa.lowered_verdict() is None  # genuinely procedural
    engine.apply_snapshot([EngineEntry(
        id="ns/hyb", hosts=["hyb.test"],
        runtime=RuntimeAuthConfig(
            labels={"namespace": "ns", "name": "hyb"},
            identity=[IdentityConfig("anon", Noop())],
            authorization=[AuthorizationConfig("rules", pm),
                           AuthorizationConfig("rego", opa)]),
        rules=ConfigRules(name="ns/hyb", evaluators=[(None, rule)]))])
    snap = engine._snapshot
    spec = fast_lane_eligible(snap.by_id["ns/hyb"], snap.policy)
    assert spec is not None and spec.hybrid and spec.has_batch

    def hyb_total():
        from prometheus_client import REGISTRY

        return sum(
            s.value for m in REGISTRY.collect()
            if m.name == "auth_server_authconfig"
            for s in m.samples
            if s.name == "auth_server_authconfig_total"
            and s.labels.get("namespace") == "ns"
            and s.labels.get("authconfig") == "hyb")

    fe = NativeFrontend(engine, port=0, max_batch=16, window_us=500)
    port = fe.start()
    holder, t = run_python_server(engine)
    try:
        base_total = hyb_total()
        # kernel deny: answered natively, zero slow-lane work
        d = grpc_call(port, make_req("hyb.test", path="/abcdefg",
                                     headers={"x-tier": "wood"}))
        assert d.status.code == 7
        s0 = fe.stats()
        assert s0["fast"] >= 1 and s0["slow"] == 0 and s0["hybrid"] == 0
        # kernel pass + rego deny: handed off, denied by the pipeline
        d2 = grpc_call(port, make_req("hyb.test", path="/ab",
                                      headers={"x-tier": "gold"}))
        assert d2.status.code == 7
        s1 = fe.stats()
        assert s1["hybrid"] == 1 and s1["slow"] == 1
        # kernel pass + rego pass: handed off, allowed by the pipeline
        ok = grpc_call(port, make_req("hyb.test", path="/abcdefg",
                                      headers={"x-tier": "gold"}))
        assert ok.status.code == 0
        assert fe.stats()["hybrid"] == 2
        # one authconfig_total per REQUEST: kernel-allowed handoffs are
        # counted by the pipeline only (no dispatch+pipeline double count)
        assert hyb_total() - base_total == 3
        # differential vs the Python server across the whole matrix
        matrix = [
            make_req("hyb.test", path=p, headers=h)
            for p in ("/ab", "/abcdefg")
            for h in ({"x-tier": "gold"}, {"x-tier": "wood"}, {})
        ]
        for i, rq in enumerate(matrix):
            native = response_key(grpc_call(port, rq))
            python = response_key(grpc_call(holder["port"], rq))
            assert native == python, f"hybrid req #{i}: {native} vs {python}"
    finally:
        holder["loop"].call_soon_threadsafe(holder["stop"].set)
        t.join(timeout=10)
        fe.stop()


def test_hybrid_priority_order_guard():
    """Kernel pre-deny must not preempt an uncovered evaluator the pipeline
    would have failed in an EARLIER priority bucket (its denial could
    differ) — such configs stay fully slow."""
    engine = PolicyEngine(max_batch=16, mesh=None)
    rule = Pattern("request.headers.x-tier", Operator.EQ, "gold")
    pm = PatternMatching(rule, batched_provider=engine.provider_for("ns/hp"),
                         evaluator_slot=0)
    opa = OPA("ns/hp/rego",
              inline_rego='allow { count(input.request.path) > 5 }')
    engine.apply_snapshot([EngineEntry(
        id="ns/hp", hosts=["hp.test"],
        runtime=RuntimeAuthConfig(
            identity=[IdentityConfig("anon", Noop())],
            authorization=[
                AuthorizationConfig("rules", pm, priority=1),
                AuthorizationConfig("rego", opa, priority=0)]),
        rules=ConfigRules(name="ns/hp", evaluators=[(None, rule)]))])
    snap = engine._snapshot
    assert fast_lane_eligible(snap.by_id["ns/hp"], snap.policy) is None


def test_hybrid_allows_arbitrary_responses():
    """Hybrid OKs run the full pipeline, so per-request response templates
    (which disqualify the FULL fast lane) are fine on hybrid configs."""
    from authorino_tpu.evaluators import ResponseConfig
    from authorino_tpu.evaluators.response import Plain

    engine = PolicyEngine(max_batch=16, mesh=None)
    rule = Pattern("request.headers.x-tier", Operator.EQ, "gold")
    pm = PatternMatching(rule, batched_provider=engine.provider_for("ns/hr"),
                         evaluator_slot=0)
    opa = OPA("ns/hr/rego",
              inline_rego='allow { count(input.request.path) > 5 }')
    engine.apply_snapshot([EngineEntry(
        id="ns/hr", hosts=["hr.test"],
        runtime=RuntimeAuthConfig(
            labels={"namespace": "ns", "name": "hr"},
            identity=[IdentityConfig("anon", Noop())],
            authorization=[AuthorizationConfig("rules", pm),
                           AuthorizationConfig("rego", opa)],
            response=[ResponseConfig(
                "x-path", Plain(JSONValue(pattern="request.path")))]),
        rules=ConfigRules(name="ns/hr", evaluators=[(None, rule)]))])
    snap = engine._snapshot
    spec = fast_lane_eligible(snap.by_id["ns/hr"], snap.policy)
    assert spec is not None and spec.hybrid

    fe = NativeFrontend(engine, port=0, max_batch=16, window_us=500)
    port = fe.start()
    holder, t = run_python_server(engine)
    try:
        ok = grpc_call(port, make_req("hr.test", path="/abcdefg",
                                      headers={"x-tier": "gold"}))
        assert ok.status.code == 0
        hdrs = {h.header.key: h.header.value
                for h in ok.ok_response.headers}
        assert hdrs.get("x-path") == "/abcdefg"
        python = grpc_call(holder["port"], make_req(
            "hr.test", path="/abcdefg", headers={"x-tier": "gold"}))
        assert response_key(ok) == response_key(python)
        # kernel deny still answers natively
        d = grpc_call(port, make_req("hr.test", path="/abcdefg",
                                     headers={"x-tier": "wood"}))
        pd = grpc_call(holder["port"], make_req(
            "hr.test", path="/abcdefg", headers={"x-tier": "wood"}))
        assert response_key(d) == response_key(pd)
        assert fe.stats()["slow"] == fe.stats()["hybrid"]
    finally:
        holder["loop"].call_soon_threadsafe(holder["stop"].set)
        t.join(timeout=10)
        fe.stop()


def test_stop_drains_inflight_slow_requests():
    """fe.stop() while slow-lane requests are in flight must complete them
    before the loop closes — a cancelled handler would leave its client
    hanging until the gRPC deadline (round-4 review finding)."""
    import concurrent.futures

    from authorino_tpu.evaluators import MetadataConfig

    class SleepyMeta:
        async def call(self, pipeline):
            await asyncio.sleep(1.0)
            return {}

    engine = PolicyEngine(max_batch=16, mesh=None)
    engine.apply_snapshot([EngineEntry(
        id="ns/sleepy2", hosts=["sleepy2.test"],
        runtime=RuntimeAuthConfig(
            identity=[IdentityConfig("anon", Noop())],
            metadata=[MetadataConfig("m", SleepyMeta())]),
        rules=None)])
    fe = NativeFrontend(engine, port=0, max_batch=16, window_us=500)
    port = fe.start()
    stopped = False
    try:
        with concurrent.futures.ThreadPoolExecutor(1) as pool:
            fut = pool.submit(grpc_call, port, make_req("sleepy2.test"))
            deadline = time.monotonic() + 5
            while fe.stats().get("slow", 0) < 1 and time.monotonic() < deadline:
                time.sleep(0.02)
            t0 = time.monotonic()
            fe.stop()
            stopped = True
            # the in-flight request still answers (drained, not cancelled)
            resp = fut.result(timeout=10)
            assert resp.status.code == 0
            assert time.monotonic() - t0 < 8
    finally:
        if not stopped:
            fe.stop()


# (these start a server of their own, so they stand before the module's
# shared `stack`: one C++ server a process at a time)
def _child_value(family, *labels):
    """The child itself, read without the registry: no drain runs."""
    return family.labels(*labels)._value.get()


@pytest.mark.parametrize("how", ["retire", "stop"])
def test_counts_folded_before_retirement_or_stop_are_named(how):
    """A count that sits in a snapshot's arrays when the snapshot is retired,
    or the server stopped, is in its Prometheus child afterwards with no
    scrape in between (the cadence is out of the way)."""
    from authorino_tpu.utils import metrics as metrics_mod

    engine = PolicyEngine(max_batch=64, mesh=None)
    name = f"drained-{how}"
    rule = Pattern("request.headers.x-org", Operator.EQ, "acme")
    entry = make_pattern_entry(engine, f"ns29/{name}", [f"{name}.test"], rule)
    engine.apply_snapshot([entry])
    fe = NativeFrontend(engine, port=0, max_batch=16, window_us=500,
                        lane_select=False)
    fe.hist_drain_s = 3600.0
    port = fe.start()
    try:
        assert fe.wait_warm(300.0)
        rec = fe._cur_rec
        base = _child_value(metrics_mod.authconfig_total, "ns29", name)
        for org in ("acme", "evil", "acme"):
            grpc_call(port, make_req(f"{name}.test", headers={"x-org": org}))
        deadline = time.monotonic() + 10
        while rec.heat.requests.sum() < 3 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert rec.heat.requests.sum() == 3 and rec.heat.ok.sum() == 2
        # in the arrays, not yet in the child
        assert _child_value(metrics_mod.authconfig_total, "ns29", name) == base
        if how == "retire":
            other = make_pattern_entry(engine, "ns29/other", ["other29.test"],
                                       rule)
            engine.apply_snapshot([entry, other])
            wait_for_snap_retire(fe)
    finally:
        fe.stop()
    assert _child_value(metrics_mod.authconfig_total, "ns29", name) == base + 3
    assert _child_value(metrics_mod.authconfig_response_status, "ns29", name,
                        "OK") == 2
    assert _child_value(metrics_mod.authconfig_response_status, "ns29", name,
                        "PERMISSION_DENIED") == 1
    vars_ = fe.debug_vars()
    assert vars_["stages"]["drain"]["count"] >= 1
    assert vars_["post"]["drained_children"] >= 3
    assert vars_["post"]["sampled_decisions"] >= 1


def test_mtls_fast_lane_cert_cache():
    """mTLS identities ride the fast lane too (round 4): the forwarded
    client certificate is the credential key of the verified-credential
    cache — first sight verifies in the slow lane, repeats serve natively,
    subject-based patterns resolve from the cached identity."""
    import urllib.parse

    from test_evaluators import TestMTLS

    from authorino_tpu.k8s import InMemoryCluster

    ca_pem, leaf_pem = TestMTLS()._make_ca_and_cert(valid=True)
    _, rogue_pem = TestMTLS()._make_ca_and_cert(valid=False)
    cluster = InMemoryCluster()
    cluster.put_secret(Secret(name="ca", namespace="ns", labels={"app": "mtls"},
                              data={"ca.crt": ca_pem}))
    mtls = __import__("authorino_tpu.evaluators.identity",
                      fromlist=["MTLS"]).MTLS(
        "mtls", LabelSelector.parse("app=mtls"), cluster=cluster)
    asyncio.run(mtls.load_secrets())

    engine = PolicyEngine(max_batch=16, mesh=None)
    rule = Pattern("auth.identity.Organization", Operator.EQ, "acme")
    pm = PatternMatching(rule, batched_provider=engine.provider_for("ns/mtls"),
                         evaluator_slot=0)
    entries = [
        EngineEntry(
            id="ns/mtls", hosts=["mtls.test"],
            runtime=RuntimeAuthConfig(
                labels={"namespace": "ns", "name": "mtls"},
                identity=[IdentityConfig("mtls", mtls)],
                authorization=[AuthorizationConfig("rules", pm)]),
            rules=ConfigRules(name="ns/mtls", evaluators=[(None, rule)])),
        EngineEntry(  # identity-only: cert validity IS the decision
            id="ns/mtls-only", hosts=["mtls-only.test"],
            runtime=RuntimeAuthConfig(
                labels={"namespace": "ns", "name": "mtls-only"},
                identity=[IdentityConfig("mtls", mtls)]),
            rules=None),
    ]
    engine.apply_snapshot(entries)
    spec = fast_lane_eligible(engine._snapshot.by_id["ns/mtls"],
                              engine._snapshot.policy)
    assert spec is not None and len(spec.sources) == 1
    assert spec.sources[0].dyn and spec.sources[0].cred_kind == 5

    fe = NativeFrontend(engine, port=0, max_batch=16, window_us=500)
    port = fe.start()
    holder, t = run_python_server(engine)
    try:
        def cert_req(host, pem=None):
            req = make_req(host)
            if pem is not None:
                req.attributes.source.certificate = urllib.parse.quote(pem)
            return req

        r1 = grpc_call(port, cert_req("mtls.test", leaf_pem))
        assert r1.status.code == 0
        assert fe.stats()["dyn_add"] >= 1
        r2 = grpc_call(port, cert_req("mtls.test", leaf_pem))
        assert r2.status.code == 0
        assert fe.stats()["dyn_hit"] >= 1
        o1 = grpc_call(port, cert_req("mtls-only.test", leaf_pem))
        o2 = grpc_call(port, cert_req("mtls-only.test", leaf_pem))
        assert o1.status.code == 0 and o2.status.code == 0

        matrix = [
            cert_req("mtls.test", leaf_pem),
            cert_req("mtls.test", rogue_pem),   # unknown authority → slow
            cert_req("mtls.test"),              # missing cert → static unauth
            cert_req("mtls-only.test", leaf_pem),
            cert_req("mtls-only.test"),
        ]
        for i, rq in enumerate(matrix):
            native = response_key(grpc_call(port, rq))
            python = response_key(grpc_call(holder["port"], rq))
            assert native == python, f"mtls req #{i}: {native} vs {python}"

        # CA rotation: the secret reconciler's in-place mutation notifies
        # swap listeners → fresh snapshot, cache dropped, old cert rejected
        new_ca, _ = TestMTLS()._make_ca_and_cert(valid=True)
        mtls.revoke_k8s_secret_based_identity("ns", "ca")
        mtls.add_k8s_secret_based_identity(Secret(
            name="ca", namespace="ns", labels={"app": "mtls"},
            data={"ca.crt": new_ca}))
        engine.notify_swap_listeners()
        wait_for_snap_retire(fe)
        r3 = grpc_call(port, cert_req("mtls.test", leaf_pem))
        assert r3.status.code == 16  # UNAUTHENTICATED: unknown authority now
    finally:
        holder["loop"].call_soon_threadsafe(holder["stop"].set)
        t.join(timeout=10)
        fe.stop()


def test_slow_lane_no_head_of_line_blocking():
    """A straggling slow-lane request (slow metadata backend) must not
    delay unrelated slow-lane requests queued behind it: admission is
    continuous, not batch-gather convoys."""
    import concurrent.futures

    from authorino_tpu.evaluators import MetadataConfig

    class SleepyMeta:
        async def call(self, pipeline):
            await asyncio.sleep(2.5)
            return {}

    engine = PolicyEngine(max_batch=16, mesh=None)
    entries = [
        EngineEntry(
            id="ns/sleepy", hosts=["sleepy.test"],
            runtime=RuntimeAuthConfig(
                identity=[IdentityConfig("anon", Noop())],
                metadata=[MetadataConfig("m", SleepyMeta())]),
            rules=None),
        # quick but slow-lane (templated denyWith)
        make_pattern_entry(
            engine, "ns/quick", ["quick.test"],
            Pattern("request.method", Operator.EQ, "GET"),
            deny_with=DenyWith(unauthorized=DenyWithValues(
                message=JSONValue(pattern="request.path")))),
    ]
    engine.apply_snapshot(entries)
    fe = NativeFrontend(engine, port=0, max_batch=16, window_us=500)
    port = fe.start()
    try:
        with concurrent.futures.ThreadPoolExecutor(2) as pool:
            straggler = pool.submit(grpc_call, port, make_req("sleepy.test"))
            deadline = time.monotonic() + 5
            while fe.stats().get("slow", 0) < 1 and time.monotonic() < deadline:
                time.sleep(0.02)  # straggler admitted into the slow lane
            t0 = time.monotonic()
            quick = grpc_call(port, make_req("quick.test"))
            quick_s = time.monotonic() - t0
            assert quick.status.code == 0
            assert quick_s < 1.5, f"head-of-line blocked: {quick_s:.2f}s"
            assert straggler.result(timeout=10).status.code == 0
    finally:
        fe.stop()


@pytest.fixture(scope="module")
def stack():
    engine = build_engine()
    fe = NativeFrontend(engine, port=0, max_batch=64, window_us=500)
    native_port = fe.start()
    holder, t = run_python_server(engine)
    yield engine, fe, native_port, holder["port"]
    holder["loop"].call_soon_threadsafe(holder["stop"].set)
    t.join(timeout=10)
    fe.stop()


def test_differential_vs_python_server(stack):
    _, fe, native_port, py_port = stack
    for i, req in enumerate(REQUESTS):
        native = response_key(grpc_call(native_port, req))
        python = response_key(grpc_call(py_port, req))
        assert native == python, f"request #{i} diverged: {native} vs {python}"
    stats = fe.stats()
    assert stats["fast"] > 0, "fast lane never engaged"
    assert stats["slow"] > 0, "slow lane never engaged"


def test_debug_vars_reports_what_the_served_kernel_scans(stack):
    """ISSUE 26: snapshot.kernel names the DFA rows one request row has
    scanned (its own config's, D) beside the corpus's (R); the entry is
    the name the trace reader finds the served XLA module by."""
    engine, fe, _, _ = stack
    assert fe.wait_warm(180) and fe.warm_error is None
    policy = engine._snapshot.policy
    R = int(policy.dfa_table_of_row.shape[0]) if policy.n_byte_attrs else 0
    D = int(policy.config_dfa_rows.shape[1]) if policy.n_byte_attrs else 0
    kernel = fe.debug_vars()["snapshot"]["kernel"]
    assert kernel["entry"] == "eval_bitpacked_staged"
    assert kernel["lane"] == "matmul"
    assert kernel["dfa_rows_total"] == R
    assert kernel["dfa_rows_per_row"] == D <= R


@pytest.mark.parametrize("key", ["leaf_cols_per_row", "dfa_rows_per_row",
                                 "dfa_states"])
def test_debug_vars_lists_the_size_classes_and_one_class_reads_the_corpus(
        stack, key):
    """ISSUE 34: snapshot.kernel.classes has one entry a size class; this
    corpus's configs are of one size, so there is one class, its widths are
    the scalars', and the ledger's own_dfa counters say what its launches
    scanned against what the launched rows' own configs have."""
    from authorino_tpu.runtime.kernel_cost import LEDGER

    engine, fe, port, _ = stack
    assert fe.wait_warm(180) and fe.warm_error is None
    policy = engine._snapshot.policy
    kernel = fe.debug_vars()["snapshot"]["kernel"]
    (only,) = kernel["classes"]
    assert len(policy.classes) == 1 and only["configs"] == policy.n_configs
    assert only[key] == kernel[key]
    assert only["cpu_cols"] == policy.n_own_cpu
    assert only["evaluators"] == policy.eval_rule.shape[1]
    assert 0 < only["operand_bytes"] < kernel["operand_bytes"]
    before = LEDGER.snapshot("native")
    assert grpc_call(port, make_req(
        "fast-rx.test", path=f"/api/v1/ok-{key}")).status.code == 0
    after = LEDGER.snapshot("native")
    d = {f: after[f] - before[f] for f in (
        "launches", "batches", "pad_rows", "own_dfa_slots", "own_dfa_rows")}
    # one row, one cut, one launch (or none: lane selection may answer a
    # one-row cut on the host twin, which folds into the `host` lane and
    # launches and scans nothing)
    assert d["launches"] == d["batches"] <= 1
    # a launch scanned D rows a pad row; the row's own config has one
    assert d["own_dfa_slots"] == d["pad_rows"] * kernel["dfa_rows_per_row"]
    assert d["own_dfa_rows"] == d["launches"]


def test_fast_lane_classification(stack):
    engine, _, _, _ = stack
    snap = engine._snapshot
    by_id = snap.by_id
    policy = snap.policy
    assert fast_lane_eligible(by_id["ns/fast-eq"], policy) is not None
    assert fast_lane_eligible(by_id["ns/fast-cond"], policy) is not None
    assert fast_lane_eligible(by_id["ns/fast-rx"], policy) is not None
    assert fast_lane_eligible(by_id["ns/fast-deny"], policy) is not None
    # API-key identity-only: pure credential-map decision, no kernel
    spec = fast_lane_eligible(by_id["ns/fast-keyonly"], policy)
    assert spec is not None and not spec.has_batch
    assert len(spec.sources) == 1 and spec.sources[0].cred_kind == 1
    assert any(k == b"sekret" for k, _, _ in spec.sources[0].variants)
    # API-key + auth.identity.* patterns: per-key K_CONST plan variants
    spec2 = fast_lane_eligible(by_id["ns/fast-key"], policy)
    assert spec2 is not None and spec2.has_batch
    assert spec2.sources[0].cred_kind == 2
    assert spec2.sources[0].cred_key == "x-api-key"
    assert len(spec2.sources[0].variants) == 2
    assert all(vplans for _, vplans, _ in spec2.sources[0].variants)
    # templated denyWith: per-request resolution → slow lane
    assert fast_lane_eligible(by_id["ns/slow-tmpl"], policy) is None
    # mixed pattern + lowered Rego: BOTH evaluators kernel-decided (r5)
    spec3 = fast_lane_eligible(by_id["ns/fast-rego"], policy)
    assert spec3 is not None and spec3.has_batch


def test_lowered_rego_rides_fast_lane(stack):
    """Mixed pattern+Rego traffic must be served natively — zero slow-lane
    handoffs for the lowered config (BASELINE class 5)."""
    _, fe, native_port, _ = stack
    before = fe.stats()
    for hdrs, method in [({"x-tier": "gold"}, "GET"),
                         ({"x-tier": "gold"}, "DELETE"),
                         ({"x-tier": "gold", "x-root": "true"}, "DELETE"),
                         ({"x-tier": "wood"}, "GET")]:
        grpc_call(native_port, make_req("fast-rego.test", method=method,
                                        headers=hdrs))
    after = fe.stats()
    assert after["fast"] - before["fast"] == 4
    assert after["slow"] == before["slow"]


def test_prewarm_covers_bucket_grid(stack):
    """Every (batch_pad, byte_eff) jit variant compiles off the serving
    path at swap time."""
    _, fe, _, _ = stack
    assert fe.wait_warm(180)
    with fe._lock:
        rec = fe._snaps[fe._next_snap_id - 1]
    assert rec.params is not None
    assert set(fe._bucket_grid(rec)) <= rec.warm


def test_warm_time_lowering_failure_is_not_ready(stack, monkeypatch):
    """A kernel that fails to lower/compile for a snapshot's warm grid is a
    swap error visible from outside — wait_warm() False, warm_done never
    true, the compiler's words on /debug/vars and a 503 /readyz — not only
    a logged exception while the degrade path serves exact verdicts."""
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from authorino_tpu.ops import pattern_eval
    from authorino_tpu.service.http_server import build_app

    engine, fe, _, _ = stack
    assert fe.wait_warm(180) and fe.warm_error is None

    def refuse(*a, **k):
        raise NotImplementedError("planted: Mosaic cannot lower this body")

    def readyz():
        async def go():
            async with TestClient(TestServer(
                    build_app(engine, frontend=fe))) as c:
                r = await c.get("/readyz")
                return r.status, await r.text()
        return asyncio.run(go())

    assert readyz()[0] == 200
    try:
        # the swap gate (largest shape, compiled before the swap goes live)
        with monkeypatch.context() as m:
            m.setattr(pattern_eval, "eval_bitpacked_staged_jit", refuse)
            fe.refresh()
        assert fe.wait_warm(30) is False
        snap = fe.debug_vars()["snapshot"]
        assert snap["warm_done"] is False and snap["warm"] == []
        assert "planted: Mosaic cannot lower" in snap["warm_error"]
        status, body = readyz()
        assert status == 503
        assert "native kernel warm failed" in body and "planted" in body

        # the background rest of the grid: the gate compiles, a later
        # shape does not
        real = pattern_eval.eval_bitpacked_staged_jit
        calls = []

        def second_refuses(*a, **k):
            calls.append(1)
            if len(calls) > 1:
                refuse()
            return real(*a, **k)

        with monkeypatch.context() as m:
            m.setattr(pattern_eval, "eval_bitpacked_staged_jit",
                      second_refuses)
            fe.refresh()
            assert fe.wait_warm(60) is False
        snap = fe.debug_vars()["snapshot"]
        assert snap["warm_done"] is False and len(snap["warm"]) == 1
        assert "planted" in snap["warm_error"]
        assert readyz()[0] == 503
    finally:
        fe.refresh()  # a clean snapshot for the tests that follow
    assert fe.wait_warm(180) and fe.warm_error is None
    assert fe.debug_vars()["snapshot"]["warm_done"] is True
    assert readyz()[0] == 200


def test_swap_under_load_never_compiles_on_live_requests(stack):
    """Reconcile swaps with NEW corpus shapes must keep serving from
    warmed jit variants only: the previous snapshot serves until the new
    one's largest bucket is compiled, then dispatch rounds up to warmed
    shapes.  A pick outside rec.warm would be an inline XLA compile on a
    live request, a latency spike on whatever it serves."""
    engine, fe, native_port, _ = stack
    assert fe.wait_warm(180)
    base_entries = list(engine._snapshot.by_id.values())

    picked_unwarmed = []
    orig = fe._pick_warm_shape

    def spy(rec, count, eff):
        out = orig(rec, count, eff)
        if rec.warm and out not in rec.warm:
            picked_unwarmed.append(out)
        return out

    fe._pick_warm_shape = spy
    stop = threading.Event()
    errs, lat = [], []

    def loader():
        with grpc.insecure_channel(f"127.0.0.1:{native_port}") as ch:
            call = ch.unary_unary(
                "/envoy.service.auth.v3.Authorization/Check",
                request_serializer=pb.CheckRequest.SerializeToString,
                response_deserializer=pb.CheckResponse.FromString)
            req = make_req("fast-eq.test", headers={"x-org": "acme"})
            while not stop.is_set():
                t0 = time.monotonic()
                try:
                    call(req, timeout=60)
                except Exception as e:  # noqa: BLE001
                    errs.append(e)
                    return
                lat.append(time.monotonic() - t0)

    t = threading.Thread(target=loader)
    t.start()
    try:
        time.sleep(0.3)
        for i in range(2):
            # a brand-new selector changes the operand shapes → the swap
            # gate must compile the new variants before going live
            extra = make_pattern_entry(
                engine, f"ns/extra-{i}", [f"extra-{i}.test"],
                Pattern(f"request.headers.x-fresh-{i}", Operator.EQ, "v"))
            engine.apply_snapshot(base_entries + [extra])
            time.sleep(0.3)
        assert fe.wait_warm(180)
        time.sleep(0.5)
    finally:
        stop.set()
        t.join(20)
        fe._pick_warm_shape = orig
        engine.apply_snapshot(base_entries)  # restore the module corpus
        wait_for_snap_retire(fe)
    assert not errs
    assert len(lat) > 20
    assert not picked_unwarmed, f"inline compiles on live requests: {picked_unwarmed}"
    lat.sort()
    assert lat[int(len(lat) * 0.99)] < 5.0


def test_api_key_rotation_rebuilds_fast_lane(stack):
    """Live add/revoke of an API key (the secret reconciler's in-place
    mutation, ref controllers/secret_controller.go:108-130) must rebuild the
    C++ credential variants via the swap-listener notification."""
    engine, fe, native_port, _ = stack
    ev = engine._snapshot.by_id["ns/fast-keyonly"].runtime.identity[0].evaluator
    ev.add_k8s_secret_based_identity(Secret(
        namespace="ns", name="k2", labels={"g": "t"}, data={"api_key": b"fresh"}))
    engine.notify_swap_listeners()
    wait_for_snap_retire(fe)
    ok = grpc_call(native_port,
                   make_req("slow-key.test", headers={"authorization": "APIKEY fresh"}))
    assert ok.status.code == 0
    ev.revoke_k8s_secret_based_identity("ns", "k2")
    engine.notify_swap_listeners()
    wait_for_snap_retire(fe)
    deny = grpc_call(native_port,
                     make_req("slow-key.test", headers={"authorization": "APIKEY fresh"}))
    assert deny.status.code == 16  # UNAUTHENTICATED
    stats = fe.stats()
    assert stats["direct_ok"] > 0 and stats["unauth"] > 0


def test_dfa_overflow_rides_fast_lane(stack):
    """Values longer than the device byte tensor run the same DFA on the
    C++ host — still the fast lane, still exact."""
    _, fe, native_port, py_port = stack
    before = fe.stats()["dfa_overflow"]
    req = make_req("fast-rx.test", path="/api/v1/ok" + "b" * 300)
    assert response_key(grpc_call(native_port, req)) == response_key(grpc_call(py_port, req))
    assert fe.stats()["dfa_overflow"] > before


def test_health_and_unimplemented(stack):
    _, _, native_port, _ = stack
    hreq = protos.health_pb2.HealthCheckRequest()
    with grpc.insecure_channel(f"127.0.0.1:{native_port}") as ch:
        health = ch.unary_unary(
            "/grpc.health.v1.Health/Check",
            request_serializer=hreq.SerializeToString,
            response_deserializer=protos.health_pb2.HealthCheckResponse.FromString,
        )(hreq, timeout=10)
        assert health.status == protos.health_pb2.HealthCheckResponse.SERVING
        with pytest.raises(grpc.RpcError) as err:
            ch.unary_unary(
                "/envoy.service.auth.v3.Authorization/Nope",
                request_serializer=pb.CheckRequest.SerializeToString,
                response_deserializer=pb.CheckResponse.FromString,
            )(make_req("fast-eq.test"), timeout=10)
        assert err.value.code() == grpc.StatusCode.UNIMPLEMENTED


def test_invalid_request(stack):
    """CheckRequest without http attributes → INVALID_ARGUMENT CheckResponse
    (ref pkg/service/auth.go:242-255)."""
    _, _, native_port, py_port = stack
    req = pb.CheckRequest()
    assert response_key(grpc_call(native_port, req)) == response_key(grpc_call(py_port, req))


def test_snapshot_swap_retires_old(stack):
    engine, fe, native_port, _ = stack
    rule = Pattern("request.headers.x-new", Operator.EQ, "v2")
    cfg_id = "ns/swapped"
    pm = PatternMatching(rule, batched_provider=engine.provider_for(cfg_id),
                         evaluator_slot=0)
    runtime = RuntimeAuthConfig(identity=[IdentityConfig("anon", Noop())],
                                authorization=[AuthorizationConfig("rules", pm)])
    old_entries = list(engine._snapshot.by_id.values())
    engine.apply_snapshot(old_entries + [
        EngineEntry(id=cfg_id, hosts=["swapped.test"], runtime=runtime,
                    rules=ConfigRules(name=cfg_id, evaluators=[(None, rule)]))])
    resp = grpc_call(native_port, make_req("swapped.test", headers={"x-new": "v2"}))
    assert resp.status.code == 0
    resp = grpc_call(native_port, make_req("swapped.test", headers={"x-new": "v1"}))
    assert resp.status.code == 7
    # old snapshots retire once their batches drain
    wait_for_snap_retire(fe)


def test_swap_storm_under_load(stack):
    """Reconcile-time snapshot swaps must never drop or corrupt in-flight
    wire traffic: fire concurrent Check()s at a config that is identical in
    every snapshot while the engine swaps corpora repeatedly; every
    response must stay deterministic and old snapshots must all retire."""
    engine, fe, native_port, _ = stack
    base_entries = list(engine._snapshot.by_id.values())

    errors = []
    done = threading.Event()
    counts = {"ok": 0, "deny": 0}

    def worker(allow: bool):
        req = make_req("fast-eq.test",
                       headers={"x-org": "acme" if allow else "evil"})
        with grpc.insecure_channel(f"127.0.0.1:{native_port}") as ch:
            call = ch.unary_unary(
                "/envoy.service.auth.v3.Authorization/Check",
                request_serializer=pb.CheckRequest.SerializeToString,
                response_deserializer=pb.CheckResponse.FromString)
            while not done.is_set():
                try:
                    resp = call(req, timeout=10)
                    want = 0 if allow else 7
                    if resp.status.code != want:
                        errors.append((allow, resp.status.code))
                    counts["ok" if allow else "deny"] += 1
                except Exception as e:  # noqa: BLE001
                    errors.append((allow, repr(e)))

    threads = [threading.Thread(target=worker, args=(i % 2 == 0,))
               for i in range(4)]
    for t in threads:
        t.start()
    # churn: each swap adds/removes a throwaway config; fast-eq is identical
    # in every snapshot so worker expectations never change
    for i in range(10):
        extra = []
        if i % 2 == 0:
            rule = Pattern("request.headers.x-tmp", Operator.EQ, f"v{i}")
            cfg_id = f"ns/tmp-{i}"
            pm = PatternMatching(rule, batched_provider=engine.provider_for(cfg_id),
                                 evaluator_slot=0)
            extra = [EngineEntry(
                id=cfg_id, hosts=[f"tmp-{i}.test"],
                runtime=RuntimeAuthConfig(
                    identity=[IdentityConfig("anon", Noop())],
                    authorization=[AuthorizationConfig("rules", pm)]),
                rules=ConfigRules(name=cfg_id, evaluators=[(None, rule)]))]
        engine.apply_snapshot(base_entries + extra)
        time.sleep(0.05)
    time.sleep(0.3)
    done.set()
    for t in threads:
        t.join(timeout=20)

    assert not errors, errors[:5]
    assert counts["ok"] > 5 and counts["deny"] > 5, counts
    # every superseded snapshot drains and retires
    wait_for_snap_retire(fe)


def test_fast_lane_metrics_labeled_per_config(stack):
    """Fast-lane decisions bump auth_server_authconfig_* with the SAME
    namespace/name labels the pipeline uses (ref auth_pipeline.go:26-36)."""
    prom = pytest.importorskip("prometheus_client")

    def sample(name, labels):
        v = prom.REGISTRY.get_sample_value(name, labels)
        return v or 0.0

    _, fe, native_port, _ = stack
    # the tests before this one left batches whose `post` ends after their
    # answers: let them land before the base is read
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        stages = fe.batch_stages.totals()
        if (not fe._rb_inflight
                and stages["post"]["count"] == stages["resolve"]["count"]):
            break
        time.sleep(0.01)
    base_total = sample("auth_server_authconfig_total",
                        {"namespace": "ns", "authconfig": "fast-eq"})
    base_ok = sample("auth_server_authconfig_response_status_total",
                     {"namespace": "ns", "authconfig": "fast-eq", "status": "OK"})
    base_deny = sample("auth_server_authconfig_response_status_total",
                       {"namespace": "ns", "authconfig": "fast-eq",
                        "status": "PERMISSION_DENIED"})
    for org in ("acme", "evil", "acme"):
        grpc_call(native_port, make_req("fast-eq.test", headers={"x-org": org}))
    # the readback thread keeps a cut's telemetry after completing the batch
    # and, with no other cut in flight, folds it at once (ISSUE 35) — the last
    # response can reach the client a beat before its own increment lands
    deadline = time.monotonic() + 10
    while (sample("auth_server_authconfig_total",
                  {"namespace": "ns", "authconfig": "fast-eq"}) < base_total + 3
           and time.monotonic() < deadline):
        time.sleep(0.05)
    assert sample("auth_server_authconfig_total",
                  {"namespace": "ns", "authconfig": "fast-eq"}) == base_total + 3
    assert sample("auth_server_authconfig_response_status_total",
                  {"namespace": "ns", "authconfig": "fast-eq", "status": "OK"}) == base_ok + 2
    assert sample("auth_server_authconfig_response_status_total",
                  {"namespace": "ns", "authconfig": "fast-eq",
                   "status": "PERMISSION_DENIED"}) == base_deny + 1


def test_hostile_wire_input(stack):
    """A hand-rolled wire must survive hostile bytes: raw garbage, a valid
    preface followed by junk, truncated frames, an abortive RST close, and
    a well-formed stream carrying a corrupt protobuf — all without taking
    the server down or wedging later traffic."""
    import socket
    import struct

    _, fe, native_port, _ = stack

    def tcp(payload, linger=0.2, rst=False):
        s = socket.create_connection(("127.0.0.1", native_port), timeout=5)
        try:
            s.sendall(payload)
            time.sleep(linger)
            if rst:  # abortive close: RST instead of FIN
                s.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                             struct.pack("ii", 1, 0))
        finally:
            s.close()

    parse_errors_before = fe.stats()["parse_errors"]
    preface = b"PRI * HTTP/2.0\r\n\r\nSM\r\n\r\n"
    tcp(b"GET / HTTP/1.1\r\nHost: x\r\n\r\n")          # not HTTP/2 at all
    tcp(preface + b"\x00\x00\x00\x04\x00\x00\x00\x00\x00", rst=True)  # RST mid-session
    tcp(b"\x00" * 64)                                   # binary garbage
    tcp(preface + b"\xff" * 32)                         # preface then junk
    tcp(preface + b"\x00\x00\x04\x04\x00\x00\x00\x00")  # truncated SETTINGS
    # valid h2 session carrying a corrupt gRPC message: hand-rolled HEADERS
    # (literal :path to Check) + DATA with a non-protobuf body
    hp = (b"\x83\x86"                                    # :method POST, :scheme http
          + b"\x04" + bytes([len(b"/envoy.service.auth.v3.Authorization/Check")])
          + b"/envoy.service.auth.v3.Authorization/Check"
          + b"\x01\x01a")                                # :authority "a"
    frames = (preface
              + b"\x00\x00\x00\x04\x00\x00\x00\x00\x00"  # empty SETTINGS
              + len(hp).to_bytes(3, "big") + b"\x01\x04" + (1).to_bytes(4, "big") + hp
              + (10).to_bytes(3, "big") + b"\x00\x01" + (1).to_bytes(4, "big")
              + b"\x00" + (5).to_bytes(4, "big") + b"\xde\xad\xbe\xef\x99")
    tcp(frames, linger=0.5)

    # the corrupt protobuf actually reached the decoder (else this test
    # silently stops covering its key scenario)
    assert fe.stats()["parse_errors"] > parse_errors_before
    # the server still answers correctly afterwards
    resp = grpc_call(native_port, make_req("fast-eq.test", headers={"x-org": "acme"}))
    assert resp.status.code == 0


def test_duration_and_stage_histograms(stack):
    """The fast lane must feed the SAME duration series the pipeline
    observes (auth_server_authconfig_duration_seconds) plus the on-box
    stage histograms (enqueue→flush→complete→respond)."""
    _, fe, native_port, _ = stack
    for _ in range(40):
        grpc_call(native_port, make_req("fast-eq.test", headers={"x-org": "acme"}))
    grpc_call(native_port, make_req("slow-key.test",
                                    headers={"authorization": "APIKEY sekret"}))
    fe.drain_histograms()
    # on-box stages recorded for every batched fast request
    for stage in ("wait", "exec", "respond"):
        assert sum(fe.stage_totals[stage]) > 0, f"stage {stage} never recorded"
    # prometheus series carries the fast-lane durations per authconfig
    from prometheus_client import REGISTRY

    samples = {
        (s.labels.get("namespace"), s.labels.get("authconfig")): s.value
        for m in REGISTRY.collect()
        if m.name == "auth_server_authconfig_duration_seconds"
        for s in m.samples if s.name.endswith("_count")
    }
    assert samples.get(("ns", "fast-eq"), 0) >= 40
    # direct decisions (identity-only API key) are clocked too
    assert samples.get(("ns", "fast-keyonly"), 0) >= 1


def test_observe_bucketed_fallback_preserves_shape():
    """If prometheus_client internals (`_buckets`/`_sum`) ever vanish, the
    fallback must keep per-bucket counts (incl. +Inf overflow binned ABOVE
    the last finite bound) and land the exact drained sum — not collapse to
    one mean observation."""
    from authorino_tpu.utils import metrics as metrics_mod

    class FakeChild:
        _upper_bounds = [0.001, 0.01, 0.1, float("inf")]

        def __init__(self):
            self.observed = []

        def observe(self, v):
            self.observed.append(v)

    child = FakeChild()
    # counts per bucket: 5 in (0,1ms], 3 in (1,10ms], 0, 2 overflow
    metrics_mod.observe_bucketed(child, [5, 3, 0, 2], sum_seconds=0.5)
    assert len(child.observed) == 10
    binned = [0, 0, 0, 0]
    for v in child.observed:
        for i, b in enumerate(FakeChild._upper_bounds):
            if v <= b:
                binned[i] += 1
                break
    assert binned == [5, 3, 0, 2]  # overflow NOT folded into le=0.1
    assert abs(sum(child.observed) - 0.5) < 1e-9


def test_randomized_differential_sweep(stack):
    """300 seeded-random requests across the module corpus — hosts (exact,
    wildcard, ports, overrides, unknown), methods, paths (regex lane,
    overflow lengths), credentials (valid/invalid/missing, all locations),
    random extra headers — every response byte-compared field-for-field
    against the Python server."""
    import random

    _, fe, native_port, py_port = stack
    rng = random.Random(20260730)
    hosts = ["fast-eq.test", "fast-cond.test", "fast-rx.test",
             "fast-deny.test", "slow-key.test", "fast-key.test",
             "cookie-key.test", "query-key.test", "slow-tmpl.test",
             "a.wild.test", "deep.a.wild.test", "wild.test", "unknown.test",
             "fast-eq.test:8080", "fast-rego.test"]
    methods = ["GET", "POST", "DELETE", "OPTIONS"]
    creds = [None, "APIKEY sekret", "APIKEY wrong", "Bearer sekret",
             "APIKEY", ""]
    cookies = [None, "ses=c0ffee", "a=1; ses=c0ffee", "ses=wrong", "x=1"]
    paths = ["/", "/api/v1/ok", "/api/v12/ok?q=1", "/api/nope",
             "/api/v2/ok" + "z" * 150, "/hello?tok=c0ffee",
             "/hello?tok=bad&x=1", "/x#frag", "/%20esc"]

    mismatches = []
    for i in range(300):
        headers = {}
        if rng.random() < 0.6:
            c = rng.choice(creds)
            if c is not None:
                headers["authorization"] = c
        if rng.random() < 0.4:
            ck = rng.choice(cookies)
            if ck is not None:
                headers["cookie"] = ck
        if rng.random() < 0.5:
            headers[f"x-attr-{rng.randrange(3)}"] = f"v{rng.randrange(5)}"
        if rng.random() < 0.3:
            headers["x-org"] = rng.choice(["acme", "evil", ""])
        if rng.random() < 0.3:
            headers["x-api-key"] = rng.choice(["adminkey", "userkey", "no"])
        if rng.random() < 0.2:
            headers["x-role"] = rng.choice(["admin", "user"])
        if rng.random() < 0.2:
            headers["x-pass"] = rng.choice(["yes", "no"])
        if rng.random() < 0.3:
            headers["x-tier"] = rng.choice(["gold", "wood", ""])
        if rng.random() < 0.3:
            headers["x-root"] = rng.choice(["true", "false", "TRUE", ""])
        ctx = ({"host": rng.choice(hosts[:4])}
               if rng.random() < 0.1 else None)
        req = make_req(rng.choice(hosts), method=rng.choice(methods),
                       path=rng.choice(paths), headers=headers, ctx=ctx)
        native = response_key(grpc_call(native_port, req))
        python = response_key(grpc_call(py_port, req))
        if native != python:
            mismatches.append((i, native, python))
    assert not mismatches, f"{len(mismatches)} diverged, first: {mismatches[0]}"


# ---------------------------------------------------------------------------
# ISSUE 29: `post` records per config into arrays; the drain names them
# ---------------------------------------------------------------------------


def _pinned_lane(path):
    """A frontend with one hand-built snapshot record of 256 configs and a
    256-row batch slot, without the server: what `plan` and `post` read."""
    import types

    import numpy as np

    from authorino_tpu.runtime import provenance as prov_mod
    from authorino_tpu.runtime.native_frontend import _SnapRec

    G = B = 256
    engine = PolicyEngine(max_batch=64, mesh=None)
    fe = NativeFrontend(engine, port=0, max_batch=B, slo_ms=250.0)
    fe._mod = types.SimpleNamespace(fe_complete_batch=lambda *a: None)
    sharded = None
    if path == "sharded":
        sharded = types.SimpleNamespace(configs_per_shard=G // 2)
    heat = prov_mod.HeatMap(
        [f"pin-{path}/c{i}" for i in range(G)], [["r0", "r1"]] * G, 2,
        configs_per_shard=G // 2 if sharded else None)
    keys = ([(s, r) for s in range(2) for r in range(G // 2)] if sharded
            else list(range(G)))
    labels = {key: (f"pin-{path}", f"c{i}") for i, key in enumerate(keys)}
    heat.bind_authconfigs(labels, hybrid=keys[::7])
    rec = _SnapRec(snap_id=1, policy=None, params=None, encoder=None,
                   sharded=sharded, heat=heat, row_labels=labels,
                   hybrid_rows=set(keys[::7]),
                   cacheable=np.ones((2, G // 2) if sharded else (G,), bool))
    a = {"config_id": np.zeros((B,), np.int32),
         "attrs_val": np.zeros((B, 4), np.int16),
         "members": np.zeros((B, 1, 2), np.int16),
         "cpu_dense": np.zeros((B, 2), np.uint8),
         "attr_bytes": np.zeros((B, 1, 64), np.uint8),
         "byte_ovf": np.zeros((B, 1), np.uint8),
         "byte_used": np.zeros((B,), np.uint16),
         "dfa_bytes": np.zeros((B, 2), np.uint32)}
    if sharded:
        a["shard_of"] = np.zeros((B,), np.int32)
    rec.arrays.append(a)
    fe._bind_cache_keys(rec, None)
    rng = np.random.default_rng(29)

    def plan_cut():
        """Encode one cut of the 256 configs, shuffled, and plan it."""
        flat = rng.permutation(G)
        rows = (flat % (G // 2) if sharded else flat).astype(np.int32)
        shards = (flat // (G // 2)).astype(np.int32) if sharded else None
        a["config_id"][:] = rows
        if sharded:
            a["shard_of"][:] = shards
        return rows, shards, fe._dedup_plan(rec, 0, B, rows, shards)

    def complete(rows, shards, fan):
        cols = np.zeros((B, 8), dtype=bool)
        denied = rng.random(B) < 0.5
        cols[:, 0] = ~denied
        cols[:, 1] = ~denied         # rule 0 false = it fires
        cols[:, 2] = True
        packed = np.packbits(cols, axis=1, bitorder="little")
        bt = fe.batch_stages.begin(1, 0, B)
        bt.ready()
        fe._complete_device_batch(rec, 1, 0, B, B, 0, rows, shards, packed,
                                  time.monotonic(), time.time_ns(), fan, 0, bt)

    return fe, rec, heat, labels, keys, plan_cut, complete


@pytest.mark.perf_guard
@pytest.mark.parametrize("path", ["device", "host-lane", "sharded"])
def test_post_runs_no_per_config_python(path, monkeypatch):
    """Structural pin, in the style of the zero-per-request-Python guards:
    `post` of a 256-row batch over 256 distinct configs mints and looks up
    no Prometheus child (`.labels`), makes no `VerdictCache.put` and no
    decision record once the tenants' first sightings are behind it, on the
    device path, the host-lane path and the sharded path."""
    import numpy as np
    from prometheus_client.metrics import MetricWrapperBase

    from authorino_tpu.runtime import provenance as prov_mod
    from authorino_tpu.utils.verdict_cache import VerdictCache

    G = B = 256
    fe, rec, heat, labels, keys, plan_cut, complete = _pinned_lane(path)
    rng = np.random.default_rng(29)
    # two cuts of the same 256 rows in flight, planned while the cache is
    # empty: the first's `post` inserts them, the second's refreshes them
    cuts = [plan_cut() for _ in range(2)] if path != "host-lane" else []

    def one_batch():
        if path != "host-lane":
            complete(*cuts.pop(0))
            return
        rows = rng.permutation(G)
        denied = rng.random(B) < 0.5
        fe._post_complete_telemetry(
            rec, B, 0, 0, rows, None, (~denied).astype(np.uint8), 0.001,
            time.time_ns(), device_rows=0, device=False,
            firing=np.where(denied, 0, -1).astype(np.int32))

    one_batch()  # first sightings sample; per-batch children are minted
    calls = {"labels": 0, "put": 0, "record": 0}

    me = threading.get_ident()

    def counting(name, real):
        def wrapper(*a, **k):
            # this thread's calls: another server's housekeeping thread may
            # drain meanwhile, and a drain is where `.labels` belongs
            calls[name] += threading.get_ident() == me
            return real(*a, **k)
        return wrapper

    monkeypatch.setattr(MetricWrapperBase, "labels",
                        counting("labels", MetricWrapperBase.labels))
    monkeypatch.setattr(VerdictCache, "put", counting("put", VerdictCache.put))
    monkeypatch.setattr(prov_mod.DECISIONS, "record",
                        counting("record", prov_mod.DECISIONS.record))
    posts = fe.batch_stages.totals()["post"]["count"]
    one_batch()
    assert calls == {"labels": 0, "put": 0, "record": 0}
    monkeypatch.undo()
    if path != "host-lane":
        assert fe.batch_stages.totals()["post"]["count"] == posts + 1
        assert fe._verdict_cache.counts() == {
            "hits": 0, "misses": 2 * B, "adds": B, "evictions": 0,
            "entries": B}
    # and the drain names all of it: every non-hybrid row counted twice,
    # a hybrid row once a denial
    from authorino_tpu.utils import metrics as metrics_mod

    metrics_mod.drain()
    total = sum(_child_value(metrics_mod.authconfig_total, *labels[key])
                for key in keys)
    assert total == heat.requests.sum() > B
    assert fe.tenancy.stats.to_json()["tenants_seen"] >= G


@pytest.mark.perf_guard
@pytest.mark.parametrize("path", ["device", "sharded"])
def test_plan_and_post_run_no_per_row_cache_python(path):
    """ISSUE 33's structural pin: a 256-row cut through `_dedup_plan` and
    `_complete_device_batch` builds no `bytes` a row (`ndarray.tobytes`),
    calls no `VerdictCache.get` / `put` / `put_many`, no `row_key_bytes` and
    no `dedup_rows`; `plan` as a whole makes fewer calls than a tenth of the
    cut has rows.  Counted by the interpreter's profile hook, which sees
    every Python and C function this thread calls."""
    import sys

    from authorino_tpu.compiler import pack
    from authorino_tpu.utils.verdict_cache import VerdictCache

    B = 256
    fe, rec, heat, labels, keys, plan_cut, complete = _pinned_lane(path)
    complete(*plan_cut())  # first sightings; the cache now holds the 256 rows
    forbidden = {f.__code__: f.__qualname__ for f in (
        VerdictCache.get, VerdictCache.put, VerdictCache.put_many,
        VerdictCache._put, pack.row_key_bytes, pack.dedup_rows)}
    seen = {"tobytes": 0, "calls": 0}

    def hook(frame, event, arg):
        if event == "call":
            seen["calls"] += 1
            name = forbidden.get(frame.f_code)
            if name is not None:
                seen[name] = seen.get(name, 0) + 1
        elif event == "c_call":
            seen["calls"] += 1
            seen["tobytes"] += getattr(arg, "__name__", "") == "tobytes"

    def profiled(fn, *args):
        seen["calls"] = 0
        sys.setprofile(hook)
        try:
            return fn(*args), seen["calls"]
        finally:
            sys.setprofile(None)

    # the hit path: every row answered by the cache, with its LRU move
    hit, hit_calls = profiled(plan_cut)
    assert len(hit[2].cached_rows) == B and len(hit[2].unique_rows) == 0
    # the miss path: new rows (another attribute value), inserted by `post`
    rec.arrays[0]["attrs_val"][:, 0] = 1
    miss, miss_calls = profiled(plan_cut)
    assert len(miss[2].unique_rows) == miss[2].eligible_misses == B
    profiled(complete, *miss)
    assert seen == {"tobytes": 0, "calls": seen["calls"]}
    assert hit_calls < B // 10 and miss_calls < B // 10
    assert fe._verdict_cache.counts() == {
        "hits": B, "misses": 2 * B, "adds": 2 * B, "evictions": 0,
        "entries": 2 * B}
