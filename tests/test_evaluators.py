"""Leaf evaluator tests with fake in-process backends (the reference's
pkg/httptest style: local HTTP servers faking Keycloak/UMA/registries;
SURVEY.md §4)."""

import asyncio
import base64
import json

import pytest
from aiohttp import web
from cryptography.hazmat.primitives import serialization
from cryptography.hazmat.primitives.asymmetric import ec, rsa

from authorino_tpu.authjson import (
    CheckRequestModel,
    HttpRequestAttributes,
    JSONProperty,
    JSONValue,
    PeerAttributes,
)
from authorino_tpu.evaluators import EvaluationError, IdentityConfig, RuntimeAuthConfig
from authorino_tpu.evaluators.authorization import OPA
from authorino_tpu.evaluators.authorization.rego import RegoError, compile_module
from authorino_tpu.evaluators.identity import APIKey, KubernetesAuth, MTLS, Noop, OAuth2, OIDC
from authorino_tpu.evaluators.metadata import GenericHttp, UserInfo
from authorino_tpu.evaluators.response import SigningKey, Wristband
from authorino_tpu.evaluators.credentials import AuthCredentials
from authorino_tpu.k8s import InMemoryCluster, LabelSelector, Secret
from authorino_tpu.pipeline import AuthPipeline
from authorino_tpu.utils import jose


def run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


def make_pipeline(headers=None, source_cert="", identity=None):
    req = CheckRequestModel(
        http=HttpRequestAttributes(
            method="GET", path="/x", host="svc.example.com", headers=headers or {}
        ),
        source=PeerAttributes(certificate=source_cert),
    )
    p = AuthPipeline(req, RuntimeAuthConfig())
    if identity is not None:
        conf = IdentityConfig("test", Noop())
        p.identity_results[conf] = identity
        p._sync_auth()
    return p


class TestAPIKey:
    def _cluster(self):
        cluster = InMemoryCluster()
        cluster.put_secret(
            Secret(
                name="app-1-key",
                namespace="ns",
                labels={"audience": "app"},
                data={"api_key": b"ndyBzreUzF4zqDQsqSPMHkRhriEOtcRx"},
            )
        )
        return cluster

    def test_valid_and_invalid_key(self):
        ak = APIKey("api-key", LabelSelector.parse("audience=app"), cluster=self._cluster())
        run(ak.load_secrets())
        p = make_pipeline(headers={"authorization": "APIKEY ndyBzreUzF4zqDQsqSPMHkRhriEOtcRx"})
        ak.credentials = AuthCredentials(key_selector="APIKEY")
        obj = run(ak.call(p))
        assert obj["metadata"]["name"] == "app-1-key"
        p2 = make_pipeline(headers={"authorization": "APIKEY wrong"})
        with pytest.raises(EvaluationError, match="invalid"):
            run(ak.call(p2))

    def test_live_rotation(self):
        cluster = self._cluster()
        ak = APIKey("api-key", LabelSelector.parse("audience=app"), cluster=cluster)
        run(ak.load_secrets())
        ak.credentials = AuthCredentials(key_selector="APIKEY")
        # revoke (ref secret_controller.go:100-106)
        ak.revoke_k8s_secret_based_identity("ns", "app-1-key")
        with pytest.raises(EvaluationError):
            run(ak.call(make_pipeline(headers={"authorization": "APIKEY ndyBzreUzF4zqDQsqSPMHkRhriEOtcRx"})))
        # add a rotated key
        ak.add_k8s_secret_based_identity(
            Secret(name="app-1-key", namespace="ns", labels={"audience": "app"}, data={"api_key": b"new-key"})
        )
        obj = run(ak.call(make_pipeline(headers={"authorization": "APIKEY new-key"})))
        assert obj["metadata"]["name"] == "app-1-key"


class TestMTLS:
    def _make_ca_and_cert(self, valid=True):
        from datetime import datetime, timedelta, timezone

        from cryptography import x509
        from cryptography.hazmat.primitives import hashes
        from cryptography.x509.oid import NameOID

        ca_key = ec.generate_private_key(ec.SECP256R1())
        ca_name = x509.Name([x509.NameAttribute(NameOID.COMMON_NAME, "test-ca")])
        now = datetime.now(timezone.utc)
        ca_cert = (
            x509.CertificateBuilder()
            .subject_name(ca_name)
            .issuer_name(ca_name)
            .public_key(ca_key.public_key())
            .serial_number(x509.random_serial_number())
            .not_valid_before(now - timedelta(days=1))
            .not_valid_after(now + timedelta(days=30))
            .add_extension(x509.BasicConstraints(ca=True, path_length=None), critical=True)
            .sign(ca_key, hashes.SHA256())
        )
        signer = ca_key if valid else ec.generate_private_key(ec.SECP256R1())
        leaf_key = ec.generate_private_key(ec.SECP256R1())
        leaf = (
            x509.CertificateBuilder()
            .subject_name(
                x509.Name(
                    [
                        x509.NameAttribute(NameOID.COMMON_NAME, "john"),
                        x509.NameAttribute(NameOID.ORGANIZATION_NAME, "acme"),
                    ]
                )
            )
            .issuer_name(ca_name)
            .public_key(leaf_key.public_key())
            .serial_number(x509.random_serial_number())
            .not_valid_before(now - timedelta(hours=1))
            .not_valid_after(now + timedelta(days=1))
            .sign(signer, hashes.SHA256())
        )
        ca_pem = ca_cert.public_bytes(serialization.Encoding.PEM)
        leaf_pem = leaf.public_bytes(serialization.Encoding.PEM).decode()
        return ca_pem, leaf_pem

    def test_verify_subject(self):
        ca_pem, leaf_pem = self._make_ca_and_cert(valid=True)
        cluster = InMemoryCluster()
        cluster.put_secret(Secret(name="ca", namespace="ns", labels={"app": "mtls"}, data={"ca.crt": ca_pem}))
        m = MTLS("mtls", LabelSelector.parse("app=mtls"), cluster=cluster)
        run(m.load_secrets())
        obj = run(m.call(make_pipeline(source_cert=leaf_pem)))
        assert obj["CommonName"] == "john"
        assert obj["Organization"] == "acme"

    def test_unknown_authority(self):
        ca_pem, _ = self._make_ca_and_cert(valid=True)
        _, rogue_pem = self._make_ca_and_cert(valid=False)
        cluster = InMemoryCluster()
        cluster.put_secret(Secret(name="ca", namespace="ns", labels={"app": "mtls"}, data={"ca.crt": ca_pem}))
        m = MTLS("mtls", LabelSelector.parse("app=mtls"), cluster=cluster)
        run(m.load_secrets())
        with pytest.raises(EvaluationError, match="unknown authority"):
            run(m.call(make_pipeline(source_cert=rogue_pem)))
        with pytest.raises(EvaluationError, match="missing"):
            run(m.call(make_pipeline()))


class FakeIdP:
    """Fake Keycloak-ish IdP: discovery, JWKS, userinfo, introspection."""

    def __init__(self):
        self.key = rsa.generate_private_key(public_exponent=65537, key_size=2048)
        self.issuer = None
        self.userinfo = {"sub": "john", "email": "john@acme.com"}
        self.active_tokens = {"opaque-token-1": {"active": True, "username": "john"}}

    def token(self, claims=None):
        iat = int(__import__("time").time())
        payload = {"iss": self.issuer, "sub": "john", "iat": iat, "exp": iat + 300,
                   "realm_access": {"roles": ["admin"]}}
        payload.update(claims or {})
        return jose.sign_jwt(payload, self.key, "RS256", kid="k1")

    def app(self):
        app = web.Application()

        async def well_known(request):
            return web.json_response(
                {
                    "issuer": self.issuer,
                    "jwks_uri": f"{self.issuer}/jwks",
                    "userinfo_endpoint": f"{self.issuer}/userinfo",
                    "token_endpoint": f"{self.issuer}/token",
                }
            )

        async def jwks(request):
            return web.json_response({"keys": [jose.jwk_from_public_key(self.key.public_key(), kid="k1")]})

        async def userinfo(request):
            return web.json_response(self.userinfo)

        async def introspect(request):
            form = await request.post()
            return web.json_response(self.active_tokens.get(form.get("token"), {"active": False}))

        app.router.add_get("/.well-known/openid-configuration", well_known)
        app.router.add_get("/jwks", jwks)
        app.router.add_get("/userinfo", userinfo)
        app.router.add_post("/introspect", introspect)
        return app


def with_fake_idp(test_body):
    async def scenario():
        from aiohttp.test_utils import TestServer

        idp = FakeIdP()
        server = TestServer(idp.app())
        await server.start_server()
        idp.issuer = str(server.make_url("")).rstrip("/")
        try:
            await test_body(idp)
        finally:
            await server.close()
            from authorino_tpu.utils.http import close_sessions

            await close_sessions()

    run(scenario())


class TestOIDC:
    def test_jwt_verify_and_claims(self):
        async def body(idp):
            oidc = OIDC("keycloak", idp.issuer)
            token = idp.token()
            p = make_pipeline(headers={"authorization": f"Bearer {token}"})
            claims = await oidc.call(p)
            assert claims["sub"] == "john"
            assert claims["realm_access"]["roles"] == ["admin"]
            # tampered token denied
            bad = token[:-4] + "AAAA"
            with pytest.raises(EvaluationError):
                await oidc.call(make_pipeline(headers={"authorization": f"Bearer {bad}"}))
            # expired token denied
            expired = idp.token({"exp": 1})
            with pytest.raises(EvaluationError, match="expired"):
                await oidc.call(make_pipeline(headers={"authorization": f"Bearer {expired}"}))
            await oidc.clean()

        with_fake_idp(body)

    def test_userinfo_bound_to_same_issuer(self):
        async def body(idp):
            oidc = OIDC("keycloak", idp.issuer)
            ui = UserInfo(oidc)
            token = idp.token()
            p = make_pipeline(headers={"authorization": f"Bearer {token}"})
            conf = IdentityConfig("keycloak", oidc)
            p.identity_results[conf] = await oidc.call(p)
            p._sync_auth()
            data = await ui.call(p)
            assert data["email"] == "john@acme.com"
            # identity resolved by a different evaluator → skip error (ref user_info.go:22-44)
            p2 = make_pipeline(identity={"anonymous": True})
            with pytest.raises(EvaluationError, match="Missing identity"):
                await ui.call(p2)
            await oidc.clean()

        with_fake_idp(body)


class TestOAuth2Introspection:
    def test_active_and_inactive(self):
        async def body(idp):
            ev = OAuth2("oauth2", f"{idp.issuer}/introspect", "client", "secret")
            p = make_pipeline(headers={"authorization": "Bearer opaque-token-1"})
            obj = await ev.call(p)
            assert obj["username"] == "john"
            with pytest.raises(EvaluationError, match="not active"):
                await ev.call(make_pipeline(headers={"authorization": "Bearer nope"}))

        with_fake_idp(body)


class TestKubernetesTokenReview:
    def test_token_review(self):
        cluster = InMemoryCluster()
        cluster.token_reviews["good-token"] = {
            "status": {"authenticated": True, "user": {"username": "system:serviceaccount:ns:app"}}
        }
        ev = KubernetesAuth("k8s", cluster=cluster)
        obj = run(ev.call(make_pipeline(headers={"authorization": "Bearer good-token"})))
        assert obj["username"].startswith("system:serviceaccount")
        with pytest.raises(EvaluationError, match="Not authenticated"):
            run(ev.call(make_pipeline(headers={"authorization": "Bearer bad"})))


class TestGenericHttp:
    def test_get_and_post(self):
        async def body(idp):
            seen = {}

            async def echo(request):
                seen["headers"] = dict(request.headers)
                seen["query"] = dict(request.query)
                seen["body"] = await request.text()
                return web.json_response({"ok": True})

            from aiohttp.test_utils import TestServer

            app = web.Application()
            app.router.add_route("*", "/meta", echo)
            server = TestServer(app)
            await server.start_server()
            base = str(server.make_url("")).rstrip("/")
            try:
                ev = GenericHttp(
                    endpoint=JSONValue(pattern=base + "/meta?user={auth.identity.user}"),
                    method="GET",
                    shared_secret="s3cr3t",
                    credentials=AuthCredentials(key_selector="Bearer"),
                    headers=[JSONProperty("X-Tag", JSONValue(static="v1"))],
                )
                p = make_pipeline(identity={"user": "john"})
                out = await ev.call(p)
                assert out == {"ok": True}
                assert seen["headers"]["Authorization"] == "Bearer s3cr3t"
                assert seen["headers"]["X-Tag"] == "v1"
                assert seen["query"] == {"user": "john"}

                ev2 = GenericHttp(
                    endpoint=JSONValue(static=base + "/meta"),
                    method="POST",
                    parameters=[JSONProperty("u", JSONValue(pattern="auth.identity.user"))],
                )
                out = await ev2.call(p)
                assert json.loads(seen["body"]) == {"u": "john"}
            finally:
                await server.close()

        with_fake_idp(body)


class TestRego:
    def test_basic_allow(self):
        m = compile_module(
            """
            default allow = false
            allow { input.auth.identity.role == "admin" }
            allow { input.request.method == "GET"; input.request.path == "/public" }
            """
        )
        assert m.evaluate({"auth": {"identity": {"role": "admin"}}, "request": {}})["allow"]
        assert m.evaluate({"auth": {"identity": {}}, "request": {"method": "GET", "path": "/public"}})["allow"]
        assert not m.evaluate({"auth": {"identity": {"role": "dev"}}, "request": {"method": "POST"}})["allow"]

    def test_iteration_and_builtins(self):
        m = compile_module(
            """
            default allow = false
            allow { input.roles[_] == "admin" }
            allow { startswith(input.path, "/public/") }
            """
        )
        assert m.evaluate({"roles": ["dev", "admin"], "path": "/x"})["allow"]
        assert m.evaluate({"roles": [], "path": "/public/a"})["allow"]
        assert not m.evaluate({"roles": ["dev"], "path": "/private"})["allow"]

    def test_bindings_and_value_rules(self):
        m = compile_module(
            """
            default allow = false
            user := input.identity.username
            allow { user == "john" }
            greeting = msg { msg := sprintf("hello %s", [user]) }
            """
        )
        out = m.evaluate({"identity": {"username": "john"}})
        assert out["allow"] and out["user"] == "john" and out["greeting"] == "hello john"

    def test_not_and_in(self):
        m = compile_module(
            """
            default allow = false
            allow { not denied; "gold" in input.tiers }
            denied { input.banned == true }
            """
        )
        assert m.evaluate({"tiers": ["gold"], "banned": False})["allow"]
        assert not m.evaluate({"tiers": ["gold"], "banned": True})["allow"]
        assert not m.evaluate({"tiers": ["silver"], "banned": False})["allow"]

    def test_unsupported_syntax_rejected(self):
        # constructs outside the subset fail CLOSED at compile, never get
        # silently misparsed into a policy that means something else
        with pytest.raises(RegoError):
            compile_module("default x = input.y")  # non-constant default
        # a `with` target that names neither a document path nor a known
        # function/builtin still fails CLOSED — at eval (round 4: real
        # function/builtin mocking is supported, unknown targets are not)
        m = compile_module("allow { count([1]) == 1 with nosuch as 3 }")
        with pytest.raises(RegoError):
            m.evaluate({})
        with pytest.raises(RegoError):
            compile_module("else = true { input.y }")  # dangling else

    def test_else_chain_ordered(self):
        # OPA: else blocks evaluate strictly in order; the first definition
        # whose body is satisfied supplies the value
        m = compile_module(
            """
            default access = "none"
            access = "admin" { input.user == "root" }
            else = "write" { input.tier == "gold" }
            else = "read" { input.known }
            """
        )
        assert m.evaluate({"user": "root"})["access"] == "admin"
        assert m.evaluate({"user": "u", "tier": "gold", "known": True})["access"] == "write"
        assert m.evaluate({"user": "u", "known": True})["access"] == "read"
        assert m.evaluate({"user": "u"})["access"] == "none"

    def test_else_bare_value_and_v1_if(self):
        # bare `else { body }` values true; `else := v if cond` (v1 sugar)
        # and a trailing unconditional `else := v` fallback
        m = compile_module(
            """
            allow { input.x == 1 }
            else { input.y == 2 }
            level := 3 if input.n > 10
            else := 2 if input.n > 5
            else := 1
            """
        )
        assert m.evaluate({"x": 1})["allow"] is True
        assert m.evaluate({"y": 2})["allow"] is True
        assert m.evaluate({}).get("allow") is None  # undefined, no default
        assert m.evaluate({"n": 20})["level"] == 3
        assert m.evaluate({"n": 7})["level"] == 2
        assert m.evaluate({"n": 1})["level"] == 1

    def test_else_rejected_on_partial_set(self):
        with pytest.raises(RegoError):
            compile_module('s contains "a" { input.x }\nelse = true { input.y }')

    def test_user_functions(self):
        # OPA functions: computed head values, multiple definitions tried in
        # order, Const params unify, undefined when no definition matches
        m = compile_module(
            """
            default allow = false
            double(x) = 2 * x
            ext(name) = out { out := trim_suffix(name, ".json") }
            classify(1) = "one"
            classify(x) = "many" { x > 1 }
            bool_fn(x) { x > 10 }
            allow { double(input.n) == 6 }
            kind := classify(input.n)
            big { bool_fn(input.n) }
            stripped := ext("a.json")
            """
        )
        out = m.evaluate({"n": 3})
        assert out["allow"] and out["kind"] == "many" and out["stripped"] == "a"
        assert "big" not in out
        assert m.evaluate({"n": 1})["kind"] == "one"
        assert m.evaluate({"n": 11})["big"] is True
        # no classify() definition matches 0 ("many" needs x > 1) → the
        # call is undefined and the rule that uses it drops out
        assert "kind" not in m.evaluate({"n": 0})

    def test_user_function_else_and_recursion_guard(self):
        m = compile_module(
            """
            f(x) = "big" { x > 10 } else = "small" { x > 0 } else = "neg"
            v := f(input.n)
            """
        )
        assert m.evaluate({"n": 11})["v"] == "big"
        assert m.evaluate({"n": 3})["v"] == "small"
        assert m.evaluate({"n": -1})["v"] == "neg"
        rec = compile_module("f(x) = f(x) { true }\nv := f(1)")
        with pytest.raises(RegoError):
            rec.evaluate({})

    def test_data_documents(self):
        # external data tree under data.*, and the module's own package
        # mounted at data.<package> as a virtual document
        m = compile_module(
            """
            package acl
            default allow = false
            helper { input.x == 1 }
            allow { input.user == data.admins[_] }
            allow { data.acl.helper }
            via_pkg := data.acl.limits.max
            """,
            package="acl",
        )
        assert m.evaluate({"user": "alice"}, data={"admins": ["alice", "bob"]})["allow"]
        assert not m.evaluate({"user": "eve"}, data={"admins": ["alice"]})["allow"]
        assert m.evaluate({"x": 1})["allow"]          # virtual self-reference
        # data falls back to the external tree under non-rule names
        out = m.evaluate({}, data={"acl": {"limits": {"max": 9}}})
        assert out["via_pkg"] == 9
        # a rule reading its own whole package document is recursive —
        # OPA raises rego_recursion_error, we match (fail closed)
        m2 = compile_module("package p\na := 1\nwhole := data.p", package="p")
        with pytest.raises(RegoError):
            m2.evaluate({})

    def test_with_recursion_fails_closed(self):
        # a cycle routed through `with` is still a cycle: the guard spans
        # the whole with-chain (OPA rejects recursion statically)
        m = compile_module('p { q with input.x as 1 }\nq { p }')
        with pytest.raises(RegoError, match="recursive"):
            m.evaluate({})

    def test_repeated_function_params_unify(self):
        # OPA: f(x, x) matches only when both arguments are equal
        m = compile_module("f(x, x) = x { true }\nr := f(input.a, input.b)")
        assert m.evaluate({"a": 2, "b": 2})["r"] == 2
        assert "r" not in m.evaluate({"a": 1, "b": 2})

    def test_with_on_some_in_and_every(self):
        m = compile_module(
            """
            default a = false
            default b = false
            a { some x in input.xs; x == 9 with input.y as 1 }
            b { every x in input.xs { x > input.min } with input.min as 0 }
            """
        )
        assert m.evaluate({"xs": [9]})["a"] is True
        assert m.evaluate({"xs": [1, 2], "min": 5})["b"] is True  # mocked min
        assert not m.evaluate({"xs": [0], "min": 5})["b"]

    def test_data_ancestor_prefix(self):
        # referencing an ancestor of your own package pulls in the whole
        # package document — including the referencing rule, which is a
        # dependency cycle: OPA raises rego_recursion_error, we fail closed
        m = compile_module("package a.b\nallow = true\nr := data.a", package="a.b")
        with pytest.raises(RegoError, match="recursive"):
            m.evaluate({}, data={"a": {"ext": 7}})
        # non-package data paths keep walking the external tree
        m2 = compile_module("package a.b\nr := data.other.k", package="a.b")
        assert m2.evaluate({}, data={"other": {"k": 5}})["r"] == 5

    def test_with_mocking(self):
        # `with` overlays input/data for the wrapped expression AND the
        # rules it references (OPA with modifier scoping)
        m = compile_module(
            """
            default allow = false
            inner { input.role == "admin" }
            allow { inner with input.role as "admin" }
            both { inner with input.role as input.alt }
            listed { input.user in data.users }
            mocked_data { listed with data.users as ["bob"] with input.user as "bob" }
            """
        )
        out = m.evaluate({"role": "user"})
        assert out["allow"] is True          # inner sees the mocked role
        assert "both" not in out             # alt missing → mock value undefined
        assert m.evaluate({"role": "u", "alt": "admin"})["both"] is True
        assert m.evaluate({"user": "eve"}, data={"users": []})["mocked_data"] is True


class TestRegoBuiltinsExtra:
    def _eval(self, rego_src, input_doc):
        from authorino_tpu.evaluators.authorization import rego

        module = rego.compile_module("default allow = false\n" + rego_src, package="t")
        return module.evaluate(input_doc)["allow"]

    def test_regex_match(self):
        src = 'allow { regex.match("^/api/v[0-9]+/", input.path) }'
        assert self._eval(src, {"path": "/api/v2/pets"}) is True
        assert self._eval(src, {"path": "/admin"}) is False

    def test_substring_indexof(self):
        src = 'allow { indexof(input.s, "-") == 3 ; substring(input.s, 0, 3) == "abc" }'
        assert self._eval(src, {"s": "abc-def"}) is True
        assert self._eval(src, {"s": "ab-cdef"}) is False

    def test_type_checks_and_sort(self):
        src = ('allow { is_string(input.s) ; is_number(input.n) ; '
               'is_array(input.a) ; sort(input.a)[0] == 1 }')
        assert self._eval(src, {"s": "x", "n": 2, "a": [3, 1, 2]}) is True
        assert self._eval(src, {"s": 1, "n": 2, "a": [3, 1, 2]}) is False

    def test_substring_negative_offset_fails_closed(self):
        # OPA errors on negative offsets; slicing from the end would fail
        # OPEN on the common substring(s, indexof(s, x), n) miss
        from authorino_tpu.evaluators.authorization import rego

        src = 'allow { substring(input.s, indexof(input.s, "#"), 2) == "ef" }'
        with pytest.raises(rego.RegoError, match="negative offset"):
            self._eval(src, {"s": "abcdef"})

    def test_every(self):
        src = 'allow { every r in input.roles { startswith(r, "team-") } }'
        assert self._eval(src, {"roles": ["team-a", "team-b"]}) is True
        assert self._eval(src, {"roles": ["team-a", "other"]}) is False
        assert self._eval(src, {"roles": []}) is True  # vacuous

    def test_every_key_value(self):
        src = 'allow { every k, v in input.limits { v <= 10 ; k != "forbidden" } }'
        assert self._eval(src, {"limits": {"a": 5, "b": 10}}) is True
        assert self._eval(src, {"limits": {"a": 11}}) is False
        assert self._eval(src, {"limits": {"forbidden": 1}}) is False

    def test_array_comprehension(self):
        src = ('names := [u.name | some u in input.users ; u.admin]\n'
               'allow { count(names) == 2 ; names[0] == "a" }')
        assert self._eval(src, {"users": [
            {"name": "a", "admin": True}, {"name": "b", "admin": False},
            {"name": "c", "admin": True}]}) is True

    def test_set_and_object_comprehensions(self):
        src = ('tiers := {u.tier | some u in input.users}\n'
               'by_name := {u.name: u.tier | some u in input.users}\n'
               'allow { count(tiers) == 2 ; by_name.a == "gold" }')
        assert self._eval(src, {"users": [
            {"name": "a", "tier": "gold"}, {"name": "b", "tier": "free"},
            {"name": "c", "tier": "gold"}]}) is True

    def test_partial_set_rules(self):
        # v1 `contains` form — the modern deny-set idiom
        src = ('violations contains msg { input.x > 5 ; msg := "too big" }\n'
               'violations contains msg { input.y == "bad" ; msg := "bad y" }\n'
               'allow { count(violations) == 0 }')
        assert self._eval(src, {"x": 1, "y": "ok"}) is True
        assert self._eval(src, {"x": 9, "y": "ok"}) is False
        assert self._eval(src, {"x": 9, "y": "bad"}) is False
        # v0 bracket form, multiple bindings dedupe as a set
        src0 = ('roles[r] { some r in input.rs }\n'
                'allow { count(roles) == 2 }')
        assert self._eval(src0, {"rs": ["a", "b", "a"]}) is True

    def test_arithmetic(self):
        src = ('allow { count(input.roles) + 1 > 2 ; input.n * 2 <= 10 ; '
               'input.n % 2 == 1 ; (input.n + 1) / 2 == 3 ; -input.n == 0 - 5 }')
        assert self._eval(src, {"roles": ["a", "b"], "n": 5}) is True
        assert self._eval(src, {"roles": [], "n": 5}) is False

    def test_arithmetic_iterates_refs(self):
        # existential ref[_] semantics flow THROUGH arithmetic: any element
        # satisfying the expression satisfies the rule (OPA behavior)
        src = "deny { input.scores[_] - input.threshold > 0 }\nallow { not deny }"
        assert self._eval(src, {"scores": [1, 100], "threshold": 50}) is False
        assert self._eval(src, {"scores": [1, 2], "threshold": 50}) is True

    def test_default_constant_folding_and_rejection(self):
        from authorino_tpu.evaluators.authorization import rego

        m = rego.compile_module("default limit = 60 * 60\nallow { input.x }", package="t")
        assert m.evaluate({"x": True}) == {"limit": 3600, "allow": True}
        with pytest.raises(rego.RegoError, match="must be a constant"):
            rego.compile_module("default limit = input.x + 1")

    def test_exact_integer_division(self):
        src = "x := input.a / input.b\nallow { x == 2 }"
        from authorino_tpu.evaluators.authorization import rego

        m = rego.compile_module("default allow = false\n" + src, package="t")
        out = m.evaluate({"a": 4, "b": 2})
        assert out["x"] == 2 and not isinstance(out["x"], float)  # JSON "2", not "2.0"
        assert rego.compile_module("y := 3 / 2", package="t").evaluate({})["y"] == 1.5

    def test_modulo_truncated_like_go(self):
        # Go big.Int.Rem: sign of the dividend (-7 rem 2 == -1, not 1)
        src = "allow { input.n % 2 == 1 }"
        assert self._eval(src, {"n": 7}) is True
        assert self._eval(src, {"n": -7}) is False
        assert self._eval("allow { input.n % 2 == 0 - 1 }", {"n": -7}) is True

    def test_arithmetic_errors_deny(self):
        from authorino_tpu.evaluators.authorization import rego

        with pytest.raises(rego.RegoError, match="divide by zero"):
            self._eval("allow { input.a / input.b == 1 }", {"a": 1, "b": 0})
        with pytest.raises(rego.RegoError, match="non-number"):
            self._eval('allow { input.s + 1 == 2 }', {"s": "x"})

    def test_braceless_if_bodies(self):
        # v1 brace-less form: the condition must BIND, not silently drop
        src = ('deny contains "x" if input.flagged\n'
               'allow if count(deny) == 0')
        assert self._eval(src, {"flagged": True}) is False
        assert self._eval(src, {"flagged": False}) is True

    def test_set_rule_iterating_head(self):
        # every value of an iterating head joins the set, not just the first
        src = ('banned contains input.blocked[_] { true }\n'
               'allow { not input.user in banned }')
        assert self._eval(src, {"blocked": ["a", "b", "c"], "user": "c"}) is False
        assert self._eval(src, {"blocked": ["a", "b", "c"], "user": "z"}) is True

    def test_partial_set_conflicting_types_rejected(self):
        from authorino_tpu.evaluators.authorization import rego

        with pytest.raises(rego.RegoError, match="conflicting rule types"):
            rego.compile_module(
                'x contains v { v := input.a }\nx { input.b }'
            )

    def test_with_parses_on_every_expression_form(self):
        # `with` is a postfix modifier on comparisons, assignments, and
        # bare terms alike — all three shapes must overlay, not misparse
        from authorino_tpu.evaluators.authorization import rego

        for src, want in [
            ("allow { input.x == 1 with input as {\"x\": 1} }", True),
            ("allow { x := input.y with input.y as 3; x == 3 }", True),
            ("allow { input.x with input as {\"x\": true} }", True),
        ]:
            m = rego.compile_module("default allow = false\n" + src)
            assert m.evaluate({})["allow"] is want, src

    def test_object_comprehension_key_conflict_denies(self):
        from authorino_tpu.evaluators.authorization import rego

        src = ('by := {u.name: u.role | some u in input.users}\n'
               'allow { by.alice == "admin" }')
        with pytest.raises(rego.RegoError, match="conflicting"):
            self._eval(src, {"users": [
                {"name": "alice", "role": "viewer"},
                {"name": "alice", "role": "admin"}]})
        # duplicate key with the SAME value is fine (like OPA)
        assert self._eval(src, {"users": [
            {"name": "alice", "role": "admin"},
            {"name": "alice", "role": "admin"}]}) is True

    def test_set_comprehension_bool_number_distinct(self):
        src = 's := {x | some x in input.xs}\nallow { count(s) == 2 }'
        assert self._eval(src, {"xs": [1, True]}) is True  # OPA: 2 elements
        assert self._eval(src, {"xs": [1, 1.0]}) is False  # numbers equal

    def test_regex_match_linear_time_on_catastrophic_pattern(self):
        # ^(a+)+$ explodes under backtracking engines; the DFA lane must
        # answer in linear time like OPA's RE2
        import time

        src = 'allow { regex.match("^(a+)+$", input.v) }'
        t0 = time.perf_counter()
        assert self._eval(src, {"v": "a" * 28 + "!"}) is False
        assert self._eval(src, {"v": "a" * 28}) is True
        assert time.perf_counter() - t0 < 1.0


class TestOPAEvaluator:
    def test_opa_call(self):
        opa = OPA("policy", inline_rego='allow { input.auth.identity.anonymous == true }')
        p = make_pipeline(identity={"anonymous": True})
        assert run(opa.call(p)) is True
        p2 = make_pipeline(identity={"anonymous": False})
        with pytest.raises(EvaluationError, match="Unauthorized"):
            run(opa.call(p2))

    def test_opa_all_values(self):
        opa = OPA(
            "policy",
            inline_rego='allow { input.auth.identity.user == "john" }\nuser := input.auth.identity.user',
            all_values=True,
        )
        out = run(opa.call(make_pipeline(identity={"user": "john"})))
        assert out["allow"] is True and out["user"] == "john"

    def test_invalid_rego_rejected_at_compile(self):
        with pytest.raises(ValueError, match="invalid rego"):
            OPA("policy", inline_rego="default x = input.y")

    def test_opa_data_documents(self):
        opa = OPA("policy",
                  inline_rego='allow { input.auth.identity.sub == data.admins[_] }',
                  data={"admins": ["u1"]})
        p = make_pipeline(identity={"sub": "u1"})
        assert run(opa.call(p)) is True
        p2 = make_pipeline(identity={"sub": "u2"})
        with pytest.raises(EvaluationError, match="Unauthorized"):
            run(opa.call(p2))


class TestWristband:
    def _signing_key(self):
        key = ec.generate_private_key(ec.SECP256R1())
        pem = key.private_bytes(
            serialization.Encoding.PEM,
            serialization.PrivateFormat.TraditionalOpenSSL,
            serialization.NoEncryption(),
        )
        return SigningKey.from_pem("wristband-key", "ES256", pem)

    def test_issue_and_verify(self):
        sk = self._signing_key()
        wb = Wristband(
            issuer="https://authorino.example.com/ns/ac/wristband",
            custom_claims=[JSONProperty("username", JSONValue(pattern="auth.identity.user"))],
            token_duration=300,
            signing_keys=[sk],
        )
        p = make_pipeline(identity={"user": "john"})
        token = run(wb.call(p))
        jwks = json.loads(wb.jwks())["keys"]
        claims = jose.verify_jws(token, jwks)
        assert claims["iss"] == wb.issuer
        assert claims["username"] == "john"
        assert claims["exp"] - claims["iat"] == 300
        assert len(claims["sub"]) == 64  # sha256 hex
        cfg = json.loads(wb.openid_config())
        assert cfg["jwks_uri"].endswith("/openid-connect/certs")


class TestRegoDataLayering:
    def test_exact_package_ref_merges_external_tree(self):
        # data.<package> exactly: virtual doc layered over the external
        # subtree at the same path, consistent with leaf/ancestor refs
        # (only reachable without recursion from outside a rule body, so
        # exercised at the resolver level)
        from authorino_tpu.evaluators.authorization import rego

        m = compile_module("package p\na := 1", package="p")
        ev = rego._Evaluator(m, {}, data={"p": {"ext": 7, "a": 99}})
        vals = list(ev._data_values(["p"], {}))
        assert vals == [{"ext": 7, "a": 1}]  # virtual wins on conflict


class TestRegoBuiltinsRound3:
    def _val(self, expr, input_doc=None, data=None):
        from authorino_tpu.evaluators.authorization import rego

        m = rego.compile_module(f"package t\nv := {expr}", package="t")
        return m.evaluate(input_doc or {}, data=data).get("v")

    def test_object_builtins(self):
        assert sorted(self._val('object.keys({"a": 1, "b": 2})')) == ["a", "b"]
        assert self._val('object.union({"a": {"x": 1}}, {"a": {"y": 2}})') == \
            {"a": {"x": 1, "y": 2}}
        assert self._val('object.remove({"a": 1, "b": 2}, ["a"])') == {"b": 2}
        assert self._val('object.filter({"a": 1, "b": 2}, ["a"])') == {"a": 1}

    def test_array_and_number_builtins(self):
        assert self._val("numbers.range(1, 4)") == [1, 2, 3, 4]   # inclusive
        assert self._val("numbers.range(3, 1)") == [3, 2, 1]      # descending
        assert self._val("array.slice([1, 2, 3, 4], 1, 3)") == [2, 3]
        assert self._val("array.slice([1, 2], -5, 99)") == [1, 2]  # clamped
        assert self._val("array.reverse([1, 2, 3])") == [3, 2, 1]
        assert self._val('strings.reverse("abc")') == "cba"
        assert self._val("format_int(255, 16)") == "ff"

    def test_set_builtins(self):
        # sets are represented as deduped arrays throughout this interpreter
        assert self._val("union([[1, 2], [2, 3]])") == [1, 2, 3]
        assert self._val("intersection([[1, 2, 3], [2, 3, 4]])") == [2, 3]

    def test_glob_match(self):
        # OPA >= 0.43: null delimiters = NO delimiters (* spans everything);
        # an EMPTY array defaults to ["."] (* stays within one label)
        assert self._val('glob.match("*.github.com", null, "a.b.github.com")') is True
        assert self._val('glob.match("*.github.com", [], "api.github.com")') is True
        assert self._val('glob.match("*.github.com", [], "a.b.github.com")') is False
        assert self._val('glob.match("*.github.com", ["."], "a.b.github.com")') is False
        # ** spans delimiters even with them set
        assert self._val('glob.match("**.github.com", ["."], "a.b.github.com")') is True
        assert self._val('glob.match("api-?.acme.com", ["."], "api-1.acme.com")') is True
        assert self._val('glob.match("api-?.acme.com", ["."], "api-12.acme.com")') is False
        # gobwas matches newlines where delimiters allow (DOTALL)
        assert self._val('glob.match("a**b", null, input.s)', {"s": "a\nb"}) is True

    def test_numbers_range_type_errors(self):
        from authorino_tpu.evaluators.authorization import rego

        m = rego.compile_module("package t\nv := numbers.range(x, 3)\nx := input.n",
                                package="t")
        assert m.evaluate({"n": 1})["v"] == [1, 2, 3]
        assert m.evaluate({"n": 1.0})["v"] == [1, 2, 3]   # integral float ok
        with pytest.raises(rego.RegoError):
            m.evaluate({"n": 1.5})


class TestRegoRound4:
    """walk(), `with` function/builtin mocking, multi-module composition
    (the round-3 fail-closed rejections, now implemented; the reference
    evaluates these via embedded OPA,
    ref pkg/evaluators/authorization/opa.go:86-141)."""

    def test_walk_relation(self):
        m = compile_module(
            'paths contains p { walk(input, [p, v]); v == "x" }\n'
            'has_admin { walk(input, [_, v]); v == "admin" }\n'
        )
        out = m.evaluate({"a": {"b": "x"}, "roles": ["admin", "user"]})
        assert out["has_admin"] is True
        assert out["paths"] == [["a", "b"]]

    def test_walk_ground_and_nested(self):
        m = compile_module(
            'allow { walk(input, [["a", "b"], v]); v == 1 }\n'
            # collect every string leaf under any "labels" object
            'labels contains v { walk(input, [p, lv]); p[count(p) - 1] == "labels"; '
            'v := lv[_] }\n'
        )
        out = m.evaluate({"a": {"b": 1},
                          "x": {"labels": {"t": "blue"}},
                          "y": {"labels": {"u": "green"}}})
        assert out["allow"] is True
        assert sorted(out["labels"]) == ["blue", "green"]

    def test_function_mocking(self):
        m = compile_module(
            "f(x) = x * 2\n"
            "g(x) = x + 100\n"
            "doubled = f(3)\n"
            "mocked { f(3) == 103 with f as g }\n"
            "consted { f(3) == 42 with f as 42 }\n"
            "builtin_const { count(\"abc\") == 99 with count as 99 }\n"
            "builtin_fn { count(\"abc\") == 6 with count as double_len }\n"
            "double_len(s) = 2 * 3\n"
        )
        out = m.evaluate({})
        assert out["doubled"] == 6
        assert out["mocked"] is True
        assert out["consted"] is True
        assert out["builtin_const"] is True
        assert out["builtin_fn"] is True

    def test_mock_scopes_referenced_rules(self):
        # the mock applies through rules the wrapped expression references
        # (OPA `with` scoping: a fresh evaluation under the override)
        m = compile_module(
            "inner = count(input.xs)\n"
            "outer { inner == 7 with count as 7 }\n"
            "normal = inner\n"
        )
        out = m.evaluate({"xs": []})
        assert out["outer"] is True
        assert out["normal"] == 0

    def test_mock_combined_with_input(self):
        m = compile_module(
            "f(x) = count(x)\n"
            "ok { f(input.xs) == 9 with input.xs as [1] with f as 9 }\n"
        )
        assert m.evaluate({"xs": []})["ok"] is True

    def test_multi_module_composition(self):
        src = (
            "package main\n"
            "allow { data.lib.helpers.is_admin }\n"
            "doubled = data.lib.mathx.double(4)\n"
            "libdoc = data.lib.helpers\n"
            "package lib.helpers\n"
            'is_admin { input.user.role == "admin" }\n'
            "level = 3\n"
            "package lib.mathx\n"
            "double(x) = x * 2\n"
        )
        m = compile_module(src)
        out = m.evaluate({"user": {"role": "admin"}})
        assert out["allow"] is True
        assert out["doubled"] == 8
        assert out["libdoc"] == {"is_admin": True, "level": 3}
        deny = compile_module(src).evaluate({"user": {"role": "peon"}})
        assert "allow" not in deny
        assert deny["libdoc"] == {"level": 3}

    def test_multi_module_subtree_and_external_data(self):
        src = (
            "package main\n"
            "tree = data.lib\n"
            "ext = data.settings.mode\n"
            "package lib.a\n"
            "x = 1\n"
            "package lib.b\n"
            "y { false }\n"
        )
        m = compile_module(src)
        out = m.evaluate({}, data={"settings": {"mode": "strict"},
                                   "lib": {"a": {"ext": True}, "c": 9}})
        # virtual docs merge over external data, packages nest
        assert out["tree"] == {"a": {"x": 1, "ext": True}, "b": {}, "c": 9}
        assert out["ext"] == "strict"

    def test_multi_module_cross_module_mock(self):
        src = (
            "package main\n"
            "ok { data.lib.f(1) == 10 with data.lib.f as ten }\n"
            "ten(x) = 10\n"
            "package lib\n"
            "f(x) = x\n"
        )
        assert compile_module(src).evaluate({})["ok"] is True

    def test_recursion_across_modules_fails_closed(self):
        src = (
            "package main\n"
            "a { data.lib.b }\n"
            "package lib\n"
            "b { data.main.a }\n"
        )
        m = compile_module(src, package="main")
        with pytest.raises(RegoError):
            m.evaluate({})

    def test_opa_evaluator_uses_round4_features(self):
        # through the real OPA evaluator seam (inline rego, main package
        # injected): helper package + walk + mocking all compose
        rego_src = (
            "roles contains v { walk(input.auth, [_, v]); is_string(v) }\n"
            'allow { "admin" in roles }\n'
        )
        opa = OPA("t/az", inline_rego=rego_src)
        out = opa._module.evaluate(
            {"auth": {"identity": {"realm_access": {"roles": ["admin"]}}}})
        assert out["allow"] is True

    def test_some_key_value_in(self):
        m = compile_module(
            "admins contains u { some u, r in input.users; r == \"admin\" }\n"
            "second = v { some i, v in input.xs; i == 1 }\n"
            "anyval { some _, v in input.users; v == \"admin\" }\n"
        )
        out = m.evaluate({"users": {"ann": "admin", "bob": "user"},
                          "xs": ["a", "b", "c"]})
        assert out["admins"] == ["ann"]
        assert out["second"] == "b"
        assert out["anyval"] is True

    def test_mock_cycle_fails_closed(self):
        # a mock chain that cycles (directly or mutually) must be a
        # RegoError (→ deny), never unbounded recursion
        direct = compile_module(
            'allow { count([1]) == 1 with count as count }')
        with pytest.raises(RegoError, match="cycle"):
            direct.evaluate({})
        mutual = compile_module(
            'allow { count([1]) == 1 with count as sum with sum as count }')
        with pytest.raises(RegoError, match="cycle"):
            mutual.evaluate({})

    def test_encoding_and_time_builtins(self):
        m = compile_module(
            'j = json.marshal({"a": [1, 2]})\n'
            'b = base64.encode("hi")\n'
            'bd = base64.decode("aGk=")\n'
            'bu = base64url.encode_no_pad("hi?")\n'
            'bud = base64url.decode("aGk_")\n'
            'h = hex.encode("hi")\n'
            'hd = hex.decode("6869")\n'
            't = time.parse_rfc3339_ns("2026-07-30T00:00:00Z")\n'
            'tns = time.parse_rfc3339_ns("2026-07-30T00:00:00.123456789Z")\n'
            'tus = time.parse_rfc3339_ns("2026-07-30T12:34:56.654321+00:00")\n'
            'js = json.marshal({"b": 1, "a": 2})\n'
        )
        out = m.evaluate({})
        assert out["j"] == '{"a":[1,2]}'
        assert out["b"] == "aGk=" and out["bd"] == "hi"
        assert out["bu"] == "aGk_" and out["bud"] == "hi?"
        assert out["h"] == "6869" and out["hd"] == "hi"
        assert out["t"] == 1785369600000000000
        # exact integer ns — no float rounding, no sub-µs truncation
        assert out["tns"] == 1785369600123456789
        assert out["tus"] == 1785414896654321000
        # Go encoding/json marshals object keys sorted
        assert out["js"] == '{"a":2,"b":1}'

    def test_crypto_units_regex_builtins(self):
        m = compile_module(
            'h = crypto.sha256("hello")\n'
            'h1 = crypto.sha1("hello")\n'
            'h5 = crypto.md5("hello")\n'
            'b = units.parse_bytes("10MiB")\n'
            'b2 = units.parse_bytes("2K")\n'
            'parts = regex.split("[,;] ?", "a,b; c")\n'
            'parts2 = regex.split("(,)|;", "a,b;c")\n'
            'rep = regex.replace("xabbcy", "a(b+)c", "<$1>")\n'
            'rep0 = regex.replace("xabbcy", "ab+c", "<$0>")\n'
            'repd = regex.replace("cost", "co", "$$")\n'
            # Go Regexp.Expand: `$1x` parses as group name "1x" → no such
            # group → "" (Python \g<1x> would raise re.error)
            'repgo = regex.replace("xabbcy", "a(b+)c", "<$1x>")\n'
            # reference to a nonexistent numeric group → "" (not an error)
            'repmiss = regex.replace("xabbcy", "a(b+)c", "<$9>")\n'
            # unmatched optional group expands to ""
            'repopt = regex.replace("ac", "a(b)?c", "<$1>")\n'
            # backslashes in the template are literal in Go
            'repbs = regex.replace("ab", "a", "\\\\d$0")\n'
        )
        out = m.evaluate({})
        assert out["h"] == ("2cf24dba5fb0a30e26e83b2ac5b9e29e"
                            "1b161e5c1fa7425e73043362938b9824")
        assert out["h1"] == "aaf4c61ddcc5e8a2dabede0f3b482cd9aea9434d"
        assert out["h5"] == "5d41402abc4b2a76b9719d911017c592"
        assert out["b"] == 10 * 1024 * 1024
        assert out["b2"] == 2000
        assert out["parts"] == ["a", "b", "c"]
        assert out["parts2"] == ["a", "b", "c"]  # no capture-group leakage
        assert out["rep"] == "x<bb>y"
        assert out["rep0"] == "x<abbc>y"
        assert out["repd"] == "$st"
        assert out["repgo"] == "x<>y"
        assert out["repmiss"] == "x<>y"
        assert out["repopt"] == "<>"
        assert out["repbs"] == "\\dab"
        with pytest.raises(RegoError):
            compile_module("h = crypto.sha256(3)").evaluate({})
