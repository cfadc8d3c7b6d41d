"""Control-plane tests: translation, v1beta1↔v1beta2 conversion, reconciler
status/bootstrap/collision, secret reconciler live rotation, YAML source."""

import asyncio
import base64
import json
import os

import pytest

from authorino_tpu.apis import to_v1beta1, to_v1beta2
from authorino_tpu.controllers import (
    AuthConfigReconciler,
    SecretReconciler,
    TranslationError,
    translate_auth_config,
)
from authorino_tpu.controllers.reconciler import (
    STATUS_CACHING_ERROR,
    STATUS_HOSTS_NOT_LINKED,
    STATUS_RECONCILED,
)
from authorino_tpu.k8s import InMemoryCluster, LabelSelector, Secret
from authorino_tpu.runtime import PolicyEngine
from authorino_tpu.authjson import CheckRequestModel, HttpRequestAttributes


def run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


V2_SPEC = {
    "hosts": ["talker-api.example.com"],
    "patterns": {
        "admin-path": [{"selector": "request.url_path", "operator": "matches", "value": "^/admin"}]
    },
    "when": [{"selector": "request.method", "operator": "neq", "value": "OPTIONS"}],
    "authentication": {
        "api-clients": {
            "apiKey": {"selector": {"matchLabels": {"audience": "talker-api"}}},
            "credentials": {"authorizationHeader": {"prefix": "APIKEY"}},
        },
        "anon": {"anonymous": {}, "priority": 1},
    },
    "authorization": {
        "admin-only": {
            "patternMatching": {
                "patterns": [
                    {
                        "any": [
                            {"selector": "auth.identity.metadata.labels.role", "operator": "eq", "value": "admin"},
                            {"selector": "auth.identity.anonymous", "operator": "neq", "value": "true"},
                        ]
                    }
                ]
            },
            "when": [{"patternRef": "admin-path"}],
        }
    },
    "response": {
        "unauthorized": {"code": 302, "message": {"value": "redirect"}},
        "success": {
            "headers": {"x-auth": {"json": {"properties": {"user": {"selector": "auth.identity.anonymous"}}}}}
        },
    },
}


def make_cluster():
    cluster = InMemoryCluster()
    cluster.put_secret(
        Secret(
            name="client-1",
            namespace="tenant",
            labels={"audience": "talker-api", "role": "admin",
                    "authorino.kuadrant.io/managed-by": "authorino"},
            data={"api_key": b"secret-key-1"},
        )
    )
    return cluster


class TestTranslate:
    def test_full_translate(self):
        engine = PolicyEngine()
        entry = run(
            translate_auth_config("ac", "tenant", V2_SPEC, cluster=make_cluster(), engine=engine)
        )
        assert entry.id == "tenant/ac"
        assert entry.hosts == ["talker-api.example.com"]
        assert [c.name for c in entry.runtime.identity] == ["api-clients", "anon"]
        assert entry.runtime.identity[0].credentials.key_selector == "APIKEY"
        assert entry.runtime.conditions is not None
        assert entry.rules is not None and len(entry.rules.evaluators) == 1
        cond, rules = entry.rules.evaluators[0]
        assert cond is not None  # when: [patternRef admin-path]

    def test_translate_errors(self):
        with pytest.raises(TranslationError, match="missing hosts"):
            run(translate_auth_config("x", "ns", {"authentication": {"a": {"anonymous": {}}}}))
        with pytest.raises(TranslationError, match="pattern not found"):
            run(
                translate_auth_config(
                    "x",
                    "ns",
                    {
                        "hosts": ["h"],
                        "authorization": {
                            "z": {"patternMatching": {"patterns": [{"patternRef": "nope"}]}}
                        },
                    },
                )
            )
        with pytest.raises(TranslationError, match="invalid rego"):
            run(
                translate_auth_config(
                    "x",
                    "ns",
                    {"hosts": ["h"], "authorization": {"z": {"opa": {"rego": "default z = input.y"}}}},
                )
            )


class TestConversion:
    def test_v1beta1_roundtrip(self):
        v2 = {
            "apiVersion": "authorino.kuadrant.io/v1beta2",
            "kind": "AuthConfig",
            "metadata": {"name": "ac", "namespace": "ns"},
            "spec": V2_SPEC,
        }
        v1 = to_v1beta1(v2)
        assert v1["apiVersion"].endswith("v1beta1")
        spec1 = v1["spec"]
        assert {i["name"] for i in spec1["identity"]} == {"api-clients", "anon"}
        assert spec1["authorization"][0]["json"]["rules"]
        assert spec1["denyWith"]["unauthorized"]["code"] == 302
        back = to_v1beta2(v1)
        spec2 = back["spec"]
        assert set(spec2["authentication"]) == {"api-clients", "anon"}
        assert spec2["authentication"]["api-clients"]["credentials"] == {
            "authorizationHeader": {"prefix": "APIKEY"}
        }
        assert spec2["authorization"]["admin-only"]["patternMatching"]["patterns"]
        assert spec2["response"]["unauthorized"]["code"] == 302
        assert spec2["response"]["success"]["headers"]["x-auth"]["json"]["properties"]["user"] == {
            "selector": "auth.identity.anonymous"
        }


def resource(name="ac", namespace="tenant", spec=None, labels=None):
    return {
        "apiVersion": "authorino.kuadrant.io/v1beta2",
        "kind": "AuthConfig",
        "metadata": {"name": name, "namespace": namespace, "labels": labels or {}},
        "spec": spec or dict(V2_SPEC),
    }


class TestReconciler:
    def test_reconcile_status_and_serving(self):
        async def body():
            engine = PolicyEngine(max_batch=4)
            cluster = make_cluster()
            rec = AuthConfigReconciler(engine, cluster=cluster)
            await rec.reconcile_all([resource()])
            assert rec.status.get("tenant/ac").reason == STATUS_RECONCILED
            assert rec.ready()
            status = rec.status.status_object("tenant/ac")
            assert status["summary"]["hostsReady"] == ["talker-api.example.com"]

            # serving end-to-end through the engine: API key + admin role
            req = CheckRequestModel(
                http=HttpRequestAttributes(
                    method="GET",
                    path="/admin/x",
                    host="talker-api.example.com",
                    headers={"authorization": "APIKEY secret-key-1"},
                )
            )
            result = await engine.check(req)
            assert result.success(), result.message

            # wrong api key → anonymous matches instead (priority 1) and the
            # admin-only pattern denies under /admin
            req2 = CheckRequestModel(
                http=HttpRequestAttributes(
                    method="GET", path="/admin/x", host="talker-api.example.com",
                    headers={"authorization": "APIKEY wrong"},
                )
            )
            result2 = await engine.check(req2)
            assert not result2.success()
            assert result2.status == 302  # denyWith

            # outside /admin → authz condition unmatched → allow
            req3 = CheckRequestModel(
                http=HttpRequestAttributes(
                    method="GET", path="/public", host="talker-api.example.com",
                    headers={"authorization": "APIKEY wrong"},
                )
            )
            result3 = await engine.check(req3)
            assert result3.success()

        run(body())

    def test_translate_error_status(self):
        async def body():
            engine = PolicyEngine()
            rec = AuthConfigReconciler(engine)
            bad = resource(spec={"hosts": ["h.example.com"], "authorization": {"z": {"opa": {"rego": "default z = input.y"}}}})
            await rec.reconcile_all([bad])
            assert rec.status.get("tenant/ac").reason == STATUS_CACHING_ERROR
            assert not rec.ready()

        run(body())

    def test_host_collision(self):
        async def body():
            engine = PolicyEngine()
            spec = {"hosts": ["shared.example.com"], "authentication": {"anon": {"anonymous": {}}}}
            r1 = resource(name="first", spec=dict(spec))
            r2 = resource(name="second", spec=dict(spec))
            rec = AuthConfigReconciler(engine)
            await rec.reconcile_all([r1, r2])
            reasons = {id_: rep.reason for id_, rep in rec.status.all().items()}
            assert reasons["tenant/first"] == STATUS_RECONCILED
            assert reasons["tenant/second"] == STATUS_HOSTS_NOT_LINKED

        run(body())

    def test_label_selector_sharding(self):
        async def body():
            engine = PolicyEngine()
            rec = AuthConfigReconciler(engine, label_selector=LabelSelector.parse("group=a"))
            spec = {"hosts": ["a.example.com"], "authentication": {"anon": {"anonymous": {}}}}
            watched = resource(name="mine", spec=dict(spec), labels={"group": "a"})
            unwatched = resource(
                name="other",
                spec={"hosts": ["b.example.com"], "authentication": {"anon": {"anonymous": {}}}},
                labels={"group": "b"},
            )
            await rec.reconcile_all([watched, unwatched])
            assert engine.lookup("a.example.com") is not None
            assert engine.lookup("b.example.com") is None

        run(body())


class TestSecretReconciler:
    def test_live_rotation_through_cluster_events(self):
        async def body():
            engine = PolicyEngine(max_batch=4)
            cluster = make_cluster()
            rec = AuthConfigReconciler(engine, cluster=cluster)
            sec_rec = SecretReconciler(
                engine,
                secret_label_selector=LabelSelector.parse("authorino.kuadrant.io/managed-by=authorino"),
            )
            cluster.on_secret_event(sec_rec.on_event)
            await rec.reconcile_all([resource()])

            def check(key):
                # /admin path: valid API key → allow; anonymous fallback → deny
                req = CheckRequestModel(
                    http=HttpRequestAttributes(
                        method="GET", path="/admin/x", host="talker-api.example.com",
                        headers={"authorization": f"APIKEY {key}"},
                    )
                )
                return engine.check(req)

            assert (await check("secret-key-1")).success()
            # rotate the key → old revoked, new works (ref secret_controller.go)
            cluster.put_secret(
                Secret(
                    name="client-1",
                    namespace="tenant",
                    labels={"audience": "talker-api", "authorino.kuadrant.io/managed-by": "authorino"},
                    data={"api_key": b"rotated-key"},
                )
            )
            r = await check("secret-key-1")
            assert not r.success()
            assert (await check("rotated-key")).success()
            # delete the secret → revoked (falls back to deny since the
            # admin-only rule's 'anonymous neq true' fails for anonymous)
            cluster.remove_secret("tenant", "client-1")
            r = await check("rotated-key")
            assert not r.success()

        run(body())


class TestYamlSource:
    def test_load_and_serve_from_dir(self, tmp_path):
        async def body():
            import yaml as yaml_mod

            from authorino_tpu.controllers.sources import YamlDirSource

            secret = {
                "apiVersion": "v1",
                "kind": "Secret",
                "metadata": {
                    "name": "client-1",
                    "namespace": "tenant",
                    "labels": {"audience": "talker-api", "authorino.kuadrant.io/managed-by": "authorino"},
                },
                "data": {"api_key": base64.b64encode(b"from-yaml").decode()},
            }
            (tmp_path / "manifests.yaml").write_text(
                yaml_mod.dump_all([resource(), secret], default_flow_style=False)
            )
            engine = PolicyEngine(max_batch=4)
            cluster = InMemoryCluster()
            rec = AuthConfigReconciler(engine, cluster=cluster)
            sec_rec = SecretReconciler(
                engine,
                secret_label_selector=LabelSelector.parse("authorino.kuadrant.io/managed-by=authorino"),
            )
            source = YamlDirSource(str(tmp_path), rec, cluster, sec_rec)
            await source.sync()
            req = CheckRequestModel(
                http=HttpRequestAttributes(
                    method="GET", path="/x", host="talker-api.example.com",
                    headers={"authorization": "APIKEY from-yaml"},
                )
            )
            assert (await engine.check(req)).success()

        run(body())


class AdversarialCluster:
    """Scripted fake API server for K8sWatchSource: serves pre-planned
    lists and watch streams that inject 410 Gone mid-watch, raw connection
    drops, and re-lists replaying unchanged state — the failure modes a
    real apiserver exhibits (envtest-style adversarial soak)."""

    def __init__(self, lists, watches, swap_counter):
        self.lists = list(lists)          # [(items, rv)]
        self.watches = list(watches)      # [[("yield", type, obj)|("raise",)]]
        self.swap_counter = swap_counter
        self.list_params = []
        self.watch_params = []
        self.swaps_at_last_list = None
        self.done = asyncio.Event()       # set when the last watch parks

    def _ac_path(self, namespace=None, name=None):
        return "/apis/authorino.kuadrant.io/v1beta1/authconfigs"

    async def list_auth_configs_rv(self, selector):
        self.list_params.append(selector)
        entry = self.lists.pop(0) if self.lists else self.lists_last
        if entry == "raise":  # scripted apiserver outage during re-list
            raise RuntimeError("apiserver unavailable")
        items, rv = entry
        self.lists_last = (items, rv)
        if not self.lists:
            # capture the swap count as the FINAL list is served: the
            # unchanged re-list must not trigger another corpus swap
            self.swaps_at_last_list = self.swap_counter[0]
        return list(items), rv

    async def watch(self, path, params=None):
        self.watch_params.append(dict(params or {}))
        if not self.watches:
            self.done.set()
            await asyncio.Event().wait()  # park forever
        script = self.watches.pop(0)
        for action in script:
            if action[0] == "yield":
                yield action[1], action[2]
            elif action[0] == "raise":
                raise RuntimeError("connection reset by peer")


def v1_ac(name, rv, hosts):
    return {
        "apiVersion": "authorino.kuadrant.io/v1beta1",
        "kind": "AuthConfig",
        "metadata": {"namespace": "t", "name": name, "resourceVersion": rv},
        "spec": {"hosts": hosts},
    }


class TestAdversarialWatch:
    def test_gone_drops_and_stale_relists(self):
        from authorino_tpu.controllers.sources import K8sWatchSource

        async def body():
            engine = PolicyEngine()
            swaps = [0]
            engine.add_swap_listener(lambda: swaps.__setitem__(0, swaps[0] + 1))
            rec = AuthConfigReconciler(engine)

            a1 = v1_ac("a", "1", ["a.test"])
            a2 = v1_ac("a", "13", ["a2.test"])   # modified during outage 2
            b = v1_ac("b", "2", ["b.test"])
            c = v1_ac("c", "11", ["c.test"])
            lists = [
                ([a1, b], "10"),                  # L1: initial
                ([a1, c], "12"),                  # L2: B deleted while down
                ([a2, c], "14"),                  # L3: identical to live state
            ]
            watches = [
                # W1: new object arrives, then the server ends the resume
                # point with a 410 Gone ERROR status
                [("yield", "ADDED", c),
                 ("yield", "ERROR", {"kind": "Status", "code": 410})],
                # W2: a modification lands, then the stream drops raw
                [("yield", "MODIFIED", a2), ("raise",)],
                # W3+: park (scripted by the cluster itself)
            ]
            cluster = AdversarialCluster(lists, watches, swaps)
            src = K8sWatchSource(cluster, rec, resync_interval_s=0.01)
            src.start()
            await asyncio.wait_for(cluster.done.wait(), timeout=10)
            await asyncio.sleep(0.1)  # let the final (no-op) re-list settle

            # no missed delete: B disappeared during the first outage
            assert engine.lookup("b.test") is None
            assert rec.status.get("t/b") is None
            # modification during the second outage is live
            assert engine.lookup("a2.test") is not None
            assert engine.lookup("a.test") is None
            assert engine.lookup("c.test") is not None
            # no duplicate reconcile: the unchanged re-list (L3) caused no
            # further corpus swap
            assert cluster.swaps_at_last_list is not None
            assert swaps[0] == cluster.swaps_at_last_list
            # resume-point continuity across failures: watch #1 resumes from
            # the initial list, #2 from the post-410 re-list, #3 from the
            # last delivered event / final list
            rvs = [p.get("resourceVersion") for p in cluster.watch_params[:3]]
            assert rvs == ["10", "12", "14"], rvs
            # readiness: every surviving config reconciled
            assert rec.ready()
            assert rec.status.get("t/a").reason == STATUS_RECONCILED
            assert rec.status.get("t/c").reason == STATUS_RECONCILED
            await src.stop()

        run(body())


class TestResyncDedupRetry:
    def test_caching_error_retried_on_identical_relist(self, monkeypatch):
        """The resourceVersion dedup must NOT swallow retries of configs in
        CachingError: resyncs are their self-heal path (a transient Secret/
        discovery failure would otherwise wedge /readyz at 503 forever)."""
        from authorino_tpu.controllers import reconciler as rec_mod

        async def body():
            engine = PolicyEngine()
            rec = AuthConfigReconciler(engine)
            calls = {"n": 0}
            real = rec_mod.translate_auth_config

            async def flaky(*a, **k):
                calls["n"] += 1
                if calls["n"] == 1:
                    raise TranslationError("transient backend failure")
                return await real(*a, **k)

            monkeypatch.setattr(rec_mod, "translate_auth_config", flaky)
            cr = {
                "apiVersion": "authorino.kuadrant.io/v1beta2",
                "kind": "AuthConfig",
                "metadata": {"namespace": "t", "name": "x", "resourceVersion": "5"},
                "spec": {"hosts": ["x.test"]},
            }
            await rec.reconcile_all([cr])
            assert rec.status.get("t/x").reason == STATUS_CACHING_ERROR
            # identical re-list (same resourceVersion): must retry, not skip
            await rec.reconcile_all([dict(cr)])
            assert rec.status.get("t/x").reason == STATUS_RECONCILED
            assert rec.ready()
            # now healthy + unchanged: the next identical re-list skips
            swaps = [0]
            engine.add_swap_listener(lambda: swaps.__setitem__(0, swaps[0] + 1))
            await rec.reconcile_all([dict(cr)])
            assert swaps[0] == 0

        run(body())


class TestWatchBookmarksAndStorms:
    def test_bookmarks_advance_resume_point(self):
        """BOOKMARK events must advance the watch resume point without
        reconciling anything: after a drop, the next watch resumes from the
        bookmark's resourceVersion, so the re-watch window shrinks to
        nothing even when no real events flowed (informer bookmark
        semantics)."""
        from authorino_tpu.controllers.sources import K8sWatchSource

        async def body():
            engine = PolicyEngine()
            swaps = [0]
            engine.add_swap_listener(lambda: swaps.__setitem__(0, swaps[0] + 1))
            rec = AuthConfigReconciler(engine)
            a = v1_ac("a", "1", ["a.test"])
            bookmark = {"kind": "AuthConfig",
                        "metadata": {"resourceVersion": "42"}}
            # W1 ends gracefully after the bookmark; the follow-up re-list
            # FAILS (apiserver outage) — the bookmark rv is then the only
            # valid resume point, and the index must keep serving meanwhile
            lists = [([a], "10"), "raise", ([a], "43")]
            watches = [
                [("yield", "BOOKMARK", bookmark)],
                # W2: resumes from the bookmark rv, then parks via cluster
            ]
            cluster = AdversarialCluster(lists, watches, swaps)
            src = K8sWatchSource(cluster, rec, resync_interval_s=0.01)
            src.start()
            await asyncio.wait_for(cluster.done.wait(), timeout=10)
            swaps_after_first = swaps[0]
            assert engine.lookup("a.test") is not None
            # the second watch resumed from the BOOKMARK rv (42) — the
            # failed re-list could not refresh it, the bookmark carried it
            rvs = [p.get("resourceVersion") for p in cluster.watch_params[:2]]
            assert rvs == ["10", "42"], rvs
            # the bookmark itself reconciled nothing new
            assert swaps[0] == swaps_after_first
            await src.stop()

        run(body())

    def test_reconnect_storm_soak_zero_missed_deletes(self):
        """A storm of watch drops / 410s / stale re-lists while requests
        are being served: the index must end EXACTLY at the final apiserver
        state (no missed deletes, no zombies), readiness must hold, and
        every concurrent Check() must answer."""
        from authorino_tpu.controllers.sources import K8sWatchSource

        async def body():
            engine = PolicyEngine(max_batch=4)
            swaps = [0]
            engine.add_swap_listener(lambda: swaps.__setitem__(0, swaps[0] + 1))
            rec = AuthConfigReconciler(engine)

            # scripted evolution: 12 reconnect cycles; each cycle adds
            # cfg-i, deletes cfg-(i-3) — half the deletes happen DURING the
            # outage (only visible via the re-list), half on the stream
            rv = [100]

            def bump():
                rv[0] += 1
                return str(rv[0])

            live: dict = {}
            lists = []
            watches = []
            live["cfg-0"] = v1_ac("cfg-0", bump(), ["cfg-0.test"])
            lists.append((list(live.values()), bump()))  # initial list
            for i in range(1, 13):
                script = []
                added = v1_ac(f"cfg-{i}", bump(), [f"cfg-{i}.test"])
                live[f"cfg-{i}"] = added
                script.append(("yield", "ADDED", added))
                gone = f"cfg-{i - 3}"
                if gone in live:
                    doomed = live.pop(gone)
                    if i % 2 == 0:
                        # on-stream delete
                        script.append(("yield", "DELETED", doomed))
                    # odd i: delete silently during the outage — only the
                    # re-list can reveal it (the missed-delete trap)
                if i % 5 == 0:
                    script.append(
                        ("yield", "ERROR", {"kind": "Status", "code": 410}))
                else:
                    script.append(("raise",))
                watches.append(script)
                lists.append((list(live.values()), bump()))
            cluster = AdversarialCluster(lists, watches, swaps)
            src = K8sWatchSource(cluster, rec, resync_interval_s=0.005)
            src.start()

            # concurrent serving during the storm
            served = [0]
            stop_serving = asyncio.Event()

            async def serve():
                while not stop_serving.is_set():
                    req = CheckRequestModel(http=HttpRequestAttributes(
                        method="GET", path="/x",
                        host=f"cfg-{served[0] % 13}.test"))
                    result = await engine.check(req)
                    assert result is not None
                    served[0] += 1
                    await asyncio.sleep(0)

            server_task = asyncio.ensure_future(serve())
            try:
                await asyncio.wait_for(cluster.done.wait(), timeout=20)
                await asyncio.sleep(0.1)  # let the final re-list settle
            finally:
                stop_serving.set()
                await server_task

            # zero missed deletes, zero zombies: the index is EXACTLY the
            # final live set
            for i in range(13):
                name = f"cfg-{i}"
                if name in live:
                    assert engine.lookup(f"{name}.test") is not None, name
                    assert rec.status.get(f"t/{name}").reason == STATUS_RECONCILED
                else:
                    assert engine.lookup(f"{name}.test") is None, f"zombie {name}"
                    assert rec.status.get(f"t/{name}") is None
            assert rec.ready()
            assert served[0] > 0
            await src.stop()

        run(body())


class TestTopLevelWhenFolding:
    def test_anonymous_gate_folds_into_kernel(self):
        """An AuthConfig-level `when` gate on an anonymous pattern config
        compiles into every evaluator's condition (unmatched gate ⇒ whole
        pipeline skipped ⇒ OK, ref auth_pipeline.go:454-457) so the config
        keeps the kernel fast lane (round 4)."""
        from authorino_tpu.runtime.native_frontend import fast_lane_eligible

        engine = PolicyEngine(max_batch=8, mesh=None)
        spec = {
            "hosts": ["gated.test"],
            "when": [{"selector": "request.method",
                      "operator": "neq", "value": "OPTIONS"}],
            "authentication": {"anon": {"anonymous": {}}},
            "authorization": {"rules": {"patternMatching": {"patterns": [
                {"selector": "request.headers.x-org",
                 "operator": "eq", "value": "acme"}]}}},
        }
        entry = run(translate_auth_config("gated", "t", spec, engine=engine))
        # the gate moved into the compiled rules
        assert entry.runtime.conditions is None
        cond, _rule = entry.rules.evaluators[0]
        assert cond is not None
        engine.apply_snapshot([entry])
        assert fast_lane_eligible(entry, engine._snapshot.policy) is not None

        async def check(method, headers=None):
            req = CheckRequestModel(http=HttpRequestAttributes(
                method=method, path="/x", host="gated.test",
                headers=headers or {}))
            return (await engine.check(req)).code

        # gate unmatched (OPTIONS) → whole pipeline skipped → OK
        assert run(check("OPTIONS")) == 0
        # gate matched: the rule decides
        assert run(check("GET", {"x-org": "acme"})) == 0
        assert run(check("GET", {"x-org": "evil"})) == 7

    def test_credential_identity_gate_does_not_fold(self):
        """Folding is only sound for anonymous identities: a skipped
        pipeline must allow credential-less requests, which the credential
        fast lane could not honor — the gate stays on the pipeline."""
        engine = PolicyEngine(max_batch=8)
        cluster = InMemoryCluster()
        cluster.put_secret(Secret(name="k", namespace="t",
                                  labels={"g": "w"}, data={"api_key": b"s3"}))
        spec = {
            "hosts": ["gated-key.test"],
            "when": [{"selector": "context.request.http.method",
                      "operator": "neq", "value": "OPTIONS"}],
            "authentication": {"keys": {"apiKey": {
                "selector": {"matchLabels": {"g": "w"}}}}},
            "authorization": {"rules": {"patternMatching": {"patterns": [
                {"selector": "context.request.http.headers.x-org",
                 "operator": "eq", "value": "acme"}]}}},
        }
        entry = run(translate_auth_config("gk", "t", spec,
                                          cluster=cluster, engine=engine))
        assert entry.runtime.conditions is not None
        engine.apply_snapshot([entry])

        async def check(method, headers=None):
            req = CheckRequestModel(http=HttpRequestAttributes(
                method=method, path="/x", host="gated-key.test",
                headers=headers or {}))
            return (await engine.check(req)).code

        # skipped pipeline allows even without credentials
        assert run(check("OPTIONS")) == 0
        # gate matched: credentials enforced
        assert run(check("GET")) == 16

    def test_auth_rooted_gate_does_not_fold(self):
        """The reference evaluates the AuthConfig gate at pipeline start,
        where auth.identity is still None (ref auth_pipeline.go:454-457);
        a folded gate would see the resolved anonymous identity instead.
        `auth.identity.anonymous neq "true"` matches pre-resolution
        (missing selector → "") and runs the deny rules — after folding it
        would be unmatched and ALLOW, a fail-open divergence.  Any
        auth.*-rooted selector keeps the gate on the pipeline."""
        engine = PolicyEngine(max_batch=8, mesh=None)
        spec = {
            "hosts": ["gated-auth.test"],
            "when": [{"selector": "auth.identity.anonymous",
                      "operator": "neq", "value": "true"}],
            "authentication": {"anon": {"anonymous": {}}},
            "authorization": {"rules": {"patternMatching": {"patterns": [
                {"selector": "request.headers.x-org",
                 "operator": "eq", "value": "acme"}]}}},
        }
        entry = run(translate_auth_config("ga", "t", spec, engine=engine))
        assert entry.runtime.conditions is not None
        engine.apply_snapshot([entry])

        async def check(headers=None):
            req = CheckRequestModel(http=HttpRequestAttributes(
                method="GET", path="/x", host="gated-auth.test",
                headers=headers or {}))
            return (await engine.check(req)).code

        # gate matches pre-resolution ("" neq "true") → rules enforced
        assert run(check({"x-org": "evil"})) == 7
        assert run(check({"x-org": "acme"})) == 0

    def test_nested_auth_rooted_gate_does_not_fold(self):
        """auth.* detection must walk nested And/Or gate trees."""
        engine = PolicyEngine(max_batch=8, mesh=None)
        spec = {
            "hosts": ["gated-nest.test"],
            "patterns": {"who": [
                {"selector": "auth.identity.sub", "operator": "eq", "value": "x"}]},
            "when": [{"any": [
                {"selector": "request.method", "operator": "eq", "value": "GET"},
                {"patternRef": "who"},
            ]}],
            "authentication": {"anon": {"anonymous": {}}},
            "authorization": {"rules": {"patternMatching": {"patterns": [
                {"selector": "request.headers.x-org",
                 "operator": "eq", "value": "acme"}]}}},
        }
        entry = run(translate_auth_config("gn", "t", spec, engine=engine))
        assert entry.runtime.conditions is not None

    def test_conditioned_anonymous_identity_does_not_fold(self):
        """A conditional anonymous identity could turn gate-unmatched
        requests from skip-OK into 401 under the fold — the gate must stay
        on the pipeline."""
        engine = PolicyEngine(max_batch=8, mesh=None)
        spec = {
            "hosts": ["gated-cond.test"],
            "when": [{"selector": "request.method",
                      "operator": "neq", "value": "OPTIONS"}],
            "authentication": {"anon": {"anonymous": {}, "when": [
                {"selector": "request.headers.x-flag",
                 "operator": "eq", "value": "on"}]}},
            "authorization": {"rules": {"patternMatching": {"patterns": [
                {"selector": "request.headers.x-org",
                 "operator": "eq", "value": "acme"}]}}},
        }
        entry = run(translate_auth_config("gc", "t", spec, engine=engine))
        assert entry.runtime.conditions is not None
        engine.apply_snapshot([entry])

        async def check(method, headers=None):
            req = CheckRequestModel(http=HttpRequestAttributes(
                method=method, path="/x", host="gated-cond.test",
                headers=headers or {}))
            return (await engine.check(req)).code

        # gate unmatched → skip whole pipeline → OK despite the identity's
        # own (unmatched) conditions
        assert run(check("OPTIONS")) == 0
        # gate matched, identity conditions unmatched → UNAUTHENTICATED
        assert run(check("GET", {"x-org": "acme"})) == 16
