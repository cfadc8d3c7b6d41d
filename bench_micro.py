#!/usr/bin/env python
"""The five reference micro-benchmarks, re-measured on this framework
(parity: ref Makefile:135-142 `make benchmarks`; numbers to beat are the
published geomeans reproduced in BASELINE.md).

  1. ReconcileAuthConfig — translate an AuthConfig (OIDC identity w/ live
     discovery against a local fake IdP, UserInfo + UMA metadata, inline-
     Rego OPA precompile) + compile the pattern corpus + index the hosts.
  2. AuthPipeline       — full 5-phase Check() evaluation: OIDC/JWT verify
     (local JWKS) + JSON pattern authz on a JWT claim.
  3. APIKeyAuthn        — API-key identity evaluator only.
  4. JSONPatternMatchingAuthz — one pattern-matching evaluator, 2 eq rules:
     (a) the sequential CPU expression path (like-for-like with the
     reference's single-threaded number), and (b) the batched device
     kernel, amortized per request — the number this framework exists for.
  5. OPAAuthz           — precompiled inline-Rego evaluator.

Prints a BASELINE.md-style markdown table with the reference values and
the measured ratio.  Honors JAX_PLATFORMS=cpu for chip-free smoke runs
(only benchmark 4b touches the device).

Usage: python bench_micro.py [--seconds-per-bench 2.0] [--batch 8192]
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

REFERENCE_US = {  # BASELINE.md geomeans (Xeon 8370C), µs/op
    "ReconcileAuthConfig": 1491.0,
    "AuthPipeline": 363.9,
    "APIKeyAuthn": 3.148,
    "JSONPatternMatchingAuthz": 1.775,
    "OPAAuthz": 93.31,
}

RIGHTS_REGO = """\
allow {
  input.auth.identity.realm_access.roles[_] == "admin"
}
"""


def log(*a):
    print(*a, file=sys.stderr, flush=True)


class FakeIdP:
    """Local discovery + JWKS + userinfo endpoints (the reference's
    benchmarks run against an equivalent local HTTP mock —
    ref pkg/service/auth_pipeline_test.go:548-560)."""

    def __init__(self):
        from cryptography.hazmat.primitives.asymmetric import rsa

        self.key = rsa.generate_private_key(public_exponent=65537, key_size=2048)
        self.issuer = None

    def token(self):
        from authorino_tpu.utils import jose

        iat = int(time.time())
        return jose.sign_jwt(
            {"iss": self.issuer, "sub": "john", "iat": iat, "exp": iat + 3600,
             "email_verified": True, "realm_access": {"roles": ["admin"]}},
            self.key, "RS256", kid="k1",
        )

    def app(self):
        from aiohttp import web

        from authorino_tpu.utils import jose

        app = web.Application()

        async def well_known(_):
            return web.json_response({
                "issuer": self.issuer,
                "jwks_uri": f"{self.issuer}/jwks",
                "userinfo_endpoint": f"{self.issuer}/userinfo",
                "token_endpoint": f"{self.issuer}/token",
            })

        async def jwks(_):
            return web.json_response(
                {"keys": [jose.jwk_from_public_key(self.key.public_key(), kid="k1")]}
            )

        app.router.add_get("/.well-known/openid-configuration", well_known)
        app.router.add_get("/jwks", jwks)
        return app


async def bench_async(fn, seconds: float, min_ops: int = 32):
    """Time repeated awaits of fn(); returns µs/op."""
    # warmup
    for _ in range(3):
        await fn()
    ops = 0
    t0 = time.perf_counter()
    while True:
        await fn()
        ops += 1
        if ops >= min_ops and time.perf_counter() - t0 >= seconds:
            break
    return (time.perf_counter() - t0) / ops * 1e6, ops


RECONCILE_SPEC = {
    # the reference's reconcile fixture shape: OIDC + UserInfo + UMA + OPA
    # (ref controllers/auth_config_controller_test.go:430)
    "hosts": ["echo-api"],
    "authentication": {
        "keycloak": {"jwt": {"issuerUrl": "{ISSUER}"}},
    },
    "metadata": {
        "userinfo": {"userInfo": {"identitySource": "keycloak"}},
        "resource-data": {"uma": {"endpoint": "{ISSUER}"}},
    },
    "authorization": {
        "main-policy": {"opa": {"rego": RIGHTS_REGO}},
        "some-extra-rules": {"patternMatching": {"patterns": [
            {"selector": "auth.identity.email_verified", "operator": "eq", "value": "true"},
            {"selector": "request.path", "operator": "neq", "value": "/forbidden"},
        ]}},
    },
}


def resolve(spec, issuer):
    out = json.loads(json.dumps(spec))
    out["authentication"]["keycloak"]["jwt"]["issuerUrl"] = issuer
    out["metadata"]["resource-data"]["uma"]["endpoint"] = issuer
    return out


async def run_benchmarks(seconds: float, batch: int, workers: int):
    from aiohttp.test_utils import TestServer

    from authorino_tpu.authjson import CheckRequestModel, HttpRequestAttributes
    from authorino_tpu.compiler import ConfigRules, compile_corpus
    from authorino_tpu.controllers.translate import translate_auth_config
    from authorino_tpu.evaluators import AuthCredentials, RuntimeAuthConfig, IdentityConfig
    from authorino_tpu.evaluators.authorization import OPA, PatternMatching
    from authorino_tpu.evaluators.identity import APIKey, Noop
    from authorino_tpu.expressions import All, Operator, Pattern
    from authorino_tpu.index import HostIndex
    from authorino_tpu.k8s.client import LabelSelector, Secret
    from authorino_tpu.pipeline import AuthPipeline

    results = {}

    idp = FakeIdP()
    server = TestServer(idp.app())
    await server.start_server()
    idp.issuer = str(server.make_url("")).rstrip("/")
    spec = resolve(RECONCILE_SPEC, idp.issuer)

    # ---- 1. ReconcileAuthConfig -------------------------------------------
    async def reconcile():
        entry = await translate_auth_config("echo-api", "bench", spec)
        compile_corpus([entry.rules] if entry.rules else [])
        index = HostIndex()
        for host in entry.hosts:
            index.set(entry.id, host, entry)

    results["ReconcileAuthConfig"] = await bench_async(reconcile, seconds, min_ops=8)

    # ---- 2. AuthPipeline (OIDC/JWT verify + pattern authz) ----------------
    entry = await translate_auth_config("echo-api", "bench", spec)
    runtime = entry.runtime
    # the reference's AuthPipeline fixture is JWT verify + JSON patterns
    # ONLY (ref pkg/service/auth_pipeline_test.go:541-560) — no metadata
    # HTTP fan-out, no OPA
    runtime.authorization = [a for a in runtime.authorization if a.name != "main-policy"]
    runtime.metadata = []
    token = idp.token()

    def check_request():
        return CheckRequestModel(
            http=HttpRequestAttributes(
                method="GET", path="/hello", host="echo-api",
                headers={"authorization": f"Bearer {token}"},
            )
        )

    async def pipeline_op():
        result = await AuthPipeline(check_request(), runtime).evaluate()
        assert result.success(), result.message

    results["AuthPipeline"] = await bench_async(pipeline_op, seconds)

    # ---- 3. APIKeyAuthn ---------------------------------------------------
    api_key = APIKey("friends", LabelSelector.from_spec({"matchLabels": {"audience": "echo"}}),
                     credentials=AuthCredentials(key_selector="APIKEY"))
    api_key.add_k8s_secret_based_identity(
        Secret(namespace="bench", name="key1",
               labels={"audience": "echo"}, data={"api_key": b"ndyBzreUzF4zqDQsqSPMHkRhriEOtcRx"})
    )
    key_req = CheckRequestModel(
        http=HttpRequestAttributes(
            method="GET", path="/", host="echo-api",
            headers={"authorization": "APIKEY ndyBzreUzF4zqDQsqSPMHkRhriEOtcRx"},
        )
    )
    key_runtime = RuntimeAuthConfig(identity=[IdentityConfig("friends", api_key)])
    key_pipeline = AuthPipeline(key_req, key_runtime)  # evaluator-only op,
    # like the reference's mocked-pipeline benchmark (api_key_test.go:140)

    async def apikey_op():
        await api_key.call(key_pipeline)

    results["APIKeyAuthn"] = await bench_async(apikey_op, seconds)

    # ---- 4a. JSONPatternMatchingAuthz (sequential CPU path) ---------------
    two_eq = All(
        Pattern("auth.identity.email_verified", Operator.EQ, "true"),
        Pattern("request.path", Operator.EQ, "/hello"),
    )
    pm = PatternMatching(two_eq)
    anon = IdentityConfig("anon", Noop())
    pm_pipeline = AuthPipeline(check_request(), RuntimeAuthConfig(identity=[anon]))
    pm_pipeline.identity_results[anon] = {"email_verified": True}
    pm_pipeline._sync_auth()

    async def pattern_op():
        await pm.call(pm_pipeline)

    results["JSONPatternMatchingAuthz"] = await bench_async(pattern_op, seconds)

    # ---- 4b. the same 2-eq evaluator, batched on the device ---------------
    import threading

    import numpy as np

    from authorino_tpu.models import PolicyModel
    from authorino_tpu.ops.pattern_eval import dispatch_packed

    model = PolicyModel.from_configs(
        [ConfigRules(name="cfg", evaluators=[(None, two_eq)])], members_k=8
    )
    doc = {"auth": {"identity": {"email_verified": True}}, "request": {"path": "/hello"}}
    db = model.encode([doc] * batch, [0] * batch, batch_pad=batch)
    np.asarray(dispatch_packed(model.params, db))  # warmup + XLA compile

    stop_at = time.perf_counter() + max(seconds, 2.0)
    totals = [0] * workers

    def device_worker(w):
        while time.perf_counter() < stop_at:
            np.asarray(dispatch_packed(model.params, db))
            totals[w] += batch

    threads = [threading.Thread(target=device_worker, args=(w,)) for w in range(workers)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    dev_elapsed = time.perf_counter() - t0
    results["JSONPatternMatchingAuthz/batched"] = (
        dev_elapsed / max(sum(totals), 1) * 1e6, sum(totals) // batch
    )

    # ---- 5. OPAAuthz ------------------------------------------------------
    opa = OPA("main-policy", inline_rego=RIGHTS_REGO)
    opa_pipeline = AuthPipeline(check_request(), RuntimeAuthConfig(identity=[anon]))
    opa_pipeline.identity_results[anon] = {"realm_access": {"roles": ["admin"]}}
    opa_pipeline._sync_auth()

    async def opa_op():
        assert await opa.call(opa_pipeline)

    results["OPAAuthz"] = await bench_async(opa_op, seconds)

    await server.close()
    from authorino_tpu.utils.http import close_sessions

    await close_sessions()
    return results


def run_kernel_cost_grid(args):
    """Structural device-cost grid (ISSUE 16, KERNELCOST_r01.json):
    launches / H2D+D2H bytes / pad occupancy per row over a
    (batch, members_k, n_dfa_tables) grid, counted by the runtime's own
    CostLedger at the engine dispatch site, plus the XLA-modeled
    flops/bytes per row at each shape.  Deliberately cryptography-free
    (no FakeIdP): everything here is compile + device dispatch.  The
    numbers are STRUCTURAL — exact on any platform; no RPS claims."""
    import jax

    from authorino_tpu.compiler import ConfigRules
    from authorino_tpu.expressions import All, Operator, Pattern
    from authorino_tpu.runtime import EngineEntry, PolicyEngine
    from authorino_tpu.runtime.kernel_cost import LEDGER

    def cell_configs(n_dfa):
        configs = []
        for i in range(8):
            pats = [Pattern("request.method", Operator.EQ, "GET"),
                    Pattern("auth.identity.roles", Operator.INCL,
                            f"role-{i}")]
            # each distinct device-lowerable regex mints its own DFA
            # table: n_dfa scales the attr_bytes/byte_ovf operand lane
            for d in range(n_dfa):
                pats.append(Pattern("request.url_path", Operator.MATCHES,
                                    rf"^/api/v{d}/x{i}"))
            configs.append(ConfigRules(
                name=f"cfg-{i}", evaluators=[(None, All(*pats))]))
        return configs

    async def run_cell(engine, batch):
        docs = [{"request": {"method": "GET", "host": "cfg-0",
                             "url_path": f"/api/v0/x{j % 8}",
                             "headers": {"x-row": f"r{j}"}},
                 "auth": {"identity": {"roles": [f"role-{j % 8}"],
                                       "org": f"org-{j}"}}}
                for j in range(batch)]
        await asyncio.gather(*(engine.submit(d, f"cfg-{j % 8}")
                               for j, d in enumerate(docs)))

    raw = ("batches", "launches", "rows", "device_rows", "pad_rows",
           "pad_waste_rows", "h2d_bytes", "d2h_bytes")
    grid = []
    for members_k in args.grid_members_k:
        for n_dfa in args.grid_dfa:
            configs = cell_configs(n_dfa)
            for batch in args.grid_batches:
                # dedup/cache off: the grid measures the device cost of
                # B REAL rows, not the avoidance planes
                engine = PolicyEngine(max_batch=batch,
                                      members_k=members_k, mesh=None,
                                      lane_select=False, batch_dedup=False,
                                      verdict_cache_size=0)
                engine.apply_snapshot([
                    EngineEntry(id=c.name, hosts=[c.name], runtime=None,
                                rules=c) for c in configs])
                policy = engine._snapshot.policy
                before = LEDGER.snapshot("engine")
                asyncio.run(run_cell(engine, batch))
                after = LEDGER.snapshot("engine")
                d = {k: after[k] - before[k] for k in raw}
                modeled = (engine.debug_vars()["kernel_cost"]["modeled"]
                           ["current"] or {}).get("entries", {})
                mb = modeled.get("eval_bitpacked") or {}
                cell = {
                    "batch": batch,
                    "members_k": members_k,
                    "n_dfa_tables": int(policy.dfa_tables.shape[0]
                                        if policy.n_byte_attrs else 0),
                    "launches_per_batch": round(
                        d["launches"] / max(d["batches"], 1), 4),
                    "h2d_bytes_per_device_row": round(
                        d["h2d_bytes"] / max(d["device_rows"], 1), 2),
                    "d2h_bytes_per_pad_row": round(
                        d["d2h_bytes"] / max(d["pad_rows"], 1), 2),
                    "pad_occupancy": round(
                        d["device_rows"] / max(d["pad_rows"], 1), 4),
                    "modeled_flops_per_row": mb.get("flops_per_row"),
                    "modeled_bytes_per_row": mb.get("bytes_per_row"),
                    "ledger_delta": d,
                }
                grid.append(cell)
                log(f"cell batch={batch} members_k={members_k} "
                    f"n_dfa={cell['n_dfa_tables']}: "
                    f"launches/batch={cell['launches_per_batch']} "
                    f"h2d/row={cell['h2d_bytes_per_device_row']} "
                    f"d2h/pad-row={cell['d2h_bytes_per_pad_row']} "
                    f"occupancy={cell['pad_occupancy']}")

    artifact = {
        "round": "r01",
        "issue": 16,
        "metric": "kernel_cost_structural",
        "platform": f"jax {jax.__version__} {jax.devices()}",
        "load_model": "closed-loop",
        "caveat": "structural counts and per-row ratios ONLY (launches, "
                  "bytes, pad occupancy, modeled flops) — exact on any "
                  "platform; no RPS/latency claims (ROADMAP bench-reality "
                  "note)",
        "grid_axes": {"batch": list(args.grid_batches),
                      "members_k": list(args.grid_members_k),
                      "n_dfa_regexes_per_config": list(args.grid_dfa)},
        "grid": grid,
    }
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "KERNELCOST_r01.json")
    with open(path, "w") as f:
        json.dump(artifact, f, indent=1, sort_keys=True)
    log(f"wrote {path}")
    print(json.dumps({"metric": "kernel_cost_structural",
                      "cells": len(grid), "artifact": path}))
    return artifact


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seconds-per-bench", type=float, default=2.0)
    ap.add_argument("--batch", type=int, default=8192)
    ap.add_argument("--workers", type=int, default=12,
                    help="in-flight batches for the batched lane")
    ap.add_argument("--kernel-cost-grid", action="store_true",
                    help="ISSUE 16: emit the structural kernel-cost grid "
                         "(KERNELCOST_r01.json) instead of the reference "
                         "micro-benchmarks — cryptography-free")
    ap.add_argument("--grid-batches", type=int, nargs="+",
                    default=[16, 128])
    ap.add_argument("--grid-members-k", type=int, nargs="+",
                    default=[4, 16])
    ap.add_argument("--grid-dfa", type=int, nargs="+", default=[0, 2],
                    help="device-lowerable regexes per config (each mints "
                         "DFA tables, scaling the attr_bytes operand lane)")
    args = ap.parse_args()

    import jax

    from authorino_tpu.utils.jax_env import setup_jax

    setup_jax()
    platform = jax.devices()[0].platform

    if args.kernel_cost_grid:
        run_kernel_cost_grid(args)
        return

    results = asyncio.run(run_benchmarks(args.seconds_per_bench, args.batch, args.workers))

    print(f"\n### Micro-benchmarks vs reference (device platform: {platform})\n")
    print("| Benchmark | reference (Go, 1 Xeon core) | this framework | ratio |")
    print("|---|---|---|---|")
    rows = {}
    for name, (us, ops) in results.items():
        base = REFERENCE_US.get(name.split("/")[0])
        ratio = base / us if base else None
        rows[name] = {"us_per_op": round(us, 3), "ops": ops,
                      "reference_us": base, "speedup": round(ratio, 3) if ratio else None}
        ref_s = f"{base:,.3f} µs/op" if base else "—"
        speed = f"{ratio:.2f}× {'faster' if ratio >= 1 else 'slower'}" if ratio else "—"
        print(f"| {name} | {ref_s} | {us:,.3f} µs/op ({ops} ops) | {speed} |")
    print()
    print(json.dumps({"metric": "micro_bench", "platform": platform, "results": rows}))

    # file artifact alongside the stdout markdown (ISSUE 16 satellite —
    # BENCH_*-style, platform-stamped): the driver can diff runs without
    # scraping the table
    from authorino_tpu.runtime.kernel_cost import LEDGER

    artifact = {
        "metric": "micro_bench",
        "platform": f"jax {jax.__version__} {jax.devices()}",
        "load_model": "closed-loop",
        "caveat": "single-process µs/op vs the Go reference geomeans "
                  "(BASELINE.md); only benchmark 4b touches the device",
        "reference_us": REFERENCE_US,
        "results": rows,
        "kernel_cost": LEDGER.to_json(),
    }
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_MICRO_r01.json")
    with open(path, "w") as f:
        json.dump(artifact, f, indent=1, sort_keys=True)
    log(f"wrote {path}")


if __name__ == "__main__":
    main()
