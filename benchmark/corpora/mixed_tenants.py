"""Corpus `mixed_tenants`: `tenant_rules`' N small AuthConfigs (ten flat
patternMatching rules, one host each) beside a few large ones that protect a
monolith's REST API route by route with ONE AuthConfig: `services` x
`route_rules`' 16 route kinds route-scoped evaluators and one catch-all.

Small tenant i is `tenant_rules.manifests`' config i as it stands (host
`svc-<i>.bench.test`).  Large tenant j (host `api-<j>.bench.test`, name
`api-<j>`) has, for service m and route kind k (route_rules.ROUTES):

  s<m>-route-<kk>: when     request.url_path matches ^/api/v[0-9]+/t<j>/s<m>/<tail k>$
                            request.method   eq      <method k>
                   patterns request.headers.x-role incl role-<(j+m+k)%17>
                            request.headers.x-org  eq   org-<j>
  tenant:          route_rules' catch-all for tenant j (tenant prefix and
                   request id `matches`, x-tier `excl`), no `when`

A one-digit j and the two-byte service segment keep the UUID route at 67 DFA
states, so the large configs' state axis is `routes-1k`'s 72.  Both base
generators are loaded by path and edited nowhere; the rows of each
population are built and broken as its base generator builds and breaks
them.
"""

from __future__ import annotations

import importlib.util
import os
import random
from typing import Any, Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))


def _load(name: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_corpora_{name}", os.path.join(HERE, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_small = _load("tenant_rules")
_routes = _load("route_rules")
NAMESPACE = _small.NAMESPACE
ROUTES, ROLES = _routes.ROUTES, _routes.ROLES


def large_host(j: int) -> str:
    return f"api-{j}.bench.test"


def route_regex(j: int, m: int, k: int) -> str:
    return f"^/api/v[0-9]+/t{j}/s{m}/{ROUTES[k][2]}$"


def _large_evaluators(j: int, services: int) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for m in range(services):
        for k, (_, method, _, _, _) in enumerate(ROUTES):
            out[f"s{m}-route-{k:02d}"] = {
                "when": [
                    {"selector": "request.url_path", "operator": "matches",
                     "value": route_regex(j, m, k)},
                    {"selector": "request.method", "operator": "eq",
                     "value": method},
                ],
                "patternMatching": {"patterns": [
                    {"selector": "request.headers.x-role", "operator": "incl",
                     "value": f"role-{(j + m + k) % ROLES}"},
                    {"selector": "request.headers.x-org", "operator": "eq",
                     "value": f"org-{j}"},
                ]},
            }
    out["tenant"] = _routes._evaluators(j)["tenant"]
    return out


def manifests(params: Dict[str, Any]) -> List[Dict[str, Any]]:
    services = int(params["services"])
    return _small.manifests(params) + [{
        "apiVersion": "authorino.kuadrant.io/v1beta2",
        "kind": "AuthConfig",
        "metadata": {"name": f"api-{j}", "namespace": NAMESPACE},
        "spec": {
            "hosts": [large_host(j)],
            "authentication": {"anon": {"anonymous": {}}},
            "authorization": _large_evaluators(j, services),
        },
    } for j in range(int(params["n_large"]))]


def _small_row(i: int, rng: random.Random, params: Dict[str, Any]) -> Dict[str, Any]:
    """One row of small tenant i, as tenant_rules.requests builds it."""
    vals = _small._allowed(i, rng)
    broke = None
    if rng.random() < float(params["deny_share"]):
        broke, breaker = _small._VIOLATIONS[rng.randrange(len(_small._VIOLATIONS))]
        vals[broke] = breaker(i, vals)
    return {"host": _small._host(i), "method": vals.pop("method"),
            "path": vals.pop("path"), "headers": vals,
            "kind": "small", "broke": broke, "route": None}


def _large_row(j: int, rng: random.Random, params: Dict[str, Any]) -> Dict[str, Any]:
    """One row of large tenant j, as route_rules._row builds a tenant's, with
    the service segment in its path and the service in its role."""
    m = rng.randrange(int(params["services"]))
    k = rng.randrange(len(ROUTES))
    _, method, _, short, long_ = ROUTES[k]
    prefix = f"/api/v{rng.randrange(1, 10)}/t{j}/s{m}/"
    long_p = (float(params["long_path_share"]) * len(ROUTES)
              / len(_routes.LONG_ROUTES))
    if long_ is not None and rng.random() < long_p:
        tail = long_(rng, rng.randrange(_routes.LONG_MIN, _routes.LONG_MAX + 1)
                     - len(prefix))
    else:
        tail = short(rng, _routes.SHORT_MAX - len(prefix))
    headers = {
        "x-request-id": f"r{j}-{rng.getrandbits(32):08x}",
        "x-role": f"role-{(j + m + k) % ROLES}",
        "x-org": f"org-{j}",
        "x-tier": rng.choice(["gold", "silver", f"banned-{j + 1}"]),
    }
    kind = "routed"
    if rng.random() < float(params["unrouted_share"]):
        if rng.random() < 0.5:
            kind = "unrouted-path"  # as long as the route's path was
            tail = "status/" + _routes._slug(rng, max(len(tail) - len("status/"), 3))
        else:
            kind = "other-method"
            method = rng.choice([x for x in _routes.METHODS if x != method])
        headers["x-role"] = f"role-{rng.randrange(ROLES)}"
        headers["x-org"] = f"org-{rng.choice([j, j + 1])}"
    path = prefix + tail
    broke = None
    if rng.random() < float(params["deny_share"]):
        broke = rng.choice(_routes.BREAKS if kind == "routed"
                           else _routes.BREAKS[2:])
        if broke == "role":
            headers["x-role"] = (
                f"role-{(j + m + k + 1 + rng.randrange(ROLES - 1)) % ROLES}")
        elif broke == "org":
            headers["x-org"] = f"org-{j + 1}"
        elif broke == "request-id":
            headers["x-request-id"] = headers["x-request-id"][:-1] + "Z"
        elif broke == "tier":
            headers["x-tier"] = f"banned-{j}"
        else:
            path = path.replace(f"/t{j}/", f"/t{j + 1}/", 1)
    return {"host": large_host(j), "method": method, "path": path,
            "headers": headers, "kind": kind, "broke": broke,
            "route": (m, k)}


def requests(params: Dict[str, Any], n: int, rng: random.Random,
             kinds: bool = False) -> List[Dict[str, Any]]:
    """n distinct rows: `large_share` of them go to the large tenants
    (uniform over them, their services and the 16 route kinds), the rest
    uniform over the small ones.  `deny_share` of either population break
    exactly one thing, as its base generator breaks it; of the large
    tenants' rows `unrouted_share` take no route and `long_path_share` carry
    a path of 65-96 bytes.  `kinds=True` keeps each row's `kind`, `broke`
    and `route` (the tests')."""
    n_small, n_large = int(params["n_configs"]), int(params["n_large"])
    large_share = float(params["large_share"])
    rows, seen = [], set()
    while len(rows) < n:
        if rng.random() < large_share:
            row = _large_row(rng.randrange(n_large), rng, params)
        else:
            row = _small_row(rng.randrange(n_small), rng, params)
        ident = (row["host"], row["headers"]["x-request-id"], row["path"])
        if ident in seen:
            continue
        seen.add(ident)
        if not kinds:
            row = {key: row[key] for key in ("host", "method", "path", "headers")}
        rows.append(row)
    return rows
