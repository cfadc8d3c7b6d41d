"""Corpus `tenant_rules`: N AuthConfigs, one host each, anonymous identity,
ten patternMatching rules a config (BASELINE.json class 4).

Copied from chip_smoke.py, whose corpus PR 21 proved on the chip: two
per-config `matches` regexes, one `incl`, one `excl`, six `eq`/`neq`.
"""

from __future__ import annotations

import random
from typing import Any, Dict, List

NAMESPACE = "bench"


def _patterns(i: int) -> List[Dict[str, str]]:
    return [
        {"selector": "request.method", "operator": "neq", "value": "DELETE"},
        {"selector": "request.url_path", "operator": "matches",
         "value": f"^/api/v[0-9]+/t{i}/[a-z0-9/_-]*$"},
        {"selector": "request.headers.x-request-id", "operator": "matches",
         "value": f"^r{i}-[0-9a-f]{{8}}$"},
        {"selector": "request.headers.x-role", "operator": "incl",
         "value": f"role-{i % 17}"},
        {"selector": "request.headers.x-tier", "operator": "excl",
         "value": f"banned-{i}"},
        {"selector": "request.headers.x-org", "operator": "eq",
         "value": f"org-{i}"},
        {"selector": "request.headers.x-env", "operator": "neq",
         "value": "dev"},
        {"selector": "request.headers.x-region", "operator": "eq",
         "value": f"region-{i % 7}"},
        {"selector": "request.headers.x-plan", "operator": "neq",
         "value": f"free-{i}"},
        {"selector": "request.headers.x-client", "operator": "eq",
         "value": f"client-{i}"},
    ]


def _host(i: int) -> str:
    return f"svc-{i}.bench.test"


def manifests(params: Dict[str, Any]) -> List[Dict[str, Any]]:
    return [{
        "apiVersion": "authorino.kuadrant.io/v1beta2",
        "kind": "AuthConfig",
        "metadata": {"name": f"cfg-{i:05d}", "namespace": NAMESPACE},
        "spec": {
            "hosts": [_host(i)],
            "authentication": {"anon": {"anonymous": {}}},
            "authorization": {"rules": {"patternMatching": {
                "patterns": _patterns(i)}}},
        },
    } for i in range(int(params["n_configs"]))]


def _allowed(i: int, rng: random.Random) -> Dict[str, str]:
    """Values that satisfy all ten rules of config i.  The request id and the
    item are drawn per row, so every encoded row is distinct."""
    return {
        "method": rng.choice(["GET", "POST", "PUT"]),
        "path": f"/api/v{rng.randrange(1, 10)}/t{i}/items/{rng.randrange(10**6)}",
        "x-request-id": f"r{i}-{rng.getrandbits(32):08x}",
        "x-role": f"role-{i % 17}",
        "x-tier": rng.choice(["gold", "silver", f"banned-{i + 1}"]),
        "x-org": f"org-{i}",
        "x-env": rng.choice(["prod", "staging"]),
        "x-region": f"region-{i % 7}",
        "x-plan": rng.choice(["team", f"free-{i + 1}"]),
        "x-client": f"client-{i}",
    }


# one way to break each of the ten rules, in _patterns' order
_VIOLATIONS = (
    ("method", lambda i, v: "DELETE"),
    ("path", lambda i, v: v["path"].replace(f"/t{i}/", f"/t{i + 1}/")),
    ("x-request-id", lambda i, v: v["x-request-id"][:-1] + "Z"),
    ("x-role", lambda i, v: f"role-{(i + 1) % 17}"),
    ("x-tier", lambda i, v: f"banned-{i}"),
    ("x-org", lambda i, v: f"org-{i + 1}"),
    ("x-env", lambda i, v: "dev"),
    ("x-region", lambda i, v: f"region-{(i + 1) % 7}"),
    ("x-plan", lambda i, v: f"free-{i}"),
    ("x-client", lambda i, v: f"client-{i + 1}"),
)


def requests(params: Dict[str, Any], n: int, rng: random.Random) -> List[Dict[str, Any]]:
    """n distinct rows, hosts uniform over the configs; `deny_share` of them
    break exactly one rule, drawn uniformly from the ten."""
    n_configs = int(params["n_configs"])
    deny_share = float(params["deny_share"])
    rows, seen = [], set()
    while len(rows) < n:
        i = rng.randrange(n_configs)
        vals = _allowed(i, rng)
        if rng.random() < deny_share:
            key, breaker = _VIOLATIONS[rng.randrange(len(_VIOLATIONS))]
            vals[key] = breaker(i, vals)
        ident = (i, vals["x-request-id"], vals["path"])
        if ident in seen:
            continue
        seen.add(ident)
        rows.append({"host": _host(i), "method": vals.pop("method"),
                     "path": vals.pop("path"), "headers": vals})
    return rows
