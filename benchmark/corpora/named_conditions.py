"""Corpus `named_conditions`: N AuthConfigs with named patterns, a `when`
condition and a nested all/any rule (BASELINE.json class 2).

Per config i: named patterns `is-write` (request.method eq POST) and
`item-path` (request.url_path matches ^/t<i>/items/[0-9]+$); one evaluator
with `when: is-write` and all(item-path, x-tier eq t-<i>, any(x-role eq
admin, x-group incl g-<i>)).  Sizes and header names as bench.py
run_mix_mode class 2 built them; the path pattern is the one regex, and the
item id in the path keeps every encoded row distinct.
"""

from __future__ import annotations

import random
from typing import Any, Dict, List

NAMESPACE = "bench"


def _host(i: int) -> str:
    return f"cond-{i}.bench.test"


def manifests(params: Dict[str, Any]) -> List[Dict[str, Any]]:
    return [{
        "apiVersion": "authorino.kuadrant.io/v1beta2",
        "kind": "AuthConfig",
        "metadata": {"name": f"cond-{i:05d}", "namespace": NAMESPACE},
        "spec": {
            "hosts": [_host(i)],
            "patterns": {
                "is-write": [{"selector": "request.method", "operator": "eq",
                              "value": "POST"}],
                "item-path": [{"selector": "request.url_path",
                               "operator": "matches",
                               "value": f"^/t{i}/items/[0-9]+$"}],
            },
            "authentication": {"anon": {"anonymous": {}}},
            "authorization": {"rules": {
                "when": [{"patternRef": "is-write"}],
                "patternMatching": {"patterns": [{"all": [
                    {"patternRef": "item-path"},
                    {"selector": "request.headers.x-tier", "operator": "eq",
                     "value": f"t-{i}"},
                    {"any": [
                        {"selector": "request.headers.x-role",
                         "operator": "eq", "value": "admin"},
                        {"selector": "request.headers.x-group",
                         "operator": "incl", "value": f"g-{i}"},
                    ]},
                ]}]},
            }},
        },
    } for i in range(int(params["n_configs"]))]


def requests(params: Dict[str, Any], n: int, rng: random.Random) -> List[Dict[str, Any]]:
    """n distinct rows, hosts uniform over the configs: `post_share` POST
    (the rest GET, which the `when` lets through), `admin_share` with x-role
    admin, the others x-role user with the config's group or its
    neighbour's, `bad_path_share` with a path the pattern refuses."""
    n_configs = int(params["n_configs"])
    rows, seen = [], set()
    while len(rows) < n:
        i = rng.randrange(n_configs)
        item = rng.randrange(10**8, 10**9)
        if (i, item) in seen:
            continue
        seen.add((i, item))
        bad_path = rng.random() < float(params["bad_path_share"])
        headers = {"x-tier": f"t-{i}"}
        if rng.random() < float(params["admin_share"]):
            headers["x-role"] = "admin"
        else:
            headers["x-role"] = "user"
            headers["x-group"] = f"g-{i if rng.random() < 0.5 else i + 1}"
        rows.append({
            "host": _host(i),
            "method": "POST" if rng.random() < float(params["post_share"]) else "GET",
            "path": (f"/t{i}/item/{item}" if bad_path else f"/t{i}/items/{item}"),
            "headers": headers,
        })
    return rows
