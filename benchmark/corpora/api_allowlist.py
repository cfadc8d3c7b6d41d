"""Corpus `api_allowlist`: N AuthConfigs, one host each, anonymous identity,
ten patternMatching rules a config (BASELINE.json class 4), nine of them
`tenant_rules`' own; the tenth, in the place of `tenant_rules`' path rule,
is the tenant's API routes written as one RE2 alternation, the way an
allowlist generated from an OpenAPI description is written:

    request.url_path matches
      ^/api/v[0-9]+/t<i>/(<c_1>|...|<c_K>)(/[0-9a-f]{24}(/(<s_1>|...|<s_4>))?)?$

K collections, uniform over K_RANGE, drawn from COLLECTIONS; four
sub-resources drawn from SUB_RESOURCES; a member id is a 24-hex object id
(MongoDB's ObjectId).  Such a regex determinizes to 119-392 states, past
the 96 of the DFA compiler's old cap and, from K about 24 on, past 256.

Allowed paths are a third each a collection, a member and a member's
sub-resource.  A denied row breaks exactly one of the ten rules, uniform;
where that is the path, with a near miss that makes the DFA read deep
(PATH_BREAKS, uniform).  `tenant_rules.py` is loaded by path and edited
nowhere.
"""

from __future__ import annotations

import importlib.util
import os
import random
import re
import statistics
from typing import Any, Callable, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_small = _load(os.path.join(HERE, "tenant_rules.py"), "bench_corpora_tenant_rules")
NAMESPACE = _small.NAMESPACE
_host = _small._host

# REST collection nouns, as the paths of public OpenAPI descriptions name
# them; no noun is another with a letter dropped
COLLECTIONS = (
    "accounts", "addresses", "alerts", "articles", "assets", "audits",
    "batches", "billing", "bookings", "branches", "builds", "campaigns",
    "carts", "catalogs", "categories", "channels", "charges", "clusters",
    "comments", "contacts", "contracts", "coupons", "customers", "datasets",
    "deployments", "devices", "documents", "domains", "employees", "events",
    "exports", "folders", "groups", "imports", "incidents", "invoices",
    "licenses", "locations", "messages", "metrics", "orders", "payments",
    "pipelines", "products", "projects", "quotes", "refunds", "releases",
    "reports", "shipments", "subscriptions", "webhooks",
)
SUB_RESOURCES = ("history", "status", "settings", "versions", "metadata",
                 "audit", "owners", "labels")
K_RANGE = (8, 48)
N_SUB = 4
OBJECT_ID = 24
PATH_BREAKS = ("foreign_collection", "dropped_letter", "short_or_long_id",
               "uppercase_hex", "foreign_sub_resource")


def _api(i: int):
    """Tenant i's API: its collections and sub-resources, drawn from the
    tenant's own index, so manifests and rows agree at any seed."""
    rng = random.Random(0x0A11 * 1_000_003 + i)
    k = rng.randint(*K_RANGE)
    return (tuple(rng.sample(COLLECTIONS, k)),
            tuple(rng.sample(SUB_RESOURCES, N_SUB)))


def path_regex(i: int) -> str:
    cols, subs = _api(i)
    return (f"^/api/v[0-9]+/t{i}/({'|'.join(cols)})"
            f"(/[0-9a-f]{{{OBJECT_ID}}}(/({'|'.join(subs)}))?)?$")


def _patterns(i: int) -> List[Dict[str, str]]:
    out = _small._patterns(i)
    (at,) = [k for k, p in enumerate(out) if p["selector"] == "request.url_path"]
    out[at] = {"selector": "request.url_path", "operator": "matches",
               "value": path_regex(i)}
    return out


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


def _hex(rng: random.Random, n: int) -> str:
    return "".join(rng.choice("0123456789abcdef") for _ in range(n))


def _allowed_path(i: int, rng: random.Random) -> str:
    cols, subs = _api(i)
    path = f"/api/v{rng.randrange(1, 10)}/t{i}/{rng.choice(cols)}"
    form = rng.randrange(3)
    if form >= 1:
        path += "/" + _hex(rng, OBJECT_ID)
    if form == 2:
        path += "/" + rng.choice(subs)
    return path


def _broken_path(i: int, rng: random.Random, kind: str) -> str:
    """A near miss of tenant i's allowlist: one of PATH_BREAKS."""
    cols, subs = _api(i)
    head = f"/api/v{rng.randrange(1, 10)}/t{i}/"
    oid = _hex(rng, OBJECT_ID)
    if kind == "foreign_collection":
        return head + rng.choice([c for c in COLLECTIONS if c not in cols])
    if kind == "dropped_letter":
        col = rng.choice(cols)
        at = rng.randrange(len(col))
        return head + col[:at] + col[at + 1:]
    if kind == "short_or_long_id":
        bad = _hex(rng, OBJECT_ID + rng.choice((-1, 1)))
        tail = "/" + rng.choice(subs) if rng.random() < 0.5 else ""
        return f"{head}{rng.choice(cols)}/{bad}{tail}"
    if kind == "uppercase_hex":
        at = rng.randrange(OBJECT_ID)
        oid = oid[:at] + rng.choice("ABCDEF") + oid[at + 1:]
        tail = "/" + rng.choice(subs) if rng.random() < 0.5 else ""
        return f"{head}{rng.choice(cols)}/{oid}{tail}"
    assert kind == "foreign_sub_resource", kind
    other = rng.choice([s for s in SUB_RESOURCES if s not in subs])
    return f"{head}{rng.choice(cols)}/{oid}/{other}"


_COMPILED: Dict[int, "re.Pattern"] = {}


def _admits(i: int, path: str) -> bool:
    rx = _COMPILED.get(i)
    if rx is None:
        rx = _COMPILED[i] = re.compile(path_regex(i))
    return rx.search(path) is not None


def requests(params: Dict[str, Any], n: int, rng: random.Random,
             kinds: bool = False) -> List[Dict[str, Any]]:
    """n distinct rows, hosts uniform over the configs; `deny_share` of them
    break exactly one rule, uniform over the ten (tenant_rules'), the path by
    a near miss of PATH_BREAKS, uniform.  `kinds` adds `broke` (the rule's
    key, or the path's break; None where the row is allowed)."""
    n_configs = int(params["n_configs"])
    deny_share = float(params["deny_share"])
    rows, seen = [], set()
    while len(rows) < n:
        i = rng.randrange(n_configs)
        vals = _small._allowed(i, rng)
        vals["path"] = _allowed_path(i, rng)
        broke = None
        if rng.random() < deny_share:
            key, breaker = _small._VIOLATIONS[rng.randrange(len(_small._VIOLATIONS))]
            if key == "path":
                broke = rng.choice(PATH_BREAKS)
                vals["path"] = _broken_path(i, rng, broke)
                if _admits(i, vals["path"]):
                    continue   # a dropped letter that spells an allowed noun
            else:
                broke = key
                vals[key] = breaker(i, vals)
        ident = (i, vals["x-request-id"], vals["path"])
        if ident in seen:
            continue
        seen.add(ident)
        row = {"host": _host(i), "method": vals.pop("method"),
               "path": vals.pop("path"), "headers": vals}
        if kinds:
            row["broke"] = broke
        rows.append(row)
    return rows


def measure(params: Dict[str, Any], n: int, seed: int,
            n_states: Optional[Callable[[str], int]] = None) -> Dict[str, Any]:
    """What configs/api-allowlist-1k.json records under
    `measured_of_the_generator`: path bytes of n rows at `seed`, and, given
    `n_states` (a regex's DFA state count, from the program's compiler), the
    state counts of the configs' path regexes and their shares past 96 and
    256."""
    rows = requests(params, n, random.Random(seed))
    lengths = sorted(len(r["path"].encode()) for r in rows)
    out: Dict[str, Any] = {
        "rows": n, "seed": seed,
        "path_bytes": {"mean": round(statistics.fmean(lengths), 1),
                       "p50": lengths[n // 2], "min": lengths[0],
                       "max": lengths[-1]},
        "rows_with_a_path_past_64_pct": round(
            100.0 * sum(v > 64 for v in lengths) / n, 2),
    }
    if n_states is not None:
        states = sorted(n_states(path_regex(i))
                        for i in range(int(params["n_configs"])))
        g = len(states)
        out["path_regex_dfa_states"] = {
            "min": states[0], "p50": states[g // 2], "max": states[-1],
            "past_96_pct": round(100.0 * sum(s > 96 for s in states) / g, 2),
            "past_256_pct": round(100.0 * sum(s > 256 for s in states) / g, 2)}
    return out
