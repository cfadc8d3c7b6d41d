"""Corpus `route_rules`: N AuthConfigs, one host each, anonymous identity,
16 route-scoped authorization evaluators and one catch-all a config.

Shape: upstream's docs/user-guides/json-pattern-matching-authorization.md,
whose evaluator is scoped to a route by `when: [{selector: <request path>,
operator: matches, value: ^/admin(/.*)?$}]` and whose patterns decide only
the requests that route takes.  Per config i and route k (ROUTES below):

  route-<kk>: when     request.url_path matches ^/api/v[0-9]+/t<i>/<tail k>$
                       request.method   eq      <method k>
              patterns request.headers.x-role incl role-<(i+k)%17>
                       request.headers.x-org  eq   org-<i>
  tenant:     patterns request.url_path             matches ^/api/v[0-9]+/t<i>/
              (no when) request.headers.x-request-id matches ^r<i>-[0-9a-f]{8}$
                       request.headers.x-tier       excl    banned-<i>

The 16 tails are fixed here, disjoint in their first segment (at most one
route's `when` holds for a request), all inside compiler/redfa.py's subset
and under its MAX_STATES; the largest is the UUID route (`orders`), 66 DFA
states with a three-digit tenant.  Evaluator-level `when` only, as the
guide's is.
"""

from __future__ import annotations

import random
from typing import Any, Callable, Dict, List, Tuple

NAMESPACE = "bench"
ROLES = 17
_HEX = "0123456789abcdef"
_SLUG = "abcdefghijklmnopqrstuvwxyz0123456789"
# a path of at most SHORT_MAX bytes fits the program's DFA value bytes
# (compiler/compile.py DFA_VALUE_BYTES = 64); a long one is LONG_MIN..LONG_MAX
SHORT_MAX, LONG_MIN, LONG_MAX = 64, 65, 96


def _hex(rng: random.Random, n: int) -> str:
    return "".join(rng.choice(_HEX) for _ in range(n))


def _slug(rng: random.Random, n: int) -> str:
    """n bytes of [a-z0-9-], no dash at either end."""
    body = [rng.choice(_SLUG) for _ in range(n)]
    for j in range(4, n - 1, 7):
        body[j] = "-"
    return "".join(body)


def _digits(rng: random.Random, n: int) -> str:
    return str(rng.randrange(1, 10)) + "".join(
        rng.choice("0123456789") for _ in range(n - 1))


def _uuid(rng: random.Random) -> str:
    return "-".join(_hex(rng, n) for n in (8, 4, 4, 4, 12))


# (name, method, regex tail, short tail, long tail or None).  A tail maker
# is handed the rng and the bytes it may use (`room`): the short one returns
# at most `room` bytes, the long one exactly `room`.
Maker = Callable[[random.Random, int], str]


def _orders_long(rng: random.Random, room: int) -> str:
    head = f"orders/{_uuid(rng)}/items/"
    return head + _digits(rng, room - len(head))


def _teams(rng: random.Random, room: int) -> str:
    fixed = len("teams//projects//members")
    a = rng.randrange(3, room - fixed - 2)
    return f"teams/{_slug(rng, a)}/projects/{_slug(rng, room - fixed - a)}/members"


def _assets(rng: random.Random, room: int) -> str:
    ext = rng.choice(["png", "jpg", "svg", "css", "js"])
    free = room - len("assets/") - 1 - len(ext)
    a = rng.randrange(2, free - 3)
    return f"assets/{_slug(rng, a)}/{_slug(rng, free - a - 1)}.{ext}"


def _admin(rng: random.Random, room: int) -> str:
    free = room - len("admin/")
    a = rng.randrange(2, free - 3)
    return f"admin/{_slug(rng, a)}/{_slug(rng, free - a - 1)}"


def _catalog(rng: random.Random, room: int) -> str:
    n = rng.randrange(3, 9)
    return (f"catalog/{_slug(rng, room - len('catalog//items/') - n)}"
            f"/items/{_digits(rng, n)}")


def _files(rng: random.Random, room: int) -> str:
    ext = rng.choice(["txt", "pdf", "tar"])
    return f"files/{_slug(rng, room - len('files/') - 1 - len(ext))}.{ext}"


def _sized(make: Maker, least: int, most: int) -> Maker:
    """The short form of a tail that can also be long: `make` at a length
    drawn from [least, min(most, room)]."""
    return lambda rng, room: make(rng, rng.randrange(least, min(most, room) + 1))


ROUTES: Tuple[Tuple[str, str, str, Maker, Any], ...] = (
    ("orders", "GET",
     r"orders/[0-9a-f]{8}-[0-9a-f]{4}-[0-9a-f]{4}-[0-9a-f]{4}-[0-9a-f]{12}"
     r"(/items(/[0-9]+)?)?",
     lambda rng, room: f"orders/{_uuid(rng)}" + rng.choice(["", "/items"]),
     _orders_long),
    ("users", "GET", r"users/[0-9]+/(profile|settings|keys)",
     lambda rng, room: f"users/{rng.randrange(10**8)}/"
     + rng.choice(["profile", "settings", "keys"]), None),
    ("accounts", "PUT", r"accounts/[0-9]+/(limits|contacts|webhooks)",
     lambda rng, room: f"accounts/{rng.randrange(10**8)}/"
     + rng.choice(["limits", "contacts", "webhooks"]), None),
    ("reports", "GET", r"reports/[0-9]{4}-[0-9]{2}-[0-9]{2}\.(csv|json|pdf)",
     lambda rng, room: f"reports/20{rng.randrange(10, 27)}-"
     f"{rng.randrange(1, 13):02d}-{rng.randrange(1, 29):02d}."
     + rng.choice(["csv", "json", "pdf"]), None),
    ("teams", "GET", r"teams/[a-z0-9-]+/projects/[a-z0-9-]+/members",
     _sized(_teams, 34, 48), _teams),
    ("admin", "POST", r"admin(/.*)?",
     _sized(_admin, 16, 40), _admin),
    ("invoices", "GET", r"invoices/(usd|eur|gbp)-[0-9]{6,10}",
     lambda rng, room: f"invoices/{rng.choice(['usd', 'eur', 'gbp'])}-"
     + _digits(rng, rng.randrange(6, 11)), None),
    ("tokens", "DELETE", r"tokens/[0-9a-f]{12}",
     lambda rng, room: f"tokens/{_hex(rng, 12)}", None),
    ("sessions", "POST", r"sessions/[0-9a-f]{16}",
     lambda rng, room: f"sessions/{_hex(rng, 16)}", None),
    ("assets", "GET", r"assets/[a-z0-9_/-]+\.(png|jpg|svg|css|js)",
     _sized(_assets, 22, 48), _assets),
    ("health", "GET", r"health(/ready|/live)?",
     lambda rng, room: "health" + rng.choice(["", "/ready", "/live"]), None),
    ("catalog", "GET", r"catalog/[a-z0-9-]+/items/[0-9]+",
     _sized(_catalog, 28, 48), _catalog),
    ("webhooks", "POST", r"webhooks/[a-z]+/[0-9a-f]{12}",
     lambda rng, room: f"webhooks/{rng.choice(['github', 'stripe', 'slack'])}/"
     + _hex(rng, 12), None),
    ("events", "GET", r"events/[0-9]{4}/[0-9]{2}(/[0-9]{2})?",
     lambda rng, room: f"events/20{rng.randrange(10, 27)}/{rng.randrange(1, 13):02d}"
     + rng.choice(["", f"/{rng.randrange(1, 29):02d}"]), None),
    ("files", "PUT", r"files/[a-z0-9_-]+(\.[a-z0-9]+)?",
     _sized(_files, 16, 48), _files),
    ("metrics", "GET", r"metrics/[a-z_]+/(p50|p95|p99)",
     lambda rng, room: f"metrics/{rng.choice(['latency', 'queue_depth', 'errors'])}/"
     + rng.choice(["p50", "p95", "p99"]), None),
)
LONG_ROUTES = tuple(k for k, r in enumerate(ROUTES) if r[4] is not None)
METHODS = ("GET", "POST", "PUT", "DELETE")


def _host(i: int) -> str:
    return f"api-{i}.bench.test"


def route_regex(i: int, k: int) -> str:
    return f"^/api/v[0-9]+/t{i}/{ROUTES[k][2]}$"


def _evaluators(i: int) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for k, (_, method, _, _, _) in enumerate(ROUTES):
        out[f"route-{k:02d}"] = {
            "when": [
                {"selector": "request.url_path", "operator": "matches",
                 "value": route_regex(i, k)},
                {"selector": "request.method", "operator": "eq", "value": method},
            ],
            "patternMatching": {"patterns": [
                {"selector": "request.headers.x-role", "operator": "incl",
                 "value": f"role-{(i + k) % ROLES}"},
                {"selector": "request.headers.x-org", "operator": "eq",
                 "value": f"org-{i}"},
            ]},
        }
    out["tenant"] = {"patternMatching": {"patterns": [
        {"selector": "request.url_path", "operator": "matches",
         "value": f"^/api/v[0-9]+/t{i}/"},
        {"selector": "request.headers.x-request-id", "operator": "matches",
         "value": f"^r{i}-[0-9a-f]{{8}}$"},
        {"selector": "request.headers.x-tier", "operator": "excl",
         "value": f"banned-{i}"},
    ]}}
    return out


def manifests(params: Dict[str, Any]) -> List[Dict[str, Any]]:
    return [{
        "apiVersion": "authorino.kuadrant.io/v1beta2",
        "kind": "AuthConfig",
        "metadata": {"name": f"api-{i:05d}", "namespace": NAMESPACE},
        "spec": {
            "hosts": [_host(i)],
            "authentication": {"anon": {"anonymous": {}}},
            "authorization": _evaluators(i),
        },
    } for i in range(int(params["n_configs"]))]


# what a denied row breaks; the first two are read by the route's evaluator
# alone, so an unrouted row draws from the last three
BREAKS = ("role", "org", "request-id", "tier", "tenant-prefix")


def _row(i: int, rng: random.Random, params: Dict[str, Any]) -> Dict[str, Any]:
    """One row of config i, with `kind` saying what it is (tests read it;
    the wire does not carry it)."""
    k = rng.randrange(len(ROUTES))
    _, method, _, short, long_ = ROUTES[k]
    prefix = f"/api/v{rng.randrange(1, 10)}/t{i}/"
    # routes stay uniform over the 16 and long paths `long_path_share` of all
    # rows: only LONG_ROUTES can be long, each that much more often
    long_p = float(params["long_path_share"]) * len(ROUTES) / len(LONG_ROUTES)
    if long_ is not None and rng.random() < long_p:
        tail = long_(rng, rng.randrange(LONG_MIN, LONG_MAX + 1) - len(prefix))
    else:
        tail = short(rng, SHORT_MAX - len(prefix))
    headers = {
        "x-request-id": f"r{i}-{rng.getrandbits(32):08x}",
        "x-role": f"role-{(i + k) % ROLES}",
        "x-org": f"org-{i}",
        "x-tier": rng.choice(["gold", "silver", f"banned-{i + 1}"]),
    }
    kind = "routed"
    if rng.random() < float(params["unrouted_share"]):
        # no route's `when` holds: only the catch-all decides, whatever the
        # role and the organisation say
        if rng.random() < 0.5:
            kind = "unrouted-path"  # as long as the route's path was
            tail = "status/" + _slug(rng, max(len(tail) - len("status/"), 3))
        else:
            kind = "other-method"
            method = rng.choice([m for m in METHODS if m != method])
        headers["x-role"] = f"role-{rng.randrange(ROLES)}"
        headers["x-org"] = f"org-{rng.choice([i, i + 1])}"
    path = prefix + tail
    broke = None
    if rng.random() < float(params["deny_share"]):
        broke = rng.choice(BREAKS if kind == "routed" else BREAKS[2:])
        if broke == "role":
            headers["x-role"] = f"role-{(i + k + 1 + rng.randrange(ROLES - 1)) % ROLES}"
        elif broke == "org":
            headers["x-org"] = f"org-{i + 1}"
        elif broke == "request-id":
            headers["x-request-id"] = headers["x-request-id"][:-1] + "Z"
        elif broke == "tier":
            headers["x-tier"] = f"banned-{i}"
        else:
            path = path.replace(f"/t{i}/", f"/t{i + 1}/", 1)
    return {"host": _host(i), "method": method, "path": path,
            "headers": headers, "kind": kind, "broke": broke, "route": k}


def requests(params: Dict[str, Any], n: int, rng: random.Random,
             kinds: bool = False) -> List[Dict[str, Any]]:
    """n distinct rows, hosts uniform over the configs, routes uniform over
    the 16.  `deny_share` of them break exactly one thing, drawn uniformly
    from BREAKS; `unrouted_share` take no route; `long_path_share` carry a
    path of 65-96 bytes (the rest at most 64).  `kinds=True` keeps each
    row's `kind`, `broke` and `route` (the tests')."""
    n_configs = int(params["n_configs"])
    rows, seen = [], set()
    while len(rows) < n:
        row = _row(rng.randrange(n_configs), rng, params)
        ident = (row["host"], row["headers"]["x-request-id"], row["path"])
        if ident in seen:
            continue
        seen.add(ident)
        if not kinds:
            row = {key: row[key] for key in ("host", "method", "path", "headers")}
        rows.append(row)
    return rows
