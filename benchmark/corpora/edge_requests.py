"""Corpus `edge_requests`: N AuthConfigs, one host each, anonymous identity,
ten patternMatching rules a config (BASELINE.json class 4), five of them
regexes, and requests of the shape Envoy's gRPC ext_authz filter sends from
an edge gateway: every request header, lowercased, pseudo-headers included
(envoy.service.auth.v3.AttributeContext.HttpRequest.headers).

Tenant i (host `svc-<i>.bench.test`, name `cfg-<iiiii>`), one evaluator:

   1 request.method                   neq     TRACE
   2 request.url_path                 matches ^/api/v[0-9]+/t<i>/[a-z0-9/_-]*$
   3 request.headers.x-request-id     matches Envoy's UUID4 form
   4 request.headers.user-agent       matches ^(Mozilla/5\\.0 \\(|t<i>-sdk/[0-9]+\\.[0-9]+)[ -~]*$
   5 any: request.headers.referer     matches ^https://app-t<i>\\.example\\.com/[A-Za-z0-9/_.?=&%-]*$
          request.headers.x-client-kind eq    sdk
   6 any: request.headers.cookie      matches tenant=t<i>-[0-9a-f]{8}   (unanchored)
          request.headers.x-client-kind eq    sdk
   7 request.headers.x-forwarded-proto eq     https
   8 request.headers.x-org            eq      org-<i>
   9 request.headers.x-tier           excl    banned-<i>
  10 request.headers.x-region         eq      region-<i % 7>

A browser row carries 26-28 headers (the `host` header benchmark/wire.py
adds included), an SDK row 18-20; `user-agent`, `referer` and `cookie` are
105-135, 45-200 and 40-380 bytes (3 % of browser rows: 400-900 more), the
path 20-64.  Nothing is downloaded: user agents, header names and formats
are written out below.  `tenant_rules.py` is loaded by path for its
namespace and host naming and edited nowhere.
"""

from __future__ import annotations

import importlib.util
import os
import random
import statistics
from typing import Any, Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_small = _load(os.path.join(HERE, "tenant_rules.py"), "bench_corpora_tenant_rules")
NAMESPACE = _small.NAMESPACE
_host = _small._host

UUID4 = (r"^[0-9a-f]{8}-[0-9a-f]{4}-4[0-9a-f]{3}-[89ab][0-9a-f]{3}"
         r"-[0-9a-f]{12}$")
# the regex-read attributes, in rule order: what `measure` takes lengths of
REGEX_READ = ("path", "x-request-id", "user-agent", "referer", "cookie")


def path_regex(i: int) -> str:
    return f"^/api/v[0-9]+/t{i}/[a-z0-9/_-]*$"


def agent_regex(i: int) -> str:
    return rf"^(Mozilla/5\.0 \(|t{i}-sdk/[0-9]+\.[0-9]+)[ -~]*$"


def referer_regex(i: int) -> str:
    return rf"^https://app-t{i}\.example\.com/[A-Za-z0-9/_.?=&%-]*$"


def cookie_regex(i: int) -> str:
    return f"tenant=t{i}-[0-9a-f]{{8}}"


def regexes(i: int) -> List[str]:
    return [path_regex(i), UUID4, agent_regex(i), referer_regex(i),
            cookie_regex(i)]


def _patterns(i: int) -> List[Dict[str, Any]]:
    sdk = {"selector": "request.headers.x-client-kind", "operator": "eq",
           "value": "sdk"}
    return [
        {"selector": "request.method", "operator": "neq", "value": "TRACE"},
        {"selector": "request.url_path", "operator": "matches",
         "value": path_regex(i)},
        {"selector": "request.headers.x-request-id", "operator": "matches",
         "value": UUID4},
        {"selector": "request.headers.user-agent", "operator": "matches",
         "value": agent_regex(i)},
        {"any": [{"selector": "request.headers.referer", "operator": "matches",
                  "value": referer_regex(i)}, dict(sdk)]},
        {"any": [{"selector": "request.headers.cookie", "operator": "matches",
                  "value": cookie_regex(i)}, dict(sdk)]},
        {"selector": "request.headers.x-forwarded-proto", "operator": "eq",
         "value": "https"},
        {"selector": "request.headers.x-org", "operator": "eq",
         "value": f"org-{i}"},
        {"selector": "request.headers.x-tier", "operator": "excl",
         "value": f"banned-{i}"},
        {"selector": "request.headers.x-region", "operator": "eq",
         "value": f"region-{i % 7}"},
    ]


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


# ---------------------------------------------------------------------------
# what a client sends, written out
# ---------------------------------------------------------------------------

_CHROME = ('"Chromium";v="{v}", "Not;A=Brand";v="24", "Google Chrome";v="{v}"')
_EDGE = '"Chromium";v="{v}", "Not;A=Brand";v="24", "Microsoft Edge";v="{v}"'
# (user-agent, sec-ch-ua or None, sec-ch-ua-mobile, sec-ch-ua-platform):
# a dozen current desktop and mobile forms, 105-135 bytes each
BROWSERS = (
    ("Mozilla/5.0 (Windows NT 10.0; Win64; x64) AppleWebKit/537.36 (KHTML, like Gecko) "
     "Chrome/{v}.0.0.0 Safari/537.36", _CHROME, "?0", '"Windows"'),
    ("Mozilla/5.0 (Macintosh; Intel Mac OS X 10_15_7) AppleWebKit/537.36 (KHTML, like Gecko) "
     "Chrome/{v}.0.0.0 Safari/537.36", _CHROME, "?0", '"macOS"'),
    ("Mozilla/5.0 (X11; Linux x86_64) AppleWebKit/537.36 (KHTML, like Gecko) "
     "Chrome/{v}.0.0.0 Safari/537.36", _CHROME, "?0", '"Linux"'),
    ("Mozilla/5.0 (Windows NT 10.0; Win64; x64) AppleWebKit/537.36 (KHTML, like Gecko) "
     "Chrome/{v}.0.0.0 Safari/537.36 Edg/{v}.0.0.0", _EDGE, "?0", '"Windows"'),
    ("Mozilla/5.0 (Linux; Android 14; Pixel 8) AppleWebKit/537.36 (KHTML, like Gecko) "
     "Chrome/{v}.0.0.0 Mobile Safari/537.36", _CHROME, "?1", '"Android"'),
    ("Mozilla/5.0 (Linux; Android 13; SM-S918B) AppleWebKit/537.36 (KHTML, like Gecko) "
     "Chrome/{v}.0.0.0 Mobile Safari/537.36", _CHROME, "?1", '"Android"'),
    ("Mozilla/5.0 (Macintosh; Intel Mac OS X 10_15_7) AppleWebKit/605.1.15 (KHTML, like Gecko) "
     "Version/17.5 Safari/605.1.15", None, "?0", '"macOS"'),
    ("Mozilla/5.0 (iPhone; CPU iPhone OS 17_5 like Mac OS X) AppleWebKit/605.1.15 "
     "(KHTML, like Gecko) Version/17.5 Mobile/15E148 Safari/604.1", None, "?1", '"iOS"'),
    ("Mozilla/5.0 (iPad; CPU OS 17_5 like Mac OS X) AppleWebKit/605.1.15 "
     "(KHTML, like Gecko) Version/17.5 Mobile/15E148 Safari/604.1", None, "?1", '"iOS"'),
    ("Mozilla/5.0 (Windows NT 10.0; Win64; x64; rv:{v}.0) Gecko/20100101 Firefox/{v}.0 "
     "(compatible; build 20240101000000)", None, "?0", '"Windows"'),
    ("Mozilla/5.0 (X11; Ubuntu; Linux x86_64; rv:{v}.0) Gecko/20100101 Firefox/{v}.0 "
     "(compatible; build 20240101000000)", None, "?0", '"Linux"'),
    ("Mozilla/5.0 (Macintosh; Intel Mac OS X 14.5; rv:{v}.0) Gecko/20100101 Firefox/{v}.0 "
     "(compatible; build 20240101000000)", None, "?0", '"macOS"'),
)
ACCEPT_NAV = ("text/html,application/xhtml+xml,application/xml;q=0.9,image/avif,"
              "image/webp,image/apng,*/*;q=0.8,application/signed-exchange;v=b3;q=0.7")
LANGUAGES = ("en-US,en;q=0.9", "en-GB,en;q=0.9,de;q=0.8", "de-DE,de;q=0.9,en;q=0.7",
             "fr-FR,fr;q=0.9,en-US;q=0.8,en;q=0.7", "pt-BR,pt;q=0.9,en;q=0.8")
COOKIE_NAMES = ("_ga", "_gid", "sid", "csrftoken", "theme", "locale", "ab",
                "consent", "last_seen", "cart", "pref", "utm")
SDK_LANGS = ("python 3.12", "go1.22", "node 20.11", "java 21", "ruby 3.3", "rust 1.79")
SDK_OSES = ("linux x86_64", "linux aarch64", "darwin arm64", "windows amd64")
_HEX = "0123456789abcdef"
_ALNUM = "abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789"
_PATHC = "abcdefghijklmnopqrstuvwxyz0123456789"
_B64 = _ALNUM + "-_"
METHODS_READ, METHODS_WRITE = ("GET",), ("POST", "PUT")


def _chars(rng: random.Random, alphabet: str, n: int) -> str:
    return "".join(rng.choices(alphabet, k=n))


def _uuid4(rng: random.Random) -> str:
    return (f"{_chars(rng, _HEX, 8)}-{_chars(rng, _HEX, 4)}-4{_chars(rng, _HEX, 3)}"
            f"-{rng.choice('89ab')}{_chars(rng, _HEX, 3)}-{_chars(rng, _HEX, 12)}")


def _ip(rng: random.Random) -> str:
    return ".".join(str(rng.randrange(1, 255)) for _ in range(4))


def _segments(rng: random.Random, n: int) -> str:
    """Exactly n bytes of path segments over [a-z0-9], a slash every 4-13
    bytes and none at either end."""
    body = [rng.choice(_PATHC) for _ in range(n)]
    at = rng.randrange(4, 13)
    while at < n - 1:
        body[at] = "/"
        at += rng.randrange(5, 14)
    return "".join(body)


def _path(i: int, rng: random.Random) -> str:
    head = f"/api/v{rng.randrange(1, 10)}/t{i}/"
    return head + _segments(rng, rng.randrange(20, 65) - len(head))


def _referer(i: int, rng: random.Random) -> str:
    head = f"https://app-t{i}.example.com/"
    total = rng.randrange(45, 201)
    body = _segments(rng, max(total - len(head), 0))
    if len(body) > 30 and rng.random() < 0.5:
        # a query string of the characters the rule's class holds
        cut = rng.randrange(8, len(body) - 16)
        body = body[:cut] + "?q=" + body[cut + 3:].replace("/", "&", 1)
    return (head + body)[:total]


def _cookie_pair(rng: random.Random) -> str:
    name = rng.choice(COOKIE_NAMES)
    return f"{name}={_chars(rng, _ALNUM, rng.randrange(18, 41) - len(name) - 1)}"


def _cookie(i: int, rng: random.Random, params: Dict[str, Any]) -> str:
    """`cookie_pairs` pairs of 18-40 bytes joined by `; `, the tenant's pair
    at a uniform position; `cookie_tail_share` of them carry one more value
    of 400-900 bytes (a serialized session, a consent string)."""
    lo, hi = params["cookie_pairs"]
    pairs = [_cookie_pair(rng) for _ in range(rng.randrange(int(lo), int(hi) + 1) - 1)]
    pairs.insert(rng.randrange(len(pairs) + 1),
                 f"tenant=t{i}-{_chars(rng, _HEX, 8)}")
    if rng.random() < float(params["cookie_tail_share"]):
        pairs.insert(rng.randrange(len(pairs) + 1),
                     "session=" + _chars(rng, _B64, rng.randrange(400, 901) - 8))
    return "; ".join(pairs)


def _envoy(rng: random.Random) -> Dict[str, str]:
    """What the edge adds to every request it forwards."""
    hops = [_ip(rng) for _ in range(rng.randrange(1, 4))]
    return {"x-forwarded-for": ", ".join(hops), "x-forwarded-proto": "https",
            "x-request-id": _uuid4(rng), "x-envoy-external-address": hops[0]}


def _claims(i: int, rng: random.Random) -> Dict[str, str]:
    return {"x-org": f"org-{i}", "x-region": f"region-{i % 7}",
            "x-tier": rng.choice(["gold", "silver", f"banned-{i + 1}"]),
            "x-user": f"u-{_chars(rng, _HEX, 12)}"}


def _browser(i: int, rng: random.Random, params: Dict[str, Any]) -> Dict[str, Any]:
    agent, ch_ua, mobile, platform = rng.choice(BROWSERS)
    version = rng.randrange(120, 131)
    write = rng.random() < float(params["write_share"])
    method = rng.choice(METHODS_WRITE if write else METHODS_READ)
    path = _path(i, rng)
    fetch = rng.random() < 0.5  # an XHR / fetch() call, else a navigation
    headers = {
        ":authority": _host(i), ":method": method, ":path": path,
        ":scheme": "https",
        "user-agent": agent.format(v=version),
        "accept": "application/json" if fetch else ACCEPT_NAV,
        "accept-encoding": "gzip, deflate, br, zstd",
        "accept-language": rng.choice(LANGUAGES),
        "cookie": _cookie(i, rng, params),
        "referer": _referer(i, rng),
        "origin": f"https://app-t{i}.example.com",
        "sec-ch-ua": (ch_ua or '"Not;A=Brand";v="24"').format(v=version),
        "sec-ch-ua-mobile": mobile,
        "sec-ch-ua-platform": platform,
        "sec-fetch-dest": "empty" if fetch else "document",
        "sec-fetch-mode": "cors" if fetch else "navigate",
        "sec-fetch-site": "same-site",
    }
    if write:
        headers["content-type"] = "application/json"
        headers["content-length"] = str(rng.randrange(20, 4000))
    headers.update(_envoy(rng))
    headers.update(_claims(i, rng))
    return {"host": _host(i), "method": method, "path": path,
            "headers": headers, "kind": "browser", "broke": None}


def _sdk(i: int, rng: random.Random, params: Dict[str, Any]) -> Dict[str, Any]:
    write = rng.random() < float(params["write_share"])
    method = rng.choice(METHODS_WRITE if write else METHODS_READ)
    path = _path(i, rng)
    sizes = [rng.randrange(30, 60), 0, 43]
    sizes[1] = rng.randrange(500, 901) - len("Bearer ") - 2 - sizes[0] - sizes[2]
    headers = {
        ":authority": _host(i), ":method": method, ":path": path,
        ":scheme": "https",
        "user-agent": (f"t{i}-sdk/{rng.randrange(1, 9)}.{rng.randrange(0, 40)} "
                       f"({rng.choice(SDK_LANGS)}; {rng.choice(SDK_OSES)})"),
        "accept": "application/json",
        "accept-encoding": "gzip",
        # read by no rule: the bytes a real client sends, parsed and skipped
        "authorization": "Bearer " + ".".join(_chars(rng, _B64, n) for n in sizes),
        "x-client-kind": "sdk",
    }
    if write:
        headers["content-type"] = "application/json"
        headers["content-length"] = str(rng.randrange(20, 4000))
    headers.update(_envoy(rng))
    headers.update(_claims(i, rng))
    return {"host": _host(i), "method": method, "path": path,
            "headers": headers, "kind": "sdk", "broke": None}


# what a denied row of each kind can break, exactly one of them
BREAKS = {
    "browser": ("method", "path", "request-id", "user-agent", "referer",
                "cookie", "proto", "org", "tier", "region"),
    # the referer and cookie rules hold for an SDK by its x-client-kind
    "sdk": ("method", "path", "request-id", "user-agent", "proto", "org",
            "tier", "region"),
}


def _break(row: Dict[str, Any], i: int, rng: random.Random) -> None:
    h = row["headers"]
    what = row["broke"] = rng.choice(BREAKS[row["kind"]])
    if what == "method":
        row["method"] = h[":method"] = "TRACE"
    elif what == "path":
        row["path"] = h[":path"] = row["path"].replace(f"/t{i}/", f"/t{i + 1}/", 1)
    elif what == "request-id":
        rid = h["x-request-id"]
        # the version nibble, or a byte outside the hex class at the end
        h["x-request-id"] = (rid[:14] + "1" + rid[15:] if rng.random() < 0.5
                             else rid[:-1] + "Z")
    elif what == "user-agent":
        if row["kind"] == "sdk":  # another tenant's SDK
            h["user-agent"] = h["user-agent"].replace(f"t{i}-sdk/", f"t{i + 1}-sdk/", 1)
        else:  # a byte outside printable ASCII, anywhere past the head
            at = rng.randrange(16, len(h["user-agent"]))
            h["user-agent"] = h["user-agent"][:at] + "\u00e9" + h["user-agent"][at + 1:]
    elif what == "referer":
        h["referer"] = h["referer"].replace(f"app-t{i}.", f"app-t{i + 1}.", 1)
    elif what == "cookie":
        mine = f"tenant=t{i}-"
        if rng.random() < 0.5:  # another tenant's pair in its place
            h["cookie"] = h["cookie"].replace(mine, f"tenant=t{i + 1}-", 1)
        else:  # no tenant pair: a pair of the same length in its place
            h["cookie"] = h["cookie"].replace(mine, f"tenent=t{i}-", 1)
    elif what == "proto":
        h["x-forwarded-proto"] = "http"
    elif what == "org":
        h["x-org"] = f"org-{i + 1}"
    elif what == "tier":
        h["x-tier"] = f"banned-{i}"
    else:
        h["x-region"] = f"region-{(i + 1) % 7}"


def requests(params: Dict[str, Any], n: int, rng: random.Random,
             kinds: bool = False) -> List[Dict[str, Any]]:
    """n distinct rows, hosts uniform over the configs: `browser_share` of
    them a browser's, the rest an SDK client's; `deny_share` of either break
    exactly one thing, uniform over what the kind can break (BREAKS).  Every
    row is distinct by its request id.  `kinds=True` keeps each row's `kind`
    and `broke` (the tests')."""
    n_configs = int(params["n_configs"])
    rows, seen = [], set()
    while len(rows) < n:
        i = rng.randrange(n_configs)
        make = _browser if rng.random() < float(params["browser_share"]) else _sdk
        row = make(i, rng, params)
        if rng.random() < float(params["deny_share"]):
            _break(row, i, rng)
        ident = row["headers"]["x-request-id"]
        if ident in seen:
            continue
        seen.add(ident)
        if not kinds:
            row = {key: row[key] for key in ("host", "method", "path", "headers")}
        rows.append(row)
    return rows


def measure(params: Dict[str, Any], n: int, seed: int) -> Dict[str, Any]:
    """What configs/edge-1k.json records under `measured_of_the_generator`:
    bytes a CheckRequest as benchmark/wire.py encodes it, headers a request
    (the `host` header wire.py adds included), and the share of rows with a
    regex-read value (REGEX_READ) past 64, 128 and 256 bytes."""
    wire = _load(os.path.join(os.path.dirname(HERE), "wire.py"), "bench_wire")
    rows = requests(params, n, random.Random(seed))
    sizes = sorted(len(wire.check_request(r)) for r in rows)
    headers = [len(r["headers"]) + 1 for r in rows]

    def longest(r):
        return max(len((r["path"] if k == "path" else r["headers"].get(k, ""))
                       .encode()) for k in REGEX_READ)

    longest_of = [longest(r) for r in rows]
    return {
        "rows": n, "seed": seed,
        "check_request_bytes": {"mean": round(statistics.fmean(sizes), 1),
                                "p50": sizes[n // 2], "p99": sizes[n * 99 // 100],
                                "min": sizes[0], "max": sizes[-1]},
        "headers_per_request": {"mean": round(statistics.fmean(headers), 2),
                                "min": min(headers), "max": max(headers)},
        "rows_with_a_regex_read_value_past_pct": {
            str(w): round(100.0 * sum(v > w for v in longest_of) / n, 2)
            for w in (64, 128, 256)},
    }
