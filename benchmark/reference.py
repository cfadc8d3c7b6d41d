"""The plain reference: what an Authorino deployment answers to a request,
from the AuthConfig manifests as written and nothing else.

Straightforward Python over the v1beta2 manifest: the request's host picks
the AuthConfig; every authorization evaluator whose `when` holds must pass
its `patternMatching` patterns; allow is gRPC OK (0), deny is
PERMISSION_DENIED (7), an unknown host is NOT_FOUND (5).  Pattern semantics
follow upstream's pkg/jsonexp over a gjson document: a missing value renders
"" for eq / neq / matches, is no element for incl and excludes everything
for excl; `matches` is an unanchored search.

It imports nothing of the program and takes nothing the program has made.
Only the selectors a request of the benchmark can carry are known; any other
is an error, not a guess.
"""

from __future__ import annotations

import re
from typing import Any, Callable, Dict, List, Optional, Sequence

OK, PERMISSION_DENIED, NOT_FOUND = 0, 7, 5

Request = Dict[str, Any]  # host, method, path, headers


def attribute(req: Request, selector: str) -> Optional[str]:
    if selector == "request.method":
        return req["method"]
    if selector == "request.host":
        return req["host"]
    if selector == "request.path":
        return req["path"]
    if selector == "request.url_path":
        return req["path"].split("?", 1)[0]
    if selector.startswith("request.headers."):
        name = selector[len("request.headers."):]
        if name == "host":
            return req["host"]
        return req["headers"].get(name)
    raise ValueError(f"the reference does not know the selector {selector!r}")


def _leaf(item: Dict[str, Any]) -> Callable[[Request], bool]:
    selector, op, want = item["selector"], item["operator"], str(item["value"])
    attribute({"method": "", "host": "", "path": "", "headers": {}}, selector)
    if op == "eq":
        return lambda r: (attribute(r, selector) or "") == want
    if op == "neq":
        return lambda r: (attribute(r, selector) or "") != want
    if op == "incl":
        return lambda r: attribute(r, selector) == want
    if op == "excl":
        return lambda r: attribute(r, selector) != want
    if op == "matches":
        rx = re.compile(want)
        return lambda r: rx.search(attribute(r, selector) or "") is not None
    raise ValueError(f"the reference does not know the operator {op!r}")


def _expr(item: Dict[str, Any], named: Dict[str, List[dict]]) -> Callable[[Request], bool]:
    if item.get("patternRef"):
        return _all(named[item["patternRef"]], named)
    if item.get("all") is not None:
        return _all(item["all"], named)
    if item.get("any") is not None:
        parts = [_expr(p, named) for p in item["any"]]
        return lambda r: any(p(r) for p in parts)
    return _leaf(item)


def _all(items: Optional[Sequence[dict]], named) -> Callable[[Request], bool]:
    parts = [_expr(p, named) for p in items or ()]
    return lambda r: all(p(r) for p in parts)


class Reference:
    """decide(request) -> the gRPC status code a correct server answers."""

    def __init__(self, manifests: Sequence[Dict[str, Any]]):
        self._by_host: Dict[str, Callable[[Request], bool]] = {}
        for m in manifests:
            spec = m["spec"]
            named = spec.get("patterns") or {}
            unknown = set(spec) - {"hosts", "patterns", "when",
                                   "authentication", "authorization"}
            if unknown:
                raise ValueError(f"the reference does not know spec keys {unknown}")
            for ident in (spec.get("authentication") or {}).values():
                if "anonymous" not in ident:
                    raise ValueError("the reference knows anonymous identity only")
            top = _all(spec.get("when"), named)
            evaluators = []
            for ev in (spec.get("authorization") or {}).values():
                if set(ev) - {"when", "patternMatching"}:
                    raise ValueError(f"the reference does not know {set(ev)}")
                evaluators.append((
                    _all(ev.get("when"), named),
                    _all(ev["patternMatching"]["patterns"], named)))
            decide = self._decider(top, evaluators)
            for host in spec["hosts"]:
                self._by_host[host] = decide

    @staticmethod
    def _decider(top, evaluators) -> Callable[[Request], bool]:
        def decide(req: Request) -> bool:
            if not top(req):
                return True  # the AuthConfig's own `when` fails: not enforced
            return all(rules(req) for when, rules in evaluators if when(req))
        return decide

    def decide(self, req: Request) -> int:
        decide = self._by_host.get(req["host"])
        if decide is None:
            return NOT_FOUND
        return OK if decide(req) else PERMISSION_DENIED
