"""The work a decision requires, whatever implements it: operations and
bytes of one request row, from the manifests and the row alone.

For the row's own AuthConfig and each pattern leaf of it: the bytes of the
attribute the leaf reads and of its constant; one DFA transition per value
byte for a `matches` leaf, one compare per value byte otherwise; and one
verdict byte out.  Nothing here knows the program's pads, operand shapes,
ledger bytes or cost model: a kernel that evaluates every config for every
row does more than this, and its share of the roofline says so.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, Iterator, List, Sequence, Tuple

from reference import attribute

HERE = os.path.dirname(os.path.abspath(__file__))


def _leaves(items: Sequence[dict], named: Dict[str, List[dict]]) -> Iterator[dict]:
    for item in items or ():
        if item.get("patternRef"):
            yield from _leaves(named[item["patternRef"]], named)
        elif item.get("all") is not None:
            yield from _leaves(item["all"], named)
        elif item.get("any") is not None:
            yield from _leaves(item["any"], named)
        else:
            yield item


def config_leaves(manifest: Dict[str, Any]) -> List[dict]:
    spec = manifest["spec"]
    named = spec.get("patterns") or {}
    out = list(_leaves(spec.get("when"), named))
    for ev in (spec.get("authorization") or {}).values():
        out += _leaves(ev.get("when"), named)
        out += _leaves(ev["patternMatching"]["patterns"], named)
    return out


def required(leaves: Sequence[dict], req: Dict[str, Any]) -> Tuple[int, int]:
    """(operations, bytes) the row needs under its own config's leaves."""
    ops = nbytes = 0
    for leaf in leaves:
        value = (attribute(req, leaf["selector"]) or "").encode()
        ops += len(value)
        nbytes += len(value) + len(str(leaf["value"]).encode())
    return ops, nbytes + 1  # the verdict byte


def peaks(device_kind: str) -> Dict[str, float]:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r} in peaks.json")
    return table[device_kind]


def least_seconds(ops: float, nbytes: float, device_kind: str) -> Tuple[float, str]:
    """The least time the chip needs, and which of the two peaks bounds it.
    Compares and DFA transitions are integer work: the int8 peak."""
    p = peaks(device_kind)
    by_ops = ops / p["int8_ops_per_s"]
    by_bytes = nbytes / p["hbm_bytes_per_s"]
    return (by_ops, "ops") if by_ops >= by_bytes else (by_bytes, "bytes")
