"""The bytes the load generator sends: envoy.service.auth.v3.CheckRequest,
encoded by hand so that the benchmark imports nothing of the program.

CheckRequest.attributes (1) . request (4) . http (2) with method (2),
headers (3, map<string,string>), path (4) and host (5); field numbers from
envoy/service/auth/v3/attribute_context.proto.
"""

from __future__ import annotations

import struct
from typing import Dict, Iterable, List


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        if n:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _ld(field: int, payload: bytes) -> bytes:
    """One length-delimited field."""
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


def check_request(req: Dict) -> bytes:
    """The request as Envoy would send it: the host also rides as a header."""
    headers = dict(req["headers"], host=req["host"])
    http = _ld(2, req["method"].encode())
    for k in sorted(headers):
        http += _ld(3, _ld(1, k.encode()) + _ld(2, headers[k].encode()))
    http += _ld(4, req["path"].encode()) + _ld(5, req["host"].encode())
    return _ld(1, _ld(4, _ld(2, http)))


def section(blob: bytes) -> bytes:
    """One section of the load generator's standard input."""
    return struct.pack("<Q", len(blob)) + blob


def payload_section(requests: Iterable[Dict]) -> bytes:
    parts: List[bytes] = []
    for req in requests:
        msg = check_request(req)
        parts.append(struct.pack(">I", len(msg)) + msg)
    return section(b"".join(parts))
