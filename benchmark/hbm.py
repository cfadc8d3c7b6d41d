"""Device memory as libtpu itself reports it: the runtime metric service a
TPU process opens on TPU_RUNTIME_METRICS_PORTS (one port a chip) answers
`tpu.runtime.hbm.memory.usage.bytes`.  The program exposes no allocator
statistic and this process may not open the chip, so the peak is the largest
reading of a sampler that asks once a second while the server runs.

The request and the answer are protobuf, written and read by hand:
MetricRequest{metric_name=1}; MetricResponse{metric=1: TPUMetric{metrics=3:
Metric{gauge=3: Gauge{as_int=2}}}}.
"""

from __future__ import annotations

import os
import threading
from typing import Iterator, List, Optional, Tuple

USAGE = "tpu.runtime.hbm.memory.usage.bytes"
METHOD = "/tpu.monitoring.runtime.RuntimeMetricService/GetRuntimeMetric"


def _fields(buf: bytes) -> Iterator[Tuple[int, int, object]]:
    """(field, wire type, value) of one protobuf message."""
    i = 0

    def varint() -> int:
        nonlocal i
        v = shift = 0
        while True:
            b = buf[i]
            i += 1
            v |= (b & 0x7F) << shift
            shift += 7
            if not b & 0x80:
                return v

    while i < len(buf):
        key = varint()
        field, wt = key >> 3, key & 7
        if wt == 0:
            yield field, wt, varint()
        elif wt == 2:
            n = varint()
            yield field, wt, buf[i:i + n]
            i += n
        elif wt == 1:
            yield field, wt, buf[i:i + 8]
            i += 8
        elif wt == 5:
            yield field, wt, buf[i:i + 4]
            i += 4
        else:
            return


def usage_bytes(response: bytes) -> List[int]:
    """Every gauge of a MetricResponse, as integers."""
    out = []
    for f, wt, metric in _fields(response):
        if f != 1 or wt != 2:
            continue
        for f2, wt2, entry in _fields(metric):
            if f2 != 3 or wt2 != 2:
                continue
            for f3, wt3, gauge in _fields(entry):
                if f3 == 3 and wt3 == 2:
                    out += [int(v) for f4, wt4, v in _fields(gauge)
                            if f4 == 2 and wt4 == 0]
    return out


def ports() -> List[int]:
    raw = os.environ.get("TPU_RUNTIME_METRICS_PORTS", "")
    return [int(p) for p in raw.split(",") if p.strip().isdigit()]


class Sampler:
    """Largest HBM usage on the fullest chip, sampled once a second between
    start() and stop(); None where no service answered."""

    def __init__(self, period_s: float = 1.0):
        self._period = period_s
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.peak: Optional[int] = None

    def _run(self) -> None:
        import grpc

        request = bytes([1 << 3 | 2, len(USAGE)]) + USAGE.encode()
        while not self._stop.wait(self._period):
            for port in ports():
                # a channel a reading: the service appears only once the
                # server has opened the chip, and a channel that has failed
                # backs off for longer than a run may last
                with grpc.insecure_channel(f"localhost:{port}") as channel:
                    call = channel.unary_unary(
                        METHOD, request_serializer=lambda b: b,
                        response_deserializer=lambda b: b)
                    try:
                        readings = usage_bytes(call(request, timeout=2))
                    except grpc.RpcError:
                        continue  # the server is not up yet, or has gone
                if readings:
                    self.peak = max(self.peak or 0, max(readings))

    def start(self) -> None:
        if ports():
            self._thread = threading.Thread(target=self._run, daemon=True)
            self._thread.start()

    def stop(self) -> Optional[int]:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(10)
        return self.peak
