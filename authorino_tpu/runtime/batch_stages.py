"""The stage clock of one lane's batches (docs/observability.md "Batch/device
series"): a batch's life from the arrival of its first row to the end of
its telemetry, cut into eight stages, each measured where its work happens
with ``time.monotonic_ns()`` and recorded once, into four sinks:

  - a cumulative table ``{stage: {count, sum_ns, max_ns}}`` (/debug/vars
    ``native_frontend.stages``): a reader takes the difference of two
    scrapes, with no profiler running.  Beside the eight stages it holds the
    row ``drain``, which is no batch's: see ``StageClock.record_drain``;
  - ``auth_server_pipeline_stage_seconds{lane, stage}``;
  - a ``jax.profiler.TraceAnnotation("atpu/<lane>/<stage>", batch=<seq>)``
    around each same-thread stage, which costs nothing without a profiler
    session.  ``device`` has none: it is the gap between ``launch`` and
    ``resolve`` of one ``batch``.  ``fill`` and ``pickup`` run in C++
    before Python sees the batch (the front end stamps the first row's
    arrival and the flush, native/frontend.cpp), so ``fill`` has none and
    ``pickup``'s is a mark at dispatch entry that carries ``mono_ns`` (now),
    ``flush_mono_ns`` and ``first_mono_ns``: the first pair ties
    CLOCK_MONOTONIC to the profiler's clock, and the three place the flush
    and the start of the cut's filling in the trace;
  - a ring of the last ``RING`` batches (/debug/batches, flight-recorder
    bundles), written once a batch at the end of ``post``.

Stage k runs from stamp k to stamp k + 1, so the stages of one thread are
contiguous: what lies between two ``with`` blocks is charged to the later one.
Nothing here runs per request.
"""

from __future__ import annotations

import itertools
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

from ..utils import metrics as metrics_mod

__all__ = ["STAGES", "STAMPS", "FIELDS", "RING", "StageClock",
           "BatchTimeline"]

STAGES = ("fill", "pickup", "plan", "encode", "launch", "device", "resolve",
          "post")
STAMPS = ("first", "flush", "entry", "planned", "encoded", "launched",
          "ready", "completed", "posted")
_INDEX = {name: k for k, name in enumerate(STAGES)}
_FILL, _PICKUP = _INDEX["fill"], _INDEX["pickup"]
_DEVICE, _POST = _INDEX["device"], _INDEX["post"]
RING = 2048

# one ring row: what the batch was, then its stamps
FIELDS = ("seq", "snap", "slot", "rows", "device_rows", "pad", "eff",
          "inflight") + tuple(f"{s}_ns" for s in STAMPS)


class BatchTimeline:
    """One batch's stamps (0 = not taken) and the facts the ring keeps.
    ``with timeline.stage(name):`` runs one same-thread stage; the object
    rides the readback queue from the dispatcher to the readback thread,
    which is the one hand-over, so no two threads hold it at once."""

    __slots__ = ("clock", "seq", "snap", "slot", "rows", "device_rows", "pad",
                 "eff", "inflight", "t", "_k", "_span")

    def __init__(self, clock: "StageClock", seq: int, snap: int, slot: int,
                 rows: int):
        self.clock = clock
        self.seq, self.snap, self.slot, self.rows = seq, snap, slot, rows
        self.device_rows = self.pad = self.eff = self.inflight = 0
        self.t: List[int] = [0] * len(STAMPS)
        self._k = 0
        self._span = None

    def stage(self, name: str) -> "BatchTimeline":
        k = self._k = _INDEX[name]
        if self.t[k + 1]:
            # the stage runs again (a cut's second launch has an `encode`
            # and a `launch` of its own): it starts where the last one
            # ended, and counts once more
            self.t[k] = max(self.t)
        return self

    def __enter__(self) -> "BatchTimeline":
        self._span = self.clock.annotate(self.clock.span_names[self._k],
                                         batch=self.seq)
        self._span.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        self._span.__exit__(*exc)
        now = time.monotonic_ns()
        k = self._k
        self.t[k + 1] = now
        self.clock.record(k, now - self.t[k])
        if k == _POST:
            self.clock.commit(self)

    def ready(self) -> None:
        """The readback loop saw the result ready: ``device`` ends (where a
        launch began it: a cache-only batch has none) and ``resolve``
        begins."""
        now = time.monotonic_ns()
        self.t[_DEVICE + 1] = now
        if self.t[_DEVICE]:
            self.clock.record(_DEVICE, now - self.t[_DEVICE])


class StageClock:
    def __init__(self, lane: str):
        from jax.profiler import TraceAnnotation

        self.lane = lane
        self.annotate = TraceAnnotation
        self.span_names = tuple(f"atpu/{lane}/{s}" for s in STAGES)
        self._seq = itertools.count(1)
        # dispatcher threads record plan/encode/launch side by side
        self._lock = threading.Lock()
        self._totals = [[0, 0, 0] for _ in STAGES]  # count, sum_ns, max_ns
        # not a batch's stage: the drains of what `post` keeps as arrays
        # into their Prometheus children (utils.metrics.drain), whoever ran
        # them.  A row of the cumulative table alone: no ring column, no
        # span, no histogram sample
        self._drain = [0, 0, 0]
        self._ring = np.zeros((RING, len(FIELDS)), dtype=np.int64)
        self._committed = 0  # one writer: the thread that runs `post`

    def begin(self, snap: int, slot: int, rows: int, flush_ns: int = 0,
              first_ns: int = 0) -> BatchTimeline:
        """Dispatch entry.  ``flush_ns``: when the front end cut the batch,
        ``first_ns``: when the cut's first row arrived (both
        CLOCK_MONOTONIC); 0 where no cut led here (a retry), which records
        neither ``fill`` nor ``pickup``."""
        now = time.monotonic_ns()
        b = BatchTimeline(self, next(self._seq), snap, slot, rows)
        b.t[_PICKUP + 1] = now
        if flush_ns:
            b.t[_PICKUP] = flush_ns
            b.t[_FILL] = first_ns
            with self.annotate(self.span_names[_PICKUP], batch=b.seq,
                               mono_ns=now, flush_mono_ns=flush_ns,
                               first_mono_ns=first_ns):
                pass
            if first_ns:
                self.record(_FILL, flush_ns - first_ns)
            self.record(_PICKUP, now - flush_ns)
        return b

    def record(self, k: int, dur_ns: int) -> None:
        with self._lock:
            row = self._totals[k]
            row[0] += 1
            row[1] += dur_ns
            if dur_ns > row[2]:
                row[2] = dur_ns
        metrics_mod.observe_pipeline_stage(self.lane, STAGES[k], dur_ns * 1e-9)

    def record_drain(self, dur_ns: int) -> None:
        with self._lock:
            row = self._drain
            row[0] += 1
            row[1] += dur_ns
            if dur_ns > row[2]:
                row[2] = dur_ns

    def commit(self, b: BatchTimeline) -> None:
        self._ring[self._committed % RING] = (
            b.seq, b.snap, b.slot, b.rows, b.device_rows, b.pad, b.eff,
            b.inflight, *b.t)
        self._committed += 1

    def totals(self) -> Dict[str, Dict[str, int]]:
        with self._lock:
            return {s: {"count": c, "sum_ns": total, "max_ns": longest}
                    for s, (c, total, longest) in zip(
                        STAGES + ("drain",), self._totals + [self._drain])}

    def to_json(self, n: Optional[int] = None) -> Dict[str, Any]:
        """The newest ``n`` batches of the ring (all of it by default),
        newest first, one list a batch in the order of ``fields``: what the
        batch was, then its stamps as ``time.monotonic_ns()`` read them
        (0: not taken)."""
        done = self._committed
        keep = min(done, RING) if n is None else max(0, min(n, done, RING))
        at = (done - 1 - np.arange(keep)) % RING
        return {"committed": done, "fields": list(FIELDS),
                "batches": self._ring[at].tolist()}
