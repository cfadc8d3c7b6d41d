"""The native lane's verdict cache and within-batch dedup
(``native/verdict_cache.cpp``): one call a cut before the launch
(``plan_cut``) and one after it (``NativeVerdictCache.commit``), both outside
the interpreter lock.

The contract is ``utils/verdict_cache.py`` + ``compiler/pack.py``
``dedup_rows``, which remain the engine lane's and the reference of
``tests/test_native_verdict_cache.py``: exact keys (token + operand bytes),
exact LRU, first occurrence wins, unique rows in submission order."""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional

import numpy as np

from . import load_library

__all__ = ["NativeVerdictCache", "CutPlan", "key_segments", "plan_cut"]


def _lib():
    mod = load_library()
    if mod is None:
        raise RuntimeError("native library unavailable")
    return mod


class CutPlan(NamedTuple):
    """What ``plan_cut`` found in one cut; row arrays are int32, ascending."""
    ticket: Any                  # keys of the eligible unique misses (None: no cache)
    cached_rows: np.ndarray      # rows the cache answered
    cached_verdict: np.ndarray   # ... their verdicts
    cached_firing: np.ndarray    # ... their firing columns (-1: none)
    miss_rows: np.ndarray        # every other row
    unique_rows: np.ndarray      # first occurrences among the miss rows
    inverse: np.ndarray          # unique_rows[inverse[j]] stands for miss_rows[j]
    eligible_misses: int


def key_segments(arrays, used=None) -> bytes:
    """The descriptor ``plan_cut`` reads a slot's rows through: (address,
    bytes a row, rows, sub-rows, their stride, used) of each operand array,
    in key order.  ``used`` maps an array's position to a uint16 array, one
    a row: that array's row is keyed by the first ``used[r]`` bytes of each
    of its sub-rows (its last axis) and not whole; whoever fills the slot
    keeps every byte past them zero.  The arrays must stay alive and
    C-contiguous for as long as the descriptor is used."""
    segs = np.zeros((len(arrays), 6), dtype=np.uint64)
    for i, a in enumerate(arrays):
        if not a.flags.c_contiguous:
            raise ValueError("key segment is not C-contiguous")
        segs[i, :3] = (a.ctypes.data, a.nbytes // a.shape[0], a.shape[0])
        u = (used or {}).get(i)
        if u is not None:
            if u.dtype != np.uint16 or u.shape != (a.shape[0],) \
                    or not u.flags.c_contiguous:
                raise ValueError("used bytes must be a contiguous uint16 a row")
            stride = a.shape[-1] * a.itemsize
            segs[i, 3:] = (segs[i, 1] // stride, stride, u.ctypes.data)
    return segs.tobytes()


class NativeVerdictCache:
    """Bounded exact LRU over (token, row bytes) -> (verdict, firing column),
    behind one mutex that is only ever taken outside the interpreter lock.
    ``buckets`` is for tests (a tiny table forces hash collisions)."""

    def __init__(self, max_entries: int = 32768, buckets: int = 0):
        self.max_entries = max(1, int(max_entries))
        self._mod = _lib()
        self._handle = self._mod.vc_new(self.max_entries, int(buckets))

    def counts(self) -> Dict[str, int]:
        return self._mod.vc_counts(self._handle)

    def commit(self, ticket, verdict: np.ndarray,
               firing: Optional[np.ndarray]) -> int:
        """Insert the ticket's keys with the values of their rows (uint8
        ``verdict``, int32 ``firing`` or None, one a row of the cut);
        returns the evictions that made."""
        return self._mod.vc_commit(ticket, verdict, firing)


def plan_cut(cache: Optional[NativeVerdictCache], segments: bytes, count: int,
             tokens: np.ndarray, eligible: np.ndarray, dedup: bool) -> CutPlan:
    """Probe ``cache`` (None: no cache) for the eligible rows of a cut and
    collapse the rest to unique rows (``dedup`` False: every miss row is its
    own).  ``tokens`` uint64 and ``eligible`` bool/uint8, one a row."""
    out, nc, nm, nu, elig_miss, ticket = _lib().vc_plan(
        cache._handle if cache is not None else None, segments, count,
        tokens, eligible, dedup)
    out = np.frombuffer(out, dtype=np.int32)
    miss = 3 * nc
    return CutPlan(ticket, out[:nc], out[nc:2 * nc], out[2 * nc:miss],
                   out[miss:miss + nm], out[miss + nm:miss + nm + nu],
                   out[miss + nm + nu:], elig_miss)
