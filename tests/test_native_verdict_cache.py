"""ISSUE 33: the native lane's verdict cache and batch dedup
(native/verdict_cache.cpp) against the plain reference they replaced there,
utils/verdict_cache.py + compiler/pack.py row_key_bytes / dedup_rows, driven
through NativeFrontend's own ``_bind_cache_keys`` / ``_dedup_plan`` and the
cache's ``commit`` on seeded sequences of cuts (CPU, no chip)."""

import gc
import sys
import threading
import types

import numpy as np
import pytest

from authorino_tpu.compiler.pack import dedup_rows, row_key_bytes
from authorino_tpu.native import load_library
from authorino_tpu.native.verdict_cache import (NativeVerdictCache,
                                                key_segments, plan_cut)
from authorino_tpu.utils.verdict_cache import VerdictCache

pytestmark = pytest.mark.skipif(load_library() is None,
                                reason="native library unavailable")

from authorino_tpu.runtime.native_frontend import (NativeFrontend,  # noqa: E402
                                                   _SnapRec)

B, G, A, M, K, C, NB, DVB, S = 64, 12, 5, 2, 3, 2, 2, 64, 2
KEY_ORDER = ["config_id", "attrs_val", "members", "cpu_dense", "attr_bytes",
             "byte_ovf"]


def slot(sharded):
    mid = (S,) if sharded else ()
    a = {
        "attrs_val": np.zeros((B,) + mid + (A,), dtype=np.int16),
        "members": np.full((B,) + mid + (M, K), -1, dtype=np.int16),
        "cpu_dense": np.zeros((B,) + mid + (C,), dtype=np.uint8),
        "config_id": np.zeros((B,), dtype=np.int32),
        "attr_bytes": np.zeros((B,) + mid + (NB, DVB), dtype=np.uint8),
        "byte_ovf": np.zeros((B,) + mid + (NB,), dtype=np.uint8),
        # the encoder's: the longest value of the row's byte lane (the key
        # reads that far), and its DFA byte counts (no part of the key)
        "byte_used": np.zeros((B,), dtype=np.uint16),
        "dfa_bytes": np.zeros((B, 2), dtype=np.uint32),
    }
    if sharded:
        a["shard_of"] = np.zeros((B,), dtype=np.int32)
    return a


def fill(a, ids, sharded):
    """Rows of a cut from row ids: the id decides every operand, so equal ids
    are equal rows and ids that differ, differ somewhere in the key."""
    n = len(ids)
    ids = np.asarray(ids, dtype=np.int64)
    flat = ids % G
    a["config_id"][:n] = flat % (G // S) if sharded else flat
    if sharded:
        a["shard_of"][:n] = flat // (G // S)
    at = (slice(None, n), 0) if sharded else (slice(None, n),)
    a["attrs_val"][at + (0,)] = ids // G % 30000
    a["attrs_val"][at + (1,)] = ids % 7
    a["members"][at + (0, 0)] = ids % 5
    a["cpu_dense"][at + (0,)] = ids % 2
    path = np.zeros((n, DVB), dtype=np.uint8)
    path[:, :8] = ids.astype("<i8").view(np.uint8).reshape(n, 8)
    a["attr_bytes"][at + (0,)] = path
    a["byte_used"][:n] = 8
    a["byte_ovf"][at + (1,)] = ids % 3 == 0


class Lane:
    """One frontend's cache and snapshot records, without the server: the
    served methods, unbound, over a namespace that holds what they read."""

    def __init__(self, size, dedup, buckets=0):
        self.fe = types.SimpleNamespace(
            _verdict_cache=(NativeVerdictCache(size, buckets) if size else None),
            batch_dedup=dedup, _cache_token_ids={})

    def snapshot(self, snap_id, sharded, tokens, cacheable):
        rec = _SnapRec(snap_id=snap_id, policy=None, params=None, encoder=None,
                       sharded=types.SimpleNamespace() if sharded else None,
                       cacheable=cacheable)
        rec.arrays.append(slot(sharded))
        NativeFrontend._bind_cache_keys(self.fe, rec, tokens)
        return rec

    def plan(self, rec, count):
        a = rec.arrays[0]
        rows = a["config_id"][:count].copy()
        shards = a["shard_of"][:count].copy() if rec.sharded else None
        return NativeFrontend._dedup_plan(self.fe, rec, 0, count, rows, shards)

    def commit(self, fan, verdict, firing):
        cache = self.fe._verdict_cache
        if fan is None or cache is None or not len(fan.unique_rows):
            return 0
        return cache.commit(fan.ticket, verdict, firing)

    def counts(self):
        cache = self.fe._verdict_cache
        return cache.counts() if cache is not None else None


class Reference:
    """What NativeFrontend._dedup_plan and `post` did before ISSUE 33."""

    def __init__(self, size, dedup):
        self.cache = VerdictCache(size) if size else None
        self.dedup = dedup

    def plan(self, rec, tokens, count):
        a = rec.arrays[0]
        arrays = [a[k] for k in KEY_ORDER]
        rows = a["config_id"][:count]
        if rec.sharded is not None:
            arrays.insert(0, a["shard_of"])
            eligible = rec.cacheable[a["shard_of"][:count], rows]
        else:
            eligible = rec.cacheable[rows]
        keys = row_key_bytes(arrays, count)
        tok = tokens if rec.sharded is None else None
        ckeys = [(tok[rows[r]] if tok is not None else rec.snap_id, keys[r])
                 for r in range(count)]
        cached, elig_miss = {}, 0
        if self.cache is not None:
            miss_rows = []
            for r in range(count):
                if eligible[r]:
                    v = self.cache.get(ckeys[r])
                    if v is not None:
                        cached[r] = v
                        continue
                    elig_miss += 1
                miss_rows.append(r)
        else:
            miss_rows = list(range(count))
        if self.dedup:
            unique_rows, inverse = dedup_rows(keys, miss_rows)
        else:
            unique_rows, inverse = miss_rows, np.arange(len(miss_rows))
        return ckeys, eligible, cached, miss_rows, unique_rows, inverse, elig_miss

    def commit(self, fan, verdict, firing):
        if self.cache is None or not fan[4]:
            return 0
        before = self.cache.evictions
        fresh = [r for r in fan[4] if fan[1][r]]
        self.cache.put_many(
            (fan[0][r] for r in fresh),
            ((int(verdict[r]), int(firing[r]) if firing is not None else -1)
             for r in fresh))
        return self.cache.evictions - before

    def counts(self):
        return self.cache.counts() if self.cache is not None else None


def same_plan(fan, ref):
    _, _, cached, miss_rows, unique_rows, inverse, elig_miss = ref
    assert fan.cached_rows.tolist() == sorted(cached)
    assert list(zip(fan.cached_verdict.tolist(), fan.cached_firing.tolist())) \
        == [cached[r] for r in sorted(cached)]
    assert fan.miss_rows.tolist() == miss_rows
    assert fan.unique_rows.tolist() == list(unique_rows)
    assert fan.inverse.tolist() == list(inverse)
    assert fan.eligible_misses == elig_miss


def tokens_of(epoch, fps):
    return [(epoch, fp) for fp in fps]


def run_cuts(size, dedup, sharded, cuts, cacheable=None, snapshots=None,
             attribution=True, buckets=0):
    """Drive the same seeded cuts through the native lane and the reference
    and compare after every one.  ``cuts``: (snapshot index, row ids)."""
    rng = np.random.default_rng(33)
    if cacheable is None:
        cacheable = np.ones((S, G // S) if sharded else (G,), dtype=bool)
    snapshots = snapshots or [tokens_of("e0", [f"fp{g}" for g in range(G)])]
    lane, ref = Lane(size, dedup, buckets), Reference(size, dedup)
    recs = [lane.snapshot(i + 1, sharded, None if sharded else toks, cacheable)
            for i, toks in enumerate(snapshots)]
    for which, ids in cuts:
        rec, n = recs[which], len(ids)
        fill(rec.arrays[0], ids, sharded)
        fan, want = lane.plan(rec, n), ref.plan(rec, snapshots[which], n)
        same_plan(fan, want)
        verdict = rng.integers(0, 2, n).astype(np.uint8)
        firing = (rng.integers(-1, 4, n).astype(np.int32) if attribution
                  else None)
        assert lane.commit(fan, verdict, firing) == \
            ref.commit(want, verdict, firing)
        assert lane.counts() == ref.counts()
    return lane, ref


def zipf_ids(rng, n, population):
    ranks = np.arange(1, population + 1, dtype=np.float64)
    p = ranks ** -0.99
    return rng.choice(population, size=n, p=p / p.sum())


def scenario(name):
    rng = np.random.default_rng(1033)
    if name == "evictions":   # every row a miss, an insert and, past 100, an eviction
        return dict(size=100, cuts=[(0, np.arange(c * B, (c + 1) * B))
                                    for c in range(8)])
    if name == "zipf-hits":   # hits with LRU moves, some evictions
        return dict(size=96, cuts=[(0, zipf_ids(rng, int(rng.integers(1, B + 1)), 400))
                                   for _ in range(40)])
    if name == "duplicates":
        return dict(size=64, cuts=[(0, rng.integers(0, 9, B)) for _ in range(6)]
                    + [(0, np.repeat(np.arange(500, 504), 16))])
    if name == "ineligible":
        cacheable = np.ones((G,), dtype=bool)
        cacheable[::3] = False
        return dict(size=64, cacheable=cacheable,
                    cuts=[(0, rng.integers(0, 40, B)) for _ in range(10)])
    if name == "dedup-off":
        return dict(size=48, dedup=False,
                    cuts=[(0, rng.integers(0, 30, B)) for _ in range(10)])
    if name == "cache-off":
        return dict(size=0, cuts=[(0, rng.integers(0, 20, B)) for _ in range(4)])
    if name == "no-attribution":
        return dict(size=32, attribution=False,
                    cuts=[(0, zipf_ids(rng, B, 100)) for _ in range(10)])
    if name == "sharded":
        cacheable = np.ones((S, G // S), dtype=bool)
        cacheable[1, 2] = False
        return dict(size=80, sharded=True, cacheable=cacheable,
                    cuts=[(0, zipf_ids(rng, B, 300)) for _ in range(16)])
    if name == "one-bucket":  # every key in one chain: the compare decides
        return dict(size=64, buckets=1,
                    cuts=[(0, zipf_ids(rng, B, 200)) for _ in range(12)])
    raise AssertionError(name)


@pytest.mark.parametrize("name", [
    "evictions", "zipf-hits", "duplicates", "ineligible", "dedup-off",
    "cache-off", "no-attribution", "sharded", "one-bucket"])
def test_cuts_equal_the_python_cache_and_dedup(name):
    kw = dict(dedup=True, sharded=False)
    kw.update(scenario(name))
    lane, ref = run_cuts(**kw)
    counts = lane.counts()
    if name == "evictions":
        assert counts["evictions"] == 7 * B + B - 100 and counts["entries"] == 100
    if name in ("zipf-hits", "sharded"):
        assert counts["hits"] > 100 and counts["evictions"] > 0
    if name == "cache-off":
        assert counts is None


def test_a_changed_token_strands_one_config_and_the_others_survive():
    """Two snapshots of one frontend: config 3's fingerprint changes, every
    other config's entries answer the second snapshot's rows."""
    fps = [f"fp{g}" for g in range(G)]
    changed = list(fps)
    changed[3] = "fp3-edited"
    ids = np.arange(4 * G)            # four rows a config
    lane, _ = run_cuts(256, True, False, [(0, ids), (1, ids), (0, ids)],
                       snapshots=[tokens_of("e0", fps), tokens_of("e0", changed)])
    # cut 2 hits all but config 3's four rows; cut 3, the old snapshot again
    # (a batch pinned to it), hits everything
    assert lane.counts()["hits"] == (len(ids) - 4) + len(ids)
    assert lane.counts()["adds"] == len(ids) + 4
    assert len(lane.fe._cache_token_ids) == G + 1


def test_a_snapshot_wide_token_is_no_interned_id():
    lane = Lane(64, True)
    wide = lane.snapshot(1, False, None, np.ones((G,), dtype=bool))
    named = lane.snapshot(2, False, tokens_of("e0", ["x"] * G), np.ones((G,), dtype=bool))
    assert set(wide.tok_ids.tolist()) == {(1 << 63) | 1}
    assert set(named.tok_ids.tolist()) == {0}
    for rec in (wide, named):
        fill(rec.arrays[0], np.arange(8), False)
    lane.commit(lane.plan(wide, 8), np.ones(8, np.uint8), None)
    assert lane.plan(named, 8).eligible_misses == 8    # same bytes, other token


def two_rows(width):
    """A cache with one entry, keyed by a row of ``width`` zero bytes."""
    rows = np.zeros((2, width), dtype=np.uint8)
    segs = key_segments([rows])
    tokens = np.zeros(2, dtype=np.uint64)
    ones = np.ones(2, dtype=bool)
    cache = NativeVerdictCache(8, buckets=1)
    first = plan_cut(cache, segs, 1, tokens, ones, True)
    cache.commit(first.ticket, np.array([1], np.uint8), np.array([7], np.int32))
    return cache, rows, segs, tokens, ones


@pytest.mark.parametrize("width, byte", [(1, 0), (8, 7), (13, 12), (200, 0),
                                         (200, 99), (200, 199)])
def test_keys_in_one_bucket_that_differ_in_one_byte_never_alias(width, byte):
    cache, rows, segs, tokens, ones = two_rows(width)
    rows[1, byte] = 1                 # row 0 is the stored key, row 1 differs
    fan = plan_cut(cache, segs, 2, tokens, ones, True)
    assert fan.cached_rows.tolist() == [0]
    assert (fan.cached_verdict.tolist(), fan.cached_firing.tolist()) == ([1], [7])
    assert fan.miss_rows.tolist() == fan.unique_rows.tolist() == [1]
    rows[1, byte] = 0                 # the same bytes under another token
    tokens[1] = 1
    fan = plan_cut(cache, segs, 2, tokens, ones, True)
    assert fan.cached_rows.tolist() == [0] and fan.miss_rows.tolist() == [1]


def test_a_ticket_holds_the_keys_as_they_were_at_plan_time():
    lane = Lane(64, True)
    rec = lane.snapshot(1, False, None, np.ones((G,), dtype=bool))
    a = rec.arrays[0]
    fill(a, np.arange(100, 108), False)
    fan = lane.plan(rec, 8)
    fill(a, np.arange(900, 908), False)       # the encoder refills the slot
    verdict = np.arange(8, dtype=np.uint8) % 2
    firing = np.arange(8, dtype=np.int32)
    assert lane.commit(fan, verdict, firing) == 0
    assert lane.plan(rec, 8).eligible_misses == 8     # 900.. was never inserted
    fill(a, np.arange(100, 108), False)
    again = lane.plan(rec, 8)
    assert again.cached_rows.tolist() == list(range(8))
    assert again.cached_verdict.tolist() == verdict.tolist()
    assert again.cached_firing.tolist() == firing.tolist()
    # a spent ticket inserts nothing more
    assert lane.fe._verdict_cache.commit(fan.ticket, verdict, firing) == 0
    assert lane.counts()["adds"] == 8


def test_a_ticket_dropped_without_commit_inserts_nothing_and_frees_its_cache():
    lane = Lane(64, True)
    rec = lane.snapshot(1, False, None, np.ones((G,), dtype=bool))
    fill(rec.arrays[0], np.arange(16), False)
    fan = lane.plan(rec, 16)
    assert fan.eligible_misses == 16
    handle = lane.fe._verdict_cache._handle
    held = sys.getrefcount(handle)
    ticket = fan.ticket
    del fan
    assert sys.getrefcount(handle) == held    # the ticket holds its cache
    del ticket
    gc.collect()
    assert sys.getrefcount(handle) == held - 1
    assert lane.counts() == {"hits": 0, "misses": 16, "adds": 0,
                             "evictions": 0, "entries": 0}


def test_plan_and_commit_refuse_arrays_shorter_than_the_cut():
    lane = Lane(64, True)
    rec = lane.snapshot(1, False, None, np.ones((G,), dtype=bool))
    fill(rec.arrays[0], np.arange(16), False)
    fan = lane.plan(rec, 16)
    with pytest.raises(ValueError):
        lane.fe._verdict_cache.commit(fan.ticket, np.zeros(8, np.uint8), None)
    with pytest.raises(ValueError):
        lane.fe._verdict_cache.commit(fan.ticket, np.zeros(16, np.uint8),
                                      np.zeros(8, np.int32))
    with pytest.raises(ValueError):
        plan_cut(lane.fe._verdict_cache, rec.key_segs[0], 16,
                 np.zeros(8, np.uint64), np.ones(16, bool), True)
    with pytest.raises(ValueError):    # more rows than the slot holds
        plan_cut(lane.fe._verdict_cache, rec.key_segs[0], B + 1,
                 np.zeros(B + 1, np.uint64), np.ones(B + 1, bool), True)


def test_six_planners_and_a_committer_keep_the_counters_adding_up():
    """The dispatchers plan while the readback thread commits: a lost update
    would break hits + misses = eligible probes, adds - evictions = entries
    or the bound."""
    bound, cuts, planners = 512, 400, 6
    cache = NativeVerdictCache(bound)
    done = []                          # (ticket, count), planner -> committer
    probes = [0] * planners
    errors = []

    def planner(k):
        try:
            rng = np.random.default_rng(k)
            rows = np.zeros((B, 24), dtype=np.uint8)
            ids = rows.view("<i8")
            segs = key_segments([rows])
            eligible = np.ones(B, dtype=bool)
            eligible[::5] = False
            tokens = np.zeros(B, dtype=np.uint64)
            for _ in range(cuts):
                ids[:, 0] = rng.integers(0, 4096, B)
                fan = plan_cut(cache, segs, B, tokens, eligible, True)
                assert len(fan.cached_rows) + fan.eligible_misses <= B
                probes[k] += int(eligible.sum())
                done.append((fan.ticket, B))
        except Exception as e:      # surfaced by the main thread
            errors.append(e)

    stop = threading.Event()

    def committer():
        try:
            while not (stop.is_set() and not done):
                try:
                    ticket, n = done.pop()
                except IndexError:
                    continue
                cache.commit(ticket, np.ones(n, np.uint8), np.zeros(n, np.int32))
        except Exception as e:
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=planner, args=(k,)) for k in range(planners)]
        back = threading.Thread(target=committer)
        for t in threads + [back]:
            t.start()
        for t in threads:
            t.join(timeout=120)
        stop.set()
        back.join(timeout=120)
    finally:
        sys.setswitchinterval(old)
    assert not errors and not back.is_alive()
    assert not any(t.is_alive() for t in threads)
    counts = cache.counts()
    assert counts["hits"] + counts["misses"] == sum(probes)
    assert counts["entries"] == counts["adds"] - counts["evictions"] <= bound
    assert counts["hits"] > 0 and counts["evictions"] > 0
