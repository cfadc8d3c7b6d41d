"""The native lane's one-call resolve (``fe_resolve_cut``, native/pymod.cpp
over native/verdict_cache.cpp ``vc::resolve``): a completed single-corpus
cut's launch results decoded, put back at their positions, fanned out
through the cut's plan, the slot completed and the plan's ticket committed,
outside the interpreter lock.  Held byte for byte to the Python path it
replaced, ``runtime/native_frontend.py`` ``resolve_cut`` (``unpack_attribution``
and the plan's fan-out), and to ``vc_commit`` for what the cache holds after.
No server runs: the call then completes nothing (CPU, no chip)."""

import numpy as np
import pytest

from authorino_tpu.native import load_library
from authorino_tpu.native.verdict_cache import (NativeVerdictCache,
                                                key_segments, plan_cut)
from authorino_tpu.ops.pattern_eval import packed_width

pytestmark = pytest.mark.skipif(load_library() is None,
                                reason="native library unavailable")

from authorino_tpu.runtime.native_frontend import resolve_cut  # noqa: E402

# a snapshot no server in this process holds: completing into it is a no-op
# even where another test left a server running
SNAP = 0x5EED_0045
# 130 evaluator columns: the width of mixed-tenants-1k's large class (8
# services x 16 route kinds and its catch-all), the widest corpus here
E_WIDE = 130


def _keys(ids):
    """One cut's key bytes from row ids: rows are equal where ids are."""
    ids = np.asarray(ids, dtype="<u4")
    return np.ascontiguousarray(ids.view(np.uint8).reshape(len(ids), 4))


def _plan(cache, keys, dedup=True):
    count = len(keys)
    return plan_cut(cache, key_segments([keys]), count,
                    np.zeros((count,), np.uint64), np.ones((count,), bool),
                    dedup)


def _packed(rng, rows, E):
    """``rows`` random readback rows at width E (every bit pattern, so
    verdict bits that disagree with the rule bits too), plus pad rows past
    them, and two rows planted: a verdict 0 with every rule passing, and a
    verdict 1 whose first rule fired."""
    W = packed_width(1 + 2 * E)
    packed = rng.integers(0, 256, (rows + 3, W), dtype=np.uint8)
    if rows >= 2 and E:
        cols = np.zeros((2, W * 8), dtype=bool)
        cols[0, 1:1 + E] = True
        cols[1, 0] = True
        packed[:2] = np.packbits(cols, axis=1, bitorder="little")
    return packed


def _strided(packed, c):
    """The same bytes as the runtime may hand them back: rows padded past
    their width (``c`` 0), or column-major (``c`` 1)."""
    if c:
        return np.asfortranarray(packed)
    wide = np.zeros((packed.shape[0], packed.shape[1] + 13), dtype=np.uint8)
    wide[:, :packed.shape[1]] = packed
    return wide[:, :packed.shape[1]]


def _parts(rng, u, E, layout):
    """The launches of ``u`` launched rows as ``_Launched.parts`` holds them:
    one part of every row, or two size classes at their own widths with
    their positions (int64, as ``np.nonzero`` gives them, or int32), their
    results row-major or, "strided", as views whose rows are not packed."""
    if layout == "one":
        return [(_packed(rng, u, E), None, u, E)]
    cls = rng.integers(0, 2, u)
    parts = []
    for c, E_c in enumerate((E, max(E // 2, 1))):
        at = np.nonzero(cls == c)[0]
        if layout == "two-int32":
            at = at.astype(np.int32)
        packed = _packed(rng, len(at), E_c)
        if layout == "strided":
            packed = _strided(packed, c)
            assert c or not packed.flags.c_contiguous
        parts.append((packed, at, len(at), E_c))
    return parts


def _native(parts, plan, count, attribute, sentinel=7):
    verdict = np.full((count,), sentinel, dtype=np.uint8)
    firing = (np.full((count,), sentinel, dtype=np.int32) if attribute
              else None)
    evicted = load_library().fe_resolve_cut(parts, plan, count, SNAP, 0,
                                            verdict, firing)
    return verdict, firing, evicted


def _cut_plan(rng, kind, count):
    """None, a dedup plan over repeated rows, or a plan whose cache answers
    part of the cut (a warm-up cut inserted half of its rows first)."""
    ids = rng.integers(0, count // 3 + 1, count)
    if kind == "none":
        return None
    if kind == "dedup":
        plan = _plan(None, _keys(ids))
        assert len(plan.unique_rows) < count
        return plan
    cache = NativeVerdictCache(4 * count)
    warm = _plan(cache, _keys(ids[: count // 2]))
    n = count // 2
    cache.commit(warm.ticket,
                 rng.integers(0, 2, n).astype(np.uint8),
                 rng.integers(-1, 5, n).astype(np.int32))
    plan = _plan(cache, _keys(ids))
    assert len(plan.cached_rows) and len(plan.unique_rows)
    return plan


@pytest.mark.parametrize("kind", ["none", "dedup", "cache"])
@pytest.mark.parametrize("layout", ["one", "two-int64", "two-int32",
                                    "strided"])
@pytest.mark.parametrize("E", [0, 1, 3, E_WIDE])
def test_verdict_and_firing_equal_the_python_path(E, layout, kind):
    rng = np.random.default_rng([E, len(layout), len(kind)])
    count = 256
    plan = _cut_plan(rng, kind, count)
    u = count if plan is None else len(plan.unique_rows)
    parts = _parts(rng, u, E, layout)
    attribute = E > 0
    want_v, want_f = resolve_cut(parts, plan, count, attribute)
    verdict, firing, _ = _native(parts, plan, count, attribute)
    assert verdict.tobytes() == want_v.tobytes()
    if attribute:
        assert firing.tobytes() == want_f.tobytes()
        assert (firing >= 0).any() and (firing < 0).any()
    assert verdict.any() and not verdict.all()


@pytest.mark.parametrize("E", [0, 3])
def test_a_cut_the_cache_answered_whole_takes_no_part(E):
    """The cache-only cut (every row a hit, nothing launched) completes
    through the same call with no part: every row reads its cached value."""
    rng = np.random.default_rng(E)
    count = 64
    ids = rng.integers(0, 40, count)
    cache = NativeVerdictCache(128)
    warm = _plan(cache, _keys(ids))
    u = len(warm.unique_rows)
    v, f = rng.integers(0, 2, count).astype(np.uint8), rng.integers(
        -1, 3, count).astype(np.int32)
    cache.commit(warm.ticket, v, f)
    plan = _plan(cache, _keys(ids))
    assert len(plan.unique_rows) == 0 and len(plan.cached_rows) == count
    want_v, want_f = resolve_cut([], plan, count, E > 0)
    verdict, firing, evicted = _native([], plan, count, E > 0)
    assert evicted == 0 and verdict.tobytes() == want_v.tobytes()
    # a row reads the value its first occurrence was committed with
    first = {i: k for k, i in reversed(list(enumerate(ids)))}
    assert verdict.tolist() == [v[first[i]] for i in ids]
    if E:
        assert firing.tobytes() == want_f.tobytes()
        assert firing.tolist() == [f[first[i]] for i in ids]
    assert cache.counts()["adds"] == u


def test_attribution_with_no_evaluator_column_fires_none():
    """A part of E 0 under attribution: no column can fire."""
    packed = np.full((5, 1), 0xFE, dtype=np.uint8)
    packed[:3] |= 1
    verdict, firing, _ = _native([(packed, None, 5, 0)], None, 5, True)
    assert verdict.tolist() == [1, 1, 1, 0, 0]
    assert firing.tolist() == [-1] * 5


@pytest.mark.parametrize("attribute", [True, False])
def test_the_cache_holds_what_vc_commit_leaves(attribute):
    """Two caches fed the same cuts, one through the call and one through
    the Python path and ``vc_commit``: the same evictions a cut, the same
    counts, and the same value under every key ever inserted.  The cache is
    small, so cuts evict."""
    rng = np.random.default_rng(45 + attribute)
    mine, theirs = NativeVerdictCache(48), NativeVerdictCache(48)
    E = 5 if attribute else 0
    seen = set()
    for _ in range(12):
        count = int(rng.integers(1, 129))
        ids = rng.integers(0, 120, count)
        seen.update(ids.tolist())
        keys = _keys(ids)
        plan_a, plan_b = _plan(mine, keys), _plan(theirs, keys)
        for x, y in zip(plan_a[1:], plan_b[1:]):
            assert np.array_equal(x, y)
        parts = _parts(rng, len(plan_a.unique_rows), max(E, 2), "two-int64")
        _, _, evicted = _native(parts, plan_a, count, attribute)
        want_v, want_f = resolve_cut(parts, plan_b, count, attribute)
        assert evicted == theirs.commit(plan_b.ticket, want_v, want_f)
        assert mine.counts() == theirs.counts()
    everything = _keys(sorted(seen))
    got = _plan(mine, everything, dedup=False)
    want = _plan(theirs, everything, dedup=False)
    assert len(got.cached_rows) == mine.counts()["entries"]
    for x, y in zip(got[1:], want[1:]):
        assert np.array_equal(x, y)


def _malformed(case, parts, plan, count):
    """One input of a good call broken as ``case`` says: (parts, plan,
    verdict, firing)."""
    verdict = np.full((count,), 7, dtype=np.uint8)
    firing = np.full((count,), 7, dtype=np.int32)
    packed, at, n, E = parts[0]
    if case == "short verdict":
        verdict = verdict[:-1]
    elif case == "short firing":
        firing = firing[:-1]
    elif case == "verdict of int32":
        verdict = verdict.astype(np.int32)
    elif case == "firing of int64":
        firing = firing.astype(np.int64)
    elif case == "strided verdict":
        verdict = np.full((2 * count,), 7, dtype=np.uint8)[::2]
    elif case == "part shorter than its rows":
        parts = [(packed[: n - 1], at, n, E)] + parts[1:]
    elif case == "part of int32":
        parts = [(packed.astype(np.int32), at, n, E)] + parts[1:]
    elif case == "part narrower than its columns":
        parts = [(packed, at, n, 8 * packed.shape[1])] + parts[1:]
    elif case == "position past the launched rows":
        at = at.copy()
        at[-1] = len(plan.unique_rows)
        parts = [(packed, at, n, E)] + parts[1:]
    elif case == "inverse past the unique rows":
        inverse = plan.inverse.copy()
        inverse[0] = len(plan.unique_rows)
        plan = plan._replace(inverse=inverse)
    elif case == "miss row past the cut":
        miss = plan.miss_rows.copy()
        miss[-1] = count
        plan = plan._replace(miss_rows=miss)
    return parts, plan, verdict, firing


@pytest.mark.parametrize("case", [
    "short verdict", "short firing", "verdict of int32", "firing of int64",
    "strided verdict", "part shorter than its rows", "part of int32",
    "part narrower than its columns", "position past the launched rows",
    "inverse past the unique rows", "miss row past the cut"])
def test_a_malformed_input_raises_before_the_slot_is_touched(case):
    """Every input is checked before anything is written: the call raises
    ValueError with the outputs as they were and the ticket unspent, so the
    readback loop may still fail the cut over to its retry."""
    rng = np.random.default_rng(len(case))
    count = 96
    cache = NativeVerdictCache(256)
    plan = _plan(cache, _keys(rng.integers(0, 40, count)))
    parts = _parts(rng, len(plan.unique_rows), 3, "two-int64")
    bad_parts, bad_plan, verdict, firing = _malformed(case, parts, plan,
                                                      count)
    with pytest.raises(ValueError):
        load_library().fe_resolve_cut(bad_parts, bad_plan, count, SNAP, 0,
                                      verdict, firing)
    assert (verdict == 7).all() and (firing == 7).all()
    assert cache.counts()["adds"] == 0
    # the same cut, well formed, still resolves and commits its ticket
    verdict, firing, _ = _native(parts, plan, count, True)
    want_v, want_f = resolve_cut(parts, plan, count, True)
    assert verdict.tobytes() == want_v.tobytes()
    assert firing.tobytes() == want_f.tobytes()
    assert cache.counts()["adds"] == len(plan.unique_rows)
