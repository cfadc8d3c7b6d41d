"""Dedup-and-cache the hot path (ISSUE 3): batch row dedup must be
bit-identical to full evaluation (dedup-evaluate-scatter property), the
snapshot-scoped verdict cache must never serve a stale verdict across a
snapshot swap (generation-keyed, structural invalidation), non-cacheable
configs must bypass the cache, and the compiler's rule-tensor compaction
(node dedup + shared DFA tables) must preserve semantics.

Deliberately import-light: collects on images without `cryptography`
(no evaluators.identity / native_frontend imports)."""

from __future__ import annotations

import asyncio
import random
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from authorino_tpu.compiler import ConfigRules, compile_corpus
from authorino_tpu.compiler.encode import encode_batch_py
from authorino_tpu.compiler.pack import (
    batch_row_keys,
    dedup_rows,
    pack_batch,
    row_key_bytes,
    select_rows,
)
from authorino_tpu.expressions import All, Any_, Operator, Pattern
from authorino_tpu.ops import pattern_eval as pe
from authorino_tpu.runtime import EngineEntry, PolicyEngine
from authorino_tpu.utils.verdict_cache import VerdictCache


def run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


def _packed(params, db, bitpack=False):
    """One DeviceBatch through the host twin's entries: eval_packed_jit's
    [B, 1+2E] bool, or eval_bitpacked_jit's [B, W] uint8 bitmask."""
    dfa = pe.has_dfa(params)
    fn = pe.eval_bitpacked_jit if bitpack else pe.eval_packed_jit
    return fn(params, jnp.asarray(db.attrs_val), jnp.asarray(db.members_c),
              jnp.asarray(db.cpu_dense), jnp.asarray(db.config_id),
              jnp.asarray(db.attr_bytes) if dfa else None,
              jnp.asarray(db.byte_ovf) if dfa else None,
              *pe._extra_operands(db))


def _corpus(n_configs=9):
    rng = random.Random(3)
    configs = []
    for i in range(n_configs):
        rule = All(
            Pattern("request.method", Operator.EQ, rng.choice(["GET", "POST"])),
            Any_(
                Pattern("auth.identity.roles", Operator.INCL, f"role-{i % 4}"),
                Pattern("auth.identity.org", Operator.NEQ, f"org-{i % 3}"),
                Pattern("request.url_path", Operator.MATCHES, rf"^/svc-{i % 2}/"),
            ),
        )
        configs.append(ConfigRules(name=f"cfg-{i}", evaluators=[(None, rule)]))
    return configs


def _doc(rng, n_roles=None):
    return {
        "request": {
            "method": rng.choice(["GET", "POST", "PUT"]),
            "url_path": rng.choice(["/svc-0/a", "/svc-1/b", "/other"]),
        },
        "auth": {"identity": {
            "org": f"org-{rng.randrange(5)}",
            # members_k=4 below: > 4 roles forces membership overflow →
            # a host-fallback row (the lossy-encoding case the row key
            # must fold in)
            "roles": [f"role-{rng.randrange(6)}" for _ in range(
                rng.randrange(0, 8) if n_roles is None else n_roles)],
        }},
    }


def _dup_docs(n, dup_fraction, seed=11):
    """n docs where ~dup_fraction of rows repeat an earlier doc exactly."""
    rng = random.Random(seed)
    docs = []
    for _ in range(n):
        if docs and rng.random() < dup_fraction:
            docs.append(rng.choice(docs))
        else:
            docs.append(_doc(rng))
    return docs


# ---------------------------------------------------------------------------
# tentpole property: dedup-evaluate-scatter ≡ full evaluation, bit for bit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dup_fraction,seed", [
    (0.0, 1),     # all-unique extreme (dedup is the identity)
    (0.5, 2),
    (0.9, 3),
    (1.0, 4),     # all-duplicate extreme (one device row)
])
def test_dedup_evaluate_scatter_bit_identical(dup_fraction, seed):
    policy = compile_corpus(_corpus(), members_k=4)
    params = pe.to_device(policy)
    rng = random.Random(seed)
    n, pad = 48, 64
    docs = ([_doc(rng)] * n if dup_fraction == 1.0
            else _dup_docs(n, dup_fraction, seed=seed))
    rows = ([0] * n if dup_fraction == 1.0
            else [rng.randrange(policy.n_configs) for _ in range(n)])
    db = pack_batch(policy, encode_batch_py(policy, docs, rows, batch_pad=pad))

    reference = np.asarray(_packed(params, db))[:n]  # [n, 1+2E]

    keys = batch_row_keys(db, n)
    unique_rows, inverse = dedup_rows(keys, list(range(n)))
    u = len(unique_rows)
    if dup_fraction == 1.0:
        assert u == 1
    if dup_fraction == 0.0:
        assert u == n
    db_u = select_rows(db, unique_rows, batch_pad=u + (-u % 16))
    packed_u = np.asarray(_packed(params, db_u))
    scattered = packed_u[inverse]  # fan unique verdicts back out
    np.testing.assert_array_equal(scattered, reference)


def test_row_keys_fold_in_the_lossy_fallback_flag():
    """Two requests identical in the compact payload but differing in
    membership overflow (first K elements equal, one has extras) must get
    DIFFERENT row keys — aliasing them would let a cached/deduped verdict
    stand in for a row whose true answer only the host oracle knows."""
    policy = compile_corpus(_corpus(), members_k=4)
    rng = random.Random(7)
    base = _doc(rng, n_roles=4)
    over = {"request": dict(base["request"]),
            "auth": {"identity": dict(base["auth"]["identity"])}}
    # same first K=4 roles, then overflow
    over["auth"]["identity"]["roles"] = (
        base["auth"]["identity"]["roles"] + ["extra-1", "extra-2"])
    db = pack_batch(policy, encode_batch_py(policy, [base, over], [0, 0],
                                            batch_pad=16))
    assert bool(db.host_fallback[1]) and not bool(db.host_fallback[0])
    keys = batch_row_keys(db, 2)
    assert keys[0] != keys[1]


def test_row_key_bytes_empty_batch():
    assert row_key_bytes([np.zeros((4, 2), dtype=np.int32)], 0) == []


# ---------------------------------------------------------------------------
# engine integration: dedup + cache on the pipelined dispatch path
# ---------------------------------------------------------------------------

RULE_ACME = Pattern("auth.identity.org", Operator.EQ, "acme")
RULE_EVIL = Pattern("auth.identity.org", Operator.EQ, "evil")


def build_engine(rule=RULE_ACME, name="c", **kw) -> PolicyEngine:
    kw.setdefault("max_batch", 8)
    # dedup + verdict-cache contracts live on the DEVICE encode path; the
    # cost model would route these small warm-RTT cuts host-side (which
    # legitimately bypasses encode and the cache — lane-selection
    # semantics are pinned in tests/test_lane_select.py)
    kw.setdefault("lane_select", False)
    engine = PolicyEngine(members_k=4, mesh=None, **kw)
    engine.apply_snapshot([
        EngineEntry(id=name, hosts=[name], runtime=None,
                    rules=ConfigRules(name=name, evaluators=[(None, rule)]))
    ])
    return engine


def doc(org="acme"):
    return {"auth": {"identity": {"org": org}}}


def test_engine_results_identical_with_and_without_dedup_cache():
    """The same submissions (duplicates included) through a dedup+cache
    engine and a both-off engine resolve to identical verdicts."""
    on = build_engine(verdict_cache_size=1024, batch_dedup=True)
    off = build_engine(verdict_cache_size=0, batch_dedup=False)
    orgs = ["acme", "evil", "acme", "acme", "zed", "evil", "acme", "acme"]

    async def drive(engine):
        return await asyncio.gather(*(engine.submit(doc(o), "c")
                                      for o in orgs))

    got_on = run(drive(on))
    got_off = run(drive(off))
    for (r1, s1), (r2, s2) in zip(got_on, got_off):
        np.testing.assert_array_equal(r1, r2)
        np.testing.assert_array_equal(s1, s2)
    assert [bool(r[0]) for r, _ in got_on] == [o == "acme" for o in orgs]


def test_engine_verdict_cache_hits_repeat_rows():
    engine = build_engine(verdict_cache_size=1024)

    async def burst():
        return await asyncio.gather(*(engine.submit(doc("acme"), "c")
                                      for _ in range(6)))

    run(burst())          # first batch: misses + adds
    hits0 = engine._verdict_cache.hits
    outs = run(burst())   # same row digest: served from the cache
    assert engine._verdict_cache.hits > hits0
    assert all(bool(r[0]) for r, _ in outs)


def test_snapshot_swap_never_serves_stale_cached_verdict():
    """Generation-keyed invalidation with batches IN FLIGHT across the
    swap: entries inserted under generation G must not satisfy lookups
    under G+1, even while a gated G batch is still completing."""
    engine = build_engine(rule=RULE_ACME, verdict_cache_size=1024)
    run(engine.submit(doc("acme"), "c"))  # warm jit + seed the G cache
    assert engine._verdict_cache.adds >= 1

    gate = threading.Event()
    real = PolicyEngine._encode_and_launch
    gated_launches = []

    class GatedHandle:
        def __init__(self, inner):
            self.inner = inner

        def is_ready(self):
            return gate.is_set() and (
                not hasattr(self.inner, "is_ready") or self.inner.is_ready())

        def __array__(self, dtype=None):
            return np.asarray(self.inner)

    def gated(snap, batch):
        item = real(engine, snap, batch)
        item.handle = GatedHandle(item.handle)
        gated_launches.append(item)
        return item

    engine._encode_and_launch = gated

    async def body():
        # a G batch launches (cache-missing doc) and stays in flight
        pre = [asyncio.ensure_future(engine.submit(doc("evil"), "c"))
               for _ in range(4)]
        deadline = time.monotonic() + 5
        while not gated_launches and time.monotonic() < deadline:
            await asyncio.sleep(0.005)
        assert gated_launches
        # swap: acme (cached as ALLOWED under G) is now DENIED
        engine._encode_and_launch = real.__get__(engine, PolicyEngine)
        engine.apply_snapshot([
            EngineEntry(id="c", hosts=["c"], runtime=None,
                        rules=ConfigRules(name="c",
                                          evaluators=[(None, RULE_EVIL)]))
        ])
        post = await asyncio.gather(*(engine.submit(doc("acme"), "c")
                                      for _ in range(3)))
        # G's cached ALLOW for acme must NOT leak into G+1
        assert not any(bool(r[0]) for r, _ in post)
        # and evil is allowed under G+1 (fresh evaluation, then cached)
        post_evil = await engine.submit(doc("evil"), "c")
        assert bool(post_evil[0][0])
        gate.set()
        outs = await asyncio.wait_for(asyncio.gather(*pre), timeout=10)
        # the in-flight G batch resolves with G semantics: evil denied
        assert not any(bool(r[0]) for r, _ in outs)

    run(body())
    # the in-flight batch's late inserts landed under G, not G+1: a fresh
    # G+1 lookup of the same evil row still answers from G+1's own entry
    out = run(engine.submit(doc("evil"), "c"))
    assert bool(out[0][0])


def test_non_cacheable_configs_bypass_the_cache():
    """A config whose rules reference a request-unique selector compiles
    with cacheable=False and must neither insert nor serve from the
    verdict cache."""
    rule = All(Pattern("request.id", Operator.NEQ, ""),
               Pattern("auth.identity.org", Operator.EQ, "acme"))
    policy = compile_corpus([ConfigRules(name="c", evaluators=[(None, rule)])])
    assert not bool(policy.config_cacheable[0])

    engine = build_engine(rule=rule, verdict_cache_size=1024)
    d = {"request": {"id": "r-1"}, "auth": {"identity": {"org": "acme"}}}

    async def twice():
        a = await engine.submit(d, "c")
        b = await engine.submit(d, "c")
        return a, b

    (r1, _), (r2, _) = run(twice())
    assert bool(r1[0]) and bool(r2[0])
    vc = engine._verdict_cache
    assert vc.adds == 0 and vc.hits == 0
    # ...while a cacheable config on the same engine does use it
    cacheable_policy = compile_corpus(
        [ConfigRules(name="c2", evaluators=[(None, RULE_ACME)])])
    assert bool(cacheable_policy.config_cacheable[0])


def test_dedup_can_be_disabled():
    engine = build_engine(verdict_cache_size=0, batch_dedup=False)

    async def burst():
        return await asyncio.gather(*(engine.submit(doc("acme"), "c")
                                      for _ in range(6)))

    outs = run(burst())
    assert all(bool(r[0]) for r, _ in outs)
    assert engine._verdict_cache is None


# ---------------------------------------------------------------------------
# verdict cache unit behavior
# ---------------------------------------------------------------------------

def test_verdict_cache_lru_bound_and_counters():
    vc = VerdictCache(max_entries=2)
    vc.put(("g1", b"a"), 1)
    vc.put(("g1", b"b"), 2)
    assert vc.get(("g1", b"a")) == 1          # refreshes a
    vc.put(("g1", b"c"), 3)                   # evicts b (LRU)
    assert vc.get(("g1", b"b")) is None
    assert vc.get(("g1", b"a")) == 1
    assert vc.evictions == 1 and vc.adds == 3
    assert vc.hits == 2 and vc.misses == 1
    assert len(vc) == 2


def test_verdict_cache_generation_keys_are_disjoint():
    vc = VerdictCache()
    vc.put((1, b"row"), "old")
    assert vc.get((2, b"row")) is None  # structural invalidation by keying


# ---------------------------------------------------------------------------
# rule-tensor compaction: node dedup + shared DFA tables
# ---------------------------------------------------------------------------

def test_identical_rule_trees_share_circuit_nodes():
    rule = lambda: All(  # noqa: E731 - fresh tree per config
        Pattern("request.method", Operator.EQ, "GET"),
        Any_(Pattern("auth.identity.org", Operator.EQ, "a"),
             Pattern("auth.identity.org", Operator.EQ, "b")),
    )
    one = compile_corpus([ConfigRules(name="c0", evaluators=[(None, rule())])],
                         pad=False)
    many = compile_corpus(
        [ConfigRules(name=f"c{i}", evaluators=[(None, rule())])
         for i in range(5)], pad=False)
    # 5 configs with the identical tree lower to the SAME circuit size
    assert many.buffer_size == one.buffer_size
    # and every config's verdict still reads its own (shared) slots
    docs = [{"request": {"method": "GET"},
             "auth": {"identity": {"org": "a"}}},
            {"request": {"method": "POST"},
             "auth": {"identity": {"org": "a"}}}]
    params = pe.to_device(many)
    db = pack_batch(many, encode_batch_py(many, docs, [2, 3], batch_pad=8))
    own, _ = pe.eval_batch_jit(params, db)
    assert bool(own[0]) and not bool(own[1])


def test_shared_regex_dfa_tables_dedupe_across_attrs_and_configs():
    pattern = r"^/api/v\d+/"
    configs = [
        ConfigRules(name="c0", evaluators=[
            (None, Pattern("request.url_path", Operator.MATCHES, pattern))]),
        ConfigRules(name="c1", evaluators=[
            (None, Pattern("request.path", Operator.MATCHES, pattern))]),
        ConfigRules(name="c2", evaluators=[
            (None, Pattern("request.headers.x-route", Operator.MATCHES,
                           pattern))]),
    ]
    policy = compile_corpus(configs)
    # three DFA rows (three attrs), ONE shared transition table
    assert int(policy.dfa_table_of_row.shape[0]) >= 3
    assert int(policy.dfa_tables.shape[0]) == 1
    assert np.array_equal(policy.dfa_table_of_row[:3], [0, 0, 0])
    # expanded view hands per-row tables to row-indexed consumers
    assert policy.dfa_tables_by_row.shape[0] == policy.dfa_table_of_row.shape[0]
    # and the deduped gather-lane scan still answers exactly (each request
    # judged against its OWN config — the encoder only resolves own attrs)
    params = pe.to_device(policy, lane="gather")
    docs = [{"request": {"url_path": "/api/v3/x"}},
            {"request": {"path": "/api/v2/z"}},
            {"request": {"headers": {"x-route": "/zzz"}}}]
    db = pack_batch(policy, encode_batch_py(policy, docs, [0, 1, 2],
                                            batch_pad=8))
    own, _ = pe.eval_batch_jit(params, db)
    assert bool(own[0])        # c0: url_path matches the shared DFA
    assert bool(own[1])        # c1: path matches through the SAME table
    assert not bool(own[2])    # c2: x-route does not match


# ---------------------------------------------------------------------------
# perf guard: dedup must beat the no-dedup path on a 90%-duplicate batch
# ---------------------------------------------------------------------------

@pytest.mark.perf_guard
def test_dedup_beats_full_evaluation_on_90pct_duplicates():
    """Device-work micro-bench: a 512-row batch with ~90% duplicates
    evaluates faster through dedup-evaluate-scatter (≤ 64 unique rows on
    the kernel) than shipping all 512 rows.  Min-of-runs on both sides to
    shed scheduler noise."""
    policy = compile_corpus(_corpus(24), members_k=4)
    params = pe.to_device(policy)
    rng = random.Random(5)
    uniques = [_doc(rng, n_roles=2) for _ in range(48)]
    docs = [rng.choice(uniques) for _ in range(512)]
    rows = [hash(id(d)) % policy.n_configs for d in docs]
    rows = [r % policy.n_configs for r in rows]
    db = pack_batch(policy, encode_batch_py(policy, docs, rows, batch_pad=512))
    n = len(docs)
    keys = batch_row_keys(db, n)
    unique_rows, inverse = dedup_rows(keys, list(range(n)))
    u = len(unique_rows)
    assert u <= 64, f"workload not duplicate-heavy enough: {u} unique"
    from authorino_tpu.utils import bucket_pow2

    db_u = select_rows(db, unique_rows, batch_pad=bucket_pow2(u))

    # warm both jit variants off the clock
    np.asarray(_packed(params, db, bitpack=True))
    np.asarray(_packed(params, db_u, bitpack=True))

    def best_of(fn, runs=5):
        best = float("inf")
        for _ in range(runs):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return best

    t_full = best_of(lambda: np.asarray(
        _packed(params, db, bitpack=True)))
    t_dedup = best_of(lambda: (
        np.asarray(_packed(params, db_u, bitpack=True))[inverse]))
    assert t_dedup < t_full, (
        f"dedup path ({t_dedup * 1e3:.2f}ms, {u} rows) not faster than "
        f"full evaluation ({t_full * 1e3:.2f}ms, {n} rows)")


# ---------------------------------------------------------------------------
# packed-bitmask helpers
# ---------------------------------------------------------------------------

def test_packed_width():
    assert pe.packed_width(1) == 1
    assert pe.packed_width(8) == 1
    assert pe.packed_width(9) == 2
    assert pe.packed_width(17) == 3


def test_unpack_verdicts_known_bytes():
    packed = np.array([[0b00000111, 0b00000001]], dtype=np.uint8)
    got = pe.unpack_verdicts(packed, 9)
    assert got.shape == (1, 9)
    assert got[0].tolist() == [True, True, True, False, False,
                               False, False, False, True]
