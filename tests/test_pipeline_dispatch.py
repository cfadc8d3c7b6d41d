"""Pipelined async device dispatch (ISSUE 2): the engine's three-stage
pipeline (encode → non-blocking dispatch window → completion) must overlap
micro-batches on the device link, resolve them FIFO-independently, dispatch
immediately at light load (no max_delay_s stacking), stay correct across
snapshot swaps with batches in flight, and leak no per-loop state.

Deliberately import-light: collects on images without `cryptography`
(no evaluators.identity / native_frontend imports)."""

from __future__ import annotations

import asyncio
import threading
import time

import numpy as np
import pytest

from authorino_tpu.compiler import ConfigRules
from authorino_tpu.expressions import Operator, Pattern
from authorino_tpu.runtime import EngineEntry, PolicyEngine
from authorino_tpu.runtime import engine as engine_mod


def run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


def sample(name, labels=None):
    from prometheus_client import REGISTRY

    v = REGISTRY.get_sample_value(name, labels or {})
    return 0.0 if v is None else v


RULE_ACME = Pattern("auth.identity.org", Operator.EQ, "acme")
RULE_EVIL = Pattern("auth.identity.org", Operator.EQ, "evil")


def build_engine(rule=RULE_ACME, **kw) -> PolicyEngine:
    kw.setdefault("max_batch", 8)
    engine = PolicyEngine(members_k=4, mesh=None, **kw)
    engine.apply_snapshot([
        EngineEntry(id="c", hosts=["c"], runtime=None,
                    rules=ConfigRules(name="c", evaluators=[(None, rule)]))
    ])
    return engine


def doc(org="acme"):
    return {"auth": {"identity": {"org": org}}}


class FakeHandle:
    """Stub device result: ready when its event is set (or after a fixed
    deadline), numpy-materializable like a jax.Array."""

    def __init__(self, ready_at: float = None):
        self.evt = threading.Event()
        self.ready_at = ready_at

    def is_ready(self) -> bool:
        if self.ready_at is not None:
            return time.monotonic() >= self.ready_at
        return self.evt.is_set()

    def __array__(self, dtype=None):
        return np.zeros((1, 1))


class StubDevice:
    """Replaces PolicyEngine._encode_and_launch with a stub whose batches
    complete only when released — models a device behind a long link and
    records launch/in-flight bookkeeping for assertions."""

    def __init__(self, engine, latency_s: float = None, allow=True):
        self.engine = engine
        self.latency_s = latency_s
        self.allow = allow
        self.launches = []          # [(FakeHandle, [config names])]
        self.lock = threading.Lock()
        self.concurrent = 0
        self.peak = 0
        engine._encode_and_launch = self._launch

    def _launch(self, snap, batch):
        n = len(batch)
        handle = FakeHandle(
            None if self.latency_s is None
            else time.monotonic() + self.latency_s)
        with self.lock:
            self.concurrent += 1
            self.peak = max(self.peak, self.concurrent)
            self.launches.append((handle, [p.config_name for p in batch]))
        binfo = {"batch_size": n, "pad": n, "eff": 0,
                 "start_ns": time.time_ns(), "duration_s": 0.0}

        def finalize(packed):
            with self.lock:
                self.concurrent -= 1
            rule = np.full((n, 1), self.allow, dtype=bool)
            return rule, np.zeros((n, 1), dtype=bool), None

        return engine_mod._Inflight(self.engine, batch, handle, finalize,
                                    binfo, np.zeros(n))


async def wait_until(cond, timeout=5.0, interval=0.005):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        await asyncio.sleep(interval)
    return False


def wait_until_sync(cond, timeout=5.0, interval=0.005):
    """Futures resolve before the completer's own bookkeeping (gauge set,
    stage observe, slot release) — poll briefly instead of racing it."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return cond()


# ---------------------------------------------------------------------------
# tentpole: overlap + FIFO-independent completion
# ---------------------------------------------------------------------------

def test_three_batches_in_flight_and_fifo_independent_resolution():
    """≥3 micro-batches concurrently in flight against a sleeping stub
    device, and a later batch's futures resolve while earlier launches are
    still on the wire (completion is arrival-ordered, not launch-ordered)."""
    engine = build_engine(max_batch=4, max_inflight_batches=8)
    dev = StubDevice(engine)

    async def body():
        tasks = [asyncio.ensure_future(engine.submit(doc(), "c"))
                 for _ in range(12)]
        assert await wait_until(lambda: len(dev.launches) == 3)
        # all three launched, none resolved: true concurrent in-flight
        assert dev.concurrent == 3
        assert engine._inflight == 3
        assert not any(t.done() for t in tasks)
        # release the LAST launch first: its 4 futures must resolve while
        # launches 0 and 1 are still in flight
        dev.launches[2][0].evt.set()
        late = await asyncio.wait_for(asyncio.gather(*tasks[8:]), timeout=5)
        assert all(bool(r[0]) for r, _ in late)
        assert not any(t.done() for t in tasks[:8])
        assert dev.concurrent == 2
        dev.launches[0][0].evt.set()
        dev.launches[1][0].evt.set()
        early = await asyncio.wait_for(asyncio.gather(*tasks[:8]), timeout=5)
        assert all(bool(r[0]) for r, _ in early)

    run(body())
    assert dev.peak >= 3
    assert engine.inflight_peak >= 3
    assert wait_until_sync(lambda: engine._inflight == 0)


def test_window_bounds_inflight_as_counter():
    """The dispatch window is a hard bound: with max_inflight_batches=2 and
    6 batches worth of queued requests, exactly 2 launch; each completion
    admits the next (completion-driven flushing)."""
    engine = build_engine(max_batch=2, max_inflight_batches=2)
    dev = StubDevice(engine)

    async def body():
        tasks = [asyncio.ensure_future(engine.submit(doc(), "c"))
                 for _ in range(12)]
        assert await wait_until(lambda: len(dev.launches) == 2)
        await asyncio.sleep(0.05)  # window full: no further launches
        assert len(dev.launches) == 2
        assert engine._inflight == 2
        assert len(engine._queue) == 8
        dev.launches[0][0].evt.set()  # one slot frees → one more batch cuts
        assert await wait_until(lambda: len(dev.launches) == 3)
        for h, _ in dev.launches:
            h.evt.set()
        while not all(t.done() for t in tasks):
            for h, _ in dev.launches:  # release every follow-on launch
                h.evt.set()
            await asyncio.sleep(0.005)
        return await asyncio.gather(*tasks)

    outs = run(body())
    assert len(outs) == 12
    assert dev.peak == 2
    assert engine.inflight_peak <= 2


def test_light_load_dispatches_without_waiting_max_delay():
    """A lone request with an open window dispatches immediately — its
    latency must not include max_delay_s (set absurdly high here)."""
    engine = build_engine()

    async def warm():
        return await engine.submit(doc(), "c")

    run(warm())  # XLA compile outside the timed window
    engine.max_delay_s = 30.0

    async def body():
        t0 = time.monotonic()
        rule, skipped = await asyncio.wait_for(engine.submit(doc(), "c"),
                                               timeout=5.0)
        return time.monotonic() - t0, rule

    elapsed, rule = run(body())
    assert bool(rule[0])
    assert elapsed < 2.0, f"light-load submit stacked a delay: {elapsed:.3f}s"


@pytest.mark.perf_guard
def test_dispatch_path_issues_no_blocking_readback():
    """Micro-benchmark guard against re-serialization: 4 batches with a
    stubbed 0.3s device latency must complete in ~one latency (pipelined),
    not four (a blocking readback anywhere on the dispatch path would
    serialize them)."""
    engine = build_engine(max_batch=4, max_inflight_batches=8)
    dev = StubDevice(engine, latency_s=0.3)

    async def body():
        t0 = time.monotonic()
        outs = await asyncio.gather(*(engine.submit(doc(), "c")
                                      for _ in range(16)))
        return time.monotonic() - t0, outs

    wall, outs = run(body())
    assert len(outs) == 16
    assert len(dev.launches) == 4
    # serial would be ≥ 1.2s; pipelined is one latency + slack for a noisy
    # 1-core host
    assert wall < 0.9, f"batches serialized: wall={wall:.3f}s for 4×0.3s"


# ---------------------------------------------------------------------------
# satellite: snapshot-swap safety with >1 batch in flight
# ---------------------------------------------------------------------------

def test_inflight_batches_survive_snapshot_swap():
    """Batches launched against generation G resolve with G's verdicts
    while apply_snapshot swaps to G+1 (double-buffer guarantee, now with
    the completion deferred past the swap)."""
    # lane selection OFF: this test gates DEVICE launches, and with the
    # cost model live the small warm-RTT cuts would ride the host lane
    # (host/device swap parity is pinned in tests/test_lane_select.py)
    engine = build_engine(rule=RULE_ACME, max_batch=4, lane_select=False)
    run(engine.submit(doc(), "c"))  # warm both jit caches
    gate = threading.Event()
    real = PolicyEngine._encode_and_launch

    class GatedHandle:
        def __init__(self, inner):
            self.inner = inner

        def is_ready(self):
            return gate.is_set() and (
                not hasattr(self.inner, "is_ready") or self.inner.is_ready())

        def __array__(self, dtype=None):
            return np.asarray(self.inner)

    gated_launches = []

    def gated(snap, batch):
        item = real(engine, snap, batch)
        item.handle = GatedHandle(item.handle)
        gated_launches.append(item)
        return item

    engine._encode_and_launch = gated

    async def body():
        # two gated batches launch against G (acme allowed).  Wait for the
        # LAUNCHES, not the window counter: the counter increments at batch
        # cut, before the encode worker runs the (gated) launch
        pre = [asyncio.ensure_future(engine.submit(doc("acme"), "c"))
               for _ in range(8)]
        assert await wait_until(lambda: len(gated_launches) >= 2)
        gen_before = engine.generation
        # swap to G+1 (evil allowed, acme denied) while G's batches fly
        engine._encode_and_launch = real.__get__(engine, PolicyEngine)
        engine.apply_snapshot([
            EngineEntry(id="c", hosts=["c"], runtime=None,
                        rules=ConfigRules(name="c",
                                          evaluators=[(None, RULE_EVIL)]))
        ])
        assert engine.generation == gen_before + 1
        post = await asyncio.gather(*(engine.submit(doc("acme"), "c")
                                      for _ in range(4)))
        assert not any(bool(r[0]) for r, _ in post)  # G+1: acme denied
        assert not any(t.done() for t in pre)        # G still in flight
        gate.set()
        outs = await asyncio.wait_for(asyncio.gather(*pre), timeout=10)
        # G's semantics: acme allowed, even though G+1 now serves
        assert all(bool(r[0]) for r, _ in outs)

    run(body())


# ---------------------------------------------------------------------------
# satellite: no per-loop dispatcher state; closed loops are harmless
# ---------------------------------------------------------------------------

def test_no_per_loop_state_accumulates():
    """The old per-loop _pending/_flush_handles dicts leaked an entry per
    event loop; the global dispatcher holds no loop-keyed state at all."""
    engine = build_engine()

    async def three():
        return await asyncio.gather(*(engine.submit(doc(), "c")
                                      for _ in range(3)))

    for _ in range(6):
        loop = asyncio.new_event_loop()
        try:
            outs = loop.run_until_complete(three())
        finally:
            loop.close()
        assert all(bool(r[0]) for r, _ in outs)
    assert not hasattr(engine, "_pending")
    assert not hasattr(engine, "_flush_handles")
    assert len(engine._queue) == 0
    assert wait_until_sync(lambda: engine._inflight == 0)
    assert engine.debug_vars()["queue_depth"] == 0


def test_loop_closed_before_completion_is_survivable():
    """A loop that dies with requests in flight must not wedge the shared
    completer: its futures are moot, the window slot frees, and fresh loops
    keep being served."""
    engine = build_engine(max_batch=2)
    dev = StubDevice(engine)

    async def launch_and_abandon():
        asyncio.ensure_future(engine.submit(doc(), "c"))
        assert await wait_until(lambda: engine._inflight >= 1)

    loop = asyncio.new_event_loop()
    try:
        loop.run_until_complete(launch_and_abandon())
    finally:
        loop.close()  # the in-flight batch's owning loop is now gone
    for h, _ in dev.launches:
        h.evt.set()
    deadline = time.monotonic() + 5
    while engine._inflight and time.monotonic() < deadline:
        time.sleep(0.005)
    assert engine._inflight == 0  # slot freed despite the dead loop
    # the engine still serves new loops afterwards
    del engine._encode_and_launch  # restore the real bound method
    outs = run(engine.submit(doc(), "c"))
    assert bool(outs[0][0])


def test_batch_error_propagates_to_every_future():
    engine = build_engine()
    with pytest.raises(Exception):
        run(engine.submit(doc(), "no-such-config"))
    assert wait_until_sync(lambda: engine._inflight == 0)


# ---------------------------------------------------------------------------
# satellite: per-request queue waits + inflight gauge on /metrics
# ---------------------------------------------------------------------------

def test_queue_wait_histogram_counts_every_request():
    """The queue-wait histogram must record TRUE per-request waits (one
    count per request), not just batch[0]'s."""
    engine = build_engine(max_batch=8)
    before = sample("auth_server_batch_queue_wait_seconds_count",
                    {"lane": "engine"})

    async def many():
        return await asyncio.gather(*(engine.submit(doc(), "c")
                                      for _ in range(24)))

    run(many())
    after = sample("auth_server_batch_queue_wait_seconds_count",
                   {"lane": "engine"})
    assert after >= before + 24, (before, after)


def test_inflight_gauge_and_pipeline_stages_exported():
    engine = build_engine()

    async def many():
        return await asyncio.gather(*(engine.submit(doc(), "c")
                                      for _ in range(8)))

    run(many())
    # gauge exists (0 once drained) and every pipeline stage recorded
    assert wait_until_sync(lambda: engine._inflight == 0)
    assert sample("auth_server_inflight_batches", {"lane": "engine"}) == 0.0
    for stage in ("encode", "launch", "device", "resolve"):
        assert wait_until_sync(lambda: sample(
            "auth_server_pipeline_stage_seconds_count",
            {"lane": "engine", "stage": stage}) > 0), stage
    dv = engine.debug_vars()
    assert dv["inflight_batches"] == 0
    assert dv["inflight_peak"] >= 1
    assert dv["max_inflight_batches"] == engine.max_inflight_batches


# ---------------------------------------------------------------------------
# satellite: fused H2D staging is bit-exact vs per-operand transfers
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("corpus", ["one-rule", "all-operand-lanes"])
def test_fused_h2d_staging_matches_per_operand_path(corpus):
    import random

    import jax.numpy as jnp

    from authorino_tpu.compiler.compile import compile_corpus
    from authorino_tpu.compiler.encode import encode_batch
    from authorino_tpu.compiler.pack import pack_batch
    from authorino_tpu.expressions import All, Any_
    from authorino_tpu.ops.pattern_eval import (
        _FUSED_FIELDS,
        eval_fused_jit,
        eval_packed_jit,
        fuse_batch,
        fused_h2d_supported,
        to_device,
        unpack_verdicts,
    )
    from test_own_config_eval import _operands

    assert fused_h2d_supported()  # little-endian bitcast probe
    if corpus == "one-rule":
        rule = All(
            Pattern("request.method", Operator.EQ, "GET"),
            Any_(Pattern("auth.identity.roles", Operator.INCL, "admin"),
                 Pattern("request.url_path", Operator.MATCHES, r"^/api/v\d+")),
        )
        policy = compile_corpus(
            [ConfigRules(name="c", evaluators=[(None, rule)])], members_k=4)
        docs = [
            {"request": {"method": "GET", "url_path": "/api/v1"},
             "auth": {"identity": {"roles": ["admin"]}}},
            {"request": {"method": "POST", "url_path": "/nope"},
             "auth": {"identity": {"roles": ["dev"]}}},
        ] * 6
        rows = [0] * len(docs)
    else:
        from test_own_config_eval import all_operand_corpus, all_operand_docs

        rng = random.Random(3)
        policy = compile_corpus(all_operand_corpus(rng), members_k=4,
                                ovf_assist=True)
        docs = all_operand_docs(rng, n=12)
        rows = [rng.randrange(policy.n_configs) for _ in docs]
    params = to_device(policy)
    enc = encode_batch(policy, docs, rows, batch_pad=16)
    db = pack_batch(policy, enc)
    reference = np.asarray(eval_packed_jit(params, *_operands(db)))
    assert reference[:len(docs), 0].any() and not reference[:len(docs), 0].all()
    buf, layout = fuse_batch(db)
    assert buf.dtype == np.uint8 and buf.ndim == 1  # ONE staging buffer
    if corpus == "all-operand-lanes":
        # every operand the staging buffer can carry is in it
        assert tuple(f[0] for f in layout) == _FUSED_FIELDS
    fused = np.asarray(eval_fused_jit(params, jnp.asarray(buf), layout))
    # the fused readback is the BIT-PACKED u8 bitmask (8 verdicts/byte);
    # decoding it must reproduce the per-operand bool result exactly
    assert fused.dtype == np.uint8
    E = int(policy.eval_rule.shape[1])
    assert fused.shape[1] == (1 + 2 * E + 7) // 8
    assert np.array_equal(reference, unpack_verdicts(fused, 1 + 2 * E))


def test_engine_dispatch_refuses_a_failed_probe(monkeypatch):
    """Where the backend's byte order fails the one-time probe the engine
    lane's launch raises, naming it, and no entry runs: a staged buffer
    would decode to wrong operands, and no other launch format serves."""
    from authorino_tpu.compiler.compile import compile_corpus
    from authorino_tpu.compiler.encode import encode_batch
    from authorino_tpu.compiler.pack import pack_batch
    from authorino_tpu.ops import pattern_eval as pe

    policy = compile_corpus([ConfigRules(name="c", evaluators=[(None, Pattern(
        "request.method", Operator.EQ, "GET"))])], members_k=4)
    params = pe.to_device(policy)
    db = pack_batch(policy, encode_batch(
        policy, [{"request": {"method": m}} for m in ("GET", "POST")], [0, 0],
        batch_pad=16))
    ran = []
    for name in ("eval_fused_jit", "eval_packed_jit", "eval_bitpacked_jit"):
        real = getattr(pe, name)
        monkeypatch.setattr(pe, name, lambda *a, _f=real, _n=name, **k: (
            ran.append(_n), _f(*a, **k))[1])
    with monkeypatch.context() as m:
        m.setattr(pe, "_FUSED_OK", False)
        with pytest.raises(RuntimeError, match="byte order"):
            pe.dispatch_fused(params, db)
    assert ran == []
    got = pe.unpack_verdicts(np.asarray(pe.dispatch_fused(params, db)),
                             1 + 2 * int(policy.eval_rule.shape[1]))
    # the staged entry, whose body (traced once) runs eval_packed_jit
    assert ran[0] == "eval_fused_jit" and "eval_bitpacked_jit" not in ran
    assert got[:2, 0].tolist() == [True, False]
