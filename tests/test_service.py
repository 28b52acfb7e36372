"""Async codec service tests: batching, SLOs, cache, fault injection.

Most tests drive :class:`repro.serve.service.CodecService` with the
cheap deterministic :class:`helpers.flaky.EchoEngine` (digest bytes, no
codec) so they exercise the asyncio dispatch machinery, not the
encoder; a couple of end-to-end tests pin the real-engine contract
(service bytes == serial ``encode_batch`` bytes).  The fault-injection
half wraps engines in :class:`helpers.flaky.FlakyEngine` and asserts
the service degrades gracefully: engine failures fail only their own
batch, slow engines surface as ``deadline_missed`` (never as silent
drops), backpressure rejects carry machine-readable reasons, and the
dispatch loop survives all of it.
"""

import asyncio

import numpy as np
import pytest
from helpers.flaky import EchoEngine, FlakyEngine, InjectedEngineError

from repro.serve import admission
from repro.serve.admission import RejectedError, TenantTier
from repro.serve.service import (CodecService, EngineFailure, Response,
                                 ServiceConfig, StreamCache)


def run(coro):
    return asyncio.run(coro)


def make_images(n, shape=(48, 48), seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, shape, dtype=np.uint8) for _ in range(n)]


def fast_config(**kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_wait_s", 0.002)
    kw.setdefault("max_queue_depth", 16)
    kw.setdefault("initial_step_s", 0.001)
    return ServiceConfig(**kw)


# ---------------------------------------------------------------------------
# lifecycle
# ---------------------------------------------------------------------------

def test_submit_before_start_raises():
    async def go():
        svc = CodecService(fast_config(), engine=EchoEngine())
        with pytest.raises(RuntimeError, match="not started"):
            await svc.submit(make_images(1)[0])
    run(go())


def test_submit_after_close_rejects_shutdown():
    async def go():
        svc = CodecService(fast_config(), engine=EchoEngine())
        async with svc:
            pass
        with pytest.raises(RejectedError) as ei:
            await svc.submit(make_images(1)[0])
        assert ei.value.reason == admission.SHUTDOWN
    run(go())


def test_close_is_idempotent_and_start_after_close_fails():
    async def go():
        svc = CodecService(fast_config(), engine=EchoEngine())
        await svc.start()
        await svc.close()
        await svc.close()
        with pytest.raises(RuntimeError, match="closed"):
            await svc.start()
    run(go())


def test_close_drains_queued_requests():
    async def go():
        # timer never fires, bucket never fills: requests only leave the
        # queue because close() drains them
        cfg = fast_config(max_batch=8, max_wait_s=30.0)
        svc = CodecService(cfg, engine=EchoEngine())
        await svc.start()
        imgs = make_images(3)
        tasks = [asyncio.ensure_future(svc.submit(im)) for im in imgs]
        await asyncio.sleep(0)
        assert svc.queue_depth() == 3
        await svc.close()
        resps = await asyncio.gather(*tasks)
        assert all(isinstance(r, Response) for r in resps)
        assert svc.stats.served == 3
    run(go())


def test_invalid_image_shape_raises_valueerror():
    async def go():
        async with CodecService(fast_config(),
                                engine=EchoEngine()) as svc:
            # (H, W, 3) is a colour image now; two channels are not
            with pytest.raises(ValueError, match="2-D"):
                await svc.submit(np.zeros((4, 4, 2), dtype=np.uint8))
            # validation errors are caller bugs, not requests: they must
            # not count as submitted, or the conservation invariant
            # submitted == served + rejected + failed would break
            assert svc.stats.submitted == 0
    run(go())


def test_close_with_inflight_batch_terminates():
    async def go():
        # regression: the dispatcher rebound its in-flight set each
        # iteration while done-callbacks discarded from the *old* set
        # object, so a batch still running when close() triggered the
        # drain iteration stayed "in flight" forever and close() hung
        eng = EchoEngine(step_s=0.1)
        svc = CodecService(fast_config(max_batch=1), engine=eng)
        await svc.start()
        task = asyncio.ensure_future(svc.submit(make_images(1)[0]))
        while not eng.calls:            # batch dispatched, engine busy
            await asyncio.sleep(0.001)
        await asyncio.wait_for(svc.close(), timeout=10.0)
        resp = await task
        assert isinstance(resp, Response)
        assert svc.stats.served == 1
    run(go())


# ---------------------------------------------------------------------------
# batching behaviour
# ---------------------------------------------------------------------------

def test_concurrent_submits_share_engine_batches():
    async def go():
        engine = EchoEngine()
        cfg = fast_config(max_batch=4, max_wait_s=0.05)
        async with CodecService(cfg, engine=engine) as svc:
            resps = await asyncio.gather(
                *[svc.submit(im) for im in make_images(8)])
        assert [n for n, _ in engine.calls] == [4, 4]
        assert {r.batch_size for r in resps} == {4}
        assert svc.stats.occupancy == {4: 2}
    run(go())


def test_mixed_shapes_and_qualities_bucket_separately():
    async def go():
        engine = EchoEngine()
        async with CodecService(fast_config(max_wait_s=0.05),
                                engine=engine) as svc:
            a = make_images(2, shape=(48, 48), seed=1)
            b = make_images(2, shape=(130, 40), seed=2)
            resps = await asyncio.gather(
                *[svc.submit(im, quality=50) for im in a],
                *[svc.submit(im, quality=50) for im in b],
                svc.submit(a[0] + 1, quality=75))
        # three buckets: (64,64)@50, (192,64)@50, (64,64)@75
        assert sorted(engine.calls) == [(1, 75), (2, 50), (2, 50)]
        assert all(r.payload for r in resps)
    run(go())


def test_lone_request_dispatches_on_timer():
    async def go():
        engine = EchoEngine()
        cfg = fast_config(max_batch=8, max_wait_s=0.005)
        async with CodecService(cfg, engine=engine) as svc:
            resp = await svc.submit(make_images(1)[0])
        assert resp.batch_size == 1
        assert engine.calls == [(1, 50)]
    run(go())


def test_response_metadata_fields():
    async def go():
        async with CodecService(fast_config(),
                                engine=EchoEngine()) as svc:
            resp = await svc.submit(make_images(1)[0], quality=30)
        assert resp.quality == 30
        assert resp.batch_size == 1
        assert resp.req_id >= 0
        assert resp.latency_s >= 0.0
        assert not resp.cache_hit and not resp.deadline_missed
    run(go())


def test_bytes_are_engine_output():
    async def go():
        engine = EchoEngine()
        imgs = make_images(3, seed=3)
        async with CodecService(fast_config(), engine=engine) as svc:
            resps = await asyncio.gather(*[svc.submit(im) for im in imgs])
        assert [r.payload for r in resps] == engine(imgs, 50)
    run(go())


# ---------------------------------------------------------------------------
# tenant tiers
# ---------------------------------------------------------------------------

def test_tenant_tier_clamps_quality_and_buckets_at_clamped_value():
    async def go():
        engine = EchoEngine()
        cfg = fast_config(tenants={"free": TenantTier(max_quality=40)})
        async with CodecService(cfg, engine=engine) as svc:
            r = await svc.submit(make_images(1)[0], quality=90,
                                 tenant="free")
        assert r.quality == 40
        assert engine.calls == [(1, 40)]
    run(go())


def test_unknown_tenant_uses_default_tier():
    async def go():
        cfg = fast_config(default_tier=TenantTier(max_quality=60))
        async with CodecService(cfg, engine=EchoEngine()) as svc:
            r = await svc.submit(make_images(1)[0], quality=90,
                                 tenant="nobody")
        assert r.quality == 60
    run(go())


def test_tenant_tier_relaxes_deadline():
    async def go():
        # the tier's deadline floor (1s) overrides the hopeless 1ns ask,
        # so the request is admitted and served instead of rejected
        cfg = fast_config(tenants={"lenient":
                                   TenantTier(min_deadline_s=1.0)})
        async with CodecService(cfg, engine=EchoEngine()) as svc:
            r = await svc.submit(make_images(1)[0], tenant="lenient",
                                 deadline_s=1e-9)
        assert not r.deadline_missed
    run(go())


# ---------------------------------------------------------------------------
# hot-stream cache
# ---------------------------------------------------------------------------

def test_cache_hit_serves_identical_payload_without_engine_call():
    async def go():
        engine = EchoEngine()
        img = make_images(1)[0]
        async with CodecService(fast_config(), engine=engine) as svc:
            r1 = await svc.submit(img)
            r2 = await svc.submit(img)
        assert not r1.cache_hit and r2.cache_hit
        assert r2.payload == r1.payload
        assert r2.batch_size == 0
        assert len(engine.calls) == 1
        assert svc.cache.hits == 1
    run(go())


def test_cache_misses_on_quality_change():
    async def go():
        engine = EchoEngine()
        img = make_images(1)[0]
        async with CodecService(fast_config(), engine=engine) as svc:
            await svc.submit(img, quality=50)
            r = await svc.submit(img, quality=75)
        assert not r.cache_hit
        assert len(engine.calls) == 2
    run(go())


def test_cache_disabled_with_zero_entries():
    async def go():
        engine = EchoEngine()
        img = make_images(1)[0]
        cfg = fast_config(cache_entries=0)
        async with CodecService(cfg, engine=engine) as svc:
            await svc.submit(img)
            r = await svc.submit(img)
        assert not r.cache_hit
        assert len(engine.calls) == 2
    run(go())


def test_stream_cache_lru_eviction():
    c = StreamCache(entries=2)
    c.put(("a", 50, "auto"), b"A")
    c.put(("b", 50, "auto"), b"B")
    assert c.get(("a", 50, "auto")) == b"A"     # refreshes "a"
    c.put(("c", 50, "auto"), b"C")              # evicts "b"
    assert c.get(("b", 50, "auto")) is None
    assert c.get(("a", 50, "auto")) == b"A"
    assert len(c) == 2


def test_stream_cache_key_separates_content_quality_tables():
    img = make_images(1)[0]
    k = StreamCache.key(img, 50, "auto")
    assert StreamCache.key(img.copy(), 50, "auto") == k
    assert StreamCache.key(img, 75, "auto") != k
    assert StreamCache.key(img, 50, "embedded") != k
    other = img.copy()
    other[0, 0] ^= 0xFF
    assert StreamCache.key(other, 50, "auto") != k


# ---------------------------------------------------------------------------
# backpressure and deadlines
# ---------------------------------------------------------------------------

def test_queue_full_rejects_with_reason():
    async def go():
        # all submits admit before the dispatcher's next poll, so the
        # third hits the depth bound deterministically
        cfg = fast_config(max_batch=2, max_queue_depth=2,
                          max_wait_s=30.0)
        async with CodecService(cfg, engine=EchoEngine()) as svc:
            out = await asyncio.gather(
                *[svc.submit(im) for im in make_images(3)],
                return_exceptions=True)
        rejects = [r for r in out if isinstance(r, RejectedError)]
        served = [r for r in out if isinstance(r, Response)]
        assert len(rejects) == 1 and len(served) == 2
        assert rejects[0].reason == admission.QUEUE_FULL
        assert svc.stats.rejected == {admission.QUEUE_FULL: 1}
    run(go())


def test_hopeless_deadline_rejected_at_admission():
    async def go():
        cfg = fast_config(initial_step_s=0.050)
        async with CodecService(cfg, engine=EchoEngine()) as svc:
            with pytest.raises(RejectedError) as ei:
                await svc.submit(make_images(1)[0], deadline_s=1e-6)
        assert ei.value.reason == admission.DEADLINE_UNMEETABLE
        assert svc.stats.total_rejected == 1
    run(go())


def test_slow_engine_marks_deadline_missed_not_dropped():
    async def go():
        # the deadline leaves the dispatcher 100 ms to hand the request
        # to the engine (a loaded test host can stall the event loop
        # for tens of ms, and a request still queued at its deadline is
        # swept as a reject); the engine then takes 300 ms, so the
        # dispatched request must finish late and still be served
        engine = FlakyEngine(EchoEngine(), latency_s=0.3)
        cfg = fast_config(initial_step_s=1e-4)
        async with CodecService(cfg, engine=engine) as svc:
            r = await svc.submit(make_images(1)[0], deadline_s=0.1)
        assert isinstance(r, Response)
        assert r.deadline_missed
        assert svc.stats.deadline_missed == 1
        assert svc.stats.served == 1
    run(go())


def test_queued_request_behind_slow_batch_is_swept_not_dispatched():
    async def go():
        # a full batch holds the engine for 50ms and teaches the
        # bucket's EWMA that steps are slow; the request queued behind
        # it has a deadline the learned step rules out (completion +
        # step > deadline), so the batch-completion wake must sweep it
        # as a reject rather than dispatch it to miss its SLO
        engine = FlakyEngine(EchoEngine(), latency_s=0.05,
                             slow_calls={0})
        cfg = fast_config(max_batch=2, max_wait_s=30.0,
                          initial_step_s=1e-4)
        async with CodecService(cfg, engine=engine) as svc:
            imgs = make_images(3)
            batch1 = [asyncio.ensure_future(svc.submit(im))
                      for im in imgs[:2]]        # fills the bucket
            await asyncio.sleep(0.01)            # batch 1 now in flight
            straggler = asyncio.ensure_future(
                svc.submit(imgs[2], deadline_s=0.07))
            out = await asyncio.gather(*batch1, straggler,
                                       return_exceptions=True)
        assert all(isinstance(r, Response) for r in out[:2])
        assert isinstance(out[2], RejectedError)
        assert out[2].reason == admission.DEADLINE_UNMEETABLE
        assert len(engine.calls) == 1       # straggler never encoded
    run(go())


# ---------------------------------------------------------------------------
# fault injection
# ---------------------------------------------------------------------------

def test_engine_failure_fails_only_its_batch():
    async def go():
        engine = FlakyEngine(EchoEngine(), fail_calls={0})
        cfg = fast_config(max_batch=2, max_wait_s=0.05)
        async with CodecService(cfg, engine=engine) as svc:
            first = await asyncio.gather(
                *[svc.submit(im) for im in make_images(2, seed=1)],
                return_exceptions=True)
            second = await asyncio.gather(
                *[svc.submit(im) for im in make_images(2, seed=2)])
        assert all(isinstance(r, EngineFailure) for r in first)
        assert all(isinstance(r.__cause__, InjectedEngineError)
                   for r in first)
        assert all(isinstance(r, Response) for r in second)
        assert svc.stats.engine_failures == 1
        assert svc.stats.failed == 2
        assert svc.stats.served == 2
    run(go())


def test_engine_short_return_is_a_batch_failure():
    async def go():
        engine = FlakyEngine(EchoEngine(), short_return_calls={0})
        cfg = fast_config(max_batch=2, max_wait_s=0.05)
        async with CodecService(cfg, engine=engine) as svc:
            out = await asyncio.gather(
                *[svc.submit(im) for im in make_images(2)],
                return_exceptions=True)
        assert all(isinstance(r, EngineFailure) for r in out)
    run(go())


def test_dispatch_loop_survives_repeated_engine_failures():
    async def go():
        engine = FlakyEngine(EchoEngine(), fail_calls={0, 1, 2})
        async with CodecService(fast_config(), engine=engine) as svc:
            for i in range(3):
                with pytest.raises(EngineFailure):
                    await svc.submit(make_images(1, seed=i)[0])
            r = await svc.submit(make_images(1, seed=99)[0])
        assert isinstance(r, Response)
        assert svc.stats.engine_failures == 3
    run(go())


def test_every_submit_reaches_exactly_one_terminal_outcome_under_faults():
    async def go():
        engine = FlakyEngine(EchoEngine(), fail_rate=0.3, seed=7)
        cfg = fast_config(max_batch=3, max_queue_depth=6,
                          max_wait_s=0.005)
        n = 24
        rng = np.random.default_rng(5)
        async with CodecService(cfg, engine=engine) as svc:
            async def one(i):
                img = make_images(1, seed=i)[0]
                dl = None if rng.random() < 0.5 else 0.5
                return await svc.submit(img, deadline_s=dl)
            out = await asyncio.gather(*[one(i) for i in range(n)],
                                       return_exceptions=True)
        served = sum(isinstance(r, Response) for r in out)
        failed = sum(isinstance(r, EngineFailure) for r in out)
        rejected = sum(isinstance(r, RejectedError) for r in out)
        assert served + failed + rejected == n
        assert svc.stats.submitted == n
        assert svc.stats.served == served
        assert svc.stats.failed == failed
        assert svc.stats.total_rejected == rejected
        assert svc.queue_depth() == 0
    run(go())


def test_flaky_latency_only_on_selected_calls():
    engine = FlakyEngine(EchoEngine(), latency_s=0.05, slow_calls={1})
    imgs = make_images(1)
    import time
    t0 = time.monotonic()
    engine(imgs, 50)
    fast = time.monotonic() - t0
    t0 = time.monotonic()
    engine(imgs, 50)
    slow = time.monotonic() - t0
    assert fast < 0.02 < slow
    assert engine.calls == [(1, 50), (1, 50)]


def test_latency_reservoir_is_bounded():
    from repro.serve.service import ServiceStats
    stats = ServiceStats()
    for i in range(ServiceStats.LATENCY_WINDOW + 100):
        stats.latencies_s.append(float(i))
    assert len(stats.latencies_s) == ServiceStats.LATENCY_WINDOW
    # the window keeps the most recent samples
    assert stats.latency_percentile(100) == float(
        ServiceStats.LATENCY_WINDOW + 99)


def test_stats_snapshot_shape():
    async def go():
        async with CodecService(fast_config(),
                                engine=EchoEngine()) as svc:
            await svc.submit(make_images(1)[0])
        snap = svc.stats.snapshot()
        assert snap["submitted"] == snap["served"] == 1
        assert snap["occupancy"] == {"1": 1}
        assert snap["p50_latency_s"] >= 0.0
        assert set(snap) >= {"rejected", "failed", "engine_failures",
                             "deadline_missed", "p99_latency_s"}
    run(go())


# ---------------------------------------------------------------------------
# real engine end-to-end
# ---------------------------------------------------------------------------

def test_service_bytes_match_serial_encode_batch():
    codec_engine = pytest.importorskip("repro.serve.codec_engine")

    async def go(imgs):
        cfg = ServiceConfig(max_batch=4, max_wait_s=0.02)
        async with CodecService(cfg) as svc:
            return await asyncio.gather(*[svc.submit(im) for im in imgs])

    imgs = make_images(4, shape=(40, 56), seed=11)
    resps = run(go(imgs))
    serial = codec_engine.encode_batch(imgs, 50)
    assert [r.payload for r in resps] == serial


def test_service_payload_decodes_roundtrip():
    pytest.importorskip("repro.serve.codec_engine")
    from repro.core.entropy import container

    async def go(img):
        async with CodecService(ServiceConfig(max_batch=2,
                                              max_wait_s=0.02)) as svc:
            return await svc.submit(img, quality=75)

    img = make_images(1, shape=(33, 47), seed=12)[0]
    resp = run(go(img))
    decoded = container.decode_image(resp.payload)
    assert decoded.shape == img.shape
