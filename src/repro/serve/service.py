"""Async codec service: deadline-aware batching over the codec engine.

The serving front end the ROADMAP's "millions of users" story needs:
callers ``await service.submit(image, ...)`` and the service turns many
concurrent single-image requests into the batched engine calls
(:func:`repro.serve.codec_engine.encode_batch`) the hardware actually
wants, while holding per-request SLOs:

* requests queue per *(shape bucket, quality)* and dispatch when the
  bucket fills, when the oldest request's deadline (minus a safety
  multiple of the bucket's measured model-step EWMA) is about to
  expire, or on a ``max_wait_s`` batching timer
  (:class:`repro.serve.queueing.BatchPlanner`),
* bounded queues give explicit backpressure — an overloaded service
  raises :class:`repro.serve.admission.RejectedError` instead of
  accepting work it cannot finish, and queued requests whose deadline
  becomes unmeetable are rejected, never dispatched and never silently
  dropped,
* per-tenant :class:`repro.serve.admission.TenantTier` policies clamp
  quality (and relax too-tight deadlines) before admission,
* an LRU **hot-stream cache** keyed on ``(payload digest, quality,
  tables)`` serves repeated images without touching the engine —
  shared-table ``DCTZ`` streams are cheap to keep (no per-stream table
  segment),
* engine failures fail *only* the affected batch's requests (with
  :class:`EngineFailure`) and the dispatch loop keeps serving — the
  fault-injection suite drives this with a flaky engine wrapper,
* an optional **resilience envelope** (:mod:`repro.serve.resilience`,
  off by default) adds per-attempt engine timeouts, bounded
  budget-guarded retries with decorrelated-jitter backoff, a
  failure-rate circuit breaker over the engine path (typed
  :class:`~repro.serve.resilience.CircuitOpen` rejects while open),
  payload integrity validation, and graceful quality degradation under
  sustained queue pressure — all without breaking the one-terminal-
  outcome invariant (a retried request is still one submit).

The planner half is synchronous and jax-free
(:mod:`repro.serve.queueing`); this module adds the asyncio shell: one
dispatcher task multiplexing queue timers, engine batches running in a
(default single-worker) thread pool so the event loop never blocks on
device work, and per-request futures carrying exactly one terminal
outcome each.  See docs/serving.md for semantics and SLO knobs, and
``bench/cases.py::service_traffic`` / ``service_chaos`` for the
open-loop load tests that measure p50/p99 latency, goodput, reject
rate and fault-storm behaviour through this layer.
"""

from __future__ import annotations

import asyncio
import collections
import concurrent.futures
import dataclasses
import hashlib
import math
import random
import time

import numpy as np

from repro.serve import admission, queueing, resilience
from repro.serve.admission import RejectedError, ServiceClosed, TenantTier


class EngineFailure(RuntimeError):
    """The engine batch carrying this request raised; see ``__cause__``."""


class EngineTimeout(RuntimeError):
    """An engine attempt exceeded ``ResilienceConfig.timeout_s``.

    Used as the ``__cause__`` of the :class:`EngineFailure` a request
    sees when its timed-out attempt was its last; the abandoned worker
    thread keeps running until the engine returns (its result is
    discarded).
    """


class PayloadCorrupt(RuntimeError):
    """An engine-produced payload failed ``validate_payload``.

    Never served; used as the ``__cause__`` of the terminal
    :class:`EngineFailure` when retries are off or exhausted.
    """


# ---------------------------------------------------------------------------
# Configuration
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ServiceConfig:
    """SLO and batching knobs for :class:`CodecService`.

    Attributes:
        max_batch: engine batch size a bucket dispatches at.
        max_wait_s: batching timer — max time the oldest queued request
            waits for batchmates.
        max_queue_depth: per-bucket queue bound (backpressure).
        safety: EWMA multiple for deadline urgency/admission margins.
        initial_step_s: model-step estimate before any measurement.
        default_quality: quality when a request does not specify one.
        default_deadline_s: relative deadline applied when a request
            has none (None = requests without deadlines never expire).
        cache_entries: LRU hot-stream cache capacity (0 disables).
        transform: encoder transform for the default engine.
        tables: Huffman table policy for the default engine (also part
            of the cache key).
        tenants: tenant name -> :class:`TenantTier` policy map.
        default_tier: tier applied to unknown tenants.
        engine_concurrency: worker threads running engine batches (1 =
            strictly one model step at a time, the EWMA's assumption).
        max_inflight_batches: dispatched-but-unfinished batch cap.
            When the engine saturates, further requests stay queued —
            where the depth bound rejects and the deadline sweep sheds
            — instead of accumulating in an unbounded executor backlog
            that would serve everything late and reject nothing.
            Default 2: one batch encoding, one forming/waiting.
        shape_bucket: shape-bucket granularity (keep at the engine's
            :data:`repro.serve.codec_engine.SHAPE_BUCKET`).
        resilience: timeout/retry/breaker/degradation envelope
            (:class:`repro.serve.resilience.ResilienceConfig`); the
            default disables every mechanism, preserving the baseline
            service semantics exactly.
    """
    max_batch: int = 8
    max_wait_s: float = 0.010
    max_queue_depth: int = 64
    safety: float = 1.5
    initial_step_s: float = 0.050
    default_quality: int = 50
    default_deadline_s: float | None = None
    cache_entries: int = 256
    transform: str = "exact"
    tables: str = "auto"
    tenants: dict = dataclasses.field(default_factory=dict)
    default_tier: TenantTier = TenantTier()
    engine_concurrency: int = 1
    max_inflight_batches: int = 2
    shape_bucket: int = queueing.DEFAULT_SHAPE_BUCKET
    resilience: resilience.ResilienceConfig = dataclasses.field(
        default_factory=resilience.ResilienceConfig)

    def tier(self, tenant: str) -> TenantTier:
        """The tier serving ``tenant`` (unknown tenants get the default)."""
        return self.tenants.get(tenant, self.default_tier)


def default_engine(config: ServiceConfig):
    """The production engine callable: batched entropy-coded encode.

    Returns ``(images, quality) -> list[bytes]`` running
    :func:`repro.serve.codec_engine.encode_batch` under the service's
    transform/table policy.  Import is deferred so constructing a
    service with a stub engine (tests, property suites) never pays for
    jax.
    """
    from repro.serve import codec_engine

    def encode(images, quality: int):
        return codec_engine.encode_batch(
            list(images), quality, transform=config.transform,
            tables=config.tables)
    return encode


# ---------------------------------------------------------------------------
# Responses, cache, stats
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Response:
    """Terminal success outcome of one :meth:`CodecService.submit`.

    Attributes:
        payload: the entropy-coded ``DCTZ`` stream.
        quality: quality actually encoded at (post tenant tier).
        latency_s: admission-to-completion wall time.
        batch_size: engine batch the request rode in (0 = cache hit).
        cache_hit: served from the hot-stream cache.
        deadline_missed: completed, but after the request's deadline
            (counts against goodput, not against delivery).
        req_id: service-assigned id (-1 for cache hits, which never
            enter a queue).
        degraded: quality was downshifted by the graceful-degradation
            controller (``quality`` reflects what was actually served).
        attempts: engine attempts this request rode in (> 1 = retried;
            0 for cache hits).
    """
    payload: bytes
    quality: int
    latency_s: float
    batch_size: int
    cache_hit: bool = False
    deadline_missed: bool = False
    req_id: int = -1
    degraded: bool = False
    attempts: int = 1


class StreamCache:
    """LRU cache of encoded streams keyed ``(digest, quality, tables)``."""

    def __init__(self, entries: int):
        self.entries = entries
        self._data: collections.OrderedDict = collections.OrderedDict()
        self.hits = 0
        self.misses = 0

    @staticmethod
    def key(image: np.ndarray, quality: int, tables: str) -> tuple:
        """Cache key: content digest + the knobs that change the bytes."""
        h = hashlib.sha1(image.tobytes())
        h.update(repr((image.shape, str(image.dtype))).encode())
        return (h.hexdigest(), quality, tables)

    def get(self, key: tuple):
        if self.entries <= 0:
            return None
        blob = self._data.get(key)
        if blob is None:
            self.misses += 1
            return None
        self._data.move_to_end(key)
        self.hits += 1
        return blob

    def put(self, key: tuple, blob: bytes) -> None:
        if self.entries <= 0:
            return
        self._data[key] = blob
        self._data.move_to_end(key)
        while len(self._data) > self.entries:
            self._data.popitem(last=False)

    def __len__(self) -> int:
        return len(self._data)


class ServiceStats:
    """Counters the service maintains; snapshot with :meth:`snapshot`.

    Attributes:
        submitted: requests entering :meth:`CodecService.submit`.
        served: requests that got a payload (cache hits included).
        rejected: reject reason -> count.
        failed: requests failed by an engine error.
        engine_failures: engine batches that raised.
        deadline_missed: served, but past the deadline.
        occupancy: engine batch size -> dispatch count.
        latencies_s: admission-to-completion times of the most recent
            :data:`LATENCY_WINDOW` served requests (a bounded sliding
            window — a long-running service must not grow memory, or
            re-sort an ever-longer list per snapshot, without limit).
        retries: retry attempts scheduled (a retried request still
            counts once in ``submitted`` and reaches one terminal
            outcome).
        retry_budget_exhausted: retries denied by the token-bucket
            retry budget (the request fails instead).
        timeouts: engine attempts abandoned at ``timeout_s``.
        corrupt_payloads: engine payloads that failed
            ``validate_payload`` (never served).
        degraded: requests whose quality the degradation controller
            downshifted at admission.
        degraded_served: degraded requests that were served (always
            ⊆ ``served``).
        closed_unserved: futures resolved with
            :class:`~repro.serve.admission.ServiceClosed` at close
            (also counted under ``rejected["shutdown"]``).
        unhandled: batch tasks whose failure handling itself raised —
            the dispatch loop's last-resort containment guard; must
            stay 0 (CI-gated by the chaos bench).
    """

    LATENCY_WINDOW = 8192

    def __init__(self):
        self.submitted = 0
        self.served = 0
        self.rejected: collections.Counter = collections.Counter()
        self.failed = 0
        self.engine_failures = 0
        self.deadline_missed = 0
        self.occupancy: collections.Counter = collections.Counter()
        self.latencies_s: collections.deque = collections.deque(
            maxlen=self.LATENCY_WINDOW)
        self.retries = 0
        self.retry_budget_exhausted = 0
        self.timeouts = 0
        self.corrupt_payloads = 0
        self.degraded = 0
        self.degraded_served = 0
        self.closed_unserved = 0
        self.unhandled = 0

    @property
    def total_rejected(self) -> int:
        return sum(self.rejected.values())

    def latency_percentile(self, pct: float) -> float:
        """Empirical latency percentile in seconds (nan when empty)."""
        if not self.latencies_s:
            return math.nan
        xs = sorted(self.latencies_s)
        i = min(len(xs) - 1, max(0, round(pct / 100 * (len(xs) - 1))))
        return xs[i]

    def snapshot(self) -> dict:
        """JSON-friendly summary of every counter."""
        return {
            "submitted": self.submitted,
            "served": self.served,
            "rejected": dict(self.rejected),
            "failed": self.failed,
            "engine_failures": self.engine_failures,
            "deadline_missed": self.deadline_missed,
            "occupancy": {str(k): v for k, v
                          in sorted(self.occupancy.items())},
            "p50_latency_s": self.latency_percentile(50),
            "p99_latency_s": self.latency_percentile(99),
            "retries": self.retries,
            "retry_budget_exhausted": self.retry_budget_exhausted,
            "timeouts": self.timeouts,
            "corrupt_payloads": self.corrupt_payloads,
            "degraded": self.degraded,
            "degraded_served": self.degraded_served,
            "closed_unserved": self.closed_unserved,
            "unhandled": self.unhandled,
        }


@dataclasses.dataclass
class _Entry:
    """Service-side payload attached to each planner request.

    ``attempts``/``backoff_s`` track the retry state across engine
    attempts (the planner ``Request`` object — id, arrival, deadline —
    is reused verbatim on re-admission so latency and SLO accounting
    span the whole request, not just the last attempt).
    """
    image: np.ndarray
    cache_key: tuple
    future: asyncio.Future
    degraded: bool = False
    attempts: int = 0
    backoff_s: float = 0.0


# ---------------------------------------------------------------------------
# The service
# ---------------------------------------------------------------------------

class CodecService:
    """Asyncio front end turning concurrent submits into engine batches.

    Use as an async context manager (or call :meth:`start` /
    :meth:`close` explicitly)::

        async with CodecService(ServiceConfig(max_batch=8)) as svc:
            resp = await svc.submit(img, quality=75, tenant="gold",
                                    deadline_s=0.25)
            resp.payload    # DCTZ bytes

    Every submit reaches exactly one terminal outcome: a
    :class:`Response`, a :class:`RejectedError` (admission or queue
    sweep), or an :class:`EngineFailure` (its batch's engine call
    raised).  All planner state is touched only from the event loop;
    engine batches run in a thread pool sized by
    ``config.engine_concurrency``.

    Args:
        config: SLO/batching knobs (default :class:`ServiceConfig`).
        engine: ``(images, quality) -> list[bytes]`` override; defaults
            to :func:`default_engine` (the real codec engine).  Called
            from worker threads — must be thread-compatible.
        clock: monotonic time source (injectable for tests).
    """

    def __init__(self, config: ServiceConfig | None = None, *,
                 engine=None, clock=time.monotonic):
        self.config = config or ServiceConfig()
        self._engine = engine if engine is not None else \
            default_engine(self.config)
        self._clock = clock
        self._planner = queueing.BatchPlanner(
            max_batch=self.config.max_batch,
            max_wait_s=self.config.max_wait_s,
            max_queue_depth=self.config.max_queue_depth,
            safety=self.config.safety,
            initial_step_s=self.config.initial_step_s,
            bucket=self.config.shape_bucket)
        self.stats = ServiceStats()
        self.cache = StreamCache(self.config.cache_entries)
        self._wake: asyncio.Event | None = None
        self._dispatcher: asyncio.Task | None = None
        self._inflight: set = set()
        self._pool: concurrent.futures.ThreadPoolExecutor | None = None
        self._draining = False
        self._closed = False
        res = self.config.resilience
        self.breaker = (resilience.CircuitBreaker(res.breaker)
                        if res.breaker is not None else None)
        self.degrade = (resilience.DegradationController(res.degrade)
                        if res.degrade is not None else None)
        self._retry_budget = res.retry.make_budget()
        self._retry_rng = random.Random(res.seed)
        self._retry_tasks: set = set()
        # every admitted request's future, until it resolves: close()
        # uses this to guarantee no awaiting client dangles even after
        # a dispatcher crash or a cancelled retry backoff
        self._pending: set = set()
        self.dispatcher_error: BaseException | None = None

    # -- lifecycle --------------------------------------------------------

    async def start(self) -> "CodecService":
        """Start the dispatcher task; idempotent until :meth:`close`."""
        if self._closed:
            raise RuntimeError("service already closed")
        if self._dispatcher is None:
            self._wake = asyncio.Event()
            self._pool = concurrent.futures.ThreadPoolExecutor(
                max_workers=max(1, self.config.engine_concurrency),
                thread_name_prefix="codec-engine")
            self._dispatcher = asyncio.get_running_loop().create_task(
                self._dispatch_loop())
        return self

    async def close(self) -> None:
        """Drain queues, finish in-flight batches, stop the dispatcher.

        Every already-admitted request still gets its terminal outcome
        (queues are drained as forced partial batches); new submits
        raise ``RejectedError(reason="shutdown")``.  Requests the drain
        could not serve — parked in a retry backoff, or stranded by a
        dispatcher crash (recorded in :attr:`dispatcher_error`) — are
        resolved with a typed
        :class:`~repro.serve.admission.ServiceClosed` rejection and
        counted in ``stats.closed_unserved``: no awaiting client is
        ever left dangling.
        """
        if self._closed:
            return
        self._draining = True
        self._closed = True
        if self._dispatcher is not None:
            self._wake.set()
            try:
                await self._dispatcher
            except Exception as exc:    # noqa: BLE001 - record, keep closing
                self.dispatcher_error = exc
            while self._inflight:
                await asyncio.gather(*list(self._inflight),
                                     return_exceptions=True)
            # retries parked in a backoff sleep never re-admit now:
            # cancel them; the sweep below resolves their futures
            for t in list(self._retry_tasks):
                t.cancel()
            if self._retry_tasks:
                await asyncio.gather(*list(self._retry_tasks),
                                     return_exceptions=True)
            self._pool.shutdown(wait=True)
            self._dispatcher = None
        for fut in [f for f in self._pending if not f.done()]:
            self.stats.closed_unserved += 1
            self.stats.rejected[admission.SHUTDOWN] += 1
            fut.set_exception(ServiceClosed(
                "service closed before serving this request"))
        self._pending.clear()

    async def __aenter__(self) -> "CodecService":
        return await self.start()

    async def __aexit__(self, *exc) -> None:
        await self.close()

    # -- client API -------------------------------------------------------

    async def submit(self, image, *, quality: int | None = None,
                     tenant: str = "default",
                     deadline_s: float | None = None) -> Response:
        """Encode one image to a ``DCTZ`` stream under the service SLOs.

        Args:
            image: 2-D (H, W) grayscale or (H, W, 3) RGB uint8 array
                (anything ``np.asarray`` accepts); colour images are
                coded as ``DCTZ`` version-3 YCbCr 4:2:0 streams.
            quality: requested JPEG quality (default
                ``config.default_quality``); clamped by the tenant tier.
            tenant: tenant name — selects the
                :class:`~repro.serve.admission.TenantTier` policy.
            deadline_s: relative SLO; None uses
                ``config.default_deadline_s`` (which may mean "none").

        Returns:
            A :class:`Response` (payload bytes + serving metadata).

        Raises:
            ValueError: invalid image/quality/deadline arguments —
                raised before the request counts as submitted, so the
                stats conservation invariant is unaffected.
            RejectedError: backpressure (``queue_full``), hopeless or
                expired deadline (``deadline_unmeetable``), a closing
                service (``shutdown``; :class:`ServiceClosed` when the
                request was admitted but shutdown beat its outcome), or
                an open engine-path breaker (``circuit_open``, typed
                :class:`~repro.serve.resilience.CircuitOpen`).
            EngineFailure: every engine attempt carrying this request
                raised, timed out or produced a corrupt payload; the
                last underlying exception is ``__cause__``.
        """
        if self._dispatcher is None and not self._closed:
            raise RuntimeError("service not started: use `async with "
                               "CodecService(...)` or await start()")
        image = np.asarray(image)
        if image.ndim != 2 and image.shape[2:] != (3,):
            raise ValueError(f"image must be 2-D (H, W) or colour "
                             f"(H, W, 3), got shape {image.shape}")
        tier = self.config.tier(tenant)
        q = tier.resolve_quality(quality if quality is not None
                                 else self.config.default_quality)
        rel_deadline = tier.resolve_deadline_s(
            deadline_s if deadline_s is not None
            else self.config.default_deadline_s)
        # invalid arguments raised above, before the request counts as
        # submitted: every counted submit reaches exactly one terminal
        # outcome, so submitted == served + rejected + failed holds
        self.stats.submitted += 1
        if self._draining:
            exc = ServiceClosed("service closing")
            self.stats.rejected[exc.reason] += 1
            raise exc
        now = self._clock()
        degraded = False
        if self.degrade is not None:
            cap = self.degrade.quality_cap()
            if q > cap:
                q = cap
                degraded = True
                self.stats.degraded += 1
        key = StreamCache.key(image, q, self.config.tables)
        blob = self.cache.get(key)
        if blob is not None:
            self.stats.served += 1
            if degraded:
                self.stats.degraded_served += 1
            self.stats.latencies_s.append(self._clock() - now)
            return Response(payload=blob, quality=q,
                            latency_s=self._clock() - now, batch_size=0,
                            cache_hit=True, degraded=degraded, attempts=0)
        if self.breaker is not None and not self.breaker.admission_open(now):
            exc = resilience.CircuitOpen(
                f"engine path open; retry in "
                f"{self.breaker.retry_after_s(now):.3f}s")
            self.stats.rejected[exc.reason] += 1
            raise exc
        deadline = now + rel_deadline      # inf stays inf
        future = asyncio.get_running_loop().create_future()
        try:
            req = self._planner.admit(
                image.shape, q, tenant, now, deadline=deadline,
                payload=_Entry(image=image, cache_key=key, future=future,
                               degraded=degraded))
        except RejectedError as exc:
            self.stats.rejected[exc.reason] += 1
            raise
        self._pending.add(future)
        future.add_done_callback(self._pending.discard)
        self._wake.set()
        return await future

    # -- dispatcher -------------------------------------------------------

    async def _dispatch_loop(self) -> None:
        cap = max(1, self.config.max_inflight_batches)
        while True:
            # drop finished tasks here rather than trusting the
            # done-callback: it runs a loop iteration *after* the task
            # completes, and counting a done task against the cap when
            # its completion wake-up was already consumed would leave
            # the dispatcher sleeping with zero budget forever.  Prune
            # IN PLACE — the done-callbacks and close()'s drain loop
            # hold references to this set object, so rebinding it would
            # strand still-running tasks in a set nobody discards from
            self._inflight.difference_update(
                [t for t in self._inflight if t.done()])
            now = self._clock()
            budget = max(0, cap - len(self._inflight))
            if self.breaker is not None and not self._draining:
                # the breaker gates *dispatch*: 0 while open (queued
                # work waits for half-open or the deadline sweep),
                # bounded probes while half-open.  Draining ignores it
                # — shutdown must resolve everything, and a failed
                # drain batch is still a terminal outcome.
                b = self.breaker.dispatch_budget(now)
                if b is not None:
                    budget = min(budget, b)
            urgent_cap = (self.degrade.urgent_cap()
                          if self.degrade is not None else None)
            poll = self._planner.poll(
                now, drain=self._draining,
                max_batches=None if self._draining else budget,
                urgent_cap=urgent_cap)
            for req, exc in poll.rejects:
                self._finish_reject(req, exc)
            for batch in poll.batches:
                if self.breaker is not None:
                    self.breaker.on_dispatch(now)
                task = asyncio.get_running_loop().create_task(
                    self._run_batch(batch))
                self._inflight.add(task)
                task.add_done_callback(self._inflight.discard)
            if self._draining and self._planner.empty():
                return
            now = self._clock()
            if self.degrade is not None:
                self.degrade.observe(now, self._planner.pressure())
            breaker_blocked = (
                self.breaker is not None
                and not self._planner.empty()
                and self.breaker.dispatch_budget(now) == 0)
            if len(self._inflight) >= cap or breaker_blocked:
                # dispatch is blocked (in-flight cap, or the breaker):
                # a batch completion sets the wake event; until then
                # only the deadline sweep — and, while open, the
                # breaker's reset timer — need the clock
                timeout = self._planner.next_sweep(now)
                if breaker_blocked:
                    retry_after = self.breaker.retry_after_s(now)
                    if retry_after > 0:
                        timeout = (retry_after if timeout is None
                                   else min(timeout, retry_after))
            else:
                timeout = self._planner.next_wake(now)
            try:
                await asyncio.wait_for(self._wake.wait(), timeout)
            except asyncio.TimeoutError:
                pass
            self._wake.clear()

    def _timed_engine_call(self, images, quality):
        # runs in the worker thread: time the engine call itself, not
        # the executor queue wait, so the EWMA tracks the model step
        t0 = self._clock()
        blobs = self._engine(images, quality)
        return blobs, self._clock() - t0

    async def _run_batch(self, batch: queueing.Batch) -> None:
        try:
            await self._run_batch_inner(batch)
        except asyncio.CancelledError:
            raise
        except BaseException as exc:   # noqa: BLE001 - last-resort guard
            # nothing may escape into the dispatch loop — not even a
            # bug in the failure handling itself.  Fail the batch's
            # requests terminally and count the guard trip (the chaos
            # bench CI-gates this counter to zero).
            self.stats.unhandled += 1
            for r in batch.requests:
                fut = r.payload.future
                if not fut.done():
                    self.stats.failed += 1
                    err = EngineFailure("batch handling failed")
                    err.__cause__ = exc
                    fut.set_exception(err)
        finally:
            # a completed batch frees an in-flight slot: wake the
            # dispatcher so blocked queues dispatch immediately
            self._wake.set()

    async def _run_batch_inner(self, batch: queueing.Batch) -> None:
        res = self.config.resilience
        requests = batch.requests
        images = [r.payload.image for r in requests]
        quality = batch.key[1]
        call = asyncio.get_running_loop().run_in_executor(
            self._pool, self._timed_engine_call, images, quality)
        # if the attempt times out the call is abandoned, not awaited:
        # retrieve its eventual exception so it never surfaces as an
        # "exception was never retrieved" warning
        call.add_done_callback(
            lambda f: None if f.cancelled() else f.exception())
        try:
            if res.timeout_s is not None:
                done, _ = await asyncio.wait({call},
                                             timeout=res.timeout_s)
                if not done:
                    # the worker thread keeps running (a thread cannot
                    # be interrupted); its result is discarded and the
                    # attempt is treated as a retryable failure
                    raise EngineTimeout(
                        f"engine attempt exceeded {res.timeout_s}s")
                blobs, step_s = call.result()
            else:
                blobs, step_s = await call
            self._planner.observe_step(batch.key, step_s)
            if len(blobs) != len(requests):
                raise RuntimeError(
                    f"engine returned {len(blobs)} streams for "
                    f"{len(requests)} images")
        except asyncio.CancelledError:
            raise
        except BaseException as exc:  # noqa: BLE001 - isolate the batch;
            # BaseException because a dying worker delivers SystemExit
            # through the executor future, and that too must only fail
            # this batch, never the service
            now = self._clock()
            self.stats.engine_failures += 1
            if isinstance(exc, EngineTimeout):
                self.stats.timeouts += 1
            if self.breaker is not None:
                self.breaker.record_failure(now)
            self._fail_or_retry(requests, batch.key, exc, now)
            return
        end = self._clock()
        self.stats.occupancy[len(requests)] += 1
        validate = res.validate_payload
        corrupt: list = []
        serve: list = []
        for r, blob in zip(requests, blobs):
            if validate is not None and not validate(blob):
                corrupt.append(r)
            else:
                serve.append((r, blob))
        if self.breaker is not None:
            # one outcome per engine call keeps the breaker window in
            # call units; any corrupt payload marks the call failed
            if corrupt:
                self.breaker.record_failure(end)
            else:
                self.breaker.record_success(end)
        if corrupt:
            self.stats.corrupt_payloads += len(corrupt)
            self._fail_or_retry(
                corrupt, batch.key,
                PayloadCorrupt(f"{len(corrupt)}/{len(requests)} payloads "
                               f"failed integrity validation"), end)
        for r, blob in serve:
            entry = r.payload
            self.cache.put(entry.cache_key, blob)
            latency = end - r.arrival
            missed = end > r.deadline
            self.stats.served += 1
            if entry.degraded:
                self.stats.degraded_served += 1
            self.stats.latencies_s.append(latency)
            if missed:
                self.stats.deadline_missed += 1
            if not entry.future.done():
                entry.future.set_result(Response(
                    payload=blob, quality=r.quality, latency_s=latency,
                    batch_size=len(requests), deadline_missed=missed,
                    req_id=r.req_id, degraded=entry.degraded,
                    attempts=entry.attempts + 1))

    def _fail_or_retry(self, requests: list, key: tuple,
                       exc: BaseException, now: float) -> None:
        """Route each failed request to a backoff retry or a terminal
        :class:`EngineFailure`, preserving one-outcome-per-submit."""
        retry = self.config.resilience.retry
        step = self._planner.step_estimate(key)
        for r in requests:
            entry = r.payload
            entry.attempts += 1
            if entry.future.done():
                continue
            if retry.enabled and entry.attempts < retry.max_attempts \
                    and not self._draining:
                if self._retry_budget.take(now):
                    delay = retry.backoff_s(entry.backoff_s,
                                            self._retry_rng)
                    entry.backoff_s = delay
                    if now + delay + step <= r.deadline:
                        self.stats.retries += 1
                        task = asyncio.get_running_loop().create_task(
                            self._retry_later(r, delay))
                        self._retry_tasks.add(task)
                        task.add_done_callback(self._retry_tasks.discard)
                        continue
                    # deadline rules the retry out: fall through to the
                    # terminal failure below
                else:
                    self.stats.retry_budget_exhausted += 1
            self.stats.failed += 1
            err = EngineFailure(
                f"engine attempt {entry.attempts} of "
                f"{retry.max_attempts} failed")
            err.__cause__ = exc
            entry.future.set_exception(err)

    async def _retry_later(self, req: queueing.Request,
                           delay: float) -> None:
        """Sleep out a backoff, then re-queue the original request.

        The planner ``Request`` is re-admitted verbatim (same req_id,
        arrival, deadline), so the eventual response's latency spans
        every attempt.  If the service closes first the task is
        cancelled and :meth:`close` resolves the future with
        :class:`~repro.serve.admission.ServiceClosed`; if the queue is
        full at re-admission the request is rejected like any other.
        """
        try:
            await asyncio.sleep(delay)
        except asyncio.CancelledError:
            return
        if self._draining or req.payload.future.done():
            return      # close() resolves the future via _pending
        try:
            self._planner.readmit(req)
        except RejectedError as exc:
            self._finish_reject(req, exc)
            return
        self._wake.set()

    def _finish_reject(self, req: queueing.Request,
                       exc: RejectedError) -> None:
        self.stats.rejected[exc.reason] += 1
        fut = req.payload.future
        if not fut.done():
            fut.set_exception(exc)

    # -- introspection ----------------------------------------------------

    def queue_depth(self) -> int:
        """Requests currently queued (excludes in-flight batches)."""
        return self._planner.total_depth()
