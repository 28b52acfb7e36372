"""Serving layer: batched codec engine + async front end.

* :mod:`repro.serve.codec_engine` — batched/multi-device encode and
  decode over the core codec (shape buckets, overlapped entropy edge,
  device-routed pack/unpack).
* :mod:`repro.serve.service` — asyncio :class:`~repro.serve.service.
  CodecService` with deadline-aware adaptive batching, bounded-queue
  backpressure, per-tenant quality tiers and a hot-stream cache.
* :mod:`repro.serve.queueing` / :mod:`repro.serve.admission` — the
  jax-free planner core (per-bucket FIFO queues, dispatch triggers,
  admission control) the property-test suite drives directly.
* :mod:`repro.serve.resilience` — the jax-free failure-handling
  envelope (per-attempt timeouts, budget-guarded retries with
  decorrelated-jitter backoff, a failure-rate circuit breaker,
  graceful quality degradation), off by default.
* :mod:`repro.serve.chaos` — deterministic seeded fault injection
  (scripted exceptions, latency spikes, payload byte flips, worker
  death) shared by the test suite and the ``service_chaos`` bench.

See docs/serving.md for the serving semantics, SLO knobs and the
failure model.
"""
