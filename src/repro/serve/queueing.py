"""Per-bucket request queues + deadline-aware adaptive batching (jax-free).

The synchronous core of the async codec service
(:mod:`repro.serve.service`): requests land in one FIFO queue per
*(shape bucket, quality)* — the unit the codec engine compiles and
batches over — and :class:`BatchPlanner` decides, from wall-clock
observations only, when each queue dispatches:

* **full** — the queue holds ``max_batch`` requests (one engine batch),
* **urgent** — the oldest request's deadline minus a safety multiple of
  the bucket's measured model-step EWMA is about to pass
  (:func:`repro.serve.admission.urgent`), so waiting for more batchmates
  would knowingly miss its SLO,
* **timer** — the oldest request has waited ``max_wait_s`` (bounds the
  latency a lone request pays for batching).

Backpressure is a bounded queue: :meth:`BatchPlanner.admit` raises
:class:`repro.serve.admission.RejectedError` (``queue_full``) at the
configured depth, and sheds requests whose deadline the current step
estimate already rules out (``deadline_unmeetable``).  :meth:`poll`
additionally *sweeps* queued requests whose deadline has become
unmeetable — they are returned as rejects, never dispatched, and never
dropped silently (the conservation invariant the property tests pin).

The planner is deliberately synchronous and single-threaded (the
asyncio service calls it only from the event loop) and imports neither
jax nor the engine, so hypothesis can drive thousands of synthetic
schedules against the real production logic.
"""

from __future__ import annotations

import dataclasses
import itertools
import math
from collections import deque

from repro.serve import admission
from repro.serve.admission import RejectedError

#: Shape-bucket granularity (must match
#: :data:`repro.serve.codec_engine.SHAPE_BUCKET`; asserted by tests so
#: this module stays importable without jax).
DEFAULT_SHAPE_BUCKET = 64


def shape_bucket(h: int, w: int, bucket: int = DEFAULT_SHAPE_BUCKET
                 ) -> tuple:
    """Bucketed (H, W): each dim rounds up to a multiple of ``bucket``."""
    return (h + (-h) % bucket, w + (-w) % bucket)


class Ewma:
    """Exponentially-weighted moving average of model-step seconds."""

    def __init__(self, alpha: float = 0.25, initial: float | None = None):
        if not 0.0 < alpha <= 1.0:
            raise ValueError(f"alpha must be in (0, 1], got {alpha}")
        self.alpha = alpha
        self._value = initial

    def observe(self, v: float) -> None:
        """Fold one measurement into the average."""
        self._value = (v if self._value is None
                       else self.alpha * v + (1 - self.alpha) * self._value)

    @property
    def value(self) -> float | None:
        return self._value


@dataclasses.dataclass
class Request:
    """One queued encode request (planner's view; payload untouched).

    Attributes:
        req_id: monotone id (assigned by :meth:`BatchPlanner.admit`).
        shape: the image's (H, W) — determines the shape bucket.
        quality: resolved (post-tier) JPEG quality.
        tenant: tenant name, for accounting only.
        arrival: clock time the request was admitted.
        deadline: absolute clock time the response is due (``inf`` =
            no deadline).
        payload: opaque caller data (the service stores the image and
            the asyncio future here; the planner never touches it).
    """
    req_id: int
    shape: tuple
    quality: int
    tenant: str
    arrival: float
    deadline: float = math.inf
    payload: object = None


@dataclasses.dataclass
class Batch:
    """One dispatchable engine batch: same bucket, FIFO order."""
    key: tuple                  # ((bh, bw), quality)
    requests: list


@dataclasses.dataclass
class PlannerPoll:
    """Result of one :meth:`BatchPlanner.poll`.

    Attributes:
        batches: batches to dispatch now (FIFO within each bucket).
        rejects: ``(request, RejectedError)`` pairs swept from queues
            because their deadline became unmeetable while queued.
    """
    batches: list
    rejects: list


class BatchPlanner:
    """Deadline-aware adaptive batcher over per-bucket FIFO queues.

    Args:
        max_batch: dispatch a bucket as soon as it holds this many.
        max_wait_s: batching timer — the oldest request never waits
            longer than this for batchmates.
        max_queue_depth: per-bucket admission bound (backpressure).
        safety: EWMA multiple used for urgency/admission margins.
        initial_step_s: model-step estimate before any observation.
        bucket: shape-bucket granularity (see :func:`shape_bucket`).
    """

    def __init__(self, max_batch: int = 8, max_wait_s: float = 0.010,
                 max_queue_depth: int = 64, safety: float = 1.5,
                 initial_step_s: float = 0.050,
                 bucket: int = DEFAULT_SHAPE_BUCKET):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        if max_queue_depth < max_batch:
            raise ValueError(f"max_queue_depth ({max_queue_depth}) must "
                             f"be >= max_batch ({max_batch})")
        self.max_batch = max_batch
        self.max_wait_s = max_wait_s
        self.max_queue_depth = max_queue_depth
        self.safety = safety
        self.initial_step_s = initial_step_s
        self.bucket = bucket
        self._queues: dict = {}          # key -> deque[Request]
        self._ewma: dict = {}            # key -> Ewma
        self._ids = itertools.count()

    # -- observation ------------------------------------------------------

    def bucket_key(self, shape: tuple, quality: int) -> tuple:
        """Queue key: requests batch only within equal buckets (a colour
        image's channel axis keeps it apart from grayscale ones)."""
        return (shape_bucket(shape[0], shape[1], self.bucket)
                + tuple(shape[2:]), quality)

    def step_estimate(self, key: tuple) -> float:
        """Current model-step EWMA for a bucket (seconds)."""
        e = self._ewma.get(key)
        v = e.value if e is not None else None
        return self.initial_step_s if v is None else v

    def observe_step(self, key: tuple, seconds: float) -> None:
        """Fold one measured engine-batch duration into the bucket EWMA."""
        self._ewma.setdefault(key, Ewma()).observe(seconds)

    # -- admission --------------------------------------------------------

    def admit(self, shape: tuple, quality: int, tenant: str, now: float,
              deadline: float = math.inf, payload: object = None
              ) -> Request:
        """Admit a request into its bucket queue or raise RejectedError.

        Raises:
            RejectedError: ``queue_full`` at the depth bound, or
                ``deadline_unmeetable`` when the bucket's current step
                estimate already rules the deadline out.
        """
        key = self.bucket_key(shape, quality)
        q = self._queues.get(key)
        depth = len(q) if q is not None else 0
        if depth >= self.max_queue_depth:
            raise RejectedError(
                admission.QUEUE_FULL,
                f"bucket {key} at depth bound {self.max_queue_depth}")
        step = self.step_estimate(key)
        if not admission.admission_deadline_ok(deadline, now, step,
                                               self.safety):
            raise RejectedError(
                admission.DEADLINE_UNMEETABLE,
                f"deadline {deadline - now:.4f}s away < {self.safety} x "
                f"step estimate {step:.4f}s")
        req = Request(req_id=next(self._ids), shape=tuple(shape),
                      quality=quality, tenant=tenant, arrival=now,
                      deadline=deadline, payload=payload)
        if q is None:
            q = self._queues[key] = deque()
        q.append(req)
        return req

    def readmit(self, req: Request) -> None:
        """Re-queue an already-admitted request (the retry path).

        The request object is reused verbatim — same ``req_id``,
        ``arrival`` and ``deadline`` — so SLO and latency accounting
        span every attempt.  Applies the depth bound (a retry does not
        get to overflow a bucket new work is being shed from) but not
        the admission deadline margin: the poll-time sweep decides
        feasibility with the request's original deadline.

        Raises:
            RejectedError: ``queue_full`` at the depth bound.
        """
        key = self.bucket_key(req.shape, req.quality)
        q = self._queues.get(key)
        if q is not None and len(q) >= self.max_queue_depth:
            raise RejectedError(
                admission.QUEUE_FULL,
                f"bucket {key} at depth bound {self.max_queue_depth} "
                f"(retry re-admission)")
        if q is None:
            q = self._queues[key] = deque()
        q.append(req)

    # -- dispatch ---------------------------------------------------------

    def poll(self, now: float, drain: bool = False,
             max_batches: int | None = None,
             urgent_cap: int | None = None) -> PlannerPoll:
        """Sweep unmeetable requests, then collect dispatchable batches.

        Args:
            now: current clock time.
            drain: dispatch every non-empty bucket regardless of
                triggers (shutdown path — nothing may stay queued).
            max_batches: dispatch at most this many batches (the
                service's in-flight cap: when the engine is saturated,
                requests stay *queued* — where the depth bound and the
                deadline sweep still apply — instead of piling up in an
                unbounded executor backlog).  ``None`` = unlimited;
                sweeping is never limited.
            urgent_cap: graceful-degradation hook — when a batch
                dispatches because its oldest request turned deadline-
                *urgent* (not full, not timer), cap its size at this
                many requests: a smaller batch completes sooner, so the
                urgent request's SLO survives overload at the cost of
                occupancy.  ``None`` = no cap.

        Returns:
            :class:`PlannerPoll` — batches preserve FIFO order within
            their bucket; swept requests come back as rejects so the
            caller can fail their futures (never silently dropped).
        """
        batches: list = []
        rejects: list = []
        for key in list(self._queues):
            q = self._queues[key]
            step = self.step_estimate(key)
            # sweep: a queued request whose deadline the step estimate
            # already rules out must be rejected, never dispatched
            kept = deque()
            for r in q:
                if admission.feasible(r.deadline, now, step):
                    kept.append(r)
                else:
                    rejects.append((r, RejectedError(
                        admission.DEADLINE_UNMEETABLE,
                        f"deadline passed in queue (step estimate "
                        f"{step:.4f}s)")))
            self._queues[key] = q = kept
            while q and (max_batches is None
                         or len(batches) < max_batches):
                trigger = ("drain" if drain
                           else self._dispatch_trigger(q, now, step))
                if trigger is None:
                    break
                take = min(len(q), self.max_batch)
                if trigger == "urgent" and urgent_cap is not None:
                    take = min(take, max(1, urgent_cap))
                batches.append(Batch(
                    key=key,
                    requests=[q.popleft() for _ in range(take)]))
            if not q:
                del self._queues[key]
        return PlannerPoll(batches=batches, rejects=rejects)

    def _dispatch_trigger(self, q: deque, now: float, step: float
                          ) -> str | None:
        """Why this queue dispatches now: "full" | "timer" | "urgent".

        Checked in that order — a full bucket is a full engine batch
        regardless of deadlines, and an expired batching timer already
        waited long enough; only a pure deadline-urgency dispatch is
        eligible for the degradation-time ``urgent_cap``.
        """
        if len(q) >= self.max_batch:
            return "full"
        oldest = q[0]
        if now - oldest.arrival >= self.max_wait_s:
            return "timer"
        if admission.urgent(oldest.deadline, now, step, self.safety):
            return "urgent"
        return None

    def next_wake(self, now: float) -> float | None:
        """Seconds until the earliest timer/urgency trigger, or None.

        ``None`` means every queue is empty — the dispatcher can sleep
        until the next admission wakes it.  A full bucket returns 0.0
        (dispatch immediately).
        """
        wake = math.inf
        for key, q in self._queues.items():
            if not q:
                continue
            if len(q) >= self.max_batch:
                return 0.0
            step = self.step_estimate(key)
            oldest = q[0]
            t = oldest.arrival + self.max_wait_s
            if oldest.deadline != math.inf:
                t = min(t, oldest.deadline - self.safety * step)
            wake = min(wake, t)
        if wake == math.inf:
            return None
        return max(0.0, wake - now)

    def next_sweep(self, now: float) -> float | None:
        """Seconds until the earliest queued deadline turns unmeetable.

        The dispatcher's timeout while the in-flight cap blocks
        dispatch: timers and urgency are moot (nothing may dispatch),
        but a queued request crossing ``deadline - step`` must still be
        swept promptly.  ``None`` = no queued request has a finite
        deadline.
        """
        t = math.inf
        for key, q in self._queues.items():
            step = self.step_estimate(key)
            for r in q:
                if r.deadline != math.inf:
                    t = min(t, r.deadline - step)
        if t == math.inf:
            return None
        return max(0.0, t - now)

    # -- introspection ----------------------------------------------------

    def depth(self, shape: tuple, quality: int) -> int:
        """Current queue depth for a request's bucket."""
        q = self._queues.get(self.bucket_key(shape, quality))
        return len(q) if q is not None else 0

    def total_depth(self) -> int:
        """Requests queued across all buckets."""
        return sum(len(q) for q in self._queues.values())

    def pressure(self) -> float:
        """Queue pressure in [0, 1]: the fullest bucket's depth fraction.

        The overload signal the degradation controller consumes — max
        (not mean) across buckets, because backpressure (``queue_full``)
        engages per bucket and one saturated bucket is already shedding.
        """
        if not self._queues:
            return 0.0
        return min(1.0, max(len(q) for q in self._queues.values())
                   / self.max_queue_depth)

    def empty(self) -> bool:
        return self.total_depth() == 0
