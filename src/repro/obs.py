"""Spans and counters inside the codec, on the profiler's clock.

``span(name, **stats)`` enters ``jax.profiler.TraceAnnotation("repro." +
name, **stats)``: under a profiler session (``jax.profiler.trace``) the
span lands in the host plane of the thread that ran it, on the same
clock as the device's ``XLA Ops`` line, so a device idle gap can be put
down to the host stage that was running. With no session a span costs
about a microsecond and records nothing; there is no other switch.

Spans sit at call, bucket and image granularity only, never inside a
per-block, per-tile or per-symbol loop. Names and stats (docs/serving.md,
"Observing the engine"):

* ``engine.{encode,decode,roundtrip}`` (``call``, ``images``) around
  each engine call, and inside them ``engine.compress``,
  ``engine.inverse``, ``engine.reassemble``, ``engine.psnr``;
* ``entropy.{encode,decode}_image`` (``call``, ``image``, ...) around
  each image's entropy stage, on whichever thread codes it, and inside
  them ``entropy.symbolize``/``pack``/``unpack`` (``route``),
  ``entropy.tables``, ``entropy.payload``, ``entropy.frame``,
  ``entropy.parse``, ``entropy.resolve`` (``route``: the host resolver
  only);
* ``xfer.h2d`` / ``xfer.d2h`` (``nbytes``) around every explicit copy
  between host and device; a ``d2h`` span also covers the host's wait
  for the device work that produces what it copies.

``count(key, n)`` adds to process-wide counters (``counts()`` returns a
snapshot): the route each entropy stage took per image
(``entropy.<stage>.<pallas|interpret|host>``), where each unpacked
stream's block chain was resolved (``entropy.resolve.<device|host>``),
the device of each entropy-kernel launch
(``entropy.<stage>.device.<id>``), the roundtrip
route per call (``engine.roundtrip.<fused|staged>``), how each group's
output left the engine
(``engine.reassemble.<direct|program|per_image>``) and the images
encoded and decoded (``engine.images.{encoded,decoded}``).

This module imports nothing outside the standard library: where jax is
not imported yet (a jax-free entropy encode or decode),
``span`` returns a shared null context and never imports it.
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
import itertools
import sys
import threading

PREFIX = "repro."

_NULL = contextlib.nullcontext()
_lock = threading.Lock()
_counts: collections.Counter = collections.Counter()
_call_ids = itertools.count(1)
_call: contextvars.ContextVar = contextvars.ContextVar("repro_call",
                                                       default=0)


def span(name: str, **stats):
    """Context manager: a ``repro.<name>`` span carrying ``stats``."""
    jax = sys.modules.get("jax")
    if jax is None:
        return _NULL
    return jax.profiler.TraceAnnotation(PREFIX + name, **stats)


@contextlib.contextmanager
def call(name: str, **stats):
    """An engine call's span with a fresh ``call`` id, which
    :func:`current_call` returns inside it so that the call's spans on
    pool threads can carry it (span nesting does not cross threads)."""
    cid = next(_call_ids)
    token = _call.set(cid)
    try:
        with span(name, call=cid, **stats):
            yield cid
    finally:
        _call.reset(token)


def current_call() -> int:
    """The id of the innermost :func:`call` of this context (0: none)."""
    return _call.get()


def _nbytes(arrays) -> int:
    return sum(int(a.nbytes) for a in arrays)


def h2d(*arrays):
    """Span of a host-to-device copy of ``arrays``."""
    return span("xfer.h2d", nbytes=_nbytes(arrays))


def d2h(*arrays):
    """Span of a device-to-host copy of ``arrays`` (and of the wait for
    the device work that produces them)."""
    return span("xfer.d2h", nbytes=_nbytes(arrays))


def route(stage: str, how: str, **stats):
    """Count one image's ``entropy.<stage>`` taking route ``how``, and
    return the stage's span."""
    count(f"entropy.{stage}.{how}")
    return span("entropy." + stage, route=how, **stats)


def device_route(stage: str, interpret: bool, **stats):
    """:func:`route` for a Pallas launch: "interpret" or "pallas"."""
    return route(stage, "interpret" if interpret else "pallas", **stats)


def launched(stage: str, out) -> None:
    """Count an entropy-kernel launch on each device ``out`` lives on."""
    for d in out.devices():
        count(f"entropy.{stage}.device.{d.id}")


def count(key: str, n: int = 1) -> None:
    with _lock:
        _counts[key] += n


def counts() -> dict:
    """A snapshot of every counter."""
    with _lock:
        return dict(_counts)
