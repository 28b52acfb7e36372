"""What every traffic kind shares: phases, compile counting, sampling.

A *phase* is a named span of the harness (``encode``, ``decode``,
``roundtrip``). The driver of a traffic kind enters one
around each call into the system under test; the phase is written into
the profiler's trace as a ``perfbench.<phase>`` annotation (so the trace
reduction can attribute device time and idle gaps to it) and tags every
compilation that happens inside it.
"""

from __future__ import annotations

import collections
import contextlib
import time

import numpy as np

# fires once per program handed to the backend, whether it is compiled
# or loaded from the persistent compilation cache
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",)
# JAX's stages from a traced function to a loaded program, by short name
STAGE_EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "trace",
                "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
                "/jax/core/compile/backend_compile_duration": "compile"}
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
ANNOTATION_PREFIX = "perfbench."


class Phases:
    """Current phase, wall time per phase, compilations per phase."""

    def __init__(self):
        self.current = "setup"
        self.wall_s = collections.Counter()
        self.compiles = collections.Counter()
        self.stage_s = collections.Counter()   # seconds per JAX stage
        self.cache_hits = collections.Counter()
        self.window_open = False

    def _key(self) -> str:
        return self.current if self.window_open else "setup"

    def on_event(self, event: str, duration: float = 0.0) -> None:
        if event in COMPILE_EVENTS:
            self.compiles[self._key()] += 1
        if event in STAGE_EVENTS:
            self.stage_s[STAGE_EVENTS[event]] += duration

    def on_count(self, event: str) -> None:
        if event == CACHE_HIT_EVENT:
            self.cache_hits[self._key()] += 1

    @contextlib.contextmanager
    def phase(self, name: str):
        import jax
        prev, self.current = self.current, name
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(ANNOTATION_PREFIX + name):
                yield
        finally:
            if self.window_open:
                self.wall_s[name] += time.perf_counter() - t0
            self.current = prev


def install_listeners(phases: Phases) -> None:
    import jax
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, duration, **kw: phases.on_event(event, duration))
    jax.monitoring.register_event_listener(
        lambda event, **kw: phases.on_count(event))


class Reservoir:
    """A uniform sample of ``k`` items from a stream of unknown length,
    drawn from a seed (Algorithm R)."""

    def __init__(self, k: int, seed: int):
        self.k = k
        self.items: list = []
        self.seen = 0
        self._rng = np.random.default_rng(seed % (1 << 63))

    def offer(self, item_fn) -> None:
        """``item_fn()`` builds the item, only if it is kept."""
        self.seen += 1
        if len(self.items) < self.k:
            self.items.append(item_fn())
            return
        j = int(self._rng.integers(self.seen))
        if j < self.k:
            self.items[j] = item_fn()


def block(x):
    """Wait until every device array in ``x`` is ready."""
    import jax
    jax.block_until_ready(x)
    return x
