"""Pin the engine's entropy stages to their Pallas routes in tests.

Each entropy kernel package chooses its route from the platform alone
(``select_backend()`` in ``repro.kernels.<kernel>.ops``, read by
``make_symbolizer`` / ``make_packer`` / ``make_unpacker``).  Off the TPU
that is always the host route, so tests that must drive the Pallas route
(interpret mode) through the whole engine pin it here, stage by stage:
``pallas_route("symbolize", "pack")`` leaves decode on the host, as a
mixed platform would.  Explicit backend names keep their meaning.
"""

from __future__ import annotations

import pytest

from repro import obs
from repro.kernels.pack_bits import ops as pack_ops
from repro.kernels.symbolize import ops as symbolize_ops
from repro.kernels.unpack_bits import ops as unpack_ops

STAGES = {"symbolize": symbolize_ops, "pack": pack_ops,
          "unpack": unpack_ops}


@pytest.fixture
def pallas_route(monkeypatch):
    """``pin(*stages)``: resolve "auto" to "pallas" for the named stages
    (all three when none are named) until the test ends."""
    def pin(*stages):
        for stage in stages or tuple(STAGES):
            ops = STAGES[stage]
            real = ops.select_backend

            def select(backend: str = "auto", _real=real) -> str:
                return "pallas" if backend == "auto" else _real(backend)
            monkeypatch.setattr(ops, "select_backend", select)
    return pin


def reassemble_routes(fn) -> tuple:
    """``(fn(), {route: groups})`` of the ``engine.reassemble.<route>``
    counters ``fn`` moved."""
    head = "engine.reassemble."
    before = obs.counts()
    out = fn()
    return out, {k[len(head):]: v - before.get(k, 0)
                 for k, v in obs.counts().items()
                 if k.startswith(head) and v != before.get(k, 0)}
