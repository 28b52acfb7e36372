"""Program spans and counters (``repro.obs``): what a profiler trace of
the engine holds, route counts, thread safety, and the jax-free entropy
import."""

import collections
import concurrent.futures
import os
import pathlib
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro import obs
from repro.core import images
from repro.serve import codec_engine as eng

SRC = pathlib.Path(__file__).resolve().parents[1] / "src"


def _spans(trace_dir) -> list:
    """(host line, name, start, end, stats) of every ``repro.`` event."""
    (path,) = pathlib.Path(trace_dir).rglob("*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(str(path))
    return [((plane.name, k), ev.name, ev.start_ns, ev.end_ns,
             dict(ev.stats))
            for plane in data.planes if plane.name.startswith("/host:")
            for k, line in enumerate(plane.lines) for ev in line.events
            if ev.name.startswith(obs.PREFIX)]


def _batch(n=3, size=32):
    return np.stack([images.lena_like(size, size, seed=i) for i in range(n)])


def _delta(fn):
    before = obs.counts()
    fn()
    return {k: v - before.get(k, 0) for k, v in obs.counts().items()
            if v != before.get(k, 0)}


def test_spans_with_stats_and_nesting_reach_the_trace(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        with obs.call("engine.test", images=2) as cid:
            with obs.h2d(np.zeros(5, np.int32), np.zeros(3, np.uint8)):
                pass
    spans = {name: (line, s, e, st) for line, name, s, e, st
             in _spans(tmp_path)}
    line, s, e, st = spans["repro.engine.test"]
    assert st == {"call": cid, "images": 2}
    cline, cs, ce, cst = spans["repro.xfer.h2d"]
    assert cst == {"nbytes": 23}
    assert cline == line and s <= cs <= ce <= e


def test_pool_thread_spans_carry_the_callers_call_id(tmp_path,
                                                     monkeypatch):
    monkeypatch.setattr(eng, "_n_workers", lambda: 2)
    with jax.profiler.trace(str(tmp_path)):
        blobs = eng.encode_batch(_batch(4), 50)
        eng.decode_batch(blobs)
    spans = _spans(tmp_path)
    calls = {name: st["call"] for _, name, _, _, st in spans
             if name in ("repro.engine.encode", "repro.engine.decode")}
    assert len(set(calls.values())) == 2
    for kind in ("encode", "decode"):
        per_image = [(line, st) for line, name, _, _, st in spans
                     if name == f"repro.entropy.{kind}_image"]
        assert sorted(st["image"] for _, st in per_image) == [0, 1, 2, 3]
        assert {st["call"] for _, st in per_image} == {
            calls[f"repro.engine.{kind}"]}
    # the images were coded on pool threads, not on the caller's line
    (caller,) = {line for line, name, *_ in spans
                 if name == "repro.engine.encode"}
    assert caller not in {line for line, name, *_ in spans
                          if name == "repro.entropy.encode_image"}


def test_device_decode_resolves_one_tile_per_image(tmp_path, monkeypatch,
                                                   pallas_route):
    # the engine's route resolves every block chain on the device: one
    # device route per image and no host resolver span; over the device
    # resolver's block guard the host resolves one tile per image
    from repro.kernels.unpack_bits import ops
    blobs = eng.encode_batch(_batch(3), 50)
    pallas_route("unpack")
    monkeypatch.setattr(eng, "_n_workers", lambda: 2)

    def decode(where):
        with jax.profiler.trace(str(tmp_path / where)):
            moved = _delta(lambda: eng.decode_batch(blobs))
        return moved, [st for _, name, _, _, st in _spans(tmp_path / where)
                       if name == "repro.entropy.resolve"]

    moved, spans = decode("device")
    assert moved["entropy.resolve.device"] == 3
    assert "entropy.resolve.host" not in moved and spans == []
    monkeypatch.setattr(ops, "MAX_DEVICE_BLOCKS", 0)
    moved, spans = decode("host")
    assert moved["entropy.resolve.host"] == 3
    assert "entropy.resolve.device" not in moved
    assert [(st["tiles"], st["route"]) for st in spans] == [(1, "host")] * 3


def test_counters_are_exact_under_a_thread_pool():
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        before = obs.counts().get("test.obs.race", 0)
        with concurrent.futures.ThreadPoolExecutor(8) as pool:
            futs = [pool.submit(lambda: [obs.count("test.obs.race", 3)
                                         for _ in range(2000)])
                    for _ in range(16)]
            for f in futs:
                f.result(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert obs.counts()["test.obs.race"] - before == 16 * 2000 * 3


def test_host_entropy_path_stays_jax_free():
    code = """
import sys
import numpy as np
import repro.core.entropy as entropy
z = np.zeros((16, 64), np.int32)
z[:, 0] = np.arange(16)
z[::3, 5] = -3
blob = entropy.encode_zigzag_host(z, 50, "exact", (32, 32))
back, _ = entropy.decode_zigzag_host(blob)
assert (back == z).all()
from repro import obs
c = obs.counts()
assert c["entropy.symbolize.host"] == c["entropy.unpack.host"] == 1, c
print("jax" in sys.modules)
"""
    env = dict(os.environ, PYTHONPATH=str(SRC))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "False"


@pytest.mark.parametrize("backend, route", [("auto", "host"),
                                            ("pallas", "interpret")])
def test_engine_counts_the_route_of_every_image(pallas_route, backend,
                                                route):
    if backend == "pallas":
        pallas_route()
    imgs = _batch(3)
    blobs = []
    enc = _delta(lambda: blobs.extend(eng.encode_batch(imgs, 50)))
    dec = _delta(lambda: eng.decode_batch(blobs))
    assert enc["engine.images.encoded"] == dec["engine.images.decoded"] == 3
    assert enc[f"entropy.symbolize.{route}"] == 3
    assert enc[f"entropy.pack.{route}"] == 3
    assert dec[f"entropy.unpack.{route}"] == 3
    launches = collections.Counter(
        k.split(".")[1] for k in {**enc, **dec} if ".device." in k)
    if route == "host":
        assert not launches
    else:
        assert launches == {"symbolize": 1, "pack": 1, "unpack": 1}
        assert enc["entropy.symbolize.device.0"] == 3


def test_roundtrip_counts_its_route():
    # the stacked 32x32 group comes back as the program's own output
    delta = _delta(lambda: eng.roundtrip_batch(_batch(2), 50))
    assert delta == {"engine.roundtrip.staged": 1,
                     "engine.reassemble.direct": 1}


def _reassemble_spans(trace_dir, fn) -> tuple:
    """(counter delta, ``engine.reassemble`` spans) of ``fn()`` under a
    profiler session."""
    with jax.profiler.trace(str(trace_dir)):
        moved = _delta(fn)
    return moved, [name for _, name, *_ in _spans(trace_dir)
                   if name == "repro.engine.reassemble"]


@pytest.mark.parametrize("size, route", [(32, "direct"), (30, "program")])
def test_stacked_roundtrip_reassembles_once_per_call(tmp_path, size, route):
    # aligned or not, a stacked call is one group: one span, one route,
    # and no per-image slice
    imgs = _batch(3, size)
    moved, spans = _reassemble_spans(
        tmp_path, lambda: [eng.roundtrip_batch(imgs, 50) for _ in range(2)])
    assert {k: v for k, v in moved.items()
            if k.startswith("engine.reassemble.")} == {
        f"engine.reassemble.{route}": 2}
    assert len(spans) == 2


def test_decode_reassembles_once_per_bucket(tmp_path):
    # a uniform bucket and a bucket of two sizes in one 8-block grid
    blobs = eng.encode_batch([images.lena_like(32, 32, seed=0),
                              images.lena_like(16, 24, seed=1),
                              images.lena_like(30, 32, seed=2),
                              images.lena_like(16, 24, seed=3)], 50)
    moved, spans = _reassemble_spans(tmp_path,
                                     lambda: eng.decode_batch(blobs))
    assert moved["engine.reassemble.program"] == 1
    assert moved["engine.reassemble.per_image"] == 1
    assert "engine.reassemble.direct" not in moved
    assert len(spans) == 2
