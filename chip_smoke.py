"""Smoke run of the codec on one TPU chip, through its user entry points.

    python chip_smoke.py             # one chip: phases 0-4
    python chip_smoke.py --chips 4   # the engine's 4-device sharded path

Phases (one process, in order; any failure raises and exits non-zero):

0. device check — the first device must be a TPU;
1. ``codec_engine.roundtrip_batch`` at the paper's Table 1-4 sizes on
   the fused Pallas kernel, against the staged ``core.codec`` path run
   on the host CPU in this process (PSNR and quantised levels), each
   stacked batch returned as the fused program's output;
2. ``codec_engine.encode_batch`` / ``decode_batch`` bytes: every stream
   equals the host NumPy entropy coder on the same chip-computed levels
   and decodes back to them exactly; the route every entropy stage took
   is read from the program's counters (``repro.obs.counts()``);
3. one ``CodecService`` serving 32 ragged requests at two quality
   tiers, every payload byte-identical to serial ``encode_batch``;
4. colour: ``encode_batch`` / ``decode_batch`` of 768x512 RGB images as
   ``DCTZ`` version-3 YCbCr 4:2:0 streams, checked against the float64
   colour reference (``perfbench/reference_colour.py``: levels, its
   independent decoder, RGB) and the route counters.

The last line of standard output is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import asyncio
import collections
import json
import pathlib
import sys
import time

import jax
import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

QUALITY = 50
TOL_PSNR_DB = 0.01
TOL_LEVEL_FRACTION = 1e-4

_compile_s = [0.0]


def _on_duration(event: str, duration_s: float, **_kw) -> None:
    if event.endswith("backend_compile_duration"):
        _compile_s[0] += duration_s


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise AssertionError(msg)


class Phase:
    """Prints a phase's wall and compile seconds when it ends."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        print(f"== {self.name}", flush=True)
        self.t0 = time.perf_counter()
        self.c0 = _compile_s[0]
        return self

    def __exit__(self, *exc):
        if exc[0] is None:
            print(f"   {self.name}: {time.perf_counter() - self.t0:.1f} s "
                  f"wall, {_compile_s[0] - self.c0:.1f} s compiling",
                  flush=True)


def counted(fn, *args, **kwargs):
    """``(fn(*args, **kwargs), the program's counters it moved)``."""
    from repro import obs
    before = obs.counts()
    out = fn(*args, **kwargs)
    return out, {k: v - before.get(k, 0) for k, v in obs.counts().items()
                 if v != before.get(k, 0)}


def routes(delta: dict, stage: str) -> dict:
    """``{route: images}`` of one entropy stage in a counter delta."""
    head = f"entropy.{stage}."
    return {k[len(head):]: v for k, v in delta.items()
            if k.startswith(head) and ".device." not in k}


def check_resolved_on_device(name: str, took: dict, dec: dict) -> None:
    """Every stream unpacked on the device had its block chain resolved
    there too (``entropy.resolve.device``), none on the host."""
    unpacked = sum(n for how, n in took["unpack"].items() if how != "host")
    resolved = routes(dec, "resolve")
    print(f"     {name}: block chains resolved per route {resolved}")
    check(resolved.get("device", 0) == unpacked and
          not resolved.get("host"),
          f"{name}: {unpacked} streams unpacked on the device, chains "
          f"resolved {resolved}")


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------

def cpu_reference(imgs, transform, mode):
    """The staged core.codec path on the host CPU: (levels, rec, psnr)."""
    from repro.core import codec, dct, metrics
    cpu = jax.devices("cpu")[0]
    levels, recs, psnrs = [], [], []
    with jax.default_device(cpu):
        for im in imgs:
            c = codec.compress(np.asarray(im), QUALITY, transform)
            rec = codec.decompress(c, mode=mode)
            levels.append(np.asarray(dct.from_blocks(c.qcoeffs)))
            recs.append(np.asarray(rec))
            psnrs.append(float(metrics.psnr(np.asarray(im), rec)))
    return np.stack(levels), np.stack(recs), np.array(psnrs)


def phase_roundtrip() -> None:
    from repro.core import cordic, images
    from repro.kernels import common
    from repro.kernels.fused_codec import fused_codec
    from repro.serve import codec_engine as eng

    check(not common.interpret_default(), "Pallas would run interpreted")
    lena = np.stack([images.lena_like(512, 512, seed=i) for i in range(8)])
    cable = np.stack([images.cablecar_like(512, 480, seed=i)
                      for i in range(8)])
    big = images.lena_like(1024, 1024)[None]
    cases = [("8 x lena 512x512, exact", lena, "exact", "standard"),
             ("8 x lena 512x512, cordic matched", lena, "cordic", "matched"),
             ("8 x cablecar 512x480, exact", cable, "exact", "standard"),
             ("1 x lena 1024x1024, exact", big, "exact", "standard")]
    for name, imgs, transform, mode in cases:
        check(eng._fused_ok(transform, mode),
              f"{name}: the fused route is not taken")
        hlo = eng._fused_roundtrip_sharded.lower(
            imgs, transform=transform, quality=QUALITY,
            cordic_config=cordic.PAPER_CONFIG, n_dev=1).as_text()
        check("tpu_custom_call" in hlo,
              f"{name}: no Pallas kernel in the fused program")
        t0 = time.perf_counter()
        (rec, psnr), delta = counted(eng.roundtrip_batch, imgs, QUALITY,
                                     transform, mode=mode)
        rec = np.asarray(rec)
        dt = time.perf_counter() - t0
        check(delta.get("engine.roundtrip.fused", 0) > 0,
              f"{name}: roundtrip_batch did not call the fused kernel")
        check(delta.get("engine.reassemble.direct", 0) == 1
              and "engine.reassemble.per_image" not in delta,
              f"{name}: the stacked roundtrip was not returned as the "
              f"fused program's output")
        _, qc = fused_codec(imgs, quality=QUALITY, transform=transform)
        qc = np.asarray(qc)
        ref_levels, ref_rec, ref_psnr = cpu_reference(imgs, transform,
                                                      mode)
        diff = np.abs(qc.astype(np.int64) - ref_levels)
        n_diff = int((diff > 0).sum())
        frac = n_diff / diff.size
        dpsnr = float(np.abs(psnr - ref_psnr).max())
        print(f"   {name}: {dt:.2f} s (first call, compile included)")
        print(f"     PSNR chip  {np.round(psnr, 4).tolist()}")
        print(f"     PSNR cpu   {np.round(ref_psnr, 4).tolist()}")
        print(f"     max |dPSNR| {dpsnr:.6f} dB; levels differing "
              f"{n_diff} of {diff.size} ({frac:.2e}), max |diff| "
              f"{int(diff.max())}; rec pixels differing "
              f"{int((rec != ref_rec).sum())}")
        check(rec.shape == ref_rec.shape and np.isfinite(psnr).all(),
              f"{name}: bad output shape or PSNR")
        check(dpsnr <= TOL_PSNR_DB, f"{name}: PSNR differs by "
              f"{dpsnr} dB from the CPU")
        check(frac <= TOL_LEVEL_FRACTION and int(diff.max()) <= 1,
              f"{name}: {n_diff} levels differ (max {diff.max()})")


def phase_bytes() -> None:
    from repro.core import entropy, images
    from repro.kernels import symbolize, unpack_bits
    from repro.serve import codec_engine as eng

    unpacker = unpack_bits.make_unpacker()
    check(unpacker is not None, "decode does not route to the device")
    batches = [("8 x 256x256", [images.lena_like(256, 256, seed=i)
                                for i in range(8)], True),
               ("4 x 512x512", [images.lena_like(512, 512, seed=10 + i)
                                for i in range(4)], False)]
    for name, imgs, must_be_device in batches:
        stacked = np.stack(imgs)
        n_blocks = (imgs[0].shape[0] // 8) * (imgs[0].shape[1] // 8)
        t0 = time.perf_counter()
        blobs, enc = counted(eng.encode_batch, stacked, QUALITY)
        t_enc = time.perf_counter() - t0
        t0 = time.perf_counter()
        recs, dec = counted(eng.decode_batch, blobs)
        t_dec = time.perf_counter() - t0
        took = {stage: routes(enc if stage != "unpack" else dec, stage)
                for stage in ("symbolize", "pack", "unpack")}
        print(f"   {name}: encode_batch {t_enc:.2f} s, decode_batch "
              f"{t_dec:.2f} s (first calls, compile included); "
              f"{n_blocks} blocks/image vs MAX_DEVICE_BLOCKS="
              f"{symbolize.MAX_DEVICE_BLOCKS}; images per route {took}")
        if n_blocks > symbolize.MAX_DEVICE_BLOCKS:
            print(f"     above the device guard: symbolize runs on the "
                  f"host for this size (ROADMAP A5)")
            check(took["symbolize"] == {"host": len(imgs)},
                  f"{name}: symbolize took {took['symbolize']}, not the "
                  f"host for every image")
        if must_be_device:
            for stage, by_route in took.items():
                check(by_route == {"pallas": len(imgs)},
                      f"{name}: {stage} took {by_route}, not the compiled "
                      f"Pallas kernel for every image")
        check_resolved_on_device(name, took, dec)
        cb = eng.compress_batch(stacked, QUALITY)
        levels = np.asarray(jax.device_get(cb.groups[0].qcoeffs))
        ref_recs = eng.decompress_batch(cb)
        shape = imgs[0].shape
        for i, (blob, q) in enumerate(zip(blobs, levels)):
            host = entropy.encode_qcoeffs(q, QUALITY, "exact", shape,
                                          packer=None, symbolizer=None)
            check(blob == host, f"{name} image {i}: bytes differ from the "
                  f"host NumPy coder on the same levels")
            back, _ = entropy.decode_qcoeffs(blob, unpacker=unpacker)
            check(np.array_equal(np.asarray(back), q),
                  f"{name} image {i}: decode does not give the levels "
                  f"back")
            check(np.array_equal(np.asarray(recs[i]),
                                 np.asarray(ref_recs[i])),
                  f"{name} image {i}: decode_batch differs from "
                  f"decompress_batch")
            print(f"     image {i} {shape[0]}x{shape[1]}: {len(blob)} B")


async def _serve(imgs, tiers):
    from repro.serve.admission import TenantTier
    from repro.serve.service import CodecService, ServiceConfig
    cfg = ServiceConfig(max_batch=8, max_queue_depth=64,
                        default_deadline_s=None,
                        tenants={"gold": TenantTier(max_quality=100),
                                 "free": TenantTier(max_quality=40)})
    async with CodecService(cfg) as svc:
        resps = await asyncio.gather(*[
            svc.submit(im, quality=75, tenant=t, deadline_s=None)
            for im, t in zip(imgs, tiers)])
        return resps, svc.stats


def phase_service() -> None:
    from repro.core import images
    from repro.serve import codec_engine as eng
    sizes = [(256, 256), (512, 512), (512, 480), (1024, 814)]
    imgs, tiers = [], []
    for i in range(32):
        h, w = sizes[i % 4]
        gen = images.lena_like if i % 2 else images.cablecar_like
        imgs.append(gen(h, w, seed=100 + i))
        tiers.append("gold" if (i // 4) % 2 else "free")
    t0 = time.perf_counter()
    resps, stats = asyncio.run(_serve(imgs, tiers))
    dt = time.perf_counter() - t0
    snap = stats.snapshot()
    print(f"   32 requests in {dt:.2f} s; served {snap['served']} of "
          f"{snap['submitted']}, failed {snap['failed']}, engine "
          f"failures {snap['engine_failures']}, unhandled "
          f"{snap['unhandled']}, rejected {snap['rejected']}; occupancy "
          f"{snap['occupancy']}")
    check(stats.served == stats.submitted == 32, "not every request served")
    check(stats.failed == stats.engine_failures == stats.unhandled == 0,
          "the service recorded failures")
    check(not stats.rejected, f"rejects: {dict(stats.rejected)}")
    qualities = collections.Counter(r.quality for r in resps)
    print(f"   qualities served: {dict(qualities)}")
    for i, (im, r) in enumerate(zip(imgs, resps)):
        want = eng.encode_batch([im], r.quality)[0]
        check(r.payload == want, f"request {i}: payload differs from "
              f"serial encode_batch")


COLOUR_QUALITY = 75
COLOUR_TOL = 1e-3    # float32 round-off, in levels and in pixel values


def phase_colour() -> None:
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
    from perfbench import colour
    from perfbench import reference_colour as rc
    from repro.serve import codec_engine as eng

    def gap(x, n):
        return float(np.maximum(0.0, np.abs(x - n) - 0.5).max())
    imgs = np.stack([colour.colour_image(g, 512, 768, seed=40 + i)
                     for i, g in enumerate(["lena_like", "cablecar_like"])])
    for rnd in ("first call, compile included", "second call"):
        t0 = time.perf_counter()
        blobs, enc = counted(eng.encode_batch, imgs, COLOUR_QUALITY)
        t_enc = time.perf_counter() - t0
        t0 = time.perf_counter()
        recs, dec = counted(eng.decode_batch, blobs)
        recs = [np.asarray(r) for r in recs]
        t_dec = time.perf_counter() - t0
        took = {stage: routes(enc if stage != "unpack" else dec, stage)
                for stage in ("symbolize", "pack", "unpack")}
        print(f"   2 x 768x512 RGB ({rnd}): encode_batch {t_enc:.2f} s, "
              f"decode_batch {t_dec:.2f} s; images per route {took}")
    check(took["unpack"] == {"pallas": len(imgs)},
          f"colour unpack took {took['unpack']}, not the compiled kernel")
    check_resolved_on_device("colour", took, dec)
    check(enc.get("engine.images.colour.encoded") == len(imgs) and
          dec.get("engine.images.colour.decoded") == len(imgs),
          "the colour counters did not count every image")
    for i, (im, blob, rec) in enumerate(zip(imgs, blobs, recs)):
        hdr, levels = rc.parse_dctz3(blob)
        lg = max(gap(x, n) for x, n in zip(
            rc.unrounded_levels(im, COLOUR_QUALITY), levels))
        check(rec.shape == (512, 768, 3), f"image {i}: shape {rec.shape}")
        pg = gap(rc.unrounded_rgb(levels, COLOUR_QUALITY)[:512, :768], rec)
        print(f"     image {i}: {len(blob)} B, level gap {lg:.3g}, pixel "
              f"gap {pg:.3g}")
        check(lg <= COLOUR_TOL and pg <= COLOUR_TOL,
              f"image {i}: the colour path departs from the reference")


def phase_four_chips() -> None:
    from repro.core import codec, images
    from repro.kernels.fused_codec import fused_codec
    from repro.serve import codec_engine as eng

    n_dev = len(jax.devices())
    check(n_dev == 4, f"--chips 4 needs 4 devices, found {n_dev}")
    imgs = np.stack([images.lena_like(512, 512, seed=i) for i in range(16)])
    t0 = time.perf_counter()
    rec, psnr = eng.roundtrip_batch(imgs, QUALITY, "exact")
    print(f"   roundtrip_batch 16 x 512x512 over {n_dev} devices: "
          f"{time.perf_counter() - t0:.2f} s (compile included); output "
          f"on devices {sorted(d.id for d in rec.devices())}")
    one = jax.devices()[0]
    rec = np.asarray(rec)
    for i, im in enumerate(imgs):
        single, _ = fused_codec(jax.device_put(im[None], one),
                                quality=QUALITY)
        check(np.array_equal(rec[i], np.asarray(single)[0]),
              f"image {i}: sharded fused roundtrip differs from one device")
        ref, ref_psnr = codec.roundtrip(jax.device_put(im, one), QUALITY)
        check(abs(psnr[i] - ref_psnr) <= TOL_PSNR_DB,
              f"image {i}: PSNR {psnr[i]} vs staged {ref_psnr}")
    print(f"   fused roundtrip identical to one device for all 16; PSNR "
          f"{np.round(psnr, 3).tolist()}")
    t0 = time.perf_counter()
    cb = eng.compress_batch(imgs, QUALITY)
    blobs, delta = counted(eng.encode_batch, imgs, QUALITY)
    print(f"   encode_batch: {time.perf_counter() - t0:.2f} s; levels on "
          f"devices {sorted(d.id for d in cb.groups[0].qcoeffs.devices())}")
    levels = np.asarray(jax.device_get(cb.groups[0].qcoeffs))
    for i, im in enumerate(imgs):
        c = codec.compress(jax.device_put(im, one), QUALITY)
        check(np.array_equal(levels[i], np.asarray(c.qcoeffs)),
              f"image {i}: sharded levels differ from one device")
        check(blobs[i] == c.to_bytes(), f"image {i}: bytes differ from "
              f"per-image compress().to_bytes()")
    kdev = {k[len("entropy."):]: n for k, n in delta.items()
            if ".device." in k}
    print(f"   levels and bytes identical to one device for all 16; "
          f"entropy-kernel launches by device: {kdev}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the engine's sharded 4-device path")
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (first device is {dev.platform}); "
              f"nothing was run", file=sys.stderr)
        return 1
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    from repro import compile_cache
    cache = compile_cache.enable()
    print(f"device {dev.device_kind} x {len(jax.devices())}, jax "
          f"{jax.__version__}, compile cache {cache}", flush=True)

    from repro import obs
    t0 = time.perf_counter()
    if args.chips == 4:
        with Phase("4 devices: sharded roundtrip and encode"):
            phase_four_chips()
    else:
        with Phase("1 roundtrip, fused kernel route"):
            phase_roundtrip()
        with Phase("2 bytes, entropy encode and decode"):
            phase_bytes()
        with Phase("3 service"):
            phase_service()
        with Phase("4 colour, YCbCr 4:2:0 encode and decode"):
            phase_colour()
    interp = sorted(k for k in obs.counts() if k.endswith(".interpret"))
    check(not interp, f"kernels ran in interpret mode: {interp}")
    print(f"total {time.perf_counter() - t0:.1f} s, of which "
          f"{_compile_s[0]:.1f} s compiling", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
