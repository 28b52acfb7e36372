"""Async image-compression service demo — concurrent clients, real SLOs.

Spins up the asyncio :class:`repro.serve.service.CodecService` in front
of the multi-device codec engine and drives it with N closed-loop
clients submitting mixed-size images under per-request deadlines and
per-tenant quality tiers ("gold" keeps its requested quality, "free" is
clamped to quality 40).  The service buckets requests by (shape,
quality), batches adaptively (bucket full / deadline urgent / max-wait
timer), sheds load with explicit rejects when queues fill, and serves
repeated images from its hot-stream cache.

Prints per-tenant outcomes plus the service-side stats: p50/p99
latency, batch-occupancy histogram, reject reasons, cache hits.

    PYTHONPATH=src python examples/image_codec_service.py
    PYTHONPATH=src python examples/image_codec_service.py \
        --clients 8 --requests 12 --deadline-ms 500
"""

import argparse
import asyncio
import collections
import time

import numpy as np

from repro import compile_cache
from repro.core import images
from repro.serve.admission import RejectedError, TenantTier
from repro.serve.service import CodecService, ServiceConfig


def make_pool(size: int, variants: int = 6):
    """A small pool of mixed-size test images; reuse produces cache hits."""
    pool = []
    for i in range(variants):
        gen = images.lena_like if i % 2 == 0 else images.cablecar_like
        h = size - 16 * (i % 3)          # e.g. 128 / 112 / 96
        w = size - 10 * (i % 4)
        pool.append(np.asarray(gen(h, w, seed=i)))
    return pool


async def client(svc: CodecService, name: str, tenant: str, pool,
                 requests: int, deadline_s: float, quality: int,
                 rng: np.random.Generator, outcomes: collections.Counter):
    """One closed-loop client: submit, await the outcome, think, repeat."""
    for _ in range(requests):
        img = pool[int(rng.integers(len(pool)))]
        try:
            resp = await svc.submit(img, quality=quality, tenant=tenant,
                                    deadline_s=deadline_s)
            tag = "cache" if resp.cache_hit else f"batch{resp.batch_size}"
            outcomes[f"{tenant}:served"] += 1
            outcomes[f"{tenant}:bytes"] += len(resp.payload)
            if resp.deadline_missed:
                outcomes[f"{tenant}:late"] += 1
            print(f"  {name}: {img.shape[0]}x{img.shape[1]} q{resp.quality}"
                  f" -> {len(resp.payload)} B ({tag},"
                  f" {resp.latency_s * 1e3:.1f} ms)")
        except RejectedError as exc:
            outcomes[f"{tenant}:rejected:{exc.reason}"] += 1
            print(f"  {name}: rejected ({exc.reason})")
        await asyncio.sleep(float(rng.uniform(0, 0.01)))   # think time


async def run(args):
    pool = make_pool(args.size)
    cfg = ServiceConfig(
        max_batch=args.max_batch,
        max_wait_s=args.max_wait_ms / 1e3,
        max_queue_depth=4 * args.max_batch,
        default_deadline_s=args.deadline_ms / 1e3,
        tenants={"gold": TenantTier(max_quality=100),
                 "free": TenantTier(max_quality=40)},
    )
    outcomes = collections.Counter()
    rng = np.random.default_rng(0)
    t0 = time.monotonic()
    async with CodecService(cfg) as svc:
        # warm the engine once so client latencies reflect steady state
        await svc.submit(pool[0], deadline_s=None)
        tasks = []
        for i in range(args.clients):
            tenant = "gold" if i % 2 == 0 else "free"
            tasks.append(client(
                svc, f"client{i}", tenant, pool, args.requests,
                args.deadline_ms / 1e3, args.quality,
                np.random.default_rng(100 + i), outcomes))
        await asyncio.gather(*tasks)
        stats = svc.stats.snapshot()
        cache = svc.cache
    dt = time.monotonic() - t0

    print(f"\n{args.clients} clients x {args.requests} requests "
          f"in {dt:.2f}s")
    for tenant in ("gold", "free"):
        served = outcomes[f"{tenant}:served"]
        if not served:
            continue
        print(f"  {tenant}: {served} served "
              f"({outcomes[f'{tenant}:late']} late), "
              f"{outcomes[f'{tenant}:bytes'] / served:.0f} B avg")
    print(f"  latency p50/p99: {stats['p50_latency_s'] * 1e3:.1f} / "
          f"{stats['p99_latency_s'] * 1e3:.1f} ms")
    print(f"  batch occupancy: {stats['occupancy']}")
    print(f"  rejected: {stats['rejected'] or 'none'}; "
          f"cache hits: {cache.hits}/{cache.hits + cache.misses}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--clients", type=int, default=6)
    ap.add_argument("--requests", type=int, default=8,
                    help="requests per client")
    ap.add_argument("--size", type=int, default=128)
    ap.add_argument("--quality", type=int, default=75,
                    help="requested quality (tiers may clamp)")
    ap.add_argument("--deadline-ms", type=float, default=1000.0)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-wait-ms", type=float, default=10.0)
    args = ap.parse_args()
    asyncio.run(run(args))


if __name__ == "__main__":
    compile_cache.enable()
    main()
