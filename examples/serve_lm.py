"""Batched serving demo: prefill + KV-cached decode, with and without DCT
KV-cache compression.

    PYTHONPATH=src python examples/serve_lm.py --batch 4 --max-new 24
"""

import argparse
import time

import jax
import jax.numpy as jnp

from repro import compile_cache
from repro.configs import registry as R
from repro.models import registry as M
from repro.serve import engine, kv_compress


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=96)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--kv-keep", type=int, default=24)
    args = ap.parse_args()

    cfg = R.reduced(args.arch, n_layers=4, d_model=128, vocab_size=1024)
    params = M.init_params(cfg, jax.random.key(0))
    prompts = jax.random.randint(jax.random.key(1),
                                 (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size)
    max_len = args.prompt_len + args.max_new + 8

    # ---- exact cache -------------------------------------------------------
    cache = M.init_cache(cfg, batch=args.batch, max_len=max_len)
    prefill = engine.make_prefill(cfg)
    step = engine.make_decode_step(cfg)
    logits, cache = prefill(params, prompts, cache)
    nxt = jnp.argmax(logits.astype(jnp.float32), -1).astype(jnp.int32)
    t0 = time.monotonic()
    toks = [nxt]
    for i in range(args.max_new - 1):
        nxt, cache = step(params, nxt[:, None], cache,
                          jnp.asarray(args.prompt_len + i, jnp.int32),
                          jax.random.key(0))
        toks.append(nxt)
    exact = jnp.stack(toks, 1)
    dt = time.monotonic() - t0
    print(f"exact cache:      {args.batch * args.max_new / dt:7.1f} tok/s")

    # ---- DCT-compressed cache ---------------------------------------------
    cache2 = M.init_cache(cfg, batch=args.batch, max_len=max_len)
    _, cache2 = prefill(params, prompts, cache2)
    raw = sum(v.size * v.dtype.itemsize for v in cache2.values())
    ckv, tails = kv_compress.compress_cache(cache2, args.kv_keep,
                                            args.prompt_len)
    comp = kv_compress.wire_bytes(ckv, tails)
    cache2 = kv_compress.reconstruct_cache(ckv, tails)
    logits2, _, _ = M.apply(cfg, params,
                            {"tokens": prompts[:, -1:],
                             "cache_index":
                                 jnp.asarray(args.prompt_len - 1, jnp.int32)},
                            mode="decode", cache=cache2)
    nxt2 = jnp.argmax(logits2[:, -1].astype(jnp.float32), -1)
    toks2 = [nxt2.astype(jnp.int32)]
    for i in range(args.max_new - 1):
        nxt2, cache2 = step(params, toks2[-1][:, None], cache2,
                            jnp.asarray(args.prompt_len + i, jnp.int32),
                            jax.random.key(0))
        toks2.append(nxt2)
    compd = jnp.stack(toks2, 1)
    agree = float((exact == compd).mean())
    print(f"dct cache (keep={args.kv_keep}/64): HBM {raw/comp:.1f}x smaller, "
          f"token agreement {agree:.0%}")
    print("sample exact :", exact[0, :12].tolist())
    print("sample dct   :", compd[0, :12].tolist())


if __name__ == "__main__":
    compile_cache.enable()
    main()
