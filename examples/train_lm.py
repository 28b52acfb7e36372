"""End-to-end training driver: train a smollm-family LM on the synthetic
Markov corpus with checkpointing and (optionally) DCT gradient compression,
then compare the two loss curves.

Default size is CPU-friendly (~5M params, 150 steps, a few minutes).
``--scale 100m --steps 300`` reproduces the brief's ~100M-for-a-few-hundred-
steps run on real hardware (on this CPU container it is hours, not run by
default — EXPERIMENTS.md records a mid-scale run).

    PYTHONPATH=src python examples/train_lm.py --steps 150 --compare-compress
"""

import argparse
import dataclasses
import tempfile

import jax

from repro import compile_cache
from repro.configs import registry as R
from repro.data.synth import DataConfig, make_batch_fn
from repro.optim.adamw import AdamWConfig
from repro.optim.grad_compress import GradCompressConfig
from repro.train.step import TrainStepConfig
from repro.train.trainer import Trainer, TrainerConfig

SCALES = {
    # name: (layers, d_model, heads, kv, d_ff, vocab, seq, batch)
    "5m": (4, 256, 4, 2, 1024, 2048, 128, 8),
    "25m": (8, 512, 8, 4, 2048, 8192, 256, 8),
    "100m": (12, 768, 12, 4, 3072, 32768, 512, 16),
}


def build(scale: str):
    ll, d, h, kv, ff, v, s, b = SCALES[scale]
    cfg = R.reduced("smollm-360m", n_layers=ll, d_model=d, n_heads=h,
                    n_kv_heads=kv, head_dim=d // h, d_ff=ff, vocab_size=v)
    data = DataConfig(vocab_size=v, seq_len=s, global_batch=b, seed=0)
    return cfg, data


def run_one(cfg, data, steps, compress, ckpt_dir=None, label=""):
    gc = GradCompressConfig(enabled=compress, keep=16, min_size=4096)
    tr = Trainer(
        cfg,
        AdamWConfig(lr_peak=1e-3, warmup_steps=max(steps // 20, 5),
                    decay_steps=steps),
        TrainerConfig(total_steps=steps, ckpt_dir=ckpt_dir, ckpt_every=50,
                      log_every=max(steps // 10, 1)),
        make_batch_fn(data),
        step_cfg=TrainStepConfig(grad_compress=gc))
    print(f"--- {label}: {cfg.param_count()/1e6:.1f}M params, "
          f"{steps} steps, compress={compress}")
    return tr.run()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", default="5m", choices=list(SCALES))
    ap.add_argument("--steps", type=int, default=150)
    ap.add_argument("--compare-compress", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()

    cfg, data = build(args.scale)
    h_base = run_one(cfg, data, args.steps, False, args.ckpt_dir, "baseline")
    print(f"baseline   loss: {h_base[0]['loss']:.4f} -> "
          f"{h_base[-1]['loss']:.4f}")

    if args.compare_compress:
        h_comp = run_one(cfg, data, args.steps, True, None,
                         "dct-compressed grads (keep=16/64, 12.8x wire)")
        print(f"compressed loss: {h_comp[0]['loss']:.4f} -> "
              f"{h_comp[-1]['loss']:.4f}")
        gap = h_comp[-1]["loss"] - h_base[-1]["loss"]
        print(f"convergence gap at step {args.steps}: {gap:+.4f} "
              f"(keep={16}/64 => 12.8x fewer wire bytes; error feedback "
              f"shrinks the gap over longer horizons)")


if __name__ == "__main__":
    compile_cache.enable()
    main()
