"""Pallas TPU kernels for the compute hot-spots the paper optimises.

Each subpackage follows the kernel.py (pl.pallas_call + BlockSpec) /
ops.py (jit wrapper) / ref.py (pure-jnp oracle) layout:

  dct8x8          blockwise 2-D DCT/IDCT (separable, over row phases)
  cordic_loeffler paper-faithful Cordic-based Loeffler DCT (VPU shift-add)
  fused_codec     DCT->quant->dequant->IDCT in one HBM round-trip
  grad_dct        DCT-domain gradient compression (encode/decode)
  pack_bits       entropy-stage bit packing (prefix-sum + scatter); its
                  ref.py is staged NumPy, not jnp — the oracle must be
                  byte-exact, and bytes are a host-edge artifact
  unpack_bits     entropy-stage speculative Huffman decode (per-offset
                  unit words + a bounded forward walk of every chain,
                  resolved per block on the host); staged NumPy ref.py
                  (pointer doubling) for the same reason

`tuning` is the shared tuned-tile lookup: when an ops.py router's tile
knob is left at None it consults the autotuned winners persisted in
``results/tuning.json`` (written by ``python -m repro.bench autotune``),
falling back to built-in defaults — with a single warning — when the
artifact is missing, invalid, or tuned for a different backend.
"""
