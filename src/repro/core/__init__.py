"""repro.core — the paper's contribution: blockwise DCT image compression.

Modules:
  dct       exact orthonormal DCT (matrix + Kronecker MXU forms)
  loeffler  Loeffler 8-point flow graph (exact rotations)
  cordic    CORDIC micro-rotation approximation (the paper's variant)
  quant     JPEG-style quantiser
  codec     compress / decompress / roundtrip pipeline
  metrics   PSNR / MSE per the paper's definitions
  images    synthetic stand-ins for the paper's test images
  entropy   lossless bitstream tail (jax-free at import)

Submodules load lazily (PEP 562): ``from repro.core import dct`` works
exactly as before, but ``import repro.core.entropy`` does not drag in
the jax array stack: the entropy stage's host halves stay NumPy-only.
"""

_SUBMODULES = ("codec", "cordic", "dct", "entropy", "images", "loeffler",
               "metrics", "quant")


def __getattr__(name):
    if name in _SUBMODULES:
        import importlib
        module = importlib.import_module(f"repro.core.{name}")
        globals()[name] = module
        return module
    raise AttributeError(f"module 'repro.core' has no attribute {name!r}")


def __dir__():
    return sorted(set(globals()) | set(_SUBMODULES))
