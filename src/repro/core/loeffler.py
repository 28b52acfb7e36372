"""Loeffler 8-point DCT flow graph (exact, 11-multiplication form).

This is the factorisation the paper's "Cordic based Loeffler DCT" is derived
from (Loeffler/Ligtenberg/Moshytz 1989; Sun/Heyne/Ruan/Götze 2006).  The graph
has 4 serial stages (the paper notes the stages are data-dependent and must
execute serially, while everything *inside* a stage is parallel):

  stage 1: 4 input butterflies  (x_i ± x_{7-i})
  stage 2: even: 2 butterflies · odd: two plane rotations (3π/16 and π/16)
  stage 3: even: butterfly + one rotation (π/8) · odd: 4 butterflies
  stage 4: odd: two √2 output scalings

Outputs here are **orthonormal** (same convention as core.dct), so this graph
is bit-comparable with ``dct.dct1d`` up to float round-off — the unit tests
assert that.  The CORDIC variant replaces the three plane rotations with
shift-add micro-rotations (see core.cordic); the rotation call is injectable
via ``rotate_fn`` precisely so both variants share one graph definition.
"""

from __future__ import annotations

import math
from typing import Callable

import jax.numpy as jnp

# Rotation angles used by the graph.
THETA_ODD_A = 3.0 * math.pi / 16.0   # rotates (d3, d0)
THETA_ODD_B = 1.0 * math.pi / 16.0   # rotates (d2, d1)
THETA_EVEN = math.pi / 8.0           # rotates (b2, b3) -> (X2, X6)

_SQRT2 = math.sqrt(2.0)
_INV_2SQRT2 = 1.0 / (2.0 * _SQRT2)


def exact_rotate(u: jnp.ndarray, v: jnp.ndarray, theta: float):
    """Plane rotation: (u, v) -> (u cosθ + v sinθ, -u sinθ + v cosθ)."""
    c, s = math.cos(theta), math.sin(theta)
    return u * c + v * s, -u * s + v * c


RotateFn = Callable[[jnp.ndarray, jnp.ndarray, float], tuple]


def dct8_terms(xs, rotate_fn: RotateFn = exact_rotate,
               quantize_fn=None) -> list:
    """Loeffler 8-point DCT-II over a list of 8 equally-shaped arrays.

    ``xs[i]`` holds input sample ``i`` of every transform at once; the
    result lists output coefficient ``k`` the same way.  This is the
    graph itself: :func:`loeffler_dct8` stacks it along an array axis,
    and the Pallas kernels apply it to the 8 row (or column) phases of
    a VMEM tile, so both run the same operations in the same order.
    """
    q = quantize_fn if quantize_fn is not None else (lambda t: t)
    x0, x1, x2, x3, x4, x5, x6, x7 = xs

    # ---- stage 1: butterflies ------------------------------------------
    a0 = q(x0 + x7)
    a1 = q(x1 + x6)
    a2 = q(x2 + x5)
    a3 = q(x3 + x4)
    d3 = q(x3 - x4)   # a4 in the paper's figure
    d2 = q(x2 - x5)   # a5
    d1 = q(x1 - x6)   # a6
    d0 = q(x0 - x7)   # a7

    # ---- stage 2: even butterflies · odd rotations ---------------------
    b0 = q(a0 + a3)
    b1 = q(a1 + a2)
    b2 = q(a1 - a2)
    b3 = q(a0 - a3)
    r4, r7 = rotate_fn(d3, d0, THETA_ODD_A)   # c3-rotator
    r5, r6 = rotate_fn(d2, d1, THETA_ODD_B)   # c1-rotator

    # ---- stage 3: even output butterfly + rotation · odd butterflies ---
    y0 = q(b0 + b1)
    y4 = q(b0 - b1)
    c4 = q(r4 + r6)
    c5 = q(r7 - r5)
    c6 = q(r4 - r6)
    c7 = q(r7 + r5)

    # Even rotation outputs: X2 = (b3 cos(π/8) + b2 sin(π/8)) / 2 and
    # X6 = (b3 sin(π/8) - b2 cos(π/8)) / 2, i.e. the plane rotation applied
    # to the swapped pair (b3, b2):
    z2, z6 = rotate_fn(b3, b2, THETA_EVEN)
    # z2 = b3 c + b2 s = 2·X2;  z6 = -b3 s + b2 c = -2·X6

    # ---- stage 4: output scalings --------------------------------------
    out = [None] * 8
    out[0] = q(y0 * _INV_2SQRT2)
    out[4] = q(y4 * _INV_2SQRT2)
    out[2] = q(z2 * 0.5)
    out[6] = q(-z6 * 0.5)
    out[1] = q((c4 + c7) * _INV_2SQRT2)
    out[7] = q((c7 - c4) * _INV_2SQRT2)
    out[3] = q(c5 * 0.5)
    out[5] = q(c6 * 0.5)
    return out


def idct8_terms(ys, rotate_fn: RotateFn = exact_rotate,
                quantize_fn=None) -> list:
    """Inverse (DCT-III) of :func:`dct8_terms` via the transposed graph.

    For the exact rotation the graph is orthonormal so the inverse is the
    exact transpose; we implement the transpose explicitly (stages reversed,
    butterflies transposed, rotations by -θ) so that the CORDIC variant's
    inverse uses CORDIC rotations too — matching the paper's pipeline where
    the IDCT kernel is also CORDIC-based.
    """
    q = quantize_fn if quantize_fn is not None else (lambda t: t)
    Y0, Y1, Y2, Y3, Y4, Y5, Y6, Y7 = ys

    # transpose of stage 4
    y0 = q(Y0 * _INV_2SQRT2)
    y4 = q(Y4 * _INV_2SQRT2)
    c4 = q((Y1 - Y7) * _INV_2SQRT2)
    c7 = q((Y1 + Y7) * _INV_2SQRT2)
    c5 = q(Y3 * 0.5)
    c6 = q(Y5 * 0.5)
    z2 = q(Y2 * 0.5)
    z6 = q(-Y6 * 0.5)

    # transpose of stage 3
    b0 = q(y0 + y4)
    b1 = q(y0 - y4)
    # (z2, z6) = R(θ) @ (b3, b2)  =>  (b3, b2) = R(-θ) @ (z2, z6)
    b3, b2 = rotate_fn(z2, z6, -THETA_EVEN)
    r4 = q(c4 + c6)
    r6 = q(c4 - c6)
    r7 = q(c7 + c5)
    r5 = q(c7 - c5)

    # transpose of stage 2
    a0 = q(b0 + b3)
    a3 = q(b0 - b3)
    a1 = q(b1 + b2)
    a2 = q(b1 - b2)
    d3, d0 = rotate_fn(r4, r7, -THETA_ODD_A)
    d2, d1 = rotate_fn(r5, r6, -THETA_ODD_B)

    # transpose of stage 1 (plain butterfly transpose — the orthonormal
    # scaling was already applied by the diagonal above)
    x0 = q(a0 + d0)
    x7 = q(a0 - d0)
    x1 = q(a1 + d1)
    x6 = q(a1 - d1)
    x2 = q(a2 + d2)
    x5 = q(a2 - d2)
    x3 = q(a3 + d3)
    x4 = q(a3 - d3)

    return [x0, x1, x2, x3, x4, x5, x6, x7]


def _along(fn, x: jnp.ndarray, axis: int, rotate_fn, quantize_fn):
    x = jnp.moveaxis(x, axis, 0)
    if x.shape[0] != 8:
        raise ValueError(f"Loeffler graph needs a length-8 axis, got "
                         f"{x.shape}")
    out = fn([x[i] for i in range(8)], rotate_fn, quantize_fn)
    return jnp.moveaxis(jnp.stack(out, axis=0), 0, axis)


def loeffler_dct8(x: jnp.ndarray, axis: int = -1,
                  rotate_fn: RotateFn = exact_rotate,
                  quantize_fn=None) -> jnp.ndarray:
    """Orthonormal 8-point DCT-II along ``axis`` via the Loeffler graph.

    ``rotate_fn(u, v, theta)`` implements the plane rotation; pass
    ``cordic.cordic_rotate`` to obtain the paper's Cordic-based variant.
    ``quantize_fn`` (optional) is applied to every stage output, emulating
    the fixed-point register grid of the short-word-length hardware the
    Cordic-Loeffler design targets (see core.cordic.fixed_quantizer).
    """
    return _along(dct8_terms, x, axis, rotate_fn, quantize_fn)


def loeffler_idct8(y: jnp.ndarray, axis: int = -1,
                   rotate_fn: RotateFn = exact_rotate,
                   quantize_fn=None) -> jnp.ndarray:
    """Inverse (DCT-III) of :func:`loeffler_dct8` along ``axis``."""
    return _along(idct8_terms, y, axis, rotate_fn, quantize_fn)


def loeffler_dct2d_8x8(blocks: jnp.ndarray,
                       rotate_fn: RotateFn = exact_rotate,
                       quantize_fn=None) -> jnp.ndarray:
    """2-D 8x8 DCT on (..., 8, 8) blocks via two separable graph passes."""
    once = loeffler_dct8(blocks, axis=-1, rotate_fn=rotate_fn,
                         quantize_fn=quantize_fn)
    return loeffler_dct8(once, axis=-2, rotate_fn=rotate_fn,
                         quantize_fn=quantize_fn)


def loeffler_idct2d_8x8(coeffs: jnp.ndarray,
                        rotate_fn: RotateFn = exact_rotate,
                        quantize_fn=None) -> jnp.ndarray:
    """Inverse of :func:`loeffler_dct2d_8x8`."""
    once = loeffler_idct8(coeffs, axis=-2, rotate_fn=rotate_fn,
                          quantize_fn=quantize_fn)
    return loeffler_idct8(once, axis=-1, rotate_fn=rotate_fn,
                          quantize_fn=quantize_fn)
