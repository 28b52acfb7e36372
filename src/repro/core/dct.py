"""Exact DCT-II / DCT-III (inverse) transforms.

Conventions
-----------
All transforms here are *orthonormal* (DCT-II with alpha(0)=sqrt(1/N),
alpha(k)=sqrt(2/N)), so ``idct == dct.T`` and Parseval holds exactly:
``||dct(x)||_2 == ||x||_2``.  This is the reference ("exact DCT") path the
paper compares the Cordic-based Loeffler DCT against (paper eq. (3)/(6)).

Two blockwise formulations are provided — they are mathematically identical
but map differently onto TPU hardware (see DESIGN.md §2):

* separable:  ``Y = C @ X @ C.T`` per 8x8 block (two small matmuls),
* kron:       ``vec(Y) = (C ⊗ C) @ vec(X)`` — one (nblocks, 64) @ (64, 64)
              matmul, the staged codec path's form.

Every matmul pins ``Precision.HIGHEST``: on TPU the default f32 dot is a
single bf16 pass, which would move quantised levels (and so bytes and
PSNR) away from what the same code computes on the CPU.

The Pallas kernels use :func:`dct8_terms` / :func:`idct8_terms`, the same
8-point transform written over the 8 sample phases of a tile, so it runs
on the vector unit with no relayout of the 8x8 blocks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_HIGHEST = jax.lax.Precision.HIGHEST


@functools.lru_cache(maxsize=None)
def _dct_matrix_np(n: int) -> np.ndarray:
    """Orthonormal DCT-II matrix C, shape (n, n):  X = C @ x."""
    k = np.arange(n)[:, None].astype(np.float64)
    i = np.arange(n)[None, :].astype(np.float64)
    mat = np.cos(np.pi * k * (2.0 * i + 1.0) / (2.0 * n))
    mat *= np.sqrt(2.0 / n)
    mat[0] *= 1.0 / np.sqrt(2.0)
    return mat


def dct_matrix(n: int, dtype=jnp.float32) -> jnp.ndarray:
    """Orthonormal DCT-II matrix of size (n, n)."""
    return jnp.asarray(_dct_matrix_np(n), dtype=dtype)


def kron_dct_matrix(n: int = 8, dtype=jnp.float32) -> jnp.ndarray:
    """(n*n, n*n) operator T with vec(Y) = T @ vec(X) for Y = C X C^T.

    vec() is row-major.  T = kron(C, C).
    """
    c = _dct_matrix_np(n)
    return jnp.asarray(np.kron(c, c), dtype=dtype)


def dct8_terms(xs) -> list:
    """Orthonormal 8-point DCT-II over a list of 8 equally-shaped arrays.

    ``xs[j]`` holds sample ``j`` of every transform at once; output
    ``k`` is ``sum_j C[k, j] * xs[j]``, summed in ``j`` order.
    """
    c = _dct_matrix_np(8).astype(np.float32)
    out = []
    for k in range(8):
        acc = float(c[k, 0]) * xs[0]
        for j in range(1, 8):
            acc = acc + float(c[k, j]) * xs[j]
        out.append(acc)
    return out


def idct8_terms(ys) -> list:
    """Inverse of :func:`dct8_terms` (``C`` is orthonormal: ``C^T``)."""
    c = _dct_matrix_np(8).astype(np.float32)
    out = []
    for j in range(8):
        acc = float(c[0, j]) * ys[0]
        for k in range(1, 8):
            acc = acc + float(c[k, j]) * ys[k]
        out.append(acc)
    return out


def dct1d(x: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    """Orthonormal DCT-II along ``axis``."""
    n = x.shape[axis]
    c = dct_matrix(n, x.dtype)
    x = jnp.moveaxis(x, axis, -1)
    y = jnp.matmul(x, c.T, precision=_HIGHEST)
    return jnp.moveaxis(y, -1, axis)


def idct1d(x: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    """Orthonormal inverse DCT (DCT-III) along ``axis``."""
    n = x.shape[axis]
    c = dct_matrix(n, x.dtype)
    x = jnp.moveaxis(x, axis, -1)
    y = jnp.matmul(x, c, precision=_HIGHEST)
    return jnp.moveaxis(y, -1, axis)


def dct2d(x: jnp.ndarray) -> jnp.ndarray:
    """Orthonormal 2-D DCT-II over the last two axes (paper eq. (6))."""
    return dct1d(dct1d(x, axis=-1), axis=-2)


def idct2d(x: jnp.ndarray) -> jnp.ndarray:
    """Orthonormal 2-D inverse DCT over the last two axes."""
    return idct1d(idct1d(x, axis=-1), axis=-2)


# ---------------------------------------------------------------------------
# Blockwise forms
# ---------------------------------------------------------------------------

def to_blocks(img: jnp.ndarray, block: int = 8) -> jnp.ndarray:
    """(..., H, W) -> (..., H//b, W//b, b, b).  H, W must divide by b."""
    *lead, h, w = img.shape
    if h % block or w % block:
        raise ValueError(f"image {h}x{w} not divisible by block {block}")
    x = img.reshape(*lead, h // block, block, w // block, block)
    # (..., hb, b, wb, b) -> (..., hb, wb, b, b)
    return jnp.swapaxes(x, -3, -2)


def from_blocks(blocks: jnp.ndarray) -> jnp.ndarray:
    """Inverse of :func:`to_blocks`."""
    *lead, hb, wb, b, b2 = blocks.shape
    assert b == b2, blocks.shape
    x = jnp.swapaxes(blocks, -3, -2)
    return x.reshape(*lead, hb * b, wb * b)


def blockwise_dct2d(img: jnp.ndarray, block: int = 8) -> jnp.ndarray:
    """Blockwise 2-D DCT.  (..., H, W) -> (..., H//b, W//b, b, b) coeffs."""
    blocks = to_blocks(img, block)
    return dct2d(blocks)


def blockwise_idct2d(coeffs: jnp.ndarray) -> jnp.ndarray:
    """Inverse of :func:`blockwise_dct2d` back to (..., H, W)."""
    return from_blocks(idct2d(coeffs))


def blockwise_dct2d_kron(img: jnp.ndarray, block: int = 8) -> jnp.ndarray:
    """Same as :func:`blockwise_dct2d` via the single-matmul Kronecker form."""
    t = kron_dct_matrix(block, img.dtype)
    blocks = to_blocks(img, block)
    *lead, hb, wb, b, _ = blocks.shape
    flat = blocks.reshape(*lead, hb, wb, b * b)
    out = jnp.matmul(flat, t.T, precision=_HIGHEST)
    return out.reshape(*lead, hb, wb, b, b)


def blockwise_idct2d_kron(coeffs: jnp.ndarray) -> jnp.ndarray:
    """Inverse of :func:`blockwise_dct2d_kron` (T is orthonormal: inv = T.T)."""
    *lead, hb, wb, b, _ = coeffs.shape
    t = kron_dct_matrix(b, coeffs.dtype)
    flat = coeffs.reshape(*lead, hb, wb, b * b)
    out = jnp.matmul(flat, t, precision=_HIGHEST)
    return from_blocks(out.reshape(*lead, hb, wb, b, b))
