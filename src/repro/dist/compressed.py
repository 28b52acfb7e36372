"""Compressed cross-axis gradient exchange (shard_map collective).

What crosses the chosen mesh axis is the grad_dct wire format — int8 codes
of the first ``keep`` DCT coefficients per 64-sample block plus one f32
scale per block — not the raw f32 gradient.  Each participant projects its
error-feedback-corrected local gradient, all-gathers the codes, decodes
every participant's projection and averages, so all participants compute
the identical mean (no second collective needed).

The projection math mirrors ``kernels/grad_dct/ref.py`` in pure jnp: the
Pallas encode kernel is the single-device fast path, while inside shard_map
we want something every backend traces cheaply.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import dct
from repro.optim.grad_compress import GradCompressConfig

BLOCK = 64


def _encode(flat: jnp.ndarray, keep: int):
    """(N,) f32 -> ((R, keep) int8 codes, (R, 1) f32 scales, (T,) f32 tail)."""
    n = flat.shape[0]
    r = n // BLOCK
    body = flat[:r * BLOCK].reshape(r, BLOCK)
    tail = flat[r * BLOCK:]
    c = dct.dct_matrix(BLOCK, jnp.float32)
    kept = (body @ c.T)[:, :keep]
    scale = jnp.maximum(jnp.max(jnp.abs(kept), axis=-1, keepdims=True)
                        / 127.0, 1e-30)
    q = jnp.clip(jnp.round(kept / scale), -127.0, 127.0).astype(jnp.int8)
    return q, scale.astype(jnp.float32), tail


def _decode(q: jnp.ndarray, scale: jnp.ndarray, tail: jnp.ndarray,
            n: int) -> jnp.ndarray:
    c = dct.dct_matrix(BLOCK, jnp.float32)
    kept = q.astype(jnp.float32) * scale
    coef = jnp.pad(kept, ((0, 0), (0, BLOCK - q.shape[-1])))
    body = (coef @ c).reshape(-1)
    return jnp.concatenate([body, tail])[:n]


def compressed_mean_flat(g: jnp.ndarray, ef: jnp.ndarray, axis: str,
                         keep: int = 16):
    """EF-corrected compressed mean of a flat gradient over a mesh axis.

    Call inside shard_map.  Returns (mean, new_ef): ``mean`` is identical on
    every participant (decoded from the gathered codes); ``new_ef`` is the
    local residual the projection dropped.
    """
    n = g.shape[0]
    corrected = g.astype(jnp.float32) + ef
    q, scale, tail = _encode(corrected, keep)
    proj = _decode(q, scale, tail, n)
    new_ef = corrected - proj

    # int8 codes + f32 scales cross the axis; tails are exact (small).
    qg = jax.lax.all_gather(q, axis)
    sg = jax.lax.all_gather(scale, axis)
    tg = jax.lax.all_gather(tail, axis)
    mean = jax.vmap(lambda qq, ss, tt: _decode(qq, ss, tt, n))(
        qg, sg, tg).mean(axis=0)
    return mean, new_ef


def make_cross_axis_grad_sync(mesh, specs: dict, cfg: GradCompressConfig):
    """Tree-level grad sync: f(grads, ef) -> (mean_grads, new_ef).

    ``specs`` gives each leaf's PartitionSpec on ``mesh``; leaves below
    ``cfg.min_size`` (or with compression disabled) take an exact pmean
    over ``cfg.axis`` instead of the compressed exchange.
    """
    axis = cfg.axis

    def body(grads: dict, ef: dict):
        out_g, out_e = {}, {}
        for path, g in grads.items():
            e = ef[path]
            if not cfg.enabled or g.size < cfg.min_size:
                out_g[path] = jax.lax.pmean(g, axis)
                out_e[path] = e
            else:
                m, ne = compressed_mean_flat(
                    g.reshape(-1), e.reshape(-1).astype(jnp.float32),
                    axis, keep=cfg.keep)
                out_g[path] = m.reshape(g.shape).astype(g.dtype)
                out_e[path] = ne.reshape(e.shape)
        return out_g, out_e

    spec_tree = {path: specs[path] for path in specs}
    sm = jax.shard_map(body, mesh=mesh,
                       in_specs=(spec_tree, spec_tree),
                       out_specs=(spec_tree, spec_tree), check_vma=False)

    def sync(grads: dict, ef: dict):
        return sm(grads, ef)

    return sync
