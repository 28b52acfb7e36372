"""GPipe-style pipeline parallelism over a "stage" mesh axis.

``split_stages`` reshapes stacked per-layer parameters into a leading
stage axis; ``gpipe`` returns a runner that shard_maps the classic GPipe
schedule: each stage applies its layer slice to the microbatch it holds,
then collective-permutes activations one stage down the ring.  After
``n_micro + n_stages - 1`` ticks the last stage has every microbatch's
output; a psum over the stage axis replicates the result.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P



def split_stages(params: dict, n_stages: int) -> dict:
    """Reshape stacked (L, ...) leaves to (n_stages, L // n_stages, ...)."""
    def r(x):
        l = x.shape[0]
        assert l % n_stages == 0, (l, n_stages)
        return x.reshape(n_stages, l // n_stages, *x.shape[1:])
    return jax.tree.map(r, params)


def gpipe(block_fn, *, n_stages: int, n_micro: int, mesh,
          stage_axis: str = "stage"):
    """Build a runner f(stage_params, x_micro) -> y_micro.

    ``block_fn(layer_params, x) -> x`` applies one layer; ``stage_params``
    leaves carry a leading (n_stages, layers_per_stage) axis pair
    (from :func:`split_stages`); ``x_micro`` is (n_micro, ...) and is
    replicated to every stage.
    """
    def body(local_params, x_micro):
        # local leaves: (1, layers_per_stage, ...) after stage sharding
        layers = jax.tree.map(lambda a: a[0], local_params)
        sidx = jax.lax.axis_index(stage_axis)
        ring = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def stage_apply(x):
            def step(carry, layer):
                return block_fn(layer, carry), None
            out, _ = jax.lax.scan(step, x, layers)
            return out

        buf = jnp.zeros_like(x_micro)
        recv = jnp.zeros_like(x_micro[0])
        for t in range(n_micro + n_stages - 1):
            feed = x_micro[min(t, n_micro - 1)]
            inp = jnp.where(sidx == 0, feed, recv)
            out = stage_apply(inp)
            done = t - (n_stages - 1)       # microbatch finishing this tick
            if 0 <= done < n_micro:
                buf = buf.at[done].set(
                    jnp.where(sidx == n_stages - 1, out, buf[done]))
            recv = jax.lax.ppermute(out, stage_axis, ring)
        # only the last stage holds results; psum replicates them
        return jax.lax.psum(buf, stage_axis)

    def run(stage_params, x_micro):
        in_param_specs = jax.tree.map(lambda _: P(stage_axis), stage_params)
        sm = jax.shard_map(body, mesh=mesh,
                           in_specs=(in_param_specs, P()),
                           out_specs=P(), check_vma=False)
        return sm(stage_params, x_micro)

    return run
