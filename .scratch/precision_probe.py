import sys, time
sys.path.insert(0, "src")
import jax, jax.numpy as jnp, numpy as np
from repro.core import dct, quant, images
imgs = np.stack([images.lena_like(512, 512, seed=i) for i in range(8)]).astype(np.float32)
t = dct.kron_dct_matrix(8)
q = quant.qtable(50).reshape(64)
def levels(x, precision):
    b = dct.to_blocks(x - 128.0)
    flat = b.reshape(*b.shape[:-2], 64)
    coef = jnp.matmul(flat, t.T, precision=precision)
    return jnp.round(coef / q).astype(jnp.int32)
cpu = jax.devices("cpu")[0]
with jax.default_device(cpu):
    ref = np.asarray(jax.jit(lambda x: levels(x, None))(jax.device_put(imgs, cpu)))
for name, p in [("DEFAULT", None), ("HIGH", jax.lax.Precision.HIGH), ("HIGHEST", jax.lax.Precision.HIGHEST)]:
    got = np.asarray(jax.jit(lambda x, p=p: levels(x, p))(imgs))
    d = np.abs(got.astype(np.int64) - ref)
    print(f"precision {name}: levels differing from CPU {int((d>0).sum())} of {d.size} ({(d>0).mean():.3e}), max |diff| {int(d.max())}")
