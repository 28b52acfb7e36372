"""Multi-device distribution tests.

Each test spawns a subprocess with XLA_FLAGS=--xla_force_host_platform_
device_count=8 so the main pytest process keeps its single CPU device.
"""

import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.multidevice

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_script(body: str, timeout: int = 600):
    script = (
        "import os\n"
        "os.environ['XLA_FLAGS'] = "
        "'--xla_force_host_platform_device_count=8'\n" + body)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env=env, cwd=REPO, timeout=timeout)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    assert "TEST-OK" in r.stdout, r.stdout


def test_data_parallel_matches_single_device():
    run_script("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import registry as R
from repro.dist import sharding as sh
from repro.launch import specs as specs_lib
from repro.launch.mesh import make_mesh
from repro.optim.adamw import AdamWConfig
from repro.train import step as step_lib
from repro.data.synth import DataConfig, make_batch_fn

cfg = R.reduced("smollm-360m", n_layers=2, d_model=64, vocab_size=128)
bf = make_batch_fn(DataConfig(vocab_size=128, seq_len=16, global_batch=8))
batch = bf(0)
state = step_lib.init_state(cfg, AdamWConfig(), jax.random.key(0))
fn = step_lib.make_train_step(cfg, AdamWConfig(), step_lib.TrainStepConfig())

# single device reference
ref, _ = jax.jit(fn)(state, batch)

# 4x2 mesh, batch sharded over data
mesh = make_mesh((4, 2), ("data", "model"))
with sh.use_mesh_and_rules(mesh, specs_lib.rules_for(cfg, "train_4k")):
    ssh = specs_lib.state_shardings(cfg, mesh)
    from repro.configs.base import input_specs
    bsh = {k: sh.input_sharding(v.shape, specs_lib.BATCH_AXES[k], mesh)
           for k, v in batch.items()}
    out, _ = jax.jit(fn, in_shardings=(ssh, bsh))(state, batch)

for k in ref["params"]:
    a = np.asarray(ref["params"][k], np.float32)
    b = np.asarray(out["params"][k], np.float32)
    np.testing.assert_allclose(a, b, atol=2e-5, err_msg=k)
print("TEST-OK")
""")


def test_compressed_cross_pod_mean_and_bytes():
    run_script("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.dist.compressed import compressed_mean_flat, make_cross_axis_grad_sync
from repro.optim.grad_compress import GradCompressConfig

mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))

# per-pod different gradients -> compressed mean over pod
n = 4096
g = jnp.stack([jnp.sin(jnp.arange(n) / 50.0),
               jnp.sin(jnp.arange(n) / 50.0) + 0.1])   # (2, N), smooth
ef = jnp.zeros((2, n))
def body(gl, el):
    m, e = compressed_mean_flat(gl[0], el[0], "pod", keep=16)
    return m[None], e[None]
sm = jax.shard_map(body, mesh=mesh, in_specs=(P("pod"), P("pod")),
                   out_specs=(P("pod"), P("pod")), check_vma=False)
mean, new_ef = jax.jit(sm)(g, ef)
true = np.asarray(g).mean(0)
a = np.asarray(mean[0]); b = np.asarray(mean[1])
np.testing.assert_allclose(a, b, atol=1e-6)          # both pods agree
rel = np.linalg.norm(a - true) / np.linalg.norm(true)
assert rel < 0.05, rel                                # smooth signal compacts
assert float(jnp.abs(new_ef).max()) > 0               # EF holds the residual

# tree-level plumbing via make_cross_axis_grad_sync
grads = {"w": jnp.tile(jnp.sin(jnp.arange(1024)/20.)[None], (2, 1)).reshape(2,1024)}
specs = {"w": P()}
sync = make_cross_axis_grad_sync(mesh, specs, GradCompressConfig(
    enabled=True, keep=16, min_size=64, axis="pod"))
out, ef2 = jax.jit(sync)({"w": grads["w"][0]}, {"w": jnp.zeros(1024)})
assert out["w"].shape == (1024,)

# collective bytes: int8 codes crossing the pod axis, not f32 grads
lowered = jax.jit(sm).lower(g, ef)
txt = lowered.compile().as_text()
assert "all-gather" in txt
print("TEST-OK")
""")


def test_dryrun_lowering_small_mesh():
    run_script("""
import jax, jax.numpy as jnp
from repro.configs import registry as R
from repro.configs.base import input_specs
from repro.dist import sharding as sh
from repro.launch import specs as specs_lib
from repro.launch.mesh import make_mesh
from repro.models import registry as M
from repro.optim import adamw
from repro.train import step as step_lib

mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
for arch in ("smollm-360m", "qwen3-moe-30b-a3b", "zamba2-1.2b"):
    cfg = R.reduced(arch, vocab_size=256)
    rules = specs_lib.rules_for(cfg, "train_4k")
    with sh.use_mesh_and_rules(mesh, rules):
        fn = step_lib.make_train_step(cfg, adamw.AdamWConfig(),
                                      step_lib.TrainStepConfig())
        state = step_lib.abstract_state(cfg, adamw.AdamWConfig())
        ssh = specs_lib.state_shardings(cfg, mesh)
        batch = {"tokens": jax.ShapeDtypeStruct((8, 16), jnp.int32),
                 "labels": jax.ShapeDtypeStruct((8, 16), jnp.int32)}
        bsh = {k: sh.input_sharding(v.shape, specs_lib.BATCH_AXES[k], mesh)
               for k, v in batch.items()}
        compiled = jax.jit(fn, in_shardings=(ssh, bsh)).lower(
            state, batch).compile()
        assert compiled.memory_analysis() is not None
        print(arch, "ok")
print("TEST-OK")
""")


def test_elastic_reshard_across_meshes():
    run_script("""
import jax, jax.numpy as jnp, numpy as np, tempfile
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.ckpt import checkpoint
from repro.launch.mesh import make_mesh

mesh_a = make_mesh((4, 2), ("data", "model"))
mesh_b = make_mesh((2, 4), ("data", "model"))
x = jnp.arange(64 * 8, dtype=jnp.float32).reshape(64, 8)
xa = jax.device_put(x, NamedSharding(mesh_a, P("data", "model")))
with tempfile.TemporaryDirectory() as td:
    checkpoint.save(td, 1, {"w": xa}, {"step": 1})
    # load resharded for a different mesh topology (elastic rescale)
    tree, _ = checkpoint.load(td, 1, shardings={
        "w": NamedSharding(mesh_b, P("model", "data"))})
    np.testing.assert_array_equal(np.asarray(tree["w"]), np.asarray(x))
    assert tree["w"].sharding.mesh.shape["data"] == 2
print("TEST-OK")
""")


def test_gpipe_pipeline_matches_sequential():
    run_script("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.dist import pipeline

mesh = make_mesh((4, 2), ("stage", "data"))

# 8 layers of a toy residual block, 4 stages x 2 layers
L, D, M, B = 8, 16, 4, 3
key = jax.random.key(0)
w = jax.random.normal(key, (L, D, D)) * (0.5 / np.sqrt(D))

def block_fn(layer_w, x):
    return x + jnp.tanh(x @ layer_w)

x_micro = jax.random.normal(jax.random.key(1), (M, B, D))

# sequential reference
def seq(x):
    for i in range(L):
        x = block_fn(w[i], x)
    return x
ref = jax.vmap(seq)(x_micro)

stage_params = pipeline.split_stages({"w": w}, 4)
run = pipeline.gpipe(lambda p, x: block_fn(p["w"], x), n_stages=4,
                     n_micro=M, mesh=mesh)
out = jax.jit(lambda sp, xm: run(sp, xm))(stage_params, x_micro)
np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
print("TEST-OK")
""")
