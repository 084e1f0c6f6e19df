"""Multi-device semantics (8 forced host devices, subprocess-isolated):
flash-decode seq-sharded attention and EP shard_map MoE must match their
single-device references.  Run in subprocesses because XLA fixes the device
count at first init.
"""
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_py(code: str) -> subprocess.CompletedProcess:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    return subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=900)


FLASH_DECODE = """
import dataclasses, jax, jax.numpy as jnp
from repro.configs import get_config, reduced
from repro.models import get_model
from repro.models import params as P
from repro.launch.mesh import make_mesh
from repro.distributed import set_current_mesh
from repro.distributed.sharding import spec_tree_shardings

cfg0 = reduced(get_config("internlm2-20b"))
api = get_model(cfg0)
params = P.materialize(api.param_spec(cfg0, 1), jax.random.PRNGKey(0), jnp.float32)
b, s = 4, 32
batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (b, s), 0, cfg0.vocab)}
cache = P.materialize(api.cache_spec(cfg0, b, 64, 1), jax.random.PRNGKey(2), jnp.float32)
_, cache = api.prefill(params, batch, cfg0, cache)
tok = jnp.ones((b, 1), jnp.int32)
ref, _ = api.decode(params, tok, jnp.int32(s), cfg0, cache)

cfg1 = dataclasses.replace(cfg0, seq_shard_cache=True)
mesh = make_mesh((2, 4), ("data", "model"))
set_current_mesh(mesh)
with mesh:
    sh = spec_tree_shardings(api.cache_spec(cfg1, b, 64, 4), mesh)
    cache_sh = jax.tree_util.tree_map(jax.device_put, dict(cache), sh)
    got, _ = jax.jit(lambda p, c, t: api.decode(p, t, jnp.int32(s), cfg1, c))(params, cache_sh, tok)
err = float(jnp.max(jnp.abs(ref - got)))
assert err < 1e-4, err
print("OK", err)
"""

EP_MOE = """
import dataclasses, jax, jax.numpy as jnp
import repro.models.moe as moe
moe.CAPACITY_FACTOR = 100.0  # no drops -> exact equivalence
from repro.configs import get_config, reduced
from repro.models import get_model
from repro.models import params as P
from repro.launch.mesh import make_mesh
from repro.distributed import set_current_mesh

cfg0 = reduced(get_config("arctic-480b"))
api = get_model(cfg0)
params = P.materialize(api.param_spec(cfg0, 1), jax.random.PRNGKey(0), jnp.float32)
batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg0.vocab)}
l0 = api.forward_train(params, batch, cfg0)
cfg1 = dataclasses.replace(cfg0, ep_shard_map=True)
mesh = make_mesh((2, 4), ("data", "model"))
set_current_mesh(mesh)
with mesh:
    l1 = jax.jit(lambda p, b: api.forward_train(p, b, cfg1))(params, batch)
assert abs(float(l0 - l1)) < 1e-5, (float(l0), float(l1))
print("OK")
"""

MULTIPOD_TRAIN_SMOKE = """
import jax, jax.numpy as jnp
from repro.configs import get_config, reduced
from repro.models import get_model
from repro.models import params as P
from repro.launch.mesh import make_mesh
from repro.distributed import set_current_mesh
from repro.distributed.sharding import spec_tree_shardings, entry_tree_shardings
from repro.train import make_train_step, state_spec

cfg = reduced(get_config("granite-34b"))
api = get_model(cfg)
mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
set_current_mesh(mesh)
sspec = state_spec(cfg, api.param_spec(cfg, 2), 4)
state = P.materialize(sspec, jax.random.PRNGKey(0), jnp.float32)
with mesh:
    sh = spec_tree_shardings(sspec, mesh)
    state = jax.tree_util.tree_map(jax.device_put, state, sh)
    batch = {"tokens": jnp.ones((8, 16), jnp.int32)}
    bsh = entry_tree_shardings({"tokens": ("batch", None)}, mesh)
    batch = jax.tree_util.tree_map(jax.device_put, batch, bsh)
    step = jax.jit(make_train_step(cfg, api))
    state, m = step(state, batch)
    assert float(m["loss"]) > 0 and float(m["loss"]) < 20
print("OK", float(m["loss"]))
"""


@pytest.mark.parametrize("name,code", [
    ("flash_decode", FLASH_DECODE),
    ("ep_moe", EP_MOE),
    ("multipod_train", MULTIPOD_TRAIN_SMOKE),
])
def test_multidevice(name, code):
    r = run_py(code)
    assert r.returncode == 0, f"{name}:\n{r.stdout[-2000:]}\n{r.stderr[-3000:]}"
    assert "OK" in r.stdout
