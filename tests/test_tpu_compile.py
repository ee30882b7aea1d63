"""Compile the main-path Pallas kernels for a described TPU v5e.

No chip is attached: the TPU compiler compiles for ``v5e:2x2`` devices that
are only described (``jax.experimental.topologies``), which refuses what
Mosaic would refuse on the chip — tile-illegal block shapes, contractions it
cannot lower, more VMEM than a kernel may use — at no chip time.  Nothing
runs, so these tests say nothing about results or speed; the interpret-mode
parity tests (``test_stacked_kernels.py``, ``test_kernels.py``) cover
results.

Widths are those of the ogbn-mag training configuration: batch 1024,
fanout 25, input width 128, hidden 64, 4 heads.  ``kernel_choice`` picks the
compiled kernels only when the backend is a TPU, so each test steers it by
reporting ``"tpu"`` from ``jax.default_backend``.

Each kernel's ``pallas_call`` carries a ``name``, which the compiled HLO
gives its custom call and a profiler trace its device operation; the
benchmark's roofline readers find kernels by it, so the tests pin it.

The topology is described inside a module fixture (never at import): only
one process at a time may load the TPU library, and every test worker
imports this file.
"""

from __future__ import annotations

import os
import re
from collections import Counter

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.relmod import ShapeCtx, get_relation_module
from repro.kernels.gather_rows import gather_rows_cfg
from repro.kernels.ops import KernelOptions
from repro.kernels.stacked_relation_agg import stacked_agg

BATCH, FANOUT, D_IN, HIDDEN, HEADS = 1024, 25, 128, 64, 4
SLOTS = 3  # branch slots of one shard (ogbn-mag: 3 relations into paper)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


_KERNEL = re.compile(r"%([A-Za-z0-9_\-]+?)(?:\.\d+)? = [^\n]*"
                     r'custom_call_target="tpu_custom_call"')


def _compile(fn, one_chip, *trees):
    """Lower + compile ``fn`` on shape trees placed on the described chip;
    returns the Mosaic kernels in the compiled program, counted by the name
    of their custom call."""
    args = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        trees)
    text = jax.jit(fn).lower(*args).compile().as_text()
    kernels = Counter(_KERNEL.findall(text))
    assert sum(kernels.values()) == text.count("tpu_custom_call")
    return kernels


MEAN_LINEAR = {"stacked_mean_linear_pallas"}
ATTENTION = {"stacked_attn_epilogue_pallas", "stacked_mean_linear_pallas"}


def _agg_operands(model: str):
    module = get_relation_module(model)
    sc = ShapeCtx(hidden=HIDDEN, num_heads=HEADS, head_dim=HIDDEN // HEADS,
                  d_src=D_IN, d_dst=D_IN)
    stacks = {s.name: jnp.zeros((SLOTS,) + tuple(s.shape(sc)), jnp.float32)
              for s in module.specs}
    slot_u = {scope: jnp.zeros((SLOTS,), jnp.int32) for scope in module.scopes}
    h = jnp.zeros((SLOTS, BATCH, FANOUT, D_IN), jnp.float32)
    q = jnp.zeros((SLOTS, BATCH, D_IN), jnp.float32)
    mask = jnp.zeros((SLOTS, BATCH, FANOUT), bool)
    return module, stacks, slot_u, h, q, mask


@pytest.mark.parametrize("model", ["rgcn", "rgat", "hgt"])
def test_stacked_agg_forward_compiles_for_v5e(model, one_chip, on_tpu):
    """rgcn: the stacked mean-linear kernel; rgat/hgt: the fused attention
    epilogue (plus the q-side projection through mean-linear)."""
    module, stacks, slot_u, h, q, mask = _agg_operands(model)

    def fwd(stacks, slot_u, h, q, mask):
        return stacked_agg(module, stacks, slot_u, h, q, mask,
                           opts=KernelOptions())

    kernels = _compile(fwd, one_chip, stacks, slot_u, h, q, mask)
    assert set(kernels) == (MEAN_LINEAR if model == "rgcn" else ATTENTION)


@pytest.mark.parametrize("model", ["rgcn", "rgat", "hgt"])
def test_stacked_agg_vjp_compiles_for_v5e(model, one_chip, on_tpu):
    """The custom VJPs: forward-with-residuals plus the scalar-prefetch
    ``dh`` kernels, gradients w.r.t. the stacks and the neighbor rows."""
    module, stacks, slot_u, h, q, mask = _agg_operands(model)

    def loss(stacks, h, slot_u, q, mask):
        out = stacked_agg(module, stacks, slot_u, h, q, mask,
                          opts=KernelOptions())
        return jnp.sum(out * out)

    vjp = jax.grad(loss, argnums=(0, 1))
    kernels = _compile(vjp, one_chip, stacks, h, slot_u, q, mask)
    assert sum(kernels.values()) >= 2
    assert set(kernels) == (
        MEAN_LINEAR | {"stacked_mean_linear_dh_pallas"} if model == "rgcn"
        else ATTENTION | {"stacked_attn_dh_pallas"})


def test_attn_parts_softmax_combine_compiles_for_v5e(one_chip, on_tpu):
    """With ``fuse_epilogue`` off the attention family keeps its projections
    under XLA and runs only the masked softmax + combine kernel."""
    module, stacks, slot_u, h, q, mask = _agg_operands("rgat")

    def fwd(stacks, slot_u, h, q, mask):
        return stacked_agg(module, stacks, slot_u, h, q, mask,
                           opts=KernelOptions(fuse_epilogue=False))

    kernels = _compile(fwd, one_chip, stacks, slot_u, h, q, mask)
    assert set(kernels) == {"stacked_softmax_combine_pallas"}


@pytest.mark.parametrize("d", [64, 128])
def test_gather_rows_compiles_for_v5e(d, one_chip, on_tpu):
    """The cache fetch: 1024 rows of a learnable (64) or paper (128) table."""
    table = jnp.zeros((100_000, d), jnp.float32)
    idx = jnp.zeros((BATCH,), jnp.int32)

    def fetch(table, idx):
        return gather_rows_cfg(table, idx, KernelOptions())

    assert set(_compile(fetch, one_chip, table, idx)) == {"gather_rows_pallas"}
