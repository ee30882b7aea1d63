"""Compile the main-path Pallas kernels for a described TPU v5e.

No chip is attached: the TPU compiler compiles for ``v5e:2x2`` devices that
are only described (``jax.experimental.topologies``), which refuses what
Mosaic would refuse on the chip — tile-illegal block shapes, contractions it
cannot lower, more VMEM than a kernel may use — at no chip time.  Nothing
runs, so these tests say nothing about results or speed; the interpret-mode
parity tests (``test_stacked_kernels.py``, ``test_kernels.py``) cover
results.

Widths are those of the ogbn-mag training configuration: batch 1024,
fanout 25, input width 128, hidden 64, 4 heads; and HGT's published 256
hidden and 8 heads at both levels of the ogbn-mag cell.  ``kernel_choice`` picks the
compiled kernels only when the backend is a TPU, so each test steers it by
reporting ``"tpu"`` from ``jax.default_backend``.

The resident train step of both ogbn-mag cells compiles whole at the
cells' shapes, its leaf rows taken straight into the aggregation's layout.

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


# kernels in one compiled gradient of an aggregation w.r.t. the stacks and
# the neighbor rows: rgcn's forward and its ``dh`` kernel (its weight
# gradient is XLA's); the attention family's forward, its backward (which
# writes the neighbor rows' gradient too) and the q-side projection's
# forward (the queries need no gradient, so its ``dh`` kernel is dropped)
VJP_KERNELS = {
    "rgcn": {"stacked_mean_linear_pallas": 1, "stacked_mean_linear_dh_pallas": 1},
    "rgat": {"stacked_attn_epilogue_pallas": 1, "stacked_attn_bwd_pallas": 1,
             "stacked_mean_linear_pallas": 1},
    "hgt": {"stacked_attn_epilogue_pallas": 1, "stacked_attn_bwd_pallas": 1,
            "stacked_mean_linear_pallas": 1},
}


@pytest.mark.parametrize("model", ["rgcn", "rgat", "hgt"])
def test_stacked_agg_vjp_compiles_for_v5e(model, one_chip, on_tpu):
    """The custom VJPs: the forward kernels plus rgcn's scalar-prefetch
    ``dh`` kernel or the attention backward, gradients w.r.t. the stacks
    and the neighbor rows."""
    module, stacks, slot_u, h, q, mask = _agg_operands(model)

    def loss(stacks, h, slot_u, q, mask):
        out = stacked_agg(module, stacks, slot_u, h, q, mask,
                          opts=KernelOptions())
        return jnp.sum(out * out)

    vjp = jax.grad(loss, argnums=(0, 1))
    kernels = _compile(vjp, one_chip, stacks, h, slot_u, q, mask)
    assert dict(kernels) == VJP_KERNELS[model]


# HGT at its published widths (arXiv:2003.01332: hidden 256, 8 heads) at
# both levels of the ogbn-mag cell: 3 slots of 1,024 parents with 25
# neighbors at input width 256 (the inner level, whose rows need a
# gradient), 6 slots of 25,600 parents with 20 at 128 (the leaf level, rows
# fixed)
HGT_LEVELS = [(3, 1024, 25, 256, True), (6, 25600, 20, 128, False)]


@pytest.mark.parametrize("rb,n,f,d_in,h_grad", HGT_LEVELS)
def test_hgt_published_widths_attention_compiles_for_v5e(
        rb, n, f, d_in, h_grad, one_chip, on_tpu):
    """The fused attention forward and backward at 256 hidden and 8 heads
    fit the scoped VMEM they ask for; the backward writes the neighbor rows'
    gradient only at the level that needs it."""
    module = get_relation_module("hgt")
    sc = ShapeCtx(hidden=256, num_heads=8, head_dim=32, d_src=d_in, d_dst=D_IN)
    stacks = {s.name: jnp.zeros((rb,) + tuple(s.shape(sc)), jnp.float32)
              for s in module.specs}
    slot_u = {scope: jnp.zeros((rb,), jnp.int32) for scope in module.scopes}
    h = jnp.zeros((rb, n, f, d_in), jnp.float32)
    q = jnp.zeros((rb, n, D_IN), jnp.float32)
    mask = jnp.zeros((rb, n, f), bool)

    def loss(stacks, h, slot_u, q, mask):
        out = stacked_agg(module, stacks, slot_u, h, q, mask,
                          opts=KernelOptions())
        return jnp.sum(out * out)

    grad = jax.grad(loss, argnums=(0, 1) if h_grad else 0)
    kernels = _compile(grad, one_chip, stacks, h, slot_u, q, mask)
    assert dict(kernels) == VJP_KERNELS["hgt"]


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


# the ogbn-mag cells' deployment: OGB's node counts, 128-d rows on every
# type, batch 1024, fanouts 25, 20, four meta-partitions folded onto 1x1
MAG_ROWS = {"paper": 736_389, "author": 1_134_649, "institution": 8_740,
            "field_of_study": 59_965}
MAG_WIDTHS = {"rgcn": (64, 4), "hgt": (256, 8)}
_BIG_OP = re.compile(r"\s*(?:ROOT )?%(\S+) = f32\[([\d,]*)\]\{[^}]*\} (\S+?)\(")


@pytest.mark.parametrize("model", ["rgcn", "hgt"])
def test_resident_step_takes_leaf_rows_in_the_aggregation_layout_for_v5e(
        model, topo, on_tpu):
    """The whole resident train step at the cells' shapes (level 2: 6
    slots of 25,600 parents with 20 neighbors each): the leaf rows are
    taken straight into the layout the aggregation reads
    (``raf_spmd.gather_leaf``): at hgt's padded fanout no pad, copy or
    relayout of a slot's leaf rows is left in the compiled step, only the
    stacking of the slots' gathers (one dynamic-update-slice per slot)."""
    import numpy as np
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

    from repro.core import raf_spmd
    from repro.core.hgnn import HGNNConfig, init_hgnn_params
    from repro.core.meta_partition import meta_partition
    from repro.core.raf import assign_branches
    from repro.graph.sampler import SampleSpec
    from repro.graph.synthetic import ogbn_mag_like
    from repro.optim.adam import AdamConfig, adam_init

    g = ogbn_mag_like(scale=0.002)  # the schema; shapes below are OGB's
    mp = meta_partition(g, 4, num_layers=2)
    spec = SampleSpec.from_metatree(mp.metatree, [25, 20])
    hidden, heads = MAG_WIDTHS[model]
    cfg = HGNNConfig(model=model, hidden=hidden, num_heads=heads,
                     num_layers=2, num_classes=349, learnable_dim=128)
    plan = raf_spmd.build_plan(spec, assign_branches(spec, mp).fold(1, spec),
                               cfg, {"paper": 128})
    rb1, rb2 = (lp.rb for lp in plan.levels)
    assert rb2 == 6
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2,
                         devices=topo.devices[:1])
    step = raf_spmd.make_train_step(plan, mesh, AdamConfig(lr=1e-3),
                                    kernels=KernelOptions())
    stacks = raf_spmd.stack_params_from_dict(
        plan, init_hgnn_params(jax.random.PRNGKey(0), cfg, spec, {"paper": 128}))

    def sds(x, pspec=P()):
        return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                    sharding=NamedSharding(mesh, pspec))

    specs = raf_spmd._array_specs(plan, ("data",), "model")
    n1 = BATCH * 25
    n2 = n1 * 20
    shapes = {"seeds": (BATCH,), "labels": (BATCH,), "qnid1": (rb1, BATCH),
              "mask1": (rb1, n1), "qnid2": (rb2, n1), "mask2": (rb2, n2),
              "hnid2": (rb2, n2)}
    arrays = {k: sds(jax.ShapeDtypeStruct(v, bool if "mask" in k else jnp.int32),
                     specs[k]) for k, v in shapes.items()}
    tables = {t: sds(jax.ShapeDtypeStruct((r, 128), jnp.float32))
              for t, r in MAG_ROWS.items()}
    text = step.lower(
        jax.tree.map(sds, stacks, raf_spmd._stack_specs(plan),
                     is_leaf=lambda x: isinstance(x, jnp.ndarray)),
        jax.tree.map(sds, adam_init(stacks)), arrays, tables,
    ).compile().as_text()

    # the entry computation's ops as large as one slot's leaf rows
    entry = text[text.index("ENTRY"):]
    entry = entry[:entry.index("\n}\n")]
    big = []
    for line in entry.splitlines():
        m = _BIG_OP.match(line)
        if m and np.prod([int(x) for x in m.group(2).split(",") if x]) >= n2 * 128:
            big.append((m.group(1), m.group(3)))
    ops = Counter(op for _, op in big)
    assert ops["pad"] == ops["copy"] == ops["transpose"] == 0, big
    # fanout 20 fills no whole 8-row tiles: rgcn's mean-linear reads it
    # unpadded, so each slot's rows are relaid once, as at the parent
    assert ops["reshape"] == {"rgcn": rb2, "hgt": 0}[model], big
    assert sum("dynamic-update-slice" in name for name, _ in big) == rb2, big
