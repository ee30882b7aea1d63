"""End-to-end system tests: the full Heta pipeline on one device, comm
accounting sanity, checkpoint round-trips, and the sharding rule tables."""

import os
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.comm import vanilla_comm_bytes, vanilla_update_bytes
from repro.core.meta_partition import EdgeCutPartition, meta_partition, random_edge_cut
from repro.core.metatree import build_metatree
from repro.graph.hetgraph import CSR, HetGraph, Relation
from repro.graph.sampler import NeighborSampler, SampleSpec
from repro.graph.synthetic import ogbn_mag_like
from repro.launch.train import train_hgnn


def test_full_pipeline_single_device():
    """partition → presample → cache → SPMD RAF train → learnable updates."""
    m = train_hgnn(
        dataset="ogbn-mag", scale=0.002, model="rgcn", num_partitions=2,
        mesh_shape=(1, 1), batch_size=16, fanouts=(4, 3), steps=5, cache_mb=2,
    )
    assert m["meta_local"]
    assert all(np.isfinite(m["losses"]))
    assert any(v > 0 for v in m["hit_rates"].values())


def test_full_pipeline_featureless():
    """Freebase-like: every node type learnable (paper's hardest cache case)."""
    m = train_hgnn(
        dataset="freebase", scale=0.0005, model="rgcn", num_partitions=2,
        mesh_shape=(1, 1), batch_size=8, fanouts=(3, 2), steps=3, cache_mb=2,
    )
    assert all(np.isfinite(m["losses"]))


def test_naive_placement_still_correct():
    m = train_hgnn(
        dataset="ogbn-mag", scale=0.002, model="rgcn", num_partitions=2,
        mesh_shape=(1, 1), batch_size=8, fanouts=(3, 2), steps=3,
        naive_placement=True,
    )
    assert not m["meta_local"]
    assert all(np.isfinite(m["losses"]))


# --------------------------------------------------------------------------
# vanilla comm accounting on a hand-built graph
# --------------------------------------------------------------------------


def _toy_graph():
    # 2 types: u (4 nodes, feat dim 8) -> v (2 target nodes)
    rel = Relation("u", "e", "v")
    csr = CSR.from_edges(np.array([0, 1, 2, 3]), np.array([0, 0, 1, 1]), 2)
    return HetGraph(
        num_nodes={"u": 4, "v": 2},
        relations={rel: csr},
        target_type="v",
        num_classes=2,
        features={"u": np.zeros((4, 8), np.float32),
                  "v": np.zeros((2, 4), np.float32)},
    )


def test_vanilla_comm_exact_count():
    g = _toy_graph()
    tree = build_metatree(g.metagraph(), "v", 1)
    spec = SampleSpec.from_metatree(tree, [2])
    sampler = NeighborSampler(g, spec, 2, seed=0)
    b = sampler.sample_batch(np.array([0, 1]))
    # seed 0 on partition 0, seed 1 on partition 1; u nodes 0,1 on 0; 2,3 on 1
    cut = EdgeCutPartition(
        assignment={"v": np.array([0, 1], np.int32),
                    "u": np.array([0, 0, 1, 1], np.int32)},
        num_partitions=2,
    )
    feat_dims = {"u": 8, "v": 4}
    got = vanilla_comm_bytes(b, cut, feat_dims, bytes_per_elem=2,
                             include_topology=False)
    # neighbors of v0 are u{0,1} (local to part 0) and of v1 are u{2,3}
    # (local to part 1): zero remote fetches
    assert got == 0
    # flip the u assignment: every fetch is remote; unique remote u per seed ≤ 2
    cut2 = EdgeCutPartition(
        assignment={"v": np.array([0, 1], np.int32),
                    "u": np.array([1, 1, 0, 0], np.int32)},
        num_partitions=2,
    )
    got2 = vanilla_comm_bytes(b, cut2, feat_dims, bytes_per_elem=2,
                              include_topology=False)
    uniq = 0
    for seed_pos, seed in enumerate(b.seeds):
        ids = set(b.levels[0].nids[0][seed_pos * 2:(seed_pos + 1) * 2])
        uniq += len(ids)
    assert got2 == uniq * 8 * 2


def test_update_bytes_zero_when_no_learnable():
    g = _toy_graph()
    tree = build_metatree(g.metagraph(), "v", 1)
    spec = SampleSpec.from_metatree(tree, [2])
    b = NeighborSampler(g, spec, 2, seed=0).sample_batch(np.array([0, 1]))
    cut = random_edge_cut(g, 2)
    assert vanilla_update_bytes(b, cut, g) == 0  # all types featured


# --------------------------------------------------------------------------
# checkpoint round-trip
# --------------------------------------------------------------------------


def test_checkpoint_roundtrip(tmp_path):
    from repro.checkpoint import latest_step, load_checkpoint, save_checkpoint

    tree = {
        "a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
        "nested": {"b": jnp.ones((4,), jnp.bfloat16), "step": jnp.asarray(7)},
    }
    save_checkpoint(str(tmp_path), 3, tree)
    save_checkpoint(str(tmp_path), 9, tree)
    assert latest_step(str(tmp_path)) == 9
    restored = load_checkpoint(str(tmp_path), 9, tree)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------------------------------------
# sharding rule tables (AbstractMesh: no devices needed)
# --------------------------------------------------------------------------


def test_param_pspecs_divide_on_production_mesh():
    import repro.configs.all_archs  # noqa: F401
    from jax.sharding import PartitionSpec as P
    from repro.configs.base import ARCHS
    from repro.launch.mesh import make_abstract_mesh
    from repro.launch.sharding import param_pspecs
    from repro.launch.specs import abstract_params

    mesh = make_abstract_mesh((16, 16), ("data", "model"))
    for name, cfg in sorted(ARCHS.items()):
        params = abstract_params(cfg)
        specs = param_pspecs(cfg, params, mesh)
        flat_p = jax.tree_util.tree_leaves_with_path(params)
        flat_s = jax.tree_util.tree_leaves(
            specs, is_leaf=lambda x: isinstance(x, P)
        )
        assert len(flat_p) == len(flat_s)
        for (path, leaf), spec in zip(flat_p, flat_s):
            for dim, ax in zip(leaf.shape, tuple(spec) + (None,) * 8):
                if ax is None:
                    continue
                size = mesh.shape[ax] if isinstance(ax, str) else int(
                    np.prod([mesh.shape[a] for a in ax])
                )
                assert dim % size == 0, f"{name} {path} {leaf.shape} {spec}"


def test_cache_pspecs_long_context():
    import repro.configs.all_archs  # noqa: F401
    from repro.configs.base import ARCHS, INPUT_SHAPES
    from repro.launch.mesh import make_abstract_mesh
    from repro.launch.sharding import cache_pspecs
    from repro.launch.specs import abstract_cache

    mesh = make_abstract_mesh((16, 16), ("data", "model"))
    cfg = ARCHS["jamba-1.5-large-398b"]
    cache = abstract_cache(cfg, INPUT_SHAPES["long_500k"])
    specs = cache_pspecs(cfg, cache, mesh)
    # batch-1: sequence axis spread over (data, model)
    assert specs["k"][3] == ("data", "model")
    assert specs["ssm"][3] == "model"


# --------------------------------------------------------------------------
# the entry points' persistent compilation cache
# --------------------------------------------------------------------------


@pytest.mark.parametrize("env_dir", [None, "custom"])
def test_compile_cache_dir_is_env_or_fixed_checkout_path(env_dir, tmp_path):
    """``enable_compile_cache`` keeps ``JAX_COMPILATION_CACHE_DIR`` when set
    (and sets no other), else the fixed ``.jax_cache`` of the checkout —
    never a path built from a temp name, pid or time."""
    import subprocess
    import sys

    from repro.launch.compile_cache import CHECKOUT_CACHE_DIR

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = os.path.join(root, "src")
    want = str(CHECKOUT_CACHE_DIR)
    if env_dir is not None:
        want = env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / env_dir)
    code = ("import jax; from repro.launch.compile_cache import "
            "enable_compile_cache; d = enable_compile_cache(); "
            "print(d); print(jax.config.jax_compilation_cache_dir)")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split() == [want, want]
    assert CHECKOUT_CACHE_DIR == Path(root).resolve() / ".jax_cache"
