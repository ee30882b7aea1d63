"""Resident staging: when every feature table a step reads is wholly in the
device cache on a one-accelerator mesh, staging sends node ids and the
compiled step gathers the feature rows on the device
(``staging.stack_batch_nids`` + ``raf_spmd.gather_resident``); otherwise
it gathers them on the host as before.  The CPU backend keeps the host
path; the session tests here let the resident one engage on it
(``device_gather_on_cpu``)."""

import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (CacheConfig, DataConfig, Heta, HetaConfig,
                       ModelConfig, PartitionConfig, RunConfig)

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
FULL_MB, PARTIAL_MB = 6, 2  # the whole tiny graph, and part of it


def _cfg(cache_mb=FULL_MB, learnable=False, **pipeline):
    cfg = HetaConfig(
        data=DataConfig(dataset="ogbn-mag", scale=0.002, fanouts=(3, 2),
                        batch_size=16),
        partition=PartitionConfig(num_partitions=2),
        model=ModelConfig(hidden=32, train_learnable=learnable),
        cache=CacheConfig(cache_mb=cache_mb),
        run=RunConfig(executor="raf_spmd", steps=3, lr=1e-2, seed=0),
    )
    return cfg.updated(pipeline=pipeline) if pipeline else cfg


def _session(cfg):
    sess = Heta(cfg)
    sess.build_graph()
    sess.partition()
    sess.profile_and_cache()
    sess.compile()
    return sess


def _rows(sess):
    """The session's staging counters: (device rows, host rows)."""
    c = sess.results()["counters"]
    return c.get("heta.stage.device_rows"), c.get("heta.stage.host_rows")


# --------------------------------------------------------------------------
# the gathered arrays equal the host gather on every slot the mask keeps
# --------------------------------------------------------------------------


@pytest.mark.parametrize("parts,learnable_dim", [(1, 64), (2, 64), (3, 64),
                                                 (2, 128)])
def test_device_gather_equals_host_gather(parts, learnable_dim):
    """Mixed widths (64-d learnable rows against 128-d paper features)
    exercise the ``d_pad`` zero padding; more than one partition left
    unfolded gives the recipe padding slots.  The parents' rows equal the
    host's bit for bit; the leaf rows arrive as ``[P*rb, n_prev, fanout,
    d_pad]``, equal to the host's on every slot the mask keeps and zero on
    the rest."""
    import jax

    from repro.core import raf_spmd
    from repro.core.hgnn import HGNNConfig, init_embed_tables
    from repro.core.meta_partition import meta_partition
    from repro.core.raf import assign_branches
    from repro.data.staging import stack_batch_host, stack_batch_nids
    from repro.graph.sampler import NeighborSampler, SampleSpec
    from repro.graph.synthetic import ogbn_mag_like

    g = ogbn_mag_like(scale=0.002)
    mp = meta_partition(g, parts, num_layers=2)
    spec = SampleSpec.from_metatree(mp.metatree, [4, 3])
    batch = NeighborSampler(g, spec, 16, seed=0).sample_batch(g.train_nodes[:16])
    feat_dims = {t: g.feat_dim(t) for t in g.num_nodes if g.feat_dim(t)}
    cfg = HGNNConfig(model="rgcn", hidden=32, num_layers=2,
                     num_classes=g.num_classes, learnable_dim=learnable_dim)
    plan = raf_spmd.build_plan(spec, assign_branches(spec, mp), cfg, feat_dims)
    recipe = raf_spmd.stack_recipe(plan)
    if parts > 1:
        assert any((lp.slot_branch < 0).any() for lp in plan.levels)
    assert {feat_dims.get(t, learnable_dim) for t in recipe.table_types()} \
        == ({128} if learnable_dim == 128 else {64, 128})

    tables = {t: np.asarray(f, np.float32) for t, f in g.features.items()}
    tables.update({t: np.asarray(v) for t, v in init_embed_tables(
        jax.random.PRNGKey(1), cfg, g.num_nodes, feat_dims).items()})
    device = {t: jnp.asarray(tables[t]) for t in recipe.table_types()}

    host = stack_batch_host(recipe, batch, tables)
    nids = stack_batch_nids(recipe, batch)
    assert all(v.dtype == np.int32 for k, v in nids.items() if "nid" in k)
    assert {k.replace("nid", "feat") for k in nids} == set(host)
    dev = raf_spmd.gather_resident(
        plan, {k: jnp.asarray(v) for k, v in nids.items()}, device)
    assert set(dev) == set(host)
    k = spec.num_layers
    for key, v in host.items():
        got = np.asarray(dev[key]) if "feat" in key else nids[key]
        if key == f"hfeat{k}":
            f = spec.fanouts[k - 1]
            assert got.shape == (v.shape[0], v.shape[1] // f, f, v.shape[2])
            got = got.reshape(v.shape)
            keep = nids[f"mask{k}"]
            assert keep.any() and not keep.all()
            assert np.array_equal(got[keep], v[keep])
            assert not got[~keep].any()
        else:  # parents' rows, seeds, labels and masks as they are
            assert got.dtype == v.dtype and got.shape == v.shape, key
            assert np.array_equal(got, v), key


def _take_oracle(tables, tab_of, nids, mask, fanout, f_k, d_pad):
    """The contract of ``gather_leaf`` in plain numpy."""
    S, n_d = nids.shape
    out = np.zeros((S, n_d // fanout, f_k, d_pad), np.float32)
    for s, t in enumerate(tab_of):
        if t < 0:
            continue
        for r in np.flatnonzero(mask[s]):
            row = np.asarray(tables[t])[nids[s, r]]
            out[s, r // fanout, r % fanout, :row.shape[0]] = row
    return out


# widths of the slots' tables, fanout, the padded fanout f_k, parents per
# slot; each with a padding slot
GATHER_CASES = {
    "attention-f20-tile24": ((128, 128, 128), 20, 24, 32),
    "mean-linear-f20": ((128, 128), 20, 20, 37),
    "f8-whole-tiles": ((128,), 8, 8, 24),
    "f5-tile8": ((128, 128), 5, 8, 13),
    "mixed-widths-64-128": ((64, 128), 3, 8, 10),
}


@pytest.mark.parametrize("case", list(GATHER_CASES))
def test_stacked_gather_matches_take(case):
    """``gather_leaf`` against a plain take: equal on every kept slot;
    zero on masked-out slots, on the fanout padding, on padding slots and
    on the lanes past a narrower table's width."""
    from repro.core.raf_spmd import gather_leaf

    widths, fanout, f_k, n = GATHER_CASES[case]
    rng = np.random.default_rng(len(case))
    tables = [jnp.asarray(rng.normal(size=(50 + 7 * t, w)).astype(np.float32))
              for t, w in enumerate(widths)]
    tab_of = [t for t in range(len(widths))] + [-1, 0]
    nids = np.stack([rng.integers(0, tables[max(t, 0)].shape[0], n * fanout)
                     for t in tab_of]).astype(np.int32)
    mask = rng.random(nids.shape) < 0.8
    mask[len(widths)] = False  # the padding slot's mask, as staged
    d_pad = max(widths)

    want = _take_oracle(tables, tab_of, nids, mask, fanout, f_k, d_pad)
    got = np.asarray(gather_leaf(
        [tables[t] if t >= 0 else None for t in tab_of], jnp.asarray(nids),
        jnp.asarray(mask), fanout=fanout, f_k=f_k, d_pad=d_pad))
    assert got.shape == want.shape and got.dtype == np.float32
    assert np.array_equal(got, want)
    keep = mask.reshape(len(tab_of), n, fanout)
    assert not got[:, :, :fanout][~keep].any()
    assert not got[:, :, fanout:].any() and not got[len(widths)].any()


# --------------------------------------------------------------------------
# sessions: the path engages where it can and trains the same
# --------------------------------------------------------------------------


@pytest.mark.parametrize("learnable", [False, True], ids=["frozen", "learnable"])
def test_fit_losses_equal_on_both_paths(learnable, monkeypatch,
                                       device_gather_on_cpu):
    """Three ``fit`` steps on the resident path and, in a session alike, on
    the host path (residency hidden from the executor) give equal losses;
    learnable rows end equal too."""
    resident = _session(_cfg(learnable=learnable))
    assert resident.executor._resident(resident, resident.plan) is not None
    resident.fit(3)
    assert _rows(resident)[1] is None and _rows(resident)[0] > 0

    host = _session(_cfg(learnable=learnable))
    monkeypatch.setattr(type(host.executor), "_resident",
                        lambda self, sess, plan: None)
    host.fit(3)
    assert _rows(host)[0] is None and _rows(host)[1] == _rows(resident)[0]

    assert resident.losses == host.losses
    for t in resident.engine.learnable_types:
        assert np.array_equal(resident.engine.table(t), host.engine.table(t)), t


@pytest.mark.parametrize("model", ["rgat", "hgt"])
def test_padded_leaf_layout_trains_as_the_host_path(model, monkeypatch,
                                                     device_gather_on_cpu):
    """The attention kernels (interpret mode) pad the fanout to whole
    sublane tiles; the resident step gathers the leaf rows at that padded
    fanout, and two steps train the same losses as the host path, whose
    kernels pad the staged rows themselves."""
    from repro.kernels.stacked_relation_agg import fanout_tile

    cfg = _cfg().updated(model=dict(model=model, num_heads=2),
                         kernels=dict(interpret=True))
    resident = _session(cfg)
    plan = resident.plan.plan
    assert fanout_tile(plan.module, resident.config.kernels) == 8
    assert plan.levels[-1].fanout == 2
    resident.fit(2)

    host = _session(cfg)
    monkeypatch.setattr(type(host.executor), "_resident",
                        lambda self, sess, plan: None)
    host.fit(2)
    assert resident.losses == host.losses


def test_feature_grads_reach_the_tables_as_staged(monkeypatch,
                                                  device_gather_on_cpu):
    """On the learnable path the step's gradient of the 4-D ``hfeat{k}``
    reaches ``apply_feature_grads`` as ``[P*rb, n_d, d_pad]``, fanout
    padding cut, as host staging's would."""
    from repro.api import executors
    from repro.core import raf_spmd

    seen = []
    apply = executors.apply_feature_grads

    def recording(engine, plan, batch, gf):
        seen.append({key: v.shape for key, v in gf.items()})
        apply(engine, plan, batch, gf)

    monkeypatch.setattr(executors, "apply_feature_grads", recording)
    sess = _session(_cfg(learnable=True))
    sess.step()
    plan = sess.plan.plan
    k, lp = plan.spec.num_layers, plan.levels[-1]
    n_d = sess._batch_for_step(0).levels[-1].nids.shape[1]
    assert seen and seen[0][f"hfeat{k}"] == (
        plan.num_shards * lp.rb, n_d, plan.d_pad)

    # a leaf gathered at a padded fanout (the attention kernels' tile):
    # fanout 5 laid out at 8
    from types import SimpleNamespace as NS

    g = jnp.arange(2 * 3 * 8 * 4, dtype=jnp.float32).reshape(2, 3, 8, 4)
    padded = NS(spec=NS(num_layers=2), levels=[None, NS(fanout=5)])
    cut = raf_spmd.feat_grads_as_staged(padded, {"hfeat2": g})["hfeat2"]
    assert np.array_equal(cut, g[:, :, :5].reshape(2, 15, 4))


def test_cpu_backend_stays_on_the_host_path():
    """On the CPU backend a cache that holds every row still stages on the
    host: snapshot, host gather, float32 rows."""
    sess = _session(_cfg())
    assert all(len(c.ids) == sess.engine.cache.host[t].shape[0]
               for t, c in sess.engine.cache.caches.items())
    assert sess.executor._resident(sess, sess.plan) is None
    assert sess.executor.worker_stage_recipe(sess, sess.plan) is not None
    sess.fit(2)
    device_rows, host_rows = _rows(sess)
    assert device_rows is None and host_rows > 0
    assert sess.results()["counters"]["heta.stage.snapshot_bytes"] > 0


def test_partial_cache_stays_on_the_host_path(device_gather_on_cpu):
    sess = _session(_cfg(cache_mb=PARTIAL_MB))
    assert sess.executor._resident(sess, sess.plan) is None
    assert sess.executor.worker_stage_recipe(sess, sess.plan) is not None
    sess.fit(2)
    device_rows, host_rows = _rows(sess)
    assert device_rows is None and host_rows > 0
    assert sess.results()["counters"]["heta.stage.snapshot_bytes"] > 0


def test_two_device_mesh_stays_on_the_host_path():
    """A 1x2 mesh over a cache that holds every row, the resident gather
    let engage on the CPU backend (a fresh interpreter: jax fixes the
    device count on first import)."""
    code = r"""
import json
from repro.api import executors
from repro.api import (CacheConfig, DataConfig, Heta, HetaConfig,
                       ModelConfig, PartitionConfig, RunConfig)
cfg = HetaConfig(
    data=DataConfig(dataset="ogbn-mag", scale=0.002, fanouts=(3, 2),
                    batch_size=16),
    partition=PartitionConfig(num_partitions=2),
    model=ModelConfig(hidden=32, train_learnable=False),
    cache=CacheConfig(cache_mb=%d),
    run=RunConfig(executor="raf_spmd", steps=2, lr=1e-2, seed=0,
                  mesh_shape=(1, 2)),
)
executors.HOST_MEMORY_PLATFORMS = frozenset()
s = Heta(cfg); s.build_graph(); s.partition(); s.profile_and_cache(); s.compile()
full = all(len(s.engine.cache.caches[t].ids) == s.engine.cache.host[t].shape[0]
           for t in s.engine.cache.host)
s.fit(2)
print(json.dumps({"full": full, "devices": int(s.plan.mesh.devices.size),
                  "resident": s.executor._resident(s, s.plan) is not None,
                  "counters": s.results()["counters"]}))
""" % FULL_MB
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["full"] and got["devices"] == 2 and not got["resident"]
    assert "heta.stage.device_rows" not in got["counters"]
    assert got["counters"]["heta.stage.host_rows"] > 0


@pytest.mark.parametrize("learnable", [False, True], ids=["frozen", "learnable"])
def test_workers_only_sample_on_the_resident_path(learnable,
                                                  device_gather_on_cpu):
    """On the resident path the pool gets no staging recipe and staging
    reads no table; a pooled fit stages on the consumer and trains as the
    serial fit does."""
    pooled = _session(_cfg(learnable=learnable, num_workers=1,
                           snapshot="stale"))
    try:
        ex = pooled.executor
        assert ex.worker_stage_recipe(pooled, pooled.plan) is None
        assert ex.stage_reads_tables(pooled, pooled.plan) is False
        pooled.fit(3)
    finally:
        pooled.close_pipeline()
    serial = _session(_cfg(learnable=learnable))
    serial.fit(3)
    assert pooled.losses == serial.losses
    assert _rows(pooled) == _rows(serial)


def test_a_batch_staged_before_a_rebalance_steps_on_the_new_caches(
        device_gather_on_cpu):
    """Once a rebalance replaces the caches (each still whole, so still in
    node-id order), the batch staged before it steps as staged, with the
    new caches' rows, to the same loss."""
    sess = _session(_cfg())
    ex, plan = sess.executor, sess.plan
    batch = sess._batch_for_step(0)
    staged = ex.stage(sess, plan, batch)
    before = ex._inputs(sess, plan, batch, staged)
    old = dict(sess.engine.cache.caches)
    sess.engine.rebalance()
    assert all(sess.engine.cache.caches[t] is not old[t] for t in before[1])
    after = ex._inputs(sess, plan, batch, staged)
    assert after[0] is staged
    for t, rows in after[1].items():
        assert rows is sess.engine.cache.caches[t].data, t
    assert float(plan.loss(sess.state["stacks"], *after)) == float(
        plan.loss(sess.state["stacks"], *before))


def test_a_batch_staged_before_the_cache_turned_partial_is_staged_again(
        device_gather_on_cpu):
    """Once a table leaves the device in part, node ids staged before can
    no longer be gathered there: the step stages the batch again, on the
    host, to the same loss."""
    sess = _session(_cfg())
    ex, plan = sess.executor, sess.plan
    batch = sess._batch_for_step(0)
    staged = ex.stage(sess, plan, batch)
    before = ex._inputs(sess, plan, batch, staged)
    cache = sess.engine.cache
    ids = cache.residency()
    t = next(iter(before[1]))
    ids[t] = ids[t][: len(ids[t]) // 2]
    with sess.engine.lock:
        cache.set_residency(ids)
    after = ex._inputs(sess, plan, batch, staged)
    assert len(after) == 1 and "qfeat1" in after[0] and "qnid1" not in after[0]
    assert float(plan.loss(sess.state["stacks"], *after)) == float(
        plan.loss(sess.state["stacks"], *before))


def test_a_whole_table_cache_is_in_node_id_order():
    """A cache that holds every row of a type lays it out in node-id order
    (a node id is its slot), however it was built: at allocation, by a
    rebalance, by a restore.  A partial cache keeps the hottest first."""
    full = _session(_cfg()).engine
    partial = _session(_cfg(cache_mb=PARTIAL_MB)).engine

    def check(engine):
        for t, c in engine.cache.caches.items():
            n = engine.cache.host[t].shape[0]
            if len(c.ids) == n:
                assert np.array_equal(c.ids, np.arange(n)), t
                assert np.array_equal(c.slot_of, np.arange(n)), t
            else:
                assert (np.diff(c.ids) < 0).any(), t  # hottest first
        return {t for t, c in engine.cache.caches.items()
                if len(c.ids) == engine.cache.host[t].shape[0]}

    assert check(full) == set(full.cache.host)
    assert check(partial) != set(partial.cache.host)
    for engine in (full, partial):
        engine.rebalance()
        check(engine)
        with engine.lock:
            ids = engine.cache.residency()
            engine.cache.set_residency(
                {t: a[np.random.default_rng(0).permutation(len(a))]
                 for t, a in ids.items()})
        check(engine)
