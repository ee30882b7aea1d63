"""Hierarchical scale-out (DESIGN.md §13): hierarchy ownership invariants,
mmap-store lifecycle + attach parity, the DP exchange protocol, restricted
per-trainer rebuild bit-identity, and 2-trainer data-parallel fit parity
against the single-process trajectory."""

import multiprocessing as mp
import os
import threading

import numpy as np
import pytest

from repro.api import Heta, HetaConfig
from repro.core.meta_partition import hierarchical_partition
from repro.graph.synthetic import mag240m_stream, ogbn_mag_like

pytestmark = pytest.mark.skipif(
    not os.path.isdir("/dev/shm"), reason="no /dev/shm on this platform"
)


def _quick_cfg(steps=3, **scale):
    cfg = HetaConfig.from_dict(dict(
        data=dict(dataset="ogbn-mag", scale=0.002, fanouts=(3, 2),
                  batch_size=16),
        model=dict(hidden=16, num_heads=2, train_learnable=False),
        run=dict(executor="raf_spmd", steps=steps, seed=11, log_every=0),
        pipeline=dict(num_workers=0),
    ))
    return cfg.updated(scale=scale) if scale else cfg


def _built(cfg):
    sess = Heta(cfg)
    sess.build_graph()
    sess.partition()
    sess.profile_and_cache()
    sess.compile()
    return sess


# --------------------------------------------------------------------------
# hierarchy ownership
# --------------------------------------------------------------------------


def test_hierarchy_ownership_invariant():
    """Every node owned by exactly one (group, sub); rank seed slices are
    disjoint and their concatenation is a permutation of train_nodes."""
    g = ogbn_mag_like(scale=0.002)
    hier = hierarchical_partition(g, num_groups=2, trainers_per_group=2,
                                  num_layers=2, seed=3)
    hier.validate_ownership(g)
    slices = [hier.trainer_train_nodes(g, r)
              for r in range(hier.num_trainers)]
    allid = np.concatenate(slices)
    assert len(allid) == len(g.train_nodes)
    assert len(np.unique(allid)) == len(allid)  # disjoint
    assert np.array_equal(np.sort(allid), np.sort(g.train_nodes))
    for r, s in enumerate(slices):
        ranks = hier.rank_of(g.target_type, s)
        assert (ranks == r).all()


def test_hierarchy_rank_out_of_range():
    g = ogbn_mag_like(scale=0.002)
    hier = hierarchical_partition(g, 2, 2)
    with pytest.raises(ValueError):
        hier.trainer_train_nodes(g, 4)


# --------------------------------------------------------------------------
# mmap store: attach parity, num_nodes ordering, janitor
# --------------------------------------------------------------------------


def test_mmap_attach_parity_and_order():
    """Attached twin is bit-equal AND iterates node types in the source
    graph's insertion order (type-arena offsets depend on it)."""
    from repro.graph.mmap_store import attach_any, live_stores, mmap_share_graph

    g = ogbn_mag_like(scale=0.002)
    store = mmap_share_graph(g, include_features=True)
    try:
        att = attach_any(store.handle)
        assert list(att.graph.num_nodes) == list(g.num_nodes)
        assert att.graph.num_nodes == g.num_nodes
        for r, csr in g.relations.items():
            np.testing.assert_array_equal(csr.indices,
                                          att.graph.relations[r].indices)
        for t, f in g.features.items():
            np.testing.assert_array_equal(f, att.graph.features[t])
        np.testing.assert_array_equal(g.train_nodes, att.graph.train_nodes)
        att.close()
    finally:
        store.unlink()
    assert store.handle.path.split(os.sep)[-1] not in live_stores()


def test_shm_handle_preserves_num_nodes_order():
    from repro.graph.shm import attach, share_graph

    g = ogbn_mag_like(scale=0.002)
    with share_graph(g, include_features=False) as store:
        att = attach(store.handle)
        assert list(att.graph.num_nodes) == list(g.num_nodes)
        att.close()


def test_mmap_janitor_reaps_dead_owner_store():
    from repro.graph import mmap_store as ms

    g = ogbn_mag_like(scale=0.002)
    store = ms.mmap_share_graph(g, include_features=False)
    name = os.path.basename(store.handle.path)
    try:
        # alive owner: never reaped
        assert name not in ms.cleanup_stale_stores()
        # forge a dead-owner name in the same root
        dead = name.replace(f"{os.getpid():x}", "3ffffffe", 1)
        os.rename(store.handle.path, os.path.join(
            os.path.dirname(store.handle.path), dead))
        assert dead in ms.cleanup_stale_stores()
        assert dead not in ms.live_stores()
    finally:
        store.unlink()


def test_mag240m_stream_tiny_attaches():
    """The chunk-wise builder commits a well-formed store at tiny scale."""
    from repro.graph.mmap_store import attach_any

    store = mag240m_stream(scale=1e-6, chunk_edges=128)
    try:
        att = attach_any(store.handle)
        g = att.graph
        assert g.target_type == "paper"
        assert set(g.num_nodes) == {"paper", "author", "institution"}
        for csr in g.relations.values():
            n_src = csr.indptr.size - 1
            assert csr.indptr[0] == 0
            assert (np.diff(csr.indptr) >= 0).all()
            assert n_src in g.num_nodes.values() or n_src > 0
        att.close()
    finally:
        store.unlink()


# --------------------------------------------------------------------------
# DP exchange protocol (threads stand in for processes; same Condition)
# --------------------------------------------------------------------------


def test_dp_exchange_fixed_order_reduction():
    from repro.data.dp_trainer import attach_exchange, create_exchange

    leaves = [np.zeros((4, 3), np.float32), np.zeros((2,), np.float64)]
    cond = mp.get_context("spawn").Condition()
    ex0 = create_exchange(leaves, num_ranks=2, cond=cond, depth=2)
    ex1 = attach_exchange(ex0.handle, cond, rank=1, template_leaves=leaves)
    steps, got = 5, {}

    def rank_main(ex, rank):
        rng = np.random.default_rng(100 + rank)
        out = []
        for k in range(steps):
            mine = [rng.standard_normal((4, 3)).astype(np.float32),
                    rng.standard_normal(2)]
            ex.contribute(k, mine, order=rank, num_contrib=2,
                          loss=float(rank + k), batch_size=8)
            red, loss_row, bs_row = ex.consume(k)
            out.append((mine, red, loss_row.copy(), bs_row.copy()))
        got[rank] = out

    t = threading.Thread(target=rank_main, args=(ex1, 1), daemon=True)
    t.start()
    rank_main(ex0, 0)
    t.join(timeout=30)
    assert not t.is_alive()
    for k in range(steps):
        m0, r0, l0, b0 = got[0][k]
        m1, r1, _, _ = got[1][k]
        # fixed order: rank0 copy then rank1 += — both see identical sums
        expect = [m0[i] + m1[i] for i in range(2)]
        for i in range(2):
            np.testing.assert_array_equal(r0[i], expect[i])
            np.testing.assert_array_equal(r1[i], expect[i])
        assert list(l0) == [float(k), float(1 + k)]
        assert list(b0) == [8, 8]
    ex1.close()
    ex0.unlink()


def test_dp_exchange_template_mismatch_fails_fast():
    from repro.data.dp_trainer import DPError, attach_exchange, create_exchange

    leaves = [np.zeros((4, 3), np.float32)]
    cond = mp.get_context("spawn").Condition()
    ex0 = create_exchange(leaves, num_ranks=2, cond=cond)
    with pytest.raises(DPError, match="mismatch"):
        attach_exchange(ex0.handle, cond, rank=1,
                        template_leaves=[np.zeros((3, 4), np.float32)])
    with pytest.raises(DPError, match="leaves"):
        attach_exchange(ex0.handle, cond, rank=1,
                        template_leaves=[np.zeros((4, 3), np.float32)] * 2)
    ex0.unlink()


def test_dp_exchange_scalar_leaf_roundtrip():
    """0-d pytree leaves survive the at-least-1-d wire canonicalisation."""
    import jax.numpy as jnp

    from repro.data.dp_trainer import _adopt, _host_leaves

    tree = {"w": jnp.ones((2, 2)), "t": jnp.asarray(3, jnp.int32)}
    host = _host_leaves(tree)
    assert all(h.ndim >= 1 for h in host)
    back = _adopt(tree, host)
    assert back["t"].shape == ()
    assert int(back["t"]) == 3
    np.testing.assert_array_equal(np.asarray(back["w"]), np.ones((2, 2)))


# --------------------------------------------------------------------------
# restricted rebuild + DP fit parity
# --------------------------------------------------------------------------


def test_trainer_rebuild_bit_identity():
    """A trainer's deterministic rebuild — config dict round-trip plus the
    attached shared store — reproduces the parent's compiled state, staged
    arrays, and step losses bit for bit (the premise of both DP modes)."""
    from repro.data.dp_trainer import state_sha
    from repro.graph.mmap_store import attach_any
    from repro.graph.shm import share_graph

    parent = _built(_quick_cfg())
    store = share_graph(parent.graph, include_features=True)
    try:
        att = attach_any(store.handle)
        child = Heta(HetaConfig.from_dict(parent.config.to_dict())
                     .updated(pipeline=dict(num_workers=0)))
        child.build_graph(graph=att.graph)
        child.partition()
        child.profile_and_cache()
        child.compile()
        assert state_sha(parent.state) == state_sha(child.state)
        l1 = parent.step()
        l2 = child.step()
        assert float(l1) == float(l2)
        assert state_sha(parent.state) == state_sha(child.state)
        att.close()
    finally:
        store.unlink()


def test_dp_fit_global_bit_identical_to_single():
    """The ISSUE's acceptance: 2-trainer DP fit (stripe discipline) must
    reproduce the single-process loss trajectory bitwise."""
    from repro.graph import mmap_store as ms

    single = _built(_quick_cfg(steps=4))
    single.fit()
    before = set(ms.live_stores())
    dp = _built(_quick_cfg(steps=4, num_trainers=2, mode="global"))
    res = dp.fit()
    assert list(map(float, dp.losses)) == list(map(float, single.losses))
    assert res["scale"]["num_trainers"] == 2
    assert res["scale"]["mode"] == "global"
    # the fit leaked no mmap stores (co-tenant processes may own some)
    assert set(ms.live_stores()) <= before


def test_dp_fit_local_mode_converges_identically_across_trainers():
    """Local mode: hierarchy-owned sub-batches, fixed-rank-order gradient
    reduction.  run_dp_fit itself asserts the cross-trainer loss lists and
    final state hashes match bitwise; here we check it completes and books
    the trajectory."""
    dp = _built(_quick_cfg(steps=3, num_trainers=2, mode="local"))
    res = dp.fit()
    assert res["scale"]["mode"] == "local"
    assert len(dp.losses) == 3
    assert all(np.isfinite(dp.losses))


def test_dp_fit_on_tpu_fails_fast_without_spawning(monkeypatch):
    """On a TPU this process holds the chip: a multi-trainer fit must refuse
    (pointing at the mesh data axis) before it starts any child."""
    import jax

    from repro.api.session import HetaStageError

    sess = _built(_quick_cfg(steps=2, num_trainers=2, mode="global"))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(HetaStageError, match="--mesh"):
        sess.fit()
    assert mp.active_children() == []
    assert sess.losses == []


def test_dp_fit_rejects_learnable_tables():
    from repro.api.session import HetaStageError

    cfg = _quick_cfg(steps=2, num_trainers=2, mode="local")
    cfg = cfg.updated(model=dict(train_learnable=True))
    sess = _built(cfg)
    with pytest.raises(HetaStageError, match="frozen"):
        sess.fit()
