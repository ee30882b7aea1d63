"""Sampler invariants: static shapes, masks, index validity, determinism."""

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.metatree import build_metatree
from repro.graph.hetgraph import CSR, Relation
from repro.graph.sampler import NeighborSampler, SampleSpec, sample_neighbors
from repro.graph.synthetic import make_dataset, ogbn_mag_like


@pytest.fixture(scope="module")
def setup():
    g = ogbn_mag_like(scale=0.002)
    tree = build_metatree(g.metagraph(), g.target_type, 2)
    spec = SampleSpec.from_metatree(tree, [5, 4])
    return g, spec


def test_static_shapes(setup):
    g, spec = setup
    sampler = NeighborSampler(g, spec, 8, seed=0)
    b = sampler.sample_batch(g.train_nodes[:8])
    n = {d: 8 for d in range(3)}
    n[1] = 8 * 5
    n[2] = 8 * 5 * 4
    for d, lv in enumerate(b.levels, start=1):
        assert lv.nids.shape == (len(spec.levels[d - 1]), n[d])
        assert lv.mask.shape == lv.nids.shape


def test_indices_within_type_range(setup):
    g, spec = setup
    sampler = NeighborSampler(g, spec, 16, seed=1)
    b = sampler.sample_batch(g.train_nodes[:16])
    for lv, branches in zip(b.levels, spec.levels):
        for i, bs in enumerate(branches):
            assert lv.nids[i].max() < g.num_nodes[bs.src_type]
            assert lv.nids[i].min() >= 0


def test_sampled_are_real_neighbors(setup):
    """Every unmasked sample must be an actual in-neighbor under the branch's
    relation."""
    g, spec = setup
    sampler = NeighborSampler(g, spec, 4, seed=2)
    b = sampler.sample_batch(g.train_nodes[:4])
    lv = b.levels[0]
    for i, bs in enumerate(spec.levels[0]):
        csr = g.relations[bs.rel]
        f = spec.fanouts[0]
        for parent_pos, parent in enumerate(b.seeds):
            nbrs = set(csr.indices[csr.indptr[parent]:csr.indptr[parent + 1]])
            for j in range(f):
                slot = parent_pos * f + j
                if lv.mask[i, slot]:
                    assert lv.nids[i, slot] in nbrs


def test_mask_false_iff_zero_degree_chain(setup):
    g, spec = setup
    sampler = NeighborSampler(g, spec, 8, seed=3)
    b = sampler.sample_batch(g.train_nodes[:8])
    lv1 = b.levels[0]
    for i, bs in enumerate(spec.levels[0]):
        deg = g.relations[bs.rel].degrees()[b.seeds]
        expect = np.repeat(deg > 0, spec.fanouts[0])
        np.testing.assert_array_equal(lv1.mask[i], expect)


def test_epoch_covers_train_nodes(setup):
    g, spec = setup
    sampler = NeighborSampler(g, spec, 64, seed=4)
    seen = []
    for b in sampler.epoch(shuffle=True, seed=9):
        seen.append(b.seeds)
    seen = np.concatenate(seen)
    assert len(seen) == sampler.steps_per_epoch() * 64
    assert len(np.unique(seen)) == len(seen)  # no duplicates within an epoch


@given(
    num_src=st.integers(1, 50),
    num_dst=st.integers(1, 50),
    num_edges=st.integers(0, 200),
    fanout=st.integers(1, 8),
    seed=st.integers(0, 999),
)
@settings(max_examples=30, deadline=None)
def test_sample_neighbors_property(num_src, num_dst, num_edges, fanout, seed):
    rng = np.random.default_rng(seed)
    if num_edges:
        csr = CSR.from_edges(
            rng.integers(0, num_src, num_edges), rng.integers(0, num_dst, num_edges),
            num_dst,
        )
    else:
        csr = CSR(np.zeros(num_dst + 1, np.int64), np.zeros(0, np.int64))
    parents = rng.integers(0, num_dst, 7)
    pm = np.ones(7, bool)
    idx, mask = sample_neighbors(csr, parents, pm, fanout, rng)
    assert idx.shape == (7, fanout) and mask.shape == (7, fanout)
    deg = csr.degrees()[parents]
    np.testing.assert_array_equal(mask.all(axis=1), deg > 0)
    if num_edges:
        assert idx.max() < num_src


def test_all_datasets_sample():
    for name in ("ogbn-mag", "freebase", "donor", "igb-het", "mag240m"):
        g = make_dataset(name)
        tree = build_metatree(g.metagraph(), g.target_type, 2)
        spec = SampleSpec.from_metatree(tree, [3, 2])
        sampler = NeighborSampler(g, spec, 4, seed=0)
        b = sampler.sample_batch(g.train_nodes[:4])
        assert b.total_sampled() > 4


def _plain_sample_neighbors(csr, parents, parent_mask, fanout, rng):
    """The draw written out with a fresh array per operation."""
    deg = csr.indptr[parents + 1] - csr.indptr[parents]
    valid = (deg > 0) & parent_mask
    offs = (rng.random((len(parents), fanout))
            * np.maximum(deg, 1)[:, None]).astype(np.int64)
    raw = np.minimum(csr.indptr[parents][:, None] + offs, csr.num_edges - 1)
    idx = np.where(valid[:, None], csr.indices[raw], 0)
    return idx, np.broadcast_to(valid[:, None], idx.shape)


@pytest.mark.parametrize("num_dst,num_edges,fanout,masked", [
    (50, 400, 6, False),   # every parent has neighbors
    (300, 40, 5, False),   # most parents have degree 0
    (80, 300, 3, True),    # some parents masked out
    (60, 300, 1, True),
])
def test_sample_neighbors_is_the_plain_draw_bit_for_bit(num_dst, num_edges,
                                                         fanout, masked):
    g = np.random.default_rng(num_dst + num_edges)
    csr = CSR.from_edges(g.integers(0, 40, num_edges),
                         g.integers(0, num_dst, num_edges), num_dst)
    parents = g.integers(0, num_dst, 97)
    pm = g.random(97) < 0.7 if masked else np.ones(97, bool)
    idx, mask = sample_neighbors(csr, parents, pm, fanout,
                                 np.random.default_rng(7))
    ref_idx, ref_mask = _plain_sample_neighbors(csr, parents, pm, fanout,
                                                np.random.default_rng(7))
    assert idx.dtype == ref_idx.dtype and mask.dtype == ref_mask.dtype
    np.testing.assert_array_equal(idx, ref_idx)
    np.testing.assert_array_equal(mask, ref_mask)
    assert mask.flags.writeable
