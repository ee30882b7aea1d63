"""Shared-memory graph store (repro.graph.shm): attach round-trip
bit-equality, zero-copy views, lifecycle (close/unlink), and the no-leaked-
segments guarantee on error paths."""

import os
import pickle

import numpy as np
import pytest

from repro.core.metatree import build_metatree
from repro.graph.sampler import NeighborSampler, SampleSpec
from repro.graph.shm import attach, live_segments, share_graph
from repro.graph.synthetic import ogbn_mag_like

pytestmark = pytest.mark.skipif(
    not os.path.isdir("/dev/shm"), reason="no /dev/shm on this platform"
)


def _graph():
    return ogbn_mag_like(scale=0.002)


def _spec(g, fanouts=(3, 2)):
    tree = build_metatree(g.metagraph(), g.target_type, len(fanouts))
    return SampleSpec.from_metatree(tree, fanouts)


def test_attach_round_trip_bit_equal():
    g = _graph()
    with share_graph(g, include_features=True) as store:
        att = attach(store.handle)
        assert att.graph.num_nodes == g.num_nodes
        assert att.graph.target_type == g.target_type
        assert att.graph.num_classes == g.num_classes
        assert set(att.graph.relations) == set(g.relations)
        for r, csr in g.relations.items():
            np.testing.assert_array_equal(csr.indptr, att.graph.relations[r].indptr)
            np.testing.assert_array_equal(csr.indices, att.graph.relations[r].indices)
            assert att.graph.relations[r].indices.dtype == csr.indices.dtype
        np.testing.assert_array_equal(g.labels, att.graph.labels)
        np.testing.assert_array_equal(g.train_nodes, att.graph.train_nodes)
        for t, f in g.features.items():
            np.testing.assert_array_equal(f, att.graph.features[t])
        att.close()
    assert not live_segments(store.handle.segment)


def test_attached_views_are_zero_copy_and_read_only():
    g = _graph()
    with share_graph(g) as store:
        att = attach(store.handle)
        # mutate through the owner's view; the attached view must see it
        # (same physical memory, not a pickled copy)
        owner_labels = store._array("labels")
        before = int(att.graph.labels[0])
        owner_labels[0] = before + 1
        assert int(att.graph.labels[0]) == before + 1
        owner_labels[0] = before
        # worker-side views are read-only: accidental writes would corrupt
        # the shared graph under every other worker
        with pytest.raises(ValueError):
            att.graph.labels[0] = 0
        att.close()


def test_sampler_on_attached_graph_bit_identical():
    g = _graph()
    spec = _spec(g)
    with share_graph(g) as store:
        att = attach(store.handle)
        s_host = NeighborSampler(g, spec, 8, seed=5)
        s_shm = NeighborSampler(att.graph, spec, 8, seed=5)
        for i in (0, 3, 1):  # out of order on purpose
            a = s_host.batch_at(i, epoch_seed=11)
            b = s_shm.batch_at(i, epoch_seed=11)
            np.testing.assert_array_equal(a.seeds, b.seeds)
            np.testing.assert_array_equal(a.labels, b.labels)
            for la, lb in zip(a.levels, b.levels):
                np.testing.assert_array_equal(la.nids, lb.nids)
                np.testing.assert_array_equal(la.mask, lb.mask)
        att.close()


def test_handle_is_small_and_picklable():
    g = _graph()
    with share_graph(g) as store:
        blob = pickle.dumps(store.handle)
        # the whole point: workers get a handle, never the graph
        assert len(blob) < 10_000
        handle = pickle.loads(blob)
        att = attach(handle)
        np.testing.assert_array_equal(g.labels, att.graph.labels)
        att.close()


def test_unlink_on_close_removes_segment():
    g = _graph()
    store = share_graph(g)
    seg = store.handle.segment
    assert live_segments(seg) == [seg]
    store.unlink()
    assert not live_segments(seg)
    store.unlink()  # idempotent
    with pytest.raises(FileNotFoundError):
        attach(store.handle)


def test_unshareable_dtype_rejected_without_segment():
    g = _graph()
    before = live_segments()
    # object arrays are pointers — meaningless in another process
    g.features["paper"] = np.array([[object()]], dtype=object)
    with pytest.raises(ValueError, match="object dtype"):
        share_graph(g, include_features=True)
    assert live_segments() == before


def test_create_failure_mid_populate_leaks_no_segment(monkeypatch):
    """A failure while populating the segment must close AND unlink it."""
    import repro.graph.shm as shm_mod

    g = _graph()
    before = live_segments()
    calls = []
    orig_copyto = np.copyto

    def exploding_copyto(dst, src, **kw):
        calls.append(1)
        if len(calls) == 3:  # fail part-way through population
            raise RuntimeError("disk full, or something")
        return orig_copyto(dst, src, **kw)

    monkeypatch.setattr(shm_mod.np, "copyto", exploding_copyto)
    with pytest.raises(RuntimeError, match="disk full"):
        share_graph(g)
    monkeypatch.undo()
    assert live_segments() == before


def test_owner_context_manager_unlinks_on_error():
    g = _graph()
    before = live_segments()
    with pytest.raises(RuntimeError, match="boom"):
        with share_graph(g):
            raise RuntimeError("boom")
    assert live_segments() == before
