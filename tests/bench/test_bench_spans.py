"""The per-layer readers over the program's own spans and counters
(``repro.obs``), on the tiny rgcn cell with ``--trace 1``."""

import sys
from types import SimpleNamespace

import numpy as np
import pytest

import tinycell
from bench import dataset, harness

SHARES = ["sample_share.train", "snapshot_share.train", "gather_share.train",
          "put_share.train", "device_wait_share.train"]
BYTES = ["snapshot_bytes_per_step.train", "staged_bytes_per_step.train"]
SEED = 11


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One traced run of the tiny rgcn cell: its result, and the session's
    staging recipe, a window batch and tables, from a session built alike."""
    from repro.core import raf_spmd
    from repro.launch import compile_cache

    tmp = tmp_path_factory.mktemp("spans")
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(compile_cache, "enable_compile_cache", lambda: "")
        mp.setattr(dataset, "CACHE", tmp / "datasets")
        result = tinycell.run(tmp, "rgcn", seed=SEED, trace=True)
        cell = harness.open_cell("tiny-rgcn.frozen", tinycell.bm("rgcn"),
                                 tinycell.root_with_limits(tmp, "rgcn"))
        ds = dataset.load(cell.config["dataset"])
        with harness.matmul_precision(cell):
            sess = harness.start_session(cell, dataset.to_hetgraph(ds), SEED)
    plan = sess.plan.plan
    out = SimpleNamespace(result=result, plan=plan,
                          recipe=raf_spmd.stack_recipe(plan),
                          batch=sess._batch_for_step(harness.WARM_STEPS),
                          tables=sess.engine.tables_snapshot())
    harness.release(sess)
    return out


def _value(traced, name):
    return traced.result["metrics"][name]["value"]


def test_every_new_metric_is_read_and_each_share_is_a_share(traced):
    assert traced.result["correct"] is True
    for name in SHARES + BYTES:
        assert name in traced.result["metrics"], name
    for name in SHARES:
        assert 0.0 <= _value(traced, name) <= 1.0, name


def test_host_spans_make_up_the_host_stage_share(traced):
    """Sampling, snapshot, gather and put lie inside the time before each
    device step, which ``host_stage_share.train`` reads.  The tiny cell's
    steps take milliseconds, so the host time no span covers weighs more
    here than on the chip."""
    host = _value(traced, "host_stage_share.train")
    parts = sum(_value(traced, n) for n in SHARES[:4])
    print(f"host stage share {host:.4f}, spans {parts:.4f}, "
          f"not covered {host - parts:.4f}")
    assert 0.5 * host <= parts <= host


def test_staged_bytes_are_the_stacked_batch_from_the_recipe(traced):
    from repro.data.staging import stack_batch_host

    r, b = traced.recipe, traced.batch
    want = b.seeds.nbytes + b.labels.nbytes
    n_prev = b.batch_size
    for d in range(1, r.num_layers + 1):
        slots = r.num_shards * r.slot_branch[d - 1].shape[1]
        n_d = b.levels[d - 1].nids.shape[1]
        want += slots * n_d  # mask, one byte a neighbor
        want += slots * n_prev * r.d_pad * 4  # qfeat, float32
        if d == r.num_layers:
            want += slots * n_d * r.d_pad * 4  # hfeat, float32
        n_prev = n_d
    host = stack_batch_host(r, b, traced.tables)
    assert sum(v.nbytes for v in host.values()) == want
    assert _value(traced, "staged_bytes_per_step.train") == want


def test_snapshot_bytes_are_every_table(traced):
    want = sum(t.nbytes for t in traced.tables.values())
    assert want == sum(int(np.prod(t.shape)) * 4 for t in traced.tables.values())
    assert _value(traced, "snapshot_bytes_per_step.train") == want


@pytest.mark.parametrize("name", SHARES + BYTES)
def test_a_reader_reads_nothing_without_its_steps(name, monkeypatch):
    """Steps the ring does not hold, or a program without the recorder (as
    a parent commit has), read as nothing."""
    import repro

    read = harness.reader(name)
    ctx = SimpleNamespace(window=SimpleNamespace(steps=10**6, wall_s=1.0))
    assert read(ctx) is None
    monkeypatch.delattr(repro, "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert read(SimpleNamespace(window=SimpleNamespace(steps=1, wall_s=1.0))) is None
