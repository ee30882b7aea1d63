"""The benchmark's tiny cells (``tests/bench/tinycell.py``) on the resident
path: their caches hold every row, and the resident gather is let engage
on the CPU backend, which keeps host staging otherwise.  The per-layer
readers see no snapshot, node ids put and every row gathered on the
device; a fault planted in the node ids' staging comes out not correct."""

import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "tests" / "bench")]

import tinycell  # noqa: E402
from bench import dataset, harness  # noqa: E402

SHARES = ["sample_share.train", "snapshot_share.train", "gather_share.train",
          "put_share.train", "device_wait_share.train",
          "device_gather_share.train"]
BYTES = ["snapshot_bytes_per_step.train", "staged_bytes_per_step.train"]
SEED = 11


@pytest.fixture(autouse=True)
def _cell_env(monkeypatch, tmp_path, device_gather_on_cpu):
    """A harness run here keeps the process's JAX configuration, writes its
    dataset under the test's own directory, and takes the resident path."""
    from repro.launch import compile_cache

    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "")
    monkeypatch.setattr(dataset, "CACHE", tmp_path / "datasets")


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One traced run of the tiny rgcn cell on the resident path: its
    result, and the session's staging recipe, a window batch, its tables
    and the counts of a snapshot of them, from a session built alike."""
    from repro import obs
    from repro.api import executors
    from repro.core import raf_spmd
    from repro.launch import compile_cache

    tmp = tmp_path_factory.mktemp("resident_cell")
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(executors, "HOST_MEMORY_PLATFORMS", frozenset())
        mp.setattr(compile_cache, "enable_compile_cache", lambda: "")
        mp.setattr(dataset, "CACHE", tmp / "datasets")
        # its own trace directory: the bench tests trace this cell too
        mp.setattr(harness, "CACHE", tmp / "cache")
        result = tinycell.run(tmp, "rgcn", seed=SEED, trace=True)
        cell = harness.open_cell("tiny-rgcn.frozen", tinycell.bm("rgcn"),
                                 tinycell.root_with_limits(tmp, "rgcn"))
        ds = dataset.load(cell.config["dataset"])
        with harness.matmul_precision(cell):
            sess = harness.start_session(cell, dataset.to_hetgraph(ds), SEED)
        resident = sess.executor._resident(sess, sess.plan) is not None
    plan = sess.plan.plan
    tables = sess.engine.tables_snapshot()
    snapshot = [r for r in obs.records() if r.name == "heta.stage.snapshot"][-1]
    out = SimpleNamespace(result=result, plan=plan, resident=resident,
                          recipe=raf_spmd.stack_recipe(plan),
                          batch=sess._batch_for_step(harness.WARM_STEPS),
                          tables=tables, snapshot_counts=snapshot.counts)
    harness.release(sess)
    return out


def _value(traced, name):
    return traced.result["metrics"][name]["value"]


def test_resident_run_reads_every_metric_and_is_correct(traced):
    assert traced.resident
    r = traced.result
    assert r["correct"] is True, r["checks"]
    for name in SHARES + BYTES:
        assert name in r["metrics"], name
    for name in SHARES:
        assert 0.0 <= _value(traced, name) <= 1.0, name


def test_resident_staged_bytes_are_node_ids(traced):
    """The resident path puts an int32 node id in place of each float32
    row of ``d_pad``: seeds, labels, a byte of mask a neighbor, and the
    ids."""
    from repro.data.staging import stack_batch_nids

    r, b = traced.recipe, traced.batch
    want = b.seeds.nbytes + b.labels.nbytes
    n_prev = b.batch_size
    for d in range(1, r.num_layers + 1):
        slots = r.num_shards * r.slot_branch[d - 1].shape[1]
        n_d = b.levels[d - 1].nids.shape[1]
        want += slots * n_d  # mask
        want += slots * n_prev * 4  # qnid, int32
        if d == r.num_layers:
            want += slots * n_d * 4  # hnid, int32
        n_prev = n_d
    staged = stack_batch_nids(r, b)
    assert sum(v.nbytes for v in staged.values()) == want
    assert _value(traced, "staged_bytes_per_step.train") == want


def test_resident_window_takes_no_snapshot(traced):
    """A snapshot counts every table; resident staging takes none, so the
    window reads 0 bytes and a share of 0.0, and every row is gathered on
    the device."""
    want = sum(a.nbytes for a in traced.tables.values())
    assert traced.snapshot_counts == {"heta.stage.snapshot_bytes": want}
    assert _value(traced, "snapshot_bytes_per_step.train") == 0
    assert _value(traced, "snapshot_share.train") == 0.0
    assert _value(traced, "device_gather_share.train") == 1.0


@pytest.mark.parametrize("counts,want", [
    ({"heta.stage.device_rows": 96}, 1.0),
    ({"heta.stage.host_rows": 96}, 0.0),
    ({}, None),
], ids=["resident", "host", "neither"])
def test_device_gather_share_reads_the_path_that_ran(counts, want):
    """Steps whose gather counted device rows read 1.0, host rows 0.0, and
    neither counter (a program before them) nothing; a window with no
    snapshot span reads a snapshot share of 0.0, not nothing."""
    from repro import obs

    sid = obs.new_session()
    steps = 2
    for i in range(harness.WARM_STEPS, harness.WARM_STEPS + steps):
        with obs.span(obs.STEP, step=i, session=sid):
            with obs.span("heta.stage.gather"):
                for name, n in counts.items():
                    obs.count(name, n)
    ctx = SimpleNamespace(window=SimpleNamespace(steps=steps, wall_s=1.0))
    assert harness.reader("device_gather_share.train")(ctx) == want
    assert harness.reader("snapshot_share.train")(ctx) == 0.0


@pytest.mark.parametrize("model", ["rgcn", "hgt"])
def test_labels_altered_in_the_node_id_staging(tmp_path, monkeypatch, model):
    """Labels shifted where the resident path stages them (the host gather
    never runs) come out not correct."""
    from repro.core import raf_spmd
    from repro.data import staging

    real = staging.stack_batch_nids

    def shifted(*args, **kw):
        out = real(*args, **kw)
        out["labels"] = (out["labels"] + 1) % 349
        return out

    def no_host_gather(*args, **kw):
        raise AssertionError("the host gather ran on the resident path")

    monkeypatch.setattr(staging, "stack_batch_nids", shifted)
    monkeypatch.setattr(raf_spmd, "stack_batch_host", no_host_gather)
    r = tinycell.run(tmp_path, model)
    assert r["correct"] is False
    assert r["checks"]["loss_gap"]["value"] > r["checks"]["loss_gap"]["limit"]
