"""The harness finds everything of a cell by name, from files: a new
configuration, traffic mix, cell or per-layer metric is new files and new
entries of ``BENCHMARK.json``, and no edit of a file under ``bench/``."""

import json
import re
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from bench import dataset, harness
from tinycell import FIXTURES

ROOT = harness.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_benchmark_json_holds_to_its_schema():
    bm = harness.benchmark()
    assert set(bm) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert bm["command"] == ["python3", "bench/run.py"]
    for p in bm["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/") and ".." not in p
    assert isinstance(bm["run_seconds"], int) and 1 <= bm["run_seconds"] <= 51
    names = set()
    for c in bm["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and (ROOT / c["file"]).is_file()
        assert c["file"].startswith("bench/")
        assert json.loads((ROOT / c["file"]).read_text())["name"] == c["name"]
        names.add(c["name"])
    cells = set()
    for w in bm["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in names and w["chips"] in (1, 4)
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        assert (harness.BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        assert (harness.BENCH / "limits" / f"{w['name']}.json").is_file()
        cells.add(w["name"])
    assert 2 * sum(w["chips"] == 4 for w in bm["workloads"]) <= max(2, len(cells))
    metrics = bm["end_to_end"] + bm["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert "setup_s" in {m["name"] for m in bm["end_to_end"]}
    for m in bm["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.25
    moves = {m["name"] for m in bm["end_to_end"]}
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert (harness.BENCH / "metrics" / f"{m['name']}.py").is_file()
        assert set(m.get("workloads", cells)) <= cells
    for m in bm["per_layer"]:
        assert m["moves"] in moves and 1 <= len(m["layer"]) <= 200
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    assert len(json.dumps(bm)) <= 64 * 1024


def test_every_cell_reports_setup_an_end_to_end_and_a_per_layer_metric():
    bm = harness.benchmark()
    for w in bm["workloads"]:
        cell = harness.open_cell(w["name"], bm)
        e2e = {m["name"] for m in harness.metrics_of(cell, "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert harness.metrics_of(cell, "per_layer")


def test_a_cell_added_as_files_only(tmp_path):
    """A new traffic mix, metric reader and limits file under another root,
    and new entries of the benchmark: the harness runs the new cell and
    reports the new metric, with every file under bench/ as it is."""
    before = {p: p.read_bytes() for p in harness.BENCH.rglob("*")
              if p.is_file() and ".cache" not in p.parts}
    bm = {
        "configs": [{"name": "tiny-hgt",
                     "file": "tests/bench/fixtures/configs/tiny-hgt.json"}],
        "workloads": [{"name": "tiny-hgt.steady", "config": "tiny-hgt",
                       "traffic": "steady", "chips": 1}],
        "end_to_end": harness.benchmark()["end_to_end"],
        "per_layer": [{"name": "window_steps", "unit": "steps",
                       "workloads": ["tiny-hgt.steady"]}],
    }
    root = FIXTURES / "added"
    cell = harness.open_cell("tiny-hgt.steady", bm, root)
    assert cell.heta["run"]["lr"] == 0.0025
    assert cell.heta["model"]["model"] == "hgt"
    r = harness.run("tiny-hgt.steady", 3, 0.2, True, time.perf_counter(),
                    require_tpu=False, bm=bm, root=root)
    assert r["correct"] is True
    assert r["metrics"]["window_steps"]["value"] == r["attempted"] >= 2
    after = {p: p.read_bytes() for p in harness.BENCH.rglob("*")
             if p.is_file() and ".cache" not in p.parts}
    assert after == before


def test_dataset_round_trips_bit_for_bit(tmp_path):
    params = {"generator": "ogbn-mag", "scale": 0.002, "seed": 3, "feat_dim": 16}
    built = dataset.build(params)
    first = dataset.load(params, cache=tmp_path)  # builds and writes
    second = dataset.load(params, cache=tmp_path)  # reads
    for ds in (first, second):
        assert ds["num_nodes"] == built["num_nodes"]
        for a, b in zip(ds["relations"], built["relations"]):
            assert a[:3] == b[:3]
            for x, y in zip(a[3:], b[3:]):
                assert x.dtype == y.dtype and np.array_equal(x, y)
        for k in ("labels", "train_nodes"):
            assert np.array_equal(ds[k], built[k])
        assert np.array_equal(ds["features"]["paper"], built["features"]["paper"])


def test_dataset_matches_the_programs_generator():
    """The copy draws what the program's generator draws: the same nodes,
    features, labels and multiset of edges per relation."""
    from repro.graph.synthetic import ogbn_mag_like

    ours = dataset.build({"generator": "ogbn-mag", "scale": 0.002, "seed": 3,
                          "feat_dim": 16})
    prog = ogbn_mag_like(scale=0.002, seed=3, feat_dim=16)
    assert ours["num_nodes"] == prog.num_nodes
    assert np.array_equal(ours["features"]["paper"], prog.features["paper"])
    assert np.array_equal(ours["labels"], prog.labels)
    for s, e, d, indptr, indices in ours["relations"]:
        csr = next(c for r, c in prog.relations.items() if (r.src, r.etype, r.dst) == (s, e, d))
        assert np.array_equal(indptr, csr.indptr)
        for v in range(len(indptr) - 1):
            row = indices[indptr[v]:indptr[v + 1]]
            assert np.array_equal(row, np.sort(csr.indices[csr.indptr[v]:csr.indptr[v + 1]]))


def _run_py(args, cwd, env_extra=None):
    import os

    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          capture_output=True, text=True, timeout=300, env=env)


def test_run_refuses_a_cpu():
    p = _run_py(["--workload", "rgcn-mag.frozen", "--seed", "1", "--seconds", "1",
                 "--trace", "0"], ROOT)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "TPU" in p.stderr


def test_run_refuses_a_directory_without_the_program(tmp_path):
    import shutil

    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    p = _run_py(["--workload", "rgcn-mag.frozen", "--seed", "1", "--seconds", "1",
                 "--trace", "0"], tmp_path)
    assert p.returncode != 0 and "{" not in p.stdout
