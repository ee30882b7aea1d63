"""The graph a cell trains on: generated from its configuration, kept on disk.

A configuration names a generator under ``bench/datasets/`` and its
parameters (scale, dataset seed, feature width).  The graph is a fixed
dataset, as a real ogbn-mag is: ``--seed`` never changes it.  The first run
in a checkout generates it and writes it under ``bench/.cache/datasets/``
(one ``.npy`` per array; the directory is renamed into place only when
complete); later runs load it.  ``np.save``/``np.load`` round-trip every
array bit for bit.
"""

from __future__ import annotations

import hashlib
import importlib.util
import json
import os
import shutil
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
CACHE = BENCH / ".cache" / "datasets"


def _generator(name: str):
    path = BENCH / "datasets" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_dataset_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod, path


def _key(params: dict, source: Path) -> str:
    blob = json.dumps(params, sort_keys=True).encode() + source.read_bytes()
    return hashlib.sha256(blob).hexdigest()[:16]


def _flatten(ds: dict):
    arrays = {"labels": ds["labels"], "train_nodes": ds["train_nodes"]}
    for t, f in ds["features"].items():
        arrays[f"feat.{t}"] = f
    for i, (_, _, _, indptr, indices) in enumerate(ds["relations"]):
        arrays[f"rel{i}.indptr"] = indptr
        arrays[f"rel{i}.indices"] = indices
    meta = {
        "num_nodes": ds["num_nodes"],
        "relations": [list(r[:3]) for r in ds["relations"]],
        "target": ds["target"],
        "num_classes": ds["num_classes"],
        "features": list(ds["features"]),
    }
    return meta, arrays


def _unflatten(meta: dict, arrays: dict) -> dict:
    return {
        "num_nodes": dict(meta["num_nodes"]),
        "relations": [
            (s, e, d, arrays[f"rel{i}.indptr"], arrays[f"rel{i}.indices"])
            for i, (s, e, d) in enumerate(meta["relations"])
        ],
        "target": meta["target"],
        "num_classes": int(meta["num_classes"]),
        "features": {t: arrays[f"feat.{t}"] for t in meta["features"]},
        "labels": arrays["labels"],
        "train_nodes": arrays["train_nodes"],
    }


def build(params: dict) -> dict:
    """Generate the dataset ``params`` describes (no disk)."""
    params = dict(params)
    mod, _ = _generator(params.pop("generator"))
    return mod.generate(**params)


def load(params: dict, cache: Path = None) -> dict:
    """The dataset ``params`` describes, from ``cache`` when it is there.

    ``params`` is the configuration's ``dataset`` object: ``generator`` (a
    file under ``bench/datasets/``) and that generator's keyword arguments.
    The cache key covers the parameters and the generator's source."""
    _, source = _generator(params["generator"])
    where = (cache or CACHE) / f"{params['generator']}-{_key(params, source)}"
    if (where / "meta.json").is_file():
        meta = json.loads((where / "meta.json").read_text())
        arrays = {k: np.load(where / f"{k}.npy") for k in meta["arrays"]}
        return _unflatten(meta, arrays)
    ds = build(params)
    meta, arrays = _flatten(ds)
    meta["arrays"] = sorted(arrays)
    tmp = where.with_name(where.name + f".tmp{os.getpid()}")
    shutil.rmtree(tmp, ignore_errors=True)
    tmp.mkdir(parents=True)
    for k, a in arrays.items():
        np.save(tmp / f"{k}.npy", a)
    (tmp / "meta.json").write_text(json.dumps(meta))
    try:
        tmp.rename(where)
    except OSError:  # another run finished first: its copy is the same
        shutil.rmtree(tmp, ignore_errors=True)
    return ds


def to_hetgraph(ds: dict):
    """The dataset as the program's ``HetGraph`` (arrays shared, not copied)."""
    from repro.graph.hetgraph import CSR, HetGraph, Relation

    return HetGraph(
        num_nodes=dict(ds["num_nodes"]),
        relations={Relation(s, e, d): CSR(indptr=ip, indices=ix)
                   for s, e, d, ip, ix in ds["relations"]},
        target_type=ds["target"],
        num_classes=ds["num_classes"],
        features=dict(ds["features"]),
        labels=ds["labels"],
        train_nodes=ds["train_nodes"],
        name="bench",
    )
