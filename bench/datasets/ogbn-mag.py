"""The ogbn-mag schema at a given scale, generated from a dataset seed.

OGB ogbn-mag (arXiv:2005.00687): papers with 128-d features, and authors,
institutions and fields of study without features; four relations plus the
reverses of three (``cites`` has none), 349 classes.  At ``scale=1.0`` the
node and edge counts are OGB's own.  Sources of edges are drawn with a
Zipf-like skew (a stable hot set, as the real graph has), destinations
uniformly; features are N(0, 0.1²); labels are uniform over the classes and
every paper is a training node.

This is a numpy-only copy of the repository's ``ogbn_mag_like`` generator,
drawing the same random numbers in the same order.  One difference: each
destination's in-neighbors are stored in ascending source order (the
program's copy keeps them in edge order), so that an edge can be looked up
by binary search when a sampled batch is checked against the graph.
"""

from __future__ import annotations

import numpy as np

NODES = {
    "paper": 736_389,
    "author": 1_134_649,
    "institution": 8_740,
    "field_of_study": 59_965,
}
MIN_NODES = {"paper": 64, "author": 64, "institution": 8, "field_of_study": 16}
# (src, etype, dst, edges at scale 1.0), in the order they are drawn
BASE = (
    ("author", "writes", "paper", 7_145_660),
    ("paper", "cites", "paper", 5_416_271),
    ("paper", "has_topic", "field_of_study", 7_505_078),
    ("author", "affiliated_with", "institution", 1_043_998),
)
NO_REVERSE = ("cites",)
TARGET = "paper"
NUM_CLASSES = 349


def _zipf_ids(rng, n_ids, n_samples, a=1.2):
    ranks = np.minimum(rng.zipf(a, size=n_samples) - 1, n_ids - 1)
    perm = np.random.default_rng(12345).permutation(n_ids)
    return perm[ranks]


def _csr(src, dst, num_src, num_dst):
    """In-CSR by destination, each row's sources ascending."""
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    order = np.argsort(dst * num_src + src, kind="stable")
    indptr = np.zeros(num_dst + 1, np.int64)
    np.cumsum(np.bincount(dst, minlength=num_dst), out=indptr[1:])
    return indptr, src[order]


def generate(scale: float = 1.0, seed: int = 0, feat_dim: int = 128) -> dict:
    rng = np.random.default_rng(seed)
    n = {t: max(int(c * scale), MIN_NODES[t]) for t, c in NODES.items()}
    relations = []
    edges = {}
    for s, et, d, ne in BASE:
        m = max(int(ne * scale), 256)
        src = _zipf_ids(rng, n[s], m)
        dst = rng.integers(0, n[d], m)
        edges[(s, et, d)] = (src, dst)
        relations.append((s, et, d, *_csr(src, dst, n[s], n[d])))
    for s, et, d, _ in BASE:
        if et in NO_REVERSE:
            continue
        src, dst = edges[(s, et, d)]
        relations.append((d, f"rev_{et}", s, *_csr(dst, src, n[d], n[s])))
    features = {
        TARGET: (rng.standard_normal((n[TARGET], feat_dim)) * 0.1).astype(np.float32)
    }
    labels = np.random.default_rng(0).integers(
        0, NUM_CLASSES, n[TARGET]).astype(np.int64)
    return {
        "num_nodes": n,
        "relations": relations,
        "target": TARGET,
        "num_classes": NUM_CLASSES,
        "features": features,
        "labels": labels,
        "train_nodes": np.arange(n[TARGET], dtype=np.int64),
    }
