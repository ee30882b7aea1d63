"""Plain reference of a cell's first training steps.

Independent of the program: it builds the model's computation tree from the
dataset's schema, initializes the weights from the seed by the recipe the
configuration states, checks every sampled batch against the graph, and
trains three steps in plain ``jax.numpy``: float32 values, matrix products
at the precision the configuration states (``PRECISIONS``; JAX's
``default`` is one bfloat16 pass with float32 sums on a TPU, and float32 on
a CPU).  It reads the batches the program trained on (the
sampler's output is an input of the step, as tokens are of a language
model's) and nothing else the program made.

The model (paper Eq. 1 of Heta, arXiv:2408.09697): for a k-layer model the
computation tree of a target node holds, level by level, the in-relations of
the level above (relations sorted by (src, etype, dst)).  A level-d branch
aggregates its sampled nodes into its parent's nodes with the relation's
AGG_r (``bench/models/<model>.py``) using layer k-d+1's weights; the leaf
level aggregates input features, inner levels the ReLU of the sum of their
children's aggregations.  The root's sum goes through ReLU and a linear
head; the loss is the mean cross-entropy over the batch.

Weights, as the configuration states them: leaf ``name`` of the parameter
group ``key`` is Glorot-uniform from ``fold_in(PRNGKey(seed),
crc32(f"{key}/{name}"))`` (zeros where the model says so); group keys are
``{src}-{etype}-{dst}@{layer}`` per relation, ``{src}@{layer}`` per source
type, ``{dst}@{layer}:q`` per destination type and ``{etype}@{layer}`` per
edge type; the head's ``w`` uses ``crc32("head/w")`` and its ``b`` is zero.
Node types without features get N(0, 0.1²) rows drawn with
``numpy.random.default_rng(seed)``, type by type in the dataset's order.
Optimizer: Adam (b1 0.9, b2 0.999, eps 1e-8) on the weights; learnable rows,
where they train, by lazy sparse Adam: a table's step count advances once
per training step, and only the rows the batch reaches move, each by its
summed gradient.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import zlib
from functools import partial
from pathlib import Path
from types import SimpleNamespace
from typing import Dict, List, Tuple

import numpy as np

BENCH = Path(__file__).resolve().parent
B1, B2, EPS = 0.9, 0.999, 1e-8


@dataclasses.dataclass(frozen=True)
class Branch:
    depth: int
    rel: Tuple[str, str, str]  # (src, etype, dst); dst is the parent's type
    parent: int  # branch index at depth - 1 (0 = the root at depth 1)
    path: Tuple[Tuple[str, str, str], ...]  # relations from the root down


def metatree(relations, target: str, depth: int) -> List[List[Branch]]:
    """Branches per level of the k-hop computation tree of ``target``."""
    into: Dict[str, list] = {}
    for s, e, d in relations:
        into.setdefault(d, []).append((s, e, d))
    levels, parents = [], [(target, ())]
    for d in range(1, depth + 1):
        level = [Branch(d, rel, pi, path + (rel,))
                 for pi, (ptype, path) in enumerate(parents)
                 for rel in sorted(into.get(ptype, ()))]
        levels.append(level)
        parents = [(b.rel[0], b.path) for b in level]
    return levels


def model_module(name: str):
    path = BENCH / "models" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_model_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _group_key(scope: str, rel, layer: int) -> str:
    s, e, d = rel
    return {"relation": f"{s}-{e}-{d}@{layer}", "src_type": f"{s}@{layer}",
            "dst_type": f"{d}@{layer}:q", "etype": f"{e}@{layer}"}[scope]


@dataclasses.dataclass
class Setup:
    """Everything static about a cell's model: tree, dims, leaves."""

    model: str
    hidden: int
    num_heads: int
    learnable_dim: int
    num_classes: int
    lr: float
    fanouts: Tuple[int, ...]
    tree: List[List[Branch]]
    dims: Dict[str, int]  # input width per node type
    learnable: Tuple[str, ...]  # node types without features
    train_learnable: bool
    matmul: str  # the configuration's precision.matmul
    leaves: Dict[str, Tuple[Tuple[int, ...], str, float]]  # "key/leaf" ->

    @property
    def depth(self) -> int:
        return len(self.fanouts)

    def leaf_ctx(self, b: Branch) -> SimpleNamespace:
        layer = self.depth - b.depth + 1
        return SimpleNamespace(
            hidden=self.hidden, num_heads=self.num_heads,
            head_dim=self.hidden // self.num_heads,
            d_src=self.dims[b.rel[0]] if layer == 1 else self.hidden,
            d_dst=self.dims[b.rel[2]])


def make_setup(ds: dict, heta: dict, matmul: str) -> Setup:
    """The reference's view of a cell from its dataset, its (merged)
    session configuration and its matmul precision."""
    model = heta["model"]
    fanouts = tuple(int(f) for f in heta["data"]["fanouts"])
    ld = int(model.get("learnable_dim", 64))
    dims = {t: (ds["features"][t].shape[1] if t in ds["features"] else ld)
            for t in ds["num_nodes"]}
    tree = metatree([r[:3] for r in ds["relations"]], ds["target"], len(fanouts))
    setup = Setup(
        model=model["model"], hidden=int(model.get("hidden", 64)),
        num_heads=int(model.get("num_heads", 4)), learnable_dim=ld,
        num_classes=int(ds["num_classes"]), lr=float(heta["run"]["lr"]),
        fanouts=fanouts, tree=tree, dims=dims,
        learnable=tuple(t for t in ds["num_nodes"] if t not in ds["features"]),
        train_learnable=bool(model.get("train_learnable", True)),
        matmul=matmul, leaves={})
    mod = model_module(setup.model)
    for level in tree:
        for b in level:
            layer = setup.depth - b.depth + 1
            ctx = setup.leaf_ctx(b)
            for name, scope, shape, init, scale in mod.LEAVES:
                key = f"{_group_key(scope, b.rel, layer)}/{name}"
                setup.leaves.setdefault(key, (tuple(shape(ctx)), init, scale))
    setup.leaves["head/w"] = ((setup.hidden, setup.num_classes), "glorot", 1.0)
    setup.leaves["head/b"] = ((setup.num_classes,), "zeros", 1.0)
    return setup


def init_params(setup: Setup, seed: int) -> Dict[str, np.ndarray]:
    """The configuration's initial weights for ``seed`` (host arrays)."""
    import jax
    import jax.numpy as jnp

    root = jax.random.PRNGKey(seed)
    out = {}
    for key, (shape, init, scale) in setup.leaves.items():
        if init == "zeros":
            out[key] = np.zeros(shape, np.float32)
            continue
        k = jax.random.fold_in(root, zlib.crc32(key.encode()))
        lim = float(np.sqrt(6.0 / (shape[-2] + shape[-1])))
        w = jax.random.uniform(k, shape, jnp.float32, -lim, lim)
        out[key] = np.asarray(w * scale if scale != 1.0 else w)
    return out


def init_tables(setup: Setup, ds: dict, seed: int) -> Dict[str, np.ndarray]:
    """Rows of the node types without features, for ``seed``."""
    rng = np.random.default_rng(seed)
    return {t: (rng.standard_normal((ds["num_nodes"][t], setup.learnable_dim))
                * 0.1).astype(np.float32)
            for t in setup.learnable}


# --------------------------------------------------------------------------
# the sampled batch, checked against the graph
# --------------------------------------------------------------------------


def batch_paths(batch) -> List[List[tuple]]:
    """Per level, the relation path of each of the batch's branches."""
    out, prev = [], [()]
    for level in batch.spec.levels:
        row = [prev[b.parent] + ((b.rel.src, b.rel.etype, b.rel.dst),)
               for b in level]
        out.append(row)
        prev = row
    return out


def host_batch(batch, setup: Setup) -> dict:
    """Copy a sampled batch into the reference's branch order."""
    paths = batch_paths(batch)
    levels = []
    for d, level in enumerate(setup.tree):
        index = {p: i for i, p in enumerate(paths[d])}
        if sorted(index) != sorted(b.path for b in level):
            raise ValueError(f"level {d + 1}: the batch's branches "
                             f"{sorted(index)} are not the model's")
        order = [index[b.path] for b in level]
        levels.append((np.array(batch.levels[d].nids[order], np.int64),
                       np.array(batch.levels[d].mask[order], bool)))
    return {"seeds": np.array(batch.seeds, np.int64),
            "labels": np.array(batch.labels, np.int64), "levels": levels}


def check_batch(hb: dict, setup: Setup, ds: dict) -> int:
    """Number of ways the batch breaks the sampler's guarantees: a seed that
    is not a training node or repeats, a label that is not the dataset's, a
    sampled node that is not an in-neighbor of its parent over the branch's
    relation, or a slot masked out although its parent exists and has
    in-neighbors (or kept although it has none)."""
    rels = {(s, e, d): (ip, ix) for s, e, d, ip, ix in ds["relations"]}
    seeds = hb["seeds"]
    bad = int(len(np.unique(seeds)) != len(seeds))
    train = np.zeros(ds["num_nodes"][ds["target"]], bool)
    train[ds["train_nodes"]] = True
    bad += int(np.sum(~train[seeds]))
    bad += int(np.sum(hb["labels"] != ds["labels"][seeds]))
    prev = [(seeds, np.ones(len(seeds), bool))]
    for d, level in enumerate(setup.tree):
        nids, mask = hb["levels"][d]
        f = setup.fanouts[d]
        row = []
        for i, b in enumerate(level):
            pn, pm = prev[b.parent]
            indptr, indices = rels[b.rel]
            n_src = ds["num_nodes"][b.rel[0]]
            parent = np.repeat(pn, f)
            deg = indptr[parent + 1] - indptr[parent]
            want = np.repeat(pm, f) & (deg > 0)
            bad += int(np.sum(mask[i] != want))
            keys = np.repeat(np.arange(len(indptr) - 1, dtype=np.int64),
                             np.diff(indptr)) * n_src + indices
            q = parent[mask[i]] * n_src + nids[i][mask[i]]
            pos = np.minimum(np.searchsorted(keys, q), len(keys) - 1)
            bad += int(np.sum(keys[pos] != q)) if len(keys) else int(q.size)
            row.append((nids[i], mask[i]))
        prev = row
    return bad


# --------------------------------------------------------------------------
# forward, loss and gradients
# --------------------------------------------------------------------------


def _block_loss_sum(setup: Setup, mod, ops, params, tables, blk):
    """Summed cross-entropy of one block of seeds.  ``tables`` maps every
    node type to its input rows (trainable ones come in via ``params``)."""
    import jax
    import jax.numpy as jnp

    feats = {**tables, **{k[len("table/"):]: v for k, v in params.items()
                          if k.startswith("table/")}}
    k = setup.depth
    child = None
    for d in range(k, 0, -1):
        level = setup.tree[d - 1]
        nids, mask = blk["levels"][d - 1]
        f = setup.fanouts[d - 1]
        layer = k - d + 1
        sums = [None] * (len(setup.tree[d - 2]) if d > 1 else 1)
        for i, b in enumerate(level):
            if d == k:
                h = feats[b.rel[0]][nids[i]]
            elif child[i] is None:
                h = jnp.zeros((nids.shape[1], setup.hidden), feats[b.rel[0]].dtype)
            else:
                h = jax.nn.relu(child[i])
            n = h.shape[0] // f
            pn = blk["seeds"] if d == 1 else blk["levels"][d - 2][0][b.parent]
            q = feats[b.rel[2]][pn]
            p = {name: params[f"{_group_key(scope, b.rel, layer)}/{name}"]
                 for name, scope, *_ in mod.LEAVES}
            out = mod.aggregate(ops, p, h.reshape(n, f, -1), q, mask[i].reshape(n, f))
            sums[b.parent] = out if sums[b.parent] is None else sums[b.parent] + out
        child = sums
    root = child[0]
    logits = ops.mm(jax.nn.relu(root), params["head/w"]) + params["head/b"]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, blk["labels"][:, None], axis=-1))


def matmul_ops(mm: str, einsum: str = None):
    """``mm(a, b)`` and ``einsum(spec, a, b)``, each at a JAX precision:
    ``"highest"`` (float32 products) or ``"default"`` (the backend's own:
    one bfloat16 pass with float32 sums on a TPU, float32 on a CPU)."""
    import jax
    import jax.numpy as jnp

    prec = {"highest": jax.lax.Precision.HIGHEST,
            "default": jax.lax.Precision.DEFAULT}
    p_mm, p_es = prec[mm], prec[einsum or mm]
    return SimpleNamespace(
        mm=partial(jnp.matmul, precision=p_mm),
        einsum=lambda spec, a, b: jnp.einsum(spec, a, b, precision=p_es))


# precisions the reference computes in: (mm, einsum, storage dtype).  The
# models' ``mm`` are the learned projections, at the configuration's matmul
# precision; their ``einsum`` are HGT's per-head transforms, logits and
# combine, which the program's fused attention kernel computes in float32.
# The configuration's ``precision.matmul`` names the reference's precision;
# CONTROL maps it to its control's, the nearest precision below it.
PRECISIONS = {
    "default": ("default", "highest", None),
    "bfloat16": ("default", "highest", "bfloat16"),
}
CONTROL = {"default": "bfloat16"}


def make_grad_fn(setup: Setup, precision: str):
    """``fn(params, tables, blk) -> (loss_sum, grads)`` for one block, jitted,
    at one of ``PRECISIONS``; gradients and the optimizer stay float32."""
    import jax
    import jax.numpy as jnp

    mod = model_module(setup.model)
    mm, einsum, store = PRECISIONS[precision]
    ops = matmul_ops(mm, einsum)

    def loss(params, tables, blk):
        if store is not None:
            cast = lambda a: a.astype(jnp.dtype(store))
            params = jax.tree.map(cast, params)
            tables = jax.tree.map(cast, tables)
        return _block_loss_sum(setup, mod, ops, params, tables, blk)

    return jax.jit(jax.value_and_grad(loss))


def blocks(hb: dict, setup: Setup, block: int):
    """Split a batch into blocks of ``block`` seeds (contiguous at every
    level: a seed's sampled nodes follow it in order)."""
    B = len(hb["seeds"])
    for i0 in range(0, B, block):
        i1 = min(B, i0 + block)
        out = {"seeds": hb["seeds"][i0:i1], "labels": hb["labels"][i0:i1],
               "levels": []}
        span = 1
        for d, (nids, mask) in enumerate(hb["levels"]):
            span *= setup.fanouts[d]
            out["levels"].append((nids[:, i0 * span:i1 * span],
                                  mask[:, i0 * span:i1 * span]))
        yield out


def touched(hb: dict, setup: Setup) -> Dict[str, np.ndarray]:
    """Per node type, the unique nodes the batch reaches (sampled and
    existing, or a seed)."""
    acc = {setup.tree[0][0].rel[2]: [hb["seeds"]]}
    for d, level in enumerate(setup.tree):
        nids, mask = hb["levels"][d]
        for i, b in enumerate(level):
            acc.setdefault(b.rel[0], []).append(nids[i][mask[i]])
    return {t: np.unique(np.concatenate(v)) for t, v in acc.items()}


FAULTS = ("half_batch", "labels")


def train(setup: Setup, params0: dict, tables: dict, batches: List[dict],
          precision: str = None, fault: str = None,
          block: int = 256) -> dict:
    """Run the reference over ``batches`` from ``params0``, at
    ``precision`` (one of ``PRECISIONS``; by default the configuration's).

    ``tables`` holds every node type's input rows; where the learnable rows
    train they start from ``tables`` and follow lazy sparse Adam.  Returns
    the losses, the first step's gradients, and the weights (and trained
    rows) after the last step, as host arrays keyed like ``params0``
    (trained rows as ``table/<type>``).

    ``fault`` plants one of the faults a check has to catch, for reading
    what the numbers say of it: ``"half_batch"`` (the loss is the mean over
    the first half of each batch), ``"labels"`` (every label one class off,
    as if altered where it is produced)."""
    import jax
    import jax.numpy as jnp

    grad_fn = make_grad_fn(setup, precision or setup.matmul)
    train_rows = setup.train_learnable and setup.learnable
    const = {t: jnp.asarray(a) for t, a in tables.items()
             if not (train_rows and t in setup.learnable)}
    params = {k: jnp.asarray(v) for k, v in params0.items()}
    if train_rows:
        params.update({f"table/{t}": jnp.asarray(tables[t])
                       for t in setup.learnable})
    m = {k: jnp.zeros_like(v) for k, v in params.items()}
    v2 = {k: jnp.zeros_like(v) for k, v in params.items()}
    row_steps = {t: 0 for t in setup.learnable}
    losses, first_grads = [], None
    for step, hb in enumerate(batches, start=1):
        B = len(hb["seeds"])
        if fault == "labels":
            hb = {**hb, "labels": (hb["labels"] + 1) % setup.num_classes}
        total, grads = 0.0, None
        parts = list(blocks(hb, setup, block))
        if fault == "half_batch":
            B //= 2
            parts = list(blocks(hb, setup, B))[:1]
        for blk in parts:
            dev = jax.tree.map(
                lambda a: jnp.asarray(a.astype(np.int32) if a.dtype == np.int64 else a),
                blk)
            ls, g = grad_fn(params, const, dev)
            total += float(ls)
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
        grads = {k: g / B for k, g in grads.items()}
        losses.append(total / B)
        if first_grads is None:
            first_grads = {k: np.asarray(g) for k, g in grads.items()}
        reached = touched(hb, setup) if train_rows else {}
        for k in params:
            g = grads[k]
            if k.startswith("table/"):
                t = k[len("table/"):]
                if t not in reached:
                    continue
                row_steps[t] += 1
                rows = np.zeros(params[k].shape[0], bool)
                rows[reached[t]] = True
                sel = jnp.asarray(rows)[:, None]
                m[k] = jnp.where(sel, B1 * m[k] + (1 - B1) * g, m[k])
                v2[k] = jnp.where(sel, B2 * v2[k] + (1 - B2) * g * g, v2[k])
                n = row_steps[t]
                upd = (m[k] / (1 - B1 ** n)) / (jnp.sqrt(v2[k] / (1 - B2 ** n)) + EPS)
                params[k] = jnp.where(sel, params[k] - setup.lr * upd, params[k])
            else:
                m[k] = B1 * m[k] + (1 - B1) * g
                v2[k] = B2 * v2[k] + (1 - B2) * g * g
                upd = (m[k] / (1 - B1 ** step)) / (jnp.sqrt(v2[k] / (1 - B2 ** step)) + EPS)
                params[k] = params[k] - setup.lr * upd
    return {"losses": losses, "grads": first_grads,
            "params": {k: np.asarray(v) for k, v in params.items()}}
