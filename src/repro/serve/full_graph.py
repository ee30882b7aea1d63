"""Layer-wise full-graph inference over the metatree plan (DESIGN.md §10).

Training samples fixed-fanout subtrees per seed; inference wants the
embedding of *every* node, and re-sampling a tree per query does redundant
work proportional to fanout^k.  Following GraphStorm's ``dist_inference``
pattern, this module computes level-l representations for **all** nodes of
every type before advancing to level l+1, so each node's layer-l value is
computed exactly once and reused by every consumer at layer l+1.

Equivalence with the minibatch forward (the serving tier's Prop-1):

  * the metatree expands *every* in-relation of every frontier type, so the
    relation set feeding a node depends only on (node type, layer) — not on
    which branch of which seed's tree the node appeared in;
  * attention queries are always the destination node's *input* features
    (DESIGN.md §7), so a node's layer-l value needs only (a) its own input
    features and (b) its in-neighbors' layer-(l-1) values;
  * with exhaustive neighborhoods (fanout = max in-degree, full CSR
    neighbor lists, padding masked) the sampled tree around any seed
    contains exactly the full neighborhoods the recurrence uses.

Hence the recurrence, for layer l = 1..k over level d = k-l+1 of the plan:

    REP[l][t][v] = sum_r AGG_r(params(r, t, l), {h_u : u in N_r(v)}, q=x_t[v])

with h_u = padded input features at l=1, else relu(REP[l-1][src(r)][u])
(zeros for types with no in-relations — the tree's leaf-at-intermediate-
depth case), and logits = relu(REP[k][target]) @ head.  Branch parameters
are gathered *from the same [P, U, ...] stacks the SPMD executor trains*
(via the plan's slot tables), and the per-level compute is the same
``stacked_agg`` dispatch — fused Pallas kernels or the vmap oracle — the
training step runs, with the same combine structure (``segment_sum`` at
inner levels, ``jnp.sum`` + head at the root).  ``tests/
test_serve_full_graph.py`` asserts per-node equality against the minibatch
``raf_spmd`` forward for rgcn/rgat/hgt.

The materialized :class:`EmbeddingStore` holds one float32 host array per
node type (pre-ReLU top-layer representations) plus the classifier head;
``shm=True`` backs it with a ``repro.graph.shm`` segment so serving
processes attach zero-copy (:meth:`EmbeddingStore.attach`).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.data.staging import _padded_gather
from repro.graph.hetgraph import CSR, HetGraph
from repro.graph.sampler import Level, SampledBatch, SampleSpec
from repro.graph.shm import ArraysHandle, AttachedArrays, SharedArrays, attach_arrays, share_arrays

__all__ = [
    "EmbeddingStore",
    "infer_all",
    "exhaustive_fanouts",
    "exhaustive_batch",
    "bounded_graph",
    "spmd_logits_for_batch",
]

# cap on one chunk's gathered-neighbor tensor [n_sel, block, f, d_in]; the
# effective node block shrinks below ServeConfig.node_block when a level's
# fanout (= max in-degree) would otherwise blow host/device memory
_BLOCK_BUDGET_BYTES = 128 << 20


# --------------------------------------------------------------------------
# exhaustive neighborhoods (full CSR lists, padding masked)
# --------------------------------------------------------------------------


def _full_neighbors(
    csr: CSR, parents: np.ndarray, parent_mask: np.ndarray, fanout: int
) -> Tuple[np.ndarray, np.ndarray]:
    """Every in-neighbor of each parent, CSR order, padded to ``fanout``.

    The deterministic counterpart of ``sample_neighbors``: slot j of parent v
    holds ``indices[indptr[v] + j]`` for j < deg(v), masked beyond.  Raises
    when any parent's degree exceeds ``fanout`` (exhaustiveness violated)."""
    n = len(parents)
    if csr.num_edges == 0:
        return np.zeros((n, fanout), np.int64), np.zeros((n, fanout), bool)
    deg = csr.indptr[parents + 1] - csr.indptr[parents]
    if int(deg.max(initial=0)) > fanout:
        raise ValueError(
            f"fanout {fanout} < max in-degree {int(deg.max())}: exhaustive "
            "neighborhoods need fanout >= the level's max in-degree"
        )
    cols = np.arange(fanout)
    raw = csr.indptr[parents][:, None] + cols[None, :]
    valid = (cols[None, :] < deg[:, None]) & parent_mask[:, None]
    raw = np.minimum(raw, csr.num_edges - 1)
    idx = np.where(valid, csr.indices[raw], 0)
    return idx, valid


def exhaustive_fanouts(graph: HetGraph, spec: SampleSpec) -> Tuple[int, ...]:
    """Per-level fanouts that make sampling exhaustive: the max in-degree
    over the level's relations (min 1).  A batch sampled with these fanouts
    via :func:`exhaustive_batch` contains every neighbor of every node."""
    out = []
    for branches in spec.levels:
        f = 1
        for b in branches:
            csr = graph.relations[b.rel]
            deg = csr.indptr[1:] - csr.indptr[:-1]
            if len(deg):
                f = max(f, int(deg.max(initial=0)))
        out.append(f)
    return tuple(out)


def bounded_graph(graph: HetGraph, cap: int) -> HetGraph:
    """A copy of ``graph`` with per-node in-degree capped at ``cap`` (the
    first ``cap`` CSR neighbors kept).

    The synthetic dataset family's Zipf skew produces hub nodes with
    thousands of in-edges, which makes exhaustive neighborhoods — fanout =
    max in-degree — intractable for the minibatch side of a parity check.
    Tests, benchmarks and demos train *and* infer on the capped graph, so
    the equivalence being asserted is unaffected."""
    rels = {}
    for rel, csr in graph.relations.items():
        deg = csr.indptr[1:] - csr.indptr[:-1]
        keep = np.minimum(deg, cap)
        indptr = np.zeros(len(deg) + 1, csr.indptr.dtype)
        np.cumsum(keep, out=indptr[1:])
        pos = (np.repeat(csr.indptr[:-1], keep)
               + np.arange(int(keep.sum())) - np.repeat(indptr[:-1], keep))
        rels[rel] = CSR(indptr=indptr, indices=csr.indices[pos])
    return HetGraph(
        num_nodes=dict(graph.num_nodes),
        relations=rels,
        target_type=graph.target_type,
        num_classes=graph.num_classes,
        features=dict(graph.features),
        labels=graph.labels,
        train_nodes=graph.train_nodes,
        name=f"{graph.name}-deg{cap}",
    )


def exhaustive_batch(
    graph: HetGraph, spec: SampleSpec, seeds: np.ndarray
) -> SampledBatch:
    """A :class:`SampledBatch` whose levels hold *full* neighbor lists.

    Requires ``spec.fanouts >= exhaustive_fanouts(graph, spec)`` per level.
    The minibatch forward on such a batch sees exactly the neighborhoods the
    layer-wise engine aggregates — the per-node parity fixture."""
    seeds = np.asarray(seeds, dtype=np.int64)
    levels: List[Level] = []
    prev_nids: List[np.ndarray] = [seeds]
    prev_mask: List[np.ndarray] = [np.ones(len(seeds), dtype=bool)]
    for d, branches in enumerate(spec.levels, start=1):
        f = spec.fanouts[d - 1]
        nids = np.zeros((len(branches), len(prev_nids[0]) * f), dtype=np.int64)
        mask = np.zeros_like(nids, dtype=bool)
        for b, bs in enumerate(branches):
            csr = graph.relations[bs.rel]
            idx, m = _full_neighbors(
                csr, prev_nids[bs.parent], prev_mask[bs.parent], f
            )
            nids[b] = idx.reshape(-1)
            mask[b] = m.reshape(-1)
        levels.append(Level(nids=nids, mask=mask))
        prev_nids = [nids[b] for b in range(len(branches))]
        prev_mask = [mask[b] for b in range(len(branches))]
    labels = graph.labels[seeds]
    return SampledBatch(spec, seeds, labels, levels)


# --------------------------------------------------------------------------
# the materialized store
# --------------------------------------------------------------------------


@dataclasses.dataclass
class EmbeddingStore:
    """Per-type top-layer representations + classifier head (DESIGN.md §10).

    ``embeddings[t]`` is the float32 **pre-ReLU** layer-``layer_of[t]``
    representation of every node of type ``t`` (the value the next layer —
    or the head — would consume through ``relu``); only types that are a
    destination somewhere in the metatree have an entry (pure leaf types
    keep their input features as their representation).  ``scores`` applies
    ``relu`` + the head to target-type rows.  When shm-backed, ``handle``
    is picklable and :meth:`attach` maps the store zero-copy in another
    process; :meth:`close` unlinks (owner) or unmaps (attached)."""

    target_type: str
    num_classes: int
    hidden: int
    embeddings: Dict[str, np.ndarray]
    layer_of: Dict[str, int]
    head: Dict[str, np.ndarray]
    handle: Optional[ArraysHandle] = None
    _segment: object = None  # SharedArrays (owner) | AttachedArrays | None
    _score_fn: object = dataclasses.field(default=None, repr=False)

    def embedding(self, ntype: str, nids) -> np.ndarray:
        """Stored (pre-ReLU) rows for ``nids`` of ``ntype``."""
        return self.embeddings[ntype][np.asarray(nids)]

    def scores(self, nids) -> np.ndarray:
        """Class logits for target-type nodes: relu(rep) @ W + b."""
        import jax
        import jax.numpy as jnp

        if self._score_fn is None:
            w = jnp.asarray(self.head["w"])
            b = jnp.asarray(self.head["b"])
            self._score_fn = jax.jit(
                lambda e: jax.nn.relu(e) @ w + b)
        emb = self.embeddings[self.target_type][np.asarray(nids)]
        return np.asarray(self._score_fn(jnp.asarray(emb)))

    @property
    def nbytes(self) -> int:
        return sum(a.nbytes for a in self.embeddings.values()) + sum(
            a.nbytes for a in self.head.values())

    @classmethod
    def attach(cls, handle: ArraysHandle) -> "EmbeddingStore":
        """Map a shm-backed store exported by :func:`infer_all` zero-copy."""
        seg = attach_arrays(handle)
        meta = handle.meta_dict
        embeddings = {k[len("emb/"):]: v for k, v in seg.arrays.items()
                      if k.startswith("emb/")}
        return cls(
            target_type=meta["target_type"],
            num_classes=int(meta["num_classes"]),
            hidden=int(meta["hidden"]),
            embeddings=embeddings,
            layer_of={t: int(meta[f"layer/{t}"]) for t in embeddings},
            head={"w": seg.arrays["head/w"], "b": seg.arrays["head/b"]},
            handle=handle,
            _segment=seg,
        )

    def close(self) -> None:
        """Release shm backing: owners unlink the segment, attached readers
        unmap their view.  Idempotent; plain-array stores are a no-op."""
        seg, self._segment = self._segment, None
        if seg is None:
            return
        self.embeddings = {}
        self.head = {}
        if isinstance(seg, SharedArrays):
            seg.unlink()
        else:
            seg.close()


def _shm_backed(store: EmbeddingStore) -> EmbeddingStore:
    """Re-materialize a store's arrays inside one shared segment."""
    arrays = {f"emb/{t}": a for t, a in store.embeddings.items()}
    arrays["head/w"] = store.head["w"]
    arrays["head/b"] = store.head["b"]
    meta = {
        "target_type": store.target_type,
        "num_classes": str(store.num_classes),
        "hidden": str(store.hidden),
        **{f"layer/{t}": str(l) for t, l in store.layer_of.items()},
    }
    seg = share_arrays(arrays, meta=meta)
    views = seg.arrays()
    store.embeddings = {t: views[f"emb/{t}"] for t in store.embeddings}
    store.head = {"w": views["head/w"], "b": views["head/b"]}
    store.handle = seg.handle
    store._segment = seg
    return store


# --------------------------------------------------------------------------
# the layer-wise engine
# --------------------------------------------------------------------------


def _host_stacks(stacks: Dict) -> Dict:
    """Pull the (possibly sharded) trained stacks to host numpy once."""
    return {
        layer: {leaf: np.asarray(v) for leaf, v in entry.items()}
        for layer, entry in stacks.items()
    }


def _slot_of(lp) -> Dict[int, Tuple[int, int]]:
    """Invert ``slot_branch``: original branch index -> (shard, slot)."""
    out: Dict[int, Tuple[int, int]] = {}
    sb = lp.slot_branch
    for p in range(sb.shape[0]):
        for s in range(sb.shape[1]):
            b = int(sb[p, s])
            if b >= 0:
                out[b] = (p, s)
    return out


def _dedup_groups(plan, d: int) -> Dict[str, List[int]]:
    """Branches at level ``d`` grouped by dst type, one per relation.

    The metatree repeats (dst type, relation) pairs once per parent branch
    of that type; parameters and neighbor sets depend only on the pair, so
    the engine aggregates each relation once per type — first occurrence,
    which preserves the child order (= sorted in-relation order) any single
    parent's children have in the minibatch tree."""
    groups: Dict[str, List[int]] = {}
    seen: Dict[str, set] = {}
    for b, bs in enumerate(plan.spec.levels[d - 1]):
        t = plan.dst_types[d - 1][b]
        if bs.rel not in seen.setdefault(t, set()):
            seen[t].add(bs.rel)
            groups.setdefault(t, []).append(b)
    return groups


def _gather_branch_params(plan, lp, host_stacks, sel, slot_of):
    """Per-leaf ``[n_sel, ...]`` parameter rows for the selected branches,
    gathered from the trained ``[P, U, ...]`` stacks via the plan's slot
    tables — no unstacking back to dict form."""
    module = plan.module
    scope_of = {s.name: s.scope for s in module.specs}
    layer_entry = host_stacks[f"layer{lp.layer}"]
    out = {}
    for leaf, slab in layer_entry.items():
        rows = []
        for b in sel:
            p, s = slot_of[b]
            u = int(lp.slot_u[scope_of[leaf]][p, s])
            rows.append(slab[p, u])
        out[leaf] = np.stack(rows)
    return out


def _group_fanout(graph: HetGraph, plan, d: int, sel: List[int]) -> int:
    """Max in-degree over the selected branches' relations (min 1).

    Masked padding slots contribute exact zeros to every aggregation, so a
    per-group fanout (tighter than the level-wide max) changes nothing
    numerically while bounding the gathered tensor."""
    f = 1
    for b in sel:
        csr = graph.relations[plan.spec.levels[d - 1][b].rel]
        deg = csr.indptr[1:] - csr.indptr[:-1]
        if len(deg):
            f = max(f, int(deg.max(initial=0)))
    return f


def infer_all(
    graph: HetGraph,
    plan,
    stacks: Dict,
    tables: Dict[str, np.ndarray],
    *,
    node_block: int = 1024,
    kernels=None,
    shm: bool = False,
) -> EmbeddingStore:
    """Materialize top-layer representations for every node of every type.

    ``plan``/``stacks`` are the SPMD executor's :class:`~repro.core.
    raf_spmd.StackedPlan` and trained parameter stacks; ``tables`` is a full
    feature-table snapshot (``EmbedEngine.tables_snapshot()``).  Nodes are
    processed in ``node_block`` chunks (shrunk automatically when a level's
    max in-degree would blow the block budget); ``shm=True`` backs the
    returned store with a shared segment for zero-copy serving attach."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.stacked_relation_agg import stacked_agg

    spec = plan.spec
    module = plan.module
    k = spec.num_layers
    hidden = plan.cfg.hidden
    d_pad = plan.d_pad

    def make_block_fn(root: bool):
        def fn(stacks_sel, h, q, mask):
            slot_u = {
                scope: jnp.arange(h.shape[0], dtype=jnp.int32)
                for scope in module.scopes
            }
            out = stacked_agg(module, stacks_sel, slot_u, h, q, mask,
                              opts=kernels)
            if root:
                return jnp.sum(out, axis=0)
            # mirror the inner-level combine of the minibatch forward
            # (segment_sum) so reduction structure — hence bit behavior —
            # matches the training step's
            seg = jnp.zeros((out.shape[0],), jnp.int32)
            return jax.ops.segment_sum(out, seg, num_segments=1)[0]

        return jax.jit(fn)

    block_fns = {True: make_block_fn(True), False: make_block_fn(False)}
    host_stacks = _host_stacks(stacks)

    prev_rep: Dict[str, np.ndarray] = {}
    final_rep: Dict[str, np.ndarray] = {}
    layer_of: Dict[str, int] = {}
    for l in range(1, k + 1):
        d = k - l + 1
        lp = plan.levels[d - 1]
        slot_of = _slot_of(lp)
        cur_rep: Dict[str, np.ndarray] = {}
        for t, sel in _dedup_groups(plan, d).items():
            n_sel = len(sel)
            f = _group_fanout(graph, plan, d, sel)
            d_in = lp.d_in
            num_nodes = graph.num_nodes[t]
            block = max(1, min(
                node_block, _BLOCK_BUDGET_BYTES // max(1, n_sel * f * d_in * 4)
            ))
            p_sel = jax.tree.map(jnp.asarray,
                                 _gather_branch_params(plan, lp, host_stacks,
                                                       sel, slot_of))
            rels = [spec.levels[d - 1][b].rel for b in sel]
            rep = np.zeros((num_nodes, hidden), np.float32)
            for lo in range(0, num_nodes, block):
                chunk = np.arange(lo, min(lo + block, num_nodes),
                                  dtype=np.int64)
                nb = len(chunk)
                ones = np.ones(nb, bool)
                h = np.zeros((n_sel, nb, f, d_in), np.float32)
                mask = np.zeros((n_sel, nb, f), bool)
                for i, rel in enumerate(rels):
                    csr = graph.relations[rel]
                    idx, m = _full_neighbors(csr, chunk, ones, f)
                    mask[i] = m
                    if l == 1:
                        h[i] = _padded_gather(
                            tables[rel.src], idx.reshape(-1), d_in
                        ).reshape(nb, f, d_in)
                    else:
                        src_rep = prev_rep.get(rel.src)
                        if src_rep is not None:
                            # relu of the previous layer; types with no
                            # in-relations stay zeros (the tree's
                            # leaf-at-intermediate-depth case)
                            h[i] = np.maximum(
                                src_rep[idx.reshape(-1)], 0.0
                            ).reshape(nb, f, hidden)
                q = np.broadcast_to(
                    _padded_gather(tables[t], chunk, d_pad)[None],
                    (n_sel, nb, d_pad),
                )
                out = block_fns[d == 1](
                    p_sel, jnp.asarray(h), jnp.asarray(q), jnp.asarray(mask)
                )
                rep[lo:lo + nb] = np.asarray(out)
            cur_rep[t] = rep
            final_rep[t] = rep
            layer_of[t] = l
        prev_rep = cur_rep

    store = EmbeddingStore(
        target_type=spec.target_type,
        num_classes=int(plan.cfg.num_classes),
        hidden=hidden,
        embeddings=final_rep,
        layer_of=layer_of,
        head={leaf: np.asarray(v) for leaf, v in stacks["head"].items()},
    )
    return _shm_backed(store) if shm else store


# --------------------------------------------------------------------------
# the minibatch reference (parity fixture for tests and CI)
# --------------------------------------------------------------------------


def spmd_logits_for_batch(plan, stacks, batch, tables, kernels=None):
    """Logits of one batch through the minibatch ``raf_spmd`` forward.

    The exact math of the training step's forward — ``shard_map`` over a
    (1, 1) mesh, same ``stacked_agg`` dispatch, head outside the shard_map —
    packaged for the serving tier's Prop-1 parity checks.  Requires a
    single-shard plan (fold the assignment to 1 before ``build_plan``)."""
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.core import raf_spmd
    from repro.launch.mesh import make_mesh

    if plan.num_shards != 1:
        raise ValueError(
            f"parity reference needs a 1-shard plan, got {plan.num_shards}")
    arrays = raf_spmd.stack_batch(plan, batch, tables)
    mesh = make_mesh((1, 1), ("data", "model"))
    rel_stacks = {k2: v for k2, v in stacks.items() if k2 != "head"}
    feats = {k2: v for k2, v in arrays.items() if "feat" in k2}
    rest = {k2: v for k2, v in arrays.items() if "feat" not in k2}

    def body(stacks_s, feats_s, rest_s):
        return raf_spmd.raf_spmd_forward(
            plan, stacks_s, {**feats_s, **rest_s}, "model", True, kernels)

    stack_specs = raf_spmd._stack_specs(plan)
    rel_specs = {k2: v for k2, v in stack_specs.items() if k2 != "head"}
    arr_specs = raf_spmd._array_specs(plan, ("data",), "model")
    root = jax.jit(jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            rel_specs,
            {k2: arr_specs[k2] for k2 in feats},
            {k2: arr_specs[k2] for k2 in rest},
        ),
        out_specs=P(("data",), None),
        check_vma=False,
    ))(rel_stacks, feats, rest)
    h = jax.nn.relu(root)
    return np.asarray(h @ stacks["head"]["w"] + stacks["head"]["b"])
