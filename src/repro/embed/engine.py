"""EmbedEngine — learnable feature tables behind the miss-penalty cache.

Ties together the pieces of Heta's learnable-feature pipeline (paper §2.3
Challenge 3 / §6): featureless node types get trainable rows + Adam states;
a minibatch *fetches* the unique rows it touches (through the cache),
the training step returns row gradients, and the engine applies a sparse
Adam step and writes rows + states back to their single authoritative copy.

This replaces the vanilla model's random host-DRAM read/modify/write storm
(24-35% of DGL's epoch time, paper Fig. 4) with mostly device-resident
traffic once the cache is warm.
"""

from __future__ import annotations

import threading
from typing import Dict, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.embed.cache import CacheAllocation, FeatureCache, allocate_cache
from repro.embed.profiler import HotnessProfile, MissPenaltyProfile
from repro.graph.hetgraph import HetGraph
from repro.optim.adam import AdamConfig, sparse_adam_rows

__all__ = ["EmbedEngine"]


class EmbedEngine:
    def __init__(
        self,
        graph: HetGraph,
        learnable_dim: int,
        hotness: HotnessProfile,
        penalties: MissPenaltyProfile,
        cache_bytes: int,
        adam: Optional[AdamConfig] = None,
        hotness_only: bool = False,
        num_shards: int = 1,
        seed: int = 0,
        kernels=None,
    ):
        self.graph = graph
        self.learnable_dim = learnable_dim
        self.adam = adam or AdamConfig(lr=1e-2)
        self.steps = {t: 0 for t in graph.num_nodes}
        # serializes table snapshots against sparse write-backs: the async
        # pipeline snapshots from a producer thread while the training loop
        # applies row grads, and the staleness contract promises whole-row
        # states some step actually held — never torn mid-write rows
        self.lock = threading.RLock()
        rng = np.random.default_rng(seed)

        self.learnable_types = {
            t: learnable_dim for t in graph.num_nodes if t not in graph.features
        }
        host: Dict[str, np.ndarray] = {
            t: f.astype(np.float32, copy=False) for t, f in graph.features.items()
        }
        for t in self.learnable_types:
            host[t] = (
                rng.standard_normal((graph.num_nodes[t], learnable_dim)) * 0.1
            ).astype(np.float32)

        self.allocation: CacheAllocation = allocate_cache(
            hotness, penalties, cache_bytes, graph.num_nodes, hotness_only
        )
        self.cache = FeatureCache(
            host, self.learnable_types, self.allocation, hotness, num_shards,
            kernels=kernels,
        )
        self.penalties = penalties
        self.cache_bytes = cache_bytes
        self.hotness_only = hotness_only
        # online re-admission state: EMA over observed per-node access
        # counts, seeded from the pre-sampled profile so the first
        # rebalance blends prior and trace rather than trusting a short
        # window outright
        self._hotness_ema: Dict[str, np.ndarray] = {
            t: hotness.counts[t].astype(np.float64)
            if t in hotness.counts
            else np.zeros(graph.num_nodes[t], np.float64)
            for t in graph.num_nodes
        }
        self.rebalances = 0

    # -- table access ----------------------------------------------------------

    def table(self, ntype: str) -> np.ndarray:
        """Host view of a feature table.  For learnable types, cached rows
        are authoritative on device; this materializes a coherent snapshot
        (used by the test oracles and single-host executors)."""
        with self.lock:
            tab = self.cache.host[ntype].copy()
            c = self.cache.caches.get(ntype)
            if c is not None:
                tab[c.ids] = np.asarray(c.data)
            return tab

    def tables_snapshot(self) -> Dict[str, np.ndarray]:
        """Coherent snapshot of every table — atomic w.r.t. concurrent
        :meth:`apply_row_grads` (the async pipeline's "stale" policy means a
        snapshot may *lag*, never interleave a half-applied update).  The
        span ``heta.stage.snapshot`` holds the copies and the device
        read-backs; counter ``heta.stage.snapshot_bytes`` their bytes."""
        with obs.span("heta.stage.snapshot"), self.lock:
            tables = {t: self.table(t) for t in self.graph.num_nodes}
            obs.count("heta.stage.snapshot_bytes",
                      sum(tab.nbytes for tab in tables.values()))
            return tables

    def fetch(self, ntype: str, nids: np.ndarray) -> jnp.ndarray:
        return self.cache.fetch(ntype, np.asarray(nids))

    # -- the sparse update path (paper Fig. 3 step 5, cache-accelerated) --------

    def apply_row_grads(self, ntype: str, nids: np.ndarray, grads: jnp.ndarray) -> None:
        """Sparse Adam on the unique rows of one type touched by a batch.

        ``nids`` may contain duplicates (multiple branches sample the same
        node); duplicates are summed into unique rows first, matching dense
        autodiff semantics.  The whole update is the span ``heta.update.adam``.
        """
        if ntype not in self.learnable_types:
            raise ValueError(f"{ntype} has fixed features")
        with obs.span("heta.update.adam"):
            nids = np.asarray(nids)
            uniq, inv = np.unique(nids, return_inverse=True)
            g = np.zeros((len(uniq), grads.shape[-1]), np.float32)
            np.add.at(g, inv,
                      np.asarray(grads, np.float32).reshape(len(nids), -1))
            with self.lock:
                rows, m, v = self.cache.fetch_states(ntype, uniq)
                new_rows, new_m, new_v = sparse_adam_rows(
                    self.adam, rows, jnp.asarray(g), m, v,
                    jnp.asarray(self.steps[ntype]))
                self.steps[ntype] += 1
                self.cache.write_learnable(ntype, uniq, new_rows, new_m, new_v)

    # -- checkpoint support (DESIGN.md §12) -------------------------------------

    def state_snapshot(self) -> Dict[str, object]:
        """The engine's restorable state: per learnable type the coherent
        full table + Adam moments (cached rows merged in), per-type Adam
        step counters, the online-readmission hotness EMA, and the cache
        residency profile.  Atomic w.r.t. concurrent ``apply_row_grads``."""
        with self.lock:
            tables, m, v = self.cache.merged_learnable_state()
            return {
                "tables": tables,
                "m": m,
                "v": v,
                "steps": {t: int(s) for t, s in self.steps.items()},
                "hotness_ema": {t: e.copy()
                                for t, e in self._hotness_ema.items()},
                "residency": self.cache.residency(),
            }

    def load_state(self, state: Dict[str, object]) -> None:
        """Inverse of :meth:`state_snapshot`: write the full tables home,
        then re-gather cached rows from host — bit-exact, because the
        merged snapshot *was* the authoritative value of every row."""
        with self.lock:
            for t in self.learnable_types:
                self.cache.host[t][:] = state["tables"][t]
                self.cache.host_m[t][:] = state["m"][t]
                self.cache.host_v[t][:] = state["v"][t]
            res = state.get("residency")
            if res is not None:
                self.cache.set_residency(res)
            else:  # keep current residency; refresh cached learnable rows
                for t in self.learnable_types:
                    c = self.cache.caches.get(t)
                    if c is not None:
                        c.data = jnp.asarray(self.cache.host[t][c.ids])
                        c.m = jnp.asarray(self.cache.host_m[t][c.ids])
                        c.v = jnp.asarray(self.cache.host_v[t][c.ids])
            for t, s in state.get("steps", {}).items():
                if t in self.steps:
                    self.steps[t] = int(s)
            for t, e in state.get("hotness_ema", {}).items():
                if t in self._hotness_ema:
                    self._hotness_ema[t][:] = np.asarray(e)

    # -- online penalty-aware re-admission (paper §6, observed traffic) ---------

    def rebalance(self, decay: float = 0.5) -> Dict[str, object]:
        """Re-score cache residency from observed traffic (paper §6 online).

        The one-shot allocation trusts the pre-sampled hotness; once
        training runs, the cache's access counters record what the
        workload *actually* touches.  This folds the drained counters
        into a decayed running profile (``ema = decay·ema + window`` —
        the same decay for every type preserves the cross-type ratios
        ``allocate_cache`` scores on), re-runs the hotness × miss-penalty
        allocation under the unchanged byte budget, and applies the plan
        incrementally via :meth:`FeatureCache.update_residency`: kept
        rows never leave the device, evicted learnable rows write row +
        Adam states home first, admitted rows transfer once.

        Safe against the async pipeline: runs under the same table lock
        as ``apply_row_grads``/snapshots, and the per-type cache swap is
        atomic w.r.t. lock-free concurrent ``fetch``.

        Returns ``{"allocation": rows, "moves": per-type counts}``.
        """
        with self.lock:
            window = self.cache.take_access_counts()
            for t, ema in self._hotness_ema.items():
                ema *= decay
                if t in window:
                    ema += window[t]
            profile = HotnessProfile(counts=self._hotness_ema)
            self.allocation = allocate_cache(
                profile, self.penalties, self.cache_bytes,
                self.graph.num_nodes, self.hotness_only,
            )
            moves = self.cache.update_residency(self.allocation, profile)
            self.rebalances += 1
        return {"allocation": dict(self.allocation.rows), "moves": moves}

    # -- reporting ---------------------------------------------------------------

    def stats(self) -> Dict[str, object]:
        return {
            "hit_rates": self.cache.hit_rates(),
            "allocation": {t: r for t, r in self.allocation.rows.items()},
            "miss_time_s": self.cache.miss_time(self.penalties),
            "rebalances": self.rebalances,
        }
