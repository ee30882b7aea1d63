"""Miss-penalty-aware device feature cache (paper §6).

Two pieces:

  * :func:`allocate_cache` — the hierarchical allocation policy: the per-type
    cache budget is proportional to ``count_a × o_a`` (hotness × miss-penalty
    ratio), then each type's budget is filled with its hottest nodes.  A
    ``hotness_only`` switch reproduces the paper's ablation baseline
    (Fig. 11's 'hotness only').

  * :class:`FeatureCache` — a functional device cache in front of host
    feature tables.  Read-only types cache feature rows; learnable types
    cache the row *and* its Adam states, and writes go to the cached copy
    (non-replicative: each row lives in exactly one place — a device shard
    or host memory — so there is never a second version to invalidate,
    paper §6 'Cache Consistency').  Multi-device splits use the paper's
    mod-hash: row ``nid`` belongs to shard ``nid % num_shards``.

Online admission (§6 extension): the one-shot allocation above scores
residency from a *pre-sampled* hotness trace.  The cache additionally
keeps per-node access counters (accumulated on every ``fetch`` under the
same stats lock as the hit/miss counters) so a caller can periodically
re-score residency from *observed* traffic: ``take_access_counts`` drains
the counters, the caller folds them into a hotness profile and re-runs
:func:`allocate_cache` under the unchanged byte budget, and
:meth:`FeatureCache.update_residency` applies the new plan
*incrementally* — rows resident under both plans stay on device (no
re-transfer), evicted learnable rows write their authoritative copy (row
+ Adam states) back to host before leaving, and only admitted rows move
host→device.  ``EmbedEngine.rebalance`` and the serving tier's
``EmbeddingServer`` both drive this hook.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.embed.profiler import (
    ADAM_STATE_MULT,
    HotnessProfile,
    MissPenaltyProfile,
    row_bytes,
)

__all__ = ["CacheAllocation", "allocate_cache", "FeatureCache"]


# --------------------------------------------------------------------------
# allocation policy
# --------------------------------------------------------------------------


@dataclasses.dataclass
class CacheAllocation:
    rows: Dict[str, int]  # ntype -> number of cached rows
    bytes_: Dict[str, int]  # ntype -> bytes allotted
    total_bytes: int
    policy: str

    def render(self) -> str:
        lines = [f"  cache allocation ({self.policy}, {self.total_bytes/2**20:.0f} MiB):"]
        for t in sorted(self.rows):
            lines.append(
                f"    {t:<18} rows={self.rows[t]:>9,}  {self.bytes_[t]/2**20:8.1f} MiB"
            )
        return "\n".join(lines)


def allocate_cache(
    hotness: HotnessProfile,
    penalties: MissPenaltyProfile,
    total_bytes: int,
    num_nodes: Dict[str, int],
    hotness_only: bool = False,
    bytes_per_elem: int = 4,
) -> CacheAllocation:
    """Split ``total_bytes`` across node types ∝ count_a × o_a (paper §6).

    ``hotness_only=True`` drops the o_a factor (ablation baseline).  Budgets
    are capped at the type's full table size; freed budget is redistributed
    proportionally among uncapped types.
    """
    types = sorted(penalties.ratios)
    score = {
        t: float(hotness.total(t)) * (1.0 if hotness_only else penalties.ratios[t])
        for t in types
    }
    rbytes = {
        t: row_bytes(penalties.dims[t], penalties.learnable[t], bytes_per_elem)
        for t in types
    }
    cap = {t: num_nodes[t] * rbytes[t] for t in types}
    alloc = {t: 0.0 for t in types}
    remaining, active = float(total_bytes), set(t for t in types if score[t] > 0)
    # waterfill: proportional split, capping saturated types and reflowing
    while remaining > 1 and active:
        tot = sum(score[t] for t in active)
        newly_capped = set()
        spent = 0.0
        for t in active:
            give = remaining * score[t] / tot
            room = cap[t] - alloc[t]
            take = min(give, room)
            alloc[t] += take
            spent += take
            if alloc[t] >= cap[t] - 1e-6:
                newly_capped.add(t)
        remaining -= spent
        active -= newly_capped
        if not newly_capped:
            break
    rows = {t: int(alloc[t] // rbytes[t]) for t in types}
    return CacheAllocation(
        rows=rows,
        bytes_={t: rows[t] * rbytes[t] for t in types},
        total_bytes=total_bytes,
        policy="hotness-only" if hotness_only else "hotness×miss-penalty",
    )


# --------------------------------------------------------------------------
# the cache itself
# --------------------------------------------------------------------------


@dataclasses.dataclass
class _TypeCache:
    ids: np.ndarray  # [C] cached node ids (host copy for bookkeeping)
    slot_of: np.ndarray  # [num_nodes] -> cache slot or -1
    data: jnp.ndarray  # [C, d] cached rows (device)
    m: Optional[jnp.ndarray]  # [C, d] Adam moment (learnable only)
    v: Optional[jnp.ndarray]  # [C, d] Adam variance
    shard_of: np.ndarray  # [C] mod-hash shard of each cached row
    hits: int = 0
    misses: int = 0


def _slot_order(ids: np.ndarray, num_nodes: int) -> np.ndarray:
    """The order a type's cached rows take on the device: a cache that holds
    every row keeps them in node-id order, so a node id is its own slot and
    a compiled step gathers rows by node id (resident staging,
    ``raf_spmd.gather_resident``); a partial cache keeps ``ids``' order."""
    if len(ids) == num_nodes:
        return np.arange(num_nodes, dtype=np.int64)
    return ids


class FeatureCache:
    """Device cache over host tables with per-type budgets.

    ``host_tables``: ntype -> np.ndarray features.  For learnable types the
    host table *is* the learnable parameter store; its Adam states live in
    ``host_m``/``host_v``.  ``fetch`` returns gathered rows (device), and for
    learnable types :meth:`write_learnable` pushes updated rows + states back
    to wherever each row lives (cache or host) — a single authoritative copy.
    """

    def __init__(
        self,
        host_tables: Dict[str, np.ndarray],
        learnable_types: Dict[str, int],  # ntype -> dim
        allocation: CacheAllocation,
        hotness: HotnessProfile,
        num_shards: int = 1,
        kernels=None,
    ):
        self.host = dict(host_tables)
        self.learnable = dict(learnable_types)
        self.num_shards = num_shards
        # guards the hit/miss counters: fetch() runs in the async pipeline's
        # producer thread while hit_rates()/miss_time() read from the
        # consumer — same lock discipline EmbedEngine uses for snapshots
        self._stats_lock = threading.Lock()
        # per-node access counters for online re-admission: every fetch
        # bumps the rows it touched (hits and misses alike — residency is
        # scored from demand, not from the current plan's hit pattern)
        self._access: Dict[str, np.ndarray] = {
            t: np.zeros(a.shape[0], np.float64) for t, a in self.host.items()
        }
        # kernels config knob: device-resident hit gathers go through the
        # scalar-prefetch gather_rows kernel when the backend supports it
        self.kernels = kernels
        self.host_m: Dict[str, np.ndarray] = {}
        self.host_v: Dict[str, np.ndarray] = {}
        self.caches: Dict[str, _TypeCache] = {}
        for t, dim in learnable_types.items():
            if t not in self.host:
                raise ValueError(f"learnable type {t} missing host table")
            self.host_m[t] = np.zeros_like(self.host[t])
            self.host_v[t] = np.zeros_like(self.host[t])
        for t, n_rows in allocation.rows.items():
            if n_rows <= 0 or t not in self.host:
                continue
            ids = _slot_order(hotness.hottest(t, n_rows), self.host[t].shape[0])
            slot_of = np.full(self.host[t].shape[0], -1, dtype=np.int64)
            slot_of[ids] = np.arange(len(ids))
            self.caches[t] = _TypeCache(
                ids=ids,
                slot_of=slot_of,
                data=jnp.asarray(self.host[t][ids]),
                m=jnp.asarray(self.host_m[t][ids]) if t in self.learnable else None,
                v=jnp.asarray(self.host_v[t][ids]) if t in self.learnable else None,
                shard_of=ids % num_shards,
            )

    # -- reads --------------------------------------------------------------

    def resident(self, types) -> Optional[Dict[str, _TypeCache]]:
        """The caches of ``types`` when each holds every row of its host
        table, so the whole table is on the device in node-id order
        (:func:`_slot_order`); else None."""
        out = {}
        for t in types:
            c = self.caches.get(t)
            if c is None or len(c.ids) != self.host[t].shape[0]:
                return None
            out[t] = c
        return out

    def _device_gather(self, data: jnp.ndarray, slots: np.ndarray) -> jnp.ndarray:
        """Device-side row gather of cached rows — the paper-§6 cache fetch
        hot path, routed through the scalar-prefetch ``gather_rows`` kernel
        when the ``kernels.gather`` knob resolves to it for this backend."""
        from repro.kernels.gather_rows import gather_rows_cfg

        return gather_rows_cfg(data, jnp.asarray(slots), self.kernels)

    def fetch(self, ntype: str, nids: np.ndarray) -> jnp.ndarray:
        """Gather rows for ``nids``; cache hits read device memory, misses
        transfer from host.  Returns a device array [len(nids), d].  The
        span ``heta.cache.fetch``."""
        with obs.span("heta.cache.fetch"):
            return self._fetch(ntype, nids)

    def _fetch(self, ntype: str, nids: np.ndarray) -> jnp.ndarray:
        with self._stats_lock:
            np.add.at(self._access[ntype], nids, 1.0)
        c = self.caches.get(ntype)
        if c is None:
            return jnp.asarray(self.host[ntype][nids])
        slots = c.slot_of[nids]
        hit = slots >= 0
        with self._stats_lock:
            c.hits += int(hit.sum())
            c.misses += int((~hit).sum())
        if hit.all():
            return self._device_gather(c.data, slots)
        rows_miss = jnp.asarray(self.host[ntype][nids[~hit]])
        # partial hits: `slots[hit]` has a different length nearly every
        # batch — a jitted Pallas call would recompile per length, so the
        # mixed path stays on the XLA gather (only the stable batch-sized
        # full-hit shape goes through the kernel)
        rows_hit = c.data[jnp.asarray(slots[hit])]
        out = jnp.zeros((len(nids), self.host[ntype].shape[1]), rows_hit.dtype)
        out = out.at[jnp.asarray(np.nonzero(hit)[0])].set(rows_hit)
        out = out.at[jnp.asarray(np.nonzero(~hit)[0])].set(rows_miss)
        return out

    def fetch_many(self, requests: Dict[str, np.ndarray]) -> Dict[str, jnp.ndarray]:
        """Batched multi-type lookup: one device gather per node type.

        ``requests`` maps ntype -> nid array (any integer dtype / shape [n]).
        The serving hot path coalesces every request in a micro-batch flush
        into a single ``fetch_many`` call, so a flush costs one gather per
        *type* rather than one per request; hit/miss counters accrue exactly
        as the equivalent sequence of :meth:`fetch` calls would."""
        return {
            t: self.fetch(t, np.asarray(nids, dtype=np.int64))
            for t, nids in requests.items()
            if len(nids)
        }

    def fetch_states(self, ntype: str, nids: np.ndarray):
        """(rows, m, v) for a learnable type (row-aligned Adam states); the
        span ``heta.cache.fetch``."""
        with obs.span("heta.cache.fetch"):
            rows = self._fetch(ntype, nids)
            c = self.caches.get(ntype)
            if c is None or c.m is None:
                return (rows, jnp.asarray(self.host_m[ntype][nids]),
                        jnp.asarray(self.host_v[ntype][nids]))
            slots = c.slot_of[nids]
            hit = slots >= 0
            m = np.asarray(self.host_m[ntype][nids])
            v = np.asarray(self.host_v[ntype][nids])
            m[hit] = np.asarray(c.m[jnp.asarray(slots[hit])])
            v[hit] = np.asarray(c.v[jnp.asarray(slots[hit])])
            return rows, jnp.asarray(m), jnp.asarray(v)

    # -- writes (learnable rows + optimizer states) ---------------------------

    def write_learnable(
        self, ntype: str, nids: np.ndarray, rows: jnp.ndarray, m: jnp.ndarray, v: jnp.ndarray
    ) -> None:
        """Write updated learnable rows to their single authoritative copy."""
        if ntype not in self.learnable:
            raise ValueError(f"{ntype} is not learnable")
        c = self.caches.get(ntype)
        if c is None:
            self.host[ntype][nids] = np.asarray(rows)
            self.host_m[ntype][nids] = np.asarray(m)
            self.host_v[ntype][nids] = np.asarray(v)
            return
        slots = c.slot_of[nids]
        hit = slots >= 0
        if hit.any():
            sl = jnp.asarray(slots[hit])
            sel = jnp.asarray(np.nonzero(hit)[0])
            c.data = c.data.at[sl].set(rows[sel])
            c.m = c.m.at[sl].set(m[sel])
            c.v = c.v.at[sl].set(v[sel])
        if (~hit).any():
            miss = nids[~hit]
            self.host[ntype][miss] = np.asarray(rows)[~hit]
            self.host_m[ntype][miss] = np.asarray(m)[~hit]
            self.host_v[ntype][miss] = np.asarray(v)[~hit]

    # -- online admission (observed-traffic residency) -------------------------

    def take_access_counts(self, reset: bool = True) -> Dict[str, np.ndarray]:
        """Drain the per-node access counters (ntype -> float64 [num_nodes]).

        ``reset=True`` (the default) zeroes them, so successive calls see
        disjoint observation windows — the natural input for an EMA."""
        with self._stats_lock:
            out = {t: a.copy() for t, a in self._access.items()}
            if reset:
                for a in self._access.values():
                    a[:] = 0.0
        return out

    def update_residency(
        self, allocation: CacheAllocation, hotness: HotnessProfile
    ) -> Dict[str, Dict[str, int]]:
        """Incrementally move the cache to a new allocation/hotness plan.

        Per type: the new resident set is the plan's ``rows[t]`` hottest
        ids.  Rows resident under both plans are *kept* — their device
        copy is gathered in place, no host traffic.  Evicted learnable
        rows write row + Adam states back to host before leaving (the
        non-replicative invariant: the authoritative copy moves, it is
        never duplicated).  Only admitted rows transfer host→device.

        Each type's cache is rebuilt as a fresh ``_TypeCache`` and swapped
        in with one attribute assignment: a concurrent ``fetch`` that
        already grabbed the old object sees a coherent (merely stale)
        view.  Callers that also write (``write_learnable`` /
        ``fetch_states``) must serialize against this method — EmbedEngine
        holds its table lock around both.

        Returns ntype -> {"kept", "admitted", "evicted"} row counts.
        """
        moves: Dict[str, Dict[str, int]] = {}
        for t in sorted(self.host):
            n_rows = int(allocation.rows.get(t, 0))
            old = self.caches.get(t)
            if n_rows <= 0 and old is None:
                continue
            new_ids = _slot_order(
                np.asarray(hotness.hottest(t, n_rows), np.int64)
                if n_rows > 0 else np.zeros(0, np.int64),
                self.host[t].shape[0])
            old_slots = (
                old.slot_of[new_ids] if old is not None
                else np.full(len(new_ids), -1, np.int64)
            )
            kept = old_slots >= 0
            n_evicted = 0
            if old is not None:
                stay = np.zeros(len(old.ids), bool)
                stay[old_slots[kept]] = True
                ev = ~stay
                n_evicted = int(ev.sum())
                if n_evicted and t in self.learnable:
                    ev_ids = old.ids[ev]
                    ev_sl = jnp.asarray(np.nonzero(ev)[0])
                    self.host[t][ev_ids] = np.asarray(old.data[ev_sl])
                    self.host_m[t][ev_ids] = np.asarray(old.m[ev_sl])
                    self.host_v[t][ev_ids] = np.asarray(old.v[ev_sl])
            if n_rows <= 0:
                del self.caches[t]
                moves[t] = {"kept": 0, "admitted": 0, "evicted": n_evicted}
                continue
            dim = self.host[t].shape[1]
            dtype = self.host[t].dtype
            learn = t in self.learnable
            data = jnp.zeros((len(new_ids), dim), dtype)
            m = jnp.zeros((len(new_ids), dim), dtype) if learn else None
            v = jnp.zeros((len(new_ids), dim), dtype) if learn else None
            if kept.any():
                dst = jnp.asarray(np.nonzero(kept)[0])
                src = jnp.asarray(old_slots[kept])
                data = data.at[dst].set(old.data[src])
                if learn:
                    m = m.at[dst].set(old.m[src])
                    v = v.at[dst].set(old.v[src])
            if (~kept).any():
                dst = jnp.asarray(np.nonzero(~kept)[0])
                admit = new_ids[~kept]
                data = data.at[dst].set(jnp.asarray(self.host[t][admit]))
                if learn:
                    m = m.at[dst].set(jnp.asarray(self.host_m[t][admit]))
                    v = v.at[dst].set(jnp.asarray(self.host_v[t][admit]))
            slot_of = np.full(self.host[t].shape[0], -1, dtype=np.int64)
            slot_of[new_ids] = np.arange(len(new_ids))
            self.caches[t] = _TypeCache(
                ids=new_ids,
                slot_of=slot_of,
                data=data,
                m=m,
                v=v,
                shard_of=new_ids % self.num_shards,
                hits=old.hits if old is not None else 0,
                misses=old.misses if old is not None else 0,
            )
            moves[t] = {
                "kept": int(kept.sum()),
                "admitted": int((~kept).sum()),
                "evicted": n_evicted,
            }
        return moves

    # -- checkpoint support (DESIGN.md §12) ------------------------------------

    def merged_learnable_state(self):
        """(tables, m, v): per learnable type, the host array with cached
        rows merged in — the coherent full-table state a checkpoint stores.
        Caller must hold the engine's table lock."""
        tables, m, v = {}, {}, {}
        for t in self.learnable:
            tab = self.host[t].copy()
            mm = self.host_m[t].copy()
            vv = self.host_v[t].copy()
            c = self.caches.get(t)
            if c is not None:
                tab[c.ids] = np.asarray(c.data)
                if c.m is not None:
                    mm[c.ids] = np.asarray(c.m)
                    vv[c.ids] = np.asarray(c.v)
            tables[t], m[t], v[t] = tab, mm, vv
        return tables, m, v

    def residency(self) -> Dict[str, np.ndarray]:
        """ntype -> cached node ids (the §6 residency profile)."""
        return {t: c.ids.copy() for t, c in self.caches.items()}

    def set_residency(self, ids_by_type: Dict[str, np.ndarray]) -> None:
        """Rebuild every per-type cache to exactly these resident ids,
        sourcing row data (and Adam states) from the host tables — restore
        path only: callers must have written authoritative full tables to
        host first (:meth:`merged_learnable_state` inverse).  Caller holds
        the engine's table lock."""
        for t in list(self.caches):
            if t not in ids_by_type:
                del self.caches[t]
        for t, ids in ids_by_type.items():
            if t not in self.host:
                continue
            ids = _slot_order(np.asarray(ids, np.int64), self.host[t].shape[0])
            slot_of = np.full(self.host[t].shape[0], -1, dtype=np.int64)
            slot_of[ids] = np.arange(len(ids))
            learn = t in self.learnable
            old = self.caches.get(t)
            self.caches[t] = _TypeCache(
                ids=ids,
                slot_of=slot_of,
                data=jnp.asarray(self.host[t][ids]),
                m=jnp.asarray(self.host_m[t][ids]) if learn else None,
                v=jnp.asarray(self.host_v[t][ids]) if learn else None,
                shard_of=ids % self.num_shards,
                hits=old.hits if old is not None else 0,
                misses=old.misses if old is not None else 0,
            )

    # -- stats ----------------------------------------------------------------

    def hit_rates(self) -> Dict[str, float]:
        out = {}
        with self._stats_lock:
            for t, c in self.caches.items():
                tot = c.hits + c.misses
                out[t] = c.hits / tot if tot else 0.0
        return out

    def reset_stats(self) -> None:
        with self._stats_lock:
            for c in self.caches.values():
                c.hits = c.misses = 0

    def miss_time(self, penalties: MissPenaltyProfile, bytes_per_elem: int = 4) -> float:
        """Estimated seconds spent on cache misses so far (penalty model)."""
        t_total = 0.0
        with self._stats_lock:
            for t, c in self.caches.items():
                rb = row_bytes(penalties.dims[t], penalties.learnable[t], bytes_per_elem)
                t_total += c.misses * penalties.ratios[t] * rb
        return t_total

    def consistency_check(self) -> bool:
        """Non-replicative invariant: a cached row's host copy is never read
        or written — verify slots are unique and shard assignment follows the
        mod-hash rule (paper §6)."""
        for t, c in self.caches.items():
            if len(np.unique(c.ids)) != len(c.ids):
                return False
            if not np.array_equal(c.shard_of, c.ids % self.num_shards):
                return False
        return True
