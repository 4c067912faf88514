"""Device-resident hot-row cache composed over any :class:`RowStore` backing.

Two halves, split by where the work runs:

* :class:`TieredCodes` — the in-jit container.  A registered pytree holding
  the ``backing`` tier (CodeStore or raw codes), a fixed-capacity ``hot``
  tier in the same layout, and two int32 id<->slot maps.  All four RowStore
  operations route per-row: reads overlay cached rows on the backing gather
  (one batched gather + a where-merge, static shapes, stable jit geometry);
  writes land in the hot tier for cached rows and in the backing for
  everything else.  Cache-on is bitwise-equal to cache-off for every
  operation — the hot tier always holds the row's *current* value.

* :class:`HotRowCache` — the host-side policy manager.  LRU eviction with
  frequency admission (a miss only displaces a victim with a strictly lower
  access count), per-slot dirty flags for write-back-before-eviction, and
  hit/miss/eviction/write-back counters.  ``observe`` consumes a batch's
  ids and returns padded-to-capacity move arrays; ``apply`` executes them
  in one jitted step (dirty write-back -> map update -> admission gather),
  so membership churn never retraces the training step.

The cache layers *codes only*.  Scale vectors and optimizer slots stay
full-size device arrays — they are dense [n]-indexed state the routed paths
already read by id, and the de-quantize multiply commutes with the row
routing, which is what keeps the parity bitwise.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import codestore
from repro.faults import plan as faultplan
from repro.faults.recovery import RetryStats, retry_with_backoff
from repro.obs import counters as obs_counters
from repro.obs.trace import tracer
from repro.storage import base as rowstore

__all__ = ["TieredCodes", "HotRowCache", "wrap_codes"]

_MET_WRITEBACK_ROWS = obs_counters.registry().counter(
    "storage.writeback_rows", "dirty hot rows flushed to the backing tier"
)


@dataclasses.dataclass(frozen=True)
class TieredCodes:
    """Hot tier + backing tier behind the RowStore protocol.

    ``slot_of_id`` is int32 ``[n_alloc]`` (-1 = not cached); ``ids_of_slot``
    is int32 ``[capacity]`` (-1 = free slot).  Both are device-resident so
    lookups route *inside* jit; the host-side policy mirror lives in
    :class:`HotRowCache`.
    """

    backing: "codestore.CodeStore | jax.Array"
    hot: "codestore.CodeStore | jax.Array"  # [capacity, d], same layout
    slot_of_id: jax.Array
    ids_of_slot: jax.Array

    # ------------------------------------------------------------ facade

    @property
    def shape(self) -> tuple[int, int]:
        return tuple(self.backing.shape)

    @property
    def dtype(self):
        return jnp.int8

    @property
    def size(self) -> int:
        return int(self.shape[0]) * int(self.shape[1])

    @property
    def ndim(self) -> int:
        return 2

    @property
    def capacity(self) -> int:
        return int(self.ids_of_slot.shape[0])

    @property
    def hot_bytes(self) -> int:
        return rowstore.resident_bytes_of(self.hot)

    @property
    def metadata_bytes(self) -> int:
        """Device bytes of the id<->slot maps (part of the cache budget)."""
        return int(self.slot_of_id.size + self.ids_of_slot.size) * 4

    @property
    def resident_bytes(self) -> int:
        """Backing + hot tier + cache metadata — the honest device footprint."""
        return (
            rowstore.resident_bytes_of(self.backing)
            + self.hot_bytes
            + self.metadata_bytes
        )

    # ------------------------------------------------------------ routing

    def slots_for(self, ids: jax.Array) -> jax.Array:
        """Hot-tier slot per id (-1 = uncached / out of range)."""
        n = self.shape[0]
        safe = jnp.clip(ids, 0, n - 1)
        slot = jnp.take(self.slot_of_id, safe)
        ok = (ids >= 0) & (ids < n)
        return jnp.where(ok, slot, -1)

    # ------------------------------------------------------------ reads

    def unpack(self) -> jax.Array:
        """Full logical [n, d] view: backing overlaid with cached rows."""
        base = rowstore.logical_codes(self.backing)
        n = base.shape[0]
        hot = rowstore.logical_codes(self.hot)
        idx = jnp.where(self.ids_of_slot >= 0, self.ids_of_slot, n)
        return base.at[idx].set(hot, mode="drop")

    def take(self, ids: jax.Array) -> jax.Array:
        """Routed gather: one backing gather + one hot gather, where-merged.

        Static shapes whatever the hit pattern — the partition is a mask,
        not a compaction, so jit geometry never depends on cache contents.
        """
        base = rowstore.take_rows(self.backing, ids)
        slot = self.slots_for(ids)
        hot = rowstore.take_rows(self.hot, jnp.clip(slot, 0, self.capacity - 1))
        return jnp.where((slot >= 0)[..., None], hot, base)

    # ------------------------------------------------------------ writes

    def set_rows(self, rows_idx: jax.Array, codes_rows: jax.Array, *,
                 mode: str = "drop",
                 indices_are_sorted: bool = False) -> "TieredCodes":
        """Row scatter routed per id: cached rows write the hot tier only
        (the host manager marks them dirty); uncached rows write the backing.
        Out-of-range ids (dedup sentinels) behave exactly as the backing
        would: real scratch rows are written, true OOB indices drop.
        The routing breaks any order of ``rows_idx``, so
        ``indices_are_sorted`` is not passed on.
        """
        n = self.shape[0]
        slot = self.slots_for(rows_idx)
        hot_idx = jnp.where(slot >= 0, slot, self.capacity)
        back_idx = jnp.where(slot >= 0, n, rows_idx)
        hot = rowstore.set_rows(self.hot, hot_idx, codes_rows, mode="drop")
        backing = rowstore.set_rows(self.backing, back_idx, codes_rows, mode=mode)
        return dataclasses.replace(self, hot=hot, backing=backing)

    def where_rows(self, row_mask: jax.Array, codes_new) -> "TieredCodes":
        """Dense masked write: selected rows take the new value in *both*
        tiers (so no dirtiness is introduced — the dense/pjit path stays
        write-back-free); unselected cached rows keep their hot value.
        """
        new_logical = rowstore.logical_codes(codes_new)
        backing = rowstore.where_rows(self.backing, row_mask, new_logical)
        n = self.shape[0]
        ids = self.ids_of_slot
        safe = jnp.clip(ids, 0, n - 1)
        mask1 = row_mask.reshape(-1)
        m_slot = (ids >= 0) & jnp.take(mask1, safe)
        new_rows = jnp.take(new_logical, safe, axis=0)
        sel = jnp.where(m_slot, jnp.arange(self.capacity), self.capacity)
        hot = rowstore.set_rows(self.hot, sel, new_rows, mode="drop")
        return dataclasses.replace(self, backing=backing, hot=hot)


def _flatten_with_keys(t: TieredCodes):
    g = jax.tree_util.GetAttrKey
    return (
        (g("backing"), t.backing), (g("hot"), t.hot),
        (g("slot_of_id"), t.slot_of_id), (g("ids_of_slot"), t.ids_of_slot),
    ), None


def _flatten(t: TieredCodes):
    return (t.backing, t.hot, t.slot_of_id, t.ids_of_slot), None


def _unflatten(aux, children) -> TieredCodes:
    return TieredCodes(*children)


jax.tree_util.register_pytree_with_keys(
    TieredCodes, _flatten_with_keys, _unflatten, _flatten
)


def wrap_codes(codes, capacity: int) -> TieredCodes:
    """Compose an (empty) hot tier over ``codes`` in the same layout."""
    n_alloc, d = codes.shape
    if isinstance(codes, codestore.CodeStore):
        hot = codestore.CodeStore.from_codes(
            jnp.zeros((capacity, d), jnp.int8), codes.bits, packed=codes.packed
        )
    else:
        hot = jnp.zeros((capacity, d), codes.dtype)
    return TieredCodes(
        backing=codes,
        hot=hot,
        slot_of_id=jnp.full((int(n_alloc),), -1, jnp.int32),
        ids_of_slot=jnp.full((int(capacity),), -1, jnp.int32),
    )


@jax.jit
def _apply_moves(tiered: TieredCodes, ev_slots, ev_ids, ev_dirty,
                 adm_slots, adm_ids) -> TieredCodes:
    """One jitted membership transaction, padded to capacity:

    1. write back the *dirty* evicted hot rows into the backing,
    2. clear the evicted ids from both maps,
    3. gather admitted rows from the post-write-back backing into the hot
       tier and set their map entries.

    Evicted and admitted id sets are disjoint by construction (the host
    policy never readmits what it just evicted in the same transaction), so
    the scatter order above is the only one that matters.
    """
    n = tiered.shape[0]
    cap = tiered.capacity
    # 1. dirty write-back (clean evictions already match the backing).
    ev_rows = rowstore.take_rows(tiered.hot, jnp.clip(ev_slots, 0, cap - 1))
    wb_idx = jnp.where((ev_ids >= 0) & ev_dirty, ev_ids, n)
    backing = rowstore.set_rows(tiered.backing, wb_idx, ev_rows, mode="drop")
    # 2. map clears.
    slot_of = tiered.slot_of_id.at[
        jnp.where(ev_ids >= 0, ev_ids, n)
    ].set(-1, mode="drop")
    ids_of = tiered.ids_of_slot.at[
        jnp.where(ev_ids >= 0, ev_slots, cap)
    ].set(-1, mode="drop")
    # 3. admissions from the post-write-back backing.
    adm_rows = rowstore.take_rows(backing, jnp.clip(adm_ids, 0, n - 1))
    hot = rowstore.set_rows(
        tiered.hot, jnp.where(adm_ids >= 0, adm_slots, cap), adm_rows,
        mode="drop",
    )
    slot_of = slot_of.at[
        jnp.where(adm_ids >= 0, adm_ids, n)
    ].set(adm_slots, mode="drop")
    ids_of = ids_of.at[
        jnp.where(adm_ids >= 0, adm_slots, cap)
    ].set(adm_ids, mode="drop")
    return TieredCodes(
        backing=backing, hot=hot, slot_of_id=slot_of, ids_of_slot=ids_of
    )


@jax.jit
def _write_back(tiered: TieredCodes, slots, ids) -> TieredCodes:
    """Flush listed hot rows into the backing (membership unchanged)."""
    n = tiered.shape[0]
    rows = rowstore.take_rows(
        tiered.hot, jnp.clip(slots, 0, tiered.capacity - 1)
    )
    backing = rowstore.set_rows(
        tiered.backing, jnp.where(ids >= 0, ids, n), rows, mode="drop"
    )
    return dataclasses.replace(tiered, backing=backing)


class HotRowCache:
    """Host-side cache policy for one :class:`TieredCodes` slot.

    LRU victim selection with frequency admission: a miss is admitted into a
    free slot unconditionally, but only displaces the least-recently-used
    victim when its lifetime access count strictly exceeds the victim's —
    the classic guard against scan traffic flushing the hot set.
    """

    def __init__(self, capacity: int, n_alloc: int, *, name: str = "codes"):
        capacity = int(min(capacity, n_alloc))
        if capacity < 1:
            raise ValueError(f"cache capacity must be >= 1, got {capacity}")
        self.name = name
        self.capacity = capacity
        self.n_alloc = int(n_alloc)
        self.slot_of_arr = np.full(self.n_alloc, -1, np.int32)
        self.slot_ids = np.full(capacity, -1, np.int64)
        self.freq = np.zeros(self.n_alloc, np.int64)
        self.last_used = np.zeros(capacity, np.int64)
        self.dirty = np.zeros(capacity, bool)
        self._free = list(range(capacity))[::-1]  # pop() fills slot 0 first
        self.clock = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.writebacks = 0
        # Waves this cache refused on (injected) admission memory pressure;
        # the wave is then served straight off the backing tier (degraded
        # but bitwise-equal — cache-on == cache-off holds per row).
        self.admission_oom = 0
        self.observe_calls = 0  # wave index for the cache.admission seam
        self.flush_calls = 0  # flush index, the tiered.writeback seam basis
        self.retry_stats = RetryStats()  # dirty write-back retry accounting

    # ------------------------------------------------------------ wrap

    def wrap(self, codes) -> TieredCodes:
        """Compose an empty hot tier over ``codes`` at this cache's capacity."""
        if codes.shape[0] != self.n_alloc:
            raise ValueError(
                f"codes rows {codes.shape[0]} != cache n_alloc {self.n_alloc}"
            )
        return wrap_codes(codes, self.capacity)

    # ------------------------------------------------------------ policy

    def observe(self, ids, *, write: bool = False):
        """Account one batch of (local) ids; returns move arrays or None.

        ``write=True`` marks touched cached rows dirty (the routed
        ``set_rows`` put their new codes in the hot tier only).  Hits and
        misses are counted per occurrence against pre-admission membership.
        Negative / out-of-range ids (other slots' traffic, sentinels) are
        ignored.
        """
        wave = self.observe_calls
        self.observe_calls += 1
        spec = faultplan.lookup("cache.admission")
        if spec is not None and spec.fires(wave):
            # Injected admission OOM: refuse BEFORE any policy state mutates
            # (a half-observed wave would desync the host maps from the
            # device overlay).  The caller serves the wave off the backing
            # tier — degraded, counted, bitwise-equal.
            self.admission_oom += 1
            return None
        ids = np.asarray(ids).reshape(-1).astype(np.int64)
        ids = ids[(ids >= 0) & (ids < self.n_alloc)]
        self.clock += 1
        if ids.size == 0:
            return None
        uniq, counts = np.unique(ids, return_counts=True)
        self.freq[uniq] += counts
        slots = self.slot_of_arr[uniq]
        hit = slots >= 0
        self.hits += int(counts[hit].sum())
        self.misses += int(counts[~hit].sum())
        hot_slots = slots[hit]
        self.last_used[hot_slots] = self.clock
        if write:
            self.dirty[hot_slots] = True
        miss_ids = uniq[~hit]
        if miss_ids.size == 0:
            return None
        ev_slots: list[int] = []
        ev_ids: list[int] = []
        ev_dirty: list[bool] = []
        adm_slots: list[int] = []
        adm_ids: list[int] = []
        # Admit hottest misses first so the frequency guard sees them before
        # colder ones contend for the same victims.
        for i in miss_ids[np.argsort(-self.freq[miss_ids], kind="stable")]:
            i = int(i)
            if self._free:
                slot = self._free.pop()
            else:
                victim = int(np.argmin(self.last_used))
                vid = int(self.slot_ids[victim])
                if self.freq[i] <= self.freq[vid]:
                    continue  # frequency admission: keep the hotter row
                ev_slots.append(victim)
                ev_ids.append(vid)
                ev_dirty.append(bool(self.dirty[victim]))
                self.evictions += 1
                if self.dirty[victim]:
                    self.writebacks += 1
                self.slot_of_arr[vid] = -1
                slot = victim
            self.slot_of_arr[i] = slot
            self.slot_ids[slot] = i
            self.last_used[slot] = self.clock
            self.dirty[slot] = False
            adm_slots.append(slot)
            adm_ids.append(i)
        if not adm_ids:
            return None
        return self._pad_moves(ev_slots, ev_ids, ev_dirty, adm_slots, adm_ids)

    def _pad_moves(self, ev_slots, ev_ids, ev_dirty, adm_slots, adm_ids):
        """Pad move lists to capacity so `apply` traces exactly once."""
        cap = self.capacity

        def pad_i32(vals):
            out = np.full(cap, -1, np.int32)
            out[: len(vals)] = vals
            return out

        dirty = np.zeros(cap, bool)
        dirty[: len(ev_dirty)] = ev_dirty
        return (
            pad_i32(ev_slots), pad_i32(ev_ids), dirty,
            pad_i32(adm_slots), pad_i32(adm_ids),
        )

    # ------------------------------------------------------------ device

    def apply(self, tiered: TieredCodes, moves) -> TieredCodes:
        """Execute ``observe``'s moves on the device container (jitted)."""
        ev_s, ev_i, ev_d, ad_s, ad_i = (jnp.asarray(m) for m in moves)
        return _apply_moves(tiered, ev_s, ev_i, ev_d, ad_s, ad_i)

    def observe_apply(self, tiered: TieredCodes, ids, *,
                      write: bool = False) -> TieredCodes:
        moves = self.observe(ids, write=write)
        return tiered if moves is None else self.apply(tiered, moves)

    def _dirty_moves(self):
        idx = np.nonzero(self.dirty)[0]
        if idx.size == 0:
            return None
        slots = np.full(self.capacity, -1, np.int32)
        ids = np.full(self.capacity, -1, np.int32)
        slots[: idx.size] = idx
        ids[: idx.size] = self.slot_ids[idx]
        return jnp.asarray(slots), jnp.asarray(ids), int(idx.size)

    def flush(self, tiered: TieredCodes) -> TieredCodes:
        """Write every dirty hot row back to the backing; membership and the
        hot tier stay intact (training can continue through the cache).

        The write-back runs behind bounded retry+backoff (the
        ``tiered.writeback`` seam: an installed plan can fail it ``fails``
        times per fired flush).  ``_write_back`` is a pure jitted function,
        so a retried attempt is bitwise-identical; exhaustion raises
        ``RetryError`` loudly with the dirty rows still flagged."""
        moves = self._dirty_moves()
        flush_idx = self.flush_calls
        self.flush_calls += 1
        if moves is None:
            return tiered
        slots, ids, k = moves
        spec = faultplan.lookup("tiered.writeback")
        armed = spec is not None and spec.fires(flush_idx)
        fails = [int(spec.param("fails", 1)) if armed else 0]

        def write():
            if fails[0] > 0:
                fails[0] -= 1
                raise faultplan.TransientFault(
                    f"tiered.writeback injected failure (flush {flush_idx})"
                )
            return _write_back(tiered, slots, ids)

        attempts = int(spec.param("attempts", 4)) if spec is not None else 4
        with tracer().span("storage.writeback", rows=k, store=self.name):
            tiered = retry_with_backoff(
                write, op="tiered.writeback", attempts=attempts, base_s=0.002,
                stats=self.retry_stats,
            )
        self.dirty[:] = False
        self.writebacks += k
        _MET_WRITEBACK_ROWS.inc(k)
        return tiered

    def unwrap(self, tiered: TieredCodes):
        """The backing with all cached writes folded in — bitwise-equal to
        the container a cache-off run would hold.  Non-destructive: dirty
        flags are left set, so the live tiered state stays consistent."""
        moves = self._dirty_moves()
        if moves is None:
            return tiered.backing
        slots, ids, _ = moves
        return _write_back(tiered, slots, ids).backing

    def warm_start(self, tiered: TieredCodes, freqs) -> TieredCodes:
        """Admit the top-capacity rows by the given frequency counts (e.g.
        training-time id statistics shipped with a serving checkpoint).
        Requires an empty cache."""
        if int((self.slot_of_arr >= 0).sum()):
            raise ValueError("warm_start requires an empty cache")
        f = np.asarray(freqs, np.int64).reshape(-1)
        full = np.zeros(self.n_alloc, np.int64)
        full[: min(f.size, self.n_alloc)] = f[: self.n_alloc]
        self.freq += full
        order = np.argsort(-full, kind="stable")
        order = order[full[order] > 0][: self.capacity]
        if order.size == 0:
            return tiered
        adm_slots, adm_ids = [], []
        self.clock += 1
        for i in order:
            i = int(i)
            slot = self._free.pop()
            self.slot_of_arr[i] = slot
            self.slot_ids[slot] = i
            self.last_used[slot] = self.clock
            adm_slots.append(slot)
            adm_ids.append(i)
        return self.apply(tiered, self._pad_moves([], [], [], adm_slots, adm_ids))

    # ------------------------------------------------------------ metrics

    @property
    def rows_cached(self) -> int:
        return int((self.slot_of_arr >= 0).sum())

    @property
    def hit_rate(self) -> float:
        total = self.hits + self.misses
        return self.hits / total if total else 0.0

    @property
    def host_metadata_bytes(self) -> int:
        """Host bytes of the policy state (id map, recency/freq counters)."""
        return int(
            self.slot_of_arr.nbytes + self.slot_ids.nbytes + self.freq.nbytes
            + self.last_used.nbytes + self.dirty.nbytes
        )

    def reset_counters(self) -> None:
        """Zero the traffic counters; membership and policy state persist."""
        self.hits = self.misses = self.evictions = self.writebacks = 0
        self.admission_oom = 0
        self.retry_stats = RetryStats()

    def stats(self) -> dict:
        return {
            "name": self.name,
            "capacity": self.capacity,
            "rows_cached": self.rows_cached,
            "hits": self.hits,
            "misses": self.misses,
            "evictions": self.evictions,
            "writebacks": self.writebacks,
            "admission_oom": self.admission_oom,
            "writeback_retries": self.retry_stats.retries,
            "hit_rate": self.hit_rate,
        }
