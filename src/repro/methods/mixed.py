"""Mixed per-field precision: one LPT sub-table per bit-width group.

CTR tables are concatenations of per-field vocabularies, and the fields are
wildly asymmetric: a handful of small fields (site category, device type)
whose rows are hit on almost every example, and a few huge ones (user id,
item id) that dominate memory but whose rows are each touched rarely.  A
single global bit width over-spends on the big fields or under-serves the
hot ones.  This method assigns a bit width *per field* — from
``spec.field_bits`` when given, otherwise from the mean per-row hit rate of
the synthetic CTR stream (:func:`assign_field_bits`) — and composes the
table from one packed LPT sub-table per distinct width via the registry's
existing pieces: ``repro.core.lpt`` does the math, ``repro.core.codestore``
packs the sub-byte groups, and no trainer learns anything new.

Geometry: fields occupy contiguous global id ranges (``offsets[f]`` fence-
posts, exactly the layout :mod:`repro.data.ctr_synth` emits).  Group ``g``
stacks the rows of every field assigned to it; global id ``i`` of field
``f`` lives at row ``i - offsets[f] + field_local[f]`` of sub-table
``field_group[f]``.  The field maps are static tuples (one entry per field,
never per row), so the id arithmetic constant-folds under jit.

Without ``field_cards`` the plan degenerates to a single group at
``spec.bits`` — ordinary LPT semantics — which is what generic consumers
(the LM trainer, the conformance suite's default spec) get.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import lpt as lpt_core
from repro.kernels import ops as kernel_ops
from repro.methods.base import IntegerTableMethod, register
from repro.serving import table as serving_tbl
from repro.storage import base as rowstore


class MixedTable(NamedTuple):
    """One LPT sub-table per bit-width group (field maps live in the spec)."""

    subs: tuple[lpt_core.LPTTable, ...]


def assign_field_bits(
    cards: tuple[int, ...],
    *,
    hot_rate: float = 1.0 / 64.0,
    cold_rate: float = 1.0 / 4096.0,
) -> tuple[int, ...]:
    """Bit width per field from the synthetic stream's row-hit statistics.

    Every example looks up exactly one id per field (the
    :mod:`repro.data.ctr_synth` contract), so a field of cardinality ``c``
    hits each of its rows at mean rate ``1/c`` per example — the Zipf skew
    moves mass to head rows but cannot raise the mean.  Hot rows see many
    SR updates between reads and keep full 8-bit codes; mid fields take
    4 bits; huge vocabularies, where residency is actually won, drop to
    2 bits (both sub-byte widths store packed, 8//bits codes per byte).
    """
    out = []
    for c in cards:
        rate = 1.0 / max(int(c), 1)
        out.append(8 if rate >= hot_rate else (4 if rate >= cold_rate else 2))
    return tuple(out)


@dataclasses.dataclass(frozen=True)
class MixedPlan:
    """Static field→(group, local row) layout derived from one spec."""

    field_offsets: tuple[int, ...]  # [F] global start row per field
    field_bits: tuple[int, ...]  # [F] resolved bit width per field
    field_group: tuple[int, ...]  # [F] sub-table index per field
    field_local: tuple[int, ...]  # [F] local start row inside the sub
    group_bits: tuple[int, ...]  # [G] bit width per sub-table
    group_rows: tuple[int, ...]  # [G] live rows per sub-table
    group_fields: tuple[tuple[int, ...], ...]  # [G] field ids per sub-table


def plan_of(spec) -> MixedPlan:
    """Resolve ``spec.field_cards`` / ``field_bits`` into a static layout."""
    cards = spec.field_cards if spec.field_cards is not None else (spec.n,)
    if sum(cards) != spec.n:
        raise ValueError(
            f"field_cards sum {sum(cards)} != table rows {spec.n}"
        )
    if spec.field_bits is not None:
        fbits = tuple(int(b) for b in spec.field_bits)
        if len(fbits) != len(cards):
            raise ValueError(
                f"{len(fbits)} field_bits for {len(cards)} fields"
            )
    elif spec.field_cards is None:
        fbits = (spec.bits,)
    else:
        fbits = assign_field_bits(cards)
    for b in fbits:
        if not 2 <= b <= 8:
            raise ValueError(f"field bit width {b} outside [2, 8]")

    group_bits = tuple(sorted(set(fbits), reverse=True))
    field_group = tuple(group_bits.index(b) for b in fbits)
    offsets, acc = [], 0
    for c in cards:
        offsets.append(acc)
        acc += int(c)
    local_acc = [0] * len(group_bits)
    field_local = []
    for f, c in enumerate(cards):
        g = field_group[f]
        field_local.append(local_acc[g])
        local_acc[g] += int(c)
    return MixedPlan(
        field_offsets=tuple(offsets),
        field_bits=fbits,
        field_group=field_group,
        field_local=tuple(field_local),
        group_bits=group_bits,
        group_rows=tuple(local_acc),
        group_fields=tuple(
            tuple(f for f in range(len(cards)) if field_group[f] == g)
            for g in range(len(group_bits))
        ),
    )


def _map_ids(plan: MixedPlan, ids: jax.Array):
    """Global ids -> (group index, local row) via the static field maps."""
    offs = jnp.asarray(plan.field_offsets, jnp.int32)
    fid = jnp.searchsorted(offs, ids.astype(jnp.int32), side="right") - 1
    local = (
        ids.astype(jnp.int32)
        - jnp.take(offs, fid)
        + jnp.take(jnp.asarray(plan.field_local, jnp.int32), fid)
    )
    gid = jnp.take(jnp.asarray(plan.field_group, jnp.int32), fid)
    return gid, local


@register("mixed")
class MixedMethod(IntegerTableMethod):
    @staticmethod
    def _pad_rows(rows: int, spec) -> int:
        """Sub-table allocation: id space + scratch row, tile-rounded."""
        if not spec.pad_to_tiles:
            return rows
        return -(-(rows + 1) // kernel_ops.SUBLANE) * kernel_ops.SUBLANE

    def init(self, key, spec):
        plan = plan_of(spec)
        subs = []
        for g, bits_g in enumerate(plan.group_bits):
            subs.append(
                lpt_core.init_table(
                    jax.random.fold_in(key, g),
                    self._pad_rows(plan.group_rows[g], spec),
                    spec.d_padded,
                    bits_g,
                    init_scale=spec.init_scale,
                    clip_value=spec.clip_value,
                    optimizer=spec.row_optimizer,
                    use_kernels=spec.use_kernels,
                    packed=spec.packed,
                )
            )
        return MixedTable(subs=tuple(subs))

    def lookup(self, state, ids, spec, grad_scale=1.0):
        plan = plan_of(spec)
        gid, local = _map_ids(plan, ids)
        # Masked sum over the groups — the identical composition (group
        # order, where/sum placement) serving's MixedQuantTable.rows uses,
        # so training reads and Engine reads stay bitwise-parity.
        out = jnp.zeros(ids.shape + (spec.d,), jnp.float32)
        for g, sub in enumerate(state.subs):
            mask = gid == g
            vals = lpt_core.lookup(
                sub, jnp.where(mask, local, 0),
                use_kernels=spec.use_kernels, out_dim=spec.d,
            )
            out = out + jnp.where(mask[..., None], vals, 0.0)
        return out

    def dense_table(self, state, spec):
        return self.lookup(state, jnp.arange(spec.n), spec)

    def memory_bytes(self, state, spec, *, training):
        # Storage-actual per group: the packed containers of the sub-byte
        # groups really hold ceil(d*bits/8) bytes per row.
        return sum(
            rowstore.resident_bytes_of(sub.codes) + sub.n_rows * 4
            for sub in state.subs
        )

    def sparse_apply(self, state, ids, g_rows, *, spec, lr, weight_decay,
                     noise_key):
        plan = plan_of(spec)
        gid, local = _map_ids(plan, ids)
        subs = []
        for g, sub in enumerate(state.subs):
            rows_g = plan.group_rows[g]
            # Non-member occurrences map to the dedup sentinel: they collapse
            # into one unique entry whose writes drop, never on live rows.
            sub_ids = jnp.where(gid == g, local, rows_g)
            subs.append(
                lpt_core.sparse_apply(
                    sub, sub_ids, g_rows,
                    lr=lr, bits=plan.group_bits[g],
                    rounding=spec.alpt.rounding,
                    noise_key=jax.random.fold_in(noise_key, g),
                    optimizer=spec.row_optimizer,
                    weight_decay=weight_decay, id_space=rows_g,
                )
            )
        return MixedTable(subs=tuple(subs))

    def dense_update(self, state, opt, grads, *, spec, lr, weight_decay,
                     noise_key=None, delta_grad=None, batch_rows=None):
        plan = plan_of(spec)
        cards = spec.field_cards if spec.field_cards is not None else (spec.n,)
        subs = []
        for g, sub in enumerate(state.subs):
            # Re-lay the global [n, d] gradient into this group's row order:
            # fields are contiguous global slices, statically bounded.
            parts = [
                grads[plan.field_offsets[f]: plan.field_offsets[f] + cards[f]]
                for f in plan.group_fields[g]
            ]
            gg = parts[0] if len(parts) == 1 else jnp.concatenate(parts, 0)
            n_alloc, d_alloc = sub.codes.shape
            gg = jnp.pad(
                gg,
                ((0, n_alloc - gg.shape[0]), (0, d_alloc - gg.shape[1])),
            )
            subs.append(
                lpt_core.dense_apply(
                    sub, gg,
                    lr=lr, bits=plan.group_bits[g],
                    rounding=spec.alpt.rounding,
                    noise_key=(
                        None if noise_key is None
                        else jax.random.fold_in(noise_key, g)
                    ),
                    optimizer=spec.row_optimizer,
                    weight_decay=weight_decay,
                    use_kernels=spec.use_kernels,
                )
            )
        return MixedTable(subs=tuple(subs)), None, {}

    def serving_state(self, state, spec):
        """Integer-resident export: every group ships its packed codes +
        per-row Delta, plus the static field maps the Engine needs to route
        ids — the fp32 table never materializes."""
        plan = plan_of(spec)
        return serving_tbl.MixedQuantTable(
            subs=tuple(
                serving_tbl.QuantTable(
                    codes=sub.codes, step=sub.step,
                    n=plan.group_rows[g], d=spec.d,
                    use_kernels=spec.use_kernels,
                )
                for g, sub in enumerate(state.subs)
            ),
            field_offsets=plan.field_offsets,
            field_group=plan.field_group,
            field_local=plan.field_local,
            n=spec.n, d=spec.d,
        )

    def storage_spec(self, spec):
        """One slot per bit-width group; global ids resolve to a group's
        local row space through the same static field maps the lookups use
        (non-member ids -> -1, ignored by the cache policy)."""
        plan = plan_of(spec)
        starts = np.asarray(plan.field_offsets, np.int64)
        group = np.asarray(plan.field_group, np.int64)
        local = np.asarray(plan.field_local, np.int64)

        def make_local(g):
            def f(ids):
                ids = np.asarray(ids, np.int64)
                fid = np.searchsorted(starts, ids, side="right") - 1
                loc = ids - starts[fid] + local[fid]
                return np.where(group[fid] == g, loc, -1)

            return f

        def make_put(g):
            def put(s, t):
                return MixedTable(subs=s.subs[:g] + (t,) + s.subs[g + 1:])

            return put

        return tuple(
            rowstore.CacheSlot(
                name=f"group{g}", rows=plan.group_rows[g],
                get=(lambda g: lambda s: s.subs[g])(g),
                put=make_put(g),
                local_ids=make_local(g),
            )
            for g in range(len(plan.group_bits))
        )

    def table_pspec(self, row, col, *, row_optimizer="adam"):
        # Group row counts rarely divide mesh axes; stay replicated.  The
        # pspec mirrors the *degenerate* single-group layout — the only one
        # generic specs (no field_cards) produce; per-field CTR configs run
        # data-parallel, not pjit-sharded.
        sub = lpt_core.LPTTable(codes=P(), step=P(), mu=P(), nu=P(), count=P())
        return MixedTable(subs=(sub,))
