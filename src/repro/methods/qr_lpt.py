"""qr_lpt / qr_alpt: quotient-remainder hashing composed with int8 LPT tables.

The composed compressor the old two-bucket ``FLOAT_METHODS``/``INT_METHODS``
split could not express: both QR sub-tables (Shi et al. 2020) live as int8
codes + per-row Delta with NO fp32 master copy (paper Eq. 8 semantics per
sub-table), so the compression ratios multiply — ~2x from hashing times ~4x
from 8-bit codes.  Row gradients reach each sub-table through the product
rule: d(rem * quo)/drem = quo and vice versa.

This file is the registry's existence proof: a brand-new method wired into
both trainers, the DP wrapper, serving, sharding, and checkpointing without
touching any of them — everything below is registered state + formulations.
The kernel path composes for free: each sub-table routes its lookups through
the same ``repro.kernels.ops`` hot paths as plain LPT (``spec.use_kernels``)
and its row updates through the same ``sparse_apply``, each with its own
dedup sentinel / scratch row under ``spec.pad_to_tiles``.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import alpt as alpt_core
from repro.core import fence
from repro.core import hashing
from repro.core import lpt as lpt_core
from repro.core import quant
from repro.kernels import ops as kernel_ops
from repro.methods.base import IntegerTableMethod, _round_up, register
from repro.serving import table as serving_tbl
from repro.storage import base as rowstore


class QRLPTTable(NamedTuple):
    remainder: lpt_core.LPTTable  # int8 [r, d] sub-table
    quotient: lpt_core.LPTTable  # int8 [ceil(n/r), d] sub-table
    r: jax.Array  # int32 scalar — remainder modulus


@register("qr_lpt")
class QRLPTMethod(IntegerTableMethod):
    @staticmethod
    def _pad_rows(rows: int, spec) -> int:
        """Sub-table allocation: id space + scratch row, tile-rounded."""
        if not spec.pad_to_tiles:
            return rows
        return _round_up(rows + 1, kernel_ops.SUBLANE)

    def init(self, key, spec):
        r, q_rows = hashing.qr_rows(spec.n, spec.hash_compression)
        k1, k2 = jax.random.split(key)
        return QRLPTTable(
            remainder=lpt_core.init_table(
                k1, self._pad_rows(r, spec), spec.d_padded, spec.bits,
                init_scale=spec.init_scale, optimizer=spec.row_optimizer,
                use_kernels=spec.use_kernels, packed=spec.packed,
            ),
            # The quotient factor starts near 1 so the product starts ~= the
            # remainder rows (Shi et al. 2020 composition).
            quotient=lpt_core.init_table(
                k2, self._pad_rows(q_rows, spec), spec.d_padded, spec.bits,
                init_scale=spec.init_scale, mean=1.0,
                optimizer=spec.row_optimizer, use_kernels=spec.use_kernels,
                packed=spec.packed,
            ),
            r=jnp.asarray(r, jnp.int32),
        )

    def lookup(self, state, ids, spec, grad_scale=1.0):
        rem = lpt_core.lookup(
            state.remainder, ids % state.r,
            use_kernels=spec.use_kernels, out_dim=spec.d,
        )
        quo = lpt_core.lookup(
            state.quotient, ids // state.r,
            use_kernels=spec.use_kernels, out_dim=spec.d,
        )
        return rem * quo

    def dense_table(self, state, spec):
        return self.lookup(state, jnp.arange(spec.n), spec)

    def memory_bytes(self, state, spec, *, training):
        # Storage-actual: packed sub-byte containers really hold
        # ceil(d*bits/8) bytes per row; the per-row fp32 Delta rides along.
        rows = state.remainder.n_rows + state.quotient.n_rows
        return (
            rowstore.resident_bytes_of(state.remainder.codes)
            + rowstore.resident_bytes_of(state.quotient.codes)
            + rows * 4
        )

    def _sub_apply(self, table, ids, g_rows, *, spec, lr, weight_decay, key,
                   id_space):
        return lpt_core.sparse_apply(
            table, ids, g_rows,
            lr=lr, bits=spec.bits, rounding=spec.alpt.rounding,
            noise_key=key, optimizer=spec.row_optimizer,
            weight_decay=weight_decay, id_space=id_space,
        )

    def sparse_apply(self, state, ids, g_rows, *, spec, lr, weight_decay,
                     noise_key):
        r, q_rows = hashing.qr_rows(spec.n, spec.hash_compression)
        rid, qid = ids % state.r, ids // state.r
        rem = lpt_core.lookup(
            state.remainder, rid, use_kernels=spec.use_kernels, out_dim=spec.d
        )
        quo = lpt_core.lookup(
            state.quotient, qid, use_kernels=spec.use_kernels, out_dim=spec.d
        )
        # Product rule: each sub-table's row cotangent is g * (other factor).
        new_rem = self._sub_apply(
            state.remainder, rid, g_rows * quo, spec=spec, lr=lr,
            weight_decay=weight_decay, key=jax.random.fold_in(noise_key, 0),
            id_space=r,
        )
        new_quo = self._sub_apply(
            state.quotient, qid, g_rows * rem, spec=spec, lr=lr,
            weight_decay=weight_decay, key=jax.random.fold_in(noise_key, 1),
            id_space=q_rows,
        )
        return QRLPTTable(remainder=new_rem, quotient=new_quo, r=state.r)

    def dense_update(self, state, opt, grads, *, spec, lr, weight_decay,
                     noise_key=None, delta_grad=None, batch_rows=None):
        """Rank-invariant formulation: ``grads`` is the dense [n, d] gradient
        of the *virtual* product table; segment-sum it into each sub-table."""
        ids = jnp.arange(spec.n)
        rid, qid = ids % state.r, ids // state.r
        rem = lpt_core.lookup(
            state.remainder, rid, use_kernels=spec.use_kernels, out_dim=spec.d
        )
        quo = lpt_core.lookup(
            state.quotient, qid, use_kernels=spec.use_kernels, out_dim=spec.d
        )
        d_pad = state.remainder.dim - spec.d
        g_rem = jax.ops.segment_sum(
            grads * quo, rid, num_segments=state.remainder.n_rows
        )
        g_quo = jax.ops.segment_sum(
            grads * rem, qid, num_segments=state.quotient.n_rows
        )
        if d_pad:
            g_rem = jnp.pad(g_rem, ((0, 0), (0, d_pad)))
            g_quo = jnp.pad(g_quo, ((0, 0), (0, d_pad)))
        kw = dict(lr=lr, bits=spec.bits, rounding=spec.alpt.rounding,
                  optimizer=spec.row_optimizer, weight_decay=weight_decay,
                  use_kernels=spec.use_kernels)
        new_rem = lpt_core.dense_apply(
            state.remainder, g_rem,
            noise_key=jax.random.fold_in(noise_key, 0), **kw,
        )
        new_quo = lpt_core.dense_apply(
            state.quotient, g_quo,
            noise_key=jax.random.fold_in(noise_key, 1), **kw,
        )
        return QRLPTTable(remainder=new_rem, quotient=new_quo, r=state.r), None, {}

    def storage_spec(self, spec):
        """Two slots — each QR sub-table caches independently; global ids
        map into a sub-table via the same ``% r`` / ``// r`` arithmetic the
        lookups use."""
        r, q_rows = hashing.qr_rows(spec.n, spec.hash_compression)
        return (
            rowstore.CacheSlot(
                name="remainder", rows=r,
                get=lambda s: s.remainder,
                put=lambda s, t: s._replace(remainder=t),
                local_ids=lambda ids: np.asarray(ids) % r,
            ),
            rowstore.CacheSlot(
                name="quotient", rows=q_rows,
                get=lambda s: s.quotient,
                put=lambda s, t: s._replace(quotient=t),
                local_ids=lambda ids: np.asarray(ids) // r,
            ),
        )

    def table_pspec(self, row, col, *, row_optimizer="adam"):
        # Sub-table row counts rarely divide the mesh axes; stay replicated.
        sub = lpt_core.LPTTable(codes=P(), step=P(), mu=P(), nu=P(), count=P())
        return QRLPTTable(remainder=sub, quotient=sub, r=P())

    def serving_state(self, state, spec):
        """int8-resident composition: both sub-tables ship codes + their own
        per-row scale vector (qr_alpt *learns* both; serving honors each)."""
        r, q_rows = hashing.qr_rows(spec.n, spec.hash_compression)

        def sub(table, live_rows):
            return serving_tbl.QuantTable(
                codes=table.codes, step=table.step, n=live_rows, d=spec.d,
                use_kernels=spec.use_kernels,
            )

        # The modulus comes from the spec (qr_rows is deterministic), not
        # int(state.r): serving templates build this under jax.eval_shape,
        # where the state is abstract.
        return serving_tbl.QRQuantTable(
            remainder=sub(state.remainder, r),
            quotient=sub(state.quotient, q_rows),
            r=r, n=spec.n, d=spec.d,
        )


@register("qr_alpt")
class QRALPTMethod(QRLPTMethod):
    """qr_lpt with ALPT's learned step size on BOTH sub-tables.

    The ROADMAP follow-up ("ALPT-ize qr_lpt"): each sub-table keeps its own
    per-row Delta and learns it via the LSQ-style second forward (paper
    Algorithm 1 line 4) evaluated *through the composed product table*, so
    the two scale vectors co-adapt — d(loss)/d(Delta_rem) sees the quotient
    factor and vice versa, exactly like the weight gradients do.  The weight
    sub-step is qr_lpt's product-rule update unchanged; serving inherits the
    per-sub-table-scale :class:`~repro.serving.table.QRQuantTable` export.
    """

    has_learned_step = True

    @staticmethod
    def _acfg(spec, weight_decay) -> alpt_core.ALPTConfig:
        return spec.alpt._replace(
            weight_decay=weight_decay, optimizer=spec.row_optimizer,
            use_kernels=spec.use_kernels,
        )

    def _delta_writeback(self, table, uniq, w_new, step_b, g_step, *, cfg,
                         noise_key):
        """Algorithm 1 line 5 for one sub-table: Delta update + SR
        re-quantize of the already-float-updated unique rows (mirrors
        ``alpt_core.alpt_step``'s tail).  ``noise_key`` must be a key
        derived for this draw alone — the caller folds, so the key flow is
        auditable at the call site (rng-key-discipline)."""
        new_step_b = step_b - cfg.step_lr * (
            g_step + cfg.step_weight_decay * step_b
        )
        new_step_b = jnp.maximum(new_step_b, 1e-8)
        noise = quant.sr_noise(noise_key, w_new.shape)
        if cfg.use_kernels and cfg.rounding == "sr":
            codes_rows = kernel_ops.sr_round(w_new, new_step_b, noise, cfg.bits)
        else:
            if cfg.use_kernels:
                kernel_ops.note_fallback("sr_round", w_new.shape, "dr rounding")
            codes_rows = quant.quantize_codes(
                w_new, new_step_b, cfg.bits, cfg.rounding, noise
            )
        return table._replace(
            codes=rowstore.set_rows(table.codes, uniq, codes_rows, mode="drop"),
            step=table.step.at[uniq].set(new_step_b, mode="drop"),
        )

    def fused_row_step(self, state, ids, *, spec, loss_from_rows, dense_params,
                       dense_opt, update_dense, lr, weight_decay, noise_key):
        cfg = self._acfg(spec, weight_decay)
        r, q_rows = hashing.qr_rows(spec.n, spec.hash_compression)
        rid, qid = ids % state.r, ids // state.r
        rem = lpt_core.lookup(
            state.remainder, rid, use_kernels=spec.use_kernels, out_dim=spec.d
        )
        quo = lpt_core.lookup(
            state.quotient, qid, use_kernels=spec.use_kernels, out_dim=spec.d
        )

        # Step 1 (weights): one joint backward, product-rule row cotangents,
        # each sub-table's sparse update keeps its updated float rows around
        # for the Delta sub-step.
        # Fenced (see repro.core.fence): the joint backward must compile the
        # same whatever storage backs the two sub-tables.
        tick = ids.reshape(-1)[0]
        loss, (g_rows, g_dense) = fence.fence_call(
            jax.value_and_grad(loss_from_rows, (0, 1)),
            (rem * quo, dense_params),
            tick=tick,
        )
        new_dense, new_opt = update_dense(g_dense, dense_opt, dense_params)
        k_rem = jax.random.fold_in(noise_key, 0)
        k_quo = jax.random.fold_in(noise_key, 1)
        kw = dict(lr=lr, bits=spec.bits, rounding=spec.alpt.rounding,
                  optimizer=spec.row_optimizer, weight_decay=weight_decay,
                  return_updated_rows=True)
        rem1, (uniq_r, w_new_r) = lpt_core.sparse_apply(
            state.remainder, rid, g_rows * quo, noise_key=k_rem, id_space=r,
            **kw,
        )
        quo1, (uniq_q, w_new_q) = lpt_core.sparse_apply(
            state.quotient, qid, g_rows * rem, noise_key=k_quo,
            id_space=q_rows, **kw,
        )

        # Step 2 (Delta, Algorithm 1 line 4): both step vectors jointly, at
        # the UPDATED dense params, through the fake-quantized product of the
        # updated sub-table rows.
        d = state.remainder.dim
        step_r = jnp.take(
            state.remainder.step, jnp.minimum(uniq_r, state.remainder.n_rows - 1)
        )
        step_q = jnp.take(
            state.quotient.step, jnp.minimum(uniq_q, state.quotient.n_rows - 1)
        )
        inv_r = lpt_core.dedup_ids(rid, r)[1]
        inv_q = lpt_core.dedup_ids(qid, q_rows)[1]
        gscale = alpt_core.grad_scale_factor(
            cfg, batch_rows=int(ids.size), dim=spec.d
        )

        def loss_wrt_steps(steps):
            s_r, s_q = steps
            rq = quant.fake_quant_lsq(
                jax.lax.stop_gradient(w_new_r), s_r, cfg.bits, gscale
            )
            qq = quant.fake_quant_lsq(
                jax.lax.stop_gradient(w_new_q), s_q, cfg.bits, gscale
            )
            occ = (
                jnp.take(rq, inv_r, axis=0) * jnp.take(qq, inv_q, axis=0)
            ).reshape(ids.shape + (d,))
            if spec.d != d:
                occ = occ[..., : spec.d]
            return loss_from_rows(occ, new_dense)

        g_sr, g_sq = fence.fence_call(
            jax.grad(loss_wrt_steps), ((step_r, step_q),), tick=tick
        )
        # Same keys as before the rng-key-discipline refactor: the fold that
        # used to live inside _delta_writeback now happens here, so each
        # k_rem/k_quo visibly feeds one draw (sparse_apply) and one derived
        # subkey (the Delta writeback) — bitwise-identical key material.
        new_rem = self._delta_writeback(
            rem1, uniq_r, w_new_r, step_r, g_sr, cfg=cfg,
            noise_key=jax.random.fold_in(k_rem, 1),
        )
        new_quo = self._delta_writeback(
            quo1, uniq_q, w_new_q, step_q, g_sq, cfg=cfg,
            noise_key=jax.random.fold_in(k_quo, 1),
        )
        aux = {
            "step_grad_norm": jnp.sqrt(
                jnp.sum(jnp.square(g_sr)) + jnp.sum(jnp.square(g_sq))
            ),
            "mean_step": 0.5 * (jnp.mean(new_rem.step) + jnp.mean(new_quo.step)),
        }
        return (
            QRLPTTable(remainder=new_rem, quotient=new_quo, r=state.r),
            new_dense, new_opt, {"loss": loss, **aux},
        )

    def dense_update(self, state, opt, grads, *, spec, lr, weight_decay,
                     noise_key=None, delta_grad=None, batch_rows=None):
        """Rank-invariant formulation: segment-summed sub-table gradients,
        then the joint two-sub-table Delta sub-step (``delta_grad`` receives
        pytrees of both sub-tables' updated rows / step vectors)."""
        cfg = self._acfg(spec, weight_decay)
        r, q_rows = hashing.qr_rows(spec.n, spec.hash_compression)
        ids = jnp.arange(spec.n)
        rid, qid = ids % state.r, ids // state.r
        rem = lpt_core.lookup(
            state.remainder, rid, use_kernels=spec.use_kernels, out_dim=spec.d
        )
        quo = lpt_core.lookup(
            state.quotient, qid, use_kernels=spec.use_kernels, out_dim=spec.d
        )
        d_pad = state.remainder.dim - spec.d
        g_rem = jax.ops.segment_sum(
            grads * quo, rid, num_segments=state.remainder.n_rows
        )
        g_quo = jax.ops.segment_sum(
            grads * rem, qid, num_segments=state.quotient.n_rows
        )
        if d_pad:
            g_rem = jnp.pad(g_rem, ((0, 0), (0, d_pad)))
            g_quo = jnp.pad(g_quo, ((0, 0), (0, d_pad)))
        upd_r = alpt_core.dense_weight_update(state.remainder, g_rem, cfg=cfg, lr=lr)
        upd_q = alpt_core.dense_weight_update(state.quotient, g_quo, cfg=cfg, lr=lr)
        gscale = alpt_core.grad_scale_factor(
            cfg, batch_rows=int(batch_rows), dim=spec.d
        )
        # Algorithm 1 line 4 at the caller's UPDATED dense params; live
        # geometry only (pad rows/cols never looked up), gradients padded back.
        g_sr, g_sq = delta_grad(
            (upd_r.w_new[:r, : spec.d], upd_q.w_new[:q_rows, : spec.d]),
            (state.remainder.step[:r], state.quotient.step[:q_rows]),
            gscale,
        )
        if g_sr.shape != state.remainder.step.shape:
            g_sr = jnp.pad(g_sr, (0, state.remainder.step.shape[0] - g_sr.shape[0]))
        if g_sq.shape != state.quotient.step.shape:
            g_sq = jnp.pad(g_sq, (0, state.quotient.step.shape[0] - g_sq.shape[0]))
        new_rem = alpt_core.dense_finish(
            state.remainder, upd_r, g_sr, cfg=cfg,
            noise_key=jax.random.fold_in(noise_key, 0),
        )
        new_quo = alpt_core.dense_finish(
            state.quotient, upd_q, g_sq, cfg=cfg,
            noise_key=jax.random.fold_in(noise_key, 1),
        )
        aux = {
            "step_grad_norm": jnp.sqrt(
                jnp.sum(jnp.square(g_sr)) + jnp.sum(jnp.square(g_sq))
            ),
            "mean_step": 0.5 * (jnp.mean(new_rem.step) + jnp.mean(new_quo.step)),
        }
        return QRLPTTable(remainder=new_rem, quotient=new_quo, r=state.r), None, aux

    def dense_delta_grad(self, w_new, step_vec, loss_fn_q, *, spec,
                         weight_decay, gscale):
        """Joint Delta gradient through the composed table: ``w_new`` /
        ``step_vec`` are (remainder, quotient) pytrees; the fake-quantized
        product is what ``loss_fn_q`` scores (Eq. 6/7 routes each gradient to
        its own scale vector)."""
        cfg = self._acfg(spec, weight_decay)
        r, _ = hashing.qr_rows(spec.n, spec.hash_compression)
        w_r, w_q = w_new
        ids = jnp.arange(spec.n)
        rid, qid = ids % r, ids // r

        def loss_wrt_steps(steps):
            s_r, s_q = steps
            rq = quant.fake_quant_lsq(
                jax.lax.stop_gradient(w_r), s_r, cfg.bits, gscale
            )
            qq = quant.fake_quant_lsq(
                jax.lax.stop_gradient(w_q), s_q, cfg.bits, gscale
            )
            return loss_fn_q(jnp.take(rq, rid, axis=0) * jnp.take(qq, qid, axis=0))

        return jax.grad(loss_wrt_steps)((step_vec[0], step_vec[1]))
