"""ALPT: LPT + learned per-row step size Delta (paper §3.2, Algorithm 1).

Inherits the LPT table/state handling; overrides the train-step pieces with
the two-substep schedule (weight update, then Delta learned via a second
fake-quant forward at the *updated* dense params).  ``spec.use_kernels``
flows into :class:`~repro.core.alpt.ALPTConfig` so the lookups, the dense
weight step (``ops.lpt_update``) and the line-5 requantize-with-learned-Delta
(``ops.sr_round``) run fused; the sparse weight step is XLA's row path.

The learned Delta is exactly what serving keeps: ``serving_state`` (inherited
int8-resident export) ships codes + the *learned* per-row scales straight
into the ``repro.serving`` Engine.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import alpt as alpt_core
from repro.core import fence
from repro.methods.base import register
from repro.methods.lpt import LPTMethod, _pad_grads


@register("alpt")
class ALPTMethod(LPTMethod):
    has_learned_step = True
    # ALPT learns Delta from the LSQ-style init; the clip knob is LPT-only.
    _clip_value_of = staticmethod(lambda spec: None)

    @staticmethod
    def _acfg(spec, weight_decay) -> alpt_core.ALPTConfig:
        # spec.bits is the table's storage width (it sized the code container
        # at init); a stale ALPTConfig.bits default must not write wider
        # codes into a narrower (possibly packed) container.
        return spec.alpt._replace(
            bits=spec.bits, weight_decay=weight_decay,
            optimizer=spec.row_optimizer, use_kernels=spec.use_kernels,
        )

    def fused_row_step(self, state, ids, *, spec, loss_from_rows, dense_params,
                       dense_opt, update_dense, lr, weight_decay, noise_key):
        rows0 = self.lookup(state, ids, spec)

        # Dense update (Algorithm 1 line 3) shares step 1's backward.
        # Fenced (see repro.core.fence): g_dense feeds the persistent dense
        # params, so this backward too must compile independently of the
        # storage graph around it.
        loss, g_dense = fence.fence_call(
            jax.value_and_grad(lambda dp: loss_from_rows(rows0, dp)),
            (dense_params,),
            tick=ids.reshape(-1)[0],
        )
        new_dense, new_opt = update_dense(g_dense, dense_opt, dense_params)
        new_state, loss2, aux = alpt_core.alpt_step(
            state,
            ids,
            lambda rows: loss_from_rows(rows, dense_params),
            cfg=self._acfg(spec, weight_decay),
            lr=lr,
            noise_key=noise_key,
            loss_fn_step2=lambda rows: loss_from_rows(rows, new_dense),
            id_space=spec.n,
            out_dim=spec.d,
        )
        return new_state, new_dense, new_opt, {"loss": loss2, **aux}

    def dense_update(self, state, opt, grads, *, spec, lr, weight_decay,
                     noise_key=None, delta_grad=None, batch_rows=None):
        acfg = self._acfg(spec, weight_decay)
        grads = _pad_grads(grads, state, spec)
        upd = alpt_core.dense_weight_update(state, grads, cfg=acfg, lr=lr)
        gscale = alpt_core.grad_scale_factor(
            acfg, batch_rows=int(batch_rows), dim=spec.d
        )
        # Algorithm 1 line 4 at the caller's UPDATED dense params; the caller
        # sees the live (n, d) table, so padded geometry is sliced away and
        # the resulting Delta gradient zero-padded back (pad rows untouched).
        g_step = delta_grad(
            upd.w_new[: spec.n, : spec.d], state.step[: spec.n], gscale
        )
        if g_step.shape != state.step.shape:
            g_step = jnp.pad(g_step, (0, state.step.shape[0] - g_step.shape[0]))
        new_state = alpt_core.dense_finish(
            state, upd, g_step, cfg=acfg, noise_key=noise_key
        )
        aux = {
            "step_grad_norm": jnp.linalg.norm(g_step),
            "mean_step": jnp.mean(new_state.step),
        }
        return new_state, None, aux

    def dense_delta_grad(self, w_new, step_vec, loss_fn_q, *, spec,
                         weight_decay, gscale):
        return alpt_core.dense_delta_grad(
            w_new, step_vec, loss_fn_q,
            cfg=self._acfg(spec, weight_decay), gscale=gscale,
        )
