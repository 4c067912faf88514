"""LPT: int8 codes + per-row Delta, no fp32 master copy (paper §2.3, Eq. 8).

Thin adapter over :mod:`repro.core.lpt` — the paper-faithful math stays there.
``spec.use_kernels`` routes the lookups (``dequant_gather``) and the dense
write-back (``lpt_update``) through the fused Pallas kernels
(``repro.kernels.ops``); the CTR sparse step is XLA's row gather, update and
scatter (``lpt_core.sparse_apply``) either way.  ``spec.pad_to_tiles``
allocates the table at kernel-tile geometry (live ``(n, d)`` is sliced back
out everywhere the model looks).

Serving ships the table as-is: ``serving_state`` (inherited from
:class:`~repro.methods.base.IntegerTableMethod`) hands the codes + per-row
Delta to the ``repro.serving`` Engine, which reads rows through
``ops.dequant_gather`` inside its jitted steps — no fp32 export.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import lpt as lpt_core
from repro.methods.base import IntegerTableMethod, register
from repro.storage import base as rowstore


def _pad_grads(grads, state, spec):
    """Zero-pad live-geometry dense gradients up to the allocated table."""
    n_alloc, d_alloc = state.codes.shape
    n, d = grads.shape
    if (n, d) == (n_alloc, d_alloc):
        return grads
    return jnp.pad(grads, ((0, n_alloc - n), (0, d_alloc - d)))


@register("lpt")
class LPTMethod(IntegerTableMethod):
    # Vanilla LPT fixes Delta from the tuned clip value; ALPT overrides this.
    _clip_value_of = staticmethod(lambda spec: spec.clip_value)

    def init(self, key, spec):
        return lpt_core.init_table(
            key,
            spec.n_padded,
            spec.d_padded,
            spec.bits,
            init_scale=spec.init_scale,
            clip_value=self._clip_value_of(spec),
            optimizer=spec.row_optimizer,
            use_kernels=spec.use_kernels,
            packed=spec.packed,
        )

    def lookup(self, state, ids, spec, grad_scale=1.0):
        return lpt_core.lookup(
            state, ids, use_kernels=spec.use_kernels, out_dim=spec.d
        )

    def dense_table(self, state, spec):
        return lpt_core.dense_table(state)[: spec.n, : spec.d]

    def memory_bytes(self, state, spec, *, training):
        # Storage-actual: the container's resident bytes (packed sub-byte
        # widths really are ceil(d*bits/8) per row) + the per-row fp32 Delta.
        return (
            rowstore.resident_bytes_of(state.codes) + spec.n_padded * 4
        )

    def sparse_apply(self, state, ids, g_rows, *, spec, lr, weight_decay,
                     noise_key):
        return lpt_core.sparse_apply(
            state, ids, g_rows,
            lr=lr, bits=spec.bits, rounding=spec.alpt.rounding,
            noise_key=noise_key, optimizer=spec.row_optimizer,
            weight_decay=weight_decay, id_space=spec.n,
        )

    def dense_update(self, state, opt, grads, *, spec, lr, weight_decay,
                     noise_key=None, delta_grad=None, batch_rows=None):
        new_state = lpt_core.dense_apply(
            state, _pad_grads(grads, state, spec),
            lr=lr, bits=spec.bits, rounding=spec.alpt.rounding,
            noise_key=noise_key, optimizer=spec.row_optimizer,
            weight_decay=weight_decay, use_kernels=spec.use_kernels,
        )
        return new_state, None, {}

    def table_pspec(self, row, col, *, row_optimizer="adam"):
        slot = P(row, col) if row_optimizer == "adam" else P(row)
        return lpt_core.LPTTable(
            codes=P(row, col), step=P(row), mu=slot, nu=slot, count=P()
        )
