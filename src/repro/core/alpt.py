"""Adaptive low-precision training (ALPT) — paper §3.2, Algorithm 1.

Per batch, two alternating sub-steps:

  Step 1 (weights):   w_hat_b = Delta_b * w_tilde_b          (de-quantize)
                      w_b'    = w_hat_b - eta * df/dw_hat    (+ dense params)
  Step 2 (step size): Delta_b' = Delta_b - eta_D * df(Q_D(w_b', Delta_b))/dDelta
                      w_tilde_b' = SR-quantize(w_b', Delta_b')

The Delta gradient comes from an LSQ-style second forward pass over the
*updated float rows* (quant.fake_quant_lsq, Eq. 6/7), scaled by
g = 1/sqrt(b * d * q) with q = 2^{m-1} - 1 (paper §3.2; Fig. 4 shows the
scale matters less than the Delta learning rate, both are exposed).

The weight sub-step reuses lpt.sparse_apply / lpt.dense_apply, so ALPT == LPT
plus the learned Delta — exactly the paper's framing.
"""
from __future__ import annotations

import math
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import fence, lpt, quant
from repro.kernels import ops
from repro.storage import base as rowstore


class ALPTConfig(NamedTuple):
    bits: int = 8
    rounding: str = "sr"  # rounding for the write-back (paper: SR)
    optimizer: str = "adam"  # row optimizer for the embeddings
    weight_decay: float = 5e-8  # paper: 5e-8 Avazu / 1e-5 Criteo
    step_lr: float = 2e-5  # paper: Delta learning rate 2e-5
    step_weight_decay: float = 5e-8  # paper: same decay as embeddings (8-bit)
    grad_scale: str = "bdq"  # '1' | 'dq' | 'bdq'  (Fig. 4 sweep)
    # Route the lookups, the dense write-back and the line-5 requantize
    # through repro.kernels.ops (methods copy EmbeddingSpec.use_kernels in
    # here; bitwise-identical).  The sparse write-back takes no such switch.
    use_kernels: bool = False
    # Absolute upper bound on the learned Delta (guardrail against step-size
    # blowup: one huge Delta poisons the whole row's quantization grid).
    # None (default) leaves the update graph byte-identical to the paper's;
    # when set, clamped rows are counted in aux["delta_clamped"].
    step_clamp: float | None = None


def grad_scale_factor(cfg: ALPTConfig, batch_rows: int, dim: int) -> float:
    q = 2 ** (cfg.bits - 1) - 1
    if cfg.grad_scale == "1":
        return 1.0
    if cfg.grad_scale == "dq":
        return 1.0 / math.sqrt(dim * q)
    if cfg.grad_scale == "bdq":
        return 1.0 / math.sqrt(batch_rows * dim * q)
    raise ValueError(f"unknown grad_scale {cfg.grad_scale!r}")


def alpt_step(
    table: lpt.LPTTable,
    ids: jax.Array,
    loss_fn: Callable[[jax.Array], jax.Array],
    *,
    cfg: ALPTConfig,
    lr: jax.Array,
    noise_key: jax.Array,
    loss_fn_step2: Callable[[jax.Array], jax.Array] | None = None,
    id_space: int | None = None,
    out_dim: int | None = None,
):
    """One ALPT update of a table against ``loss_fn(rows) -> scalar``.

    ``loss_fn`` closes over the batch and any dense parameters; it receives the
    de-quantized rows for ``ids`` (same leading shape as ids, trailing dim d).
    Returns (new_table, loss, aux) where aux carries diagnostics.

    Dense-parameter updates happen outside (the caller differentiates the same
    loss w.r.t. its own params); this function owns lines 1-2 and 4-5 of
    Algorithm 1 for the embedding table.  Algorithm 1 line 4 evaluates the
    step-size loss at the *updated* dense params w_o^{t+1}; pass that closure
    as ``loss_fn_step2`` (defaults to ``loss_fn``).

    ``id_space``/``out_dim`` carry the live geometry of ``pad_to_tiles``
    tables (dedup sentinel and model-facing row width); the paper's b and d
    count live lookups, not padding.
    """
    if loss_fn_step2 is None:
        loss_fn_step2 = loss_fn
    d = table.dim
    d_live = d if out_dim is None else out_dim
    n = table.n_rows
    sentinel = n if id_space is None else id_space

    # ---- Step 1: de-quantize, get row gradients, float update. ----
    rows = lpt.lookup(
        table, ids, use_kernels=cfg.use_kernels, out_dim=out_dim
    )  # w_hat_b^t
    # Fenced (see repro.core.fence): the model backward compiles as its own
    # unit whatever storage backs the codes, keeping cache-on bitwise-equal
    # to cache-off.  Ids are non-negative, so one doubles as the tick.
    tick = ids.reshape(-1)[0]
    loss, g_rows = fence.fence_call(
        jax.value_and_grad(loss_fn), (rows,), tick=tick
    )
    table1, (uniq, w_new) = lpt.sparse_apply(
        table,
        ids,
        g_rows,
        lr=lr,
        bits=cfg.bits,
        rounding=cfg.rounding,
        noise_key=noise_key,
        optimizer=cfg.optimizer,
        weight_decay=cfg.weight_decay,
        return_updated_rows=True,
        id_space=id_space,
    )
    # ---- Step 2: learn Delta on the *updated* float rows (line 4). ----
    # Re-run the forward with fake-quantized updated rows; the LSQ custom-vjp
    # routes the gradient to Delta via Eq. 7.
    safe = jnp.minimum(uniq, n - 1)
    step_b = jnp.take(table.step, safe)  # Delta_b^t
    gscale = grad_scale_factor(cfg, batch_rows=int(ids.size), dim=d_live)
    inv = lpt.dedup_ids(ids, sentinel)[1]

    def loss_wrt_step(step_vec):
        rows_q = quant.fake_quant_lsq(
            jax.lax.stop_gradient(w_new), step_vec, cfg.bits, gscale
        )
        # Re-broadcast unique rows back to per-occurrence layout for the loss.
        occ = jnp.take(rows_q, inv, axis=0).reshape(ids.shape + (d,))
        if d_live != d:
            occ = occ[..., :d_live]
        return loss_fn_step2(occ)

    # The Delta pass and update under one scope; the dedup above keeps its own.
    with jax.named_scope("alpt.step_size"):
        g_step = fence.fence_call(jax.grad(loss_wrt_step), (step_b,), tick=tick)
        new_step_b = step_b - cfg.step_lr * (
            g_step + cfg.step_weight_decay * step_b
        )
        new_step_b = jnp.maximum(new_step_b, 1e-8)  # Delta must stay positive
        delta_clamped = None
        if cfg.step_clamp is not None:
            delta_clamped = jnp.sum(new_step_b > cfg.step_clamp).astype(jnp.int32)
            new_step_b = jnp.minimum(new_step_b, cfg.step_clamp)

    # ---- Line 5: re-quantize w^{t+1} with the NEW Delta (SR). ----
    k2 = jax.random.fold_in(noise_key, 1)
    noise = quant.sr_noise(k2, w_new.shape)
    if cfg.use_kernels and cfg.rounding == "sr":
        codes_rows = ops.sr_round(w_new, new_step_b, noise, cfg.bits)
    else:
        if cfg.use_kernels:
            ops.note_fallback("sr_round", w_new.shape, "dr rounding")
        codes_rows = quant.quantize_codes(
            w_new, new_step_b, cfg.bits, cfg.rounding, noise
        )
    # ``uniq`` is sorted; its dropped slots come last (lpt.sparse_apply).
    codes = rowstore.set_rows(
        table1.codes, uniq, codes_rows, mode="drop", indices_are_sorted=True
    )
    step = table1.step.at[uniq].set(
        new_step_b, mode="drop", indices_are_sorted=True
    )
    new_table = table1._replace(codes=codes, step=step)
    aux = {
        "step_grad_norm": jnp.linalg.norm(g_step),
        "mean_step": jnp.mean(new_step_b),
    }
    if delta_clamped is not None:
        aux["delta_clamped"] = delta_clamped
    return new_table, loss, aux


class DenseWeightUpdate(NamedTuple):
    """Intermediate of the dense ALPT weight sub-step (Algorithm 1 lines 1-3),
    handed between :func:`dense_weight_update` and :func:`dense_finish` so a
    data-parallel caller can interleave gradient synchronization."""

    w_new: jax.Array  # f32 [n, d] float-updated rows
    mu_new: jax.Array
    nu_new: jax.Array
    touched: jax.Array  # bool [n]
    count: jax.Array  # int32 scalar


def dense_weight_update(
    table: lpt.LPTTable,
    grad_table: jax.Array,
    *,
    cfg: ALPTConfig,
    lr: jax.Array,
) -> DenseWeightUpdate:
    """Dense float weight update (Algorithm 1 line 2) without the write-back."""
    touched = jnp.any(grad_table != 0.0, axis=-1)
    w = lpt.dense_table(table)
    count = table.count + 1
    t = count.astype(jnp.float32)
    w_new, mu_new, nu_new = lpt._row_update(
        w, grad_table, table.mu, table.nu, t, lr, cfg.optimizer, cfg.weight_decay
    )
    return DenseWeightUpdate(
        w_new=w_new, mu_new=mu_new, nu_new=nu_new, touched=touched, count=count
    )


def dense_delta_grad(
    w_new: jax.Array,
    step_vec: jax.Array,
    loss_fn_q: Callable[[jax.Array], jax.Array],
    *,
    cfg: ALPTConfig,
    gscale: float,
) -> jax.Array:
    """Delta gradient (Algorithm 1 line 4): differentiate the fake-quant
    forward of the *updated* rows w.r.t. the step vector."""

    def loss_wrt_step(step_vec):
        table_q = quant.fake_quant_lsq(
            jax.lax.stop_gradient(w_new), step_vec, cfg.bits, gscale
        )
        return loss_fn_q(table_q)

    return jax.grad(loss_wrt_step)(step_vec)


def dense_finish(
    table: lpt.LPTTable,
    upd: DenseWeightUpdate,
    g_step: jax.Array,
    *,
    cfg: ALPTConfig,
    noise_key: jax.Array,
) -> lpt.LPTTable:
    """Delta update + SR re-quantization (Algorithm 1 line 5), touched-row
    masked so untouched rows keep codes and Delta bit-identical."""
    new_step = table.step - cfg.step_lr * (g_step + cfg.step_weight_decay * table.step)
    new_step = jnp.maximum(new_step, 1e-8)
    if cfg.step_clamp is not None:
        new_step = jnp.minimum(new_step, cfg.step_clamp)
    new_step = jnp.where(upd.touched, new_step, table.step)

    noise = quant.sr_noise(jax.random.fold_in(noise_key, 1), upd.w_new.shape)
    if cfg.use_kernels and cfg.rounding == "sr":
        # Algorithm 1 line 5 already materialized w_new for the Delta
        # gradient, so the fused piece here is the SR write-back itself
        # (fp32 in, int8 out — no intermediate rounding buffers).
        codes_new = ops.sr_round(upd.w_new, new_step, noise, cfg.bits)
    else:
        if cfg.use_kernels:
            ops.note_fallback("sr_round", upd.w_new.shape, "dr rounding")
        codes_new = quant.quantize_codes(
            upd.w_new, new_step, cfg.bits, cfg.rounding, noise
        )
    mask = upd.touched[:, None]
    codes = rowstore.where_rows(table.codes, upd.touched, codes_new)
    if table.mu.ndim == 2:
        mu = jnp.where(mask, upd.mu_new, table.mu)
        nu = jnp.where(mask, upd.nu_new, table.nu)
    else:
        mu = jnp.where(upd.touched, upd.mu_new, table.mu)
        nu = jnp.where(upd.touched, upd.nu_new, table.nu)
    return table._replace(codes=codes, step=new_step, mu=mu, nu=nu, count=upd.count)


def alpt_dense_step(
    table: lpt.LPTTable,
    grad_table: jax.Array,
    loss_fn_q: Callable[[jax.Array], jax.Array],
    *,
    cfg: ALPTConfig,
    lr: jax.Array,
    noise_key: jax.Array,
    batch_rows: int,
):
    """pjit-friendly ALPT: dense gradients + dense Delta learning.

    ``grad_table`` is the dense df/dtable from the caller's backward pass.
    ``loss_fn_q(table_fp) -> scalar`` re-evaluates the loss from a dense float
    table (used for the Delta gradient via fake-quant).  Untouched rows keep
    codes and Delta bit-identical.

    ``batch_rows`` is the paper's b — the number of table-row lookups the
    batch performed (token count for an LM) — feeding the Delta gradient
    scale g = 1/sqrt(b*d*q).  It matches the sparse path's ``ids.size``; the
    table's total row count is NOT a substitute (it over-damps the Delta
    learning rate by sqrt(V/b)).

    Composed from :func:`dense_weight_update` / :func:`dense_delta_grad` /
    :func:`dense_finish`; the data-parallel trainer calls the pieces directly
    so it can all-reduce ``grad_table`` and the Delta gradient in between.
    """
    upd = dense_weight_update(table, grad_table, cfg=cfg, lr=lr)
    gscale = grad_scale_factor(cfg, batch_rows=int(batch_rows), dim=table.dim)
    g_step = dense_delta_grad(
        upd.w_new, table.step, loss_fn_q, cfg=cfg, gscale=gscale
    )
    return dense_finish(table, upd, g_step, cfg=cfg, noise_key=noise_key)
