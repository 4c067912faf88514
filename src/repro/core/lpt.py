"""Low-precision training (LPT) of embedding tables (paper §2.3, Eq. 8).

The table lives as int8 codes plus a per-row (feature-wise) step size; there is
NO full-precision master copy.  Each step de-quantizes only the rows a batch
touches, applies the optimizer update in float, and re-quantizes with SR or DR:

    w_hat^{t+1} = Q( w_hat^t - eta * grad f(w_hat^t) )            (Eq. 8)

Two execution paths share identical semantics:

* ``sparse`` — CTR-style: ids are de-duplicated under jit (`jnp.unique(size=)`),
  per-unique-row gradients are segment-summed, and only those rows are updated
  and re-quantized.  This is the paper-faithful path: the de-quantized floats
  for a batch are "negligible compared to the embedding tables" (§2.3).
* ``dense`` — LM/pjit-style: the table gradient arrives dense (XLA scatter-add
  from the token gather); rows whose gradient is exactly zero keep their old
  codes bit-for-bit, so untouched rows never drift.  This path shards cleanly
  over a vocab-partitioned mesh axis.

Row optimizers: 'sgd' (Eq. 8 literally), 'adam' (paper §4.1: Adam with
decoupled weight decay), 'adagrad' (industry-standard per-row accumulator,
cheapest state).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import codestore, quant
from repro.kernels import ops
from repro.storage import base as rowstore
from repro.storage.tiered import TieredCodes


class LPTTable(NamedTuple):
    """Quantized embedding table + per-row step + row optimizer state."""

    # A CodeStore (packed uint8 at bits<=4, int8 otherwise) or a raw int8
    # array for hand-built tables; `.shape` is the logical [n, d] either way.
    codes: "codestore.CodeStore | jax.Array"
    step: jax.Array  # f32  [n]   (feature-wise Delta; ALPT learns this)
    # Row-optimizer slots (zeros-shaped () when unused):
    mu: jax.Array  # f32 [n, d] (adam) | [n] zeros (adagrad/sgd)
    nu: jax.Array  # f32 [n, d] (adam) | [n] (adagrad accumulator) | [n] zeros
    count: jax.Array  # int32 scalar — global step for Adam bias correction

    @property
    def n_rows(self) -> int:
        return self.codes.shape[0]

    @property
    def dim(self) -> int:
        return self.codes.shape[1]


def init_table(
    key: jax.Array,
    n: int,
    d: int,
    bits: int,
    *,
    init_scale: float = 1e-2,
    mean: float = 0.0,
    step_size: float | None = None,
    clip_value: float | None = None,
    optimizer: str = "adam",
    use_kernels: bool = False,
    packed: bool | None = None,
) -> LPTTable:
    """Initialize weights ~ N(mean, init_scale^2), choose Delta, quantize.

    Vanilla LPT (Xu et al. 2021) fixes Delta from a tuned clip value:
    Delta = clip / 2^{m-1}.  If neither ``step_size`` nor ``clip_value`` is
    given, Delta is set per-row LSQ-style from the init (the ALPT default).
    ``mean`` shifts the init (composed tables start multiplicative factors
    near 1); the paper's tables use the zero-mean default.

    ``packed`` selects the code container (see :mod:`repro.core.codestore`):
    None/True packs sub-byte widths (bits in {2, 4}) into uint8; False keeps
    one byte per code.  Packing is a storage-layout choice only — training is
    bitwise identical either way.
    """
    kw, kn = jax.random.split(key)
    w = jax.random.normal(kw, (n, d), jnp.float32) * init_scale
    if mean:
        w = mean + w
    if step_size is not None:
        step = jnp.full((n,), step_size, jnp.float32)
    elif clip_value is not None:
        step = jnp.full((n,), clip_value / (2 ** (bits - 1)), jnp.float32)
    else:
        step = quant.init_step_size(w, bits, per_row=True)
    noise = quant.sr_noise(kn, w.shape)
    if use_kernels:
        codes = ops.sr_round(w, step, noise, bits)
    else:
        codes = quant.quantize_codes(w, step, bits, "sr", noise)
    codes = codestore.CodeStore.from_codes(codes, bits, packed=packed)
    if optimizer == "adam":
        mu = jnp.zeros((n, d), jnp.float32)
        nu = jnp.zeros((n, d), jnp.float32)
    elif optimizer == "adagrad":
        mu = jnp.zeros((n,), jnp.float32)
        nu = jnp.zeros((n,), jnp.float32)
    elif optimizer == "sgd":
        mu = jnp.zeros((n,), jnp.float32)
        nu = jnp.zeros((n,), jnp.float32)
    else:
        raise ValueError(f"unknown row optimizer {optimizer!r}")
    return LPTTable(codes=codes, step=step, mu=mu, nu=nu, count=jnp.zeros((), jnp.int32))


def lookup(
    table: LPTTable,
    ids: jax.Array,
    *,
    use_kernels: bool = False,
    out_dim: int | None = None,
) -> jax.Array:
    """De-quantize the rows for ``ids`` (any leading shape) -> f32 [..., d].

    ``use_kernels`` routes through the fused gather+dequantize Pallas kernel
    (``ops.dequant_gather``: int8 rows leave HBM, the fp table never
    materializes); the jnp path is bitwise-identical.  ``out_dim`` slices
    padded tables back to the live embedding width (``pad_to_tiles``).
    """
    if use_kernels:
        flat = ids.reshape(-1)
        rows = ops.dequant_gather(table.codes, table.step, flat)
        rows = rows.reshape(ids.shape + (table.dim,))
    else:
        codes = rowstore.take_rows(table.codes, ids)
        step = jnp.take(table.step, ids, axis=0)
        rows = quant.dequantize(codes, step)
    if out_dim is not None and out_dim != rows.shape[-1]:
        rows = rows[..., :out_dim]
    return rows


def dense_table(table: LPTTable) -> jax.Array:
    """Materialize the full de-quantized table (dense/pjit path)."""
    return quant.dequantize(rowstore.logical_codes(table.codes), table.step)


# ---------------------------------------------------------------------------
# Row-update rules (shared by the sparse and dense paths).
# ---------------------------------------------------------------------------


def _opt_direction(
    g: jax.Array,  # f32 [k, d] summed row gradients
    mu: jax.Array,
    nu: jax.Array,
    t: jax.Array,  # scalar f32, 1-indexed adam step
    optimizer: str,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
):
    """Weight-independent part of the row update: (direction, mu_new, nu_new).

    The fused kernels consume the direction and fold the decoupled weight
    decay + subtraction + re-quantization into one VMEM pass.
    """
    g = g.astype(jnp.float32)
    if optimizer == "adam":
        mu = b1 * mu + (1.0 - b1) * g
        nu = b2 * nu + (1.0 - b2) * jnp.square(g)
        upd = (mu / (1.0 - b1**t)) / (jnp.sqrt(nu / (1.0 - b2**t)) + eps)
    elif optimizer == "adagrad":
        nu = nu + jnp.mean(jnp.square(g), axis=-1)
        upd = g / (jnp.sqrt(nu)[..., None] + eps)
    else:  # sgd
        upd = g
    return upd, mu, nu


def _row_update(
    w: jax.Array,  # f32 [k, d] current de-quantized rows
    g: jax.Array,  # f32 [k, d] summed row gradients
    mu: jax.Array,
    nu: jax.Array,
    t: jax.Array,  # scalar f32, 1-indexed adam step
    lr: jax.Array,
    optimizer: str,
    weight_decay: float,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
):
    """Returns (w_new, mu_new, nu_new)."""
    upd, mu, nu = _opt_direction(g, mu, nu, t, optimizer, b1, b2, eps)
    if weight_decay:
        upd = upd + weight_decay * w
    return w - lr * upd, mu, nu


def dedup_ids(ids: jax.Array, n_rows: int):
    """jit-stable de-duplication: returns (unique_ids [K], inverse [K_in]).

    ``unique_ids`` is padded with ``n_rows`` (an out-of-range sentinel row);
    scatters use mode='drop' so padding is inert.  Its ops carry the
    ``lpt.dedup`` scope in their metadata, where a device trace finds them.
    """
    with jax.named_scope("lpt.dedup"):
        flat = ids.reshape(-1)
        uniq, inv = jnp.unique(
            flat, return_inverse=True, size=flat.shape[0], fill_value=n_rows
        )
        return uniq, inv.reshape(-1)


def sparse_apply(
    table: LPTTable,
    ids: jax.Array,  # int32 [...], the ids that were looked up
    grad_rows: jax.Array,  # f32 [..., d], cotangent per lookup occurrence
    *,
    lr: jax.Array,
    bits: int,
    rounding: str = "sr",
    noise_key: jax.Array | None = None,
    optimizer: str = "adam",
    weight_decay: float = 0.0,
    new_step: jax.Array | None = None,  # ALPT passes the freshly learned Delta_b
    return_updated_rows: bool = False,
    id_space: int | None = None,  # sentinel for dedup (< n_rows on padded tables)
):
    """Paper-faithful LPT update: only rows present in ``ids`` change.

    Duplicate ids in the batch have their gradients summed (the same semantics
    autodiff would give a dense table scatter-add).  The write-back is one
    batched row gather, the row update over ``[K, d]`` and one in-place row
    scatter per leaf, for every table layout and row optimizer; its ops
    carry the ``lpt.row_update`` scope.

    ``id_space`` is the logical id range (``spec.n``, smaller than
    ``n_rows`` on ``pad_to_tiles`` tables).  The dedup slots that hold no
    batch id (ids at or past ``id_space``) point past the table, so all of
    their writes drop; ``return_updated_rows`` returns those row indices.
    """
    n = table.n_rows
    d = table.dim
    sentinel = n if id_space is None else id_space
    flat_ids = ids.reshape(-1)
    flat_g = grad_rows.reshape(-1, grad_rows.shape[-1]).astype(jnp.float32)
    if flat_g.shape[-1] != d:
        # Live-width cotangents against a pad_to_tiles table: the tail
        # columns were never looked up, so their gradient is exactly zero.
        flat_g = jnp.pad(flat_g, ((0, 0), (0, d - flat_g.shape[-1])))
    uniq, inv = dedup_ids(flat_ids, sentinel)
    k = uniq.shape[0]
    with jax.named_scope("lpt.dedup"):
        g_sum = jnp.zeros((k, d), jnp.float32).at[inv].add(flat_g)
    count = table.count + 1
    t = count.astype(jnp.float32)

    if rounding == "sr" and noise_key is None:
        raise ValueError("SR requires noise_key")
    # Gather -> Adam + requantize -> scatter over the K deduplicated rows.
    # ``rows`` is sorted with the dropped slots last, so every scatter may
    # say so; the dropped slots repeat, so the indices are not unique.
    with jax.named_scope("lpt.row_update"):
        rows = jnp.where(uniq < sentinel, uniq, n)
        safe = jnp.minimum(rows, n - 1)
        step_rows = jnp.take(table.step, safe)
        w = quant.dequantize(rowstore.take_rows(table.codes, safe), step_rows)
        # Slot layout is optimizer-dependent ([k, d] adam / [k] otherwise)
        # but the gather is row-indexed either way.
        mu = jnp.take(table.mu, safe, axis=0)
        nu = jnp.take(table.nu, safe, axis=0)
        w_new, mu_new, nu_new = _row_update(
            w, g_sum, mu, nu, t, lr, optimizer, weight_decay
        )
        if new_step is not None:
            step_rows = new_step
        noise = (
            quant.sr_noise(noise_key, w_new.shape) if rounding == "sr" else None
        )
        new_codes_rows = quant.quantize_codes(
            w_new, step_rows, bits, rounding, noise
        )
        scatter = dict(mode="drop", indices_are_sorted=True)
        codes = rowstore.set_rows(table.codes, rows, new_codes_rows, **scatter)
        step = table.step
        if new_step is not None:
            step = step.at[rows].set(new_step, **scatter)
        mu_t = table.mu.at[rows].set(mu_new, **scatter)
        nu_t = table.nu.at[rows].set(nu_new, **scatter)
    new_table = LPTTable(codes=codes, step=step, mu=mu_t, nu=nu_t, count=count)
    if return_updated_rows:
        return new_table, (rows, w_new)
    return new_table


def dense_apply(
    table: LPTTable,
    grad_table: jax.Array,  # f32 [n, d] dense gradient (zero on untouched rows)
    *,
    lr: jax.Array,
    bits: int,
    rounding: str = "sr",
    noise_key: jax.Array | None = None,
    optimizer: str = "adam",
    weight_decay: float = 0.0,
    new_step: jax.Array | None = None,
    use_kernels: bool = False,
) -> LPTTable:
    """pjit-friendly LPT update: dense compute, touched-row masking.

    A row is "touched" iff any element of its gradient is nonzero; untouched
    rows keep their codes/slots bit-identical (exact sparse semantics, but the
    computation is dense and therefore shards trivially over the vocab axis).

    ``use_kernels`` routes the write-back through the fused
    ``ops.lpt_update`` kernel — the optimizer *direction* is formed in jnp
    (it needs only the gradient and the Adam/Adagrad slots), then one VMEM
    pass de-quantizes, applies the decayed step and SR-requantizes without
    ever materializing the fp32 table in HBM (Eq. 8 in one kernel, including
    ALPT's ``new_step`` requantize-with-learned-Delta).
    """
    touched = jnp.any(grad_table != 0.0, axis=-1)  # [n]
    count = table.count + 1
    t = count.astype(jnp.float32)
    step = table.step if new_step is None else new_step
    kernel_ok = use_kernels and rounding == "sr"
    if use_kernels and rounding != "sr":
        ops.note_fallback("lpt_update", table.codes.shape, "dr rounding")
    if kernel_ok and isinstance(table.codes, TieredCodes):
        # The fused write-back targets the backing container; cached rows
        # must take their new codes through the hot tier's where-merge.
        ops.note_fallback(
            "lpt_update", table.codes.shape, "tiered hot-row cache"
        )
        kernel_ok = False
    if kernel_ok:
        if noise_key is None:
            raise ValueError("SR requires noise_key")
        upd, mu_new, nu_new = _opt_direction(
            grad_table, table.mu, table.nu, t, optimizer
        )
        noise = quant.sr_noise(noise_key, grad_table.shape)
        codes_new = ops.lpt_update(
            table.codes, table.step, upd, noise, lr, bits,
            new_step=None if new_step is None else step,
            weight_decay=weight_decay,
        )
    else:
        w = dense_table(table)
        w_new, mu_new, nu_new = _row_update(
            w, grad_table, table.mu, table.nu, t, lr, optimizer, weight_decay
        )
        if rounding == "sr":
            if noise_key is None:
                raise ValueError("SR requires noise_key")
            noise = quant.sr_noise(noise_key, w_new.shape)
        else:
            noise = None
        codes_new = quant.quantize_codes(w_new, step, bits, rounding, noise)
    mask = touched[:, None]
    codes = rowstore.where_rows(table.codes, touched, codes_new)
    if table.mu.ndim == 2:
        mu = jnp.where(mask, mu_new, table.mu)
        nu = jnp.where(mask, nu_new, table.nu)
    else:
        mu = jnp.where(touched, mu_new, table.mu)
        nu = jnp.where(touched, nu_new, table.nu)
    step_out = jnp.where(touched, step, table.step) if new_step is not None else table.step
    return LPTTable(codes=codes, step=step_out, mu=mu, nu=nu, count=count)


def memory_bytes(table: LPTTable, bits: int, count_optimizer: bool = False) -> int:
    """Training-memory accounting (codes + Delta), storage-actual.

    Reports the *container's* resident bytes — ``ceil(d * bits / 8)`` per row
    for a packed CodeStore, one byte per code otherwise — so the paper Table 1
    compression figures reflect what is actually allocated, not an idealized
    bits/8 that an int8-per-code layout never achieved.
    """
    n, _ = table.codes.shape
    code_bytes = rowstore.resident_bytes_of(table.codes)
    step_bytes = n * 4
    total = code_bytes + step_bytes
    if count_optimizer:
        total += table.mu.size * 4 + table.nu.size * 4
    return int(total)
