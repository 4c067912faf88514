"""Plain reference for the ALPT CTR cells, independent of the program.

Straight ``jax.numpy`` written from the paper (Li et al., AAAI 2023, §3.2,
Algorithm 1) and the configuration file: no kernels, no dedup, no sparse
scatter of rows, no batching tricks.  It imports nothing of the program and
takes nothing the program made.  It rebuilds the initial state from the seed
with the key schedule and distributions the configuration states:

* ``k_emb, k_dense, k_rng = split(key, 3)``;
* table: ``kw, kn = split(k_emb)``, ``w ~ N(0, init_scale^2)`` drawn over
  ``kw``, per-row step ``max(2 mean|w| / sqrt(q), 1e-8)``, codes by
  stochastic rounding against ``uniform(kn)``;
* DCN: ``split(k_dense, 2 * depth + 2 * len(mlp) + 1)``, taken in order by
  the cross vectors, the MLP kernels and the output vector;
* each step: ``rng, kd, kn = split(rng, 3)``; dropout masks from ``kd``,
  split once per MLP layer.

Stochastic rounding in the steps uses the reference's own noise, so that its
readings do not depend on how the program lays its noise out; what is
compared (losses and norms) does not need the same draws.

``dtype`` selects the precision of every array: float32 is the reference,
bfloat16 the control that a correct run must be told apart from.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

B1, B2, EPS = 0.9, 0.999, 1e-8


def run_key(seed: int) -> jax.Array:
    """The key a run starts from; the program and the reference share it."""
    return jax.random.PRNGKey(int(seed))


def code_range(bits: int) -> tuple[int, int]:
    return -(2 ** (bits - 1)), 2 ** (bits - 1) - 1


def sr_codes(w, step, bits, noise):
    lo, hi = code_range(bits)
    x = jnp.clip(w / step[:, None], lo, hi)
    fl = jnp.floor(x)
    return jnp.clip(fl + (x - fl > noise).astype(x.dtype), lo, hi)


class Weights(NamedTuple):
    codes: jax.Array  # [n, d] integer-valued, in ``dtype``
    step: jax.Array  # [n]
    dense: dict


def init_weights(key, cfg: dict, dtype=jnp.float32) -> Weights:
    """The initial table and DCN weights from the run key (see module doc)."""
    emb, model = cfg["embedding"], cfg["model"]
    n, d, bits = cfg["data"]["n_ids"], emb["d"], emb["bits"]
    k_emb, k_dense, _ = jax.random.split(key, 3)
    kw, kn = jax.random.split(k_emb)
    w = jax.random.normal(kw, (n, d), jnp.float32) * emb["init_scale"]
    q = 2 ** (bits - 1) - 1
    step = jnp.maximum(2.0 * jnp.mean(jnp.abs(w), -1) / jnp.sqrt(float(q)), 1e-8)
    codes = sr_codes(w, step, bits, jax.random.uniform(kn, (n, d), jnp.float32))
    return Weights(codes.astype(dtype), step.astype(dtype),
                   jax.tree.map(lambda a: a.astype(dtype), init_dcn(k_dense, cfg)))


def init_dcn(key, cfg: dict) -> dict:
    model = cfg["model"]
    d0 = cfg["data"]["fields"] * cfg["embedding"]["d"]
    depth, widths = model["cross_depth"], model["mlp_widths"]
    keys = jax.random.split(key, 2 * depth + 2 * len(widths) + 1)
    p = {"cross_w": [jax.random.normal(keys[i], (d0,)) / jnp.sqrt(d0) for i in range(depth)],
         "cross_b": [jnp.zeros((d0,)) for _ in range(depth)], "mlp": []}
    prev = d0
    for j, width in enumerate(widths):
        p["mlp"].append({"w": jax.random.normal(keys[depth + j], (prev, width))
                         * jnp.sqrt(2.0 / prev), "b": jnp.zeros((width,))})
        prev = width
    p["out_w"] = jax.random.normal(keys[depth + len(widths)], (d0 + prev,)) / jnp.sqrt(d0 + prev)
    p["out_b"] = jnp.zeros(())
    return p


def rounded(x):
    """``x`` rounded to its own dtype.  XLA may keep bfloat16 intermediates in
    float32 inside a fusion; a bfloat16 computation rounds each one."""
    if x.dtype == jnp.bfloat16:
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    return x


def dcn_logits(params, rows, dropout: float, key=None):
    """DCN (Wang et al. 2017): cross network beside an MLP, joined by a linear
    output.  With ``key``, inverted dropout after each MLP layer."""
    b = rows.shape[0]
    x0 = rounded(rows.reshape(b, -1))
    x = x0
    for w, bias in zip(params["cross_w"], params["cross_b"]):
        xw = rounded(x @ w)
        x = rounded(rounded(rounded(x0 * xw[:, None]) + bias[None, :]) + x)
    h = x0
    for layer in params["mlp"]:
        h = rounded(jax.nn.relu(rounded(rounded(h @ layer["w"]) + layer["b"])))
        if dropout > 0.0 and key is not None:
            key, sub = jax.random.split(key)
            keep = jax.random.bernoulli(sub, 1.0 - dropout, h.shape)
            h = jnp.where(keep, rounded(h / (1.0 - dropout)), 0.0)
    out = rounded(jnp.concatenate([x, h], axis=-1) @ params["out_w"])
    return rounded(out + params["out_b"])


def bce(logits, labels):
    return jnp.mean(jnp.maximum(logits, 0.0) - logits * labels
                    + jnp.log1p(jnp.exp(-jnp.abs(logits))))


class TrainState(NamedTuple):
    codes: jax.Array
    step: jax.Array
    mu: jax.Array
    nu: jax.Array
    dense: dict
    dmu: dict
    dnu: dict
    t: jax.Array  # completed steps
    rng: jax.Array


def init_train(key, cfg: dict, dtype=jnp.float32) -> TrainState:
    w = init_weights(key, cfg, dtype)
    zeros = lambda a: jnp.zeros_like(a)
    return TrainState(w.codes, w.step, jnp.zeros(w.codes.shape, dtype),
                      jnp.zeros(w.codes.shape, dtype), w.dense,
                      jax.tree.map(zeros, w.dense), jax.tree.map(zeros, w.dense),
                      jnp.zeros((), jnp.int32), jax.random.split(key, 3)[2])


def _adam_dir(g, m, v, t):
    # The bias corrections are scalars worked out in float32 (in bfloat16,
    # 0.999 rounds to 1 and the correction to 0); the arrays keep ``dtype``.
    c1 = (1 - B1 ** t.astype(jnp.float32)).astype(g.dtype)
    c2 = (1 - B2 ** t.astype(jnp.float32)).astype(g.dtype)
    m = B1 * m + (1 - B1) * g
    v = B2 * v + (1 - B2) * g * g
    return (m / c1) / (jnp.sqrt(v / c2) + EPS), m, v


def mean_over_replicas(parts, bits, key):
    """Mean of the replicas' arrays; with ``bits``, each replica first rounds
    its array stochastically to integer multiples of one shared step, the
    largest magnitude over all replicas over ``2^(bits-1) - 1``."""
    if bits is None or len(parts) == 1:
        return sum(parts) / len(parts)
    _, hi = code_range(bits)
    step = jnp.maximum(jnp.max(jnp.stack([jnp.max(jnp.abs(p)) for p in parts])) / hi, 1e-30)
    total = 0
    for r, p in enumerate(parts):
        u = jax.random.uniform(jax.random.fold_in(key, r), p.shape).astype(p.dtype)
        x = p / step
        fl = jnp.floor(x)
        total = total + fl + (x - fl > u).astype(p.dtype)
    return total * step / len(parts)


def train_step(st: TrainState, ids, labels, cfg: dict, noise_key, shards: int = 1,
               sync_bits=None):
    """One step of Algorithm 1 over the whole batch, the table kept dense.

    ``shards`` > 1 splits the batch over that many replicas, each with the
    same dropout key over its own rows; their gradients are averaged (with
    ``sync_bits``, through stochastic rounding to a shared step), and a row
    counts as touched where its averaged gradient is not zero, as in a
    data-parallel step over a dense table gradient.

    Returns ``(state, loss, grads)``; ``grads`` holds the first-order
    gradients as the optimizers receive them: ``dense`` (a tree), ``table``
    ([n, d], summed over a row's occurrences) and ``step`` ([n], the step
    size's gradient)."""
    emb, opt = cfg["embedding"], cfg["optimizer"]
    dropout = cfg["model"]["dropout"]
    bits, d = emb["bits"], emb["d"]
    lo, hi = code_range(bits)
    lr = opt["lr"]
    rng, kd, _ = jax.random.split(st.rng, 3)
    t = (st.t + 1).astype(st.step.dtype)
    k_sync, k_sync_step, k_round = jax.random.split(noise_key, 3)
    part_ids = jnp.split(ids, shards)
    part_labels = jnp.split(labels, shards)

    def loss_fn(rows, dense, lab):
        return bce(dcn_logits(dense, rows, dropout, kd), lab)

    def table_of(i, g):
        return jnp.zeros(w.shape, w.dtype).at[i.reshape(-1)].add(g.reshape(-1, d))

    w = st.codes * st.step[:, None]
    losses, g_denses, g_tabs = [], [], []
    for i, lab in zip(part_ids, part_labels):
        loss, (g_rows, g_dense) = jax.value_and_grad(loss_fn, (0, 1))(w[i], st.dense, lab)
        losses.append(loss)
        g_denses.append(g_dense)
        g_tabs.append(table_of(i, g_rows))
    loss = sum(losses) / shards
    leaves = [jax.tree.leaves(g) for g in g_denses]
    g_dense = jax.tree.unflatten(jax.tree.structure(g_denses[0]), [
        mean_over_replicas([lv[j] for lv in leaves], sync_bits, jax.random.fold_in(k_sync, j))
        for j in range(len(leaves[0]))])
    g_tab = mean_over_replicas(g_tabs, sync_bits, jax.random.fold_in(k_sync, len(leaves[0])))

    # Dense parameters: Adam.
    upd = jax.tree.map(lambda g, m, v: _adam_dir(g, m, v, t), g_dense, st.dmu, st.dnu)
    pick = lambda i: jax.tree.map(lambda _, u: u[i], g_dense, upd)
    new_dense = jax.tree.map(lambda p, u: p - lr * u, st.dense, pick(0))

    # Table rows (Algorithm 1, lines 1-2): Adam on the touched rows.
    if shards == 1:
        touched = jnp.zeros(w.shape[0], bool).at[ids.reshape(-1)].set(True)
    else:
        touched = jnp.any(g_tab != 0, axis=-1)
    direction, mu, nu = _adam_dir(g_tab, st.mu, st.nu, t)
    w_new = w - lr * (direction + emb["emb_weight_decay"] * w)

    # Step size (line 4): LSQ gradient of the loss at the new dense weights,
    # through a deterministic fake-quantisation of the updated rows.
    scaled = w_new / st.step[:, None]
    w_q = jnp.floor(jnp.clip(scaled, lo, hi) + 0.5) * st.step[:, None]
    d_elem = jnp.where(scaled <= lo, float(lo),
                       jnp.where(scaled >= hi, float(hi), jnp.floor(scaled + 0.5) - scaled))
    gscale = 1.0 / (ids.size * d * hi) ** 0.5
    g_steps = []
    for i, lab in zip(part_ids, part_labels):
        g_q = jax.grad(lambda r: loss_fn(r, new_dense, lab))(w_q[i])
        g_steps.append(gscale * jnp.sum(table_of(i, g_q) * d_elem, -1))
    g_step = mean_over_replicas(g_steps, sync_bits, k_sync_step)
    new_step = jnp.maximum(st.step - emb["step_lr"] * (g_step + emb["step_weight_decay"] * st.step), 1e-8)
    new_step = jnp.where(touched, new_step, st.step)

    # Line 5: stochastic rounding with the new step, touched rows only.
    noise = jax.random.uniform(k_round, w.shape).astype(w.dtype)
    codes = jnp.where(touched[:, None], sr_codes(w_new, new_step, bits, noise), st.codes)
    keep = lambda new, old: jnp.where(touched[:, None], new, old)
    state = TrainState(codes, new_step, keep(mu, st.mu), keep(nu, st.nu), new_dense,
                       pick(1), pick(2), st.t + 1, rng)
    return state, loss, {"dense": g_dense, "table": g_tab, "step": g_step}
