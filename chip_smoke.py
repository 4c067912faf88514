#!/usr/bin/env python3
"""Chip smoke test: the paper's ALPT CTR trainer and scorer on one TPU.

Drives the main path once, at Criteo width, through the objects a user
calls.  The configuration is ``configs/dcn_ctr.py:criteo_setup(method="alpt",
scale=1.0)``: 39 fields, about 1.09M embedding rows of d=16, DCN with cross
depth 5 and an MLP of 5x1000, on seeded ``CTRSynthetic`` data, with the
Pallas kernels on and the table padded to kernel tiles.

Phases, in one process:

1. ``CTRTrainer.train_step``, ALPT bits=8 (int8 codes): 5 steps at batch 4096.
2. The same at bits=4 on the packed container: 3 steps.
3. On each trained table at full size: the row lookup kernel
   (``dequant_gather``) against its jnp reference, and the row write-back
   (``lpt.sparse_apply``) leaving every row outside the batch bit for bit.
4. ``CTREngine.from_state`` scores 128 requests; each probability is checked
   against a plain f32 reference (host-dequantized rows through
   ``models/ctr.py:logits_from_rows`` at ``precision="highest"``).
5. The kernel dispatch report: each op's route and call count; any fallback
   to the jnp reference fails the run.

``--four-chips`` runs only the data-parallel path instead: ``make_ctr_dp_step``
with ``sync_bits=8`` over a 4-device ``data`` mesh, against
``make_ctr_microbatch_step`` with 4 shards on one device.

Usage, from the root of the repository on a TPU host::

    python chip_smoke.py [--four-chips]

It fails at once where JAX finds no TPU.  Every failed check exits non-zero;
the last line of standard output is then never the result line
``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent

#: Size of the run: the dataset scale (1.0 = the full Criteo id space) and
#: the training batch.
SCALE = 1.0
BATCH = 4096
SEED = 0
STEPS = {8: 5, 4: 3}
DP_STEPS = 3
REQUESTS = 128
ENGINE_BATCH = 64

#: Engine probability vs. the f32 reference.  The engine runs the dense
#: layers at the chip's default f32 matmul precision (one bf16 pass per
#: product), the reference at "highest"; over DCN's 5 cross and 6 dense
#: layers that moves a probability by well under this.
PROB_BOUND = 5e-3
#: --four-chips: DP vs. microbatched losses, and the share of code bytes
#: allowed to differ, should the chip not hold the two bitwise-equal.
DP_LOSS_ATOL = 1e-4
DP_CODE_FRACTION = 1e-3


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {msg}")


def require_tpu():
    import jax

    backend = jax.default_backend()
    check(backend == "tpu", f"needs a TPU, JAX found backend {backend!r}")
    devs = jax.devices()
    print(f"jax {jax.__version__}  device_kind={devs[0].device_kind}  "
          f"count={len(devs)}", flush=True)
    return devs


def build(bits: int):
    from repro.configs.dcn_ctr import criteo_setup
    from repro.training.ctr_trainer import TrainerConfig

    data_cfg, spec, dcn = criteo_setup(method="alpt", bits=bits, scale=SCALE)
    spec = dataclasses.replace(spec, use_kernels=True, pad_to_tiles=True)
    return data_cfg, TrainerConfig(spec=spec, model="dcn", dcn=dcn, seed=SEED)


def train(bits: int, data, steps: int):
    """``steps`` trainer steps; returns (trainer config, state, last ids)."""
    import jax

    from repro.training.ctr_trainer import CTRTrainer

    _, cfg = build(bits)
    trainer = CTRTrainer(cfg)
    state = trainer.init_state()
    codes = state.emb_state.codes
    print(f"[train bits={bits}] rows={cfg.spec.n_padded} d={cfg.spec.d_padded} "
          f"container={codes.data.dtype}{tuple(codes.data.shape)} "
          f"packed={codes.packed}", flush=True)
    check(codes.packed == (bits == 4), f"bits={bits} container layout")
    for i in range(steps):
        ids, labels = data.batch("train", i, BATCH)
        t0 = time.perf_counter()
        state, m = trainer.train_step(state, ids, labels)
        loss = float(jax.block_until_ready(m["loss"]))
        if i == 0:
            print(f"[train bits={bits}] first step, compile included: "
                  f"{time.perf_counter() - t0:.1f} s", flush=True)
        print(f"[train bits={bits}] step {i} loss {loss:.6f}", flush=True)
        check(math.isfinite(loss), f"bits={bits} step {i} loss {loss}")
    return cfg, state, ids


def kernel_parity(bits: int, cfg, state, ids) -> None:
    """The lookup kernel vs. its jnp reference, and the row write-back's
    untouched rows, on the trained table."""
    import jax
    import jax.numpy as jnp

    from repro.core import lpt
    from repro.kernels import ops
    from repro.storage import base as rowstore

    table = state.emb_state
    n, d = cfg.spec.n, cfg.spec.d_padded
    flat = jnp.asarray(ids.reshape(-1))
    got = ops.dequant_gather(table.codes, table.step, flat)
    ref = ops.dequant_gather(table.codes, table.step, flat, use_kernel=False)
    check(bool(jnp.array_equal(got, ref)),
          f"bits={bits} dequant_gather differs from its reference")
    print(f"[kernels bits={bits}] dequant_gather: {flat.size} ids, bitwise "
          "equal to the jnp reference", flush=True)

    # The trainer's own write-back on the batch's ids.  Every other row,
    # the scratch row and tile padding past the id space among them, keeps
    # its bits.
    kg, kn = jax.random.split(jax.random.PRNGKey(SEED + bits))
    g = jax.random.normal(kg, (flat.size, d), jnp.float32) * 0.1
    out = jax.jit(lambda t, i, g, k: lpt.sparse_apply(
        t, i, g, lr=jnp.float32(1e-3), bits=bits, noise_key=k, id_space=n,
    ))(table, flat, g, kn)
    touched = np.zeros(table.step.shape[0], bool)
    touched[np.asarray(ids).reshape(-1)] = True
    for name, before, after in (
        ("codes", rowstore.logical_codes(table.codes),
         rowstore.logical_codes(out.codes)),
        ("step", table.step, out.step), ("mu", table.mu, out.mu),
        ("nu", table.nu, out.nu),
    ):
        check(np.array_equal(np.asarray(after)[~touched],
                             np.asarray(before)[~touched]),
              f"bits={bits} sparse_apply changed untouched {name}")
    moved = np.asarray(out.mu)[touched] != np.asarray(table.mu)[touched]
    print(f"[rows bits={bits}] sparse_apply: {int(touched.sum())} touched "
          f"rows of {touched.size}; untouched codes, step, mu, nu bit for bit; "
          f"{float(moved.mean()):.4f} of touched mu entries moved", flush=True)
    check(bool(moved.any()), f"bits={bits} sparse_apply moved no touched row")


def engine_check(cfg, state, data) -> None:
    import jax
    import jax.numpy as jnp

    from repro.models import ctr as ctr_models
    from repro.serving.ctr import CTREngine, CTRRequest
    from repro.storage import base as rowstore

    engine = CTREngine.from_state(state, cfg, batch=ENGINE_BATCH)
    ids, _ = data.batch("test", 0, REQUESTS)
    rids = [engine.submit(CTRRequest(ids=row)) for row in ids]
    t0 = time.perf_counter()
    out = engine.run()
    dt = time.perf_counter() - t0
    check(len(out) == REQUESTS, f"engine finished {len(out)} of {REQUESTS}")
    prob = np.array([out[r]["prob"] for r in rids], np.float32)
    check(bool(np.isfinite(prob).all()), "engine probabilities not finite")

    # Plain f32 reference: rows de-quantized on the host, dense layers at
    # full f32 precision.
    table = state.emb_state
    codes = np.asarray(rowstore.logical_codes(table.codes))
    step = np.asarray(table.step)
    d = cfg.spec.d
    rows = (codes[ids].astype(np.float32) * step[ids][..., None])[..., :d]

    @jax.jit
    def reference(dense, rows):
        return jax.nn.sigmoid(ctr_models.logits_from_rows(
            dense, rows, cfg.dcn, model=cfg.model))

    with jax.default_matmul_precision("highest"):
        p_ref = np.asarray(reference(state.dense_params, jnp.asarray(rows)))
        p_zero = np.asarray(reference(state.dense_params,
                                      jnp.zeros_like(jnp.asarray(rows))))
    err = float(np.max(np.abs(prob - p_ref)))
    print(f"[engine] {REQUESTS} requests in waves of {ENGINE_BATCH}, "
          f"{dt:.2f} s (compile included); fallbacks: "
          f"{engine.fallback_report()['total_fallbacks']}", flush=True)
    print(f"[engine] max |prob - f32 reference| = {err:.3e}  "
          f"(bound {PROB_BOUND:.0e}); rows move the reference by up to "
          f"{float(np.max(np.abs(p_ref - p_zero))):.3e}", flush=True)
    check(err <= PROB_BOUND, f"engine scores off the reference by {err}")


def dispatch_report() -> None:
    from repro.kernels import ops

    check(not ops._default_interpret(), "Pallas interpret mode on the chip")
    stats = ops.fallback_stats()
    print("[dispatch] " + json.dumps(stats, sort_keys=True), flush=True)
    for op, calls in sorted(stats["kernel_calls"].items()):
        print(f"[dispatch] {op}: route=pallas (compiled for the chip), "
              f"traced calls={calls}", flush=True)
    check(stats["total_fallbacks"] == 0,
          f"{stats['total_fallbacks']} kernel fallbacks")
    for op in ("dequant_gather", "sr_round"):
        check(stats["kernel_calls"].get(op, 0) > 0, f"{op} never dispatched")


def four_chips(devs) -> None:
    """DP training over a 4-chip ``data`` mesh vs. its 1-chip microbatched
    twin (the README holds them bitwise-equal)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.data.ctr_synth import CTRSynthetic
    from repro.launch.mesh import make_host_mesh
    from repro.storage import base as rowstore
    from repro.training import data_parallel as dpm
    from repro.training.ctr_trainer import CTRTrainer

    check(len(devs) == 4, f"--four-chips needs 4 devices, found {len(devs)}")
    data_cfg, cfg = build(8)
    data = CTRSynthetic(data_cfg)
    trainer = CTRTrainer(cfg)
    mesh = make_host_mesh(data=4, model=1)
    dpc = dpm.DPConfig(sync_bits=8)
    dp_step = dpm.make_ctr_dp_step(trainer, mesh, dpc)
    mb_step = dpm.make_ctr_microbatch_step(trainer, 4, dpc)
    replicated = NamedSharding(mesh, P())
    sharded = NamedSharding(mesh, P("data"))
    s_dp = jax.device_put(trainer.init_state(), replicated)
    s_mb = jax.device_put(trainer.init_state(), devs[0])
    worst = 0.0
    for i in range(DP_STEPS):
        ids, labels = data.batch("train", i, BATCH)
        t0 = time.perf_counter()
        s_dp, m_dp = dp_step(s_dp, jax.device_put(ids, sharded),
                             jax.device_put(labels, sharded))
        l_dp = float(m_dp["loss"])
        t1 = time.perf_counter()
        s_mb, m_mb = mb_step(s_mb, jax.device_put(ids, devs[0]),
                             jax.device_put(labels, devs[0]))
        l_mb = float(m_mb["loss"])
        t2 = time.perf_counter()
        if i == 0:
            print(f"[dp] first step, compile included: 4-chip {t1 - t0:.1f} s, "
                  f"1-chip microbatched {t2 - t1:.1f} s", flush=True)
        print(f"[dp] step {i} loss 4-chip {l_dp:.8f}  1-chip microbatched "
              f"{l_mb:.8f}  diff {abs(l_dp - l_mb):.3e}", flush=True)
        check(math.isfinite(l_dp) and math.isfinite(l_mb), "non-finite loss")
        worst = max(worst, abs(l_dp - l_mb))
    leaves_dp = [np.asarray(a) for a in jax.tree.leaves(s_dp)]
    leaves_mb = [np.asarray(b) for b in jax.tree.leaves(s_mb)]
    equal = sum(np.array_equal(a, b) for a, b in zip(leaves_dp, leaves_mb))
    c_dp = np.asarray(rowstore.logical_codes(s_dp.emb_state.codes))
    c_mb = np.asarray(rowstore.logical_codes(s_mb.emb_state.codes))
    code_frac = float((c_dp != c_mb).mean())
    max_abs = max(float(np.max(np.abs(a.astype(np.float64) - b)))
                  for a, b in zip(leaves_dp, leaves_mb))
    print(f"[dp] after {DP_STEPS} steps: {equal}/{len(leaves_dp)} state leaves "
          f"bitwise equal; codes differing {code_frac:.3e} (bound "
          f"{DP_CODE_FRACTION:.0e}); max |leaf diff| {max_abs:.3e}; max loss "
          f"diff {worst:.3e} (bound {DP_LOSS_ATOL:.0e})", flush=True)
    print(f"[dp] bitwise equal: {equal == len(leaves_dp)}", flush=True)
    check(worst <= DP_LOSS_ATOL, f"DP loss off the microbatched twin by {worst}")
    check(code_frac <= DP_CODE_FRACTION,
          f"DP codes off the microbatched twin: {code_frac}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip data-parallel comparison")
    args = ap.parse_args(argv)
    devs = require_tpu()
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch import compile_cache

    print(f"compile cache: {compile_cache.setup()}", flush=True)
    if args.four_chips:
        four_chips(devs)
    else:
        from repro.data.ctr_synth import CTRSynthetic

        data_cfg, _ = build(8)
        t0 = time.perf_counter()
        data = CTRSynthetic(data_cfg)
        print(f"[data] {data_cfg.name}: {data_cfg.n_fields} fields, "
              f"{data_cfg.n_features} ids ({time.perf_counter() - t0:.1f} s)",
              flush=True)
        cfg8, state8, ids8 = train(8, data, STEPS[8])
        kernel_parity(8, cfg8, state8, ids8)
        cfg4, state4, ids4 = train(4, data, STEPS[4])
        kernel_parity(4, cfg4, state4, ids4)
        del state4
        engine_check(cfg8, state8, data)
        dispatch_report()
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
