"""Production training driver: preemption-safe, resumable, straggler-aware.

Usage (single host, reduced config):
  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m --smoke \
      --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

Fault-tolerance contract (DESIGN.md §6):
  * SIGTERM/SIGINT -> finish the in-flight step, checkpoint, exit(75) so the
    scheduler requeues the job.
  * Restart resumes from the latest committed checkpoint; the data pipeline
    is indexed by step, so the replay is exact (no data skew across restarts).
  * A per-step wall-time EWMA flags stragglers (> straggler_factor x EWMA);
    on a real pod this feeds the controller's replace-node decision — here it
    is logged and counted.
  * Elastic restart: --mesh-data/--mesh-model may differ from the run that
    wrote the checkpoint; restore re-shards (checkpoint/manager.py).
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import functools
import json
import signal
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs, faults, methods
from repro.checkpoint import CheckpointManager
from repro.checkpoint.manager import (
    check_embedding_manifest,
    config_hash,
    embedding_manifest,
)
from repro.data.lm_synth import LMTokenStream
from repro.dist import context as dist_ctx
from repro.dist import sharding
from repro.kernels import ops as kernel_ops
from repro.launch import compile_cache
from repro.launch.mesh import make_host_mesh
from repro.obs import counters as obs_counters
from repro.obs.stats import StreamingQuantiles
from repro.obs.trace import tracer
from repro.training import data_parallel, lm_trainer

# Per-host straggler accounting: ticked whenever the watchdog flags a step
# (> factor x EWMA); read back in end-of-run summaries and obs snapshots.
_MET_STRAGGLERS = obs_counters.registry().counter(
    "train.straggler_warnings", "steps flagged slow by the watchdog"
)


class GracefulShutdown:
    """Latches SIGTERM/SIGINT; the loop checkpoints and exits cleanly."""

    def __init__(self):
        self.requested = False
        for sig in (signal.SIGTERM, signal.SIGINT):
            signal.signal(sig, self._handler)

    def _handler(self, signum, frame):
        self.requested = True


class StragglerWatchdog:
    def __init__(self, factor: float = 2.5, warmup: int = 5):
        self.factor = factor
        self.warmup = warmup
        self.ewma = None
        self.n = 0
        self.flagged = 0

    def observe(self, dt: float) -> bool:
        self.n += 1
        if self.ewma is None:
            self.ewma = dt
            return False
        slow = self.n > self.warmup and dt > self.factor * self.ewma
        if slow:
            self.flagged += 1
            _MET_STRAGGLERS.inc()
            tracer().instant("train.straggler", step=self.n, dt_ms=dt * 1e3)
        # Slow steps don't poison the EWMA.
        self.ewma = 0.9 * self.ewma + 0.1 * min(dt, 2 * self.ewma)
        return slow


def _run_ctr(args) -> int:
    """CTR training loop (sparse integer-table path) with optional tiered
    storage: ``--cache-rows`` wraps every cacheable storage slot in a device
    hot-row cache with dirty-row write-back — training metrics are
    bitwise-identical to the uncached run (tests/test_storage.py).

    The LM path below stays cache-free on purpose: its dense update touches
    every table row each step, so a hot-row cache would be permanently dirty.
    """
    from repro.launch.serve import CTR_DEMO_DATA, CTR_ZIPF_DATA
    from repro.data.ctr_synth import CTRSynthetic
    from repro.models.ctr import DCNConfig
    from repro.training.ctr_trainer import CTRTrainer, TrainerConfig

    data_cfg = CTR_ZIPF_DATA if args.zipf else CTR_DEMO_DATA
    data = CTRSynthetic(data_cfg)
    spec = methods.EmbeddingSpec(
        method=args.embedding_method or "alpt", n=data_cfg.n_features, d=32,
        bits=8, init_scale=0.05, use_kernels=not args.no_kernels,
    )
    trainer = CTRTrainer(TrainerConfig(
        spec=spec, model="dcn", lr=args.lr,
        dcn=DCNConfig(n_fields=data_cfg.n_fields, emb_dim=32,
                      cross_depth=2, mlp_widths=(64, 32)),
        cache_rows=args.cache_rows,
        guard=args.guard,
    ))
    state = trainer.init_state(jax.random.PRNGKey(0))
    shutdown = GracefulShutdown()

    start_step = 0
    ckpt = None
    if args.ckpt_dir:
        ckpt = CheckpointManager(
            args.ckpt_dir, keep=3, save_every=args.ckpt_every
        )
        if ckpt.latest_step() is not None:
            # Checkpoints hold the exported (cache-off-equivalent) state;
            # restore into that structure, then re-wrap the caches cold.
            restored, manifest = ckpt.restore(trainer.export_state(state))
            state = trainer.import_state(restored)
            start_step = manifest["step"]
            print(f"[train] ctr resumed from step {start_step}")

    def save(step: int, *, force: bool = False) -> None:
        if ckpt:
            ckpt.maybe_save(trainer.export_state(state), step, force=force)

    losses = []
    step_times = StreamingQuantiles()
    for step in range(start_step, args.steps):
        ids, labels = data.batch("train", step, args.batch)
        t0 = time.time()
        state, metrics = trainer.train_step(state, ids, labels)
        losses.append(float(metrics["loss"]))  # blocks; also the step barrier
        step_times.add((time.time() - t0) * 1e6)
        if (step + 1) % args.log_every == 0:
            print(f"[train] ctr step {step+1} loss {losses[-1]:.4f}")
        save(step + 1)
        if faults.fires("train.preempt", step + 1):
            print(f"[train] injected preemption at step {step+1}")
            shutdown.requested = True
        if shutdown.requested:
            save(step + 1, force=True)
            print(f"[train] preempted at step {step+1}; checkpointed; "
                  f"exiting 75 for requeue")
            return 75
    save(args.steps, force=True)
    summary = {
        "first_loss": losses[0] if losses else None,
        "final_loss": losses[-1] if losses else None,
        "steps": len(losses),
        "step_time_us": step_times.to_json(),
    }
    for stats in trainer.cache_stats():
        print(f"[train] hot tier '{stats['name']}': hit rate "
              f"{stats['hit_rate']:.3f}, {stats['evictions']} evictions, "
              f"{stats['writebacks']} write-backs, "
              f"{stats['writeback_retries']} write-back retries, "
              f"{stats['admission_oom']} admission refusals")
    if trainer.guard_stats is not None:
        trainer.guard_stats.publish()
        g = trainer.guard_stats.to_json()
        summary["guard"] = g
        print(f"[train] guard: {g['skipped']} skipped steps "
              f"({g['nonfinite_fired']} injected non-finite, "
              f"{g['delta_fired']} injected Delta blowups, "
              f"{g['delta_clamped']} Delta rows clamped)")
    if ckpt and ckpt.corrupt_steps:
        summary["corrupt_checkpoints"] = ckpt.corrupt_steps
        print(f"[train] WARNING: refused corrupted checkpoint step(s) "
              f"{ckpt.corrupt_steps} on restore")
    if not args.no_kernels:
        stats = kernel_ops.fallback_stats()
        summary["kernel_fallbacks"] = stats["total_fallbacks"]
        for fb in stats["fallbacks"]:
            print(f"[train] kernel fallback: {fb['op']} {fb['shape']} "
                  f"({fb['reason']})")
    print("[train] done:", json.dumps(summary))
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(configs.ARCHS) + ["ctr"],
                    required=True,
                    help="an LM arch, or 'ctr' for the sparse CTR trainer")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--embedding-method", default=None,
                    choices=sorted(methods.available()),
                    help="any registered repro.methods name")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--mesh-data", type=int, default=1)
    ap.add_argument("--mesh-model", type=int, default=1)
    ap.add_argument(
        "--dp-compress-bits", type=int, default=None, metavar="BITS",
        help="data-parallel mode: replicate the state over a --mesh-data-way "
        "'data' axis (shard_map) and sync gradients at this bit width "
        "(32 = exact fp32 mean, 8/4/2 = SR-compressed codes); requires "
        "--mesh-model 1",
    )
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument(
        "--no-kernels", action="store_true",
        help="disable the fused Pallas embedding hot paths "
        "(EmbeddingSpec.use_kernels; default on, auto-interpret off-TPU)",
    )
    ap.add_argument(
        "--pad-to-tiles", action="store_true",
        help="pad the vocab table to kernel-tile geometry so the fused paths "
        "run without shape fallbacks (EmbeddingSpec.pad_to_tiles)",
    )
    ap.add_argument(
        "--cache-rows", type=int, default=0,
        help="--arch ctr only: device hot-row cache capacity per storage "
        "slot (repro.storage); bitwise-equal to uncached training",
    )
    ap.add_argument(
        "--zipf", action="store_true",
        help="--arch ctr only: use the Zipf(1.1) skewed-traffic fixture",
    )
    ap.add_argument(
        "--fault-plan", default=None, metavar="JSON",
        help="install a repro.faults FaultPlan (JSON file) for this run; "
        "see the seam catalog in repro/faults/__init__.py",
    )
    ap.add_argument(
        "--guard", action="store_true",
        help="enable the non-finite skip-step guard (repro.faults.guards); "
        "auto-enabled when --fault-plan schedules a trainer seam",
    )
    ap.add_argument(
        "--trace-out", default=None, metavar="PATH",
        help="arm the obs span tracer and write a Chrome-trace JSON "
        "(chrome://tracing / ui.perfetto.dev) to PATH at exit",
    )
    args = ap.parse_args(argv)
    compile_cache.setup()

    if args.trace_out:
        tracer().enable(args.trace_out)
        print(f"[train] tracing armed -> {args.trace_out}")
    try:
        return _main(ap, args)
    finally:
        if args.trace_out and tracer().export():
            print(f"[train] trace written: {args.trace_out} "
                  f"({len(tracer().events)} events)")


def _main(ap, args) -> int:

    if args.fault_plan:
        plan = faults.FaultPlan.load(args.fault_plan)
        faults.install(plan)
        print(f"[train] fault plan installed: sites {sorted(plan.sites())}")
        trainer_seams = {"trainer.nonfinite", "alpt.delta"} & set(plan.sites())
        if trainer_seams and not args.guard:
            print(f"[train] plan schedules {sorted(trainer_seams)}; "
                  f"enabling --guard")
            args.guard = True

    if args.arch == "ctr":
        return _run_ctr(args)
    if args.cache_rows:
        ap.error("--cache-rows is the sparse CTR trainer's tiered-storage "
                 "knob (--arch ctr); the LM dense update rewrites every row "
                 "each step, so a hot-row cache cannot stay coherent there")

    cfg = configs.smoke_config(args.arch) if args.smoke else configs.full_config(args.arch)
    if args.embedding_method:
        cfg = dataclasses.replace(cfg, embedding_method=args.embedding_method)
    dp_mode = args.dp_compress_bits is not None
    tcfg = lm_trainer.LMTrainerConfig(
        lr=args.lr,
        dp_sync_bits=args.dp_compress_bits if dp_mode else 32,
        use_kernels=not args.no_kernels,
        pad_to_tiles=args.pad_to_tiles,
        guard=args.guard,
    )

    if dp_mode and args.mesh_model != 1:
        ap.error("--dp-compress-bits is pure data parallelism; use --mesh-model 1")
    if dp_mode and args.guard:
        # Inside shard_map the guard would gate on the per-replica (pre-sync)
        # loss, so replicas could disagree on skip-vs-apply and diverge.
        ap.error("--guard is single-program only; drop --dp-compress-bits")
    if dp_mode and args.dp_compress_bits != 32 and not 2 <= args.dp_compress_bits <= 8:
        ap.error("--dp-compress-bits must be 32 (exact) or in [2, 8] "
                 f"(SR-compressed), got {args.dp_compress_bits}")
    if dp_mode and args.mesh_data == 1 and tcfg.dp_sync_bits != 32:
        print("[train] WARNING: --dp-compress-bits < 32 with --mesh-data 1 "
              "injects quantization noise with nothing to communicate")
    mesh = make_host_mesh(args.mesh_data, args.mesh_model)
    pol = sharding.Policy(name="tp", data_axes=("data",),
                          model_size=args.mesh_model)
    if dp_mode:
        # Replicated state, batch sharded over 'data', compressed sync.
        state_spec = jax.tree.map(
            lambda _: jax.sharding.PartitionSpec(),
            sharding.state_pspecs(cfg, pol, tcfg),
            is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec),
        )
    else:
        state_spec = sharding.state_pspecs(cfg, pol, tcfg)
    state_sh = sharding.to_named(state_spec, mesh)

    data = LMTokenStream(cfg.vocab_size, args.seq, seed=17)
    shutdown = GracefulShutdown()
    watchdog = StragglerWatchdog()
    # Checkpoint manifests carry the embedding method's name + schema so a
    # resume with a different --embedding-method fails loudly, not subtly.
    ckpt_meta = {
        "config_hash": config_hash(cfg),
        **embedding_manifest(lm_trainer.embedding_spec_of(cfg, tcfg)),
    }

    def make_batch(step: int) -> dict:
        full = data.batch(step, args.batch)
        batch = {
            "tokens": jnp.asarray(full[:, :-1]),
            "labels": jnp.asarray(full[:, 1:]),
        }
        if cfg.input_mode == "embeds":
            emb = np.random.RandomState(step).normal(
                0, 1, (args.batch, args.seq, cfg.d_model)
            )
            batch = {
                "embeds": jnp.asarray(emb, cfg.dtype),
                "labels": jnp.asarray(full[:, 1:] % cfg.vocab_size),
            }
        elif cfg.input_mode == "mixed":
            emb = np.random.RandomState(step).normal(
                0, 1, (args.batch, cfg.visual_prefix, cfg.d_model)
            )
            batch["prefix_embeds"] = jnp.asarray(emb, cfg.dtype)
            pos = jnp.arange(args.seq, dtype=jnp.int32)[None].repeat(args.batch, 0)
            batch["positions"] = jnp.stack([pos, pos, pos], 0)
        return batch

    # In DP mode the state is replicated and the step runs under shard_map,
    # where hint()'s with_sharding_constraint must not fire (the mesh axes are
    # manual there) — so the ambient dist context stays uninstalled.
    amb = contextlib.nullcontext() if dp_mode else dist_ctx.use(mesh, pol)
    with mesh, amb:
        init = jax.jit(
            functools.partial(lm_trainer.init_state, cfg=cfg, tcfg=tcfg),
            out_shardings=state_sh,
        )
        state = init(jax.random.PRNGKey(0))
        if dp_mode:
            if cfg.input_mode == "mixed":
                ap.error("--dp-compress-bits does not support mixed-input "
                         "(M-RoPE positions) archs")
            step_fn = data_parallel.make_lm_dp_step(cfg, tcfg, mesh)
            # Probe the wire bytes with the shapes of a real loop batch (one
            # throwaway host batch at startup — negligible next to init()).
            probe = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                make_batch(0),
            )
            grad_shapes = data_parallel.lm_grad_shapes(cfg, tcfg, state, probe)
            report = data_parallel.wire_report(grad_shapes, tcfg.dp_sync_bits)
            print(f"[train] dp sync_bits={tcfg.dp_sync_bits} "
                  f"wire_bytes/step={report['wire_bytes_per_step']} "
                  f"({report['compression_ratio']:.2f}x vs fp32)")
        else:
            step_fn = jax.jit(
                lm_trainer.make_train_step(cfg, tcfg),
                in_shardings=(state_sh, None),
                out_shardings=(state_sh, None),
                donate_argnums=(0,),
            )
            # Host-side periodic refresh (prune mask); identity otherwise.
            step_fn = lm_trainer.wrap_host_refresh(step_fn, cfg, tcfg)

        start_step = 0
        ckpt = None
        if args.ckpt_dir:
            ckpt = CheckpointManager(
                args.ckpt_dir, keep=3, save_every=args.ckpt_every
            )
            latest = ckpt.latest_step()
            if latest is not None:
                # Surface method mismatches BEFORE the structural restore
                # errors out on leaf counts (clearer failure story).
                for problem in check_embedding_manifest(
                        ckpt.read_manifest(latest),
                        lm_trainer.embedding_spec_of(cfg, tcfg)):
                    print(f"[train] WARNING: {problem}")
                state, manifest = ckpt.restore(state, shardings=state_sh)
                if manifest.get("config_hash") != config_hash(cfg):
                    print("[train] WARNING: config hash mismatch on resume")
                start_step = manifest["step"]
                print(f"[train] resumed from step {start_step}")

        losses = []
        step_times = StreamingQuantiles()
        guard_stats = faults.GuardStats() if args.guard else None
        for step in range(start_step, args.steps):
            batch = make_batch(step)
            t0 = time.time()
            with tracer().span("train.step", step=step):
                state, metrics = step_fn(state, batch)
                loss = float(metrics["loss"])  # blocks; also the step barrier
            dt = time.time() - t0
            step_times.add(dt * 1e6)
            slow = watchdog.observe(dt)
            losses.append(loss)
            if guard_stats is not None:
                guard_stats.observe(metrics)
            if (step + 1) % args.log_every == 0:
                print(
                    f"[train] step {step+1} loss {loss:.4f} "
                    f"{dt*1e3:.0f}ms{' STRAGGLER' if slow else ''}"
                )
            if ckpt:
                ckpt.maybe_save(
                    state, step + 1,
                    extra_meta=ckpt_meta,
                )
            if faults.fires("train.preempt", step + 1):
                print(f"[train] injected preemption at step {step+1}")
                shutdown.requested = True
            if shutdown.requested:
                if ckpt:
                    ckpt.maybe_save(
                        state, step + 1, force=True,
                        extra_meta=ckpt_meta,
                    )
                print(f"[train] preempted at step {step+1}; checkpointed; "
                      f"exiting 75 for requeue")
                return 75
        if ckpt:
            ckpt.maybe_save(
                state, args.steps, force=True,
                extra_meta=ckpt_meta,
            )
        summary = {
            "final_loss": losses[-1] if losses else None,
            "first_loss": losses[0] if losses else None,
            "straggler_steps": watchdog.flagged,
            "steps": len(losses),
            "step_time_us": step_times.to_json(),
        }
        if guard_stats is not None:
            guard_stats.publish()
            g = guard_stats.to_json()
            summary["guard"] = g
            print(f"[train] guard: {g['skipped']} skipped steps "
                  f"({g['nonfinite_fired']} injected non-finite, "
                  f"{g['delta_fired']} injected Delta blowups)")
        if ckpt and ckpt.corrupt_steps:
            summary["corrupt_checkpoints"] = ckpt.corrupt_steps
            print(f"[train] WARNING: refused corrupted checkpoint step(s) "
                  f"{ckpt.corrupt_steps} on restore")
        if not args.no_kernels:
            # Explicit fallback accounting: surface any embedding op that
            # silently would have missed the fused path (never silent).
            stats = kernel_ops.fallback_stats()
            summary["kernel_fallbacks"] = stats["total_fallbacks"]
            for fb in stats["fallbacks"]:
                print(f"[train] kernel fallback: {fb['op']} {fb['shape']} "
                      f"({fb['reason']}) — consider --pad-to-tiles")
        print("[train] done:", json.dumps(summary))
        return 0


if __name__ == "__main__":
    sys.exit(main())
