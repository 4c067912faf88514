"""Serving driver: a thin CLI over the `repro.serving` Engine API.

Two scenarios share one int8-resident Engine:

  LM decode (slot-based continuous batching):
    PYTHONPATH=src python -m repro.launch.serve lm --arch qwen3-1.7b --smoke \
        --batch 4 --prompt-len 32 --gen 16 --requests 8

  CTR scoring (fixed-geometry batched admission):
    PYTHONPATH=src python -m repro.launch.serve ctr --method alpt \
        --batch 32 --requests 64

Everything interesting lives in :mod:`repro.serving` — the Engine builds the
method's ``serving_state`` (codes + scales for integer tables; the fp32
table is never materialized), steps the scheduler, and reports metrics
including resident embedding bytes and an accurate per-engine kernel
fallback tally (``ops.fallback_scope``).  This file only parses flags,
fabricates synthetic requests, and prints the report.
"""
from __future__ import annotations

import argparse
import json
import sys

import jax
import numpy as np

from repro import configs, faults, methods
from repro.data.ctr_synth import CTRDatasetConfig, CTRSynthetic
from repro.launch import compile_cache
from repro.models.ctr import DCNConfig
from repro.obs.trace import tracer
from repro.serving.ctr import CTREngine, CTRRequest
from repro.serving.lm import LMEngine, LMRequest
from repro.training import lm_trainer
from repro.training.ctr_trainer import CTRTrainer, TrainerConfig

# One synthetic CTR fixture shared by this CLI and benchmarks/serve_bench.py,
# so the artifact's cells stay comparable with what the CLI demonstrates.
CTR_DEMO_DATA = CTRDatasetConfig(
    name="serve-synth", n_fields=8,
    cardinalities=(97, 41, 13, 211, 89, 53, 17, 149),
    teacher_rank=4, seed=0,
)
# d=64: wide enough that the per-row fp32 scale doesn't mask the packed
# sub-byte code savings (bits=4 resident <= 0.55x bits=8, asserted in
# benchmarks/serve_bench.py).
CTR_DEMO_DIM = 64

# Skewed-traffic fixture for the tiered-storage cells: Zipf(1.1) request ids
# over a 4092-row vocabulary, so a hot tier holding ~10% of the rows catches
# >=90% of lookups (asserted in benchmarks/serve_bench.py).
CTR_ZIPF_DATA = CTRDatasetConfig(
    name="serve-zipf", n_fields=8,
    cardinalities=(4, 8, 12, 24, 48, 96, 1400, 2500),
    teacher_rank=4, zipf_a=1.1, seed=0,
)


def build_ctr_demo_engine(method: str, *, bits: int = 8, batch: int,
                          train_steps: int, train_batch: int = 256,
                          data_cfg: CTRDatasetConfig = CTR_DEMO_DATA,
                          cache_rows: int = 0, cold_tier: bool = False,
                          device_budget_bytes: int | None = None):
    """Train a few steps on the demo fixture, return ``(engine, data)``."""
    data = CTRSynthetic(data_cfg)
    spec = methods.EmbeddingSpec(
        method=method, n=data_cfg.n_features, d=CTR_DEMO_DIM, bits=bits,
        init_scale=0.05,
    )
    trainer = CTRTrainer(TrainerConfig(
        spec=spec, model="dcn",
        dcn=DCNConfig(n_fields=data_cfg.n_fields, emb_dim=CTR_DEMO_DIM,
                      cross_depth=2, mlp_widths=(64, 32)),
    ))
    state = trainer.init_state()
    for i in range(train_steps):
        ids, labels = data.batch("train", i, train_batch)
        state, _ = trainer.train_step(state, ids, labels)
    engine = CTREngine.from_state(
        state, trainer.cfg, batch=batch, cache_rows=cache_rows,
        cold_tier=cold_tier, device_budget_bytes=device_budget_bytes,
    )
    return engine, data


def _print_report(engine) -> None:
    m = engine.metrics()
    per = (
        f"{m.get('us_per_token', 0.0):.0f} us/token"
        if engine.scenario == "lm" else f"{m.get('us_per_request', 0.0):.0f} us/request"
    )
    print(
        f"[serve] {m['scenario']}/{m['embedding_method']}: "
        f"{m['requests_completed']} requests in {m['wall_s']:.2f}s ({per}); "
        f"resident embedding bytes {m['resident_embedding_bytes']} "
        f"(codes {m['embedding_code_bytes']} + scales "
        f"{m['embedding_scale_bytes']}; int8_resident={m['int8_resident']})"
    )
    for c in m.caches:
        print(
            f"[serve] {c.tier} tier '{c.name}': {c.rows_cached}/{c.capacity} "
            f"rows, hit rate {c.hit_rate:.3f} ({c.hits} hits / {c.misses} "
            f"misses), {c.hot_bytes + c.metadata_bytes} device bytes "
            f"(rows {c.hot_bytes} + metadata {c.metadata_bytes})"
        )
        if c.admission_oom or c.prefetch_dropped or c.corruption_detected:
            print(
                f"[serve] {c.tier} tier '{c.name}' recovery: "
                f"{c.admission_oom} admission refusals, "
                f"{c.prefetch_dropped} prefetch losses, "
                f"{c.corruption_detected} corrupted prefetches re-fetched"
            )
    if m.caches:
        print(f"[serve] aggregate cache hit rate {m.cache_hit_rate:.3f}")
    if m.latency_us:
        for which, q in sorted(m.latency_us.items()):
            if q.get("count"):
                print(f"[serve] {which} latency: p50 {q['p50']:.0f}us "
                      f"p95 {q['p95']:.0f}us p99 {q['p99']:.0f}us "
                      f"(n={q['count']})")
    report = engine.fallback_report()
    for fb in report["fallbacks"]:
        print(f"[serve] kernel fallback: {fb['op']} {fb['shape']} "
              f"({fb['reason']})")
    if not report["fallbacks"]:
        print("[serve] kernel fallbacks: none")
    print(f"[serve] recovery: {m['served_degraded']} degraded waves, "
          f"{m['deadline_misses']} deadline misses, "
          f"{m['wave_retries']} wave retries, "
          f"{m['retry_failures']} retry exhaustions")
    for name, stats in engine._tier_retry_stats():
        print(f"[serve] {name} tier retries: {json.dumps(stats.to_json())}")
    h = engine.health()
    status = "READY" if h["ready"] else "NOT READY"
    failed = [k for k, ok in h["checks"].items() if not ok]
    print(f"[serve] health: {status}"
          + (f" (failing: {', '.join(failed)})" if failed else ""))


def _run_lm(args) -> int:
    cfg = (configs.smoke_config(args.arch) if args.smoke
           else configs.full_config(args.arch))
    if cfg.input_mode == "embeds":
        print("[serve] encoder-only arch has no decode; nothing to serve")
        return 0
    tcfg = lm_trainer.LMTrainerConfig()
    state = lm_trainer.init_state(jax.random.PRNGKey(0), cfg, tcfg)
    engine = LMEngine.from_state(
        state, cfg, tcfg, batch=args.batch,
        max_len=args.prompt_len + args.gen,
    )
    if args.deadline_ms is not None:
        engine.deadline_s = args.deadline_ms / 1e3
    rng = np.random.RandomState(0)
    for _ in range(args.requests):
        engine.submit(LMRequest(
            prompt=rng.randint(0, cfg.vocab_size, args.prompt_len).astype(np.int32),
            max_new=args.gen,
        ))
    done = engine.run()
    _print_report(engine)
    for rid in sorted(done)[:2]:
        print(f"  rid={rid} tokens={done[rid][:8]}...")
    return 0


def _run_ctr(args) -> int:
    engine, data = build_ctr_demo_engine(
        args.method, bits=args.bits, batch=args.batch,
        train_steps=args.train_steps,
        data_cfg=CTR_ZIPF_DATA if args.zipf else CTR_DEMO_DATA,
        cache_rows=args.cache_rows, cold_tier=args.cold_tier,
        device_budget_bytes=args.device_budget_bytes,
    )
    if args.deadline_ms is not None:
        engine.deadline_s = args.deadline_ms / 1e3
    ids, _ = data.batch("test", 0, args.requests)
    rids = [engine.submit(CTRRequest(ids=row)) for row in ids]
    done = engine.run()
    _print_report(engine)
    probs = [done[r]["prob"] for r in rids[:4]]
    print(f"  first probs: {[round(p, 4) for p in probs]}")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    sub = ap.add_subparsers(dest="scenario", required=True)

    lm = sub.add_parser("lm", help="continuous-batch LM decode")
    lm.add_argument("--arch", choices=sorted(configs.ARCHS), required=True)
    lm.add_argument("--smoke", action="store_true")
    lm.add_argument("--batch", type=int, default=4)
    lm.add_argument("--prompt-len", type=int, default=32)
    lm.add_argument("--gen", type=int, default=16)
    lm.add_argument("--requests", type=int, default=8)

    ctr = sub.add_parser("ctr", help="batched CTR request scoring")
    ctr.add_argument("--method", choices=methods.available(), default="alpt")
    ctr.add_argument("--bits", type=int, default=8)
    ctr.add_argument("--batch", type=int, default=32)
    ctr.add_argument("--requests", type=int, default=64)
    ctr.add_argument("--train-steps", type=int, default=5)
    ctr.add_argument("--zipf", action="store_true",
                     help="use the Zipf(1.1) skewed-traffic fixture")
    ctr.add_argument("--cache-rows", type=int, default=0,
                     help="device hot-row cache capacity per storage slot "
                          "(0 = off); bitwise-equal to uncached serving")
    ctr.add_argument("--cold-tier", action="store_true",
                     help="host-resident codes; device holds scales + hot "
                          "rows only (requires --cache-rows > 0)")
    ctr.add_argument("--device-budget-bytes", type=int, default=None,
                     help="assert hot-tier device bytes stay under this")

    for p in (lm, ctr):
        p.add_argument("--fault-plan", default=None, metavar="JSON",
                       help="install a repro.faults FaultPlan (JSON file); "
                       "see the seam catalog in repro/faults/__init__.py")
        p.add_argument("--deadline-ms", type=float, default=None,
                       help="per-wave deadline; waves over it tick the "
                       "deadline_misses counter (observed, not enforced)")
        p.add_argument("--trace-out", default=None, metavar="PATH",
                       help="arm the obs span tracer and write a Chrome-trace "
                       "JSON (chrome://tracing / ui.perfetto.dev) to PATH")

    args = ap.parse_args(argv)
    compile_cache.setup()
    if args.fault_plan:
        plan = faults.FaultPlan.load(args.fault_plan)
        faults.install(plan)
        print(f"[serve] fault plan installed: sites {sorted(plan.sites())}")
    if args.trace_out:
        tracer().enable(args.trace_out)
        print(f"[serve] tracing armed -> {args.trace_out}")
    try:
        return _run_lm(args) if args.scenario == "lm" else _run_ctr(args)
    finally:
        if args.trace_out and tracer().export():
            print(f"[serve] trace written: {args.trace_out} "
                  f"({len(tracer().events)} events)")


if __name__ == "__main__":
    sys.exit(main())
