"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train \
        --arch llama-60m --steps 200 --batch 8 --seq 256 \
        --optimizer blockllm --sparsity 0.9 --ckpt-dir /tmp/ckpt

``--optimizer`` is a ``repro.trainers`` registry lookup (blockllm,
adam, galore, lora, badam, and the Q8State variants blockllm+q8 /
adam+q8 / badam+q8 — plus anything registered by downstream code): the
launcher builds the named ``TrainerCore``, wraps its
``TrainState`` in a ``TrainerHandle``, and hands it to the generic
``runtime.train_loop`` — no per-trainer branches anywhere.

Any registered arch runs; use --reduce to scale an assigned production
arch down for CPU (divides layers/width, shrinks vocab).  XLA latency-
hiding-scheduler flags for real TPU fleets are appended via --tpu-flags.
"""
from __future__ import annotations

import argparse
import os
import sys


TPU_PERF_FLAGS = (
    "--xla_tpu_enable_latency_hiding_scheduler=true "
    "--xla_tpu_megacore_fusion_allow_ags=true "
    "--xla_enable_async_collective_permute=true "
    "--xla_tpu_enable_async_collective_fusion=true "
    "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true"
)


def reduce_config(cfg, factor=4):
    """Scale an assigned arch down for CPU execution, same family/blocks."""
    pat_len = len(cfg.pattern)
    layers = max(pat_len, (cfg.num_layers // factor) // pat_len * pat_len)
    heads = max(1, cfg.num_heads // factor)
    kv = max(1, min(cfg.num_kv_heads, heads))
    return cfg.replace(
        num_layers=layers,
        d_model=max(32, cfg.d_model // factor),
        num_heads=heads, num_kv_heads=kv,
        head_dim=max(8, cfg.resolved_head_dim // factor),
        d_ff=max(32, cfg.d_ff // factor) if cfg.d_ff else 0,
        vocab_size=min(cfg.vocab_size, 2048),
        num_experts=min(cfg.num_experts, 8) if cfg.num_experts else 0,
        moe_d_ff=max(16, cfg.moe_d_ff // factor) if cfg.moe_d_ff else 0,
        shared_expert_d_ff=(max(16, cfg.shared_expert_d_ff // factor)
                            if cfg.shared_expert_d_ff else 0),
        lru_width=max(32, cfg.lru_width // factor) if cfg.lru_width else 0,
        window_size=min(cfg.window_size, 64) if cfg.window_size else 0,
        num_encoder_layers=(max(1, cfg.num_encoder_layers // factor)
                            if cfg.num_encoder_layers else 0),
        encoder_seq_len=(min(cfg.encoder_seq_len, 64)
                         if cfg.encoder_seq_len else 0),
        encoder_feature_dim=(min(cfg.encoder_feature_dim, 80)
                             if cfg.encoder_feature_dim else 0),
        vision_embed_dim=(min(cfg.vision_embed_dim, 64)
                          if cfg.vision_embed_dim else 0),
        num_patches=min(cfg.num_patches, 8) if cfg.num_patches else 0,
        remat=False,
    )


def make_trainer(cfg, args, params=None):
    """Registry lookup: ``--optimizer`` -> TrainerCore -> TrainerHandle.

    Every factory takes the union of launcher hyperparameters and picks
    what it needs (blockllm: sparsity/patience/policy/k_frac; galore:
    rank/lr; lora: rank/adam; badam: switch_every; adam: adam).
    """
    import jax
    from repro import trainers
    from repro.models import model as model_lib
    from repro.optim.adam import Adam
    from repro.optim import schedule

    if params is None:
        params = model_lib.init_params(jax.random.PRNGKey(args.seed), cfg)
    lr = schedule.cosine(args.lr, args.steps) if args.cosine else args.lr
    adam = Adam(lr=lr, weight_decay=args.weight_decay)
    core = trainers.make(
        args.optimizer, cfg, adam=adam, lr=args.lr,
        sparsity=args.sparsity, patience=args.patience,
        policy=args.policy, k_frac=args.k_frac, rank=args.rank,
        switch_every=args.patience,
        quantize_state=args.quantize_state)
    return trainers.TrainerHandle(
        core, core.init(jax.random.PRNGKey(args.seed), params))


def main(argv=None, *, on_step=None):
    """Run the launcher; ``on_step(step, metrics)`` is called after each
    train step (see ``runtime.train_loop.run``)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama-60m")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--optimizer", default="blockllm",
                    choices=["blockllm", "adam", "galore", "lora", "badam",
                             "blockllm+q8", "adam+q8", "badam+q8"])
    ap.add_argument("--quantize-state", action="store_true",
                    help="Q8State: store Adam moments int8 + per-block "
                         "f32 scales (~4x smaller optimizer state; "
                         "blockllm/adam/badam — equivalent to the +q8 "
                         "registry names)")
    ap.add_argument("--sparsity", type=float, default=0.95)
    ap.add_argument("--patience", type=int, default=100)
    ap.add_argument("--policy", default="static",
                    choices=["static", "greedy"])
    ap.add_argument("--k-frac", type=float, default=0.25)
    ap.add_argument("--rank", type=int, default=8)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--cosine", action="store_true")
    ap.add_argument("--weight-decay", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--reduce", type=int, default=0,
                    help="divide model dims by this factor (CPU runs)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a TraceKit trace: .jsonl = event log "
                         "(per-step selection telemetry), else Chrome/"
                         "Perfetto trace JSON")
    ap.add_argument("--metrics-every", type=int, default=0,
                    help="dump the metrics registry as text every N "
                         "steps (0 = off)")
    ap.add_argument("--tpu-flags", action="store_true",
                    help="append latency-hiding XLA flags (set BEFORE jax)")
    args = ap.parse_args(argv)

    if args.quantize_state and args.optimizer.split("+")[0] not in (
            "blockllm", "adam", "badam"):
        ap.error(f"--quantize-state is not supported by "
                 f"--optimizer {args.optimizer} (Q8State cores: "
                 f"blockllm, adam, badam)")

    if args.tpu_flags:
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " "
                                   + TPU_PERF_FLAGS)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from repro.configs import base as config_base
    from repro.data.pipeline import DataConfig, TokenPipeline
    from repro.runtime.train_loop import TrainLoopConfig, run

    cfg = config_base.get_config(args.arch)
    if args.reduce:
        cfg = reduce_config(cfg, args.reduce)
    trainer = make_trainer(cfg, args)
    pipe = TokenPipeline(DataConfig(vocab_size=cfg.vocab_size,
                                    seq_len=args.seq,
                                    global_batch=args.batch,
                                    seed=args.seed))

    def batch_fn(step):
        b = pipe.batch(step)
        if cfg.family == "vlm":
            import jax, jax.numpy as jnp
            b["patch_embeds"] = jax.random.normal(
                jax.random.PRNGKey(step), (args.batch, cfg.num_patches,
                                           cfg.vision_embed_dim))
        if cfg.is_encoder_decoder:
            import jax
            b["frames"] = jax.random.normal(
                jax.random.PRNGKey(step), (args.batch, cfg.encoder_seq_len,
                                           cfg.encoder_feature_dim))
        return b

    tracer, metrics = None, None
    if args.trace or args.metrics_every:
        from repro.obs import MetricsRegistry, Tracer
        metrics = MetricsRegistry()
        if args.trace:
            tracer = Tracer()
    out = run(trainer, batch_fn,
              TrainLoopConfig(total_steps=args.steps,
                              ckpt_every=args.ckpt_every,
                              ckpt_dir=args.ckpt_dir,
                              metrics_every=args.metrics_every),
              on_step=on_step, tracer=tracer, metrics=metrics)
    rep = trainer.memory_report()
    print(f"final loss: {out['losses'][-1]:.4f}")
    print("memory report:", {k: f"{v/2**20:.1f}MiB" for k, v in rep.items()})
    if tracer is not None:
        from repro.obs import write_trace
        p = write_trace(args.trace, tracer, metrics)
        print(f"trace: {len(tracer)} events -> {p}")
    return out


if __name__ == "__main__":
    main()
