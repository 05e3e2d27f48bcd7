"""End-to-end training driver: any assigned architecture, reduced or full.

Exercises the whole substrate: synthetic data pipeline, AdamW, microbatch
gradient accumulation, atomic checkpoints, crash recovery, optional BNN
quantization and gradient compression.

Defaults train a ~15M-parameter reduced model for 200 steps on CPU; pass
``--preset full`` to use the real architecture config (sized for the TPU
mesh, not this container).

Run:  PYTHONPATH=src python examples/train_lm.py --arch phi3-mini-3.8b --steps 200
"""
import argparse
import dataclasses

import jax

from repro import compile_cache
from repro.configs import get_config
from repro.configs.base import QuantConfig
from repro.train.trainer import Trainer, TrainerConfig


def reduced(cfg):
    extra = {}
    if cfg.ssm is not None:
        extra["ssm"] = dataclasses.replace(cfg.ssm, state_dim=32, head_dim=32, chunk=32)
    if cfg.moe is not None:
        extra["moe"] = dataclasses.replace(
            cfg.moe, num_experts=8, top_k=2, expert_ffn_dim=128
        )
    if cfg.mla is not None:
        extra["mla"] = dataclasses.replace(
            cfg.mla, kv_lora_rank=64, q_lora_rank=96, qk_nope_dim=32,
            qk_rope_dim=16, v_head_dim=32,
        )
    if cfg.family == "hybrid":
        extra["hybrid_period"] = 3
    return dataclasses.replace(
        cfg, num_layers=6, d_model=256, num_heads=8, num_kv_heads=4,
        head_dim=32, d_ff=512, vocab_size=2048, attn_q_chunk=64,
        fsdp=False, **extra,
    )


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="phi3-mini-3.8b")
    ap.add_argument("--preset", choices=["reduced", "full"], default="reduced")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=2)
    ap.add_argument("--quant", action="store_true")
    ap.add_argument("--compression", default="none",
                    choices=["none", "sign", "int8", "topk"])
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    ap.add_argument("--inject-failure", type=int, default=-1,
                    help="step at which to simulate a node failure")
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.preset == "reduced":
        cfg = reduced(cfg)
    if args.quant:
        cfg = dataclasses.replace(
            cfg, quant=QuantConfig(mode="bnn_weight_only", targets=("ffn",))
        )

    tcfg = TrainerConfig(
        total_steps=args.steps,
        checkpoint_every=max(10, args.steps // 4),
        checkpoint_dir=args.ckpt_dir,
        log_every=max(1, args.steps // 20),
        microbatches=args.microbatches,
        compression=args.compression,
        global_batch=args.batch,
        seq_len=args.seq,
        fail_at_steps=(args.inject_failure,) if args.inject_failure >= 0 else (),
    )
    trainer = Trainer(cfg, tcfg)
    n_params = sum(x.size for x in jax.tree.leaves(trainer.params))
    print(f"training {cfg.name} ({cfg.family}) — {n_params/1e6:.1f}M params, "
          f"quant={cfg.quant.mode}, compression={args.compression}")

    out = trainer.run()
    print(f"\nfinished at step {out['final_step']} "
          f"(recoveries: {out['recoveries']}, stragglers: {len(out['stragglers'])})")
    for h in out["history"]:
        if "loss" in h:
            print(f"  step {h['step']:5d}  loss {h['loss']:.4f}  "
                  f"grad_norm {h.get('grad_norm', float('nan')):.3f}  dt {h['dt']:.2f}s")


if __name__ == "__main__":
    main()
