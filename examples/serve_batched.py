"""End-to-end serving driver: batched requests through the Engine.

Serves a small LM (optionally BNN-quantized — the paper's technique as a
serving-time compression knob) with slot-based continuous batching:
requests of different prompt lengths stream through ``max_batch`` decode
slots, one batched decode_step per engine tick.

Run:  PYTHONPATH=src python examples/serve_batched.py [--quant] [--arch phi3-mini-3.8b]
"""
import argparse
import dataclasses
import time

import jax
import numpy as np

from repro import compile_cache
from repro.configs import get_config
from repro.configs.base import QuantConfig
from repro.models import init_params
from repro.serving.engine import Engine, Request


def small(cfg):
    extra = {}
    if cfg.ssm is not None:
        extra["ssm"] = dataclasses.replace(cfg.ssm, state_dim=16, head_dim=16, chunk=16)
    if cfg.moe is not None:
        extra["moe"] = dataclasses.replace(
            cfg.moe, num_experts=8, top_k=2, expert_ffn_dim=64
        )
    if cfg.mla is not None:
        extra["mla"] = dataclasses.replace(
            cfg.mla, kv_lora_rank=32, q_lora_rank=64, qk_nope_dim=16,
            qk_rope_dim=8, v_head_dim=16,
        )
    if cfg.family == "hybrid":
        extra["hybrid_period"] = 2
    return dataclasses.replace(
        cfg, num_layers=4, d_model=128, num_heads=8, num_kv_heads=4,
        head_dim=16, d_ff=256, vocab_size=512, attn_q_chunk=32, fsdp=False,
        **extra,
    )


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="phi3-mini-3.8b")
    ap.add_argument("--quant", action="store_true",
                    help="binarize FFN/attn projections (paper technique)")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--new-tokens", type=int, default=16)
    args = ap.parse_args()

    cfg = small(get_config(args.arch))
    if args.quant:
        cfg = dataclasses.replace(
            cfg, quant=QuantConfig(mode="bnn_weight_only", targets=("ffn", "attn_proj"))
        )
    print(f"serving {cfg.name} ({cfg.family}) quant={cfg.quant.mode}")

    params = init_params(cfg, jax.random.PRNGKey(0))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"params: {n_params/1e6:.2f}M")

    eng = Engine(cfg, params, max_batch=args.max_batch, max_len=128)
    rng = np.random.default_rng(0)
    t0 = time.perf_counter()
    for i in range(args.requests):
        plen = int(rng.integers(4, 24))
        eng.submit(Request(
            rid=i,
            prompt=rng.integers(0, cfg.vocab_size, plen).astype(np.int32),
            max_new_tokens=args.new_tokens,
        ))
    done = eng.run()
    dt = time.perf_counter() - t0

    total_new = sum(len(r.output) for r in done)
    print(f"\ncompleted {len(done)} requests, {total_new} tokens "
          f"in {dt:.2f}s ({total_new/dt:.1f} tok/s on CPU)")
    for r in done[:4]:
        print(f"  req {r.rid}: {r.output[:10]}{'...' if len(r.output) > 10 else ''}")


if __name__ == "__main__":
    main()
