"""The closed train->deploy loop, end to end.

1. **Train** — a straight-through-estimator BNN learns to classify traffic
   generated by the dataplane scenario library: IoT-telemetry headers
   (class 0) vs DDoS-burst headers (class 1), a drop/mark decision a switch
   would act on.  The STE forward pass is bit-for-bit the deployed network.
2. **Export** — latent weights round to {0,1} bit matrices, ``compile_bnn``
   schedules them onto RMT pipeline elements, and the program lowers to the
   dataplane's dense op-tables.
3. **Deploy** — the op-tables run on a simulated switch fabric; held-out
   packets (later packets from the same traffic worlds) prove the training
   forward pass, the mathematical oracle, the fused executor, and the
   multi-hop fabric all agree bit-for-bit, and the classifier holds >= 90%
   accuracy where it matters: on the switch.

Run:   PYTHONPATH=src python examples/train_deploy_loop.py
Smoke: PYTHONPATH=src python examples/train_deploy_loop.py --smoke
(exits non-zero if accuracy or bit-exactness acceptance fails)
"""
from __future__ import annotations

import argparse

from repro import compile_cache
from repro.core.export import verify_roundtrip
from repro.train.bnn_trainer import BnnTrainConfig, BnnTrainer

ACCURACY_FLOOR = 0.90
MIN_HELD_OUT = 10_000


def main() -> None:
    compile_cache.enable()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--steps", type=int, default=600)
    ap.add_argument("--eval-per-class", type=int, default=MIN_HELD_OUT // 2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--smoke",
        action="store_true",
        help="tiny budget for CI: skips the accuracy gate, keeps bit-exactness",
    )
    args = ap.parse_args()

    cfg = BnnTrainConfig(
        steps=30 if args.smoke else args.steps,
        train_packets_per_class=1024 if args.smoke else 8192,
        eval_packets_per_class=256 if args.smoke else args.eval_per_class,
        seed=args.seed,
    )

    print("== 1. train (STE over dataplane traffic) ==")
    print(f"model {cfg.layer_sizes}, task {cfg.scenarios[0]} vs {cfg.scenarios[1]}")
    trainer = BnnTrainer(cfg)
    summary = trainer.train()
    last = summary["history"][-1]
    print(
        f"{summary['final_step']} steps in {summary['seconds']:.2f}s "
        f"({summary['steps_per_second']:.0f} steps/s), "
        f"final batch loss {last['loss']:.3f} acc {last['accuracy']:.3f}"
    )

    held = trainer.evaluate_held_out()
    print(
        f"held-out: {held['accuracy']:.2%} accuracy on "
        f"{held['packets']} unseen packets"
    )

    print("\n== 2. export (bits -> compile_bnn -> op-tables) ==")
    exported = trainer.export()
    print(exported.program.summary())
    print(exported.lowered.summary())
    print(
        f"compile {exported.compile_seconds * 1e3:.1f}ms + "
        f"lower {exported.lower_seconds * 1e3:.1f}ms"
    )

    print("\n== 3. deploy (simulated switch fabric) ==")
    fab = exported.fabric(mode="multi_hop")
    report = verify_roundtrip(
        exported,
        trainer.eval_x,
        fabric=fab,
        reference_bits=trainer.forward_bits(trainer.eval_x),
        check=False,
    )
    print(report.summary())
    print(fab.telemetry().render())  # telemetry of the verification run

    failures = []
    if not report.ok:
        failures.append(f"round-trip not bit-exact: {report.summary()}")
    if not args.smoke:
        if held["packets"] < MIN_HELD_OUT:
            failures.append(f"held-out set {held['packets']} < {MIN_HELD_OUT}")
        if held["accuracy"] < ACCURACY_FLOOR:
            failures.append(
                f"held-out accuracy {held['accuracy']:.2%} < {ACCURACY_FLOOR:.0%}"
            )
    if failures:
        raise SystemExit("ACCEPTANCE FAILED: " + "; ".join(failures))
    print("\nacceptance: OK (train-time == switch-fabric, bit for bit)")


if __name__ == "__main__":
    main()
