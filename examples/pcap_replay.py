"""Real-capture ingestion, end to end: pcap file -> trained BNN -> switch.

1. **Capture** — a deterministic two-class trace (IoT UDP telemetry vs TCP
   SYN flood) is synthesized as raw packet bytes and written to disk as
   BOTH classic pcap and pcapng; reading the files back must reproduce
   every packet byte-exactly (the reader/writer round-trip contract).
2. **Featurize** — the capture's Ethernet/IPv4/TCP/UDP header fields are
   sliced into activation-bit matrices (``dataplane.pcap.featurize``), the
   same fixed-width {0,1} rows the synthetic scenarios emit.
3. **Train** — a straight-through-estimator BNN fits the capture on a
   temporal split (``make_capture_task``): early packets train, the unseen
   tail is held out, exactly how a capture-then-deploy pipeline would.
4. **Deploy** — the exported op-tables run on a 5-hop simulated switch
   fabric; held-out packets must classify bit-exactly vs the mathematical
   oracle AND the training forward pass.
5. **Serve** — the capture is registered as a traffic scenario and served
   as one tenant of three on a shared chip (``SwitchScheduler``) in both
   merged and time-sliced modes, with per-tenant telemetry; the pcap
   tenant's outputs must again be bit-exact with the oracle.

Run:   PYTHONPATH=src python examples/pcap_replay.py
Smoke: PYTHONPATH=src python examples/pcap_replay.py --smoke
(exits non-zero if any round-trip, accuracy, or bit-exactness gate fails)
"""
from __future__ import annotations

import argparse
import os
import tempfile

import numpy as np

from repro import compile_cache
from repro.core.export import verify_roundtrip
from repro.dataplane import FleetSpec, TenantSpec, build_fleet, pcap, traffic
from repro.train.bnn_trainer import BnnTrainConfig, BnnTrainer, make_capture_task

ACCURACY_FLOOR = 0.95
FABRIC_HOPS = 5
SCENARIO_NAME = "pcap:replay"


def main() -> None:
    compile_cache.enable()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--packets", type=int, default=20_000)
    ap.add_argument("--steps", type=int, default=400)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--smoke",
        action="store_true",
        help="tiny budget for CI: skips the accuracy gate, keeps every "
        "round-trip and bit-exactness gate",
    )
    args = ap.parse_args()
    n = 4000 if args.smoke else args.packets
    steps = 40 if args.smoke else args.steps
    failures: list[str] = []

    print("== 1. capture (synthesize -> write -> read, both formats) ==")
    packets, ts, labels = pcap.synthesize_capture(n, seed=args.seed)
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "trace.pcap")
        path_ng = os.path.join(tmp, "trace.pcapng")
        pcap.write_pcap(packets, ts, path=path)
        pcap.write_pcapng(packets, ts, path=path_ng)
        cap = pcap.read_pcap(path)
        cap_ng = pcap.read_pcap(path_ng)
        print(
            f"{cap.num_packets} packets, {os.path.getsize(path)} bytes pcap, "
            f"{os.path.getsize(path_ng)} bytes pcapng"
        )
    if cap.packets() != packets or cap_ng.packets() != packets:
        failures.append("capture file round trip is not byte-exact")
    flood = int(labels.sum())
    print(f"ground truth: {n - flood} telemetry, {flood} flood packets")

    print("\n== 2. featurize (header fields -> activation bits) ==")
    input_bits = 64
    bits = pcap.featurize(cap, input_bits)
    fields = pcap.parse_headers(cap)
    print(
        f"{pcap.PCAP_FEATURE_BITS}-bit layout folded to {input_bits} bits; "
        f"{int(fields.is_udp.sum())} UDP / {int(fields.is_tcp.sum())} TCP, "
        f"IAT buckets {sorted(np.unique(fields.iat_bucket).tolist())}"
    )

    print("\n== 3. train (temporal split of the capture) ==")
    task = make_capture_task(bits, labels, train_frac=0.8, seed=args.seed)
    cfg = BnnTrainConfig(
        layer_sizes=(input_bits, 64, 1), steps=steps, seed=args.seed
    )
    trainer = BnnTrainer(cfg, task=task)
    summary = trainer.train()
    held = trainer.evaluate_held_out()
    print(
        f"{summary['final_step']} steps in {summary['seconds']:.2f}s; "
        f"held-out (capture tail): {held['accuracy']:.2%} on "
        f"{held['packets']} packets"
    )
    if not args.smoke and held["accuracy"] < ACCURACY_FLOOR:
        failures.append(
            f"held-out accuracy {held['accuracy']:.2%} < {ACCURACY_FLOOR:.0%}"
        )

    print(f"\n== 4. deploy ({FABRIC_HOPS}-hop switch fabric) ==")
    exported = trainer.export()
    # One declarative spec builds the whole serving stack — the trained
    # export as the pcap-replay tenant plus two synthetic tenants — and
    # also hands out the deploy fabric for the export's program.
    traffic.register_scenario(
        pcap.pcap_scenario(cap, name=SCENARIO_NAME), overwrite=True
    )
    fleet = build_fleet(FleetSpec(tenants=(
        TenantSpec(f"t0:{SCENARIO_NAME}", scenario=SCENARIO_NAME,
                   program=exported.program, weight=2.0),
        TenantSpec("t1:iot_telemetry", scenario="iot_telemetry",
                   shape=(32, 16, 4), seed=100),
        TenantSpec("t2:ddos_burst", scenario="ddos_burst",
                   shape=(24, 12, 4), seed=101),
    )))
    fab = fleet.fabric(0, hops=FABRIC_HOPS)
    report = verify_roundtrip(
        exported,
        trainer.eval_x,
        fabric=fab,
        reference_bits=trainer.forward_bits(trainer.eval_x),
        check=False,
    )
    print(report.summary())
    if not report.ok:
        failures.append(f"round trip not bit-exact: {report.summary()}")
    if report.hops != FABRIC_HOPS:
        failures.append(f"expected {FABRIC_HOPS} hops, got {report.hops}")

    print("\n== 5. serve (3 tenants on one chip, one pcap-backed) ==")
    stream_n = 2 * n
    for mode in ("merged", "time_sliced"):
        sched = fleet.scheduler(mode=mode)
        res = sched.run(
            fleet.stream(stream_n, chunk_size=4096, seed=args.seed),
            chunk_size=4096,
        )
        print(sched.telemetry(res).render())
        for st in res.tenants:
            if st.packets != st.served + st.dropped:
                failures.append(
                    f"{mode} tenant {st.tid}: {st.packets} arrived != "
                    f"{st.served} served + {st.dropped} dropped"
                )
        # The pcap tenant's served packets ARE the capture replay: its
        # outputs must match the oracle on that exact subsequence.
        st = res.stats_for(0)
        replay = traffic.generate(SCENARIO_NAME, st.served, input_bits)
        want = exported.oracle_forward(replay)
        if not np.array_equal(res.outputs_for(0), want):
            failures.append(f"{mode}: pcap tenant outputs != oracle")
        else:
            print(
                f"{mode}: pcap tenant bit-exact vs oracle on "
                f"{st.served} replayed packets\n"
            )

    if failures:
        raise SystemExit("ACCEPTANCE FAILED: " + "; ".join(failures))
    print("acceptance: OK (file round trip, fabric + scheduler bit-exact)")


if __name__ == "__main__":
    main()
