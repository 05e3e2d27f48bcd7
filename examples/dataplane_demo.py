"""Dataplane walkthrough: a million packets through the switch fleet.

Part 1 — the paper's headline model (32b activations, layers 64+32) fits a
single RMT pipeline pass; we stream 1M DDoS-burst packets through the fused
op-table executor and compare the simulator's measured packets/s with the
ASIC's analytic 960M pkt/s.

Part 2 — a model too big for one chip (64b activations, layers 128+32) is
partitioned across a simulated switch chain, once as a multi-hop fabric
(line rate preserved, latency grows) and once as recirculation on a single
switch (throughput divides by passes), with per-stage telemetry for both.

Run:  PYTHONPATH=src python examples/dataplane_demo.py [--packets 1000000]
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro import compile_cache
from repro.core import bnn, compile_bnn
from repro.dataplane import (
    SwitchFabric,
    execute_stream,
    lower_program,
    traffic,
)


def main() -> None:
    compile_cache.enable()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--packets", type=int, default=1_000_000)
    ap.add_argument(
        "--scenario", default="ddos_burst", choices=sorted(traffic.SCENARIOS)
    )
    args = ap.parse_args()

    # -- part 1: headline model at line rate --------------------------------
    spec = bnn.BnnSpec((32, 64, 32))
    params = bnn.init_params(spec, jax.random.PRNGKey(0))
    prog = compile_bnn([np.asarray(w) for w in params])
    lp = lower_program(prog)
    print("== headline model ==")
    print(prog.summary())
    print(lp.summary())

    print(f"\nstreaming {args.packets} '{args.scenario}' packets ...")
    sr = execute_stream(
        lp,
        traffic.stream(args.scenario, args.packets, 32, chunk_size=1 << 15),
        chunk_size=1 << 15,
    )
    print(
        f"  {sr.packets} packets in {sr.seconds:.2f}s "
        f"-> {sr.packets_per_second:.3e} pkt/s (simulated)"
    )
    fab = SwitchFabric.partition(prog)
    print(fab.telemetry().render())
    hot = sr.bit_counts.argmax()
    print(
        f"  Y-bit histogram: bit {hot} fired most "
        f"({sr.bit_counts[hot]}/{sr.packets} packets)"
    )

    # -- part 2: a model that outgrows one chip -----------------------------
    big = bnn.BnnSpec((64, 128, 32))
    big_params = bnn.init_params(big, jax.random.PRNGKey(1))
    big_prog = compile_bnn([np.asarray(w) for w in big_params])
    n = max(1, args.packets // 10)
    x = traffic.generate(args.scenario, n, 64, seed=1)
    print(f"\n== partitioned model ({big_prog.num_elements} elements) ==")
    for mode in ("multi_hop", "recirculate"):
        fab = SwitchFabric.partition(big_prog, mode=mode)
        res = fab.run(x)
        print(fab.telemetry(res).render())


if __name__ == "__main__":
    main()
