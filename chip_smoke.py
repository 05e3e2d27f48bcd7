#!/usr/bin/env python3
"""Smoke test of the served dataplane path on a TPU.

Drives the system once through the entry points a user calls —
``repro.dataplane.run`` / ``ExecutionPlan``, ``SwitchScheduler``,
``execute_fleet``, ``FleetEngine`` and the train->deploy loop — at the
paper's BNN widths, and compares every result packet for packet with the
``core.bnn.forward`` oracle evaluated on the host CPU.  Weights and traffic
come from ``--seed``.

    python chip_smoke.py             # phases (a)-(e) on one chip
    python chip_smoke.py --chips 4   # only the sharded fleet, on four chips

Each phase prints one line with the packets checked, the mismatches (must
be 0) and its wall seconds, compile included — smoke timing, not a
benchmark.  The last line is ``{"ok": true, "device": {...}}`` and appears
only when every phase passed.  Without a TPU, or outside a checkout of the
repository, the script exits non-zero before running anything.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
SMOKE_NOTE = "(smoke timing, not a benchmark)"


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Packet counts and step counts of every phase."""

    train_steps: int = 200
    held_out_per_class: int = 5000
    chunk: int = 1 << 15
    headline_packets: int = 1 << 20
    five_tuple_packets: int = 1 << 16
    neuron_2048_packets: int = 1 << 16
    fabric_hops: int = 5
    tenant_packets: int = 1 << 16
    tenant_chunk: int = 1 << 13
    fleet_streams: int = 16
    fleet_packets: int = 1 << 13     # per stream, before the per-stream skew
    fleet_chunk: int = 1 << 12
    obs_packets: int = 1 << 17


# Mixed-width tenants for phase (c): the paper's headline and 5-tuple
# models among smaller classifiers.
TENANT_SHAPES = (
    (32, 64, 32), (16, 32, 8), (128, 64, 32, 2), (64, 32, 4),
    (32, 16, 1), (48, 24, 2), (96, 48, 8), (24, 12, 3),
)


# ---------------------------------------------------------------------------
# Oracle and comparison
# ---------------------------------------------------------------------------

def oracle(weights, x, chunk: int = 1 << 15):
    """``core.bnn.forward`` on the host CPU, in chunks: a reference that
    shares no compiler with the device paths under test."""
    import jax
    import numpy as np

    from repro.core import bnn

    cpu = jax.devices("cpu")[0]
    ws = [jax.device_put(np.asarray(w, np.int32), cpu) for w in weights]
    fwd = jax.jit(lambda xb: bnn.forward(ws, xb))
    x = np.asarray(x, np.int32)
    n = x.shape[0]
    out = np.empty((n, ws[-1].shape[0]), np.int32)
    for s in range(0, n, chunk):
        block = x[s : s + chunk]
        pad = chunk - block.shape[0] if n > chunk else 0
        if pad:
            block = np.pad(block, ((0, pad), (0, 0)))
        res = np.asarray(fwd(jax.device_put(block, cpu)))
        out[s : s + chunk] = res[: res.shape[0] - pad]
    return out


class Tally:
    """Packets checked and row mismatches over one phase."""

    def __init__(self) -> None:
        self.checked = 0
        self.mismatches = 0
        self.failures: list[str] = []

    def compare(self, label: str, got, want) -> None:
        import numpy as np

        got, want = np.asarray(got), np.asarray(want)
        if got.shape != want.shape:
            self.failures.append(f"{label}: shape {got.shape} != {want.shape}")
            self.mismatches += max(want.shape[0], 1)
            return
        bad = int((got.astype(np.int64) != want).any(axis=1).sum())
        self.checked += want.shape[0]
        self.mismatches += bad
        if bad:
            self.failures.append(f"{label}: {bad} of {want.shape[0]} rows differ")

    def require(self, label: str, cond: bool) -> None:
        if not cond:
            self.failures.append(label)


def params_for(shape, seed: int):
    """Seeded {0,1} weights, made on the host CPU."""
    import jax
    import numpy as np

    from repro.core import bnn

    with jax.default_device(jax.devices("cpu")[0]):
        key = jax.random.PRNGKey(seed)
        return [np.asarray(w) for w in bnn.init_params(bnn.BnnSpec(shape), key)]


def kernel_in_auto_dispatch(lp, chunk: int) -> bool:
    """Whether the ``auto`` chunk dispatch compiles to the Pallas op-table
    kernel (a ``tpu_custom_call``), not the interpreter's plain HLO."""
    import jax
    import jax.numpy as jnp

    from repro.dataplane import executor

    backend = executor.resolve_backend("auto")
    fn = jax.jit(lambda p: executor._run_chunk(lp, p, backend, None))
    spec = jax.ShapeDtypeStruct((chunk, lp.input_bits), jnp.int32)
    return "tpu_custom_call" in fn.lower(spec).compile().as_text()


def chunks_of(x, chunk: int):
    for s in range(0, x.shape[0], chunk):
        yield x[s : s + chunk]


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------

def phase_train_deploy(sz: Sizes, seed: int, tally: Tally) -> dict:
    """(a) Train the STE BNN, export it, and serve held-out packets."""
    from repro.core.pipeline import ChipSpec
    from repro.dataplane import Backend, ExecutionPlan, SwitchFabric, run
    from repro.train.bnn_trainer import BnnTrainConfig, BnnTrainer

    trainer = BnnTrainer(
        BnnTrainConfig(
            steps=sz.train_steps,
            eval_packets_per_class=sz.held_out_per_class,
            seed=seed,
        )
    )
    summary = trainer.train()
    exported = trainer.export()
    x = trainer.eval_x
    want = oracle(exported.weights, x)
    tally.compare("STE forward vs oracle", trainer.forward_bits(x), want)
    for backend in (Backend.AUTO, Backend.PACKED, Backend.FUSED):
        got = run(exported.lowered, x, plan=ExecutionPlan(backend=backend))
        tally.compare(f"run[{backend.value}]", got, want)
    prog = exported.program
    per_hop = math.ceil(prog.num_elements / sz.fabric_hops)
    fab = SwitchFabric.partition(
        prog,
        chip=ChipSpec(
            num_elements=per_hop, phv_bits=prog.chip.phv_bits, name="hop"
        ),
    )
    tally.require(
        f"fabric has {fab.num_hops} hops, wanted {sz.fabric_hops}",
        fab.num_hops == sz.fabric_hops,
    )
    for backend in (Backend.AUTO, Backend.PACKED):
        res = run(
            fab, x, plan=ExecutionPlan(backend=backend, scan_hops=True)
        )
        tally.compare(f"fabric[{backend.value}, scanned]", res.outputs, want)
    acc = float((want[:, 0] == trainer.eval_y).mean())
    return {
        "steps": summary["final_step"],
        "held_out": int(x.shape[0]),
        "held_out_accuracy": round(acc, 4),
        "hops": fab.num_hops,
    }


def phase_paper_widths(sz: Sizes, seed: int, tally: Tally) -> dict:
    """(b) HEADLINE, FIVE_TUPLE and SINGLE_NEURON_2048 through the stream
    path under ``auto`` and ``packed``."""
    from repro.configs import n2net_paper
    from repro.core import compile_bnn
    from repro.dataplane import (
        Backend,
        ExecutionPlan,
        featurize,
        generate,
        lower_program,
        pcap,
        run,
    )
    from repro.dataplane.executor import resolve_backend

    packets = {
        "HEADLINE": generate(
            "flow_tuple", sz.headline_packets, 32, seed=seed
        ),
        "FIVE_TUPLE": featurize(
            pcap.read_pcap(
                pcap.write_pcap(
                    *pcap.synthesize_capture(sz.five_tuple_packets, seed)[:2]
                )
            ),
            input_bits=128,
        ),
        "SINGLE_NEURON_2048": generate(
            "uniform_random", sz.neuron_2048_packets, 2048, seed=seed
        ),
    }
    detail = {"auto_resolves_to": resolve_backend("auto")}
    for i, (name, x) in enumerate(packets.items()):
        spec = getattr(n2net_paper, name)
        weights = params_for(spec.layer_sizes, seed + i)
        lp = lower_program(compile_bnn(weights))
        want = oracle(weights, x)
        for backend in (Backend.AUTO, Backend.PACKED):
            res = run(
                lp,
                chunks_of(x, sz.chunk),
                plan=ExecutionPlan(
                    backend=backend, chunk_size=sz.chunk, collect=True
                ),
            )
            tally.compare(f"{name}[{backend.value}]", res.outputs, want)
        kernel = kernel_in_auto_dispatch(lp, sz.chunk)
        tally.require(f"{name}: no tpu_custom_call in the auto dispatch", kernel)
        detail[name] = {"packets": int(x.shape[0]), "kernel": kernel}
    return detail


def phase_tenancy(sz: Sizes, seed: int, tally: Tally) -> dict:
    """(c) Eight mixed-width tenants through the scheduler's three modes."""
    import numpy as np

    from repro.core import compile_bnn
    from repro.dataplane import (
        SCENARIOS,
        Backend,
        ExecutionPlan,
        FleetSpec,
        TenantSpec,
        build_fleet,
        mixed_tenant_generate,
        run,
    )

    scenarios = sorted(SCENARIOS)
    weights = [params_for(s, seed + 100 + t) for t, s in enumerate(TENANT_SHAPES)]
    fleet = build_fleet(
        FleetSpec(
            tenants=tuple(
                TenantSpec(
                    f"t{t}",
                    scenarios[t % len(scenarios)],
                    program=compile_bnn(w),
                    weight=1.0 + (t % 3),
                )
                for t, w in enumerate(weights)
            )
        )
    )
    tids, bits = mixed_tenant_generate(
        fleet.traffic_specs, sz.tenant_packets, seed=seed
    )
    wants = [
        oracle(w, bits[tids == t, : s[0]])
        for t, (w, s) in enumerate(zip(weights, TENANT_SHAPES))
    ]
    modes = (
        ("merged", "interleave"), ("merged", "concat"), ("time_sliced", None)
    )
    for mode, layout in modes:
        for backend in (Backend.PACKED, Backend.AUTO):
            sched = fleet.scheduler(mode=mode)
            stream = (
                (tids[s : s + sz.tenant_chunk], bits[s : s + sz.tenant_chunk])
                for s in range(0, tids.shape[0], sz.tenant_chunk)
            )
            res = run(
                sched,
                stream,
                plan=ExecutionPlan(
                    backend=backend, chunk_size=sz.tenant_chunk, merged=layout
                ),
            )
            label = f"{mode}{'-' + layout if layout else ''}[{backend.value}]"
            for t, st in enumerate(res.tenants):
                tally.compare(f"{label} tenant {t}", st.outputs, wants[t])
    return {
        "tenants": len(TENANT_SHAPES),
        "packets": int(tids.shape[0]),
        "per_tenant": np.bincount(tids, minlength=len(TENANT_SHAPES)).tolist(),
    }


def fleet_streams(sz: Sizes, seed: int, input_bits: int) -> list:
    """Streams of unequal length (so short ones are padded) from every
    scenario of the library."""
    from repro.dataplane import SCENARIOS, generate

    scenarios = sorted(SCENARIOS)
    return [
        generate(
            scenarios[i % len(scenarios)],
            sz.fleet_packets + 517 * i,
            input_bits,
            seed=seed + i,
        )
        for i in range(sz.fleet_streams)
    ]


def headline_program(seed: int):
    from repro.configs import n2net_paper
    from repro.core import compile_bnn
    from repro.dataplane import lower_program

    weights = params_for(n2net_paper.HEADLINE.layer_sizes, seed)
    return weights, lower_program(compile_bnn(weights))


def phase_fleet(sz: Sizes, seed: int, tally: Tally) -> dict:
    """(d) Sixteen streams through ``execute_fleet`` and ``FleetEngine``."""
    from repro.dataplane import Backend, ExecutionPlan, run
    from repro.serving.engine import FleetEngine

    weights, lp = headline_program(seed)
    streams = fleet_streams(sz, seed, lp.input_bits)
    wants = [oracle(weights, s) for s in streams]
    for backend in (Backend.AUTO, Backend.PACKED):
        plan = ExecutionPlan(
            backend=backend,
            chunk_size=sz.fleet_chunk,
            fleet=len(streams),
            collect=True,
        )
        res = run(lp, streams, plan=plan)
        served = FleetEngine(lp, plan=plan).serve(streams, collect=True)
        for i, want in enumerate(wants):
            tally.compare(f"execute_fleet[{backend.value}] stream {i}",
                          res.outputs[i], want)
            tally.compare(f"FleetEngine[{backend.value}] stream {i}",
                          served.outputs[i], want)
    return {"streams": len(streams), "packets": sum(s.shape[0] for s in streams)}


def phase_observability(sz: Sizes, seed: int, tally: Tally) -> dict:
    """(e) The stream, fleet and ``FleetEngine`` paths again with
    ``repro.obs`` on: outputs stay exact, every served chunk passes through
    each phase span once, lowerings are counted per span, and
    ``FleetEngine``'s roofline probe reports a bound for the TPU paths
    without failing."""
    from repro import obs
    from repro.dataplane import Backend, ExecutionPlan, generate, run
    from repro.dataplane.executor import PHASES, resolve_backend
    from repro.serving.engine import FleetEngine

    weights, lp = headline_program(seed)
    x = generate("ddos_burst", sz.obs_packets, lp.input_bits, seed=seed)
    want = oracle(weights, x)
    streams = fleet_streams(sz, seed, lp.input_bits)
    fleet_wants = [oracle(weights, s) for s in streams]
    chunks = 0
    bounds = {}
    obs.enable(reset=True)
    try:
        for backend in (Backend.AUTO, Backend.PACKED):
            res = run(
                lp,
                chunks_of(x, sz.chunk),
                plan=ExecutionPlan(
                    backend=backend, chunk_size=sz.chunk, collect=True
                ),
            )
            tally.compare(f"obs stream[{backend.value}]", res.outputs, want)
            plan = ExecutionPlan(
                backend=backend,
                chunk_size=sz.fleet_chunk,
                fleet=len(streams),
                collect=True,
            )
            fl = run(lp, streams, plan=plan)
            eng = FleetEngine(lp, plan=plan)
            served = eng.serve(streams, collect=True)
            for i, w in enumerate(fleet_wants):
                tally.compare(
                    f"obs fleet[{backend.value}] stream {i}", fl.outputs[i], w
                )
                tally.compare(
                    f"obs FleetEngine[{backend.value}] stream {i}",
                    served.outputs[i], w,
                )
            bounds[backend.value] = eng.health().roofline_pps_bound
            chunks += res.chunks + fl.chunks
        snap = obs.registry().snapshot()
        records = list(obs.tracer().records)
        errors = obs.registry().counter("roofline.probe_errors_total").value
    finally:
        obs.disable()
    served = [r for r in records if r.cat == "phase" and not r.args.get("warm")]
    for phase in PHASES:
        # one of each per served chunk; one more ingest per run finds it dry
        extra = 4 if phase == "ingest" else 0
        n = sum(r.name == phase for r in served)
        tally.require(f"{n} {phase} spans for {chunks} chunks", n == chunks + extra)
    lowerings = {
        row["labels"].get("span"): row["value"]
        for row in snap
        if row["name"] == "jax.lowerings_total"
    }
    tally.require(f"roofline.probe_errors_total = {errors}", errors == 0)
    missing = sorted(b for b, bound in bounds.items() if bound is None)
    tally.require(f"FleetEngine.health() has no roofline bound for {missing}",
                  not missing)
    return {
        "probe_errors": errors,
        "fleet_pps_bound": {
            f"fleet{len(streams)}:{resolve_backend(b)}": v for b, v in bounds.items()
        },
        "lowerings_by_span": lowerings,
    }


def phase_sharded_fleet(sz: Sizes, seed: int, tally: Tally) -> dict:
    """``--chips 4``: the stream axis sharded over four devices, against
    the same fleet on one device."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.dataplane import Backend, ExecutionPlan, fleet_fn, run

    weights, lp = headline_program(seed)
    streams = fleet_streams(sz, seed, lp.input_bits)
    wants = [oracle(weights, s) for s in streams]
    detail = {}
    for backend in (Backend.AUTO, Backend.PACKED):
        outs = {}
        for devices in (None, 4):
            plan = ExecutionPlan(
                backend=backend,
                chunk_size=sz.fleet_chunk,
                fleet=len(streams),
                devices=devices,
                collect=True,
            )
            outs[devices] = run(lp, streams, plan=plan).outputs
        for i, want in enumerate(wants):
            tally.compare(f"devices=4[{backend.value}] stream {i}",
                          outs[4][i], want)
            tally.compare(f"devices=None[{backend.value}] stream {i}",
                          outs[None][i], want)
        block = np.zeros(
            (len(streams), sz.fleet_chunk, lp.input_bits), np.int32
        )
        out = fleet_fn(lp, backend=backend, devices=4)(jnp.asarray(block))
        shards = out.addressable_shards
        spread = {s.device.id: s.data.shape[0] for s in shards}
        tally.require(
            f"{backend.value}: output shards {spread}, wanted 4 devices "
            f"holding {len(streams) // 4} streams each",
            len(spread) == 4
            and all(v == len(streams) // 4 for v in spread.values()),
        )
        detail[backend.value] = {"streams_per_device": spread}
    detail["devices"] = [str(d) for d in jax.devices()[:4]]
    return detail


PHASES = (
    ("a_train_deploy", phase_train_deploy),
    ("b_paper_widths", phase_paper_widths),
    ("c_tenancy", phase_tenancy),
    ("d_fleet", phase_fleet),
    ("e_observability", phase_observability),
)


def run_phases(phases, sz: Sizes, seed: int) -> bool:
    """Run every phase, print one line each, and say whether all passed."""
    ok = True
    for name, fn in phases:
        tally = Tally()
        t0 = time.perf_counter()
        try:
            detail = fn(sz, seed, tally)
        except Exception as e:  # noqa: BLE001 - report, then fail the run
            traceback.print_exc()
            tally.failures.append(f"{type(e).__name__}: {e}")
            detail = {}
        wall = time.perf_counter() - t0
        passed = not tally.failures and tally.mismatches == 0
        ok &= passed
        print(
            f"phase {name}: packets_checked={tally.checked} "
            f"mismatches={tally.mismatches} wall_s={wall:.3f} "
            f"{'PASS' if passed else 'FAIL'} {json.dumps(detail)} "
            f"{SMOKE_NOTE}",
            flush=True,
        )
        for f in tally.failures:
            print(f"  failure: {f}", file=sys.stderr, flush=True)
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4: run only the sharded fleet, across four chips",
    )
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(
            f"chip_smoke: no TPU (JAX sees {devices[0].platform}); "
            "nothing was run",
            file=sys.stderr,
        )
        return 1
    if len(devices) < args.chips:
        print(
            f"chip_smoke: --chips {args.chips} but JAX sees {len(devices)} "
            "device(s)",
            file=sys.stderr,
        )
        return 1
    src = os.path.join(HERE, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(
            f"chip_smoke: {src}/repro not found; run from a checkout of the "
            "repository",
            file=sys.stderr,
        )
        return 1
    sys.path.insert(0, src)
    from repro import compile_cache

    compile_cache.enable()
    phases = (
        (("sharded_fleet", phase_sharded_fleet),) if args.chips == 4 else PHASES
    )
    if not run_phases(phases, Sizes(), args.seed):
        return 1
    print(json.dumps({
        "ok": True,
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind, "count": len(devices)},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
