"""Mode ``fleet``: many independent switches, one model, one dispatch.

The window is one call of ``run(lowered, streams, plan)`` with
``plan.fleet`` streams (``fleet.execute_fleet``; with ``plan.devices`` the
stream axis is sharded over that many chips).  Stream ``i`` replays the
pool from row ``i * pool / streams`` onward, so no two streams carry the
same packets at once.  Stream 0 watches the deadline and the others stop
with it, so every stream serves the same number of chunks.

The plan collects each stream's verdicts (``collect``), and every verdict
bit of every stream is compared with the reference over the packets that
stream replayed, which covers the per-stream padding and the placement of
streams on chips.
"""
from __future__ import annotations

import types

import numpy as np

import sut


class Driver:
    def __init__(self, system):
        if len(system.weights) != 1:
            raise ValueError("mode 'fleet' serves one model")
        self.system = system
        self.plan = sut.plan(system.plan)
        self.lp = sut.lowered(system.weights[0])
        pool = system.bits.shape[0]
        self.streams = self.plan.fleet
        self.chunk = self.plan.chunk_size
        if system.traffic.get("streams", self.streams) != self.streams:
            raise ValueError("the traffic's streams and plan.fleet differ")
        if pool % (self.streams * self.chunk):
            raise ValueError("the pool must split into whole chunks per stream")
        self.starts = [i * pool // self.streams for i in range(self.streams)]
        self.replays = [
            sut.Replay((system.bits,), self.chunk, system.annotate)
            for _ in range(self.streams)
        ]
        self.entry = lambda streams: sut.run(self.lp, streams, self.plan)
        self.result = None

    def sources(self, stop) -> list:
        halted = [False]

        def lead() -> bool:
            halted[0] = halted[0] or stop()
            return halted[0]

        return [
            r.slices(s, lead if i == 0 else (lambda: halted[0]))
            for i, (r, s) in enumerate(zip(self.replays, self.starts))
        ]

    def warm(self) -> None:
        self.entry(self.sources(sut.after(2)))

    def window(self, seconds: float) -> dict:
        for r in self.replays:
            r.offered = 0
        streams = self.sources(sut.deadline(seconds))
        self.result, dt = sut.timed(self.system.annotate, self.entry, streams)
        return {
            "attempted": sum(r.offered for r in self.replays),
            "packets": int(self.result.packets),
            "seconds": dt,
        }

    def check(self, forward) -> dict:
        ref = forward(self.system.weights[0], self.system.bits)
        outs = self.result.outputs
        return {
            "packets_gap": (sum(
                abs(o.shape[0] - r.offered) for o, r in zip(outs, self.replays)
            ), 0),
            "bits_wrong": (sum(
                sut.wrong_bits(o, ref, s) for o, s in zip(outs, self.starts)
            ), 0),
        }


def control_entry(driver: Driver, forward):
    """The entry with ``forward`` in the program's place, returning what
    the program returns: the packets verdicted and each stream's verdicts."""
    weights = driver.system.weights[0]

    def entry(streams):
        outs = [[] for _ in streams]
        live = list(range(len(streams)))
        while live:
            for i in list(live):
                block = next(streams[i], None)
                if block is None:
                    live.remove(i)
                    continue
                outs[i].append(forward(weights, block))
        outputs = [np.concatenate(o) for o in outs]
        return types.SimpleNamespace(
            packets=sum(o.shape[0] for o in outputs), outputs=outputs
        )

    return entry
