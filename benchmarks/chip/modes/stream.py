"""Mode ``stream``: one model over a replayed packet stream.

The window is one call of ``run(lowered, chunks, plan)``, which streams
the chunks through ``executor.execute_stream``: ingest from the pool, the
host-to-device copy, dispatch, the copy back and the program's per-chunk
bookkeeping all fall inside it.  With ``collect`` off the entry returns
the packets it verdicted and the ones per verdict bit; both are compared,
exactly, with the reference over the same replayed packets.
"""
from __future__ import annotations

import types

import numpy as np

import sut


class Driver:
    def __init__(self, system):
        if len(system.weights) != 1:
            raise ValueError("mode 'stream' serves one model")
        self.system = system
        self.plan = sut.plan(system.plan)
        self.lp = sut.lowered(system.weights[0])
        self.replay = sut.Replay((system.bits,), self.plan.chunk_size, system.annotate)
        self.entry = lambda source: sut.run(self.lp, source, self.plan)
        self.result = None

    def warm(self) -> None:
        self.entry(self.replay.slices(0, sut.after(2)))

    def window(self, seconds: float) -> dict:
        self.replay.offered = 0
        source = self.replay.slices(0, sut.deadline(seconds))
        self.result, dt = sut.timed(self.system.annotate, self.entry, source)
        return {
            "attempted": self.replay.offered,
            "packets": int(self.result.packets),
            "seconds": dt,
        }

    def check(self, forward) -> dict:
        ref = forward(self.system.weights[0], self.system.bits)
        got = self.result.outputs
        return {
            "packets_gap": (abs(got.shape[0] - self.replay.offered), 0),
            "bits_wrong": (sut.wrong_bits(got, ref, 0), 0),
        }


def control_entry(driver: Driver, forward):
    """The entry with ``forward`` in the program's place, returning what
    the program returns: the packets verdicted and their verdicts."""
    weights = driver.system.weights[0]

    def entry(source):
        outs = [forward(weights, block) for block in source]
        return types.SimpleNamespace(packets=sum(o.shape[0] for o in outs),
                                     outputs=np.concatenate(outs))

    return entry
