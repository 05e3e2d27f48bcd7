"""The traffic scenarios the benchmark's generators draw from.

Copied from ``repro.dataplane.traffic`` so that a later change to the
program cannot change the traffic the benchmark offers.  At the copy,
``generate`` gave the same bits as ``repro.dataplane.traffic.generate``
at the same seed (``tests/test_traffic.py`` holds them together while
the program keeps its own copy).

A scenario is a ``setup`` (the trace's persistent world: flow pool,
attacker signature, device fleet) plus an ``emit`` over an absolute packet
range.  The sequence is defined over fixed canonical emission chunks: the
chunk starting at packet ``p`` draws from ``default_rng([seed, 1, p])``
and the world from ``default_rng([seed, 0])``.  Every emitter returns
``(n, input_bits)`` int32 in {0,1}.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Iterator

import numpy as np

# Canonical 5-tuple layout: src ip (32) dst ip (32) ports (16+16) proto (8).
_TUPLE_BITS = 104

# The packet sequence is defined over emission chunks of this many packets;
# chunk ``p`` draws from ``default_rng([seed, _EMIT_TAG, p])``.  Part of the
# sequence definition: changing it changes every scenario's packets.
CANONICAL_CHUNK = 1024
_SETUP_TAG = 0
_EMIT_TAG = 1


def _fold_bits(bits: np.ndarray, width: int) -> np.ndarray:
    """XOR-fold (n, k) bit rows to exactly ``width`` columns.

    Wider rows fold back onto themselves (hash-like, parity-preserving per
    column); narrower rows tile.  Keeps every scenario usable at any model
    input width.
    """
    n, k = bits.shape
    if n == 0:
        return np.zeros((0, width), np.int32)
    if k < width:
        reps = -(-width // k)
        bits = np.tile(bits, (1, reps))
        k = bits.shape[1]
    if k == width:
        return bits.astype(np.int32)
    pad = (-k) % width
    if pad:
        bits = np.concatenate([bits, np.zeros((n, pad), bits.dtype)], axis=1)
    # XOR-reduce == per-column parity of the sum for {0,1} entries, at a
    # fraction of the cost (this is the pcap featurizer's hot loop too).
    return np.bitwise_xor.reduce(
        bits.reshape(n, -1, width).astype(np.int32), axis=1
    )


def _int_bits(vals: np.ndarray, width: int) -> np.ndarray:
    """(n,) unsigned ints -> (n, width) little-endian bits."""
    # uint32 math is exact for the bits we keep (<= 32) and much faster.
    dtype = np.uint32 if width <= 32 else np.uint64
    shifts = np.arange(width, dtype=dtype)
    return ((vals[:, None].astype(dtype) >> shifts) & 1).astype(np.int32)


def _gray(vals: np.ndarray) -> np.ndarray:
    v = vals.astype(np.uint64)
    return v ^ (v >> 1)


@dataclasses.dataclass(frozen=True)
class Scenario:
    """``setup(rng, bits) -> state`` once per trace, then
    ``emit(state, rng, start, n, bits)`` over absolute packet positions
    ``[start, start + n)``.  ``state`` may be mutable (e.g. sensor walks).

    Emission happens in canonical ``CANONICAL_CHUNK``-packet chunks with a
    per-chunk rng derived from ``(seed, chunk position)`` — see the module
    docstring — so the sequence is identical under any consumer chunking.
    """

    name: str
    description: str
    _setup: Callable[[np.random.Generator, int], Any]
    _emit: Callable[[Any, np.random.Generator, int, int, int], np.ndarray]

    def iter_chunks(
        self, input_bits: int, seed: int = 0
    ) -> Iterator[np.ndarray]:
        """Infinite iterator over the canonical emission chunks of one world."""
        if input_bits <= 0:
            raise ValueError(f"input_bits must be positive, got {input_bits}")
        state = self._setup(
            np.random.default_rng([seed, _SETUP_TAG]), input_bits
        )
        start = 0
        while True:
            rng = np.random.default_rng([seed, _EMIT_TAG, start])
            yield self._emit(state, rng, start, CANONICAL_CHUNK, input_bits)
            start += CANONICAL_CHUNK

    def generate(self, n: int, input_bits: int, seed: int = 0) -> np.ndarray:
        """(n, input_bits) int32 {0,1} packet activation bits."""
        if n < 0 or input_bits <= 0:
            raise ValueError(f"bad trace shape n={n} input_bits={input_bits}")
        if n == 0:
            return np.zeros((0, input_bits), np.int32)
        chunks = []
        have = 0
        for c in self.iter_chunks(input_bits, seed):
            chunks.append(c)
            have += c.shape[0]
            if have >= n:
                break
        return np.concatenate(chunks, axis=0)[:n]


# -- scenario implementations -----------------------------------------------

def _uniform_emit(state, rng, start, n, bits):
    return rng.integers(0, 2, (n, bits), dtype=np.int32)


def _flow_setup(rng, bits):
    n_flows = 256
    # Flow pool: random 5-tuples; popularity ~ 1/rank (elephants and mice).
    pool = _fold_bits(
        rng.integers(0, 2, (n_flows, _TUPLE_BITS), dtype=np.int32), bits
    )
    rank = np.arange(1, n_flows + 1, dtype=np.float64)
    p = (1.0 / rank) / (1.0 / rank).sum()
    return pool, p


def _flow_emit(state, rng, start, n, bits):
    pool, p = state
    return pool[rng.choice(pool.shape[0], size=n, p=p)]


def _ddos_setup(rng, bits):
    return rng.integers(0, 2, bits, dtype=np.int32)  # attacker signature


def _ddos_emit(state, rng, start, n, bits):
    period, burst_len = 1024, 256
    out = rng.integers(0, 2, (n, bits), dtype=np.int32)  # background
    pos = start + np.arange(n)  # burst phase follows *global* position
    in_burst = (pos % period) < burst_len
    jitter = rng.random((n, bits)) < 0.02  # per-bit flip prob inside a burst
    attack = np.where(jitter, 1 - state[None, :], state[None, :])
    out[in_burst] = attack[in_burst]
    return out


def _iot_setup(rng, bits):
    n_dev = 32
    return {"level": rng.integers(0, 1 << 16, n_dev)}  # walks continue


def _iot_emit(state, rng, start, n, bits):
    n_dev = state["level"].shape[0]
    dev = rng.integers(0, n_dev, n)
    steps = rng.integers(-3, 4, n)
    drift = np.zeros(n, np.int64)
    for d in range(n_dev):  # per-device cumulative walk from carried level
        sel = dev == d
        walk = state["level"][d] + np.cumsum(steps[sel])
        drift[sel] = walk
        if walk.size:
            state["level"][d] = walk[-1]
    reading = _gray(drift.astype(np.uint64) & 0xFFFF)
    header = np.concatenate(
        [_int_bits(dev.astype(np.uint64), 8), _int_bits(reading, 16)], axis=1
    )
    return _fold_bits(header, bits)


def _adv_setup(rng, bits):
    return rng.integers(0, 2, (8, bits), dtype=np.int32)  # prototypes


def _adv_emit(state, rng, start, n, bits):
    out = state[rng.integers(0, state.shape[0], n)].copy()
    k = max(1, bits // 16)  # flips per packet
    flips = rng.integers(0, bits, (n, k))
    rows = np.repeat(np.arange(n), k)
    np.add.at(out, (rows, flips.ravel()), 1)
    return (out % 2).astype(np.int32)


SCENARIOS: dict[str, Scenario] = {
    s.name: s
    for s in (
        Scenario(
            "uniform_random",
            "i.i.d. fair-coin bits",
            lambda rng, bits: None,
            _uniform_emit,
        ),
        Scenario(
            "flow_tuple",
            "heavy-tailed 5-tuple flow pool (flow classification)",
            _flow_setup,
            _flow_emit,
        ),
        Scenario(
            "ddos_burst",
            "background + periodic jittered attack bursts",
            _ddos_setup,
            _ddos_emit,
        ),
        Scenario(
            "iot_telemetry",
            "small device fleet, Gray-coded drifting sensor readings",
            _iot_setup,
            _iot_emit,
        ),
        Scenario(
            "adversarial_bitflip",
            "prototype headers with sparse random bit flips",
            _adv_setup,
            _adv_emit,
        ),
    )
}
