"""Each cell, driven through the harness with the timed path broken
underneath (the chip look skipped), comes out as not correct: once for
each fault the cell can have."""
import jax.numpy as jnp
import pytest

import harness
import tiny
from repro.dataplane import executor, fleet


@pytest.fixture(autouse=True)
def own_cache(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "CACHE_DIR", tmp_path / "jax_cache")


def unchanged(out, packets):
    """The state passes through untouched: verdicts are the input bits."""
    k = min(out.shape[-1], packets.shape[-1])
    return jnp.zeros_like(out).at[..., :k].set(packets[..., :k].astype(out.dtype))


def half_left_out(out, packets):
    """The second half of each batch is never computed."""
    rows = out.shape[-2]
    return out.at[..., rows // 2 :, :].set(0)


def answer_altered(out, packets):
    """One verdict bit flipped where it is produced."""
    first = (0,) * (out.ndim - 1) + (0,)
    return out.at[first].set(1 - out[first])


def exchange_left_out(out, packets):
    """Every chip's share replaced by the first chip's."""
    share = out.shape[0] // 4
    return jnp.concatenate([out[:share]] * 4)


def plant(monkeypatch, mode: str, fault):
    if mode == "stream":
        real = executor._run_chunk

        def broken(lp, packets, *a, **k):
            return fault(real(lp, packets, *a, **k), packets)

        monkeypatch.setattr(executor, "_run_chunk", broken)
    elif mode == "fleet":
        real = fleet.fleet_fn

        def broken_fleet(*a, **k):
            fn = real(*a, **k)
            return lambda blocks: fault(fn(blocks), blocks)

        monkeypatch.setattr(fleet, "fleet_fn", broken_fleet)
    else:
        raise ValueError(mode)


CASES = [
    (name, fault)
    for name in tiny.CELLS
    for fault in (unchanged, half_left_out, answer_altered)
] + [("headline-fleet16", exchange_left_out)]


@pytest.mark.parametrize(
    "name,fault", CASES, ids=[f"{n}-{f.__name__}" for n, f in CASES]
)
def test_fault_is_not_correct(monkeypatch, name, fault):
    c = tiny.cell(name)
    plant(monkeypatch, c.workload["mode"], fault)
    line = tiny.run(c, seconds=0.3)
    assert line["correct"] is False, line["checks"]
