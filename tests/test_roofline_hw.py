"""Peaks keyed by device kind, and the fail-soft roofline probes."""
import math
import types

import jax
import numpy as np
import pytest

from repro import obs
from repro.core import bnn, compile_bnn
from repro.dataplane import ExecutionPlan, lower_program
from repro.roofline import dataplane as roofline_dp
from repro.roofline import hw
from repro.serving.engine import FleetEngine


def _device(platform: str, kind: str):
    return types.SimpleNamespace(platform=platform, device_kind=kind)


def _roofline(peaks, **kw):
    base = dict(
        path="packed", fingerprint="f", chunk=1000, streams=1,
        hlo_flops=0.0, hlo_bytes=819e6, collective_bytes=0.0, peaks=peaks,
    )
    base.update(kw)
    return roofline_dp.DataplaneRoofline(**base)


@pytest.fixture
def obs_on():
    obs.enable(reset=True)
    yield obs.registry()
    obs.disable()


def test_v5e_peaks_by_name():
    p = hw.peaks("TPU v5 lite")
    assert (p.flops_bf16, p.ops_int8, p.hbm_bw) == (197e12, 393e12, 819e9)
    assert p.ici_link_bw == 50e9  # 1,600 Gbit/s over four links


@pytest.mark.parametrize(
    "device, expect",
    [
        (_device("cpu", "cpu"), None),
        (_device("tpu", "TPU v5 lite"), hw.peaks("TPU v5 lite")),
    ],
    ids=["cpu", "v5e"],
)
def test_device_peaks(device, expect):
    assert hw.device_peaks(device) == expect


def test_unknown_tpu_kind_raises_instead_of_assuming_v5e():
    with pytest.raises(hw.UnknownDeviceError, match="TPU v99"):
        hw.device_peaks(_device("tpu", "TPU v99"))


def test_v5e_memory_roofline_arithmetic():
    rf = _roofline(hw.peaks("TPU v5 lite"))
    assert rf.memory_s == pytest.approx(1e-3)
    assert rf.bottleneck == "memory"
    assert rf.roofline_pps == pytest.approx(1e6)
    assert rf.fraction(2.5e5) == pytest.approx(0.25)


def test_no_peaks_means_no_bound(obs_on):
    rf = _roofline(None)
    assert math.isinf(rf.roofline_pps) and rf.bottleneck == "unknown"
    assert rf.fraction(1e6) == 0.0
    roofline_dp.record(rf, measured_pps=1e6)
    names = {row["name"] for row in obs_on.snapshot()}
    assert "roofline.hlo_bytes" in names
    assert not names & {"roofline.pps_bound", "roofline.fraction"}


def test_failed_probe_is_counted_and_run_stays_exact(obs_on, monkeypatch):
    """``FleetEngine`` is the one path that still probes: a probe that
    fails is counted, the serve's outputs stay exact, and ``health()``
    reports no bound."""
    def refuse(device):
        raise hw.UnknownDeviceError("no peaks")

    monkeypatch.setattr(hw, "device_peaks", refuse)
    params = bnn.init_params(bnn.BnnSpec((16, 8, 4)), jax.random.PRNGKey(3))
    lp = lower_program(compile_bnn([np.asarray(w) for w in params]))
    rng = np.random.default_rng(0)
    streams = [rng.integers(0, 2, (n, 16)).astype(np.int32) for n in (300, 170)]
    eng = FleetEngine(
        lp, plan=ExecutionPlan(backend="jnp", fleet=2, chunk_size=128)
    )
    res = eng.serve(streams, collect=True)
    for got, x in zip(res.outputs, streams):
        np.testing.assert_array_equal(got, np.asarray(bnn.forward(params, x)))
    assert obs_on.counter("roofline.probe_errors_total").value == 1
    assert eng.health().roofline_pps_bound is None
