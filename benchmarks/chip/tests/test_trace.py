"""The trace reduction: on hand-made traces, and on a trace recorded on
a TPU v5e (``data/headline-flow.xplane.pb``: ``headline-flow`` through
the harness with a 0.05 s window)."""
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

import tracereduce

RECORDED = Path(__file__).parent / "data" / "headline-flow.xplane.pb"


def ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def profile(host_events, chips):
    planes = [NS(name="/host:CPU", lines=[NS(name="python", events=host_events)])]
    for i, ops in enumerate(chips):
        planes.append(NS(name=f"/device:TPU:{i}", lines=[
            NS(name="XLA Modules", events=[ev("module", 0, 10**9)]),
            NS(name="XLA Ops", events=ops),
        ]))
    return NS(planes=planes)


def test_union_and_gaps():
    busy = tracereduce.union([(5, 8), (1, 3), (2, 4), (8, 9)])
    assert busy == [[1, 4], [5, 9]]
    assert tracereduce.gaps(busy, 0, 12) == [(0, 1), (4, 5), (9, 12)]
    assert tracereduce.gaps(busy, 2, 6) == [(4, 5)]


def test_innermost_takes_the_deepest_covering_event():
    events = sorted([(0, 100, "a"), (10, 50, "b"), (20, 30, "c"), (60, 70, "d")])
    got = tracereduce.innermost(events, [5, 25, 40, 65, 80, 150])
    assert [g[2] if g else None for g in got] == ["a", "c", "b", "d", "a", None]


def test_reduce_busy_idle_and_labels():
    host = [
        ev("window", 0, 1000),
        ev("source.next", 0, 200),
        ev("entry.run", 200, 800),
        ev("TransferToDevice", 600, 300),
    ]
    chip0 = [ev("fusion", 300, 100), ev("copy", 350, 150), ev("before", -100, 50)]
    chip1 = [ev("fusion", 300, 400)]
    r = tracereduce.reduce_profile(profile(host, [chip0, chip1]), chips=2)
    assert r.window_s == pytest.approx(1000e-9)
    assert r.chip_busy_s == pytest.approx([200e-9, 400e-9])
    assert r.busy_s == pytest.approx(300e-9)
    assert dict(r.ops) == pytest.approx({"fusion": 500e-9, "copy": 150e-9})
    assert dict(r.idle) == pytest.approx({
        "source.next": 300e-9,
        "entry.run:TransferToDevice": 500e-9,
    })
    assert r.breakdown() == {
        "device_ops": [["fusion", pytest.approx(500e-9)], ["copy", pytest.approx(150e-9)]],
        "idle_gaps": [["entry.run:TransferToDevice", pytest.approx(500e-9)],
                      ["source.next", pytest.approx(300e-9)]],
    }


def test_reduce_uses_only_the_cells_chips():
    host = [ev("window", 0, 100)]
    r = tracereduce.reduce_profile(profile(host, [[ev("a", 0, 50)], [ev("a", 0, 100)]]), chips=1)
    assert r.busy_s == pytest.approx(50e-9)


def test_no_window_is_an_error():
    with pytest.raises(ValueError):
        tracereduce.reduce_profile(profile([ev("entry.run", 0, 5)], []), chips=1)


def test_recorded_chip_trace():
    r = tracereduce.reduce_file(RECORDED, chips=1)
    assert 0 < r.busy_s < r.window_s < 5
    assert r.chip_busy_s == [r.busy_s]
    idle = dict(r.idle)
    assert sum(idle.values()) == pytest.approx(r.window_s - r.busy_s)
    # the op-table kernel is recompiled inside every call of the stream path
    assert max(idle, key=idle.get) == "entry.run:backend_compile_and_load"
    top = r.breakdown()["device_ops"]
    assert "tpu_custom_call" in top[0][0]
    assert all(len(name) <= tracereduce.NAME_CHARS for name, _ in top)
    assert sum(s for _, s in r.ops) >= r.busy_s
