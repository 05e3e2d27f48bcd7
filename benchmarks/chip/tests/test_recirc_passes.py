"""The reader of ``recirc_passes`` on hand-made traces: the ``passes``
argument of the program's ``dispatch`` phase spans inside the window."""
from types import SimpleNamespace as NS

import harness
import programspans
import pytest

reader = harness.load_module(harness.HERE, "metrics", "recirc_passes")


def ev(name, start, end, **stats):
    return NS(name=name, start_ns=float(start), duration_ns=float(end - start),
              stats=list(stats.items()))


def profile(events):
    return NS(planes=[NS(name="/host:CPU", lines=[NS(name="python", events=events)])])


def test_reads_the_windows_dispatch_spans():
    events = [
        ev("dispatch", -50, -10, chunk=0, passes=99),  # before the window
        ev("window", 0, 1000),
        ev("dispatch", 10, 20, chunk=0, passes=81, warm=True),
        ev("dispatch", 100, 200, chunk=0, passes=81),
        ev("dispatch", 300, 400, passes=7),  # no chunk: not the program's phase
        ev("d2h", 400, 500, chunk=0, passes=5),
    ]
    assert reader.passes_in(profile(events)) == 81


@pytest.mark.parametrize("events", [
    [ev("window", 0, 100), ev("dispatch", 10, 20, chunk=0)],  # a program without passes
    [ev("dispatch", 10, 20, chunk=0, passes=3)],  # no window
])
def test_nothing_to_read_is_none(events):
    assert reader.passes_in(profile(events)) is None


def test_an_untraced_run_or_a_missing_trace_reads_none(tmp_path, monkeypatch):
    assert reader.read(NS(trace=None)) is None
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    assert reader.read(NS(trace=object())) is None
    assert programspans.trace_dir() == tmp_path
