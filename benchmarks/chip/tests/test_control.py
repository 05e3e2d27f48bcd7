"""The control, put in the program's place, comes out as not correct in
every cell; the program, at the same size, comes out correct."""
import pytest

import harness
import tiny


@pytest.fixture(autouse=True)
def own_cache(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "CACHE_DIR", tmp_path / "jax_cache")


@pytest.mark.parametrize("name", tiny.CELLS)
@pytest.mark.parametrize("seed", [3, 2**31 + 3])
def test_control_is_not_correct(name, seed):
    line = tiny.run(tiny.cell(name), seed=seed, seconds=0.3, control=True)
    assert line["correct"] is False, line["checks"]
