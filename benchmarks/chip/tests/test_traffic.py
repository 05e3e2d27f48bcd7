"""The benchmark's copies of the traffic scenarios: deterministic in the
seed, and (while the program keeps its own copy) the same bits."""
import numpy as np
import pytest

from traffic import library

SEED = 2**31 + 77


@pytest.mark.parametrize("name", sorted(library.SCENARIOS))
def test_scenario_is_deterministic_in_the_seed(name):
    a = library.SCENARIOS[name].generate(3000, 32, SEED)
    b = library.SCENARIOS[name].generate(3000, 32, SEED)
    c = library.SCENARIOS[name].generate(3000, 32, SEED + 1)
    assert a.shape == (3000, 32) and a.dtype == np.int32
    assert np.array_equal(a, b)
    assert not np.array_equal(a, c)


@pytest.mark.parametrize("name", sorted(library.SCENARIOS))
def test_scenario_matches_the_program_copy(name):
    from repro.dataplane import traffic

    assert np.array_equal(
        library.SCENARIOS[name].generate(2500, 48, SEED),
        traffic.generate(name, 2500, 48, seed=SEED),
    )
