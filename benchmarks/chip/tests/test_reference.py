"""The plain reference agrees with the program's oracle, and its control
does not."""
import numpy as np
import pytest

import reference

SHAPES = [(32, 64, 32), (128, 64, 32, 2), (24, 12, 3)]


@pytest.mark.parametrize("shape", SHAPES)
def test_reference_is_core_bnn_forward(shape):
    import jax.numpy as jnp

    from repro.core import bnn

    (w,) = reference.make_weights([shape], 2**31 + 5)
    x = np.random.default_rng(0).integers(0, 2, (4000, shape[0]), dtype=np.int32)
    want = np.asarray(bnn.forward([jnp.asarray(m) for m in w], jnp.asarray(x)))
    assert np.array_equal(reference.forward(w, x), want)


@pytest.mark.parametrize("shape", SHAPES)
def test_control_breaks_the_tie_rule(shape):
    (w,) = reference.make_weights([shape], 3)
    x = np.random.default_rng(1).integers(0, 2, (4000, shape[0]), dtype=np.int32)
    assert (reference.control_forward(w, x) != reference.forward(w, x)).any()


def test_weights_follow_the_seed():
    a = reference.make_weights(SHAPES, 9)
    b = reference.make_weights(SHAPES, 9)
    c = reference.make_weights(SHAPES, 10)
    assert all(np.array_equal(x, y) for ta, tb in zip(a, b) for x, y in zip(ta, tb))
    assert not np.array_equal(a[0][0], c[0][0])
