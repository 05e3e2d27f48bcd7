"""Plain reference for the N2Net BNNs, and the control that must fail.

Imports nothing of the program.  A layer of a BNN with {0,1} inputs ``x``
and {0,1} weights ``w`` (bit 1 stands for +1) fires iff
``sum_i (2 x_i - 1)(2 w_i - 1) >= 0``: the sign of the +-1 dot product,
with a tie (sum 0) resolving to +1.  That is N2Net's SIGN step,
``popcount(XNOR(x, w)) >= n / 2``, and the guarantee both configurations
state, bit for bit.

The dot product is taken as a float32 matrix product of +-1 values, which
is exact here: every partial sum is an integer of magnitude at most the
layer's fan-in (<= 2048 < 2**24).

The control is the same network with the tie resolved the other way
(``sum > 0``): the step that would tempt a later change, since ``2 *
agree - n > 0`` is the natural test in a matmul formulation, and it breaks
the stated SIGN guarantee on every neuron whose inputs split evenly.
"""
from __future__ import annotations

import numpy as np

BLOCK = 1 << 16  # rows per block, so the reference fits beside the pool


def make_weights(shapes, seed: int) -> list[list[np.ndarray]]:
    """Seeded {0,1} int32 weights, one ``(out, in)`` matrix per layer of
    each tenant's model."""
    out = []
    for t, shape in enumerate(shapes):
        rng = np.random.default_rng([seed, 1, t])
        out.append([
            rng.integers(0, 2, (n_out, n_in), dtype=np.int32)
            for n_in, n_out in zip(shape[:-1], shape[1:])
        ])
    return out


def forward(weights, x, *, tie_fires: bool = True) -> np.ndarray:
    """Verdict bits, ``(n, output_bits)`` uint8, of ``x`` (``(n, input
    bits)`` in {0,1}) through one model's ``weights``."""
    x = np.asarray(x)
    n = x.shape[0]
    out = np.empty((n, weights[-1].shape[0]), np.uint8)
    signs = [(2.0 * w - 1.0).astype(np.float32).T for w in weights]
    for s in range(0, n, BLOCK):
        h = x[s : s + BLOCK].astype(np.float32) * 2.0 - 1.0
        for i, w in enumerate(signs):
            pre = h @ w
            fired = pre >= 0 if tie_fires else pre > 0
            h = fired.astype(np.float32) * 2.0 - 1.0 if i + 1 < len(signs) else fired
        out[s : s + BLOCK] = h
    return out


def control_forward(weights, x) -> np.ndarray:
    """The control: :func:`forward` with the SIGN tie resolved to 0."""
    return forward(weights, x, tie_fires=False)
