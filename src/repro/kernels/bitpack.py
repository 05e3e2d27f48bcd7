"""Pallas TPU kernel: fused sign + bitpack (bf16/f32 -> uint32 words).

Converts real-valued activations into the packed sign representation consumed
by ``bnn_matmul.py``, writing 32x fewer bytes than the input.  This is the
"SIGN + folding" pair of N2Net's five steps, fused: on the switch the fold
deposits sign bits into the Y vector; on TPU we deposit 32 lane-neighbour
signs into one uint32 via a weighted reduction over the lane axis.

Tiling: grid (M/bm, K/(32*bkw)); each step reads an (bm, 32*bkw) activation
tile and writes an (bm, bkw) uint32 tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

WORD = 32


def _kernel(x_ref, o_ref):
    x = x_ref[...]
    bm, kb = x.shape
    bits = (x >= 0).astype(jnp.uint32)
    grouped = bits.reshape(bm, kb // WORD, WORD)
    weights = jnp.uint32(1) << jnp.arange(WORD, dtype=jnp.uint32)
    o_ref[...] = jnp.sum(grouped * weights, axis=-1, dtype=jnp.uint32)


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_kw", "interpret")
)
def bitpack(
    x: jax.Array,
    *,
    block_m: int = 256,
    block_kw: int = 16,
    interpret: bool = False,
) -> jax.Array:
    """Pack sign bits of ``x`` (M, K) into (M, K/32) uint32 (K % 32 == 0)."""
    m, k = x.shape
    if k % WORD:
        raise ValueError(f"K={k} must be a multiple of {WORD}")
    kw = k // WORD
    block_m = min(block_m, m)
    block_kw = min(block_kw, kw)
    if m % block_m or kw % block_kw:
        raise ValueError(
            f"shape ({m},{kw}) not divisible by blocks ({block_m},{block_kw})"
        )
    return pl.pallas_call(
        _kernel,
        grid=(m // block_m, kw // block_kw),
        in_specs=[
            pl.BlockSpec((block_m, block_kw * WORD), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_kw), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, kw), jnp.uint32),
        interpret=interpret,
    )(x)
