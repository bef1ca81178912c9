"""Pallas TPU kernel: dense tiled matmul — the "standard weight-stationary
systolic array" baseline the paper compares VUSA against (Table I-III).

Classic MXU tiling: grid over (M/bm, N/bn, K/bk); the K axis is the
innermost (sequential) grid dimension so the fp32 accumulator lives in the
output block across K steps.  Block shapes are MXU-aligned (multiples of
8 x 128).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["dense_matmul"]


def _kernel(x_ref, w_ref, y_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    y_ref[...] += jnp.dot(
        x_ref[...].astype(jnp.float32),
        w_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    ).astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def dense_matmul(
    x: jax.Array,  # (M, K)
    w: jax.Array,  # (K, N)
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    m, k = x.shape
    _, n = w.shape
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k, bm, bn, bk)
    grid = (m // bm, n // bn, k // bk)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, l: (i, l)),
            pl.BlockSpec((bk, bn), lambda i, j, l: (l, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, l: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
    )(x, w)
