"""Pallas TPU kernel: block-VUSA packed sparse matmul.

TPU adaptation of the paper's virtually-upscaled systolic array (DESIGN.md
§2): per output tile of ``tile_n`` lanes, the reduction dimension is covered
by ``n_jobs`` jobs of ``a_blk`` packed rows + an int32 row-index map (the
"shifter setting").  Each job issues one dense ``(B, a_blk) @ (a_blk,
tile_n)`` MXU matmul after gathering the matching activation rows, so HBM
weight traffic and issued MACs scale with the *non-zero* rows only — the
M/A virtual growth realised as bytes and MACs saved.

Grid: one step per output tile.  VMEM working set per step:
    x          (B, K)            — activations resident (decode-sized B)
    values     (n_jobs, a_blk, tile_n)
    row_idx    (n_jobs, a_blk)
    y          (B, tile_n) accumulator (fp32)
``a_blk`` is a multiple of 8 (sublanes) and ``tile_n`` a multiple of 128
(lanes) so every matmul is MXU-aligned.

The in-kernel gather runs along the lane dimension of ``x``; on TPU this
lowers to a dynamic-gather, on the CPU backend it runs in interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["vusa_spmm"]


def _kernel(x_ref, val_ref, idx_ref, y_ref):
    b = x_ref.shape[0]
    _, n_jobs, a_blk, tile_n = val_ref.shape  # leading 1: one tile per step
    x = x_ref[...]

    def job(j, acc):
        idx = idx_ref[0, j, :]  # (a_blk,) absolute K indices
        xg = jnp.take(x, idx, axis=1)  # (B, a_blk) — the shifter/gather
        vals = val_ref[0, j, :, :]  # (a_blk, tile_n)
        return acc + jnp.dot(
            xg.astype(jnp.float32), vals.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )

    acc = jax.lax.fori_loop(0, n_jobs, job, jnp.zeros((b, tile_n), jnp.float32))
    y_ref[...] = acc.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def vusa_spmm(
    x: jax.Array,  # (B, K)
    values: jax.Array,  # (T, J, A, Tn)
    row_idx: jax.Array,  # (T, J, A) int32
    *,
    interpret: bool = False,  # ops.interpret_mode() picks it from the backend
) -> jax.Array:
    b, k = x.shape
    t, j, a, tn = values.shape
    grid = (t,)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((b, k), lambda i: (0, 0)),  # x resident across tiles
            pl.BlockSpec((1, j, a, tn), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((1, j, a), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((b, tn), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((b, t * tn), x.dtype),
        interpret=interpret,
    )(x, values, row_idx)
