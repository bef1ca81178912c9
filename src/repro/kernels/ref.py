"""Pure-jnp oracles for the Pallas kernels (the correctness ground truth).

``vusa_spmm_ref`` consumes the *packed* operands, so kernel-vs-ref equality
checks the kernel, and ``unpack_blocks``-vs-dense checks the packer — the two
composed give end-to-end ``x @ W`` equality (see tests/test_kernels.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "dense_matmul_ref", "vusa_spmm_ref", "vusa_packed_ref", "vusa_fused_mlp_ref",
    "vusa_fused_moe_ref",
]


def dense_matmul_ref(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """(B, K) @ (K, C) in fp32 accumulation."""
    return jnp.einsum("bk,kc->bc", x, w, preferred_element_type=jnp.float32).astype(x.dtype)


def vusa_spmm_ref(x: jnp.ndarray, values: jnp.ndarray, row_idx: jnp.ndarray) -> jnp.ndarray:
    """Block-VUSA packed matmul, pure jnp.

    x:       (B, K)
    values:  (T, J, A, Tn)  packed non-zero weight rows per output tile
    row_idx: (T, J, A)      absolute K index per packed row (padding -> 0
                            with zero values)
    returns  (B, T * Tn)
    """
    t, j, a, tn = values.shape
    xg = x[:, row_idx]  # (B, T, J, A) gather — the SPE->MAC shifter
    y = jnp.einsum("btja,tjan->btn", xg.astype(jnp.float32), values.astype(jnp.float32))
    return y.reshape(x.shape[0], t * tn).astype(x.dtype)


def vusa_packed_ref(x: jnp.ndarray, values: jnp.ndarray, positions: jnp.ndarray) -> jnp.ndarray:
    """Row-wise VUSA packed matmul oracle, pure jnp.

    x: (B, K); values/positions: (T, K, S) with int8 lane positions
    (-1 = idle slot).  Returns (B, T*128) fp32.
    """
    return jnp.einsum(
        "bk,kc->bc", x.astype(jnp.float32), _unpack_dense(values, positions)
    )


def _unpack_dense(values: jnp.ndarray, positions: jnp.ndarray, m: int = 128) -> jnp.ndarray:
    """Row-pack -> dense (K, T*m) fp32 (shared by both oracles)."""
    t, k, _ = values.shape
    lanes = jnp.arange(m, dtype=jnp.int32)
    onehot = (positions.astype(jnp.int32)[..., None] == lanes).astype(jnp.float32)
    w = jnp.einsum("tks,tksm->tkm", values.astype(jnp.float32), onehot)
    return w.transpose(1, 0, 2).reshape(k, t * m)


def vusa_fused_mlp_ref(
    x: jnp.ndarray,
    gate_values: jnp.ndarray,
    gate_positions: jnp.ndarray,
    up_values: jnp.ndarray,
    up_positions: jnp.ndarray,
    down_values: jnp.ndarray,
    down_positions: jnp.ndarray,
    m: int = 128,
) -> jnp.ndarray:
    """Fused SwiGLU MLP oracle over row-packed operands, pure jnp.

    ``gate``/``up`` pack (K, ff); ``down`` packs ``w_down`` *transposed*
    (D, ff) so the ff reduction dim is the windowed one — exactly the
    operands of ``vusa_fused_mlp_matmul``.  Returns (B, D) fp32.
    """
    wg = _unpack_dense(gate_values, gate_positions, m)  # (K, T*m)
    wu = _unpack_dense(up_values, up_positions, m)
    wdt = _unpack_dense(down_values, down_positions, m)  # (D, T*m) = w_down.T padded
    xf = x.astype(jnp.float32)
    h = jax.nn.silu(xf @ wg) * (xf @ wu)  # (B, T*m)
    return h @ wdt.T


def vusa_fused_moe_ref(
    x: jnp.ndarray,
    weights: jnp.ndarray,
    gate_values: jnp.ndarray,
    gate_positions: jnp.ndarray,
    up_values: jnp.ndarray,
    up_positions: jnp.ndarray,
    down_values: jnp.ndarray,
    down_positions: jnp.ndarray,
    m: int = 128,
) -> jnp.ndarray:
    """Grouped-expert oracle, pure jnp: ``sum_e weights[:, e] *
    vusa_fused_mlp_ref(x, expert e's packs)`` over the leading expert axis of
    the ``vusa_fused_moe_matmul`` operands.  Returns (B, D) fp32."""
    y = 0.0
    for e in range(gate_values.shape[0]):
        y = y + weights[:, e : e + 1].astype(jnp.float32) * vusa_fused_mlp_ref(
            x, gate_values[e], gate_positions[e], up_values[e], up_positions[e],
            down_values[e], down_positions[e], m,
        )
    return y
