"""Pallas TPU kernel: VUSA row-wise packed matmul (the paper's format, exact).

Per output *window* of ``M`` lanes (M <= 128, one MXU tile of columns), each
reduction row ``k`` stores at most ``A`` non-zero weights as ``A`` value
slots + ``A`` int8 *position* slots — precisely the paper's VUSA row: the
positions are the SPE indices the physical MACs are shifted onto (Fig. 5).
Rows with more than ``A`` non-zeros spill into additional *jobs* of the same
window — the dense-fallback guarantee of Section III-C ("down to N x A, at
which the conditions are guaranteed").

On TPU the fixed 128x128 MXU plays the role of the physical MAC array, so
virtual growth cannot reduce issued MACs; what it does reduce — exactly as
in the paper — is what must be *moved* for a given logical matmul: HBM
weight bytes shrink from ``K*M*dtype`` to ``K*J*A*(dtype + 1)``.  At 85 %
sparsity with (M=128, A=16, J=2) that is ~2.4x less weight traffic, which is
the whole game for memory-bound decode (Edge-AI inference, the paper's
target).

Dense-tile reconstruction (DESIGN.md §3) has two implementations, selected
by the static ``reconstruct`` argument:

* ``"onehot"`` (default) — a vectorized contraction over chunks of slots:
  ``positions == lanes[..., None]`` builds the one-hot scatter tensor and
  one multiply-reduce produces the dense (K_blk, M) tile.  One VPU pass per
  ``slot_chunk`` slots; this is the fast path and the only one that
  compiles for the TPU.
* ``"loop"`` — the original per-slot ``fori_loop`` select-accumulate
  (``J*A`` sequential VPU passes).  Kept as the measured baseline for
  ``benchmarks/run.py kernel_vusa_packed``; its traced-offset slot slice
  runs in interpret mode only.

Values may be fp32 or bf16; accumulation is always fp32 (both the one-hot
contraction and the MXU matmul run with ``preferred_element_type=float32``)
and the kernel output is fp32.

Quantized value slots (DESIGN.md §10): with ``value_dtype="int8"`` or
``"int4"`` the value operand is raw int8 bytes plus a per-(window, row) fp32
``scales`` operand.  int4 packs two slots per byte, low nibbles holding
slots ``[0, S/2)`` and high nibbles ``[S/2, S)``, so each half decodes with
one arithmetic shift and no interleave.  The kernel reconstructs the tile
from the integer values and folds the row scale into the operand that row
meets: the matching ``x`` column for a plain window, the matching output
column for the transposed ``w_down`` pack.  HBM only ever moves quantized
bytes.  Positions stay full-resolution int8 either way.

Grid: (output windows, K blocks); K innermost for output-block accumulation.
VMEM per step: x (B, K_blk), vals (K_blk, J*A), pos (K_blk, J*A),
one-hot scratch (K_blk, slot_chunk, M) for "onehot", reconstructed W
(K_blk, M) fp32, acc (B, M) fp32.  ``k_blk`` is the knob that bounds the
scratch — see ``repro.kernels.ops.choose_k_blk``.

``vusa_fused_mlp_matmul`` is the whole-MLP megakernel (DESIGN.md §7): one
``pallas_call`` whose grid walks the ff windows.  Each step reconstructs
that window's ``w_gate`` and ``w_up`` tiles, forms ``silu(gate) * up`` in
VMEM, reconstructs the matching ``w_down`` *rows* (``w_down`` is packed
transposed, so its reduction dim is the windowed one) and accumulates
straight into the ``(B, d_model)`` output — the ``(B, ff)`` intermediate
never touches HBM and the per-layer dispatch count drops from three to one.
Its chunk loops over ``k_blk`` rows are ``fori_loop``s, so only one chunk's
scratch is live at a time.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = [
    "vusa_packed_matmul",
    "vusa_fused_mlp_matmul",
    "RECONSTRUCT_MODES",
    "DEFAULT_SLOT_CHUNK",
]

RECONSTRUCT_MODES = ("onehot", "loop")
DEFAULT_SLOT_CHUNK = 24  # slots per one-hot pass; bounds the scatter scratch


def _reconstruct_onehot(vals, pos, m: int, slot_chunk: int):
    """Vectorized scatter: slots in wide select-reduce chunks.

    vals: (K_blk, S) fp32, pos: (K_blk, S) int32 (-1 = idle slot).
    Returns the dense (K_blk, M) tile in fp32.  Idle slots compare unequal
    to every lane, so they contribute exact zeros.  ``slot_chunk`` bounds
    the (K_blk, chunk, M) scatter tensor; chunks are static slices, so a
    chunk covering all S slots is a single VPU pass.
    """
    k_blk, s = vals.shape
    chunk = min(slot_chunk, s)
    w = jnp.zeros((k_blk, m), jnp.float32)
    for s0 in range(0, s, chunk):
        v = vals[:, s0 : s0 + chunk]
        q = pos[:, s0 : s0 + chunk]
        lanes = jax.lax.broadcasted_iota(jnp.int32, q.shape + (m,), 2)
        w += jnp.sum(jnp.where(q[..., None] == lanes, v[..., None], 0.0), axis=1)
    return w


def _reconstruct_loop(vals, pos, m: int):
    """Seed baseline: one VPU select-accumulate pass per slot (interpret
    mode only: the slot offset is traced)."""
    k_blk, slots = vals.shape
    lanes = jax.lax.broadcasted_iota(jnp.int32, (k_blk, m), 1)

    def slot(a, w):
        v = jax.lax.dynamic_slice_in_dim(vals, a, 1, axis=1)  # (K_blk, 1)
        p = jax.lax.dynamic_slice_in_dim(pos, a, 1, axis=1)
        return w + jnp.where(lanes == p, v, 0.0)

    return jax.lax.fori_loop(0, slots, slot, jnp.zeros((k_blk, m), jnp.float32))


def _decode(raw, value_dtype: str):
    """Value block (R, Sb) -> fp32 slot groups whose concatenation is the
    slot order.  int4 bytes hold slots ``[0, S/2)`` in the low nibble —
    sign-extended by ``(b << 28) >> 28`` on the int32 widening — and
    ``[S/2, S)`` in the high one (``b >> 4``), so the two halves need no
    interleave.  Quantized values stay unscaled integers here."""
    if value_dtype != "int4":
        return (raw.astype(jnp.float32),)
    b = raw.astype(jnp.int32)
    lo = jnp.right_shift(jnp.left_shift(b, 28), 28)
    hi = jnp.right_shift(b, 4)
    return lo.astype(jnp.float32), hi.astype(jnp.float32)


def _tile(raw, pos, m: int, reconstruct: str, slot_chunk: int, value_dtype: str):
    """Dense (R, m) fp32 tile of one window's rows from their value and
    position blocks."""
    groups = _decode(raw, value_dtype)
    pos = pos.astype(jnp.int32)
    if reconstruct == "loop":
        return _reconstruct_loop(jnp.concatenate(groups, axis=1), pos, m)
    w, s0 = None, 0
    for g in groups:  # each lane is hit by at most one slot: the sum is exact
        part = _reconstruct_onehot(g, pos[:, s0 : s0 + g.shape[1]], m, slot_chunk)
        w = part if w is None else w + part
        s0 += g.shape[1]
    return w


def _kernel(x_ref, val_ref, pos_ref, *rest, m: int, reconstruct: str, slot_chunk: int,
            value_dtype: str):
    """One (window, K block) step; ``rest`` is ``(scale_ref, y_ref)`` for a
    quantized pack and ``(y_ref,)`` otherwise."""
    scale_ref, y_ref = rest if len(rest) == 2 else (None, rest[0])

    @pl.when(pl.program_id(1) == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    x = x_ref[...].astype(jnp.float32)
    if scale_ref is not None:
        x = x * scale_ref[0]  # (1, K_blk) row scales fold into x's columns
    w = _tile(val_ref[0], pos_ref[0], m, reconstruct, slot_chunk, value_dtype)
    y_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)


@functools.partial(
    jax.jit,
    static_argnames=("interpret", "k_blk", "m", "reconstruct", "slot_chunk", "value_dtype"),
)
def vusa_packed_matmul(
    x: jax.Array,  # (B, K)
    values: jax.Array,  # (T, K, J*A)  per window: A slots x J jobs per row
    positions: jax.Array,  # (T, K, J*A) int8 lane index per slot (-1 = idle)
    scales: jax.Array | None = None,  # (T, K) fp32, quantized packs only
    *,
    m: int = 128,
    k_blk: int = 256,
    interpret: bool = False,
    reconstruct: str = "onehot",
    slot_chunk: int = DEFAULT_SLOT_CHUNK,
    value_dtype: str = "dense",
) -> jax.Array:
    b, k = x.shape
    t, kk, vslots = values.shape
    slots = positions.shape[2]
    assert kk == k, (kk, k)
    assert m <= 128, m  # int8 positions index lanes within one MXU tile
    assert reconstruct in RECONSTRUCT_MODES, reconstruct
    k_blk = min(k_blk, k)
    assert k % k_blk == 0, (k, k_blk)
    # int4 packs two slots per byte; either way the decode must cover exactly
    # the position slots
    assert vslots * (2 if value_dtype == "int4" else 1) == slots, (value_dtype, vslots, slots)
    in_specs = [
        pl.BlockSpec((b, k_blk), lambda i, l: (0, l)),
        pl.BlockSpec((1, k_blk, vslots), lambda i, l: (i, l, 0)),
        pl.BlockSpec((1, k_blk, slots), lambda i, l: (i, l, 0)),
    ]
    operands = [x, values, positions]
    if value_dtype == "dense":
        assert scales is None, value_dtype
    else:
        assert scales is not None and scales.shape == (t, k), (
            value_dtype, None if scales is None else scales.shape,
        )
        # a (T, 1, K) view: its (1, K_blk) blocks satisfy the TPU tiling rule
        # (last two block dims divisible by (8, 128) or equal to the array's)
        in_specs.append(pl.BlockSpec((1, 1, k_blk), lambda i, l: (i, 0, l)))
        operands.append(scales.reshape(t, 1, k))
    return pl.pallas_call(
        functools.partial(
            _kernel, m=m, reconstruct=reconstruct, slot_chunk=slot_chunk,
            value_dtype=value_dtype,
        ),
        grid=(t, k // k_blk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((b, m), lambda i, l: (0, i)),
        out_shape=jax.ShapeDtypeStruct((b, t * m), jnp.float32),
        interpret=interpret,
    )(*operands)


# --------------------------------------------------------------------------
# Fused packed-MLP megakernel (DESIGN.md §7)
# --------------------------------------------------------------------------


def _contract(a, b, b_dim: int, exact_rows: bool):
    """``a`` (B, n) contracted with axis ``b_dim`` of the 2-D ``b``, in fp32.

    ``exact_rows`` (interpret mode) spells it as a broadcast multiply-reduce,
    whose summation order does not depend on B.  XLA:CPU's gemms pick their
    order by shape, so a B-row call would differ in the last bit from B
    one-row calls, and the batched speculative verify relies on the
    fused MLP being row-bitwise (DESIGN.md §13).  Compiled, it is the MXU
    ``dot_general``."""
    if not exact_rows:
        return jax.lax.dot_general(
            a, b, (((1,), (b_dim,)), ((), ())), preferred_element_type=jnp.float32
        )
    if b_dim == 0:
        return jnp.sum(a[:, :, None] * b[None], axis=1)
    return jnp.sum(a[:, None, :] * b[None], axis=2)


def _row_chunks(n: int, k_blk: int, body, carry):
    """``carry = body(r0, width, carry)`` over rows ``[0, n)`` in ``k_blk``
    chunks: the full chunks in a ``fori_loop`` (one chunk's scratch live at
    a time, offsets hinted ``k_blk``-aligned), a ragged tail statically."""
    full = n // k_blk
    if full:
        carry = jax.lax.fori_loop(
            0, full, lambda c, cr: body(pl.multiple_of(c * k_blk, k_blk), k_blk, cr), carry
        )
    if n % k_blk:
        carry = body(full * k_blk, n % k_blk, carry)
    return carry


def _fused_mlp_kernel(
    x_ref, gv_ref, gp_ref, uv_ref, up_ref, dv_ref, dp_ref, *rest,
    m: int, k_blk: int, reconstruct: str, slot_chunk: int, value_dtype: str,
    exact_rows: bool,
):
    """One ff window of the fused MLP: gate/up reconstruct + matmul,
    ``silu(gate) * up`` in VMEM, then the window's ``w_down`` rows
    (transposed pack: ``dv``/``dp`` are (1, D, Sd) over the same window)
    accumulate into the full (B, D) output block.  ``rest`` is ``(gs, us,
    ds, y_ref)`` — (1, 1, rows) scale blocks — for quantized packs and
    ``(y_ref,)`` otherwise."""
    *scales, y_ref = rest
    gs_ref, us_ref, ds_ref = scales or (None, None, None)
    tile = functools.partial(
        _tile, m=m, reconstruct=reconstruct, slot_chunk=slot_chunk, value_dtype=value_dtype
    )

    @pl.when(pl.program_id(0) == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    def gate_up(r0, width, acc):
        rows = pl.ds(r0, width)
        x = x_ref[:, rows].astype(jnp.float32)
        xg = x if gs_ref is None else x * gs_ref[0, :, rows]
        xu = x if us_ref is None else x * us_ref[0, :, rows]
        g = _contract(xg, tile(gv_ref[0, rows, :], gp_ref[0, rows, :]), 0, exact_rows)
        u = _contract(xu, tile(uv_ref[0, rows, :], up_ref[0, rows, :]), 0, exact_rows)
        return acc[0] + g, acc[1] + u

    b = x_ref.shape[0]
    zeros = jnp.zeros((b, m), jnp.float32)
    gate, up = _row_chunks(x_ref.shape[1], k_blk, gate_up, (zeros, zeros))
    h = jax.nn.silu(gate) * up  # (B, m) — the (B, ff) intermediate, one window of it

    def down(r0, width, carry):
        rows = pl.ds(r0, width)
        # (width, m) rows of w_down.T — lanes are this window's ff rows
        wd = tile(dv_ref[0, rows, :], dp_ref[0, rows, :])
        y = _contract(h, wd, 1, exact_rows)
        if ds_ref is not None:
            y = y * ds_ref[0, :, rows]  # a w_down_t row is an output column
        y_ref[:, rows] += y
        return carry

    _row_chunks(y_ref.shape[1], k_blk, down, 0)


@functools.partial(
    jax.jit,
    static_argnames=("interpret", "k_blk", "m", "reconstruct", "slot_chunk", "value_dtype"),
)
def vusa_fused_mlp_matmul(
    x: jax.Array,  # (B, K)
    gate_values: jax.Array,  # (T, K, Sg)   w_gate row-pack
    gate_positions: jax.Array,  # (T, K, Sg) int8
    up_values: jax.Array,  # (T, K, Su)     w_up row-pack
    up_positions: jax.Array,  # (T, K, Su) int8
    down_values: jax.Array,  # (T, D, Sd)   w_down.T row-pack (ff windowed)
    down_positions: jax.Array,  # (T, D, Sd) int8
    gate_scales: jax.Array | None = None,  # (T, K) fp32, quantized packs only
    up_scales: jax.Array | None = None,  # (T, K) fp32
    down_scales: jax.Array | None = None,  # (T, D) fp32
    *,
    m: int = 128,
    k_blk: int = 256,
    interpret: bool = False,
    reconstruct: str = "onehot",
    slot_chunk: int = DEFAULT_SLOT_CHUNK,
    value_dtype: str = "dense",
) -> jax.Array:
    """Whole SwiGLU MLP in one ``pallas_call``: ``silu(x@Wg) * (x@Wu) @ Wd``.

    All three weights are row-packed over the *same* ff windows: ``w_gate``
    and ``w_up`` as (K=d_model, C=ff) with ff the lane dim, ``w_down``
    *transposed* as (K=d_model out, C=ff) so its reduction dim is windowed
    too.  The grid walks the T ff windows; each step finishes one window's
    ``(B, m)`` slice of the hidden state and scatters its contribution into
    the full ``(B, D)`` output, which accumulates across the grid in fp32.
    Zero-padded ff lanes (C % m != 0) are exact no-ops: gate/up reconstruct
    to zero columns there (``silu(0) * 0 = 0``) and the transposed down pack
    holds no slots pointing at them.  Returns (B, D) fp32.
    """
    b, k = x.shape
    t, kk, _ = gate_values.shape
    tu, ku, _ = up_values.shape
    td, d_out, _ = down_values.shape
    assert kk == k and ku == k, (kk, ku, k)
    assert tu == t and td == t, (t, tu, td)
    assert m <= 128, m
    assert reconstruct in RECONSTRUCT_MODES, reconstruct
    k_blk = max(1, min(k_blk, max(k, d_out)))
    nib = 2 if value_dtype == "int4" else 1
    # value slot dims may be nibble-packed; position slot dims are the truth
    vg, vu, vd = gate_values.shape[2], up_values.shape[2], down_values.shape[2]
    sg, su, sd = gate_positions.shape[2], up_positions.shape[2], down_positions.shape[2]
    assert (vg * nib, vu * nib, vd * nib) == (sg, su, sd), (value_dtype, (vg, vu, vd), (sg, su, sd))

    def window(rows, slots):  # one ff window's whole (rows, slots) pack block
        return pl.BlockSpec((1, rows, slots), lambda i: (i, 0, 0))

    in_specs = [
        pl.BlockSpec((b, k), lambda i: (0, 0)),
        window(k, vg), window(k, sg), window(k, vu), window(k, su),
        window(d_out, vd), window(d_out, sd),
    ]
    operands = [
        x, gate_values, gate_positions, up_values, up_positions, down_values, down_positions,
    ]
    if value_dtype == "dense":
        assert gate_scales is None and up_scales is None and down_scales is None
    else:
        assert gate_scales is not None and up_scales is not None and down_scales is not None
        assert gate_scales.shape == (t, k) and up_scales.shape == (t, k), (
            gate_scales.shape, up_scales.shape,
        )
        assert down_scales.shape == (t, d_out), (down_scales.shape, t, d_out)
        # (T, 1, rows) views, as in vusa_packed_matmul
        in_specs += [window(1, k), window(1, k), window(1, d_out)]
        operands += [
            gate_scales.reshape(t, 1, k), up_scales.reshape(t, 1, k),
            down_scales.reshape(t, 1, d_out),
        ]
    return pl.pallas_call(
        functools.partial(
            _fused_mlp_kernel, m=m, k_blk=k_blk, reconstruct=reconstruct,
            slot_chunk=slot_chunk, value_dtype=value_dtype, exact_rows=interpret,
        ),
        grid=(t,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((b, d_out), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, d_out), jnp.float32),
        interpret=interpret,
    )(*operands)
