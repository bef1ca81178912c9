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

Dense-tile reconstruction (DESIGN.md §3) builds each window's tile
*transposed*, ``(M, R)`` for ``R`` rows: the window's lanes in sublanes,
the rows in lanes.  The rows' value and position blocks are widened and
transposed in VMEM to ``(S, R)``, so each slot is one lane row.  Two
implementations, selected by the static ``reconstruct`` argument:

* ``"onehot"`` (default) — one select per slot, a static loop: the slot's
  positions, broadcast over sublanes, against the sublane iota are its
  one-hot columns, and ``where`` writes its values there.  A row holds
  each lane at most once, so the select is exact and idle slots (-1)
  match nothing.  There is no 3-D one-hot tensor and no reduction across
  sublanes.  This is the fast path and the only one that compiles for the
  TPU.
* ``"loop"`` — the original per-slot ``fori_loop`` select-accumulate over
  the same transposed blocks.  Kept as the measured baseline for
  ``benchmarks/run.py kernel_vusa_packed``; its traced-offset slot slice
  runs in interpret mode only.

The MXU contracts the transposed tile where it lies: ``x (B, R)`` against
its dim 1 (an NT matmul) for a plain window, ``h (B, M)`` against its dim 0
for the transposed ``w_down`` pack.

Values may be fp32 or bf16; accumulation is always fp32 (the tile is
fp32 and the MXU matmul runs with ``preferred_element_type=float32``) and
the kernel output is fp32.

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
VMEM per step: x (B, K_blk), vals (K_blk, J*A), pos (K_blk, J*A), acc
(B, M) fp32; the block is rebuilt ``TILE_ROWS`` rows at a time, each chunk
keeping its widened, transposed slots (2 x (S, R) 32-bit) and its
transposed tile (M, R) fp32 live (``repro.kernels.ops.choose_k_blk`` picks
``k_blk``).  A ``jax.vmap`` over ``x`` alone (the Scheduler's slot axis)
keeps this grid: the vmapped rows become more rows of one call
(``fold_slot_vmap``).

``vusa_fused_mlp_matmul`` is the whole-MLP megakernel (DESIGN.md §7): one
``pallas_call`` whose grid walks the ff windows.  Each step reconstructs
that window's ``w_gate`` and ``w_up`` tiles, forms ``silu(gate) * up`` in
VMEM, reconstructs the matching ``w_down`` *rows* (``w_down`` is packed
transposed, so its reduction dim is the windowed one) and accumulates
straight into the ``(B, d_model)`` output — the ``(B, ff)`` intermediate
never touches HBM and the per-layer dispatch count drops from three to one.
Its chunk loops over ``k_blk`` rows are ``fori_loop``s, so only one chunk's
tile is live at a time.

``vusa_fused_moe_matmul`` runs an expert layer's held experts, each a fused
MLP in that layout, in one call whose grid walks (expert, ff window); each
row's hidden state is scaled by its weight for the expert, and all experts
accumulate into one output.
"""

from __future__ import annotations

import contextlib
import contextvars
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = [
    "vusa_packed_matmul",
    "vusa_fused_mlp_matmul",
    "vusa_fused_moe_matmul",
    "fold_slot_vmap",
    "fold_tally",
    "calls_repeat",
    "FoldTally",
    "RECONSTRUCT_MODES",
    "TILE_ROWS",
]

RECONSTRUCT_MODES = ("onehot", "loop")
TILE_ROWS = 128  # rows a plain kernel's block is rebuilt in: one lane tile


def _reconstruct_onehot(vals_t, pos_t, m: int):
    """Per-slot select into the transposed tile.

    vals_t: (S, R) fp32, pos_t: (S, R) int32 (-1 = idle slot).  Returns
    the transposed dense (m, R) tile in fp32.  Slot ``a``'s row, broadcast
    over sublanes, meets the sublane iota: each lane (a pack row) selects
    its value into the sublane its position names.  A row holds each lane
    at most once, so a select replaces the sum exactly."""
    s, r = vals_t.shape
    sub = jax.lax.broadcasted_iota(jnp.int32, (m, r), 0)
    w = jnp.zeros((m, r), jnp.float32)
    for a in range(s):
        w = jnp.where(pos_t[a : a + 1] == sub, vals_t[a : a + 1], w)
    return w


def _reconstruct_loop(vals_t, pos_t, m: int):
    """Seed baseline: one VPU select-accumulate pass per slot, on the same
    transposed blocks (interpret mode only: the slot offset is traced)."""
    slots, r = vals_t.shape
    sub = jax.lax.broadcasted_iota(jnp.int32, (m, r), 0)

    def slot(a, w):
        v = jax.lax.dynamic_slice_in_dim(vals_t, a, 1, axis=0)  # (1, R)
        p = jax.lax.dynamic_slice_in_dim(pos_t, a, 1, axis=0)
        return w + jnp.where(sub == p, v, 0.0)

    return jax.lax.fori_loop(0, slots, slot, jnp.zeros((m, r), jnp.float32))


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


def _tile(raw, pos, m: int, reconstruct: str, value_dtype: str):
    """Transposed dense (m, R) fp32 tile of one window's R rows from their
    value and position blocks: entry (i, r) is row r's weight at lane i.
    The widened blocks are transposed to (S, R), an int4 pack's two
    nibble groups each on its own."""
    pos_t = pos.astype(jnp.int32).T
    vals_t = jnp.concatenate([g.T for g in _decode(raw, value_dtype)], axis=0)
    rebuild = _reconstruct_loop if reconstruct == "loop" else _reconstruct_onehot
    return rebuild(vals_t, pos_t, m)


def _contract(a, w_t, dim: int, exact_rows: bool):
    """``a`` (B, n) contracted with axis ``dim`` of the transposed (m, R)
    tile ``w_t``, in fp32: dim 1 where the tile's rows are the reduction
    (``x @ W``, an NT matmul), dim 0 for the transposed ``w_down`` pack,
    whose tile holds the window's ``w_down`` rows as they lie.

    ``exact_rows`` (interpret mode) spells it as one rank-1 update per
    contracted index in a ``fori_loop``, a summation order that depends on
    neither B nor the slot count.  XLA:CPU picks a gemm's order by shape,
    and a reduction's by what it fuses with (the tile's select chain grows
    with the slots), so a B-row call would differ in the last bit from B
    one-row calls, and a padded pack from its unpadded one.  The batched
    speculative verify (DESIGN.md §13), a slot vmap folded into rows and
    layer stacking's slot padding rely on the kernels being row-bitwise.
    Compiled, it is the MXU ``dot_general``."""
    if not exact_rows:
        return jax.lax.dot_general(
            a, w_t, (((1,), (dim,)), ((), ())), preferred_element_type=jnp.float32
        )

    def update(r, acc):
        col = jax.lax.dynamic_index_in_dim(a, r, 1, keepdims=False)
        row = jax.lax.dynamic_index_in_dim(w_t, r, dim, keepdims=False)
        return acc + col[:, None] * row[None, :]

    out = jnp.zeros((a.shape[0], w_t.shape[1 - dim]), jnp.float32)
    return jax.lax.fori_loop(0, a.shape[1], update, out)


def _row_chunks(n: int, k_blk: int, body, carry):
    """``carry = body(r0, width, carry)`` over rows ``[0, n)`` in ``k_blk``
    chunks: the full chunks in a ``fori_loop`` (one chunk's tile live at a
    time, offsets hinted ``k_blk``-aligned) unless there is one, a ragged
    tail statically."""
    full = n // k_blk
    if full == 1:
        carry = body(0, k_blk, carry)
    elif full:
        carry = jax.lax.fori_loop(
            0, full, lambda c, cr: body(pl.multiple_of(c * k_blk, k_blk), k_blk, cr), carry
        )
    if n % k_blk:
        carry = body(full * k_blk, n % k_blk, carry)
    return carry


def _kernel(x_ref, val_ref, pos_ref, *rest, m: int, reconstruct: str, value_dtype: str,
            exact_rows: bool):
    """One (window, K block) step, the block rebuilt ``TILE_ROWS`` rows at
    a time; ``rest`` is ``(scale_ref, y_ref)`` for a quantized pack and
    ``(y_ref,)`` otherwise.  ``exact_rows``: as in ``_contract``, so a call
    on a slot vmap's folded rows equals the per-slot calls bit for bit in
    interpret mode."""
    scale_ref, y_ref = rest if len(rest) == 2 else (None, rest[0])

    @pl.when(pl.program_id(1) == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    def chunk(r0, width, acc):
        rows = pl.ds(r0, width)
        x = x_ref[:, rows].astype(jnp.float32)
        if scale_ref is not None:
            x = x * scale_ref[0, :, rows]  # (1, width) row scales fold into x's columns
        w_t = _tile(val_ref[0, rows, :], pos_ref[0, rows, :], m, reconstruct, value_dtype)
        return acc + _contract(x, w_t, 1, exact_rows)

    y_ref[...] += _row_chunks(
        x_ref.shape[1], TILE_ROWS, chunk, jnp.zeros(y_ref.shape, jnp.float32)
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "interpret", "k_blk", "m", "reconstruct", "value_dtype", "name",
    ),
)
def _packed_matmul(
    x: jax.Array,  # (B, K)
    values: jax.Array,  # (T, K, J*A)  per window: A slots x J jobs per row
    positions: jax.Array,  # (T, K, J*A) int8 lane index per slot (-1 = idle)
    scales: jax.Array | None = None,  # (T, K) fp32, quantized packs only
    *,
    m: int = 128,
    k_blk: int = 256,
    interpret: bool = False,
    reconstruct: str = "onehot",
    value_dtype: str = "dense",
    name: str = "vusa_packed_matmul",
) -> jax.Array:
    """y = x @ W for a row-packed W, fp32 (B, T*m).  ``name`` labels the
    call in compiled programs and device traces; a trace reader finds every
    call by its ``vusa_packed_matmul`` prefix.  A ``jax.vmap`` over ``x``
    alone folds into the call's rows (``fold_slot_vmap``)."""
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
            _kernel, m=m, reconstruct=reconstruct, value_dtype=value_dtype,
            exact_rows=interpret,
        ),
        grid=(t, k // k_blk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((b, m), lambda i, l: (0, i)),
        out_shape=jax.ShapeDtypeStruct((b, t * m), jnp.float32),
        interpret=interpret,
        name=name,
    )(*operands)


# --------------------------------------------------------------------------
# Fused packed-MLP megakernel (DESIGN.md §7)
# --------------------------------------------------------------------------


def _fused_mlp_kernel(
    x_ref, *refs, m: int, k_blk: int, reconstruct: str, value_dtype: str,
    exact_rows: bool, gated: bool = False, grid_rank: int = 1,
):
    """One ff window of the fused MLP: gate/up reconstruct + matmul,
    ``silu(gate) * up`` in VMEM, then the window's ``w_down`` rows
    (transposed pack: ``dv``/``dp`` are (1, D, Sd) over the same window)
    accumulate into the full (B, D) output block.  ``refs`` is ``([w_ref,]
    gv, gp, uv, up, dv, dp, [gs, us, ds,] y_ref)``: the scale blocks (1, 1,
    rows) for quantized packs; ``w_ref`` (1, B, 1), with ``gated``, is each
    row's gate weight for this expert, scaling its hidden state.  The output
    accumulates over the whole ``grid_rank``-axis grid."""
    w_ref, refs = (refs[0], refs[1:]) if gated else (None, refs)
    gv_ref, gp_ref, uv_ref, up_ref, dv_ref, dp_ref, *rest = refs
    *scales, y_ref = rest
    gs_ref, us_ref, ds_ref = scales or (None, None, None)
    tile = functools.partial(_tile, m=m, reconstruct=reconstruct, value_dtype=value_dtype)
    first = pl.program_id(0) == 0
    for axis in range(1, grid_rank):
        first = jnp.logical_and(first, pl.program_id(axis) == 0)

    @pl.when(first)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    def gate_up(r0, width, acc):
        rows = pl.ds(r0, width)
        x = x_ref[:, rows].astype(jnp.float32)
        xg = x if gs_ref is None else x * gs_ref[0, :, rows]
        xu = x if us_ref is None else x * us_ref[0, :, rows]
        g = _contract(xg, tile(gv_ref[0, rows, :], gp_ref[0, rows, :]), 1, exact_rows)
        u = _contract(xu, tile(uv_ref[0, rows, :], up_ref[0, rows, :]), 1, exact_rows)
        return acc[0] + g, acc[1] + u

    b = x_ref.shape[0]
    zeros = jnp.zeros((b, m), jnp.float32)
    gate, up = _row_chunks(x_ref.shape[1], k_blk, gate_up, (zeros, zeros))
    h = jax.nn.silu(gate) * up  # (B, m) — the (B, ff) intermediate, one window of it
    if w_ref is not None:
        h = h * w_ref[0]  # (B, 1): the expert's weight per row, 0 where not chosen

    def down(r0, width, carry):
        rows = pl.ds(r0, width)
        # (m, width): this window's ff rows of w_down, output columns in lanes
        wd_t = tile(dv_ref[0, rows, :], dp_ref[0, rows, :])
        y = _contract(h, wd_t, 0, exact_rows)
        if ds_ref is not None:
            y = y * ds_ref[0, :, rows]  # a w_down_t row is an output column
        y_ref[:, rows] += y
        return carry

    _row_chunks(y_ref.shape[1], k_blk, down, 0)


@functools.partial(
    jax.jit,
    static_argnames=("interpret", "k_blk", "m", "reconstruct", "value_dtype"),
)
def _fused_mlp_matmul(
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
    holds no slots pointing at them.  Returns (B, D) fp32.  A ``jax.vmap``
    over ``x`` alone folds into the call's rows (``fold_slot_vmap``).
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
            value_dtype=value_dtype, exact_rows=interpret,
        ),
        grid=(t,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((b, d_out), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, d_out), jnp.float32),
        interpret=interpret,
        name="vusa_fused_mlp_matmul",
    )(*operands)


# --------------------------------------------------------------------------
# Grouped packed experts (the held experts of an expert layer)
# --------------------------------------------------------------------------


@functools.partial(
    jax.jit,
    static_argnames=("interpret", "k_blk", "m", "reconstruct", "value_dtype"),
)
def _fused_moe_matmul(
    x: jax.Array,  # (B, K)
    weights: jax.Array,  # (B, E) each row's gate weight per expert, 0 = not chosen
    gate_values: jax.Array,  # (E, T, K, Sg)   per expert, as _fused_mlp_matmul's
    gate_positions: jax.Array,  # (E, T, K, Sg) int8
    up_values: jax.Array,  # (E, T, K, Su)
    up_positions: jax.Array,  # (E, T, K, Su) int8
    down_values: jax.Array,  # (E, T, D, Sd)   w_down.T packs (ff windowed)
    down_positions: jax.Array,  # (E, T, D, Sd) int8
    gate_scales: jax.Array | None = None,  # (E, T, K) fp32, quantized packs only
    up_scales: jax.Array | None = None,  # (E, T, K) fp32
    down_scales: jax.Array | None = None,  # (E, T, D) fp32
    *,
    m: int = 128,
    k_blk: int = 256,
    interpret: bool = False,
    reconstruct: str = "onehot",
    value_dtype: str = "dense",
) -> jax.Array:
    """``sum_e weights[:, e] * mlp_e(x)`` over E stacked SwiGLU experts in
    one ``pallas_call``.  Each expert's packs are laid out as
    ``_fused_mlp_matmul``'s (gate and up over the ff windows, down
    transposed), stacked on a leading expert axis and padded to a common
    slot count (idle slots are exact no-ops).  The grid walks (expert, ff
    window); each step is a fused-MLP window whose hidden state is scaled
    by the rows' weights for that expert before its down projection, and
    every step accumulates into one ``(B, D)`` fp32 output.  Every row runs
    through every expert: a row an expert was not chosen for has weight 0.
    A ``jax.vmap`` over ``x`` and ``weights`` folds into the call's rows
    (``fold_slot_vmap``)."""
    b, k = x.shape
    e, t, kk, _ = gate_values.shape
    d_out = down_values.shape[2]
    assert kk == k and up_values.shape[:3] == (e, t, k), (gate_values.shape, up_values.shape, k)
    assert down_values.shape[:2] == (e, t) and weights.shape == (b, e), (
        down_values.shape, weights.shape,
    )
    assert m <= 128, m
    assert reconstruct in RECONSTRUCT_MODES, reconstruct
    k_blk = max(1, min(k_blk, max(k, d_out)))
    nib = 2 if value_dtype == "int4" else 1
    vg, vu, vd = gate_values.shape[3], up_values.shape[3], down_values.shape[3]
    sg, su, sd = gate_positions.shape[3], up_positions.shape[3], down_positions.shape[3]
    assert (vg * nib, vu * nib, vd * nib) == (sg, su, sd), (value_dtype, (vg, vu, vd), (sg, su, sd))

    def window(rows, slots):  # expert e's ff window i: one (rows, slots) pack block
        return pl.BlockSpec((1, rows, slots), lambda j, i: (j * t + i, 0, 0))

    def flat(a):  # (E, T, ...) -> (E*T, ...): windows in grid order
        return a.reshape(e * t, *a.shape[2:])

    in_specs = [
        pl.BlockSpec((b, k), lambda j, i: (0, 0)),
        pl.BlockSpec((1, b, 1), lambda j, i: (j, 0, 0)),
        window(k, vg), window(k, sg), window(k, vu), window(k, su),
        window(d_out, vd), window(d_out, sd),
    ]
    operands = [
        x, weights.T.astype(jnp.float32)[:, :, None],
        flat(gate_values), flat(gate_positions), flat(up_values), flat(up_positions),
        flat(down_values), flat(down_positions),
    ]
    if value_dtype == "dense":
        assert gate_scales is None and up_scales is None and down_scales is None
    else:
        assert gate_scales.shape == (e, t, k) and up_scales.shape == (e, t, k), (
            gate_scales.shape, up_scales.shape,
        )
        assert down_scales.shape == (e, t, d_out), (down_scales.shape, e, t, d_out)
        in_specs += [window(1, k), window(1, k), window(1, d_out)]
        operands += [
            gate_scales.reshape(e * t, 1, k), up_scales.reshape(e * t, 1, k),
            down_scales.reshape(e * t, 1, d_out),
        ]
    return pl.pallas_call(
        functools.partial(
            _fused_mlp_kernel, m=m, k_blk=k_blk, reconstruct=reconstruct,
            value_dtype=value_dtype, exact_rows=interpret,
            gated=True, grid_rank=2,
        ),
        grid=(e, t),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((b, d_out), lambda j, i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, d_out), jnp.float32),
        interpret=interpret,
        name="vusa_fused_moe_matmul",
    )(*operands)


# --------------------------------------------------------------------------
# Slot vmap -> kernel rows
# --------------------------------------------------------------------------
#
# ``pallas_call``'s own batching rule turns a vmapped axis into an extra
# outer grid axis, so a Scheduler that decodes n slots under ``jax.vmap``
# would run n grid passes per call, each reconstructing the whole pack to
# multiply one slot's rows.  The kernels cost almost the same for 1 row as
# for 8 (a block pads to 8 sublanes; the reconstruction does not see B), so
# when only ``x`` carries the vmapped axis the rule below reshapes it to
# rows and makes one call.  Each row's math is unchanged.

_TALLY: contextvars.ContextVar = contextvars.ContextVar("vusa_fold_tally", default=None)
_REPEAT: contextvars.ContextVar = contextvars.ContextVar("vusa_call_repeat", default=1)


class FoldTally:
    """Packed-kernel calls that met a vmap while a program was traced:
    ``folded`` went to the kernel's rows, ``fallback`` to ``pallas_call``'s
    grid-axis rule (a batched pack).  Each call counts the times it runs
    per step of the traced program (``calls_repeat``)."""

    def __init__(self):
        self._sites = {}

    def record(self, site, repeat: int, folded: bool):
        self._sites[site] = (repeat, folded)  # a site re-batched counts once

    @property
    def folded(self) -> int:
        return sum(r for r, f in self._sites.values() if f)

    @property
    def fallback(self) -> int:
        return sum(r for r, f in self._sites.values() if not f)


@contextlib.contextmanager
def fold_tally():
    """Collect a :class:`FoldTally` of the vmapped kernel calls traced
    inside the block (trace time only; a compiled program reruns nothing)."""
    tally = FoldTally()
    token = _TALLY.set(tally)
    try:
        yield tally
    finally:
        _TALLY.reset(token)


@contextlib.contextmanager
def calls_repeat(n: int):
    """Kernel calls traced inside the block run ``n`` times per step: the
    body of an ``n``-long scan (layers, draft steps).  Nests by product."""
    token = _REPEAT.set(_REPEAT.get() * n)
    try:
        yield
    finally:
        _REPEAT.reset(token)


def fold_slot_vmap(kernel, rows: int = 1):
    """Give ``kernel(*row_args, *pack, **static)`` a vmap rule of its own:
    its first ``rows`` operands are per-row ((B, ...): ``x``, and the
    grouped kernel's weights).  When only those carry the vmapped axis, the
    (n, B, ...) rows run as one (n*B, ...) call (an unbatched row operand
    is broadcast first) and the result is reshaped back; when any pack
    operand is batched, it falls back to ``jax.vmap`` of the kernel (the
    grid axis).  Unbatched calls are the kernel's own program."""

    @functools.wraps(kernel)
    def call(*args, **static):
        run = functools.partial(kernel, **static)
        repeat = _REPEAT.get()

        @jax.custom_batching.custom_vmap
        def folded(*args):
            return run(*args)

        @folded.def_vmap
        def rule(axis_size, in_batched, *args):
            row_batched, pack_batched = in_batched[:rows], in_batched[rows:]
            fold = any(row_batched) and not any(jax.tree.leaves(pack_batched))
            tally = _TALLY.get()
            if tally is not None:
                tally.record(rule, repeat, fold)
            if fold:
                lead = [a if bt else jnp.broadcast_to(a, (axis_size, *a.shape))
                        for a, bt in zip(args[:rows], row_batched)]
                n, b = lead[0].shape[:2]
                # an outer vmap folds again
                y = folded(*[a.reshape(n * b, *a.shape[2:]) for a in lead], *args[rows:])
                return y.reshape(n, b, *y.shape[1:]), True
            axes = [0 if bt else None for bt in in_batched]
            return jax.vmap(run, in_axes=axes)(*args), True

        return folded(*args)

    return call


vusa_packed_matmul = fold_slot_vmap(_packed_matmul)
vusa_fused_mlp_matmul = fold_slot_vmap(_fused_mlp_matmul)
vusa_fused_moe_matmul = fold_slot_vmap(_fused_moe_matmul, rows=2)
