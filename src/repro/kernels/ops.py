"""Jit'd public wrappers around the Pallas kernels.

* runs the kernels in interpret mode on the CPU backend and compiled on
  every other backend (``interpret_mode``);
* hosts the pack/apply glue so a model layer can swap a dense matmul for a
  VUSA-packed one in a single call.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from ..core.packing import BlockPacked, pack_blocks
from .dense_matmul import dense_matmul
from .ref import vusa_spmm_ref
from .vusa_spmm import vusa_spmm

__all__ = [
    "on_tpu",
    "interpret_mode",
    "PackedLinear",
    "pack_linear",
    "apply_packed",
    "apply_packed_ref",
    "matmul",
    "RowPackedLinear",
    "pack_linear_rows",
    "pack_linear_rows_t",
    "pack_linear_rows_nm",
    "dequantize_linear_values",
    "pad_slots",
    "apply_row_packed",
    "apply_row_packed_ref",
    "choose_k_blk",
    "autotune_row_packed",
    "apply_fused_mlp",
    "apply_fused_mlp_ref",
    "autotune_fused_mlp",
    "shard_linear_windows",
    "mesh_axis_size",
    "apply_row_packed_sharded",
    "apply_fused_mlp_sharded",
]


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def interpret_mode(interpret: bool | None = None) -> bool:
    """Resolve a wrapper's ``interpret`` argument.  ``None`` interprets on
    the CPU backend only; an accelerator backend always compiles the
    kernels, and asking it to interpret is an error, not a fallback."""
    cpu = jax.default_backend() == "cpu"
    if interpret is None:
        return cpu
    if interpret and not cpu:
        raise ValueError(
            f"Pallas interpret mode is for the CPU backend only, not {jax.default_backend()!r}"
        )
    return interpret


@dataclasses.dataclass
class PackedLinear:
    """Device-resident VUSA-packed weight (K, C) -> jobs of a_blk rows."""

    values: jax.Array  # (T, J, A, Tn)
    row_idx: jax.Array  # (T, J, A) int32
    k: int  # logical K (pre-padding)
    c: int  # logical C (pre-padding)
    k_padded: int = 0

    @property
    def compression(self) -> float:
        dense = self.k * self.c * self.values.dtype.itemsize
        packed = self.values.size * self.values.dtype.itemsize + self.row_idx.size * 4
        return packed / dense


def pack_linear(
    w: np.ndarray, m_blk: int = 32, a_blk: int = 8, tile_n: int = 128
) -> PackedLinear:
    """Host-side pack of a sparse (K, C) weight matrix (pads C to tile_n)."""
    k, c = w.shape
    w = np.asarray(w)
    c_pad = (-c) % tile_n
    k_pad = (-k) % m_blk
    if c_pad or k_pad:
        w = np.pad(w, ((0, k_pad), (0, c_pad)))
    bp: BlockPacked = pack_blocks(w, m_blk=m_blk, a_blk=a_blk, tile_n=tile_n)
    return PackedLinear(
        values=jnp.asarray(bp.values),
        row_idx=jnp.asarray(bp.row_idx),
        k=k,
        c=c,
        k_padded=k + k_pad,
    )


def apply_packed(x: jax.Array, p: PackedLinear, *, interpret: bool | None = None) -> jax.Array:
    """y = x @ W for packed W.  x: (..., K) -> (..., C)."""
    interp = interpret_mode(interpret)
    lead = x.shape[:-1]
    xf = x.reshape(-1, x.shape[-1])
    if p.k_padded > p.k:  # weight was K-padded at pack time
        xf = jnp.pad(xf, ((0, 0), (0, p.k_padded - p.k)))
    y = vusa_spmm(xf, p.values, p.row_idx, interpret=interp)
    y = y[..., : p.c]
    return y.reshape(*lead, p.c)


def apply_packed_ref(x: jax.Array, p: PackedLinear) -> jax.Array:
    lead = x.shape[:-1]
    xf = x.reshape(-1, x.shape[-1])
    if p.k_padded > p.k:
        xf = jnp.pad(xf, ((0, 0), (0, p.k_padded - p.k)))
    y = vusa_spmm_ref(xf, p.values, p.row_idx)[..., : p.c]
    return y.reshape(*lead, p.c)


def matmul(x: jax.Array, w: jax.Array, *, interpret: bool | None = None) -> jax.Array:
    """Dense baseline kernel wrapper (pads to MXU-aligned tiles)."""
    interp = interpret_mode(interpret)
    m, k = x.shape
    _, n = w.shape
    bm = 128 if m % 128 == 0 else (8 if m % 8 == 0 else 1)
    y = dense_matmul(x, w, bm=bm, interpret=interp)
    return y


# --------------------------------------------------------------------------
# Row-wise (paper-format) packed linear
# --------------------------------------------------------------------------

import os  # noqa: E402
import time  # noqa: E402

from ..core.packing import (  # noqa: E402
    QUANT_DTYPES,
    RowPacked,
    pack_rows,
    pack_rows_nm,
    pack_rows_t,
    quantize_rows,
)
from .ref import vusa_fused_mlp_ref, vusa_packed_ref  # noqa: E402
from .vusa_packed import (  # noqa: E402
    DEFAULT_SLOT_CHUNK,
    vusa_fused_mlp_matmul,
    vusa_packed_matmul,
)


@dataclasses.dataclass
class RowPackedLinear:
    """Device-resident row-wise VUSA pack (see kernels/vusa_packed.py).

    ``value_dtype="dense"`` (default) keeps values in their native float
    dtype.  ``"int8"``/``"int4"`` carry raw quantized bytes (int4 nibble-
    packed, two slots per byte) plus per-(window, row) fp32 ``scales``;
    ``dense_itemsize`` remembers the original dense weight's element size so
    byte-ratio accounting keeps the honest denominator."""

    values: jax.Array  # (T, K, J*A) float, or (T, K, Sb) int8 when quantized
    positions: jax.Array  # (T, K, J*A) int8
    k: int
    c: int
    a: int
    m: int = 128  # window width (lanes)
    scales: jax.Array | None = None  # (T, K) fp32, quantized packs only
    value_dtype: str = "dense"
    dense_itemsize: int | None = None

    @property
    def slots(self) -> int:
        """Logical slot count — positions are never nibble-packed."""
        return self.positions.shape[2]

    @property
    def byte_ratio(self) -> float:
        t = self.values.shape[0]
        vb = self.values.dtype.itemsize
        dense_b = self.dense_itemsize if self.dense_itemsize else vb
        dense = self.k * t * self.m * dense_b
        packed = self.values.size * vb + self.positions.size
        if self.scales is not None:
            packed += self.scales.size * self.scales.dtype.itemsize
        return packed / dense


def _linear_from_pack(rp: RowPacked, value_dtype: str) -> RowPackedLinear:
    if value_dtype == "dense":
        return RowPackedLinear(
            values=jnp.asarray(rp.values),
            positions=jnp.asarray(rp.row_positions),
            k=rp.k, c=rp.c, a=rp.a, m=rp.m,
        )
    if value_dtype not in QUANT_DTYPES:
        raise ValueError(
            f"value_dtype must be 'dense' or one of {QUANT_DTYPES}, got {value_dtype!r}"
        )
    q = quantize_rows(rp, value_dtype)
    return RowPackedLinear(
        values=jnp.asarray(q.values),
        positions=jnp.asarray(q.row_positions),
        k=q.k, c=q.c, a=q.a, m=q.m,
        scales=jnp.asarray(q.scales),
        value_dtype=value_dtype,
        dense_itemsize=q.dense_itemsize,
    )


def pack_linear_rows(
    w: np.ndarray, m: int = 128, a: int = 16, value_dtype: str = "dense"
) -> RowPackedLinear:
    return _linear_from_pack(pack_rows(np.asarray(w), m=m, a=a), value_dtype)


def pack_linear_rows_t(
    w: np.ndarray, m: int = 128, a: int = 16, value_dtype: str = "dense"
) -> RowPackedLinear:
    """Row-pack ``w`` *transposed* — windows cover ``w``'s leading (reduction)
    dim, the operand shape ``vusa_fused_mlp_matmul`` wants for ``w_down``."""
    return _linear_from_pack(pack_rows_t(np.asarray(w), m=m, a=a), value_dtype)


def pack_linear_rows_nm(
    w: np.ndarray,
    n: int = 2,
    block: int = 4,
    m: int = 128,
    a: int = 16,
    value_dtype: str = "dense",
) -> RowPackedLinear:
    """Prune to N:M structure (S2TA DBB blocks) then row-pack — the
    structured-sparsity comparison arm, same kernel interface."""
    return _linear_from_pack(pack_rows_nm(np.asarray(w), n=n, block=block, m=m, a=a), value_dtype)


def dequantize_linear_values(p: RowPackedLinear) -> jax.Array:
    """fp32 (T, K, S) value slots of any pack — the jnp twin of the kernel's
    VMEM dequant (int4 nibbles decoded with the same arithmetic shifts: low
    nibbles are slots ``[0, S/2)``, high nibbles ``[S/2, S)``), used by the
    reference appliers and fault tooling."""
    raw = p.values
    if p.value_dtype == "dense":
        return raw.astype(jnp.float32)
    if p.value_dtype == "int4":
        lo = jnp.right_shift(jnp.left_shift(raw, 4), 4)
        hi = jnp.right_shift(raw, 4)
        raw = jnp.concatenate([lo, hi], axis=-1)
    return raw.astype(jnp.float32) * p.scales.astype(jnp.float32)[..., None]


def pad_slots(p: RowPackedLinear, slots: int) -> RowPackedLinear:
    """Pad a pack's slot axis to ``slots`` with idle slots (value 0,
    position -1), exact no-ops.  An int4 pack pads its value bytes with
    zeros at the end, which appends idle slots to *both* nibble halves, so
    its positions pad each half to ``slots / 2``."""
    s = p.slots
    if slots == s:
        return p
    assert slots > s, (slots, s)

    def idle(q, n):
        return jnp.pad(q, ((0, 0), (0, 0), (0, n)), constant_values=-1)

    if p.value_dtype == "int4":
        assert slots % 2 == 0, slots
        h, pad = s // 2, (slots - s) // 2
        positions = jnp.concatenate(
            [idle(p.positions[..., :h], pad), idle(p.positions[..., h:], pad)], axis=-1
        )
        vpad = pad
    else:
        positions, vpad = idle(p.positions, slots - s), slots - s
    return dataclasses.replace(
        p, values=jnp.pad(p.values, ((0, 0), (0, 0), (0, vpad))), positions=positions
    )


# -- k_blk / m tuning ------------------------------------------------------
#
# The kernel's only free parameters are the K block (bounds the one-hot
# scratch: k_blk * min(slots, slot_chunk) * m * 4 bytes) and the window
# width m (fixed at pack time, <= 128).  ``choose_k_blk`` is the heuristic;
# ``autotune_row_packed`` measures the candidates once per shape and caches
# the winner so subsequent ``apply_row_packed`` calls use it.

_KBLK_CACHE: dict = {}  # (k, slots, m, b, backend) -> k_blk
_VMEM_SCRATCH_BUDGET = 2 * 1024 * 1024  # bytes for the one-hot scatter tensor


def _kblk_candidates(k: int, slots: int, m: int):
    """K blocks worth trying for a reduction of ``k`` rows, ascending.

    On the TPU a block must be lane-aligned — a multiple of 128 that divides
    ``k``, or all of ``k`` — and its one-hot scatter scratch, ``k_blk *
    min(slots, slot_chunk) * m * 4`` bytes, must fit the VMEM budget: Mosaic
    refuses the rest.  When no aligned block fits, the smallest aligned one
    is the only candidate.  Off the TPU (interpret mode) there is no such
    wall, and powers of two from 64 plus ``k`` itself are tried."""
    if not on_tpu():
        c = [blk for blk in (64, 128, 256, 512, 1024) if k % blk == 0 and blk <= k]
        if k <= 2048 and k not in c:
            c.append(k)
        return c or [k]
    aligned = [blk for blk in range(128, k + 1, 128) if k % blk == 0] or [k]
    scratch = min(slots, DEFAULT_SLOT_CHUNK) * m * 4
    return [blk for blk in aligned if blk * scratch <= _VMEM_SCRATCH_BUDGET] or aligned[:1]


def _largest_divisor_leq(k: int, blk: int) -> int:
    """Largest divisor of ``k`` that is <= ``blk``, in O(sqrt k).

    The seed snapped ``REPRO_VUSA_KBLK`` down one step at a time
    (``while k % blk: blk -= 1``) — O(k) when the override lands just above
    a small divisor of a large prime-ish K."""
    blk = max(1, min(blk, k))
    best = 1
    for i in range(1, int(k**0.5) + 1):
        if k % i == 0:
            if i <= blk:
                best = max(best, i)
            if k // i <= blk:
                best = max(best, k // i)
    return best


def choose_k_blk(k: int, slots: int, m: int) -> int:
    """Pick the K block without measuring: the largest candidate.  On the
    TPU the candidates are already VMEM-budgeted; off it (interpret mode)
    fewer, larger grid steps win (measured in benchmarks/run.py
    kernel_vusa_packed)."""
    env = os.environ.get("REPRO_VUSA_KBLK")
    if env:
        try:
            blk = int(env)
        except ValueError as e:
            raise ValueError(f"REPRO_VUSA_KBLK must be an integer, got {env!r}") from e
        return _largest_divisor_leq(k, blk)  # snap down to a divisor of k
    return _kblk_candidates(k, slots, m)[-1]


def _tune_key(
    xf: jax.Array, p: RowPackedLinear, interp: bool, reconstruct: str, slot_chunk: int
):
    # reconstruct/slot_chunk are part of the key: a k_blk tuned for the
    # one-pass "onehot" reconstruction is generally wrong for the per-slot
    # "loop" baseline (and vice versa) — the seed omitted both, so a cache
    # entry from one mode silently drove the other
    # value_dtype must be explicit: int8 and int4 packs share the jnp int8
    # array dtype, so str(dtype) alone would collide their cache entries
    # The REPRO_VUSA_KBLK override is part of the key: an entry tuned while
    # the override was set (or cleared) must not be served after the env
    # changes mid-process
    return (
        xf.shape[-1], p.values.shape[2], p.m, xf.shape[0],
        str(p.values.dtype), p.value_dtype, interp, jax.default_backend(),
        reconstruct, slot_chunk, os.environ.get("REPRO_VUSA_KBLK", ""),
    )


def autotune_row_packed(
    x: jax.Array,
    p: RowPackedLinear,
    *,
    interpret: bool | None = None,
    iters: int = 5,
    reconstruct: str = "onehot",
    slot_chunk: int = DEFAULT_SLOT_CHUNK,
) -> int:
    """Time the kernel over k_blk candidates; cache + return the winner."""
    interp = interpret_mode(interpret)
    xf = x.reshape(-1, x.shape[-1])
    key = _tune_key(xf, p, interp, reconstruct, slot_chunk)
    if key in _KBLK_CACHE:
        return _KBLK_CACHE[key]
    best_blk, best_t = None, float("inf")
    for blk in _kblk_candidates(xf.shape[-1], p.slots, p.m):
        f = lambda a: vusa_packed_matmul(
            a, p.values, p.positions, p.scales, m=p.m, k_blk=blk, interpret=interp,
            reconstruct=reconstruct, slot_chunk=slot_chunk, value_dtype=p.value_dtype,
        )
        f(xf).block_until_ready()  # compile
        t0 = time.perf_counter()
        for _ in range(iters):
            f(xf).block_until_ready()
        dt = (time.perf_counter() - t0) / iters
        if dt < best_t:
            best_blk, best_t = blk, dt
    _KBLK_CACHE[key] = best_blk
    return best_blk


def apply_row_packed(
    x: jax.Array,
    p: RowPackedLinear,
    *,
    interpret: bool | None = None,
    k_blk: int | None = None,
    reconstruct: str = "onehot",
    slot_chunk: int = DEFAULT_SLOT_CHUNK,
) -> jax.Array:
    """y = x @ W for row-packed W.  x: (..., K) -> (..., C).

    ``k_blk=None`` consults the autotune cache (populated by
    ``autotune_row_packed``), falling back to the ``choose_k_blk`` heuristic.
    """
    interp = interpret_mode(interpret)
    lead = x.shape[:-1]
    xf = x.reshape(-1, x.shape[-1])
    k = xf.shape[-1]
    slots = p.slots  # logical slots: the scratch bound sees decoded nibbles
    if k_blk is None:
        if os.environ.get("REPRO_VUSA_KBLK"):  # explicit override beats the cache
            k_blk = choose_k_blk(k, slots, p.m)
        else:
            k_blk = _KBLK_CACHE.get(
                _tune_key(xf, p, interp, reconstruct, slot_chunk)
            ) or choose_k_blk(k, slots, p.m)
    k_blk = min(k_blk, k)
    while k % k_blk:
        k_blk //= 2
    y = vusa_packed_matmul(
        xf,
        p.values,
        p.positions,
        p.scales,
        m=p.m,
        k_blk=max(k_blk, 1),
        interpret=interp,
        reconstruct=reconstruct,
        slot_chunk=slot_chunk,
        value_dtype=p.value_dtype,
    )
    return y[..., : p.c].reshape(*lead, p.c).astype(x.dtype)


def apply_row_packed_ref(x: jax.Array, p: RowPackedLinear) -> jax.Array:
    lead = x.shape[:-1]
    xf = x.reshape(-1, x.shape[-1])
    y = vusa_packed_ref(xf, dequantize_linear_values(p), p.positions)
    return y[..., : p.c].reshape(*lead, p.c).astype(x.dtype)


# --------------------------------------------------------------------------
# Fused packed MLP (DESIGN.md §7): silu(x@Wg) * (x@Wu) @ Wd in one kernel
# --------------------------------------------------------------------------


def _check_fused_packs(
    k: int, gate: RowPackedLinear, up: RowPackedLinear, down_t: RowPackedLinear
) -> None:
    assert gate.k == k and up.k == k, (gate.k, up.k, k)
    assert gate.m == up.m == down_t.m, (gate.m, up.m, down_t.m)
    assert gate.c == up.c == down_t.c, (gate.c, up.c, down_t.c)  # all windowed over ff
    t = gate.values.shape[0]
    assert up.values.shape[0] == t and down_t.values.shape[0] == t
    assert gate.value_dtype == up.value_dtype == down_t.value_dtype, (
        gate.value_dtype, up.value_dtype, down_t.value_dtype,
    )


def _fused_tune_key(
    xf: jax.Array,
    gate: RowPackedLinear,
    up: RowPackedLinear,
    down_t: RowPackedLinear,
    interp: bool,
    reconstruct: str,
    slot_chunk: int,
):
    return (
        "fused", xf.shape[-1], down_t.k, xf.shape[0],
        gate.values.shape[2], up.values.shape[2], down_t.values.shape[2], gate.m,
        str(gate.values.dtype), gate.value_dtype, interp, jax.default_backend(),
        reconstruct, slot_chunk, os.environ.get("REPRO_VUSA_KBLK", ""),
    )


def autotune_fused_mlp(
    x: jax.Array,
    gate: RowPackedLinear,
    up: RowPackedLinear,
    down_t: RowPackedLinear,
    *,
    interpret: bool | None = None,
    iters: int = 5,
    reconstruct: str = "onehot",
    slot_chunk: int = DEFAULT_SLOT_CHUNK,
) -> int:
    """Time the fused megakernel over k_blk candidates; cache the winner.

    The fused shape is its own tuning problem — its k_blk chunks *both* the
    d_model reduction of gate/up and the d_model output rows of the down
    accumulation, so the row-packed winner does not transfer."""
    interp = interpret_mode(interpret)
    xf = x.reshape(-1, x.shape[-1])
    _check_fused_packs(xf.shape[-1], gate, up, down_t)
    key = _fused_tune_key(xf, gate, up, down_t, interp, reconstruct, slot_chunk)
    if key in _KBLK_CACHE:
        return _KBLK_CACHE[key]
    best_blk, best_t = None, float("inf")
    slots = max(gate.slots, up.slots, down_t.slots)
    cands = _kblk_candidates(xf.shape[-1], slots, gate.m) + _kblk_candidates(
        down_t.k, slots, gate.m
    )
    for blk in sorted(set(cands)):
        f = lambda a: vusa_fused_mlp_matmul(
            a, gate.values, gate.positions, up.values, up.positions,
            down_t.values, down_t.positions,
            gate.scales, up.scales, down_t.scales, m=gate.m, k_blk=blk,
            interpret=interp, reconstruct=reconstruct, slot_chunk=slot_chunk,
            value_dtype=gate.value_dtype,
        )
        f(xf).block_until_ready()  # compile
        t0 = time.perf_counter()
        for _ in range(iters):
            f(xf).block_until_ready()
        dt = (time.perf_counter() - t0) / iters
        if dt < best_t:
            best_blk, best_t = blk, dt
    _KBLK_CACHE[key] = best_blk
    return best_blk


def apply_fused_mlp(
    x: jax.Array,
    gate: RowPackedLinear,
    up: RowPackedLinear,
    down_t: RowPackedLinear,
    *,
    interpret: bool | None = None,
    k_blk: int | None = None,
    reconstruct: str = "onehot",
    slot_chunk: int = DEFAULT_SLOT_CHUNK,
) -> jax.Array:
    """Whole SwiGLU MLP through the fused megakernel.

    ``gate``/``up`` row-pack (K, ff); ``down_t`` row-packs ``w_down``
    transposed (``pack_linear_rows_t``) so the ff reduction is windowed.
    x: (..., K) -> (..., D) where D = ``down_t.k``.  One ``pallas_call``
    replaces the gate/up/down dispatch triple and the (..., ff) intermediate
    stays in VMEM.  ``k_blk=None`` consults the autotune cache (populated by
    ``autotune_fused_mlp``), falling back to ``choose_k_blk``; unlike the
    plain row-packed kernel the chunk size need not divide K."""
    interp = interpret_mode(interpret)
    lead = x.shape[:-1]
    xf = x.reshape(-1, x.shape[-1])
    k = xf.shape[-1]
    _check_fused_packs(k, gate, up, down_t)
    if k_blk is None:
        slots = max(gate.slots, up.slots, down_t.slots)
        if os.environ.get("REPRO_VUSA_KBLK"):  # explicit override beats the cache
            k_blk = choose_k_blk(k, slots, gate.m)
        else:
            k_blk = _KBLK_CACHE.get(
                _fused_tune_key(xf, gate, up, down_t, interp, reconstruct, slot_chunk)
            ) or choose_k_blk(k, slots, gate.m)
    y = vusa_fused_mlp_matmul(
        xf,
        gate.values,
        gate.positions,
        up.values,
        up.positions,
        down_t.values,
        down_t.positions,
        gate.scales,
        up.scales,
        down_t.scales,
        m=gate.m,
        k_blk=max(int(k_blk), 1),
        interpret=interp,
        reconstruct=reconstruct,
        slot_chunk=slot_chunk,
        value_dtype=gate.value_dtype,
    )
    return y.reshape(*lead, down_t.k).astype(x.dtype)


def apply_fused_mlp_ref(
    x: jax.Array, gate: RowPackedLinear, up: RowPackedLinear, down_t: RowPackedLinear
) -> jax.Array:
    lead = x.shape[:-1]
    xf = x.reshape(-1, x.shape[-1])
    _check_fused_packs(xf.shape[-1], gate, up, down_t)
    y = vusa_fused_mlp_ref(
        xf, dequantize_linear_values(gate), gate.positions,
        dequantize_linear_values(up), up.positions,
        dequantize_linear_values(down_t), down_t.positions, m=gate.m,
    )
    return y.reshape(*lead, down_t.k).astype(x.dtype)


# --------------------------------------------------------------------------
# Mesh-sharded appliers (DESIGN.md §8): the pack's window axis is split over
# the `model` mesh axis and each device runs the *single-device* kernel on
# its window shard — the virtually upscaled array spans devices, not just
# one chip's lanes.  mesh=None (or a size-1 model axis) is the degenerate
# case and routes straight to the plain appliers, byte-identical program.
# --------------------------------------------------------------------------

from jax.sharding import PartitionSpec as _P  # noqa: E402


def mesh_axis_size(mesh, axis_name: str = "model") -> int:
    """Size of a mesh axis; 1 for no mesh / absent axis (degenerate case)."""
    if mesh is None or axis_name not in mesh.shape:
        return 1
    return int(mesh.shape[axis_name])


def shard_linear_windows(p: RowPackedLinear, n_shards: int) -> RowPackedLinear:
    """Pad the window axis to a multiple of ``n_shards`` with no-op windows
    (value 0, position -1) — the device-array twin of
    ``core.packing.shard_windows``.  ``k``/``c`` metadata is unchanged: pad
    windows reconstruct zero tiles past the real column range.  Quantized
    packs pad scales with 1.0 so the no-op windows dequant to exact zeros
    while staying finite."""
    t = p.values.shape[0]
    pad = (-t) % n_shards
    if pad == 0:
        return p
    values = jnp.pad(p.values, ((0, pad), (0, 0), (0, 0)))
    positions = jnp.pad(p.positions, ((0, pad), (0, 0), (0, 0)), constant_values=-1)
    scales = None
    if p.scales is not None:
        scales = jnp.pad(p.scales, ((0, pad), (0, 0)), constant_values=1.0)
    return RowPackedLinear(
        values=values, positions=positions, k=p.k, c=p.c, a=p.a, m=p.m,
        scales=scales, value_dtype=p.value_dtype, dense_itemsize=p.dense_itemsize,
    )


def _local_view(p: RowPackedLinear, values, positions, t_local: int, scales=None) -> RowPackedLinear:
    """Per-shard view: same geometry, ``c`` covering only the local windows."""
    return RowPackedLinear(
        values=values, positions=positions, k=p.k, c=t_local * p.m, a=p.a, m=p.m,
        scales=scales, value_dtype=p.value_dtype, dense_itemsize=p.dense_itemsize,
    )


def apply_row_packed_sharded(
    x: jax.Array,
    p: RowPackedLinear,
    mesh=None,
    axis_name: str = "model",
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """``apply_row_packed`` with the window axis sharded over ``axis_name``.

    Windows tile the *output* columns, so each shard's kernel emits a
    contiguous ``(B, T_loc*m)`` column slice; a tiled all-gather over the
    mesh axis reassembles the full width on every device (column-parallel
    output, the tensor-parallel twin of the fused kernel's psum).  Values
    and positions enter the shard_map split on their leading window axis —
    pre-placing them with ``dist.sharding.window_sharding`` makes that split
    free.  Degenerate mesh (None or size-1 axis) runs the plain kernel."""
    tp = mesh_axis_size(mesh, axis_name)
    if tp == 1:
        return apply_row_packed(x, p, interpret=interpret)
    p = shard_linear_windows(p, tp)
    t = p.values.shape[0]
    t_local = t // tp
    lead = x.shape[:-1]
    xf = x.reshape(-1, x.shape[-1])
    quant = p.scales is not None

    def local(xf, values, positions, scales=None):
        y = apply_row_packed(
            xf, _local_view(p, values, positions, t_local, scales), interpret=interpret
        )
        return jax.lax.all_gather(y, axis_name, axis=1, tiled=True)

    # scales share the leading window axis, so they ride the same spec
    operands = (xf, p.values, p.positions) + ((p.scales,) if quant else ())
    y = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(_P(),) + (_P(axis_name),) * (3 if quant else 2),
        out_specs=_P(),
        check_vma=False,
    )(*operands)
    return y[..., : p.c].reshape(*lead, p.c).astype(x.dtype)


def apply_fused_mlp_sharded(
    x: jax.Array,
    gate: RowPackedLinear,
    up: RowPackedLinear,
    down_t: RowPackedLinear,
    mesh=None,
    axis_name: str = "model",
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """``apply_fused_mlp`` with the ff-window axis sharded over ``axis_name``.

    All three packs window the same ff dim, so one shard owns a slab of ff:
    it reconstructs its ``w_gate``/``w_up`` windows, forms that slab of
    ``silu(gate) * up`` in VMEM, and folds it through its ``w_down`` rows
    into a *partial* ``(B, d_model)`` output; a psum over the mesh axis sums
    the shards — ff is ``w_down``'s reduction dim, so partial outputs add.
    Degenerate mesh runs the plain megakernel."""
    tp = mesh_axis_size(mesh, axis_name)
    if tp == 1:
        return apply_fused_mlp(x, gate, up, down_t, interpret=interpret)
    _check_fused_packs(x.shape[-1], gate, up, down_t)
    gate = shard_linear_windows(gate, tp)
    up = shard_linear_windows(up, tp)
    down_t = shard_linear_windows(down_t, tp)
    t_local = gate.values.shape[0] // tp
    lead = x.shape[:-1]
    xf = x.reshape(-1, x.shape[-1])
    quant = gate.scales is not None

    def local(xf, gv, gp, uv, upp, dv, dp, gs=None, us=None, ds=None):
        y = apply_fused_mlp(
            xf,
            _local_view(gate, gv, gp, t_local, gs),
            _local_view(up, uv, upp, t_local, us),
            _local_view(down_t, dv, dp, t_local, ds),
            interpret=interpret,
        )
        return jax.lax.psum(y.astype(jnp.float32), axis_name)

    operands = (
        xf, gate.values, gate.positions, up.values, up.positions,
        down_t.values, down_t.positions,
    ) + ((gate.scales, up.scales, down_t.scales) if quant else ())
    y = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(_P(),) + (_P(axis_name),) * (9 if quant else 6),
        out_specs=_P(),
        check_vma=False,
    )(*operands)
    return y.reshape(*lead, down_t.k).astype(x.dtype)
