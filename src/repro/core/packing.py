"""VUSA packed sparse-weight formats.

Two granularities:

* ``pack_exact`` — the paper's scalar-granularity format: per row-tile, the
  greedy scheduler's jobs with per-row MAC<->SPE assignments (Section III).
  Used by the simulator and to property-test the wiring claim.

* ``pack_blocks`` — the TPU adaptation (DESIGN.md §2): the reduction dim is
  cut into windows of ``m_blk`` rows; per output tile of ``tile_n`` columns,
  only rows containing any non-zero are kept and packed into jobs of
  ``a_blk`` rows + an int32 row-index map (the "shifter setting").  This is
  what ``repro.kernels.vusa_spmm`` consumes.
"""

from __future__ import annotations

import dataclasses
from typing import List, Tuple

import numpy as np

from .vusa import Job, mac_assignment, schedule_matrix

__all__ = [
    "ExactPacked", "pack_exact", "unpack_exact",
    "BlockPacked", "pack_blocks", "unpack_blocks",
    "RowPacked", "pack_rows", "pack_rows_t", "unpack_rows", "shard_windows",
    "validate_rows",
    "QUANT_DTYPES", "QMAX", "QuantizedRowPacked",
    "quantize_rows", "dequantize_rows", "pack_nibbles", "unpack_nibbles",
    "nm_mask", "pack_rows_nm",
]


# --------------------------------------------------------------------------
# Exact (scalar) VUSA format
# --------------------------------------------------------------------------


@dataclasses.dataclass
class ExactPacked:
    """Scalar VUSA pack of a (K, C) matrix on an (N, M, A) array."""

    N: int
    M: int
    A: int
    rows: int
    cols: int
    # Per row-tile: list of (job, values (N, A), spe_positions (N, A) int, -1 = idle MAC)
    tiles: List[List[Tuple[Job, np.ndarray, np.ndarray]]]

    @property
    def n_jobs(self) -> int:
        return sum(len(t) for t in self.tiles)


def pack_exact(w: np.ndarray, N: int, M: int, A: int) -> ExactPacked:
    k, c = w.shape
    sched = schedule_matrix(w != 0, N, M, A)
    tiles = []
    for t, jobs in enumerate(sched.jobs):
        r0 = t * N
        rows = min(N, k - r0)
        packed_jobs = []
        for job in jobs:
            vals = np.zeros((N, A), dtype=w.dtype)
            pos = np.full((N, A), -1, dtype=np.int64)
            for r in range(rows):
                row = w[r0 + r, job.start : job.start + job.width]
                nz = np.flatnonzero(row)
                macs = mac_assignment(nz, M, A)
                assert macs is not None, "scheduler produced an infeasible window"
                for p, j in zip(nz, macs):
                    vals[r, j] = row[p]
                    pos[r, j] = p
            packed_jobs.append((job, vals, pos))
        tiles.append(packed_jobs)
    return ExactPacked(N=N, M=M, A=A, rows=k, cols=c, tiles=tiles)


def unpack_exact(p: ExactPacked) -> np.ndarray:
    w = np.zeros((p.rows, p.cols), dtype=p.tiles[0][0][1].dtype if p.tiles else np.float32)
    for t, jobs in enumerate(p.tiles):
        r0 = t * p.N
        for job, vals, pos in jobs:
            for r in range(min(p.N, p.rows - r0)):
                for j in range(p.A):
                    if pos[r, j] >= 0:
                        w[r0 + r, job.start + pos[r, j]] = vals[r, j]
    return w


# --------------------------------------------------------------------------
# Block (TPU) VUSA format
# --------------------------------------------------------------------------


@dataclasses.dataclass
class BlockPacked:
    """Block-VUSA pack of a (K, C) matrix.

    values : (n_tiles, n_jobs, a_blk, tile_n) — packed non-zero weight rows
    row_idx: (n_tiles, n_jobs, a_blk) int32   — absolute K index per packed
             row (padding rows point at 0 with zero values, so the gathered
             contribution is exactly zero)
    """

    k: int
    c: int
    m_blk: int
    a_blk: int
    tile_n: int
    values: np.ndarray
    row_idx: np.ndarray

    @property
    def n_tiles(self) -> int:
        return self.values.shape[0]

    @property
    def n_jobs(self) -> int:
        return self.values.shape[1]

    @property
    def compression(self) -> float:
        """Packed weight bytes / dense weight bytes (index bytes included)."""
        dense = self.k * self.c * self.values.dtype.itemsize
        packed = self.values.size * self.values.dtype.itemsize + self.row_idx.size * 4
        return packed / dense

    @property
    def virtual_growth(self) -> float:
        """Mean K-rows covered per physical a_blk-row job (the M/A analogue)."""
        return self.k * self.n_tiles / (self.n_jobs * self.a_blk * self.n_tiles)


def pack_blocks(
    w: np.ndarray, m_blk: int, a_blk: int, tile_n: int
) -> BlockPacked:
    """Pack (K, C) sparse ``w``; K % m_blk == 0, C % tile_n == 0, m_blk % a_blk == 0."""
    k, c = w.shape
    assert k % m_blk == 0 and c % tile_n == 0 and m_blk % a_blk == 0, (k, c, m_blk, a_blk, tile_n)
    n_tiles = c // tile_n
    n_win = k // m_blk

    # Per (tile, window): rows with any non-zero -> ceil(nnz_rows/a_blk) jobs.
    jobs_per_tile = np.zeros(n_tiles, dtype=np.int64)
    tile_jobs: List[List[Tuple[int, np.ndarray]]] = [[] for _ in range(n_tiles)]
    for t in range(n_tiles):
        for wi in range(n_win):
            blk = w[wi * m_blk : (wi + 1) * m_blk, t * tile_n : (t + 1) * tile_n]
            nz_rows = np.flatnonzero((blk != 0).any(axis=1)) + wi * m_blk
            if len(nz_rows) == 0:
                continue  # fully-zero window: no job at all (MAC gating)
            for j0 in range(0, len(nz_rows), a_blk):
                rows = nz_rows[j0 : j0 + a_blk]
                tile_jobs[t].append((wi, rows))
        jobs_per_tile[t] = len(tile_jobs[t])

    n_jobs = int(jobs_per_tile.max())
    values = np.zeros((n_tiles, n_jobs, a_blk, tile_n), dtype=w.dtype)
    row_idx = np.zeros((n_tiles, n_jobs, a_blk), dtype=np.int32)
    for t in range(n_tiles):
        for j, (wi, rows) in enumerate(tile_jobs[t]):
            if len(rows):
                values[t, j, : len(rows)] = w[rows, t * tile_n : (t + 1) * tile_n]
                row_idx[t, j, : len(rows)] = rows
    return BlockPacked(
        k=k, c=c, m_blk=m_blk, a_blk=a_blk, tile_n=tile_n, values=values, row_idx=row_idx
    )


def unpack_blocks(p: BlockPacked) -> np.ndarray:
    w = np.zeros((p.k, p.c), dtype=p.values.dtype)
    for t in range(p.n_tiles):
        for j in range(p.n_jobs):
            for a in range(p.a_blk):
                # padding rows have zero values; adding is safe and exact
                w[p.row_idx[t, j, a], t * p.tile_n : (t + 1) * p.tile_n] += p.values[t, j, a]
    return w


# --------------------------------------------------------------------------
# Row-wise (exact paper format) VUSA pack for the TPU kernel
# --------------------------------------------------------------------------


@dataclasses.dataclass
class RowPacked:
    """Row-wise VUSA pack of a (K, C) matrix over windows of ``m`` lanes.

    values:    (T, K, J*A)       value slots (0 = idle)
    positions: (T, K, J*A) int8  lane index within window (-1 = idle)

    Job ``j`` slot block ``[j*A, (j+1)*A)`` is one pass of the physical
    N x A array over window ``t`` (paper Section III-C: overflow rows force
    extra passes; fully-dense still works at J = ceil(M/A)).
    """

    k: int
    c: int
    m: int
    a: int
    values: np.ndarray
    row_positions: np.ndarray

    @property
    def n_jobs(self) -> int:
        return self.values.shape[2] // self.a

    def byte_ratio(self, value_bytes: int = 2) -> float:
        """Packed / dense HBM bytes (int8 positions)."""
        dense = self.k * self.c * value_bytes
        packed = self.values.shape[0] * self.k * self.values.shape[2] * (value_bytes + 1)
        return packed / dense


def pack_rows(w: np.ndarray, m: int = 128, a: int = 16) -> RowPacked:
    """Pack (K, C) into the row-wise VUSA format (C padded to m)."""
    k, c = w.shape
    c_pad = (-c) % m
    if c_pad:
        w = np.pad(w, ((0, 0), (0, c_pad)))
    t = w.shape[1] // m
    # jobs needed per window = ceil(max row-nnz / a)
    n_jobs = 1
    per_window_nnz = []
    for ti in range(t):
        blk = w[:, ti * m : (ti + 1) * m]
        nnz = (blk != 0).sum(axis=1)
        per_window_nnz.append(nnz)
        n_jobs = max(n_jobs, int(np.ceil(nnz.max(initial=1) / a)))
    slots = n_jobs * a
    values = np.zeros((t, k, slots), dtype=w.dtype)
    positions = np.full((t, k, slots), -1, dtype=np.int8)
    for ti in range(t):
        blk = w[:, ti * m : (ti + 1) * m]
        for r in range(k):
            pos = np.flatnonzero(blk[r])
            if len(pos):
                values[ti, r, : len(pos)] = blk[r, pos]
                positions[ti, r, : len(pos)] = pos.astype(np.int8)
    return RowPacked(k=k, c=c, m=m, a=a, values=values, row_positions=positions)


def pack_rows_t(w: np.ndarray, m: int = 128, a: int = 16) -> RowPacked:
    """Row-pack ``w`` *transposed*: windows cover ``w``'s leading dim.

    For a down-projection ``w_down`` of shape (ff, d) the fused MLP kernel
    (DESIGN.md §7) needs ff — ``w_down``'s *reduction* dim — to be the
    windowed lane dim, so the window that produced a ``(B, m)`` slice of the
    hidden state can immediately consume it: ``pack_rows_t(w_down)`` packs
    the (d, ff) transpose, and reconstructing window ``t`` yields the dense
    ``(d, m)`` tile whose lanes are ``w_down`` rows ``[t*m, (t+1)*m)``.
    ``unpack_rows`` of the result therefore returns ``w.T``."""
    return pack_rows(np.ascontiguousarray(np.asarray(w).T), m=m, a=a)


def shard_windows(p: RowPacked, n_shards: int) -> RowPacked:
    """Pad the window axis so ``n_shards`` devices can each hold a contiguous
    block of windows (the mesh ``model``-axis view used by sharded serving,
    DESIGN.md §8).

    Padded windows are exact no-op jobs — value 0, position -1 — so
    ``unpack_rows`` of the result is unchanged: window ``t`` still covers
    columns ``[t*m, (t+1)*m)`` and the pad windows reconstruct all-zero tiles
    past the real column range.  Shard ``s`` of the result owns windows
    ``[s*T/n, (s+1)*T/n)``, a contiguous column slice of the output, so the
    shards' partial outputs reassemble by concatenation (or, zero-extended,
    by sum).  ``n_shards`` that already divides the window count returns the
    pack unchanged."""
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    t = p.values.shape[0]
    pad = (-t) % n_shards
    if pad == 0:
        return p
    values = np.concatenate(
        [p.values, np.zeros((pad,) + p.values.shape[1:], p.values.dtype)]
    )
    positions = np.concatenate(
        [p.row_positions, np.full((pad,) + p.row_positions.shape[1:], -1, np.int8)]
    )
    return RowPacked(k=p.k, c=p.c, m=p.m, a=p.a, values=values, row_positions=positions)


def validate_rows(p: RowPacked) -> None:
    """Check a :class:`RowPacked`'s structural invariants; raise ``ValueError``
    naming the first violation (DESIGN.md §9).

    This is the pack/load-time integrity guard: a bit flip in the position
    metadata (the "shifter setting") would silently scatter weight values
    into the wrong lanes — finite, plausible-looking, and wrong — which the
    runtime ``isfinite`` guard can never catch.  Bounds/dtype/shape checks
    here are the only place such corruption is detectable, so every consumer
    validates before serving a pack."""
    v, q = np.asarray(p.values), np.asarray(p.row_positions)
    if v.shape != q.shape:
        raise ValueError(f"values shape {v.shape} != positions shape {q.shape}")
    if q.dtype != np.int8:
        raise ValueError(f"positions dtype must be int8, got {q.dtype}")
    if v.ndim != 3:
        raise ValueError(f"expected (T, K, S) pack, got shape {v.shape}")
    if p.m < 1 or p.a < 1 or p.m > 128:
        raise ValueError(f"window m={p.m} / slots a={p.a} out of range (int8 lanes)")
    t, k, slots = v.shape
    if k != p.k:
        raise ValueError(f"pack rows {k} != declared k={p.k}")
    if slots % p.a:
        raise ValueError(f"slot count {slots} not a multiple of a={p.a}")
    if t * p.m < p.c:
        raise ValueError(f"{t} windows of {p.m} lanes cover {t * p.m} < c={p.c} columns")
    # widen before comparing: m=128 does not fit int8, and int8 promotion
    # would wrap it, corrupting the bound itself
    q = q.astype(np.int32)
    bad = (q < -1) | (q >= p.m)
    if bad.any():
        i = tuple(int(x) for x in np.argwhere(bad)[0])
        raise ValueError(
            f"position {int(q[i])} at {i} outside [-1, {p.m}) — corrupt metadata"
        )
    if not np.isfinite(v).all():
        i = tuple(int(x) for x in np.argwhere(~np.isfinite(v))[0])
        raise ValueError(f"non-finite packed value at {i}")


def unpack_rows(p: RowPacked) -> np.ndarray:
    t, k, _ = p.values.shape
    w = np.zeros((k, t, p.m), dtype=p.values.dtype)
    ti, r, s = np.nonzero(np.asarray(p.row_positions) >= 0)
    lane = np.asarray(p.row_positions)[ti, r, s].astype(np.int64)
    # add.at accumulates repeated (row, lane) slots, as the kernel's sum does
    np.add.at(w, (r, ti, lane), np.asarray(p.values)[ti, r, s])
    return w.reshape(k, t * p.m)[:, : p.c]


# --------------------------------------------------------------------------
# Quantized row-wise pack: int8 / int4-nibble values + per-window fp32 scales
# --------------------------------------------------------------------------

QUANT_DTYPES = ("int8", "int4")
QMAX = {"int8": 127, "int4": 7}


@dataclasses.dataclass
class QuantizedRowPacked:
    """Row-wise VUSA pack with integer-quantized value slots (DESIGN.md §10).

    values:    (T, K, S) int8 for ``int8``; (T, K, S//2) int8 for ``int4``
               (two slots per byte: slot i in the low nibble of byte i,
               slot S/2 + i in its high nibble)
    positions: (T, K, S) int8  lane index within window (-1 = idle) —
               always full-resolution regardless of value dtype
    scales:    (T, K) float32  per-(window, row) dequant scale; all-zero
               rows carry scale 1.0 so dequant stays finite
    dense_itemsize: bytes per element of the *original* dense matrix — the
               honest denominator for byte-ratio accounting (quantization
               changes the pack's bytes, not the dense baseline it replaces)
    """

    k: int
    c: int
    m: int
    a: int
    value_dtype: str
    values: np.ndarray
    row_positions: np.ndarray
    scales: np.ndarray
    dense_itemsize: int

    @property
    def slots(self) -> int:
        return self.row_positions.shape[2]

    @property
    def n_jobs(self) -> int:
        return self.slots // self.a


def pack_nibbles(q: np.ndarray) -> np.ndarray:
    """Pack int4-range int8 values (..., S) into (..., S//2) bytes, S even.

    Slot ``i < S/2`` lands in the low nibble of byte ``i`` and slot
    ``S/2 + i`` in its high nibble, so the kernel decodes each half of the
    slots with one shift and never interleaves lanes."""
    if q.shape[-1] % 2:
        raise ValueError(f"slot count {q.shape[-1]} must be even to nibble-pack")
    u = q.astype(np.uint8)
    half = q.shape[-1] // 2
    lo, hi = u[..., :half], u[..., half:]
    return (((hi & 0xF) << 4) | (lo & 0xF)).astype(np.int8)


def unpack_nibbles(b: np.ndarray) -> np.ndarray:
    """Inverse of :func:`pack_nibbles`: (..., S//2) bytes -> (..., S) int8.

    ``(b << 4) >> 4`` sign-extends the low nibble, ``b >> 4`` the high one
    (int8 arithmetic shifts) — the same decode the kernel does in VMEM."""
    b = b.astype(np.int8)
    lo = ((b << 4) >> 4).astype(np.int8)
    hi = (b >> 4).astype(np.int8)
    return np.concatenate([lo, hi], axis=-1)


def quantize_rows(p: RowPacked, value_dtype: str) -> QuantizedRowPacked:
    """Quantize a :class:`RowPacked`'s value slots to ``int8`` or ``int4``.

    Symmetric per-(window, row) scaling: scale = amax / qmax over the row's
    slots within the window, q = clip(round(v / scale)).  For ``int4`` the
    slot axis is first padded to even (value 0, position -1 — an exact idle
    slot) and then nibble-packed two slots per byte."""
    if value_dtype not in QUANT_DTYPES:
        raise ValueError(f"value_dtype must be one of {QUANT_DTYPES}, got {value_dtype!r}")
    qmax = QMAX[value_dtype]
    vals = np.asarray(p.values, dtype=np.float32)
    positions = np.asarray(p.row_positions)
    amax = np.abs(vals).max(axis=2)
    scales = np.where(amax > 0, amax / qmax, 1.0).astype(np.float32)
    q = np.clip(np.rint(vals / scales[:, :, None]), -qmax, qmax).astype(np.int8)
    if value_dtype == "int4":
        if q.shape[2] % 2:
            q = np.pad(q, ((0, 0), (0, 0), (0, 1)))
            positions = np.pad(positions, ((0, 0), (0, 0), (0, 1)), constant_values=-1)
        q = pack_nibbles(q)
    return QuantizedRowPacked(
        k=p.k, c=p.c, m=p.m, a=p.a, value_dtype=value_dtype,
        values=q, row_positions=np.ascontiguousarray(positions),
        scales=scales, dense_itemsize=int(np.asarray(p.values).dtype.itemsize),
    )


def dequantize_rows(q: QuantizedRowPacked) -> RowPacked:
    """Expand a quantized pack back to a float32 :class:`RowPacked`.

    The reconstruction is exact w.r.t. the stored integers — ``q * scale``
    in float32 — which is precisely what the fused kernel computes in VMEM,
    so this is the oracle for kernel-vs-reference bit-equality."""
    raw = np.asarray(q.values)
    if q.value_dtype == "int4":
        raw = unpack_nibbles(raw)
    vals = raw.astype(np.float32) * np.asarray(q.scales, np.float32)[:, :, None]
    return RowPacked(
        k=q.k, c=q.c, m=q.m, a=q.a,
        values=vals, row_positions=np.asarray(q.row_positions),
    )


# --------------------------------------------------------------------------
# N:M structured pack (S2TA DBB blocks) — comparison arm
# --------------------------------------------------------------------------


def nm_mask(w: np.ndarray, n: int, block: int) -> np.ndarray:
    """Boolean keep-mask enforcing N:M structure along each row: in every
    block of ``block`` consecutive columns keep the ``n`` largest-magnitude
    entries (S2TA's density-bound block, PAPERS.md).  Columns past the last
    full block are kept as-is."""
    if not 1 <= n <= block:
        raise ValueError(f"need 1 <= n <= block, got n={n} block={block}")
    k, c = w.shape
    c_full = (c // block) * block
    mask = np.ones_like(w, dtype=bool)
    if c_full:
        blk = np.abs(w[:, :c_full]).reshape(k, c_full // block, block)
        # keep the top-n magnitudes per block; argpartition is O(block)
        kth = np.argpartition(blk, block - n, axis=2)[:, :, : block - n]
        bm = np.ones_like(blk, dtype=bool)
        np.put_along_axis(bm, kth, False, axis=2)
        mask[:, :c_full] = bm.reshape(k, c_full)
    return mask


def pack_rows_nm(
    w: np.ndarray, n: int = 2, block: int = 4, m: int = 128, a: int = 16
) -> RowPacked:
    """Prune ``w`` to N:M structure, then row-pack it.  The result is an
    ordinary :class:`RowPacked` — same kernel interface — but with a hard
    per-window slot bound of ``n * ceil(m / block)``, i.e. job count is
    data-independent, the property structured sparsity buys."""
    w = np.asarray(w)
    return pack_rows(np.where(nm_mask(w, n, block), w, 0), m=m, a=a)
