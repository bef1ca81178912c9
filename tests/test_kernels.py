"""Per-kernel allclose tests: Pallas (interpret mode) vs the pure-jnp oracle
in ref.py, swept over shapes, dtypes and sparsity levels (+ hypothesis)."""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.kernels.ops import (
    apply_packed,
    apply_packed_ref,
    apply_row_packed,
    apply_row_packed_ref,
    matmul,
    pack_linear,
    pack_linear_rows,
)
from repro.kernels.ref import dense_matmul_ref


def _sparse(rng, k, c, sparsity, dtype):
    w = rng.normal(size=(k, c)) * (rng.random((k, c)) > sparsity)
    return w.astype(dtype)


# ---------------------------------------------------------------------------
# dense baseline kernel
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m,k,n", [(8, 128, 128), (128, 256, 384), (16, 64, 256)])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_dense_matmul_vs_ref(m, k, n, dtype):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(m, k)), dtype=dtype)
    w = jnp.asarray(rng.normal(size=(k, n)), dtype=dtype)
    got = matmul(x, w)
    want = dense_matmul_ref(x, w).astype(jnp.float32)
    tol = 1e-5 if dtype == np.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=tol, atol=tol)


# ---------------------------------------------------------------------------
# block-gated kernel (vusa_spmm)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "b,k,c,sp,m_blk,a_blk",
    [
        (8, 256, 384, 0.9, 32, 8),
        (4, 100, 130, 0.85, 32, 8),  # unaligned -> padding path
        (16, 512, 256, 0.0, 32, 8),  # fully dense still exact
        (2, 64, 128, 0.99, 16, 8),
    ],
)
def test_vusa_spmm_vs_dense(b, k, c, sp, m_blk, a_blk):
    rng = np.random.default_rng(1)
    w = _sparse(rng, k, c, sp, np.float32)
    x = jnp.asarray(rng.normal(size=(b, k)), dtype=jnp.float32)
    p = pack_linear(w, m_blk, a_blk, 128)
    got = apply_packed(x, p)
    ref = apply_packed_ref(x, p)
    dense = np.asarray(x) @ w
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(got), dense, rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------------
# row-wise packed kernel (vusa_packed) — the paper's format
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "b,k,c,sp,a",
    [
        (8, 256, 384, 0.85, 16),
        (4, 128, 130, 0.9, 8),
        (16, 256, 128, 0.0, 64),  # dense fallback
        (2, 512, 256, 0.97, 8),
        (1, 64, 128, 0.5, 32),  # B=1 decode
    ],
)
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_vusa_packed_vs_dense(b, k, c, sp, a, dtype):
    rng = np.random.default_rng(2)
    w = jnp.asarray(_sparse(rng, k, c, sp, np.float32), dtype=dtype)
    x = jnp.asarray(rng.normal(size=(b, k)), dtype=dtype)
    p = pack_linear_rows(np.asarray(w, np.float32), a=a)
    got = np.asarray(apply_row_packed(x, p), np.float32)
    ref = np.asarray(apply_row_packed_ref(x, p), np.float32)
    dense = np.asarray(x, np.float32) @ np.asarray(w, np.float32)
    tol = 1e-4 if dtype == np.float32 else 0.5
    np.testing.assert_allclose(got, ref, rtol=tol, atol=tol)
    np.testing.assert_allclose(got, dense, rtol=tol, atol=tol)


@given(
    b=st.integers(1, 8),
    kt=st.integers(1, 4),
    sp=st.floats(0.0, 0.99),
    seed=st.integers(0, 10),
)
@settings(max_examples=15, deadline=None)
def test_vusa_packed_property(b, kt, sp, seed):
    """Property: packed execution == dense matmul for any sparsity pattern."""
    rng = np.random.default_rng(seed)
    k, c = 32 * kt, 128
    w = _sparse(rng, k, c, sp, np.float32)
    x = jnp.asarray(rng.normal(size=(b, k)), dtype=jnp.float32)
    p = pack_linear_rows(w, a=8)
    got = np.asarray(apply_row_packed(x, p))
    np.testing.assert_allclose(got, np.asarray(x) @ w, rtol=1e-4, atol=1e-4)


def test_byte_ratio_vs_sparsity_tracks_growth_model():
    """Kernel-format byte savings follow the paper's virtual-growth math:
    at sparsity s, jobs ~ ceil(max_row_nnz/A) so bytes shrink ~ (1-s)."""
    rng = np.random.default_rng(3)
    ratios = []
    for sp in (0.5, 0.85, 0.95):
        w = _sparse(rng, 512, 512, sp, np.float32)
        ratios.append(pack_linear_rows(w, a=8).byte_ratio)
    assert ratios[0] > ratios[1] > ratios[2]


# ---------------------------------------------------------------------------
# k_blk candidates and interpret mode
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "k,slots,want",
    [
        (768, 48, [128, 256, 384, 768]),  # vusa_edge d_model rows at 85 %
        (3072, 48, [128, 256, 384, 512, 768, 1024, 1536]),  # d_ff rows (w_down)
        (768, 8, [128, 256, 384, 768]),  # few slots: the whole K fits too
        (64, 48, [64]),  # K below one lane tile: the whole K
    ],
)
def test_kblk_candidates_on_chip(monkeypatch, k, slots, want):
    """On the TPU only lane-aligned blocks whose reconstruction chunk fits
    the VMEM budget (per row: 4 x slots x 4 bytes of widened, transposed
    slots and an m x 4 column of the tile) are candidates, so the
    autotuners never time a block Mosaic refuses; choose_k_blk takes one
    lane tile of rows, ``TILE_ROWS``, wherever it is a candidate."""
    from repro.kernels import ops
    from repro.kernels.vusa_packed import TILE_ROWS

    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    monkeypatch.delenv("REPRO_VUSA_KBLK", raising=False)
    assert ops._kblk_candidates(k, slots, 128) == want
    assert ops.choose_k_blk(k, slots, 128) == (TILE_ROWS if TILE_ROWS in want else want[-1])


@pytest.mark.parametrize("reconstruct", ["onehot", "loop"])
@pytest.mark.parametrize("value_dtype", ["bf16", "int8", "int4"])
def test_tile_is_the_transposed_window(value_dtype, reconstruct):
    """A window's reconstructed tile is its dense columns transposed, (m,
    rows), exactly: bf16, int8 and int4 packs whose rows leave slots idle,
    over a ragged last window (200 columns in two 128-lane windows)."""
    from repro.core.packing import dequantize_rows, pack_rows, quantize_rows, unpack_rows
    from repro.kernels.vusa_packed import _tile

    rng = np.random.default_rng(12)
    k, c, m = 40, 200, 128
    w = _sparse(rng, k, c, 0.85, np.float32)
    w = np.asarray(jnp.asarray(w, jnp.bfloat16), np.float32)  # exact in bf16
    rp = pack_rows(w, m=m, a=8)
    assert (rp.row_positions < 0).any()  # idle slots
    if value_dtype == "bf16":
        values, positions, scales = jnp.asarray(rp.values, jnp.bfloat16), rp.row_positions, None
        dense, vd = unpack_rows(rp), "dense"
    else:
        q = quantize_rows(rp, value_dtype)
        values, positions = jnp.asarray(q.values), q.row_positions
        scales = np.asarray(q.scales, np.float32)
        dense, vd = unpack_rows(dequantize_rows(q)), value_dtype
    for t in range(rp.values.shape[0]):
        w_t = np.asarray(_tile(values[t], jnp.asarray(positions[t]), m, reconstruct, vd))
        assert w_t.shape == (m, k)
        if scales is not None:
            w_t = w_t * scales[t][None, :]  # a row's scale: a lane of the tile
        cols = dense[:, t * m : (t + 1) * m]
        np.testing.assert_array_equal(w_t[: cols.shape[1]], cols.T)
        np.testing.assert_array_equal(w_t[cols.shape[1] :], 0.0)  # padded lanes


def test_interpret_mode_only_on_cpu(monkeypatch):
    import jax

    from repro.kernels.ops import interpret_mode

    assert interpret_mode() is True  # the test backend is the CPU
    assert interpret_mode(False) is False
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert interpret_mode() is False
    with pytest.raises(ValueError, match="CPU backend only"):
        interpret_mode(True)
