"""Whole-model row-packed decode (DESIGN.md §7): fused packed-MLP megakernel
vs the jnp oracle across sparsities/dtypes/edge shapes, whole-model
``packed_weights`` serving bit-parity (one-shot and through the Scheduler's
vmapped slot axis), and the kernels/ops autotune-cache bugfixes."""

import dataclasses
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_smoke_config
from repro.core.packing import pack_rows, pack_rows_t, unpack_rows
from repro.core.pruning import prune_tree
from repro.kernels.ops import (
    _KBLK_CACHE,
    _largest_divisor_leq,
    apply_fused_mlp,
    apply_fused_mlp_ref,
    autotune_row_packed,
    choose_k_blk,
    pack_linear_rows,
    pack_linear_rows_t,
)
from repro.models import build_model
from repro.serve import Engine, Request, Scheduler, ServeConfig


def _sparse(rng, k, c, sparsity, dtype=np.float32):
    w = rng.normal(size=(k, c)) * (rng.random((k, c)) > sparsity)
    return w.astype(dtype)


def _mlp_trio(rng, d, ff, sp, a=8):
    wg = _sparse(rng, d, ff, sp)
    wu = _sparse(rng, d, ff, sp)
    wd = _sparse(rng, ff, d, sp)
    return wg, wu, wd, (
        pack_linear_rows(wg, a=a),
        pack_linear_rows(wu, a=a),
        pack_linear_rows_t(wd, a=a),
    )


def _dense_mlp(x, wg, wu, wd):
    xf = np.asarray(x, np.float32)
    return (jax.nn.silu(xf @ wg) * (xf @ wu)) @ wd


# ---------------------------------------------------------------------------
# fused megakernel vs oracle vs dense
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sp", [0.0, 0.85, 0.99])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_fused_mlp_vs_oracle_sparsity_dtype(sp, dtype):
    """Kernel == jnp oracle == dense SwiGLU at every sparsity, fp32 + bf16
    values (fp32 accumulation either way)."""
    rng = np.random.default_rng(0)
    d, ff, b = 64, 256, 4
    wg, wu, wd, _ = _mlp_trio(rng, d, ff, sp)
    # pack the dtype-rounded weights so kernel and dense reference agree
    wgq, wuq, wdq = (np.asarray(jnp.asarray(w, dtype), np.float32) for w in (wg, wu, wd))
    pg = pack_linear_rows(np.asarray(jnp.asarray(wgq, dtype)), a=8)
    pu = pack_linear_rows(np.asarray(jnp.asarray(wuq, dtype)), a=8)
    pd = pack_linear_rows_t(np.asarray(jnp.asarray(wdq, dtype)), a=8)
    x = jnp.asarray(rng.normal(size=(b, d)), jnp.float32)
    got = np.asarray(apply_fused_mlp(x, pg, pu, pd), np.float32)
    ref = np.asarray(apply_fused_mlp_ref(x, pg, pu, pd), np.float32)
    tol = 1e-4 if dtype == np.float32 else 5e-2
    np.testing.assert_allclose(got, ref, rtol=tol, atol=tol)
    dense = _dense_mlp(x, wgq, wuq, wdq)
    np.testing.assert_allclose(got, dense, rtol=max(tol, 1e-3), atol=max(tol, 1e-3))


@pytest.mark.parametrize("reconstruct", ["onehot", "loop"])
def test_fused_mlp_reconstruct_modes_agree(reconstruct):
    rng = np.random.default_rng(1)
    d, ff = 48, 200  # non-divisible ff: windows padded to 256
    wg, wu, wd, (pg, pu, pd) = _mlp_trio(rng, d, ff, 0.85)
    x = jnp.asarray(rng.normal(size=(3, d)), jnp.float32)
    got = np.asarray(apply_fused_mlp(x, pg, pu, pd, reconstruct=reconstruct), np.float32)
    np.testing.assert_allclose(got, _dense_mlp(x, wg, wu, wd), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize(
    "d,ff",
    [
        (48, 200),  # ff % 128 != 0: zero-padded lanes must be exact no-ops
        (100, 130),  # both dims ragged
        (64, 96),  # ff < window width
    ],
)
def test_fused_mlp_nondivisible_shapes(d, ff):
    rng = np.random.default_rng(2)
    wg, wu, wd, (pg, pu, pd) = _mlp_trio(rng, d, ff, 0.9)
    x = jnp.asarray(rng.normal(size=(2, d)), jnp.float32)
    got = np.asarray(apply_fused_mlp(x, pg, pu, pd, k_blk=32), np.float32)
    np.testing.assert_allclose(got, _dense_mlp(x, wg, wu, wd), rtol=1e-4, atol=1e-4)


def test_fused_mlp_all_zero_rows():
    """Rows with no non-zeros (empty jobs, position -1 throughout) and even a
    fully-zero gate matrix contribute exact zeros."""
    rng = np.random.default_rng(3)
    d, ff = 64, 128
    wg = _sparse(rng, d, ff, 0.85)
    wg[10:30] = 0.0  # dead reduction rows
    wu = _sparse(rng, d, ff, 0.85)
    wu[:, 40:80] = 0.0  # dead ff lanes
    wd = _sparse(rng, ff, d, 0.85)
    wd[5:60] = 0.0  # dead ff rows of the down projection
    pg, pu, pd = (
        pack_linear_rows(wg, a=8),
        pack_linear_rows(wu, a=8),
        pack_linear_rows_t(wd, a=8),
    )
    x = jnp.asarray(rng.normal(size=(2, d)), jnp.float32)
    got = np.asarray(apply_fused_mlp(x, pg, pu, pd), np.float32)
    np.testing.assert_allclose(got, _dense_mlp(x, wg, wu, wd), rtol=1e-4, atol=1e-4)
    # fully-zero gate: the whole MLP output is exactly zero
    pz = pack_linear_rows(np.zeros_like(wg), a=8)
    got = np.asarray(apply_fused_mlp(x, pz, pu, pd), np.float32)
    np.testing.assert_array_equal(got, np.zeros_like(got))


def test_pack_rows_t_roundtrip():
    """pack_rows_t windows the leading (reduction) dim: unpack gives w.T."""
    rng = np.random.default_rng(4)
    w = _sparse(rng, 130, 64, 0.8)
    np.testing.assert_array_equal(unpack_rows(pack_rows_t(w, a=8)), w.T)
    np.testing.assert_array_equal(unpack_rows(pack_rows(w, a=8)), w)


# ---------------------------------------------------------------------------
# whole-model packed serving: one-shot + Scheduler bit-parity vs dense
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def vusa_pruned():
    cfg = get_smoke_config("vusa_edge")
    params = prune_tree(build_model(cfg).init(jax.random.key(0)), 0.85)
    return cfg, params


@pytest.mark.parametrize("temperature", [0.0, 1.0])
def test_packed_weights_engine_matches_dense(vusa_pruned, temperature):
    """Whole-model packing (qkv/o + fused MLP + untied head): same tokens as
    the dense engine, greedy and sampled."""
    cfg, params = vusa_pruned
    prompts = np.ones((2, 8), np.int32)
    outs = {}
    for packed in (False, "all"):
        sc = ServeConfig(max_len=64, temperature=temperature, packed_weights=packed)
        outs[packed] = Engine(cfg, params, sc).generate(prompts, max_new=8)["tokens"]
    np.testing.assert_array_equal(outs[False], outs["all"])


@pytest.mark.parametrize("temperature", [0.0, 1.0])
def test_packed_weights_scheduler_bit_parity_vs_dense(vusa_pruned, temperature):
    """End to end through the Scheduler (vmapped slot axis): the
    ``packed_weights`` pool must emit the dense pool's exact token streams
    per request/seed, greedy + sampled."""
    cfg, params = vusa_pruned
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, 100, n).astype(np.int32) for n in (4, 5, 6, 5)]

    def reqs():
        return [
            Request(prompt=prompts[i], max_new=8, seed=30 + i) for i in range(len(prompts))
        ]

    done = {}
    for packed in (False, "all"):
        sc = ServeConfig(max_len=64, temperature=temperature, packed_weights=packed)
        sched = Scheduler(Engine(cfg, params, sc), slots=2, segment=4)
        done[packed] = sched.run(reqs())
    assert sorted(done[False]) == sorted(done["all"])
    for rid in done[False]:
        np.testing.assert_array_equal(
            done["all"][rid].tokens, done[False][rid].tokens, err_msg=f"rid {rid}"
        )


def test_packed_weights_fused_matches_split3(vusa_pruned):
    """Megakernel and 3-dispatch MLP paths emit identical tokens (the perf
    A/B in bench_packed_decode never trades correctness)."""
    cfg, params = vusa_pruned
    prompts = np.ones((2, 6), np.int32)
    outs = {}
    for fused in (True, False):
        sc = ServeConfig(max_len=64, packed_weights="mlp", fused_mlp=fused)
        outs[fused] = Engine(cfg, params, sc).generate(prompts, max_new=8)["tokens"]
    np.testing.assert_array_equal(outs[True], outs[False])


def test_serveconfig_packed_aliases():
    """packed_mlp=True -> scope "mlp"; True -> "all"; junk rejected."""
    assert ServeConfig(packed_mlp=True).packed_weights == "mlp"
    assert ServeConfig(packed_weights=True).packed_weights == "all"
    assert ServeConfig().packed_weights is False
    # an explicit packed_weights wins over the legacy alias
    assert ServeConfig(packed_mlp=True, packed_weights="all").packed_weights == "all"
    with pytest.raises(ValueError):
        ServeConfig(packed_weights="everything")


def test_packed_head_only_when_untied(vusa_pruned):
    from repro.serve.packed import pack_lm_weights

    cfg, params = vusa_pruned
    packed = pack_lm_weights(cfg, params, scope="all")
    assert (packed["head"] is not None) == (not cfg.tie_embeddings)
    assert set(packed["attn"]) == {"wq", "wk", "wv", "wo"}
    tied = dataclasses.replace(cfg, tie_embeddings=True)
    params_tied = {k: v for k, v in params.items() if k != "lm_head"}
    assert pack_lm_weights(tied, params_tied, scope="all")["head"] is None


# ---------------------------------------------------------------------------
# kernels/ops satellite bugfixes
# ---------------------------------------------------------------------------


def test_largest_divisor_snap():
    """REPRO_VUSA_KBLK snaps to the largest divisor <= blk in O(sqrt k) —
    the seed walked down one step at a time (O(k) for prime-ish K)."""
    assert _largest_divisor_leq(1024, 300) == 256
    assert _largest_divisor_leq(360, 100) == 90
    assert _largest_divisor_leq(7919, 100) == 1  # prime K
    assert _largest_divisor_leq(7919, 7919) == 7919
    assert _largest_divisor_leq(100, 1) == 1
    os.environ["REPRO_VUSA_KBLK"] = "300"
    try:
        assert choose_k_blk(1024, 16, 128) == 256
        assert choose_k_blk(7919, 16, 128) == 1
    finally:
        del os.environ["REPRO_VUSA_KBLK"]


def test_tune_key_separates_reconstruct_modes():
    """A k_blk autotuned for "onehot" must not drive "loop" calls: the cache
    key includes reconstruct (the seed omitted it)."""
    rng = np.random.default_rng(5)
    p = pack_linear_rows(_sparse(rng, 64, 128, 0.85), a=8)
    x = jnp.asarray(rng.normal(size=(4, 64)), jnp.float32)
    before = dict(_KBLK_CACHE)
    try:
        _KBLK_CACHE.clear()
        autotune_row_packed(x, p, iters=1)
        autotune_row_packed(x, p, iters=1, reconstruct="loop")
        autotune_row_packed(x, p, iters=1)  # served from the cache
        assert len(_KBLK_CACHE) == 2  # one entry per mode
        assert {k[-2] for k in _KBLK_CACHE} == {"onehot", "loop"}
    finally:
        _KBLK_CACHE.clear()
        _KBLK_CACHE.update(before)


def test_tune_key_separates_kblk_env():
    """A k_blk autotuned without REPRO_VUSA_KBLK must not be served after the
    override changes mid-process (and vice versa): the env value is part of
    the cache key — the seed's key omitted it, so a pre-override entry
    silently shadowed an explicit operator override."""
    rng = np.random.default_rng(6)
    p = pack_linear_rows(_sparse(rng, 64, 128, 0.85), a=8)
    x = jnp.asarray(rng.normal(size=(4, 64)), jnp.float32)
    before = dict(_KBLK_CACHE)
    assert "REPRO_VUSA_KBLK" not in os.environ
    try:
        _KBLK_CACHE.clear()
        autotune_row_packed(x, p, iters=1)
        assert len(_KBLK_CACHE) == 1
        os.environ["REPRO_VUSA_KBLK"] = "16"
        autotune_row_packed(x, p, iters=1)
        assert len(_KBLK_CACHE) == 2, (
            "the env override must key its own autotune entry, not reuse "
            "the pre-override one"
        )
        assert {k[-1] for k in _KBLK_CACHE} == {"", "16"}
    finally:
        del os.environ["REPRO_VUSA_KBLK"]
        _KBLK_CACHE.clear()
        _KBLK_CACHE.update(before)
