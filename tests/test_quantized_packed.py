"""Quantized packed decode (DESIGN.md §10): int8 / int4-nibble window values
with per-window fp32 scales and dequant fused into VMEM reconstruction.

The correctness contract, layer by layer:
* kernels      — quantized ``apply_row_packed``/``apply_fused_mlp`` match the
  jnp dequant oracle (same qdq grid, fp32 accumulation) and the dense matmul
  over the host-side quantize-dequantize matrix.
* serve        — ``packed_values="bf16"`` is byte-identical to the pre-§10
  dense-value path; ``packed_values="int8"`` greedy tokens are bit-exact vs
  a quantize-dequantize-then-dense oracle (``qdq_lm_params``), one-shot and
  through the Scheduler; byte ratios meet the §10 ceilings.
* validation   — ``validate_packed`` refuses quantized packs with missing /
  malformed / non-finite / non-positive scales.
* chaos        — value-corruption faults on quantized packs NaN the dequant
  scale (int8 bytes can't hold a NaN) and still reach the runtime guard.
* sharding     — window-sharded quantized packs match the single-device
  kernel across real multi-device meshes.
* N:M arm      — the S2TA-style structured pack rides the same kernel.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_smoke_config
from repro.core.packing import nm_mask, pack_rows, quantize_rows, unpack_rows
from repro.core.pruning import prune_tree
from repro.kernels.ops import (
    _KBLK_CACHE,
    apply_fused_mlp,
    apply_fused_mlp_ref,
    apply_fused_mlp_sharded,
    apply_row_packed,
    apply_row_packed_ref,
    apply_row_packed_sharded,
    autotune_row_packed,
    dequantize_linear_values,
    pack_linear_rows,
    pack_linear_rows_nm,
    pack_linear_rows_t,
)
from repro.models import build_model
from repro.serve import Engine, FaultConfig, Request, Scheduler, ServeConfig
from repro.serve.faults import corrupt_pack_values
from repro.serve.packed import (
    pack_lm_weights,
    packed_byte_ratios,
    qdq_lm_params,
    validate_packed,
)


def _sparse(rng, k, c, sparsity, dtype=np.float32):
    w = rng.normal(size=(k, c)) * (rng.random((k, c)) > sparsity)
    return w.astype(dtype)


def _qdq_dense(w, m, a, value_dtype):
    """Host-side quantize-dequantize of a dense matrix under pack geometry."""
    from repro.core.packing import dequantize_rows

    return unpack_rows(dequantize_rows(quantize_rows(pack_rows(w, m=m, a=a), value_dtype)))


# ---------------------------------------------------------------------------
# kernels: fused dequant vs oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dt", ["int8", "int4"])
def test_quantized_kernel_matches_dequant_oracle(dt):
    """The in-kernel nibble/scale dequant reproduces the jnp dequant oracle
    and the dense matmul over the host qdq matrix (fp32 accumulation both
    sides; tolerance is accumulation order only)."""
    rng = np.random.default_rng(0)
    k, c, b = 64, 256, 4
    w = _sparse(rng, k, c, 0.85)
    p = pack_linear_rows(w, a=8, value_dtype=dt)
    assert p.value_dtype == dt and p.scales is not None
    x = jnp.asarray(rng.normal(size=(b, k)), jnp.float32)
    got = np.asarray(apply_row_packed(x, p), np.float32)
    ref = np.asarray(apply_row_packed_ref(x, p), np.float32)
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)
    dense = np.asarray(x, np.float32) @ _qdq_dense(w, 128, 8, dt)
    np.testing.assert_allclose(got, dense, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dt", ["int8", "int4"])
def test_quantized_fused_mlp_matches_ref(dt):
    rng = np.random.default_rng(1)
    d, ff = 64, 256
    pg = pack_linear_rows(_sparse(rng, d, ff, 0.85), a=8, value_dtype=dt)
    pu = pack_linear_rows(_sparse(rng, d, ff, 0.85), a=8, value_dtype=dt)
    pd = pack_linear_rows_t(_sparse(rng, ff, d, 0.85), a=8, value_dtype=dt)
    x = jnp.asarray(rng.normal(size=(3, d)), jnp.float32)
    got = np.asarray(apply_fused_mlp(x, pg, pu, pd), np.float32)
    ref = np.asarray(apply_fused_mlp_ref(x, pg, pu, pd), np.float32)
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dt", ["int8", "int4"])
@pytest.mark.parametrize("k,c", [(48, 200), (100, 130), (64, 96)])
def test_quantized_kernel_nondivisible_shapes(dt, k, c):
    """Ragged dims: padded lanes / nibble-padded slots must be exact no-ops."""
    rng = np.random.default_rng(2)
    w = _sparse(rng, k, c, 0.9)
    p = pack_linear_rows(w, m=32, a=4, value_dtype=dt)
    x = jnp.asarray(rng.normal(size=(2, k)), jnp.float32)
    got = np.asarray(apply_row_packed(x, p, k_blk=32), np.float32)
    dense = np.asarray(x, np.float32) @ _qdq_dense(w, 32, 4, dt)
    np.testing.assert_allclose(got, dense, rtol=1e-4, atol=1e-4)


def test_quantized_all_zero_matrix_exact_zero():
    for dt in ("int8", "int4"):
        p = pack_linear_rows(np.zeros((32, 64), np.float32), m=32, a=4, value_dtype=dt)
        x = jnp.asarray(np.random.default_rng(3).normal(size=(2, 32)), jnp.float32)
        got = np.asarray(apply_row_packed(x, p), np.float32)
        np.testing.assert_array_equal(got, np.zeros_like(got))


def test_dequantize_linear_values_matches_host():
    """The jnp dequant twin (ref path) agrees with the numpy codec."""
    rng = np.random.default_rng(4)
    w = _sparse(rng, 32, 100, 0.8)
    for dt in ("int8", "int4"):
        p = pack_linear_rows(w, m=32, a=4, value_dtype=dt)
        from repro.core.packing import dequantize_rows

        host = dequantize_rows(quantize_rows(pack_rows(w, m=32, a=4), dt)).values
        np.testing.assert_array_equal(np.asarray(dequantize_linear_values(p)), host)


def test_tune_key_separates_value_dtypes():
    """int8 and int4 packs share the jnp int8 value dtype, so the autotune
    cache must key on the explicit value_dtype tag, not the array dtype."""
    rng = np.random.default_rng(5)
    w = _sparse(rng, 64, 128, 0.85)
    x = jnp.asarray(rng.normal(size=(4, 64)), jnp.float32)
    before = dict(_KBLK_CACHE)
    try:
        _KBLK_CACHE.clear()
        for dt in ("dense", "int8", "int4"):
            autotune_row_packed(x, pack_linear_rows(w, a=8, value_dtype=dt), iters=1)
        assert len(_KBLK_CACHE) == 3
    finally:
        _KBLK_CACHE.clear()
        _KBLK_CACHE.update(before)


# ---------------------------------------------------------------------------
# N:M structured comparison arm (S2TA DBB) through the same kernel
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dt", ["dense", "int8"])
def test_nm_pack_through_kernel(dt):
    rng = np.random.default_rng(6)
    k, c = 64, 160
    w = _sparse(rng, k, c, 0.0)  # dense input: N:M does all the pruning
    p = pack_linear_rows_nm(w, n=2, block=4, m=32, a=4, value_dtype=dt)
    masked = np.where(nm_mask(w, 2, 4), w, 0.0)
    x = jnp.asarray(rng.normal(size=(2, k)), jnp.float32)
    got = np.asarray(apply_row_packed(x, p), np.float32)
    if dt == "dense":
        dense = np.asarray(x, np.float32) @ masked
    else:
        dense = np.asarray(x, np.float32) @ _qdq_dense(masked, 32, 4, dt)
    np.testing.assert_allclose(got, dense, rtol=1e-4, atol=1e-4)
    # structural slot bound: n * ceil(m / block), rounded up to a
    assert p.slots <= -(-(2 * -(-32 // 4)) // 4) * 4


# ---------------------------------------------------------------------------
# sharded quantized parity (real multi-device meshes via conftest's 8 CPUs)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dt", ["int8", "int4"])
def test_quantized_sharded_matches_single(dt):
    from repro.launch.mesh import make_serve_mesh

    rng = np.random.default_rng(7)
    w = _sparse(rng, 48, 5 * 32 - 3, 0.85)  # 5 windows -> padded to 8
    p = pack_linear_rows(w, m=32, a=4, value_dtype=dt)
    x = jnp.asarray(rng.normal(size=(2, 48)), jnp.float32)
    ref = np.asarray(apply_row_packed(x, p), np.float32)
    mesh = make_serve_mesh("1,4")
    got = np.asarray(apply_row_packed_sharded(x, p, mesh), np.float32)
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dt", ["int8", "int4"])
def test_quantized_fused_sharded_matches_single(dt):
    from repro.launch.mesh import make_serve_mesh

    rng = np.random.default_rng(8)
    d, ff = 48, 4 * 32  # 4 ff windows over a 4-way model axis
    pg = pack_linear_rows(_sparse(rng, d, ff, 0.85), m=32, a=4, value_dtype=dt)
    pu = pack_linear_rows(_sparse(rng, d, ff, 0.85), m=32, a=4, value_dtype=dt)
    pd = pack_linear_rows_t(_sparse(rng, ff, d, 0.85), m=32, a=4, value_dtype=dt)
    x = jnp.asarray(rng.normal(size=(2, d)), jnp.float32)
    ref = np.asarray(apply_fused_mlp(x, pg, pu, pd), np.float32)
    mesh = make_serve_mesh("1,4")
    got = np.asarray(apply_fused_mlp_sharded(x, pg, pu, pd, mesh), np.float32)
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# serve: bf16 byte-identity, int8 oracle bit-parity, ratios, validation
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def vusa_pruned():
    cfg = get_smoke_config("vusa_edge")
    params = prune_tree(build_model(cfg).init(jax.random.key(0)), 0.85)
    return cfg, params


def test_serveconfig_packed_values_validation():
    assert ServeConfig().packed_values == "bf16"  # default: pre-§10 behaviour
    assert ServeConfig(packed_values="int8").packed_values == "int8"
    with pytest.raises(ValueError):
        ServeConfig(packed_values="fp8")


def test_bf16_pack_byte_identity(vusa_pruned):
    """``packed_values="bf16"`` must be the pre-§10 dense-value path exactly:
    same tokens as the dense engine, and the pack carries no quant metadata."""
    cfg, params = vusa_pruned
    prompts = np.ones((2, 8), np.int32)
    dense = Engine(cfg, params, ServeConfig(max_len=64))
    eng = Engine(
        cfg, params,
        ServeConfig(max_len=64, packed_weights="all", packed_values="bf16"),
    )
    for _, e in _flat(eng._packed):
        assert e.get("value_dtype", "dense") == "dense"
        assert "scales" not in e
    np.testing.assert_array_equal(
        eng.generate(prompts, max_new=8)["tokens"],
        dense.generate(prompts, max_new=8)["tokens"],
    )


def _flat(packed):
    from repro.serve.packed import _flat_entries

    return _flat_entries(packed).items()


def test_int8_tokens_match_qdq_dense_oracle(vusa_pruned):
    """The §10 acceptance bar: greedy tokens under int8 packs are bit-exact
    vs a dense engine running on quantize-dequantize'd weights."""
    cfg, params = vusa_pruned
    prompts = np.ones((2, 8), np.int32)
    eng = Engine(
        cfg, params,
        ServeConfig(max_len=64, packed_weights="all", packed_values="int8"),
    )
    oracle = Engine(cfg, qdq_lm_params(cfg, params, value_dtype="int8"),
                    ServeConfig(max_len=64))
    np.testing.assert_array_equal(
        eng.generate(prompts, max_new=8)["tokens"],
        oracle.generate(prompts, max_new=8)["tokens"],
    )


def test_int8_scheduler_tokens_match_qdq_dense_oracle(vusa_pruned):
    """Same bar through the Scheduler's vmapped slot axis (greedy)."""
    cfg, params = vusa_pruned
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, 100, n).astype(np.int32) for n in (4, 6, 5)]

    def run(engine):
        sched = Scheduler(engine, slots=2, segment=4)
        return sched.run([
            Request(prompt=prompts[i], max_new=8, seed=50 + i)
            for i in range(len(prompts))
        ])

    got = run(Engine(
        cfg, params,
        ServeConfig(max_len=64, packed_weights="all", packed_values="int8"),
    ))
    ref = run(Engine(cfg, qdq_lm_params(cfg, params, value_dtype="int8"),
                     ServeConfig(max_len=64)))
    assert sorted(got) == sorted(ref)
    for rid in ref:
        np.testing.assert_array_equal(got[rid].tokens, ref[rid].tokens,
                                      err_msg=f"rid {rid}")


def test_int4_engine_serves_and_validates(vusa_pruned):
    """int4 is gated on kernel closeness + ratios (token parity vs the qdq
    oracle is not promised: the oracle *prefills* on qdq weights while the
    packed engine prefills dense, so near-tie argmaxes may flip).  The engine
    must still validate, serve, and emit finite in-vocab tokens."""
    cfg, params = vusa_pruned
    eng = Engine(
        cfg, params,
        ServeConfig(max_len=64, packed_weights="all", packed_values="int4"),
    )
    validate_packed(eng._packed)
    toks = eng.generate(np.ones((2, 8), np.int32), max_new=8)["tokens"]
    assert toks.shape == (2, 8)
    assert (toks >= 0).all() and (toks < cfg.vocab).all()


@pytest.mark.parametrize("dt,ceiling", [("int8", 0.25), ("int4", 0.15)])
def test_quantized_byte_ratio_ceilings(vusa_pruned, dt, ceiling):
    """§10 HBM budget at 85% sparsity: int8 total <= 0.25x dense, int4 <=
    0.15x (measured ~0.162 / ~0.124 on the smoke model; bf16-pack ~0.38)."""
    cfg, params = vusa_pruned
    packed = pack_lm_weights(cfg, params, scope="all", value_dtype=dt)
    ratios = packed_byte_ratios(packed)
    assert ratios["total"] <= ceiling, ratios
    dense_ratios = packed_byte_ratios(pack_lm_weights(cfg, params, scope="all"))
    assert ratios["total"] < dense_ratios["total"]


def test_validate_packed_quantized_rejections(vusa_pruned):
    cfg, params = vusa_pruned
    base = pack_lm_weights(cfg, params, scope="all", value_dtype="int8")
    validate_packed(base)  # the clean pack must pass

    def mutate(fn, match):
        packed = {k: (dict(v) if isinstance(v, dict) else v) for k, v in base.items()}
        e = dict(packed["mlp"]["w_gate"])
        fn(e)
        packed["mlp"]["w_gate"] = e
        with pytest.raises(ValueError, match=match):
            validate_packed(packed)

    mutate(lambda e: e.pop("scales"), "missing its scales")
    mutate(lambda e: e.update(scales=e["scales"][..., :-1]), "scales shape")
    mutate(lambda e: e.update(scales=e["scales"].at[0, 0, 0].set(np.nan)),
           "non-finite dequant scale")
    mutate(lambda e: e.update(scales=e["scales"].at[0, 0, 0].set(0.0)),
           "non-positive dequant scale")
    mutate(lambda e: e.update(values=e["values"].astype(jnp.float32)),
           "values dtype must be int8")
    mutate(lambda e: e.update(values=e["values"][..., :-1]), "does not decode")


def test_fault_injection_nans_scale_for_quantized(vusa_pruned):
    """Post-load value corruption on a quantized pack lands on the dequant
    scale (int8 bytes can't encode NaN); values/positions stay untouched so
    the fault is runtime-guard territory, not validate_packed's."""
    cfg, params = vusa_pruned
    packed = pack_lm_weights(cfg, params, scope="all", value_dtype="int8")
    out = corrupt_pack_values(packed, FaultConfig(seed=3, pack_value_nans=4))
    nan_scales = 0
    for (_, e), (_, e0) in zip(_flat(out), _flat(packed)):
        nan_scales += int((~np.isfinite(np.asarray(e["scales"]))).sum())
        np.testing.assert_array_equal(np.asarray(e["values"]), np.asarray(e0["values"]))
        np.testing.assert_array_equal(
            np.asarray(e["positions"]), np.asarray(e0["positions"])
        )
    assert nan_scales >= 1  # seeded flips may collide, but at least one lands


def test_quantized_fault_reaches_runtime_guard(vusa_pruned):
    """End to end: a NaN'd scale propagates to the logits and the Scheduler's
    guard + dense fallback still delivers every request."""
    cfg, params = vusa_pruned
    sc = ServeConfig(
        max_len=64, packed_weights="all", packed_values="int8",
        faults=FaultConfig(seed=5, pack_value_nans=3),
    )
    sched = Scheduler(Engine(cfg, params, sc), slots=2, segment=4)
    done = sched.run([
        Request(prompt=np.arange(1, 7, dtype=np.int32), max_new=6, seed=i)
        for i in range(3)
    ])
    assert len(done) == 3
    for rid, c in done.items():
        assert c.status.value in ("OK", "FAILED_FALLBACK_OK"), (rid, c.status)
        assert len(c.tokens) == 6


@pytest.mark.parametrize("dt", ["dense", "int8", "int4"])
def test_pad_slots_is_exact(dt):
    """Layer stacking pads every pack to the widest slot count.  An int4
    pack keeps slots [0, S/2) in low nibbles and [S/2, S) in high ones, so
    its padding must append idle slots to both halves: the padded pack
    dequantizes to the same weights and computes the same product."""
    from repro.kernels.ops import pad_slots

    rng = np.random.default_rng(11)
    w = _sparse(rng, 32, 64, 0.7)
    p = pack_linear_rows(w, m=32, a=4, value_dtype=dt)
    wide = pad_slots(p, p.slots + 8)
    assert wide.slots == p.slots + 8
    deq, deq_wide = (np.asarray(dequantize_linear_values(q)) for q in (p, wide))
    pos, pos_wide = np.asarray(p.positions), np.asarray(wide.positions)

    def dense(v, q):  # (T, K, S) slots -> {(t, k, lane): value}
        return {(t, k, int(q[t, k, s])): v[t, k, s] for t, k, s in zip(*np.nonzero(q >= 0))}

    assert dense(deq, pos) == dense(deq_wide, pos_wide)
    x = jnp.asarray(rng.normal(size=(2, 32)), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(apply_row_packed(x, wide)), np.asarray(apply_row_packed(x, p))
    )
