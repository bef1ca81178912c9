"""Tensor-parallel sharded serving (DESIGN.md §8): differential tests of the
mesh-aware decode/serve stack against the single-device path.

The whole suite runs on a forced 8-device CPU backend (tests/conftest.py), so
every mesh here — 1x1, 2x1 (DP), 1x2 (TP), 2x4 (DP x TP) — is a real
multi-device mesh exercising real collectives.  The correctness bar is the
one the serve stack has pinned since §5: sharding changes *where* work runs,
never *what* it computes — per-request tokens bit-identical (fp32) to the
single-device engine, logits allclose at bf16.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import requires_devices

from repro.configs import get_smoke_config
from repro.core.pruning import prune_tree
from repro.launch.mesh import make_serve_mesh
from repro.models import build_model
from repro.serve import Engine, Request, Scheduler, ServeConfig

MESHES = ["1,1", "2,1", "1,2", "2,4"]
# vusa_m=32 so the smoke shapes span several windows per matmul (d_ff=128 ->
# 4 ff windows, vocab head -> 16) and the 1x2 / 2x4 meshes genuinely split
# windows across devices instead of degenerating to one window per mesh
PACK = dict(vusa_m=32, vusa_a=8)


def _sc(**kw):
    return ServeConfig(max_len=48, **PACK, **kw)


@pytest.fixture(scope="module")
def cfg():
    return get_smoke_config("vusa_edge")


@pytest.fixture(scope="module")
def params(cfg):
    return prune_tree(build_model(cfg).init(jax.random.key(0)), 0.85)


@pytest.fixture(scope="module")
def prompts():
    return np.arange(12, dtype=np.int32).reshape(2, 6) % 500


@pytest.fixture(scope="module")
def reference(cfg, params, prompts):
    """Single-device (mesh=None) token streams per (packed, temperature)."""
    out = {}

    def get(packed, temp):
        if (packed, temp) not in out:
            eng = Engine(cfg, params, _sc(packed_weights=packed, temperature=temp))
            out[(packed, temp)] = eng.generate(prompts, max_new=10)["tokens"]
        return out[(packed, temp)]

    return get


# ---------------------------------------------------------------------------
# Engine: sharded == single-device, bit-identical tokens
# ---------------------------------------------------------------------------


@requires_devices(8)
@pytest.mark.parametrize("spec", MESHES)
@pytest.mark.parametrize("packed", [False, "mlp", "all"])
def test_engine_sharded_greedy(cfg, params, prompts, reference, spec, packed):
    mesh = make_serve_mesh(spec)
    eng = Engine(cfg, params, _sc(packed_weights=packed), mesh=mesh)
    toks = eng.generate(prompts, max_new=10)["tokens"]
    np.testing.assert_array_equal(toks, reference(packed, 0.0))


@requires_devices(8)
@pytest.mark.parametrize("spec,packed", [("1,2", False), ("1,2", "all"), ("2,4", "all")])
def test_engine_sharded_sampled(cfg, params, prompts, reference, spec, packed):
    """Temperature sampling: the sharded engine splits the same key stream,
    so even sampled streams are bit-identical at fp32."""
    mesh = make_serve_mesh(spec)
    eng = Engine(cfg, params, _sc(packed_weights=packed, temperature=0.8), mesh=mesh)
    toks = eng.generate(prompts, max_new=10)["tokens"]
    np.testing.assert_array_equal(toks, reference(packed, 0.8))


@requires_devices(1)
def test_engine_mesh1_degenerate(cfg, params, prompts, reference):
    """A 1x1 mesh must be the single-device path: same tokens, and the packs
    gain no padding windows (shards=1 pads nothing, shard_map is skipped)."""
    eng0 = Engine(cfg, params, _sc(packed_weights="all"))
    eng1 = Engine(cfg, params, _sc(packed_weights="all"), mesh=make_serve_mesh("1,1"))
    for a, b in zip(
        jax.tree_util.tree_leaves(eng0._packed), jax.tree_util.tree_leaves(eng1._packed)
    ):
        assert np.asarray(a).shape == np.asarray(b).shape
    toks = eng1.generate(prompts, max_new=10)["tokens"]
    np.testing.assert_array_equal(toks, reference("all", 0.0))


@requires_devices(8)
@pytest.mark.parametrize("packed", [False, "all"])
def test_bf16_logits_allclose(cfg, params, prompts, packed):
    """bf16 decode: psum/all-gather reassociate the low-precision sums, so
    the bar is allclose logits (and it holds one full decode step)."""
    bcfg = dataclasses.replace(cfg, dtype="bfloat16")
    mesh = make_serve_mesh("2,4")
    engines = [
        Engine(bcfg, params, _sc(packed_weights=packed)),
        Engine(bcfg, params, _sc(packed_weights=packed), mesh=mesh),
    ]
    logits = []
    for eng in engines:
        nxt, cache, _ = eng.prime(prompts, jax.random.key(0))
        if eng._packed is not None:
            from repro.serve.packed import lm_decode_step_packed

            lg, _ = lm_decode_step_packed(
                eng.params, eng._packed, nxt, cache, bcfg, mesh=eng.mesh
            )
        else:
            lg, _ = eng.model.decode_step(eng.params, nxt, cache)
        logits.append(np.asarray(lg, np.float32))
    np.testing.assert_allclose(logits[0], logits[1], rtol=5e-2, atol=5e-2)


# ---------------------------------------------------------------------------
# Scheduler: sharded slot pool == single-device slot pool
# ---------------------------------------------------------------------------


def _requests(cfg):
    rng = np.random.default_rng(7)
    return [
        Request(
            prompt=rng.integers(0, cfg.vocab, 3 + i % 4).astype(np.int32),
            max_new=5 + i % 4,
            seed=i,
            eos_id=3 if i % 3 == 0 else None,
        )
        for i in range(6)
    ]


@pytest.mark.slow
@requires_devices(8)
@pytest.mark.parametrize("spec", ["2,1", "1,2", "2,4"])
@pytest.mark.parametrize("temp", [0.0, 0.8])
def test_scheduler_sharded(cfg, params, spec, temp):
    """Continuous batching over a sharded slot pool: every completion must be
    bit-identical to the single-device scheduler — ragged admission, EOS
    retirement and all (packed 'all', greedy and sampled)."""
    sc = _sc(packed_weights="all", temperature=temp)
    base = Scheduler(Engine(cfg, params, dataclasses.replace(sc)), slots=4, segment=3)
    want = base.run(_requests(cfg))
    mesh = make_serve_mesh(spec)
    sched = Scheduler(
        Engine(cfg, params, dataclasses.replace(sc), mesh=mesh), slots=4, segment=3
    )
    got = sched.run(_requests(cfg))
    assert set(got) == set(want)
    for rid in want:
        np.testing.assert_array_equal(got[rid].tokens, want[rid].tokens)


@requires_devices(8)
def test_scheduler_sharded_dense(cfg, params):
    """Dense (unpacked) family through the sharded slot pool."""
    sc = _sc()
    base = Scheduler(Engine(cfg, params, dataclasses.replace(sc)), slots=4, segment=3)
    want = base.run(_requests(cfg))
    sched = Scheduler(
        Engine(cfg, params, dataclasses.replace(sc), mesh=make_serve_mesh("2,4")),
        slots=4, segment=3,
    )
    got = sched.run(_requests(cfg))
    for rid in want:
        np.testing.assert_array_equal(got[rid].tokens, want[rid].tokens)


# ---------------------------------------------------------------------------
# Kernel-level: sharded appliers vs plain appliers vs dense oracle
# ---------------------------------------------------------------------------


def _sparse(rng, k, c, sp):
    return (rng.normal(size=(k, c)) * (rng.random((k, c)) > sp)).astype(np.float32)


@requires_devices(8)
@pytest.mark.parametrize("tp", [1, 2, 4])
@pytest.mark.parametrize("t_windows", [4, 5])  # 5 % 4 != 0 -> pad-window path
def test_apply_row_packed_sharded(tp, t_windows):
    from repro.kernels.ops import apply_row_packed, apply_row_packed_sharded, pack_linear_rows

    rng = np.random.default_rng(0)
    m = 32
    w = _sparse(rng, 40, t_windows * m - 7, 0.8)  # c % m != 0 too
    x = jnp.asarray(rng.normal(size=(3, 40)), jnp.float32)
    p = pack_linear_rows(w, m=m, a=8)
    mesh = make_serve_mesh(f"{8 // tp if tp < 8 else 1},{tp}")
    got = np.asarray(apply_row_packed_sharded(x, p, mesh))
    np.testing.assert_allclose(got, np.asarray(x) @ w, rtol=1e-4, atol=1e-4)
    if tp == 1:  # degenerate: exactly the plain applier
        np.testing.assert_array_equal(got, np.asarray(apply_row_packed(x, p)))


@requires_devices(8)
@pytest.mark.parametrize("tp", [2, 4])
def test_apply_fused_mlp_sharded(tp):
    import jax.nn

    from repro.kernels.ops import (
        apply_fused_mlp,
        apply_fused_mlp_sharded,
        pack_linear_rows,
        pack_linear_rows_t,
    )

    rng = np.random.default_rng(1)
    k, ff, m = 48, 80, 32  # ff % m != 0 and windows % tp != 0
    wg, wu = _sparse(rng, k, ff, 0.8), _sparse(rng, k, ff, 0.8)
    wd = _sparse(rng, ff, k, 0.8)
    x = jnp.asarray(rng.normal(size=(2, k)), jnp.float32)
    gate, up = pack_linear_rows(wg, m=m, a=8), pack_linear_rows(wu, m=m, a=8)
    down_t = pack_linear_rows_t(wd, m=m, a=8)
    mesh = make_serve_mesh(f"1,{tp}")
    got = np.asarray(apply_fused_mlp_sharded(x, gate, up, down_t, mesh))
    want = np.asarray(apply_fused_mlp(x, gate, up, down_t))
    dense = (np.asarray(jax.nn.silu(x @ wg)) * np.asarray(x @ wu)) @ wd
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(got, dense, rtol=1e-3, atol=1e-3)


@requires_devices(8)
def test_sharded_applier_replicated_fallback():
    """A mesh whose model axis the window count cannot use still computes the
    right answer (shard_linear_windows pads on the fly) — and a mesh with no
    model axis at all degenerates to the plain path."""
    from repro.kernels.ops import apply_row_packed_sharded, pack_linear_rows
    from repro.launch.mesh import make_mesh

    rng = np.random.default_rng(2)
    w = _sparse(rng, 16, 33, 0.5)  # 2 windows of m=32 after padding
    x = jnp.asarray(rng.normal(size=(2, 16)), jnp.float32)
    p = pack_linear_rows(w, m=32, a=8)
    data_only = make_mesh((2,), ("data",), jax.devices()[:2])
    got = np.asarray(apply_row_packed_sharded(x, p, data_only))
    np.testing.assert_allclose(got, np.asarray(x) @ w, rtol=1e-4, atol=1e-4)
    got3 = np.asarray(apply_row_packed_sharded(x, p, make_serve_mesh("1,4")))
    np.testing.assert_allclose(got3, np.asarray(x) @ w, rtol=1e-4, atol=1e-4)
