"""Distribution-layer tests.

The main test process itself runs on a forced 8-device CPU backend
(tests/conftest.py), so rule/fallback tests use real 2x4 meshes in-process —
prefer that for new tests.  The subprocess harness (`_run`) survives for the
*training* integration tests, which want a 16-device mesh and an isolated
backend (and predate the conftest hook)."""

import subprocess
import sys
import textwrap
from pathlib import Path

import jax

REPO_ROOT = Path(__file__).resolve().parent.parent

from repro.dist.sharding import param_sharding  # noqa: E402
from repro.launch.mesh import make_local_mesh  # noqa: E402
from repro.models.common import ParamSpec  # noqa: E402


def _run(code: str) -> str:
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        timeout=600,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             # pin the backend: without it, plugin discovery in the bare
             # subprocess env can stall for minutes probing accelerators
             "JAX_PLATFORMS": "cpu",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=16"},
        cwd=str(REPO_ROOT),
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


import pytest  # noqa: E402


@pytest.mark.slow
def test_param_rules_multi_device():
    code = textwrap.dedent("""
        import jax
        from repro.configs import get_config
        from repro.dist.sharding import params_shardings
        from repro.launch.mesh import make_local_mesh
        from repro.models import build_model

        mesh = make_local_mesh(4, 4)
        for arch in ("qwen3_8b", "olmoe_1b_7b", "mamba2_2_7b"):
            cfg = get_config(arch)
            model = build_model(cfg)
            sh = params_shardings(model.specs(), mesh)
            leaves = jax.tree_util.tree_leaves(sh)
            def uses(spec, axis):
                return any(
                    e == axis or (isinstance(e, tuple) and axis in e)
                    for e in spec if e is not None
                )
            n_model = sum(1 for s in leaves if uses(s.spec, "model"))
            n_data = sum(1 for s in leaves if uses(s.spec, "data"))
            assert n_model > 0, arch  # TP actually engaged
            assert n_data > 0, arch   # FSDP actually engaged
            print(arch, "ok", n_model, "TP +", n_data, "FSDP of", len(leaves))
    """)
    out = _run(code)
    assert out.count("ok") == 3


@pytest.mark.slow
def test_train_step_runs_sharded():
    """A real sharded train step on a 4x4 host-device mesh (tiny model)."""
    code = textwrap.dedent("""
        import jax, numpy as np
        from repro.configs import get_smoke_config
        from repro.launch.mesh import make_local_mesh
        from repro.train import TrainConfig, Trainer, TrainHParams
        mesh = make_local_mesh(4, 4)
        cfg = get_smoke_config("llama3_2_1b")
        tc = TrainConfig(steps=3, global_batch=8, seq_len=32, prune_begin=100,
                         hp=TrainHParams(lr=1e-3, total_steps=3), log_every=100)
        out = Trainer(cfg, tc, mesh=mesh).train()
        assert np.isfinite(out["final_loss"])
        print("sharded loss", out["final_loss"])
    """)
    out = _run(code)
    assert "sharded loss" in out


@pytest.mark.slow
def test_sharded_matches_single_device():
    """Same seed, same data: 16-device mesh loss == single-device loss."""
    code = textwrap.dedent("""
        import jax, numpy as np
        from repro.configs import get_smoke_config
        from repro.launch.mesh import make_local_mesh
        from repro.train import TrainConfig, Trainer, TrainHParams
        tc = TrainConfig(steps=2, global_batch=8, seq_len=16, prune_begin=100,
                         hp=TrainHParams(lr=1e-3, total_steps=2), log_every=100)
        cfg = get_smoke_config("qwen2_0_5b")
        mesh = make_local_mesh(4, 4)
        l_multi = Trainer(cfg, tc, mesh=mesh).train()["final_loss"]
        mesh1 = make_local_mesh(1, 1)
        l_single = Trainer(cfg, tc, mesh=mesh1).train()["final_loss"]
        print("multi", l_multi, "single", l_single)
        assert abs(l_multi - l_single) < 2e-3, (l_multi, l_single)
    """)
    _run(code)


def test_param_sharding_divisibility_fallback():
    """Non-divisible dims must fall back to replication, never error."""
    mesh = make_local_mesh(1, 1)
    spec = ParamSpec((7, 13), ("embed", "ff"))  # nothing divides
    s = param_sharding(spec, mesh)
    assert s.spec == jax.sharding.PartitionSpec(None, None)


def test_batch_sharding_non_divisible_batch():
    from repro.dist.sharding import batch_sharding

    mesh = make_local_mesh(1, 1)
    s = batch_sharding(mesh, batch_size=1, ndim=2)  # long_500k case
    assert s.spec[0] in (None, "data")  # batch=1 on 1-dev mesh: either is valid


# ---------------------------------------------------------------------------
# odd-dim fallbacks on a real multi-device mesh (tests/conftest.py forces 8
# host devices, so these run against actual 2x4 shardings, not 1x1 stubs)
# ---------------------------------------------------------------------------


def _mesh24():
    import pytest

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 forced host devices")
    return make_local_mesh(2, 4)


def test_param_sharding_odd_dims_replicate_on_real_mesh():
    """e.g. a vocab the model axis does not divide: replicate, never error —
    and dims that do divide still shard (partial fallback, per-dim)."""
    mesh = _mesh24()
    P = jax.sharding.PartitionSpec
    # vocab 151 not divisible by model=4 -> replicated; embed 6 not divisible
    # by data=2? 6 % 2 == 0 -> sharded
    s = param_sharding(ParamSpec((151, 6), ("vocab", "embed")), mesh)
    assert s.spec == P(None, "data")
    # both odd -> fully replicated
    s = param_sharding(ParamSpec((151, 7), ("vocab", "embed")), mesh)
    assert s.spec == P(None, None)
    # zero-size and size-1 dims never error
    s = param_sharding(ParamSpec((1, 3), ("vocab", "embed")), mesh)
    assert s.spec == P(None, None)


def test_window_sharding_fallback():
    """Packed-weight window axes (values AND the int8 positions metadata):
    divisible counts shard over `model`, odd counts replicate, a mesh without
    a model axis replicates — never an error."""
    from repro.dist.sharding import window_sharding
    from repro.launch.mesh import make_mesh

    mesh = _mesh24()
    P = jax.sharding.PartitionSpec
    assert window_sharding(mesh, 8, 3, axis=0).spec == P("model", None, None)
    assert window_sharding(mesh, 8, 4, axis=1).spec == P(None, "model", None, None)
    assert window_sharding(mesh, 7, 3, axis=0).spec == P(None, None, None)  # odd
    data_only = make_mesh((2,), ("data",), jax.devices()[:2])
    assert window_sharding(data_only, 8, 3).spec == P(None, None, None)


def test_shard_packed_odd_windows_replicate():
    """A pack whose window count the model axis does not divide (packed
    without shards=tp) must land fully replicated — values and positions
    alike — and still serve correct results (the applier re-pads on the
    fly, tests/test_serve_sharded.py)."""
    import numpy as np

    from repro.kernels.ops import pack_linear_rows
    from repro.serve.packed import _pack_one, shard_packed

    mesh = _mesh24()
    rng = np.random.default_rng(0)
    w = rng.normal(size=(16, 3 * 32)).astype(np.float32)  # 3 windows, tp=4
    entry = _pack_one(pack_linear_rows(w, m=32, a=8))
    packed = {"mlp": {"w_gate": {**entry, "values": entry["values"][None],
                                 "positions": entry["positions"][None]}},
              "attn": None, "head": entry, "scope": "all", "fused_mlp": False}
    out = shard_packed(packed, mesh)
    for leaf in ("values", "positions"):
        assert out["head"][leaf].sharding.spec == jax.sharding.PartitionSpec(None, None, None)
        spec = out["mlp"]["w_gate"][leaf].sharding.spec
        assert all(p is None for p in spec)


def test_serve_shardings_structural_axes():
    """With a batch_axes tree, serve_shardings shards exactly the located
    axis — immune to the 'another leading dim equals the batch size' guess
    ambiguity (e.g. n_layers == batch)."""
    from repro.dist.sharding import serve_shardings

    mesh = _mesh24()
    P = jax.sharding.PartitionSpec
    cache = {
        "k": jax.ShapeDtypeStruct((2, 2, 16, 4, 8), jax.numpy.float32),
        "pos": jax.ShapeDtypeStruct((), jax.numpy.int32),
    }
    # guess path would shard axis 0 (n_layers == batch == 2); structural
    # axes pin axis 1
    sh = serve_shardings(cache, mesh, 2, batch_axes={"k": 1, "pos": -1})
    assert sh["k"].spec == P(None, "data", None, None, None)
    assert sh["pos"].spec == P()
    # odd batch falls back to replication, never errors
    sh = serve_shardings(cache, mesh, 3, batch_axes={"k": 1, "pos": -1})
    assert all(p is None for p in sh["k"].spec)
