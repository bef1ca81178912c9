"""The served kernels compiled for a TPU v5e that is described, not attached.

The TPU compiler (Mosaic) refuses what interpret mode accepts: unaligned
blocks, dynamic slices, shape casts, and scratch beyond VMEM.  These tests
compile ``vusa_packed_matmul`` and ``vusa_fused_mlp_matmul`` at
``vusa_edge``'s published widths (d_model 768, d_ff 3072, 85 % sparsity ->
48 slots per row, m=128) for every value format, at the ``k_blk`` that
``choose_k_blk`` picks on the chip, alone and under the Scheduler's vmapped
slot axis.  Nothing runs: a pass means the chip's compiler accepts the
kernels and that they lower to a Pallas TPU call.

This is the only test file that describes the chip.  The topology is
described inside a module fixture (never at import or collection time), so
only the worker that runs this file loads the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.vusa_packed import vusa_fused_mlp_matmul, vusa_packed_matmul

D_MODEL, D_FF, SLOTS, M, BATCH = 768, 3072, 48, 128, 8


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without the chip; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def chip_k_blk():
    """``choose_k_blk``'s choice on the chip for the d_model reduction."""
    mp = pytest.MonkeyPatch()
    mp.setattr(ops, "on_tpu", lambda: True)
    mp.delenv("REPRO_VUSA_KBLK", raising=False)
    try:
        return ops.choose_k_blk(D_MODEL, SLOTS, M)
    finally:
        mp.undo()


def _pack_shapes(sharding, t: int, rows: int, value_dtype: str):
    """ShapeDtypeStructs of one (T, rows, S) pack: values, positions, scales."""
    nib = 2 if value_dtype == "int4" else 1
    vdt = jnp.bfloat16 if value_dtype == "dense" else jnp.int8
    values = jax.ShapeDtypeStruct((t, rows, SLOTS // nib), vdt, sharding=sharding)
    positions = jax.ShapeDtypeStruct((t, rows, SLOTS), jnp.int8, sharding=sharding)
    scales = (
        None if value_dtype == "dense"
        else jax.ShapeDtypeStruct((t, rows), jnp.float32, sharding=sharding)
    )
    return values, positions, scales


@pytest.mark.parametrize("slot_axis", [False, True], ids=["engine", "scheduler"])
@pytest.mark.parametrize("value_dtype", ["dense", "int8", "int4"])
@pytest.mark.parametrize("kernel", ["packed", "fused"])
def test_kernel_compiles_for_v5e(
    one_chip, no_persistent_cache, chip_k_blk, kernel, value_dtype, slot_axis
):
    t = D_FF // M
    v, p, s = _pack_shapes(one_chip, t, D_MODEL, value_dtype)
    kw = dict(m=M, k_blk=chip_k_blk, interpret=False, value_dtype=value_dtype)
    if kernel == "packed":
        def call(x, v, p, s):
            return vusa_packed_matmul(x, v, p, s, **kw)
    else:
        def call(x, v, p, s):
            return vusa_fused_mlp_matmul(x, v, p, v, p, v, p, s, s, s, **kw)
    # the Scheduler decodes every slot at batch 1 under jax.vmap, which turns
    # the slot axis into an extra grid axis of the same kernel
    x_shape = (BATCH, 1, D_MODEL) if slot_axis else (BATCH, D_MODEL)
    x = jax.ShapeDtypeStruct(x_shape, jnp.bfloat16, sharding=one_chip)
    fn = jax.vmap(call, in_axes=(0, None, None, None)) if slot_axis else call
    compiled = jax.jit(fn).lower(x, v, p, s).compile()
    assert "tpu_custom_call" in compiled.as_text()
