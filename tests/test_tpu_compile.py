"""The served kernels compiled for a TPU v5e that is described, not attached.

The TPU compiler (Mosaic) refuses what interpret mode accepts: unaligned
blocks, dynamic slices, shape casts, and scratch beyond VMEM.  These tests
compile ``vusa_packed_matmul`` and ``vusa_fused_mlp_matmul`` at
``vusa_edge``'s published widths (d_model 768, d_ff 3072, 85 % sparsity ->
48 slots per row, m=128) for every value format, at the ``k_blk`` that
``choose_k_blk`` picks on the chip, alone and under the Scheduler's vmapped
slot axis.  Nothing runs: a pass means the chip's compiler accepts the
kernels and that they lower to a Pallas TPU call named after the kernel,
the name a device trace shows for each call.  Under the slot vmap the
program holds one such call, on all the slots' rows.  The three kernels
also compile at the chip benchmark cells' own widths (InternLM2-1.8B's MLP
and head, Moonlight-16B-A3B's held experts), int8 under the slot vmap.

This is the only test file that describes the chip.  The topology is
described inside a module fixture (never at import or collection time), so
only the worker that runs this file loads the TPU library.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.vusa_packed import (
    vusa_fused_mlp_matmul,
    vusa_fused_moe_matmul,
    vusa_packed_matmul,
)

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


def _tpu_calls(text: str):
    """``(instruction name, x operand shape)`` of each TPU custom call in a
    compiled program's text."""
    return [
        (name, tuple(int(d) for d in shape.split(",")))
        for name, shape in re.findall(
            r"%([\w.]+) = [^\n]*custom_call_target=\"tpu_custom_call\", "
            r"operand_layout_constraints=\{\w+\[([\d,]+)\]",
            text,
        )
    ]


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
    # the Scheduler decodes every slot at batch 1 under jax.vmap; the
    # kernels' vmap rule folds the slot axis into the call's rows
    x_shape = (BATCH, 1, D_MODEL) if slot_axis else (BATCH, D_MODEL)
    x = jax.ShapeDtypeStruct(x_shape, jnp.bfloat16, sharding=one_chip)
    fn = jax.vmap(call, in_axes=(0, None, None, None)) if slot_axis else call
    text = jax.jit(fn).lower(x, v, p, s).compile().as_text()
    # one call on all BATCH rows, with the slot axis or without
    calls = _tpu_calls(text)
    assert [shape for _, shape in calls] == [(BATCH, D_MODEL)], calls


@pytest.mark.parametrize("kernel, name", [
    ("packed", None), ("packed", "vusa_packed_matmul_head"), ("fused", None),
], ids=["packed", "packed_head", "fused"])
def test_kernel_calls_carry_their_names(one_chip, no_persistent_cache, chip_k_blk, kernel, name):
    """Each call compiles to a TPU custom call whose instruction is named
    ``vusa_packed_matmul``, ``vusa_packed_matmul_head`` (the LM head's
    call) or ``vusa_fused_mlp_matmul``, under the Scheduler's slot vmap."""
    t = D_FF // M
    v, p, s = _pack_shapes(one_chip, t, D_MODEL, "int8")
    kw = dict(m=M, k_blk=chip_k_blk, interpret=False, value_dtype="int8")
    if kernel == "packed":
        if name is not None:
            kw["name"] = name

        def call(x, v, p, s):
            return vusa_packed_matmul(x, v, p, s, **kw)
    else:
        def call(x, v, p, s):
            return vusa_fused_mlp_matmul(x, v, p, v, p, v, p, s, s, s, **kw)
    want = name or {"packed": "vusa_packed_matmul", "fused": "vusa_fused_mlp_matmul"}[kernel]
    x = jax.ShapeDtypeStruct((BATCH, 1, D_MODEL), jnp.bfloat16, sharding=one_chip)
    fn = jax.vmap(call, in_axes=(0, None, None, None))
    text = jax.jit(fn).lower(x, v, p, s).compile().as_text()
    calls = _tpu_calls(text)
    assert len(calls) == 1, calls
    (call_name, x_shape), = calls
    assert re.fullmatch(rf"{want}(\.\d+)?", call_name), calls
    assert x_shape == (BATCH, D_MODEL), calls


# an expert layer's held experts at Moonlight-16B-A3B's widths: 8 of 64
# experts held, each a (2048, 1408) SwiGLU, 11 ff windows
EXPERTS, D_EXPERT, D_HIDDEN = 8, 1408, 2048


@pytest.mark.parametrize("slot_axis", [False, True], ids=["engine", "scheduler"])
@pytest.mark.parametrize("value_dtype", ["dense", "int8", "int4"])
def test_grouped_expert_kernel_compiles_for_v5e(
    one_chip, no_persistent_cache, value_dtype, slot_axis, monkeypatch
):
    """``vusa_fused_moe_matmul`` compiles for the chip at the k_blk the
    chip picks, and under the Scheduler's slot vmap, where the rows and
    their expert weights both carry the slot axis, it is one call on all
    the slots' rows, named after the kernel."""
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    monkeypatch.delenv("REPRO_VUSA_KBLK", raising=False)
    k_blk = ops.choose_k_blk(D_HIDDEN, SLOTS, M)
    t = -(-D_EXPERT // M)
    nib = 2 if value_dtype == "int4" else 1
    vdt = jnp.bfloat16 if value_dtype == "dense" else jnp.int8

    def pack(rows):
        v = jax.ShapeDtypeStruct((EXPERTS, t, rows, SLOTS // nib), vdt, sharding=one_chip)
        p = jax.ShapeDtypeStruct((EXPERTS, t, rows, SLOTS), jnp.int8, sharding=one_chip)
        s = (None if value_dtype == "dense"
             else jax.ShapeDtypeStruct((EXPERTS, t, rows), jnp.float32, sharding=one_chip))
        return v, p, s

    (v, p, s), (dv, dp, ds) = pack(D_HIDDEN), pack(D_HIDDEN)
    kw = dict(m=M, k_blk=k_blk, interpret=False, value_dtype=value_dtype)

    def call(x, w, v, p, s, dv, dp, ds):
        return vusa_fused_moe_matmul(x, w, v, p, v, p, dv, dp, s, s, ds, **kw)

    lead = (BATCH, 1) if slot_axis else (BATCH,)
    x = jax.ShapeDtypeStruct(lead + (D_HIDDEN,), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct(lead + (EXPERTS,), jnp.float32, sharding=one_chip)
    fn = jax.vmap(call, in_axes=(0, 0) + (None,) * 6) if slot_axis else call
    text = jax.jit(fn).lower(x, w, v, p, s, dv, dp, ds).compile().as_text()
    calls = _tpu_calls(text)
    assert len(calls) == 1, calls
    (call_name, x_shape), = calls
    assert re.fullmatch(r"vusa_fused_moe_matmul(\.\d+)?", call_name), calls
    assert x_shape == (BATCH, D_HIDDEN), calls


# the chip benchmark cells' own widths, int8 at 85 % sparsity (48 slots):
# InternLM2-1.8B's fused MLP (2048 x 8192) and 92544-wide head, 8 slots a
# step; Moonlight-16B-A3B's 8 held experts (2048 x 1408), 64 slots a step
CELL_D, CELL_FF, CELL_VOCAB = 2048, 8192, 92544


@pytest.mark.parametrize("case", ["internlm2_mlp", "internlm2_head", "moonlight_experts"])
def test_cell_width_kernels_compile_for_v5e(one_chip, no_persistent_cache, case, monkeypatch):
    """Each kernel compiles at a benchmark cell's widths, at the k_blk
    ``choose_k_blk`` picks on the chip, int8, under the Scheduler's slot
    vmap: one call on all the slots' rows, named after the kernel."""
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    monkeypatch.delenv("REPRO_VUSA_KBLK", raising=False)
    kw = dict(m=M, k_blk=ops.choose_k_blk(CELL_D, SLOTS, M), interpret=False,
              value_dtype="int8")
    rows = 64 if case == "moonlight_experts" else BATCH
    x = jax.ShapeDtypeStruct((rows, 1, CELL_D), jnp.bfloat16, sharding=one_chip)
    if case == "moonlight_experts":
        def pack(lead):
            return (
                jax.ShapeDtypeStruct(lead + (CELL_D, SLOTS), jnp.int8, sharding=one_chip),
                jax.ShapeDtypeStruct(lead + (CELL_D, SLOTS), jnp.int8, sharding=one_chip),
                jax.ShapeDtypeStruct(lead + (CELL_D,), jnp.float32, sharding=one_chip),
            )

        v, p, s = pack((EXPERTS, -(-D_EXPERT // M)))
        w = jax.ShapeDtypeStruct((rows, 1, EXPERTS), jnp.float32, sharding=one_chip)

        def call(x, w, v, p, s):
            return vusa_fused_moe_matmul(x, w, v, p, v, p, v, p, s, s, s, **kw)

        fn, args, want = (jax.vmap(call, in_axes=(0, 0, None, None, None)),
                          (x, w, v, p, s), "vusa_fused_moe_matmul")
    else:
        t = CELL_FF // M if case == "internlm2_mlp" else -(-CELL_VOCAB // M)
        v, p, s = _pack_shapes(one_chip, t, CELL_D, "int8")
        if case == "internlm2_mlp":
            def call(x, v, p, s):
                return vusa_fused_mlp_matmul(x, v, p, v, p, v, p, s, s, s, **kw)

            want = "vusa_fused_mlp_matmul"
        else:
            def call(x, v, p, s):
                return vusa_packed_matmul(x, v, p, s, name="vusa_packed_matmul_head", **kw)

            want = "vusa_packed_matmul_head"
        fn, args = jax.vmap(call, in_axes=(0, None, None, None)), (x, v, p, s)
    text = jax.jit(fn).lower(*args).compile().as_text()
    calls = _tpu_calls(text)
    assert len(calls) == 1, calls
    (call_name, x_shape), = calls
    assert re.fullmatch(rf"{want}(\.\d+)?", call_name), calls
    assert x_shape == (rows, CELL_D), calls
