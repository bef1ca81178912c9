"""The family seam: everything the harness knows of a model family comes
from its module (``references/<name>.py``), so a second family joins with
files of its own and no edit to the harness, ``weights.py``, ``work.py``
or ``xplane.py``; and the dense module gives what the harness gave before
it moved there (``parent_dense.json``), so a seed makes the same weights
and the counts read the same.
"""

import dataclasses
import hashlib
import importlib.util
import json
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest
from jax.profiler import ProfileData

import harness
import weights
import xplane
from test_xplane import HOST, event, meta

HERE = Path(__file__).resolve().parent
FIXTURE = json.loads((HERE / "moe_fixture.json").read_text())
PARENT = json.loads((HERE / "parent_dense.json").read_text())
DENSE_CONFIG = json.loads((harness.HERE / "configs" / "internlm2_1_8b.json").read_text())


def _module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def fam():
    return _module(HERE / "moe_fixture.py", "moe_fixture")


@pytest.fixture(scope="module")
def dense():
    return harness.family(DENSE_CONFIG)


@pytest.fixture(scope="module")
def smoke(fam):
    """The fixture at olmoe_1b_7b's SMOKE sizes: model, layout, weights."""
    import jax

    _, model = harness.program_config(FIXTURE, True, fam)
    layout = fam.layout(model)
    w = weights.make_weights(layout, 0.85, 11, model["torch_dtype"], model["vocab_size"])
    return model, layout, weights.flatten(jax.device_get(w)), weights.nonzeros(w, layout)


# -- a second family, with no edit to the harness ---------------------------

def test_program_config_takes_the_familys_keys_and_guard(fam, dense):
    from repro.configs import get_config, get_smoke_config

    arch, model = harness.program_config(FIXTURE, True, fam)
    small = get_smoke_config("olmoe_1b_7b")
    assert arch == small and arch.family == "moe"
    assert (model["num_experts"], model["num_experts_per_tok"], model["hidden_size"]) == (
        small.n_experts, small.top_k, small.d_model)
    assert model["first_k_dense_replace"] == 1  # the family's own key, from the file
    arch, model = harness.program_config(FIXTURE, False, fam)
    assert arch == dataclasses.replace(get_config("olmoe_1b_7b"), n_layers=4)
    wrong = json.loads(json.dumps(FIXTURE))
    wrong["model"]["moe_intermediate_size"] = 2048
    with pytest.raises(ValueError, match="moe_intermediate_size"):
        harness.program_config(wrong, False, fam)
    with pytest.raises(ValueError, match="dense reference"):
        dense.check_program(small)
    with pytest.raises(ValueError, match="not an expert model"):
        harness.program_config(DENSE_CONFIG, True, fam)


def test_weights_take_each_leafs_shape_and_draw(smoke):
    model, layout, flat, _ = smoke
    assert {k: v.shape for k, v in flat.items()} == {k: l.shape for k, l in layout.items()}
    d, e = model["hidden_size"], model["num_experts"]
    router = flat["moe/router"]
    assert router.shape == (1, d, e) and np.all(router != 0)  # scaled: not pruned
    assert abs(np.var(router) * d - 1) < 0.1
    assert not flat["moe/router_bias"].any() and not flat["final_norm"].any()
    for name in ("w_up", "w_down"):
        w = flat[f"moe/experts/{name}"]
        assert abs(np.mean(w == 0) - 0.85) < 0.01
        assert abs(np.var(w) * layout[f"moe/experts/{name}"].fan_in - 1) < 0.06
    assert np.all(flat["embed"][: model["vocab_size"]] != 0)  # normal: not pruned
    assert not flat["embed"][model["vocab_size"]:].any()
    assert not flat["lm_head"][:, model["vocab_size"]:].any()


def test_nonzeros_keep_both_stacked_axes(smoke):
    model, layout, flat, nnz = smoke
    assert set(nnz) == {k for k, l in layout.items() if l.init == "pruned"}
    up = nnz["moe/experts/w_up"]
    assert up.shape == (1, model["num_experts"])
    assert np.array_equal(up, np.count_nonzero(flat["moe/experts/w_up"], axis=(2, 3)))
    assert nnz["dense/w_down"].shape == (1,) and nnz["lm_head"].shape == ()


def test_counts_reach_the_metrics_context(fam, smoke):
    model, _, _, nnz = smoke
    serve = {"slots": 8, "packed_values": "int8"}
    got = harness.counts(fam, model, nnz, serve)
    assert got["calls"] == fam.packed_calls(model, nnz, 8, "int8")
    assert len(got["calls"][fam.EXPERT_KERNEL]) == 2 * model["num_experts"]
    assert got["decode_flops"](7) == fam.decode_flops(model, 7)
    assert got["prefill_flops"](7) == fam.prefill_flops(model, 7)


def test_the_trace_counts_the_familys_own_kernel(fam, smoke):
    """A synthetic trace with the expert kernel beside the packed kernel's
    head call site: each is counted under its own name, and the packed
    kernel's roofline reads its calls from the family's counts."""
    model, _, _, nnz = smoke
    dev = {1: "jit__segment_paged_fn(3)", 2: "fusion.1",
           3: f"%{fam.EXPERT_KERNEL}.3 = f32[2,64] custom-call(bf16[2,64] %copy.1)",
           4: "%vusa_packed_matmul_head.6 = f32[8,1,512] custom-call(bf16[8,1,64] %copy.2)"}
    text = f"""
planes {{ name: "/host:CPU" lines {{ name: "python3" timestamp_ns: 0 {event(1, 0, 10)} }}
  {meta(HOST)} }}
planes {{ name: "/device:TPU:0"
  lines {{ name: "XLA Modules" timestamp_ns: 0 {event(1, 1, 8)} }}
  lines {{ name: "XLA Ops" timestamp_ns: 0 {event(2, 1, 1)} {event(3, 2, 3)} {event(3, 5, 1)}
    {event(4, 6, 2)} }}
  {meta(dev)} }}
"""
    ctx = SimpleNamespace(**harness.counts(fam, model, nnz, {"slots": 8, "packed_values": "int8"}),
                          peaks={"bf16_flop_per_s": 197e12, "hbm_byte_per_s": 819e9})
    ctx.trace = xplane.summarize(ProfileData.from_text_proto(text), list(ctx.calls))
    assert ctx.trace.kernels == {fam.EXPERT_KERNEL: [2, pytest.approx(4e-3)],
                                 "vusa_packed_matmul": [1, pytest.approx(2e-3)]}
    roofline = _module(harness.HERE / "metrics" / "vusa_packed_matmul_roofline.py", "roofline")
    head = ctx.calls["vusa_packed_matmul"][0]
    assert roofline.read(ctx) == pytest.approx(100 * head.least_s(ctx.peaks) / 2e-3)


# -- the dense family gives what the harness gave before --------------------

def test_dense_layout_is_the_parents(dense):
    model = {**DENSE_CONFIG["model"], "num_hidden_layers": 24}  # the published depth
    got = {k: [list(l.shape), l.fan_in] for k, l in dense.layout(model).items()}
    assert got == PARENT["shapes_published"]


def test_dense_weights_are_the_parents(dense):
    """Bit for bit: the same leaves in the same order, each drawn as before."""
    import jax

    _, model = harness.program_config(DENSE_CONFIG, True, dense)
    layout = dense.layout(model)
    w = weights.make_weights(layout, PARENT["smoke_sparsity"], PARENT["smoke_seed32"],
                             model["torch_dtype"], model["vocab_size"])
    flat = weights.flatten(jax.device_get(w))
    h = hashlib.sha256()
    for k in sorted(flat):
        a = np.asarray(flat[k])
        h.update(k.encode())
        h.update(str(a.dtype).encode())
        h.update(a.tobytes())
    assert h.hexdigest() == PARENT["smoke_digest"]
    nnz = weights.nonzeros(w, layout)
    assert {k: v.tolist() for k, v in nnz.items()} == PARENT["smoke_nonzeros"]


@pytest.mark.parametrize("values", ["int8", "bf16", "int4"])
def test_dense_calls_are_the_parents(dense, values):
    nnz = {k: np.asarray(v) for k, v in PARENT["nonzeros"].items()}
    got = dense.packed_calls(DENSE_CONFIG["model"], nnz, PARENT["rows"], values)
    assert {k: [[c.flops, c.bytes] for c in v] for k, v in got.items()} == PARENT["calls"][values]


def test_dense_flops_are_the_parents(dense):
    m = DENSE_CONFIG["model"]
    assert {str(n): dense.decode_flops(m, n) for n in (1, 1020, 2047)} == PARENT["decode_flops"]
    assert {str(n): dense.prefill_flops(m, n) for n in (1, 128, 1020, 1400)} == \
        PARENT["prefill_flops"]
