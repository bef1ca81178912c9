"""The Moonlight-16B-A3B family module (``references/mla_moe_lm.py``):
its configuration states the published model but for the cuts it lists,
its layout is the served program's weight tree, its counts name the
program's kernels, and the readers of its three per-layer metrics.

Run by hand, like the rest of this directory:

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""

import dataclasses
import json
import math
from types import SimpleNamespace

import jax
import numpy as np
import pytest

import harness
import weights

CELL = "moonlight_16b_a3b.decode_backlog"
CONFIG = json.loads((harness.HERE / "configs" / "moonlight_16b_a3b.json").read_text())


@pytest.fixture(scope="module")
def fam():
    return harness.family(CONFIG)


def _tree_shapes(tree) -> dict:
    return {k: tuple(v.shape) for k, v in weights.flatten(tree).items()}


def test_configuration_is_the_published_model_but_for_its_cuts(fam):
    from repro.configs import get_config

    model = CONFIG["model"]
    for key, published in CONFIG["published"].items():
        assert key in CONFIG["reduced"] and model[key] != published
    assert CONFIG["published"] == {"num_hidden_layers": 27, "ep_size": 1,
                                   "vocab_size": 163840, "rms_norm_eps": 1e-05}
    for key, value in model.items():  # the catalog's numbers, at top level and under model
        if key != "torch_dtype":
            assert CONFIG[key] == value, key
    arch, got = harness.program_config(CONFIG, False, fam)
    assert arch == dataclasses.replace(get_config("moonlight_16b_a3b"), n_layers=5, ep_size=8,
                                       vocab=20480)
    assert (arch.held_experts, arch.n_experts, arch.top_k) == (8, 64, 6)
    assert got is model


@pytest.mark.parametrize("smoke", [True, False], ids=["smoke", "published_widths"])
def test_layout_is_the_programs_weight_tree(fam, smoke):
    from repro.models import build_model

    arch, model = harness.program_config(CONFIG, smoke, fam)
    want = _tree_shapes(build_model(arch).abstract_params())
    got = {k: leaf.shape for k, leaf in fam.layout(model).items()}
    assert got == want


def test_counts_name_the_programs_kernels(fam):
    _, model = harness.program_config(CONFIG, False, fam)
    layout = fam.layout(model)
    nnz = {k: math.prod(leaf.shape[leaf.stacks:]) * 0.15 * np.ones(leaf.shape[: leaf.stacks])
           for k, leaf in layout.items() if leaf.init == "pruned"}
    c = harness.counts(fam, model, nnz, CONFIG["serve"])
    calls = c["calls"]
    assert set(calls) == {"vusa_packed_matmul", "vusa_fused_mlp_matmul", "vusa_fused_moe_matmul"}
    # 3 projections a layer and the head; the dense MLP and 4 shared; 4 grouped
    assert [len(calls[k]) for k in sorted(calls)] == [5, 4, 16]
    # the grouped call counts 8 experts at ceil(64 x 6 / 64) = 6 rows each
    g = calls["vusa_fused_moe_matmul"][0]
    z = fam.sizes(model)
    assert g.flops == pytest.approx(2 * 6 * 8 * 3 * 2048 * 1408 * 0.15)
    assert z["held"] == 8 and z["le"] == 4
    assert c["decode_flops"](0) > 2 * 20480 * 2048


def test_reference_runs_on_smoke_weights(fam):
    _, model = harness.program_config(CONFIG, True, fam)
    layout = fam.layout(model)
    w = weights.make_weights(layout, 0.85, 5, model["torch_dtype"], model["vocab_size"])
    assert float(abs(w["layers"]["ffn"]["router_bias"]).max()) > 0  # drawn non-zero
    out = fam.logits(w, jax.numpy.arange(1, 12), model)
    assert out.shape == (11, weights.pad_vocab(model["vocab_size"]))


def test_metric_readers():
    stats = {"expert_rows": 4 * 8 * 4 * 48, "decode_steps": 32, "expert_layers": 4,
             "held_experts": 8}
    trace = SimpleNamespace(module_time=lambda needle: (4, 2.0),
                            kernels={"vusa_fused_moe_matmul": (16, 1.0)})
    read = {m: harness._load("metrics", m).read for m in
            ("routed_rows_per_expert", "expert_kernel_share")}
    assert read["routed_rows_per_expert"](SimpleNamespace(stats=stats)) == 6.0
    # a program without the counters, or without held experts, reads nothing
    assert read["routed_rows_per_expert"](SimpleNamespace(stats={"syncs": 3})) is None
    none_held = SimpleNamespace(stats={**stats, "held_experts": 0})
    assert read["routed_rows_per_expert"](none_held) is None
    assert read["expert_kernel_share"](SimpleNamespace(trace=trace)) == 50.0
    assert read["expert_kernel_share"](SimpleNamespace(trace=None)) is None
    dense = SimpleNamespace(module_time=lambda needle: (4, 2.0), kernels={})
    assert read["expert_kernel_share"](SimpleNamespace(trace=dense)) is None
