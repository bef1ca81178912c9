import numpy as np
import pytest

import harness
import weights
import work

DENSE = harness.family({"reference": "dense_lm"})


def test_peaks_are_keyed_by_device_kind():
    assert work.peaks("TPU v5 lite")["hbm_byte_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")


def test_pruned_weights_hit_the_sparsity_at_unit_fan_in_variance():
    import jax

    m = {"num_hidden_layers": 2, "hidden_size": 256, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 64, "intermediate_size": 512,
         "vocab_size": 1000, "rope_theta": 1e4, "rms_norm_eps": 1e-6,
         "torch_dtype": "float32"}
    layout = DENSE.layout(m)
    w = weights.make_weights(layout, 0.85, 7, m["torch_dtype"], m["vocab_size"])
    flat = weights.flatten(jax.device_get(w))
    wg = np.asarray(flat["layers/ffn/w_gate"])
    assert abs(np.mean(wg == 0) - 0.85) < 0.01
    assert abs(np.var(wg) * 256 - 1) < 0.05
    assert not np.asarray(flat["lm_head"])[:, 1000:].any()
    nnz = weights.nonzeros(w, layout)
    assert nnz["layers/attn/wq"].shape == (2,)
    calls = DENSE.packed_calls(m, nnz, rows=8, values="int8")
    assert len(calls["vusa_packed_matmul"]) == 4 * 2 + 1
    assert len(calls["vusa_fused_mlp_matmul"]) == 2
    c = calls["vusa_fused_mlp_matmul"][0]
    z = sum(int(nnz[f"layers/ffn/{n}"][0]) for n in ("w_gate", "w_up", "w_down"))
    assert c.flops == 2 * 8 * z


def test_model_flops():
    m = {"num_hidden_layers": 1, "hidden_size": 4, "num_attention_heads": 1,
         "num_key_value_heads": 1, "head_dim": 4, "intermediate_size": 8, "vocab_size": 10}
    body, head = DENSE.dense_params(m)
    assert body == 4 * 4 * 4 + 3 * 4 * 8 and head == 40
    assert DENSE.decode_flops(m, 3) == 2 * (body + head) + 4 * 4 * 3
