"""Operations and bytes: the peaks of each chip, the least time of each
packed-kernel call, and the model FLOPs of the served tokens.

Counts come from the configuration and from the non-zeros of the weights
the benchmark made, never from the program: a later change to the kernels
cannot change what their work is said to be.
"""

from __future__ import annotations

import json
import math
from dataclasses import dataclass
from pathlib import Path

HERE = Path(__file__).resolve().parent
VALUE_BYTES = {"bf16": 2.0, "int8": 1.0, "int4": 0.5}
ACT_BYTES = 2  # bf16 activations into a kernel
OUT_BYTES = 4  # both kernels write float32
LANES = 128  # window width of a pack; one float32 scale per (window, row)


def peaks(device_kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: {sorted(table)}")
    return table[device_kind]


@dataclass(frozen=True)
class Call:
    flops: float
    bytes: float

    def least_s(self, pk: dict) -> float:
        return max(self.flops / pk["bf16_flop_per_s"], self.bytes / pk["hbm_byte_per_s"])


def _linear(rows: int, k: int, n: int, nnz: float, values: str) -> Call:
    scales = 0 if values == "bf16" else math.ceil(n / LANES) * k * 4
    return Call(
        flops=2.0 * rows * nnz,
        bytes=nnz * (VALUE_BYTES[values] + 1) + scales + rows * k * ACT_BYTES
        + rows * n * OUT_BYTES,
    )


def packed_calls(model: dict, nnz: dict, rows: int, values: str) -> dict:
    """The calls of one decode step, per kernel: ``vusa_packed_matmul`` runs
    wq, wk, wv, wo of every layer and the head; ``vusa_fused_mlp_matmul``
    runs each layer's whole MLP.  ``rows`` rows per call (one per slot);
    the pack's bytes are counted once per call, however many rows it
    serves."""
    L, d, f = model["num_hidden_layers"], model["hidden_size"], model["intermediate_size"]
    h, kv, hd = model["num_attention_heads"], model["num_key_value_heads"], model["head_dim"]
    packed, fused = [], []
    for layer in range(L):
        for name, k, n in (("wq", d, h * hd), ("wk", d, kv * hd), ("wv", d, kv * hd),
                           ("wo", h * hd, d)):
            packed.append(_linear(rows, k, n, float(nnz[f"layers/attn/{name}"][layer]), values))
        z = sum(float(nnz[f"layers/ffn/{n}"][layer]) for n in ("w_gate", "w_up", "w_down"))
        scales = 0 if values == "bf16" else 3 * math.ceil(f / LANES) * d * 4
        fused.append(Call(flops=2.0 * rows * z,
                          bytes=z * (VALUE_BYTES[values] + 1) + scales
                          + rows * d * ACT_BYTES + rows * d * OUT_BYTES))
    packed.append(_linear(rows, d, model["vocab_size"], float(nnz["lm_head"]), values))
    return {"vusa_packed_matmul": packed, "vusa_fused_mlp_matmul": fused}


def dense_params(model: dict) -> tuple:
    """(matmul parameters of the layers, of the head), dense-equivalent."""
    L, d, f = model["num_hidden_layers"], model["hidden_size"], model["intermediate_size"]
    h, kv, hd = model["num_attention_heads"], model["num_key_value_heads"], model["head_dim"]
    body = L * (d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f)
    return body, d * model["vocab_size"]


def attn_flops(model: dict, ctx: float) -> float:
    """QK^T and AV of one token against ``ctx`` positions, all layers."""
    return 4.0 * model["num_hidden_layers"] * model["num_attention_heads"] \
        * model["head_dim"] * ctx


def decode_flops(model: dict, ctx: int) -> float:
    body, head = dense_params(model)
    return 2.0 * (body + head) + attn_flops(model, ctx)


def prefill_flops(model: dict, n: int) -> float:
    """A prompt of ``n`` tokens: every token through the layers, causal
    attention, and the head at the last position only."""
    body, head = dense_params(model)
    return 2.0 * body * n + 2.0 * head + attn_flops(model, n * (n + 1) / 2)
