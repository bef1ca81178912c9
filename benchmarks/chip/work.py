"""Operations and bytes: the peaks of each chip, and the least time of a
packed-kernel call from its FLOPs and bytes.

A family module (``references/<family>.py``) lists the calls of one
decode step from the configuration and the non-zeros of the weights the
benchmark made, through the byte and FLOP rules here, never from the
program: a later change to the kernels cannot change what their work is
said to be.
"""

from __future__ import annotations

import json
import math
from dataclasses import dataclass
from pathlib import Path

HERE = Path(__file__).resolve().parent
VALUE_BYTES = {"bf16": 2.0, "int8": 1.0, "int4": 0.5}
ACT_BYTES = 2  # bf16 activations into a kernel
OUT_BYTES = 4  # the packed kernels write float32
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


def linear(rows: int, k: int, n: int, nnz: float, values: str) -> Call:
    """One packed ``(k, n)`` matrix with ``nnz`` non-zeros applied to
    ``rows`` rows: 2 FLOPs per row and non-zero; bytes for the values, a
    position byte each, the scales (none for bf16 values), the rows in and
    the float32 rows out.  The pack is read once, however many rows."""
    scales = 0 if values == "bf16" else math.ceil(n / LANES) * k * 4
    return Call(
        flops=2.0 * rows * nnz,
        bytes=nnz * (VALUE_BYTES[values] + 1) + scales + rows * k * ACT_BYTES
        + rows * n * OUT_BYTES,
    )


def fused_mlp(rows: int, d: int, f: int, nnz: float, values: str) -> Call:
    """One fused gated MLP (``(d, f)`` gate and up, ``(f, d)`` down, ``nnz``
    non-zeros in the three) on ``rows`` rows: the intermediate never leaves
    the chip, so only ``d``-wide rows go in and out."""
    scales = 0 if values == "bf16" else 3 * math.ceil(f / LANES) * d * 4
    return Call(flops=2.0 * rows * nnz,
                bytes=nnz * (VALUE_BYTES[values] + 1) + scales
                + rows * d * ACT_BYTES + rows * d * OUT_BYTES)
