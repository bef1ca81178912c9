"""Time the packed kernels over their K-block candidates on the chip.

    python scripts/kblk_sweep.py                           # on a TPU
    python scripts/kblk_sweep.py --baseline <dir>/src/repro/kernels/vusa_packed.py
    JAX_PLATFORMS=cpu python scripts/kblk_sweep.py --tiny  # rehearsal, interpret mode

The shapes are the chip benchmark cells' own, int8 packs at 85 % sparsity's
48 slots a row: InternLM2-1.8B's q projection (2048 x 2048), its 92544-wide
head (``vusa_packed_matmul_head``) and its fused MLP (2048 x 8192), 8 rows a
call; Moonlight-16B-A3B's 8 held experts (2048 x 1408), 64 rows a call.
Each call is timed over every ``k_blk`` that ``ops._kblk_candidates`` lists
for the chip and over ``TILE_ROWS`` multiples past them, as the median of
``--reps`` host-clock windows of ``--iters`` back-to-back calls.  With
``--baseline`` the same calls run through another kernel module at
``--baseline-kblk`` (a parent checkout's ``vusa_packed.py``), and each
result is compared with it.  Positions and values are random: a packed
call's work does not depend on them.  One JSON line per measurement.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import statistics
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from repro.kernels import ops, vusa_packed  # noqa: E402

SLOTS, M = 48, 128


def _pack(rng, lead, rows):
    """Random int8 (values, positions, scales) of ``lead + (rows, SLOTS)``:
    a row's positions are distinct lanes, as in a pack, and a fifth of the
    slots idle (-1)."""
    values = rng.integers(-127, 128, size=lead + (rows, SLOTS), dtype=np.int8)
    start = rng.integers(0, M, size=lead + (rows, 1))
    positions = ((start + 2 * np.arange(SLOTS)) % M).astype(np.int8)
    positions[rng.random(positions.shape) < 0.2] = -1
    scales = rng.random(lead + (rows,), dtype=np.float32) * 1e-2
    return jnp.asarray(values), jnp.asarray(positions), jnp.asarray(scales)


def _cases(tiny: bool, interpret: bool):
    d, ff, vocab, d_exp, experts = (256, 512, 1024, 256, 2) if tiny else (
        2048, 8192, 92544, 1408, 8)
    rng = np.random.default_rng(0)
    x8 = jnp.asarray(rng.normal(size=(8, d)), jnp.bfloat16)
    x64 = jnp.asarray(rng.normal(size=(64, d)), jnp.bfloat16)
    gates = jnp.asarray(rng.random((64, experts)) * (rng.random((64, experts)) < 0.75),
                        jnp.float32)
    wq = _pack(rng, (d // M,), d)
    head = _pack(rng, (-(-vocab // M),), d)
    mlp = [_pack(rng, (ff // M,), d) for _ in range(3)]
    exp = [_pack(rng, (experts, -(-d_exp // M)), d) for _ in range(3)]

    def packed(k, name="vusa_packed_matmul", p=wq):
        return lambda kb, x=x8: k.vusa_packed_matmul(
            x, *p, m=M, k_blk=kb, value_dtype="int8", name=name,
            interpret=interpret)

    def fused(k):
        (gv, gp, gs), (uv, up, us), (dv, dp, ds) = mlp
        return lambda kb: k.vusa_fused_mlp_matmul(
            x8, gv, gp, uv, up, dv, dp, gs, us, ds, m=M, k_blk=kb, value_dtype="int8",
            interpret=interpret)

    def grouped(k):
        (gv, gp, gs), (uv, up, us), (dv, dp, ds) = exp
        return lambda kb: k.vusa_fused_moe_matmul(
            x64, gates, gv, gp, uv, up, dv, dp, gs, us, ds, m=M, k_blk=kb,
            value_dtype="int8", interpret=interpret)

    return d, {
        "internlm2.wq": lambda k: packed(k),
        "internlm2.head": lambda k: packed(k, "vusa_packed_matmul_head", head),
        "internlm2.mlp": fused,
        "moonlight.experts": grouped,
    }


def _time(fn, iters: int, reps: int) -> float:
    fn().block_until_ready()  # compile
    windows = []
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(iters):
            y = fn()
        y.block_until_ready()
        windows.append((time.perf_counter() - t0) / iters)
    return statistics.median(windows)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--baseline", help="another checkout's kernels/vusa_packed.py")
    ap.add_argument("--baseline-kblk", type=int, default=128)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--tiny", action="store_true", help="small shapes, for the CPU")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.tiny:
        print("no TPU: pass --tiny to rehearse on the CPU", file=sys.stderr)
        return 2
    base = None
    if args.baseline:
        spec = importlib.util.spec_from_file_location("baseline_vusa_packed", args.baseline)
        base = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(base)
    on_chip = dev.platform == "tpu"
    d, cases = _cases(args.tiny, interpret=not on_chip)
    blocks = ops._kblk_candidates(d, SLOTS, M) if on_chip else [64, 128, 256]
    blocks = sorted(set(blocks) | {b for b in (512, 1024, 2048) if d % b == 0})
    head = {"device": dev.device_kind, "platform": dev.platform, "slots": SLOTS}
    for case, make in cases.items():
        call = make(vusa_packed)
        ref = None
        if base is not None:
            bcall = make(base)
            ref = np.asarray(bcall(args.baseline_kblk), np.float32)
            s = _time(lambda: bcall(args.baseline_kblk), args.iters, args.reps)
            print(json.dumps({**head, "case": case, "kernel": "baseline",
                              "k_blk": args.baseline_kblk, "ms": s * 1e3}), flush=True)
        for kb in blocks:
            line = {**head, "case": case, "kernel": "this", "k_blk": kb}
            try:
                y = np.asarray(call(kb), np.float32)
                line["ms"] = _time(lambda: call(kb), args.iters, args.reps) * 1e3
            except Exception as e:  # a block the compiler refuses
                line["error"] = f"{type(e).__name__}: {str(e)[:200]}"
                print(json.dumps(line), flush=True)
                continue
            if ref is not None:
                scale = float(np.abs(ref).max()) or 1.0
                line["max_rel_err"] = float(np.abs(y - ref).max()) / scale
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
