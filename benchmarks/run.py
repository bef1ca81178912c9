"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV to stdout and writes full tables to
``experiments/benchmarks/*.json``.

Paper artifacts:
  fig6_growth           Fig. 6   growth probability curves (N=3, M=6, A=3)
  table1_area_power     Table I  16-nm PPA, component model vs paper values
  table2_resnet18       Table II ResNet-18 @ 85% unstructured sparsity
  table3_mobilenet      Table III MobileNetV1 @ 75%
  fig89_pruning_sweep   Fig. 8/9 area/power efficiency vs pruning rate
Framework micro-benchmarks:
  kernel_vusa_packed    packed-vs-dense matmul (bytes + wall time, CPU jnp)
  bench_spec_decode     self-speculative decode: accepted-tok/s vs baseline
  bench_scheduler       host-side schedule throughput
  bench_train_decode    smoke-model jitted train/decode step wall time
  bench_admission       bucketed batched admission vs per-request admission
"""

from __future__ import annotations

import json
import time
from pathlib import Path

import numpy as np

OUT = Path(__file__).resolve().parent.parent / "experiments" / "benchmarks"

VUSA = (3, 6, 3)  # the paper's (N, M, A)
FREQ_HZ = 1e9


RESULTS = {}  # bench name -> saved table (for the regression gate)


def _emit(name, us, derived):
    print(f"{name},{us:.1f},{derived}")


def _save(name, obj):
    OUT.mkdir(parents=True, exist_ok=True)
    (OUT / f"{name}.json").write_text(json.dumps(obj, indent=1, default=float))
    RESULTS[name] = obj


# ---------------------------------------------------------------------------


def fig6_growth():
    from repro.core.growth import growth_curves

    t0 = time.time()
    sparsity = np.linspace(0, 1, 101)
    curves = growth_curves(3, 6, 3, sparsity)
    us = (time.time() - t0) * 1e6
    anchors = {
        "P(3x6)@s=0.9": float(curves[6][90]),
        "P(3x6)@s=0.6": float(curves[6][60]),
        "P(3x4)@s=0.3": float(curves[4][30]),
    }
    _save("fig6_growth", {"sparsity": sparsity.tolist(),
                          **{f"w{w}": c.tolist() for w, c in curves.items()},
                          "anchors": anchors})
    _emit("fig6_growth", us, ";".join(f"{k}={v:.3f}" for k, v in anchors.items()))


def table1_area_power():
    from repro.core.hwmodel import TABLE1_PAPER, table1

    t0 = time.time()
    model = table1()
    us = (time.time() - t0) * 1e6
    rows = {}
    max_err = 0.0
    for k, (macs, area, power) in model.items():
        pm, pa, pp = TABLE1_PAPER[k]
        rows[k] = {"macs": macs, "area": area, "area_paper": pa,
                   "power": power, "power_paper": pp}
        max_err = max(max_err, abs(area - pa), abs(power - pp))
    _save("table1_area_power", rows)
    _emit("table1_area_power", us, f"max_abs_err_vs_paper={max_err:.3f}")


# ---------------------------------------------------------------------------


def _prune_masks(gemms, rate, seed=0):
    """Magnitude-prune random-init weights per layer (DESIGN.md: SparseZoo
    is offline; iid random init + magnitude pruning = unstructured sparsity)."""
    rng = np.random.default_rng(seed)
    masks = []
    for g in gemms:
        w = rng.normal(size=(g.K, g.C))
        thresh = np.quantile(np.abs(w), rate)
        masks.append(np.abs(w) > thresh)
    return masks


def _evaluate_model(gemms, masks, label, paper_row=None):
    """Full Section V-C methodology: standard 3x3..3x6 + VUSA cycles,
    GOP/s @1 GHz, PPA efficiency normalized to standard 3x6."""
    from repro.core.hwmodel import HwModel
    from repro.core.simulator import gemm_cycles_standard, ws_cycles
    from repro.core.vusa import schedule_widths_fast

    n, m, a = VUSA
    hw = HwModel()
    total_ops = sum(g.ops for g in gemms)

    cycles_std = {w: sum(gemm_cycles_standard(g, n, w) for g in gemms) for w in range(a, m + 1)}

    hist_total = np.zeros(m + 1, dtype=np.int64)
    load = np.zeros(m + 1)
    cycles_vusa = 0
    for g, mask in zip(gemms, masks):
        hist, _ = schedule_widths_fast(mask, n, m, a)
        hist_total += hist
        for w in range(a, m + 1):
            cycles_vusa += int(hist[w]) * ws_cycles(g.B, n, w)
            load[w] += hist[w] * w * g.B
    load_split = (load / load.sum()).tolist()

    def perf(cycles):
        return total_ops / (cycles / FREQ_HZ) / 1e9  # GOP/s

    area6, power6 = hw.area_standard(n, m), hw.power_standard(n, m)
    t6 = cycles_std[m]
    table = {}
    for w in range(a, m + 1):
        cyc = cycles_std[w]
        aw, pw = hw.area_standard(n, w), hw.power_standard(n, w)
        table[f"standard_3x{w}"] = {
            "cycles": cyc,
            "time_ms": cyc / FREQ_HZ * 1e3,
            "gops": perf(cyc),
            "perf_per_area": (perf(cyc) / aw) / (perf(t6) / area6),
            "perf_per_power": (perf(cyc) / pw) / (perf(t6) / power6),
            "energy": (pw * cyc) / (power6 * t6),
        }
    av, pv = hw.area_vusa(n, m, a), hw.power_vusa(n, m, a)
    table["vusa_3x6"] = {
        "cycles": cycles_vusa,
        "time_ms": cycles_vusa / FREQ_HZ * 1e3,
        "gops": perf(cycles_vusa),
        "perf_per_area": (perf(cycles_vusa) / av) / (perf(t6) / area6),
        "perf_per_power": (perf(cycles_vusa) / pv) / (perf(t6) / power6),
        "energy": (pv * cycles_vusa) / (power6 * t6),
        "load_split": load_split,
    }
    if paper_row:
        table["paper_vusa"] = paper_row
    return table


_PAPER_T2 = {"cycles": 9.65e7, "gops": 16.02, "perf_per_area": 1.27,
             "perf_per_power": 1.56, "energy": 0.64, "load6": 0.8685}
_PAPER_T3 = {"cycles": 4.43e7, "gops": 12.86, "perf_per_area": 1.18,
             "perf_per_power": 1.45, "energy": 0.69, "load6": 0.6864}


def table2_resnet18():
    from repro.core.workloads import resnet18_gemms

    t0 = time.time()
    gemms = resnet18_gemms()
    masks = _prune_masks(gemms, 0.85)
    table = _evaluate_model(gemms, masks, "resnet18@85", _PAPER_T2)
    us = (time.time() - t0) * 1e6
    _save("table2_resnet18", table)
    v = table["vusa_3x6"]
    _emit(
        "table2_resnet18",
        us,
        f"vusa_gops={v['gops']:.2f}(paper {_PAPER_T2['gops']});"
        f"pp_area={v['perf_per_area']:.2f}(paper {_PAPER_T2['perf_per_area']});"
        f"pp_power={v['perf_per_power']:.2f}(paper {_PAPER_T2['perf_per_power']});"
        f"energy={v['energy']:.2f}(paper {_PAPER_T2['energy']});"
        f"load6={v['load_split'][6]:.3f}(paper {_PAPER_T2['load6']})",
    )


def table3_mobilenet():
    from repro.core.workloads import mobilenetv1_gemms

    t0 = time.time()
    gemms = mobilenetv1_gemms()
    masks = _prune_masks(gemms, 0.75)
    table = _evaluate_model(gemms, masks, "mobilenetv1@75", _PAPER_T3)
    us = (time.time() - t0) * 1e6
    _save("table3_mobilenet", table)
    v = table["vusa_3x6"]
    _emit(
        "table3_mobilenet",
        us,
        f"vusa_gops={v['gops']:.2f}(paper {_PAPER_T3['gops']});"
        f"pp_area={v['perf_per_area']:.2f}(paper {_PAPER_T3['perf_per_area']});"
        f"pp_power={v['perf_per_power']:.2f}(paper {_PAPER_T3['perf_per_power']});"
        f"energy={v['energy']:.2f}(paper {_PAPER_T3['energy']});"
        f"load6={v['load_split'][6]:.3f}(paper {_PAPER_T3['load6']})",
    )


def fig89_pruning_sweep():
    from repro.core.workloads import resnet18_gemms

    t0 = time.time()
    gemms = resnet18_gemms()
    rates = [0.0, 0.15, 0.3, 0.45, 0.55, 0.65, 0.75, 0.85, 0.95]
    area_eff, power_eff = [], []
    for r in rates:
        masks = _prune_masks(gemms, r)
        table = _evaluate_model(gemms, masks, f"sweep@{r}")
        area_eff.append(table["vusa_3x6"]["perf_per_area"])
        power_eff.append(table["vusa_3x6"]["perf_per_power"])
    us = (time.time() - t0) * 1e6
    # crossover rates (efficiency > 1 vs standard 3x6)
    a_cross = next((r for r, e in zip(rates, area_eff) if e >= 1.0), None)
    p_cross = next((r for r, e in zip(rates, power_eff) if e >= 1.0), None)
    _save("fig89_pruning_sweep", {"rates": rates, "area_eff": area_eff,
                                  "power_eff": power_eff,
                                  "area_crossover": a_cross, "power_crossover": p_cross,
                                  "paper": {"area_crossover": 0.55, "power_crossover": 0.30,
                                            "area_eff@95": 1.36, "power_eff@95": 1.67}})
    _emit("fig89_pruning_sweep", us,
          f"area_eff@95={area_eff[-1]:.2f}(paper 1.36);power_eff@95={power_eff[-1]:.2f}(paper 1.67);"
          f"area_cross={a_cross}(paper ~0.55);power_cross={p_cross}(paper ~0.30)")


# ---------------------------------------------------------------------------


def kernel_vusa_packed():
    """Packed vs dense matmul: HBM byte ratio (the TPU-side VUSA gain) and
    a before/after of the Pallas kernel's dense-tile reconstruction —
    "before" is the seed per-slot fori_loop at its default k_blk=256,
    "after" is the vectorized one-hot contraction with the autotuned k_blk
    (repro.kernels.ops.choose_k_blk/autotune_row_packed)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.ops import (
        apply_row_packed,
        autotune_row_packed,
        pack_linear_rows,
    )
    from repro.kernels.ref import dense_matmul_ref, vusa_packed_ref
    from repro.kernels.vusa_packed import vusa_packed_matmul

    rng = np.random.default_rng(0)
    k = c = 1024
    b = 64
    iters = 10
    results = {}
    x = jnp.asarray(rng.normal(size=(b, k)), jnp.float32)

    def best_of(f, reps=3):
        f(x).block_until_ready()  # compile
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            for _ in range(iters):
                f(x).block_until_ready()
            best = min(best, (time.perf_counter() - t0) / iters)
        return best

    for sp in (0.0, 0.5, 0.85, 0.95):
        w = rng.normal(size=(k, c)) * (rng.random((k, c)) > sp)
        w = w.astype(np.float32)
        p = pack_linear_rows(w, a=16)
        wj = jnp.asarray(w)
        entry = {
            "byte_ratio": p.byte_ratio,
            "n_jobs": int(p.values.shape[2] // p.a),
        }
        if sp == 0.0:
            # N:M structured comparison arm (S2TA-style density-bound blocks):
            # 2:4 prunes the dense matrix itself, then rides the same kernel
            from repro.core.packing import nm_mask
            from repro.kernels.ops import pack_linear_rows_nm

            pnm = pack_linear_rows_nm(w, n=2, block=4, a=16)
            masked = np.where(nm_mask(w, 2, 4), w, 0.0)
            got = np.asarray(apply_row_packed(x, pnm), np.float32)
            np.testing.assert_allclose(
                got, np.asarray(x, np.float32) @ masked, rtol=1e-4, atol=1e-4
            )
            f_nm = jax.jit(lambda a: apply_row_packed(a, pnm))
            results["nm_2of4"] = {
                "byte_ratio": pnm.byte_ratio,
                "n_jobs": int(pnm.values.shape[2] // pnm.a),
                "kernel_vec_us": best_of(f_nm) * 1e6,
            }
        if sp in (0.85, 0.95):  # wall-time A/B on the interesting points
            ref = np.asarray(vusa_packed_ref(x, p.values, p.positions))[:, : p.c]
            got = np.asarray(apply_row_packed(x, p), np.float32)
            np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)
            k_blk = autotune_row_packed(x, p)
            from repro.kernels.ops import interpret_mode

            interp = interpret_mode()  # both arms on the same execution mode
            f_dense = jax.jit(lambda a: dense_matmul_ref(a, wj))
            f_before = jax.jit(
                lambda a: vusa_packed_matmul(
                    a, p.values, p.positions, m=p.m, k_blk=256,
                    interpret=interp, reconstruct="loop",
                )
            )
            f_after = jax.jit(lambda a: apply_row_packed(a, p))
            entry.update(
                dense_us=best_of(f_dense) * 1e6,
                kernel_loop_us=best_of(f_before) * 1e6,
                kernel_vec_us=best_of(f_after) * 1e6,
                k_blk=k_blk,
            )
            entry["kernel_speedup"] = entry["kernel_loop_us"] / entry["kernel_vec_us"]
        results[f"sparsity_{sp}"] = entry
    _save("kernel_vusa_packed", results)
    r85 = results["sparsity_0.85"]
    _emit("kernel_vusa_packed", r85["kernel_vec_us"],
          f"byte_ratio@85={r85['byte_ratio']:.3f};jobs@85={r85['n_jobs']};"
          f"loop_us@85={r85['kernel_loop_us']:.0f};vec_us@85={r85['kernel_vec_us']:.0f};"
          f"speedup@85={r85['kernel_speedup']:.2f}x;"
          f"byte_ratio@95={results['sparsity_0.95']['byte_ratio']:.3f}")


def bench_decode_fused():
    """Fused on-device decode loop vs the seed per-token host loop: same
    smoke model, same prompts, greedy — identical tokens required, tokens/s
    compared (best of 3 after a matched-shape compile warmup)."""
    import jax

    from repro.configs import get_smoke_config
    from repro.models import build_model
    from repro.serve import Engine, ServeConfig

    cfg = get_smoke_config("llama3_2_1b")
    params = build_model(cfg).init(jax.random.key(0))
    prompts = np.ones((2, 6), np.int32)
    max_new = 64
    runs = {}
    for fused in (False, True):
        eng = Engine(cfg, params, ServeConfig(max_len=128, fused=fused))
        eng.generate(prompts, max_new=max_new)  # compile (same steps shape)
        best = None
        for _ in range(3):
            out = eng.generate(prompts, max_new=max_new)
            if best is None or out["tok_per_s"] > best["tok_per_s"]:
                best = out
        runs[fused] = best
    assert (runs[False]["tokens"] == runs[True]["tokens"]).all(), "fused decode diverged"
    us = runs[True]["decode_s"] * 1e6  # per-generate decode time of the fused arm
    speedup = runs[True]["tok_per_s"] / runs[False]["tok_per_s"]
    _save("bench_decode_fused", {
        "seed_tok_per_s": runs[False]["tok_per_s"],
        "fused_tok_per_s": runs[True]["tok_per_s"],
        "speedup": speedup,
        "batch": int(prompts.shape[0]),
        "max_new": max_new,
    })
    _emit("bench_decode_fused", us,
          f"seed_tok_s={runs[False]['tok_per_s']:.0f};"
          f"fused_tok_s={runs[True]['tok_per_s']:.0f};speedup={speedup:.2f}x")


def bench_packed_decode():
    """Fused packed-MLP megakernel vs the 3-dispatch packed path, and
    whole-model packing vs MLP-only, on the decode hot loop (DESIGN.md §7).

    Three packed engines serve the same pruned smoke model: ``split3`` is
    the seed 3-dispatch MLP-only path (one Pallas call per MLP matrix, the
    (B, ff) intermediate round-trips between them), ``fused`` runs the
    megakernel (one call per layer), ``whole`` additionally packs qkv/o and
    the untied LM head.  Token streams must be identical to the dense
    engine; arms are interleaved best-of-N so machine noise hits them
    alike.  Also reports the packed/dense weight-byte ratios, the paper's
    actual currency."""
    import jax

    from repro.configs import get_smoke_config
    from repro.core.pruning import prune_tree
    from repro.models import build_model
    from repro.serve import Engine, ServeConfig
    from repro.serve.packed import packed_byte_ratios

    cfg = get_smoke_config("vusa_edge")
    params = prune_tree(build_model(cfg).init(jax.random.key(0)), 0.85)
    prompts = np.ones((2, 6), np.int32)
    max_new = 64
    engines = {
        "dense": Engine(cfg, params, ServeConfig(max_len=128)),
        "split3": Engine(
            cfg, params, ServeConfig(max_len=128, packed_weights="mlp", fused_mlp=False)
        ),
        "fused": Engine(cfg, params, ServeConfig(max_len=128, packed_weights="mlp")),
        "whole": Engine(cfg, params, ServeConfig(max_len=128, packed_weights="all")),
        "int8": Engine(
            cfg, params,
            ServeConfig(max_len=128, packed_weights="all", packed_values="int8"),
        ),
        "int4": Engine(
            cfg, params,
            ServeConfig(max_len=128, packed_weights="all", packed_values="int4"),
        ),
    }
    # tune the fused shape before the engines trace: apply_fused_mlp consults
    # the autotune cache at trace time, so the winner reaches the megakernel
    import jax.numpy as jnp
    from repro.kernels.ops import RowPackedLinear, autotune_fused_mlp

    mlp = engines["fused"]._packed["mlp"]

    def layer0(e):  # one layer of the stacked pack, job-padding included
        return RowPackedLinear(
            values=e["values"][0], positions=e["positions"][0],
            k=e["k"], c=e["c"], a=e["a"], m=e["m"],
        )

    k_blk = autotune_fused_mlp(
        jnp.ones((prompts.shape[0], cfg.d_model), jnp.float32),
        layer0(mlp["w_gate"]), layer0(mlp["w_up"]), layer0(mlp["w_down_t"]),
    )
    toks = {}
    for name, eng in engines.items():  # compile + parity check
        toks[name] = eng.generate(prompts, max_new=max_new)["tokens"]
        if name in ("int8", "int4"):
            continue  # quantized arms gate against the qdq oracle below
        assert (toks[name] == toks["dense"]).all(), f"{name} decode diverged from dense"
    # int8 correctness bar (DESIGN.md §10): greedy tokens bit-exact vs a
    # dense engine running on quantize-dequantize'd weights.  int4 is gated
    # on same-cache decode-step logits instead: the oracle *prefills* on qdq
    # weights while the packed engine prefills dense, so near-tie argmaxes
    # may flip a token without any kernel error.
    from repro.serve.packed import lm_decode_step_packed, qdq_lm_params

    oracle8 = Engine(cfg, qdq_lm_params(cfg, params, value_dtype="int8"),
                     ServeConfig(max_len=128))
    otoks = oracle8.generate(prompts, max_new=max_new)["tokens"]
    assert (toks["int8"] == otoks).all(), "int8 decode diverged from qdq-dense oracle"
    # same-cache decode step: prefill is dense in every packed arm, so one
    # prime supplies the shared cache; quantized logits must stay within the
    # quantization error of the bf16-pack logits and within accumulation
    # noise of their own qdq-dense step
    nxt, cache, _ = engines["whole"].prime(prompts, jax.random.key(0))
    step_logits = {}
    for name in ("whole", "int8", "int4"):
        lg, _ = lm_decode_step_packed(
            engines[name].params, engines[name]._packed, nxt, cache, cfg
        )
        step_logits[name] = np.asarray(lg, np.float32)
    for dt in ("int8", "int4"):
        qdq = qdq_lm_params(cfg, params, value_dtype=dt)
        lg, _ = engines["dense"].model.decode_step(qdq, nxt, cache)
        np.testing.assert_allclose(
            step_logits[dt], np.asarray(lg, np.float32), rtol=1e-4, atol=1e-4,
            err_msg=f"{dt} kernel dequant diverged from its qdq-dense step",
        )
        span = float(np.abs(step_logits["whole"]).max())
        err = float(np.abs(step_logits[dt] - step_logits["whole"]).max())
        assert err <= 0.35 * max(span, 1.0), (
            f"{dt} logits drifted {err:.3f} from bf16 pack (span {span:.3f})"
        )
    best = {n: 0.0 for n in engines}
    for _ in range(6):  # interleave trials so noise hits every arm alike
        for name, eng in engines.items():
            out = eng.generate(prompts, max_new=max_new)
            best[name] = max(best[name], out["tok_per_s"])
    fused_speedup = best["fused"] / best["split3"]
    whole_vs_mlp = best["whole"] / best["fused"]
    ratios = packed_byte_ratios(engines["whole"]._packed)
    qratios = {dt: packed_byte_ratios(engines[dt]._packed) for dt in ("int8", "int4")}
    # §10 HBM budget at 85% sparsity: quantized packs must beat these totals
    assert qratios["int8"]["total"] <= 0.18, qratios["int8"]
    assert qratios["int4"]["total"] <= 0.15, qratios["int4"]
    _save("bench_packed_decode", {
        "split3_tok_per_s": best["split3"],
        "fused_tok_per_s": best["fused"],
        "whole_tok_per_s": best["whole"],
        "dense_tok_per_s": best["dense"],
        "int8_tok_per_s": best["int8"],
        "int4_tok_per_s": best["int4"],
        "fused_speedup": fused_speedup,
        "whole_vs_mlp": whole_vs_mlp,
        "byte_ratio_total": ratios["total"],
        "byte_ratio_int8": qratios["int8"]["total"],
        "byte_ratio_int4": qratios["int4"]["total"],
        "byte_ratios": ratios,
        "fused_k_blk": k_blk,
        "batch": int(prompts.shape[0]),
        "max_new": max_new,
    })
    _emit("bench_packed_decode", 1e6 / max(best["fused"], 1e-9),
          f"split3_tok_s={best['split3']:.0f};fused_tok_s={best['fused']:.0f};"
          f"whole_tok_s={best['whole']:.0f};int8_tok_s={best['int8']:.0f};"
          f"int4_tok_s={best['int4']:.0f};fused_speedup={fused_speedup:.2f}x;"
          f"whole_vs_mlp={whole_vs_mlp:.2f}x;bytes={ratios['total']:.3f};"
          f"bytes_int8={qratios['int8']['total']:.3f};"
          f"bytes_int4={qratios['int4']['total']:.3f}")


def bench_spec_decode():
    """Self-speculative decoding via sparsity tiers (DESIGN.md §13):
    accepted-tokens/s at draft lengths k in {2, 4, 8} vs the non-speculative
    packed baseline, same weights, same 85%-sparsity verifier pack.

    The weights carry the tier structure the mechanism exploits — a dense
    core (top 1% of magnitudes), a detail tier (next 14%, scaled down), and
    zeros — so the 99%-sparsity drafter keeps exactly the core that drives
    most argmax decisions.  Every speculative arm's greedy tokens must be
    bit-identical to the baseline's (the accept rule guarantees it; the
    bench enforces it), so the only thing speculation can change is wall
    time.  Arms are interleaved best-of-N; tok/s is the unified accounting:
    accepted tokens / decode wall time."""
    import dataclasses

    import jax

    from repro.configs import get_smoke_config
    from repro.models import build_model
    from repro.serve import Engine, ServeConfig

    # the smoke config is too small for speculation to pay (drafter and
    # verifier dispatches cost the same at d_model=64) — widen it to where
    # the drafter's 99%-sparsity pack is genuinely cheaper per token
    cfg = dataclasses.replace(
        get_smoke_config("vusa_edge"),
        d_model=256, d_ff=1024, vocab=2048, n_heads=4, kv_heads=4,
    )

    def tiered(w):
        w = np.asarray(w)
        if w.ndim < 2:
            return w
        a = np.abs(w)
        srt = np.sort(a.ravel())[::-1]
        t1 = srt[max(int(0.01 * a.size) - 1, 0)]
        t2 = srt[max(int(0.15 * a.size) - 1, 0)]
        return np.where(a >= t1, w, np.where(a >= t2, w * 0.01, 0.0)).astype(w.dtype)

    import jax.tree_util as jtu

    params = jtu.tree_map(tiered, build_model(cfg).init(jax.random.key(0)))
    rng = np.random.default_rng(0)
    prompt = rng.integers(1, cfg.vocab, (1, 6)).astype(np.int32)  # spec = B=1
    max_new, ks = 64, (2, 4, 8)
    engines = {
        "base": Engine(cfg, params, ServeConfig(max_len=128, packed_weights="all")),
        **{
            f"k{k}": Engine(cfg, params, ServeConfig(
                max_len=128, packed_weights="all",
                speculative=True, draft_k=k, draft_sparsity=0.99,
            ))
            for k in ks
        },
    }
    outs = {}
    for name, eng in engines.items():  # compile + greedy parity check
        outs[name] = eng.generate(prompt, max_new=max_new)
        assert (outs[name]["tokens"] == outs["base"]["tokens"]).all(), (
            f"{name} speculative decode diverged from the non-speculative stream"
        )
    best = {n: 0.0 for n in engines}
    for _ in range(5):  # interleave trials so noise hits every arm alike
        for name, eng in engines.items():
            out = eng.generate(prompt, max_new=max_new)
            best[name] = max(best[name], out["tok_per_s"])
            outs[name] = out
    speedups = {k: best[f"k{k}"] / best["base"] for k in ks}
    acc = {k: outs[f"k{k}"]["acceptance_rate"] for k in ks}
    # the SLO the feature exists for: >= 1.3x accepted-tok/s at k=4 on
    # tier-structured weights (observed ~2.7x idle; 1.3 leaves co-tenant room)
    assert speedups[4] >= 1.3, (
        f"speculative k=4 speedup {speedups[4]:.2f}x below the 1.3x SLO "
        f"(acceptance {acc[4]:.2f})"
    )
    _save("bench_spec_decode", {
        "base_tok_per_s": best["base"],
        **{f"k{k}_tok_per_s": best[f"k{k}"] for k in ks},
        **{f"k{k}_speedup": speedups[k] for k in ks},
        **{f"k{k}_acceptance": float(acc[k]) for k in ks},
        "draft_sparsity": 0.99,
        "max_new": max_new,
    })
    _emit("bench_spec_decode", 1e6 / max(best["k4"], 1e-9),
          f"base_tok_s={best['base']:.0f};" +
          ";".join(f"k{k}_tok_s={best[f'k{k}']:.0f}" for k in ks) + ";" +
          ";".join(f"k{k}_speedup={speedups[k]:.2f}x" for k in ks) + ";" +
          f"k4_acc={acc[4]:.2f}")


def bench_continuous_batching():
    """Continuous-batching scheduler vs one-shot fused batches at equal slot
    count: 16 requests, ragged Poisson arrivals, ragged prompt lengths and
    budgets.  The one-shot baseline serves the same requests in FIFO batches
    of ``slots``, each batch padded to its longest budget (the padding waste
    continuous batching exists to recover).  Reports sustained useful tok/s,
    p50/p95 request latency and slot occupancy."""
    import jax

    from repro.configs import get_smoke_config
    from repro.models import build_model
    from repro.serve import Engine, Request, Scheduler, ServeConfig

    cfg = get_smoke_config("llama3_2_1b")
    params = build_model(cfg).init(jax.random.key(0))
    slots, segment, max_len = 4, 8, 160
    rng = np.random.default_rng(0)
    n_req = 24
    lens = [(4, 6, 8)[i % 3] for i in range(n_req)]
    prompts = [rng.integers(0, 100, n).astype(np.int32) for n in lens]
    # heavy-tailed budgets (most generations short, a few long) — the ragged
    # regime where one-shot batches burn the most padding
    budgets = np.minimum(4 + rng.geometric(1.0 / 24, n_req), 128)
    arrivals = np.cumsum(rng.exponential(0.0008, n_req))

    def requests(with_arrivals):
        return [
            Request(prompt=prompts[i], max_new=int(budgets[i]), seed=i,
                    arrival_s=float(arrivals[i]) if with_arrivals else 0.0)
            for i in range(n_req)
        ]

    def run_sched(sched):
        t0 = time.time()
        done = sched.run(requests(True))
        # stats() reports NaN percentiles when nothing completed (instead of
        # a fabricated 0.0 that reads as infinitely fast); the assert keeps
        # this bench from ever publishing numbers for such a hollow run
        assert len(done) == n_req, "scheduler lost requests"
        return sched.stats(), (time.time() - t0) * 1e6

    def run_baseline(eng):
        """FIFO batches of `slots`, padded to the batch max; busy time
        includes prefill, matching the scheduler's admit accounting."""
        busy_s, decoded = 0.0, 0
        for g in range(0, n_req, slots):
            idx = range(g, min(g + slots, n_req))
            batch = np.stack([
                np.pad(prompts[i], (0, max(lens[j] for j in idx) - lens[i]),
                       constant_values=1) for i in idx
            ])
            out = eng.generate(batch, max_new=int(max(budgets[i] for i in idx)))
            busy_s += out["decode_s"] + out["prefill_s"]
            decoded += sum(int(budgets[i]) - 1 for i in idx)
        return decoded / max(busy_s, 1e-9)

    sched = Scheduler(Engine(cfg, params, ServeConfig(max_len=max_len)),
                      slots=slots, segment=segment)
    eng = Engine(cfg, params, ServeConfig(max_len=max_len))
    sched.run(requests(False))  # warmup: compiles segment + per-length prefill
    run_baseline(eng)  # warmup: compiles each batch's step count
    # interleave trials so machine noise hits both systems alike
    stats, us, base_tok_s = None, 0.0, 0.0
    for _ in range(3):
        s, t = run_sched(sched)
        if stats is None or s["sustained_tok_per_s"] > stats["sustained_tok_per_s"]:
            stats, us = s, t
        base_tok_s = max(base_tok_s, run_baseline(eng))
    speedup = stats["sustained_tok_per_s"] / base_tok_s
    _save("bench_continuous_batching", {
        "sched_tok_per_s": stats["sustained_tok_per_s"],
        "oneshot_tok_per_s": base_tok_s,
        "speedup_vs_oneshot": speedup,
        "latency_p50_s": stats["latency_p50_s"],
        "latency_p95_s": stats["latency_p95_s"],
        "slot_occupancy": stats["slot_occupancy"],
        "requests": n_req,
        "slots": slots,
        "segment": segment,
        "decoded_tokens": stats["decoded_tokens"],
    })
    _emit("bench_continuous_batching", us,
          f"sched_tok_s={stats['sustained_tok_per_s']:.0f};"
          f"oneshot_tok_s={base_tok_s:.0f};speedup={speedup:.2f}x;"
          f"occ={stats['slot_occupancy']:.2f};"
          f"p50={stats['latency_p50_s'] * 1e3:.0f}ms;"
          f"p95={stats['latency_p95_s'] * 1e3:.0f}ms")


def bench_admission():
    """Bucketed batched admission vs per-request admission (DESIGN.md §6) on
    an admission-bound workload: many short ragged prompts (10 distinct
    lengths), out-of-order sub-ms arrivals, EOS-heavy early retirement.  The
    sequential arm primes one request per dispatch at its exact length; the
    batched arm coalesces each round's arrivals into one masked-prefill
    dispatch per length bucket + one multi-slot scatter.  Identical tokens
    required; sustained useful tok/s and prefill compile counts compared."""
    import jax

    from repro.configs import get_smoke_config
    from repro.models import build_model
    from repro.serve import Engine, Request, Scheduler, ServeConfig

    cfg = get_smoke_config("llama3_2_1b")
    params = build_model(cfg).init(jax.random.key(0))
    slots, segment, max_len = 4, 4, 64
    rng = np.random.default_rng(0)
    n_req = 32
    lens = [3 + i % 10 for i in range(n_req)]  # 10 distinct lengths, all short
    prompts = [rng.integers(0, 100, n).astype(np.int32) for n in lens]
    arrivals = rng.permutation(np.linspace(0, 0.002, n_req))  # out of submit order
    # EOS-heavy: a third of the requests stop early on a token they really emit
    ref = Engine(cfg, params, ServeConfig(max_len=max_len))  # greedy: seed unused
    eos_ids = {}
    for i in range(0, n_req, 3):
        eos_ids[i] = int(ref.generate(prompts[i][None], max_new=8)["tokens"][0, 2])

    def requests():
        return [
            Request(prompt=prompts[i], max_new=8, eos_id=eos_ids.get(i), seed=i,
                    arrival_s=float(arrivals[i]))
            for i in range(n_req)
        ]

    stats, scheds = {}, {}
    for mode in ("sequential", "batched"):
        sched = Scheduler(Engine(cfg, params, ServeConfig(max_len=max_len)),
                          slots=slots, segment=segment, admission=mode)
        scheds[mode] = sched
        done = sched.run(requests())  # warmup: compiles every program the mode needs
        tokens = {rid: c.tokens for rid, c in done.items()}
        best = None
        for _ in range(3):
            done = sched.run(requests())
            assert len(done) == n_req, "scheduler lost requests"
            s = sched.stats()
            if best is None or s["sustained_tok_per_s"] > best["sustained_tok_per_s"]:
                best = s
        stats[mode] = best
        stats[mode]["tokens"] = tokens
    for rid in range(n_req):  # batching must not change a single token
        np.testing.assert_array_equal(stats["batched"]["tokens"][rid],
                                      stats["sequential"]["tokens"][rid])
    b, s = stats["batched"], stats["sequential"]
    speedup = b["sustained_tok_per_s"] / s["sustained_tok_per_s"]
    compiles = {
        "batched": scheds["batched"].eng._prefill_masked._cache_size(),
        "sequential": scheds["sequential"].eng._prefill._cache_size(),
    }
    _save("bench_admission", {
        "batched_tok_per_s": b["sustained_tok_per_s"],
        "sequential_tok_per_s": s["sustained_tok_per_s"],
        "speedup_vs_sequential": speedup,
        "batched_admit_s": b["admit_s"],
        "sequential_admit_s": s["admit_s"],
        "prefill_compiles_batched": compiles["batched"],
        "prefill_compiles_sequential": compiles["sequential"],
        "requests": n_req,
        "slots": slots,
        "segment": segment,
    })
    _emit("bench_admission", b["admit_s"] * 1e6,
          f"batched_tok_s={b['sustained_tok_per_s']:.0f};"
          f"sequential_tok_s={s['sustained_tok_per_s']:.0f};"
          f"speedup={speedup:.2f}x;"
          f"compiles={compiles['batched']}vs{compiles['sequential']};"
          f"admit_s={b['admit_s']:.3f}vs{s['admit_s']:.3f}")


def bench_faults():
    """Goodput under faults (DESIGN.md §9): the same request stream served
    clean and with a seeded ~1% request-fault rate (slot-cache NaN poisoning
    — the guard + bounded dense-retry path).  Every faulted-arm completion
    must be OK or FAILED_FALLBACK_OK with tokens bit-identical to the clean
    arm, and sustained delivered tok/s must hold >= 90% of the clean run:
    recovery costs one re-prime + re-decode of the afflicted request, never
    a stall of the pool."""
    import jax

    from repro.configs import get_smoke_config
    from repro.models import build_model
    from repro.serve import Engine, FaultConfig, Request, Scheduler, ServeConfig, Status

    cfg = get_smoke_config("llama3_2_1b")
    params = build_model(cfg).init(jax.random.key(0))
    slots, segment, max_len = 4, 4, 64
    n_req = 64
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 100, 6).astype(np.int32) for _ in range(n_req)]

    def requests():
        return [
            Request(prompt=prompts[i], max_new=24, seed=i) for i in range(n_req)
        ]

    # rids keep incrementing across the warmup + 3 timed runs (the scheduler
    # is reused to keep its compiled programs); seed 17 at this rate faults
    # exactly one rid in every 64-rid block, so each run serves a ~1%
    # request-fault rate
    arms = {"clean": None, "faulted": FaultConfig(seed=17, cache_nan_rate=0.013)}
    stats, tokens = {}, {}
    for arm, faults in arms.items():
        sched = Scheduler(
            Engine(cfg, params, ServeConfig(max_len=max_len, faults=faults)),
            slots=slots, segment=segment,
        )
        done = sched.run(requests())  # warmup: compiles + first fault/retry
        best = None
        for _ in range(3):
            done = sched.run(requests())
            assert len(done) == n_req, "scheduler lost requests"
            s = sched.stats()
            if best is None or s["sustained_tok_per_s"] > best["sustained_tok_per_s"]:
                best = s
        stats[arm] = best
        # rids run on across runs; rid % n_req recovers the prompt index
        tokens[arm] = {rid % n_req: c.tokens for rid, c in done.items()}
        for rid, c in done.items():
            assert c.status in (Status.OK, Status.FAILED_FALLBACK_OK), (
                f"{arm}: rid {rid} finished {c.status}"
            )
    n_fallback = stats["faulted"]["fallback"]
    assert n_fallback >= 1, "fault plan injected nothing — bench is vacuous"
    for rid in range(n_req):  # faults must never corrupt delivered tokens
        np.testing.assert_array_equal(tokens["faulted"][rid], tokens["clean"][rid])
    ratio = (
        stats["faulted"]["sustained_tok_per_s"] / stats["clean"]["sustained_tok_per_s"]
    )
    assert ratio >= 0.9, f"goodput under faults collapsed: {ratio:.2f}x of clean"
    _save("bench_faults", {
        "clean_tok_per_s": stats["clean"]["sustained_tok_per_s"],
        "faulted_tok_per_s": stats["faulted"]["sustained_tok_per_s"],
        "goodput_ratio": ratio,
        "fallback_requests": n_fallback,
        "requests": n_req,
        "slots": slots,
        "segment": segment,
    })
    _emit("bench_faults", stats["faulted"]["decode_s"] * 1e6,
          f"clean_tok_s={stats['clean']['sustained_tok_per_s']:.0f};"
          f"faulted_tok_s={stats['faulted']['sustained_tok_per_s']:.0f};"
          f"goodput={ratio:.3f};fallbacks={n_fallback}")


def bench_paged_serving():
    """Paged KV pool vs the slot pool (DESIGN.md §11) on mixed traffic: long
    prompts with short generation budgets interleaved with short chatty
    requests — the regime where the slot pool's max_len-per-slot reservation
    burns the most HBM.  Both pools must emit bit-identical tokens; gated are
    paged sustained tok/s (conservative floor) and the time-averaged
    HBM-bytes-per-active-request reduction, which must hold >= 2x (asserted
    here too, so a lazy-allocation regression fails the bench before the
    gate).  A repeat pass of the same prompts measures the prefix-cache hit
    rate and COW splits (deterministic, reported not gated)."""
    import jax

    from repro.configs import get_smoke_config
    from repro.models import build_model
    from repro.serve import Engine, Request, Scheduler, ServeConfig

    cfg = get_smoke_config("llama3_2_1b")
    params = build_model(cfg).init(jax.random.key(0))
    slots, segment, max_len, page = 4, 8, 256, 16
    n_req = 16
    rng = np.random.default_rng(0)
    # even rids: long document prompts, short budgets; odd rids: short chat
    # prompts, longer budgets — nobody comes close to max_len rows
    lens = [int(rng.integers(64, 121)) if i % 2 == 0 else int(rng.integers(4, 9))
            for i in range(n_req)]
    budgets = [int(rng.integers(8, 17)) if i % 2 == 0 else int(rng.integers(16, 33))
               for i in range(n_req)]
    prompts = [rng.integers(1, 100, n).astype(np.int32) for n in lens]

    def requests():
        return [Request(prompt=prompts[i], max_new=budgets[i], seed=i)
                for i in range(n_req)]

    arms = {
        "slot": ServeConfig(max_len=max_len),
        "paged": ServeConfig(max_len=max_len, page_size=page),
    }
    stats, tokens, scheds = {}, {}, {}
    for arm, sc in arms.items():
        sched = Scheduler(Engine(cfg, params, sc), slots=slots, segment=segment)
        scheds[arm] = sched
        done = sched.run(requests())  # warmup: compiles segment + prefills
        tokens[arm] = {rid % n_req: c.tokens for rid, c in done.items()}
        best = None
        for _ in range(3):
            done = sched.run(requests())
            assert len(done) == n_req, "scheduler lost requests"
            s = sched.stats()
            if best is None or s["sustained_tok_per_s"] > best["sustained_tok_per_s"]:
                best = s
        stats[arm] = best
    for rid in range(n_req):  # paging must not change a single token
        np.testing.assert_array_equal(tokens["paged"][rid], tokens["slot"][rid])
    hbm_slot = stats["slot"]["hbm_bytes_per_active_request"]
    hbm_paged = stats["paged"]["hbm_bytes_per_active_request"]
    reduction = hbm_slot / hbm_paged
    assert reduction >= 2.0, (
        f"paged pool only cut HBM/request {reduction:.2f}x (< 2x): lazy "
        "allocation is broken or the traffic mix degenerated"
    )
    # repeat pass: identical prompts → prefix hits skip re-prefill entirely
    done = scheds["paged"].run(requests())
    assert len(done) == n_req
    rs = scheds["paged"].stats()
    _save("bench_paged_serving", {
        "paged_tok_per_s": stats["paged"]["sustained_tok_per_s"],
        "slot_tok_per_s": stats["slot"]["sustained_tok_per_s"],
        "hbm_bytes_per_req_paged": hbm_paged,
        "hbm_bytes_per_req_slot": hbm_slot,
        "hbm_reduction_vs_slot": reduction,
        "prefix_hit_rate_repeat": rs["prefix_hit_rate"],
        "cow_copies_repeat": rs["cow_copies"],
        "arena_bytes": stats["paged"]["kv_pool_bytes"],
        "block_bytes": stats["paged"]["kv_block_bytes"],
        "requests": n_req,
        "slots": slots,
        "segment": segment,
        "page_size": page,
    })
    _emit("bench_paged_serving", stats["paged"]["decode_s"] * 1e6,
          f"paged_tok_s={stats['paged']['sustained_tok_per_s']:.0f};"
          f"slot_tok_s={stats['slot']['sustained_tok_per_s']:.0f};"
          f"hbm_per_req={hbm_paged / 2**10:.0f}KiBvs{hbm_slot / 2**10:.0f}KiB;"
          f"reduction={reduction:.2f}x;"
          f"repeat_hit_rate={rs['prefix_hit_rate']:.2f}")


def bench_streaming():
    """Async streaming serving (DESIGN.md §12): delivered tok/s through the
    AsyncEngine's per-sync token streams, goodput under seeded transient
    decode stalls with the watchdog armed (must hold >= 0.9x of the clean
    arm — asserted here and gated), and crash recovery: a journaled run
    killed mid-stream must recover to completions bit-identical to the
    clean arm (asserted; replay wall time reported, compile included).

    The scheduler is reused across reps to keep its compiled programs, so
    each rep pins a fresh rid block (rids never reuse) — the transient-stall
    injectors are one-shot per rid and must fire in the *timed* rep, not be
    used up by the warmup."""
    import asyncio
    import tempfile

    import jax

    from repro.configs import get_smoke_config
    from repro.models import build_model
    from repro.serve import (
        AsyncEngine,
        Engine,
        FaultConfig,
        Journal,
        JournalTap,
        Request,
        Scheduler,
        ServeConfig,
        Status,
    )
    from repro.serve.journal import recover_into, replay

    cfg = get_smoke_config("llama3_2_1b")
    params = build_model(cfg).init(jax.random.key(0))
    slots, segment, max_len = 4, 8, 64
    n_req, max_new = 96, 48
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 100, 6).astype(np.int32) for _ in range(n_req)]

    def requests():
        return [
            Request(prompt=prompts[i], max_new=max_new, seed=i) for i in range(n_req)
        ]

    async def serve(engine, rid0):
        """Submit one rid block and stream every token back; returns
        ({index: tokens}, delivered token count, wall seconds)."""
        t0 = time.perf_counter()
        streams = [engine.submit(r, rid=rid0 + i) for i, r in enumerate(requests())]
        outs, total = {}, 0
        for i, s in enumerate(streams):
            toks = [t async for t in s]
            comp = await s.completion()
            assert comp.status is Status.OK, f"rid {comp.rid} finished {comp.status}"
            assert toks == [int(t) for t in comp.tokens]
            outs[i] = toks
            total += len(toks)
        return outs, total, time.perf_counter() - t0

    def stall_plan(rid0):
        # three deterministic one-shot 2 ms stalls per rep — a transient
        # wedge the pool must absorb, sized a few percent of the clean wall
        # so >= 0.9x goodput is headroom, not luck
        return FaultConfig(
            decode_stall_s=0.002,
            decode_stall_rids=(rid0 + 5, rid0 + 23, rid0 + 41),
        )

    arms = ("clean", "stalled")
    engines = {
        arm: Engine(cfg, params, ServeConfig(max_len=max_len)) for arm in arms
    }
    scheds = {
        arm: Scheduler(engines[arm], slots=slots, segment=segment) for arm in arms
    }
    tokens, best = {}, {}
    rid0 = 0

    async def one_rep(arm):
        nonlocal rid0
        block, rid0 = rid0, rid0 + n_req
        if arm == "stalled":
            engines[arm].sc.faults = stall_plan(block)
        engine = AsyncEngine(
            scheds[arm], watchdog_s=None if arm == "clean" else 10.0
        )
        async with engine:
            outs, total, wall = await serve(engine, block)
        assert total == n_req * max_new
        if arm == "stalled":
            fired = [r for r in stall_plan(block).decode_stall_rids
                     if r in scheds[arm]._stall_fired]
            assert len(fired) == 3, "stall plan injected nothing"
        return outs, total / wall

    for arm in arms:  # warmup rep per arm (compiles) — untimed
        tokens[arm], _ = asyncio.run(one_rep(arm))
    # interleave the timed reps so host noise (GC pauses, scheduler jitter a
    # few hundred ms wide on shared runners) hits both arms alike; best-of-4
    # per arm makes the ratio a property of the stalls, not the noise
    for _ in range(4):
        for arm in arms:
            tokens[arm], rate = asyncio.run(one_rep(arm))
            best[arm] = max(best.get(arm, 0.0), rate)
    for i in range(n_req):  # stalls delay tokens, never change them
        np.testing.assert_array_equal(tokens["stalled"][i], tokens["clean"][i])
    goodput = best["stalled"] / best["clean"]
    assert goodput >= 0.9, f"goodput under stalls collapsed: {goodput:.2f}x of clean"

    # crash + recover differential: journal a run, kill it mid-stream (the
    # exception fires before the sync's tap, so everything past the last
    # fsync is lost), recover into a fresh scheduler, require bit-parity
    class _Boom(Exception):
        pass

    with tempfile.TemporaryDirectory() as td:
        path = Path(td) / "journal"
        journal = Journal(path)
        tap = JournalTap(journal)
        sched = scheds["clean"]  # warm programs; crashed state is discarded
        for i, r in enumerate(requests()):
            tap.note_submit(sched.submit(r, rid=rid0 + i), r)
        journal.sync()
        syncs = 0

        def crash(s):
            nonlocal syncs
            syncs += 1
            if syncs > 3:
                raise _Boom()
            tap.on_sync(s)

        try:
            sched.run(on_sync=crash)
            raise AssertionError("crash hook never fired")
        except _Boom:
            pass
        journal._fh.close()  # no close marker: the journal reads as a crash
        t0 = time.perf_counter()
        sched2 = Scheduler(engines["clean"], slots=slots, segment=segment)
        journal2, completed, recovered = recover_into(path, sched2)
        tap2 = JournalTap(journal2)
        done = sched2.run(on_sync=tap2.on_sync)
        tap2.on_sync(sched2)
        journal2.close()
        recovery_wall = time.perf_counter() - t0
        assert recovered, "crash landed after the run finished — nothing recovered"
        merged = {**completed, **done}
        for i in range(n_req):
            np.testing.assert_array_equal(
                merged[rid0 + i].tokens, tokens["clean"][i]
            )
        final = replay(path)
        assert final.closed and not final.pending

    _save("bench_streaming", {
        "stream_tok_per_s": best["clean"],
        "stalled_tok_per_s": best["stalled"],
        "stall_goodput": goodput,
        "recovered_requests": len(recovered),
        "journal_completions": len(completed),
        "recovery_wall_s": recovery_wall,
        "requests": n_req,
        "max_new": max_new,
        "slots": slots,
        "segment": segment,
    })
    _emit("bench_streaming", (n_req * max_new / best["clean"]) * 1e6,
          f"stream_tok_s={best['clean']:.0f};stalled_tok_s={best['stalled']:.0f};"
          f"goodput={goodput:.3f};recovered={len(recovered)};"
          f"recovery_s={recovery_wall:.2f}")


_SHARDED_BENCH_CODE = """
import json, time
import jax, numpy as np
from repro.configs import get_smoke_config
from repro.core.pruning import prune_tree
from repro.models import build_model
from repro.serve import Engine, ServeConfig
from repro.launch.mesh import make_serve_mesh

cfg = get_smoke_config("vusa_edge")
params = prune_tree(build_model(cfg).init(jax.random.key(0)), 0.85)
prompts = np.ones((4, 6), np.int32)
max_new = 48
sc = dict(max_len=64, packed_weights="all", vusa_m=32, vusa_a=8)
engines = {
    "single": Engine(cfg, params, ServeConfig(**sc)),
    "dp": Engine(cfg, params, ServeConfig(**sc), mesh=make_serve_mesh("2,1")),
    "tp": Engine(cfg, params, ServeConfig(**sc), mesh=make_serve_mesh("1,2")),
    "dp_tp": Engine(cfg, params, ServeConfig(**sc), mesh=make_serve_mesh("2,4")),
}
toks = {}
for name, eng in engines.items():  # compile + parity check
    toks[name] = eng.generate(prompts, max_new=max_new)["tokens"]
    assert (toks[name] == toks["single"]).all(), name + " decode diverged from single-device"
best = {n: 0.0 for n in engines}
for _ in range(4):  # interleave trials so noise hits every arm alike
    for name, eng in engines.items():
        best[name] = max(best[name], eng.generate(prompts, max_new=max_new)["tok_per_s"])
print("RESULT " + json.dumps(best))
"""


def bench_sharded_decode():
    """Mesh-sharded whole-model packed decode vs the single-device engine on
    a forced 8-device CPU backend (DESIGN.md §8): 2x1 (DP), 1x2 (TP) and 2x4
    meshes must emit bit-identical tokens, throughput reported per arm.

    Runs in a subprocess with its own XLA_FLAGS: the device count is fixed at
    backend init, and forcing 8 host devices on the *parent* process would
    perturb every other bench's numbers (they share the committed baselines).
    On virtual CPU devices the collectives are pure overhead — the gated
    floor guards the sharded path *working and not collapsing*, the real
    speedup story needs real chips."""
    import os
    import subprocess
    import sys
    from pathlib import Path as _P

    t0 = time.time()
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-c", _SHARDED_BENCH_CODE],
        capture_output=True, text=True, timeout=1200, env=env,
        cwd=str(_P(__file__).resolve().parent.parent),
    )
    assert out.returncode == 0, out.stderr[-3000:]
    line = [ln for ln in out.stdout.splitlines() if ln.startswith("RESULT ")][-1]
    best = json.loads(line[len("RESULT "):])
    us = (time.time() - t0) * 1e6
    table = {
        "single_tok_per_s": best["single"],
        "dp_tok_per_s": best["dp"],
        "tp_tok_per_s": best["tp"],
        "dp_tp_tok_per_s": best["dp_tp"],
        "tp_vs_single": best["tp"] / max(best["single"], 1e-9),
        "devices": 8,
        "meshes": ["2,1", "1,2", "2,4"],
    }
    _save("bench_sharded_decode", table)
    _emit("bench_sharded_decode", us,
          f"single_tok_s={best['single']:.0f};dp_tok_s={best['dp']:.0f};"
          f"tp_tok_s={best['tp']:.0f};dp_tp_tok_s={best['dp_tp']:.0f};"
          "parity=identical")


def bench_scheduler():
    from repro.core.vusa import schedule_widths_fast

    rng = np.random.default_rng(0)
    mask = rng.random((4608, 512)) > 0.85
    t0 = time.time()
    hist, jobs = schedule_widths_fast(mask, *VUSA)
    us = (time.time() - t0) * 1e6
    cols_per_s = mask.size / (us / 1e6)
    _emit("bench_scheduler", us, f"elements_per_s={cols_per_s:.3g};jobs={sum(jobs)}")


def bench_train_decode():
    import jax
    import jax.numpy as jnp

    from repro.configs import get_smoke_config
    from repro.models import build_model
    from repro.optim import adamw_init
    from repro.train.step import TrainHParams, make_train_step

    cfg = get_smoke_config("llama3_2_1b")
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    batch = {"tokens": jnp.ones((4, 64), jnp.int32)}
    step = jax.jit(make_train_step(model.loss, TrainHParams()))
    opt = adamw_init(params)
    params2, opt2, m = step(params, opt, batch)
    jax.block_until_ready(m["loss"])
    t0 = time.time()
    for _ in range(5):
        params2, opt2, m = step(params2, opt2, batch)
        jax.block_until_ready(m["loss"])
    tt = (time.time() - t0) / 5 * 1e6

    cache = model.init_cache(4, 128)
    dec = jax.jit(model.decode_step)
    tok = jnp.ones((4, 1), jnp.int32)
    _, cache = dec(params, tok, cache)
    t0 = time.time()
    for _ in range(20):
        logits, cache = dec(params, tok, cache)
    jax.block_until_ready(logits)
    td = (time.time() - t0) / 20 * 1e6
    _emit("bench_train_step", tt, "smoke llama 4x64")
    _emit("bench_decode_step", td, "smoke llama batch4")


def table_lm_vusa():
    """Beyond-paper: the paper's Table-II methodology applied to the LM we
    actually trained to 85% sparsity (examples/train_sparse_lm.py) — VUSA
    efficiency on transformer GEMMs instead of CNN im2col GEMMs."""
    import numpy as np
    from pathlib import Path

    from repro.checkpoint import latest_step, restore
    from repro.configs import get_config
    from repro.core.simulator import Gemm
    from repro.models import build_model

    ck = Path("experiments/train_run/ckpt")
    step = latest_step(ck) if ck.exists() else None
    t0 = time.time()
    cfg = get_config("vusa_edge")
    if step is None:  # no trained run available: prune random init instead
        import jax
        from repro.core.pruning import prune_tree

        params = prune_tree(build_model(cfg).init(jax.random.key(0)), cfg.sparsity)
        src = "random-init pruned"
    else:
        import jax

        model = build_model(cfg)
        like = {"params": model.init(jax.random.key(0))}
        params = restore(ck, step, like)["params"]
        src = f"trained ckpt step {step}"

    # every pruned matmul becomes a GEMM job streamed over the batch dim
    gemms, masks = [], []
    seq = 64
    layers = params["layers"]
    for name in ("w_gate", "w_up", "w_down"):
        w = np.asarray(layers["ffn"][name])
        for l in range(cfg.n_layers):
            gemms.append(Gemm(B=seq, K=w.shape[1], C=int(np.prod(w.shape[2:])), name=f"{name}{l}"))
            masks.append(np.asarray(w[l]).reshape(w.shape[1], -1) != 0)
    table = _evaluate_model(gemms, masks, "vusa_edge_lm")
    us = (time.time() - t0) * 1e6
    _save("table_lm_vusa", {**table, "weights": src})
    v = table["vusa_3x6"]
    _emit("table_lm_vusa", us,
          f"src={src.replace(' ', '_')};pp_area={v['perf_per_area']:.2f};"
          f"pp_power={v['perf_per_power']:.2f};energy={v['energy']:.2f};"
          f"load6={v['load_split'][6]:.3f}")


# ---------------------------------------------------------------------------


BENCHES = {
    "fig6_growth": fig6_growth,
    "table1_area_power": table1_area_power,
    "table2_resnet18": table2_resnet18,
    "table3_mobilenet": table3_mobilenet,
    "fig89_pruning_sweep": fig89_pruning_sweep,
    "table_lm_vusa": table_lm_vusa,
    "kernel_vusa_packed": kernel_vusa_packed,
    "bench_scheduler": bench_scheduler,
    "bench_train_decode": bench_train_decode,
    "bench_decode_fused": bench_decode_fused,
    "bench_packed_decode": bench_packed_decode,
    "bench_spec_decode": bench_spec_decode,
    "bench_continuous_batching": bench_continuous_batching,
    "bench_admission": bench_admission,
    "bench_faults": bench_faults,
    "bench_paged_serving": bench_paged_serving,
    "bench_streaming": bench_streaming,
    "bench_sharded_decode": bench_sharded_decode,
}

# Metrics protected by the CI regression gate.  All are higher-is-better;
# "/" indexes into the bench's saved JSON table.  Throughput baselines are
# machine-relative — regenerate with --write-baseline when the runner class
# changes (CI uploads the fresh JSON as an artifact for exactly that).  In
# the committed BENCH_BASELINE.json, high-variance entries (absolute tok/s,
# and the fused-vs-seed speedup whose host-loop arm is dispatch-bound)
# record a conservative noise floor (~0.85x of a best-of-N measurement) so
# run-to-run variance does not trip the gate while a real perf loss still
# does; the interleaved ratios (speedup_vs_oneshot, kernel_speedup) are
# stable and committed as measured.  Both bench_admission entries are such
# floors (its sequential arm is dispatch-bound and the noisiest measurement
# here): a structural loss of admission batching still lands well below
# them, while scheduler-level jitter does not.  bench_packed_decode's three
# entries are likewise conservative floors of idle best-of-N measurements
# (fused_speedup observed 1.26-1.50x idle, committed 1.25, gate floor
# 0.94 at the CI-wide 0.25 tolerance): the floor catches an *inversion* —
# the megakernel running slower than the 3-dispatch path it replaces —
# while co-tenant noise (observed down to 1.11 under load) does not trip
# it; a mere loss of the fused advantage to ~1.0x needs the idle-machine
# bench run, not CI, to show up.
BASELINE_METRICS = {
    "bench_decode_fused": ["fused_tok_per_s", "speedup"],
    "kernel_vusa_packed": ["sparsity_0.85/kernel_speedup"],
    # the quantized arms' tok/s floors sit beside the bf16 whole-model floor:
    # fused dequant must not cost the packed path its throughput (correctness
    # and byte ratios are asserted inside the bench itself)
    "bench_packed_decode": [
        "fused_tok_per_s", "fused_speedup", "whole_tok_per_s",
        "int8_tok_per_s", "int4_tok_per_s",
    ],
    # self-speculative decoding (§13): the k=4 speedup baseline holds the
    # 1.3x SLO the bench itself asserts (observed ~2.7x idle), so the gate
    # also sees the speculative advantage collapsing; the tok/s entry is a
    # conservative machine-relative floor like the other throughput gates
    "bench_spec_decode": ["k4_speedup", "k4_tok_per_s"],
    "bench_continuous_batching": ["sched_tok_per_s", "speedup_vs_oneshot"],
    "bench_admission": ["batched_tok_per_s", "speedup_vs_sequential"],
    # sharded decode on 8 forced CPU devices: collectives are pure overhead
    # there, so the gate holds a conservative tok/s floor per mesh arm (DP,
    # TP, and DP x TP) — it catches the sharded path breaking or collapsing
    # (e.g. an accidental all-gather of the weights per step), not CPU
    # "speedups"
    "bench_sharded_decode": ["dp_tok_per_s", "tp_tok_per_s", "dp_tp_tok_per_s"],
    # goodput under a ~1% seeded request-fault rate: the ratio is the SLO
    # (>= 0.9 asserted in-bench; the committed baseline holds 0.9 so the
    # gate also sees a drop), faulted_tok_per_s is a conservative floor
    "bench_faults": ["goodput_ratio", "faulted_tok_per_s"],
    # paged pool (§11): tok/s is a conservative floor; the HBM-per-request
    # reduction is a deterministic allocation ratio (no timing in it) — the
    # committed baseline holds the 2.0 SLO the bench itself asserts, so the
    # gate also sees lazy allocation regressing
    "bench_paged_serving": ["paged_tok_per_s", "hbm_reduction_vs_slot"],
    # async streaming (§12): delivered tok/s is a conservative floor; the
    # stall-goodput ratio is the SLO (>= 0.9 asserted in-bench, and the
    # committed baseline holds 0.9 so the gate also sees a drop)
    "bench_streaming": ["stream_tok_per_s", "stall_goodput"],
}


def _lookup(table, path: str):
    for part in path.split("/"):
        table = table[part]
    return float(table)


def write_baseline(path: str) -> None:
    """Snapshot the gated metrics of the benches that just ran."""
    base = {
        name: {m: _lookup(RESULTS[name], m) for m in metrics}
        for name, metrics in BASELINE_METRICS.items()
        if name in RESULTS
    }
    Path(path).write_text(json.dumps(base, indent=1) + "\n")
    print(f"wrote baseline for {list(base)} to {path}")


GATE_ROWS = []  # (bench, metric, baseline, fresh, status) — for --summary-md


def check_against(path: str, tolerance: float) -> bool:
    """Compare the benches that just ran against a committed baseline.
    A metric regresses when fresh < baseline * (1 - tolerance).  Returns
    True when everything held."""
    base = json.loads(Path(path).read_text())
    ok = True
    GATE_ROWS.clear()
    for name, metrics in base.items():
        if name not in RESULTS:
            # a gated bench that silently stops running is itself a
            # regression — the gate must not go green while blind
            print(f"gate: {name} MISSING (baseline-gated but not run)")
            GATE_ROWS.append((name, "*", None, None, "MISSING"))
            ok = False
            continue
        for metric, ref in metrics.items():
            try:
                fresh = _lookup(RESULTS[name], metric)
            except (KeyError, TypeError):
                # the bench ran but no longer reports a gated metric — name
                # it instead of crashing (or silently passing): a metric the
                # baseline protects must exist in every fresh run
                print(f"gate: {name}.{metric} MISSING (gated metric absent "
                      f"from the fresh {name} results)")
                GATE_ROWS.append((name, metric, ref, None, "MISSING"))
                ok = False
                continue
            floor = ref * (1.0 - tolerance)
            status = "ok" if fresh >= floor else "REGRESSION"
            if fresh < floor:
                ok = False
            print(f"gate: {name}.{metric} = {fresh:.3f} vs baseline {ref:.3f}"
                  f" (floor {floor:.3f}) {status}")
            GATE_ROWS.append((name, metric, ref, fresh, status))
    # inverse check: every declared gated metric of a bench that ran must be
    # in the baseline file, else newly added metrics silently go unprotected
    for name, metrics in BASELINE_METRICS.items():
        if name not in RESULTS:
            continue
        if name not in base:
            print(f"gate: {name} UNGATED (ran, declared in BASELINE_METRICS, "
                  f"but absent from {path} — regenerate with --write-baseline)")
            GATE_ROWS.append((name, "*", None, None, "UNGATED"))
            ok = False
            continue
        for metric in metrics:
            if metric not in base[name]:
                print(f"gate: {name}.{metric} UNGATED (declared in "
                      f"BASELINE_METRICS but absent from {path} — "
                      f"regenerate with --write-baseline)")
                GATE_ROWS.append((name, metric, None, None, "UNGATED"))
                ok = False
    return ok


def write_summary_md(path: str) -> None:
    """Render the gate comparison as a GitHub-flavored markdown table —
    CI cats this into ``$GITHUB_STEP_SUMMARY`` so the fresh-vs-baseline
    numbers are readable on the job page without digging through logs."""

    def fmt(v):
        return "—" if v is None else f"{v:.3f}"

    lines = [
        "### Bench gate: fresh vs committed baseline",
        "",
        "| bench | metric | baseline | fresh | delta | status |",
        "|---|---|---:|---:|---:|---|",
    ]
    for name, metric, ref, fresh, status in GATE_ROWS:
        delta = (
            f"{(fresh - ref) / ref * 100:+.1f}%"
            if ref not in (None, 0) and fresh is not None else "—"
        )
        mark = {"ok": "✅", "REGRESSION": "❌", "MISSING": "❌", "UNGATED": "❌"}[status]
        lines.append(
            f"| {name} | {metric} | {fmt(ref)} | {fmt(fresh)} | {delta} | {mark} {status} |"
        )
    if not GATE_ROWS:
        lines.append("| _no gated benches ran_ | | | | | |")
    Path(path).write_text("\n".join(lines) + "\n")
    print(f"wrote gate summary to {path}")


def main(argv=None) -> None:
    """Run all benchmarks, or only the ones named on the command line
    (``python benchmarks/run.py kernel_vusa_packed bench_decode_fused``).
    ``--check-against BENCH_BASELINE.json --tolerance 0.25`` turns the run
    into a regression gate; ``--write-baseline`` refreshes the snapshot."""
    import argparse
    import sys

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("names", nargs="*",
                    help="benchmarks to run (default: all)")
    ap.add_argument("--check-against", metavar="FILE",
                    help="fail if gated metrics regress vs this baseline JSON")
    ap.add_argument("--tolerance", type=float, default=0.25,
                    help="allowed fractional regression (default 0.25)")
    ap.add_argument("--write-baseline", metavar="FILE",
                    help="write a fresh baseline JSON after the run")
    ap.add_argument("--summary-md", metavar="FILE",
                    help="with --check-against: also write the gate table as "
                    "markdown (for $GITHUB_STEP_SUMMARY)")
    args = ap.parse_args(argv)
    names = args.names or list(BENCHES)
    unknown = [n for n in names if n not in BENCHES]
    if unknown:
        ap.error(f"unknown benchmarks {unknown}; known: {list(BENCHES)}")
    print("name,us_per_call,derived")
    for n in names:
        BENCHES[n]()
    if args.write_baseline:
        write_baseline(args.write_baseline)
    if args.check_against:
        held = check_against(args.check_against, args.tolerance)
        if args.summary_md:
            # write the table even on failure — the job summary is most
            # valuable exactly when the gate trips
            write_summary_md(args.summary_md)
        if not held:
            sys.exit(1)


if __name__ == "__main__":
    main()
