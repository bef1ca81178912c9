"""Smoke test on the chip: serve ``vusa_edge`` at its published widths.

    python chip_smoke.py                    # one chip: bf16, int8 and int4 packs
    python chip_smoke.py --chips 4          # sharded serving, meshes 1,4 and 2,2

One process, one JAX import.  The model is ``vusa_edge`` (12 layers, d_model
768, 12 heads, d_ff 3072, vocab 32000, bf16) with random weights from
``--seed``, magnitude-pruned to 85 % and served through ``Engine`` +
``Scheduler`` with every decode-step weight VUSA-packed
(``packed_weights="all"``), the fused packed-MLP megakernel and the paged KV
pool.  Eight requests with prompt lengths drawn from ``--seed`` in 64-512
each decode 32 greedy tokens; every one must finish ``OK`` on the packed path.

Correctness is checked on the tokens the Scheduler served.  Each served token
is scored by a plain float32 reference -- the dense model on the weights the
pack holds (quantize-dequantize weights for int8/int4), run teacher-forced on
the prompt and the served tokens before it, under ``highest`` matmul
precision.  A token's regret is how far its reference logit falls short of the
reference's largest, over the largest magnitude.  With random weights the bf16
model is chaotic in depth (PERF.md: one step's error grows from 5e-2 at 2
layers to about 1 at 12, dense as much as packed), so regret is asserted on
a cut of the model to its first ``CUT_LAYERS`` layers at full width, served
the same way, dense included.  At full depth every mode must serve OK, and
its greedy-token agreement with the dense engine is reported.  Also
asserted: layer 0's fused MLP and the LM head through the compiled kernels
against their float32 oracles, and a ``tpu_custom_call`` in the engine's
compiled decode step (no Pallas kernel may run in interpret mode).

``--chips 4`` runs only the sharded path and what it is compared with: the
cut served on one device and on meshes ``1,4`` and ``2,2``, bf16 and int4 packs;
the regret of the served tokens, and each engine's prefill KV rows against
a float32 prefill, both asserted.

The script exits non-zero, and prints no result, when JAX finds no TPU.  The
last line of a passing run is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

ARCH = "vusa_edge"
SPARSITY = 0.85
SLOTS = 8
MAX_NEW = 32
MAX_LEN = 1024
PAGE_SIZE = 16
PROMPT_LENS = (64, 512)
MODES = ("bf16", "int8", "int4")
# int4 runs every sharded quantized path int8 does (window-sharded scales)
# plus the nibble decode, at four times the chip cost per mode
FOUR_CHIP_MODES = ("bf16", "int4")
CUT_LAYERS = 2
# Largest regret of a served token at the cut (see the module docstring).  On
# a v5e, seed 0, correct programs read 0.28 (dense bf16) and 0.14 / 0.17 /
# 0.30 (bf16 / int8 / int4 packs): bf16 activations and KV rows.  A served
# program that writes its KV rows one position off reads 0.95 (CPU, smoke
# config, where the correct one reads 0).
REGRET_TOL = 0.5
# max|got - oracle| / max|oracle| of one kernel call (layer 0's fused MLP, the
# LM head): the MXU's default precision rounds the scaled int8/int4 operands
# and the MLP's hidden state to bf16, a few 1e-3 on a v5e.
KERNEL_TOL = 1e-2
# Largest max|err| / max|reference| of a prefill's KV rows for one prompt
# against a float32 prefill of the same weights, single device and meshes
# alike.  The cut's second-layer rows of any bf16 program lie about 5e-2 from
# float32, so two correct programs that round at different points differ by
# that much: mesh 2,2 lay 7.6e-2 from the single device on a v5e.  On CPU at
# these widths: single device 5.3e-2 / 5.6e-2 (k / v), mesh 1,4 5.2e-2 /
# 5.6e-2, mesh 2,2 5.2e-2 / 6.5e-2.  Not yet measured on the chip.
KV_TOL = 0.15
KERNEL_OP = "tpu_custom_call"  # what a compiled (not interpreted) Pallas call lowers to


def make_requests(cfg, seed: int):
    from repro.serve import Request

    rng = np.random.default_rng(seed)
    lens = rng.integers(PROMPT_LENS[0], PROMPT_LENS[1] + 1, size=SLOTS)
    return [
        Request(
            prompt=rng.integers(1, cfg.vocab, size=int(n)).astype(np.int32),
            max_new=MAX_NEW, seed=seed + i,
        )
        for i, n in enumerate(lens)
    ]


def serve(cfg, params, mode, requests, mesh=None):
    """Serve ``requests`` through Engine + Scheduler; every one must finish
    OK on the configured path.  Returns (engine, tokens per request, s)."""
    from repro.serve import Engine, Scheduler, ServeConfig, Status

    packed = {} if mode == "dense" else {"packed_weights": "all", "packed_values": mode}
    eng = Engine(cfg, params, ServeConfig(max_len=MAX_LEN, page_size=PAGE_SIZE, **packed),
                 mesh=mesh)
    sched = Scheduler(eng, slots=SLOTS)
    rids = [sched.submit(r) for r in requests]
    t0 = time.perf_counter()
    done = sched.run()
    secs = time.perf_counter() - t0
    bad = {rid: c.status.value for rid, c in done.items() if c.status is not Status.OK}
    if bad or sorted(done) != sorted(rids):
        raise RuntimeError(f"{mode}: requests did not finish OK: {bad}")
    st = sched.stats()
    faults = ("rejected", "shed", "timed_out", "cancelled", "fallback", "failed",
              "quarantined", "stalled")
    if any(st[k] for k in faults):
        raise RuntimeError(f"{mode}: {dict((k, st[k]) for k in faults)}")
    if mode != "dense" and not eng.packed_active:
        raise RuntimeError(f"{mode}: the packed path is no longer active")
    tokens = [np.asarray(done[rid].tokens) for rid in rids]
    if not all(len(t) == MAX_NEW for t in tokens):
        raise RuntimeError(f"{mode}: a request delivered fewer than {MAX_NEW} tokens")
    return eng, tokens, secs


def agreement(tokens, ref) -> float:
    """Share of greedy tokens equal to the reference's, position by position."""
    return float(np.mean([np.mean(t == r) for t, r in zip(tokens, ref)]))


def rel_err(got, ref) -> float:
    """max |got - ref| over max |ref|."""
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return float(np.abs(got - ref).max() / np.abs(ref).max())


def first_layers(params, layers: int):
    """The model cut to its first ``layers`` layers (embedding and head kept)."""
    import jax

    return {**params, "layers": jax.tree.map(lambda a: a[:layers], params["layers"])}


def reference_weights(cfg, params, mode):
    """(prefill, decode) weights of the served program in float32: the
    Engine prefills on the model's own weights and decodes on the pack's,
    which for int8/int4 are their quantize-dequantize values."""
    import jax
    import jax.numpy as jnp

    from repro.serve.packed import qdq_lm_params

    params32 = jax.tree.map(lambda a: np.asarray(a, np.float32), jax.device_get(params))
    decode = params32
    if mode in MODES[1:]:
        decode = qdq_lm_params(cfg, params32, value_dtype=mode)
    return jax.tree.map(jnp.asarray, params32), jax.tree.map(jnp.asarray, decode)


@functools.cache
def scorer(cfg):
    """Jitted float32 reference of the served program, one per model cut:
    ``score(weights32, prompts, lengths, served)`` prefills each right-padded
    prompt on the prefill weights, then decodes its served tokens teacher-
    forced on the decode weights.  Returns each served token's regret and
    whether it is the reference's argmax."""
    import jax
    import jax.numpy as jnp

    from repro.models import build_model

    model = build_model(dataclasses.replace(cfg, dtype="float32"))

    def one(w, prompt, n, served):
        prefill_w, decode_w = w
        first, cache = model.prefill(prefill_w, {"tokens": prompt},
                                     PROMPT_LENS[1] + MAX_NEW, lengths=n[None])
        cache = {**cache, "pos": n}  # the padded rows past n are never attended

        def step(cache, tok):
            lg, cache = model.decode_step(decode_w, tok[None, None], cache)
            return cache, lg[0, 0]

        _, rest = jax.lax.scan(step, cache, served[:-1])
        lg = jnp.concatenate([first, rest])  # (MAX_NEW, V): the scores of each served token
        top = lg.max(-1)
        got = jnp.take_along_axis(lg, served[:, None], axis=-1)[:, 0]
        return (top - got) / jnp.abs(lg).max(-1), got == top

    return jax.jit(jax.vmap(one, in_axes=(None, 0, 0, 0)))


def regret(cfg, weights32, requests, tokens):
    """Score the served greedy tokens with the float32 reference on
    ``weights32`` = (prefill, decode) weights.  Returns (largest regret,
    share of served tokens that are the reference's argmax)."""
    import jax

    prompts = np.zeros((len(requests), 1, PROMPT_LENS[1]), np.int32)  # right-padded
    for i, r in enumerate(requests):
        prompts[i, 0, : len(r.prompt)] = r.prompt
    lengths = np.array([len(r.prompt) for r in requests], np.int32)
    with jax.default_matmul_precision("highest"):
        r, hit = scorer(cfg)(weights32, prompts, lengths, np.stack(tokens).astype(np.int32))
    return float(np.max(r)), float(np.mean(hit))


def check_kernels(cfg, eng, mode, seed: int):
    """Layer 0's fused MLP and the LM head through the compiled kernels,
    against the repo's jnp oracles of the same packs in float32, on one
    random bf16 batch.  Returns (mlp error, head error)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops
    from repro.serve.packed import _as_linear

    def linear(entry, layer=None):
        pick = (lambda a: a) if layer is None else (lambda a: a[layer])
        scales = entry.get("scales")
        return _as_linear(entry, pick(entry["values"]), pick(entry["positions"]),
                          None if scales is None else pick(scales))

    mlp = eng._packed["mlp"]
    g, u, d = (linear(mlp[n], 0) for n in ("w_gate", "w_up", "w_down_t"))
    head = linear(eng._packed["head"])
    x = jnp.asarray(np.random.default_rng(seed).standard_normal((SLOTS, cfg.d_model)),
                    jnp.bfloat16)
    got_mlp, got_head = ops.apply_fused_mlp(x, g, u, d), ops.apply_row_packed(x, head)
    x32 = x.astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        ref_mlp = ops.apply_fused_mlp_ref(x32, g, u, d)
        ref_head = ops.apply_row_packed_ref(x32, head)
    errs = rel_err(got_mlp, ref_mlp), rel_err(got_head, ref_head)
    if not all(np.isfinite(e) and e <= KERNEL_TOL for e in errs):
        raise RuntimeError(f"{mode}: kernels off their float32 oracles by {errs}")
    return errs


def check_compiled(eng, mode, prompt):
    """The engine's decode step, compiled from a primed cache, must hold a
    Pallas TPU kernel.  Returns the compile seconds."""
    import jax

    token, cache, key = eng.prime(prompt[None], jax.random.key(0))
    t0 = time.perf_counter()
    text = eng._decode.lower(eng.params, token, cache, key).compile().as_text()
    if KERNEL_OP not in text:
        raise RuntimeError(f"{mode}: the compiled decode step holds no Pallas TPU kernel")
    return time.perf_counter() - t0


def serve_and_score(cfg, params, mode, requests, ref_tokens, mesh=None):
    """Serve, then score the served tokens.  Returns (engine, tokens,
    largest regret, report line)."""
    eng, tokens, secs = serve(cfg, params, mode, requests, mesh=mesh)
    worst, hit = regret(cfg, reference_weights(cfg, params, mode), requests, tokens)
    line = (f"{len(requests)} requests OK in {secs:.1f}s  served-token regret vs float32 "
            f"{worst:.3e}, reference argmax {hit:.4f}")
    if ref_tokens is not None:
        line += f", agreement {agreement(tokens, ref_tokens):.4f}"
    return eng, tokens, worst, line


def check_regret(mode, worst, what):
    if not worst <= REGRET_TOL:
        raise RuntimeError(f"{mode} {what}: a served token's regret {worst:.3e} "
                           f"exceeds {REGRET_TOL:g}")


def build(seed: int):
    """vusa_edge with random weights from ``seed``, magnitude-pruned.
    Returns (cfg, params, init s, prune s)."""
    import jax

    from repro.configs import get_config
    from repro.core.pruning import prune_tree
    from repro.models import build_model

    cfg = get_config(ARCH)
    t0 = time.perf_counter()
    params = jax.block_until_ready(build_model(cfg).init(jax.random.key(seed)))
    t1 = time.perf_counter()
    params = jax.block_until_ready(prune_tree(params, SPARSITY))
    return cfg, params, t1 - t0, time.perf_counter() - t1


def one_chip(cfg, params, requests, seed: int):
    """Full depth: every mode serves OK, its kernels match their oracles,
    and its tokens are compared with the dense engine's.  The cut: every
    served token's regret within ``REGRET_TOL``, dense included, and the
    compiled decode step holds a Pallas kernel."""
    _, dense, _ = serve(cfg, params, "dense", requests)
    print(f"{cfg.n_layers} layers dense: {len(requests)} requests OK")
    for mode in MODES:
        eng, tokens, secs = serve(cfg, params, mode, requests)
        errs = check_kernels(cfg, eng, mode, seed)
        print(f"{cfg.n_layers} layers {mode}: {len(requests)} requests OK in {secs:.1f}s  "
              f"agreement with dense {agreement(tokens, dense):.4f}  kernel error vs "
              f"float32 oracle: fused MLP {errs[0]:.3e}, head {errs[1]:.3e} "
              f"(limit {KERNEL_TOL:g})")
    cfg = dataclasses.replace(cfg, n_layers=CUT_LAYERS)
    params = first_layers(params, CUT_LAYERS)
    dense = None
    for mode in ("dense",) + MODES:
        eng, tokens, worst, line = serve_and_score(cfg, params, mode, requests, dense)
        check_regret(mode, worst, f"{CUT_LAYERS} layers")
        line += f" (limit {REGRET_TOL:g})"
        if mode == "dense":
            dense = tokens
        else:
            compile_s = check_compiled(eng, mode, requests[0].prompt)
            line += f"  decode step holds {KERNEL_OP}, compile {compile_s:.1f}s"
        print(f"{CUT_LAYERS} layers {mode}: {line}")


def prefill_kv_error(cfg, params, eng, prompt, ref=None):
    """Largest error of ``eng``'s prefill KV rows for ``prompt`` against a
    float32 prefill of the same weights (``ref``, computed when None).
    Returns (error, ref)."""
    import jax

    from repro.models import build_model

    if ref is None:
        model = build_model(dataclasses.replace(cfg, dtype="float32"))
        weights32 = reference_weights(cfg, params, "dense")[0]
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(lambda w, t: model.prefill(w, {"tokens": t}, MAX_LEN)[1])(
                weights32, prompt)
    kv = eng.prime(prompt, jax.random.key(0))[1]
    return max(rel_err(kv[n], ref[n]) for n in ("k", "v")), ref


def four_chips(cfg, params, requests):
    """The cut, bf16 and int4 packs, on one device and on meshes 1,4 and 2,2:
    every served token's regret within ``REGRET_TOL``, and each engine's
    prefill KV rows (the same dense program in every mode) within
    ``KV_TOL`` of float32."""
    from repro.launch.mesh import make_serve_mesh

    cfg = dataclasses.replace(cfg, n_layers=CUT_LAYERS)
    params = first_layers(params, CUT_LAYERS)
    prompt = requests[0].prompt[None]
    ref = None
    for mode in FOUR_CHIP_MODES:
        single = None
        for spec in (None, "1,4", "2,2"):
            where = "single device" if spec is None else f"mesh {spec}"
            mesh = None if spec is None else make_serve_mesh(spec)
            eng, tokens, worst, line = serve_and_score(cfg, params, mode, requests, single,
                                                       mesh=mesh)
            check_regret(mode, worst, where)
            line += f" (limit {REGRET_TOL:g})"
            single = tokens if single is None else single
            if mode == FOUR_CHIP_MODES[0]:
                err, ref = prefill_kv_error(cfg, params, eng, prompt, ref)
                if not err <= KV_TOL:
                    raise RuntimeError(f"{where}: prefill KV rows off float32 by {err:.3e}")
                line += f"  prefill KV error vs float32 {err:.3e} (limit {KV_TOL:g})"
            print(f"{CUT_LAYERS} layers {mode} {where}: {line}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args()

    cache_dir = enable_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 2
    t0 = time.perf_counter()
    cfg, params, init_s, prune_s = build(args.seed)
    requests = make_requests(cfg, args.seed)
    print(f"device {dev.device_kind} x{len(devices)}  compile cache {cache_dir}")
    print(f"{ARCH}: {cfg.n_layers} layers, d_model {cfg.d_model}, {cfg.n_heads} heads, "
          f"d_ff {cfg.d_ff}, vocab {cfg.vocab}, {cfg.dtype}, sparsity {SPARSITY}; "
          f"{len(requests)} requests, prompts "
          f"{sorted(len(r.prompt) for r in requests)}, {MAX_NEW} new tokens each; "
          f"modes {','.join(FOUR_CHIP_MODES if args.chips == 4 else MODES)}; "
          f"init {init_s:.1f}s, prune {prune_s:.1f}s")
    if args.chips == 4:
        four_chips(cfg, params, requests)
    else:
        one_chip(cfg, params, requests, args.seed)
    print(f"total {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
