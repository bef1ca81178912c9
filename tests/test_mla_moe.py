"""Latent attention with sigmoid-routed held experts (Moonlight-16B-A3B's
family) at its SMOKE preset on the CPU, against the benchmark's plain
float32 reference (``benchmarks/chip/references/mla_moe_lm.py``, loaded by
path: the repository keeps one reference, independent of the program).

The program runs in float32 here with float32 attention probabilities, so
it and the reference differ only by summation order; the tolerances below
are float32 rounding over a few layers, not room for a different
computation."""

import importlib.util
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config
from repro.kernels.ops import (
    apply_fused_moe,
    apply_fused_moe_ref,
    pack_linear_rows,
    pack_linear_rows_t,
)
from repro.kernels.vusa_packed import fold_tally
from repro.models import build_model
from repro.models.cache import (
    PagedLayout,
    bind_slot_pages,
    paged_in_axes,
    paged_scatter_token,
    paged_view,
    write_prefill_pages,
)
from repro.models.layers import (
    MaskSpec,
    _mla_attend,
    held_experts,
    mla_decode,
    mla_latent,
    moe_held,
    route,
)
from repro.models.opt_flags import FLAGS
from repro.serve import Engine, Request, Scheduler, ServeConfig
from repro.serve.packed import _as_linear, _stack_packs, lm_decode_step_packed

CHIP = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
CFG = get_smoke_config("moonlight_16b_a3b")
MAX_LEN, PAGE = 64, 8
# float32 logits of unit spread through 3 layers: summation order (flash
# chunks, absorbed against decompressed attention, the kernels' window
# reconstruction) moves them by ~1e-5; 2e-4 leaves that 20x room and is far
# below what a wrong mask, rope, norm or routing rule moves (>= 1e-1)
LOGIT_TOL = 2e-4


def _reference():
    sys.path.insert(0, str(CHIP))
    try:
        path = CHIP / "references" / "mla_moe_lm.py"
        spec = importlib.util.spec_from_file_location("mla_moe_lm", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
    finally:
        sys.path.remove(str(CHIP))
    return mod


REF = _reference()


def _model_dict(cfg, **over):
    """The reference's configuration keys for a program configuration."""
    m = {k: getattr(cfg, f) for k, f in REF.PROGRAM_KEYS.items()}
    return {**m, "rms_norm_eps": 1e-6, **over}


@pytest.fixture(autouse=True)
def _fp32_attention_probs():
    prev = FLAGS["attn_bf16_probs"]
    FLAGS["attn_bf16_probs"] = False
    yield
    FLAGS["attn_bf16_probs"] = prev


@pytest.fixture(scope="module")
def params():
    p = build_model(CFG).init(jax.random.key(7))
    bias = p["layers"]["ffn"]["router_bias"]
    # a non-zero correction bias: selection by s + bias, weights by s alone
    p["layers"]["ffn"]["router_bias"] = 0.3 * jax.random.normal(jax.random.key(8), bias.shape)
    return p


def _prompts():
    rng = np.random.default_rng(3)
    return [rng.integers(1, CFG.vocab, size=n).astype(np.int32) for n in (13, 21)]


def test_published_config_counts_16b_a3b():
    cfg = get_config("moonlight_16b_a3b")
    assert abs(cfg.param_count() / 16.0e9 - 1) < 0.02, cfg.param_count()
    assert abs(cfg.active_param_count() / 2.9e9 - 1) < 0.02, cfg.active_param_count()


def test_paged_packed_decode_matches_reference_logits(params):
    """Masked bucketed prefill, then the paged packed decode step over the
    latent arena exactly as the Scheduler's paged segment runs it (slot
    vmap, pending rows scattered once a step), teacher-forced on its own
    greedy tokens: every step's logits against the reference's full
    forward over the same sequence."""
    eng = Engine(CFG, params, ServeConfig(max_len=MAX_LEN, page_size=PAGE,
                                          packed_weights="all", packed_values="bf16"))
    prompts = _prompts()
    lengths = np.asarray([len(p) for p in prompts], np.int32)
    toks = np.zeros((2, 32), np.int32)
    for i, p in enumerate(prompts):
        toks[i, : len(p)] = p
    first, primed = eng.prime_many(toks, lengths)
    model = build_model(CFG)
    layout = PagedLayout.build(2, MAX_LEN, PAGE)
    pstate = model.init_paged_pool(layout, MAX_LEN)
    rows = layout.reserved + np.arange(2 * layout.n_pages).reshape(2, layout.n_pages)
    # the primed cache spans max_len rows: every page of both tables
    arena = write_prefill_pages(pstate["arena"], jnp.asarray(rows, jnp.int32), primed)
    table, pos = bind_slot_pages(pstate["table"], pstate["pos"], jnp.arange(2),
                                 jnp.asarray(rows), jnp.asarray(lengths))
    pstate = {"arena": arena, "table": table, "pos": pos}

    def one(tok, c):
        logits, c2 = lm_decode_step_packed(params, eng._packed, tok, c, CFG)
        return logits, {n + "_new": c2[n + "_new"] for n in pstate["arena"]}

    step = jax.jit(lambda tok, ps: jax.vmap(one, in_axes=(0, paged_in_axes(ps)))(
        tok, paged_view(ps)))
    seqs = [list(p) + [int(first[i, 0])] for i, p in enumerate(prompts)]
    got = [[] for _ in prompts]
    tok = jnp.asarray(first)[:, :, None]
    for _ in range(6):
        logits, new_rows = step(tok, pstate)
        pstate = paged_scatter_token(pstate, new_rows)
        for i in range(2):
            got[i].append(np.asarray(logits[i, 0, 0]))
        tok = jnp.argmax(logits[:, :, 0], axis=-1).astype(jnp.int32)[:, :, None]
        for i in range(2):
            seqs[i].append(int(tok[i, 0, 0]))
    m = _model_dict(CFG)
    for i, p in enumerate(prompts):
        ref = np.asarray(REF.logits(params, jnp.asarray(seqs[i]), m))
        # the prefill's first token is the reference's argmax after the prompt
        assert int(first[i, 0]) == int(np.argmax(ref[len(p) - 1, : CFG.vocab]))
        want = ref[len(p) : len(p) + 6, : CFG.vocab]
        np.testing.assert_allclose(np.stack(got[i])[:, : CFG.vocab], want, atol=LOGIT_TOL,
                                   rtol=LOGIT_TOL)


@pytest.mark.parametrize("page_size, chunk", [(PAGE, 0), (PAGE, PAGE), (0, 0)],
                         ids=["paged", "paged_chunked", "slot_pool"])
@pytest.mark.parametrize("packed", ["all", False], ids=["packed", "unpacked"])
def test_scheduler_serves_the_reference_argmax(params, page_size, chunk, packed):
    """``Engine`` -> ``Scheduler.run`` (paged latent pool, with whole or
    chunked prefill, or the slot pool; the packed or the unpacked decode
    step): every served token is the reference's argmax at its position,
    and the run counts its routed rows and runs every packed call, the
    grouped kernel's too, as slot rows."""
    eng = Engine(CFG, params, ServeConfig(max_len=MAX_LEN, page_size=page_size,
                                          prefill_chunk=chunk, packed_weights=packed,
                                          packed_values="bf16"))
    sched = Scheduler(eng, slots=2, segment=4)
    prompts = _prompts()
    done = sched.run([Request(prompt=p, max_new=9, seed=i) for i, p in enumerate(prompts)])
    st = sched.stats()
    m = _model_dict(CFG)
    for rid, p in enumerate(prompts):
        served = np.asarray(done[rid].tokens)
        assert len(served) == 9
        ref = np.asarray(REF.logits(params, jnp.asarray(np.concatenate([p, served[:-1]])), m))
        np.testing.assert_array_equal(
            served, np.argmax(ref[len(p) - 1 :, : CFG.vocab], axis=-1))
    assert sched.paged == bool(page_size)
    assert st["held_experts"] == CFG.held_experts
    assert st["expert_layers"] == CFG.n_layers - CFG.n_dense_layers
    # each step routes every live row to top_k experts, some of them held
    assert 0 < st["expert_rows"] <= st["decode_steps"] * 2 * st["expert_layers"] * CFG.top_k
    if packed:
        # 3 projections + the shared or dense MLP a layer, the grouped
        # kernel an expert layer, and the head
        want = 4 * CFG.n_layers + (CFG.n_layers - CFG.n_dense_layers) + 1
        assert (st["packed_calls_folded"], st["packed_calls_fallback"]) == (want, 0)


def test_expert_shares_sum_to_the_uncut_layer(params):
    """Each of the ``ep_size`` chips holds its block of experts and routes
    over all of them; their parts, with the shared experts counted once,
    add up to the reference layer with every expert held."""
    rng = np.random.default_rng(5)
    p = jax.tree.map(lambda a: a[0], params["layers"]["ffn"])
    e, held = CFG.n_experts, CFG.held_experts
    full = {n: jnp.asarray(rng.normal(size=(e,) + p[n].shape[1:]) / np.sqrt(p[n].shape[1]),
                           jnp.float32) for n in ("w_gate", "w_up", "w_down")}
    h = jnp.asarray(rng.normal(size=(20, CFG.d_model)), jnp.float32)
    total = 0.0
    for rank in range(CFG.ep_size):
        # rank r holds experts [r*held, (r+1)*held): the program's chip holds
        # the first block, so renumber the experts to put rank r's first
        order = np.roll(np.arange(e), -rank * held)
        q = {**p, "router": p["router"][:, order], "router_bias": p["router_bias"][order],
             **{n: full[n][order[:held]] for n in full}}
        gates, _ = route(q, h, CFG)
        total = total + held_experts(q, h, gates)
    total = total + REF.swiglu(h, p["shared"])
    uncut = REF.experts(h, {**p, **full}, _model_dict(CFG, ep_size=1))
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut), atol=1e-4, rtol=1e-4)
    # and the program's layer on rank 0 is the reference's share
    y, _ = moe_held({**p, **{n: full[n][:held] for n in full}}, h[None], CFG)
    want = REF.experts(h, {**p, **{n: full[n][:held] for n in full}}, _model_dict(CFG))
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(want), atol=1e-4, rtol=1e-4)


def test_routing_selects_by_biased_score_and_weights_by_score():
    """Four experts, two a token, two held: the bias lifts expert 3 over
    expert 1 for selection, and the weights are the sigmoid scores of the
    chosen, normalised, times routed_scale."""
    import dataclasses

    cfg = dataclasses.replace(CFG, n_experts=4, top_k=2, ep_size=2, routed_scale=2.446)
    logits = np.asarray([2.0, 1.0, -3.0, 0.5], np.float32)  # sigmoid: .881 .731 .047 .622
    p = {"router": jnp.asarray(np.eye(4, dtype=np.float32)),
         "router_bias": jnp.asarray([0.0, 0.0, 0.0, 0.2], jnp.float32)}
    gates, rows = route(p, jnp.asarray(logits)[None], cfg)
    s = 1 / (1 + np.exp(-logits))
    w0 = 2.446 * s[0] / (s[0] + s[3])  # chosen: experts 0 and 3 (0.622 + 0.2 > 0.731)
    np.testing.assert_allclose(np.asarray(gates), [[w0, 0.0]], rtol=1e-6)
    assert int(rows) == 1  # expert 0 is held here, expert 3 is not


@pytest.mark.parametrize("paged", [False, True], ids=["contiguous", "paged"])
def test_absorbed_decode_equals_decompressed(params, paged):
    """One absorbed decode step over the latent cache (contiguous, or paged
    through a shuffled block table) gives the decompressed attention of the
    same query over the same rows, and returns the new latent row."""
    rng = np.random.default_rng(11)
    p = jax.tree.map(lambda a: a[0], params["layers"]["attn"])
    n, pos = 24, 17
    x = jnp.asarray(rng.normal(size=(1, n, CFG.d_model)), jnp.float32)
    lat = mla_latent(p, x, CFG, jnp.arange(n))  # (1, n, w)
    cache = jnp.zeros((1, 32, lat.shape[-1]), jnp.float32).at[:, :pos].set(lat[:, :pos])
    c = {"latent": cache, "pos": jnp.int32(pos)}
    if paged:
        blocks = rng.permutation(np.arange(1, 9))[:4]  # 4 pages of 8 rows
        arena = jnp.zeros((9, 8, lat.shape[-1]), jnp.float32)
        arena = arena.at[blocks].set(cache[0].reshape(4, 8, -1))
        c = {"latent": arena, "table": jnp.asarray(blocks, jnp.int32), "pos": jnp.int32(pos)}
    y, new = mla_decode(p, x[:, pos : pos + 1], CFG, c)
    want = _mla_attend(p, x[:, pos : pos + 1], CFG, jnp.asarray([pos]), lat[:, : pos + 1],
                       jnp.arange(pos + 1), None, MaskSpec("causal"))
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-5, rtol=1e-5)
    row = new["latent_new"] if paged else new["latent"][:, pos : pos + 1]
    np.testing.assert_allclose(np.asarray(row), np.asarray(lat[:, pos : pos + 1]), atol=1e-6)


@pytest.mark.parametrize("value_dtype", ["dense", "int8", "int4"])
def test_grouped_expert_kernel(value_dtype):
    """``vusa_fused_moe_matmul`` against the jnp oracle (kernels/ref.py),
    and under a slot vmap of rows and weights: one folded call, bitwise the
    per-slot calls."""
    rng = np.random.default_rng(2)
    e, d, f = 3, 64, 96

    def sparse(*shape):
        return np.where(rng.random(shape) < 0.8, 0, rng.normal(size=shape)).astype(np.float32)

    def stack(fn, ws):
        st = _stack_packs([fn(w, m=32, a=4, value_dtype=value_dtype) for w in ws])
        return _as_linear(st, st["values"], st["positions"], st.get("scales"))

    gate = stack(pack_linear_rows, [sparse(d, f) for _ in range(e)])
    up = stack(pack_linear_rows, [sparse(d, f) for _ in range(e)])
    down = stack(pack_linear_rows_t, [sparse(f, d) for _ in range(e)])
    x = jnp.asarray(rng.normal(size=(5, d)), jnp.float32)
    w = jnp.asarray(rng.random((5, e)) * (rng.random((5, e)) < 0.6), jnp.float32)
    y = apply_fused_moe(x, w, gate, up, down)
    want = apply_fused_moe_ref(x, w, gate, up, down)
    scale = float(jnp.max(jnp.abs(want)))
    # fp32 accumulation over 96-wide windows in a different order
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-5 * scale)
    xs = jnp.asarray(rng.normal(size=(4, 2, d)), jnp.float32)
    ws = jnp.asarray(rng.random((4, 2, e)), jnp.float32)
    with fold_tally() as tally:
        folded = jax.vmap(lambda a, b: apply_fused_moe(a, b, gate, up, down))(xs, ws)
    assert (tally.folded, tally.fallback) == (1, 0)
    per_slot = jnp.stack([apply_fused_moe(xs[i], ws[i], gate, up, down) for i in range(4)])
    np.testing.assert_array_equal(np.asarray(folded), np.asarray(per_slot))
