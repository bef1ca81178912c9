"""Model families: decoder-LM (dense / MoE / VLM), hybrid (Griffin),
SSM (Mamba-2), encoder-decoder (Whisper).

A family provides:
  specs(cfg)                          ParamSpec tree (layer-stacked)
  forward(params, batch, cfg)         logits for teacher-forced tokens
  loss(params, batch, cfg)            scalar LM loss (+ MoE aux)
  init_cache(cfg, batch, max_len)     decode cache pytree (zeros)
  cache_specs(cfg, batch, max_len)    ShapeDtypeStruct twin of init_cache
  prefill(params, tokens, cfg)        run prompt, return (logits_last, cache)
  decode_step(params, token, cache, cfg)  one-token step

Layer parameters carry a leading "layers" axis and run under ``lax.scan``
(small HLO, fast multi-pod compiles); ``cfg.remat`` wraps the layer body in
``jax.checkpoint`` for training.
"""

from __future__ import annotations

import contextlib
from functools import partial

import jax
import jax.numpy as jnp

from . import ssm as ssm_mod
from .common import ParamSpec, rms_norm, shard
from .layers import (
    MaskSpec,
    _project_qkv,
    attention,
    attention_decode,
    attention_specs,
    cross_attention,
    encode_cross_kv,
    held_experts,
    mla_attention,
    mla_chunk,
    mla_decode,
    mla_latent,
    mla_specs,
    mlp,
    mlp_specs,
    moe,
    moe_held,
    moe_specs,
    route,
)

# --------------------------------------------------------------------------
# helpers
# --------------------------------------------------------------------------


def _stack_specs(tree, n: int):
    """Add a leading `layers` axis of size n to every ParamSpec leaf."""
    return jax.tree_util.tree_map(
        lambda s: ParamSpec((n,) + s.shape, (None,) + s.axes, s.dtype, s.init, s.scale),
        tree,
        is_leaf=lambda x: isinstance(x, ParamSpec),
    )


def _act_dtype(cfg):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def _xent(logits: jax.Array, labels: jax.Array, vocab: int) -> jax.Array:
    """Mean next-token cross-entropy; ids >= vocab (padding) are masked."""
    from .opt_flags import FLAGS

    mask = (labels >= 0) & (labels < vocab)
    labels = jnp.clip(labels, 0, vocab - 1)
    if FLAGS["xent_lse"]:
        # logsumexp form: no fp32 (B,S,V) log-softmax tensor; picked logits
        # and the reduction run in fp32, the big tensor stays in model dtype
        lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
        picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        ll = picked.astype(jnp.float32) - lse
    else:
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return -(ll * mask).sum() / jnp.maximum(mask.sum(), 1)


# --------------------------------------------------------------------------
# Decoder-only LM (dense / moe / vlm)
# --------------------------------------------------------------------------


def _lm_stacks(cfg):
    """The layer stacks of a decoder LM, in order: ``(params key, layers,
    cache-leaf prefix, dense)``.  A model with leading dense layers
    (``n_dense_layers``) runs them as their own stack, with a SwiGLU of
    ``dense_d_ff``, before the main stack; the main stack alone is the case
    with no dense prefix."""
    stacks = []
    if cfg.n_dense_layers:
        stacks.append(("dense_layers", cfg.n_dense_layers, "dense_", True))
    stacks.append(("layers", cfg.n_layers - cfg.n_dense_layers, "", False))
    return stacks


def lm_attn_leaves(cfg) -> tuple:
    """A layer's cache leaves: the latent row under latent attention, else
    per-head keys and values."""
    return ("latent",) if cfg.mla else ("k", "v")


def _lm_layer_specs(cfg, dense: bool = False) -> dict:
    d = cfg.d_model
    specs = {
        "norm1": ParamSpec((d,), ("embed",), init="zeros"),
        "attn": mla_specs(cfg) if cfg.mla else attention_specs(cfg),
        "norm2": ParamSpec((d,), ("embed",), init="zeros"),
    }
    if dense:
        specs["ffn"] = mlp_specs(cfg, cfg.dense_d_ff)
    else:
        specs["ffn"] = moe_specs(cfg) if cfg.family == "moe" else mlp_specs(cfg)
    return specs


def lm_specs(cfg) -> dict:
    d, v = cfg.d_model, cfg.padded_vocab
    specs = {"embed": ParamSpec((v, d), ("vocab", "embed"), scale=1.0)}
    for key, n, _, dense in _lm_stacks(cfg):
        specs[key] = _stack_specs(_lm_layer_specs(cfg, dense), n)
    specs["final_norm"] = ParamSpec((d,), ("embed",), init="zeros")
    if not cfg.tie_embeddings:
        specs["lm_head"] = ParamSpec((d, v), ("embed", "vocab"))
    if cfg.family == "vlm":
        specs["patch_proj"] = ParamSpec((d, d), ("embed", "embed2"))
    return specs


def _lm_ffn(p, h, cfg, dense: bool):
    """The layer's feed-forward: (y, aux loss)."""
    if dense or cfg.family != "moe":
        return mlp(p, h), 0.0
    if cfg.held_moe:
        return moe_held(p, h, cfg)
    return moe(p, h, cfg)


def _lm_layer(lp, x, cfg, mask: MaskSpec, positions, kv_valid=None, dense=False):
    h = rms_norm(x, lp["norm1"])
    attend = mla_attention if cfg.mla else attention
    x = x + attend(lp["attn"], h, cfg, mask, positions, kv_valid=kv_valid)
    x = shard(x, "batch", None, "embed")
    h = rms_norm(x, lp["norm2"])
    y, aux = _lm_ffn(lp["ffn"], h, cfg, dense)
    return x + y, aux


def _lm_backbone(params, x, cfg, mask: MaskSpec, positions):
    aux = 0.0
    for key, _, _, dense in _lm_stacks(cfg):
        layer = partial(_lm_layer, cfg=cfg, mask=mask, positions=positions, dense=dense)
        if cfg.remat:
            layer = jax.checkpoint(layer)

        def body(carry, lp, layer=layer):
            x, aux = carry
            x, a = layer(lp, x)
            return (x, aux + a), None

        (x, aux), _ = jax.lax.scan(body, (x, aux), params[key])
    return rms_norm(x, params["final_norm"]), aux


def _embed_tokens(params, tokens, cfg):
    x = params["embed"][tokens].astype(_act_dtype(cfg))
    return x * (cfg.d_model ** 0.5 if cfg.family in ("vlm",) else 1.0)


def _lm_inputs(params, batch, cfg):
    """Build (x, mask, positions) from a batch; handles the VLM patch prefix."""
    tokens = batch["tokens"]
    x = _embed_tokens(params, tokens, cfg)
    if cfg.family == "vlm":
        patches = batch["patches"].astype(_act_dtype(cfg))  # (B, P, d) stub frontend
        patches = patches @ params["patch_proj"].astype(patches.dtype)
        x = jnp.concatenate([patches, x], axis=1)
        mask = MaskSpec("prefix", prefix_len=cfg.patch_tokens)
    else:
        mask = MaskSpec("causal")
    positions = jnp.arange(x.shape[1])
    return x, mask, positions


def lm_forward(params, batch, cfg):
    x, mask, positions = _lm_inputs(params, batch, cfg)
    x = shard(x, "batch", None, "embed")
    x, aux = _lm_backbone(params, x, cfg, mask, positions)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", x, head.astype(x.dtype))
    if cfg.family == "vlm":  # only text positions produce logits
        logits = logits[:, cfg.patch_tokens :]
    return logits, aux


def lm_loss(params, batch, cfg):
    logits, aux = lm_forward(params, batch, cfg)
    return _xent(logits[:, :-1], batch["tokens"][:, 1:], cfg.vocab) + 0.01 * aux


# ---- decode ----------------------------------------------------------------


def lm_cache_specs(cfg, batch: int, max_len: int):
    """One leaf per layer stack and attention leaf, stacked over the stack's
    layers: ``k``/``v`` (L, B, T, kvh, hd), or under latent attention
    ``latent`` (L, B, T, kv_lora_rank + qk_rope) — a token's whole cached
    state; the leading dense stack's leaves carry the prefix ``dense_``."""
    adt = _act_dtype(cfg)
    if cfg.mla:
        row = (cfg.kv_lora_rank + cfg.qk_rope_head_dim,)
    else:
        row = (cfg.kv_heads, cfg.hd)
    out = {
        pre + name: jax.ShapeDtypeStruct((n, batch, max_len) + row, adt)
        for _, n, pre, _ in _lm_stacks(cfg)
        for name in lm_attn_leaves(cfg)
    }
    out["pos"] = jax.ShapeDtypeStruct((), jnp.int32)
    return out


def lm_init_cache(cfg, batch: int, max_len: int):
    return jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), lm_cache_specs(cfg, batch, max_len)
    )


def _lm_decode_ffn(lp, x, cfg, dense: bool):
    """Feed-forward of one decode step: ``(x + y, expert rows or None)``.
    The held-expert layer's routing runs under ``decode.router``, its
    experts and their combine under ``decode.experts``, the shared experts
    under ``decode.mlp``."""
    if dense or not cfg.held_moe:
        with jax.named_scope("decode.mlp"):
            h = rms_norm(x, lp["norm2"])
            y, _ = _lm_ffn(lp["ffn"], h, cfg, dense)
        return x + y, None
    p = lp["ffn"]
    with jax.named_scope("decode.mlp"):
        h = rms_norm(x, lp["norm2"])
        b, s, d = h.shape
        hf = h.reshape(b * s, d)
    with jax.named_scope("decode.router"):
        gates, rows = route(p, hf, cfg)
    with jax.named_scope("decode.experts"):
        x = x + held_experts(p, hf, gates).astype(x.dtype).reshape(b, s, d)
    if "shared" in p:
        with jax.named_scope("decode.mlp"):
            x = x + mlp(p["shared"], h)
    return x, rows


def _lm_decode_layer(lp, x, cache_l, cfg, pos, dense=False):
    h = rms_norm(x, lp["norm1"])
    attend = mla_decode if cfg.mla else attention_decode
    y, new_cache = attend(lp["attn"], h, cfg, {**cache_l, "pos": pos})
    x, rows = _lm_decode_ffn(lp, x + y, cfg, dense)
    out = lm_step_rows(new_cache, cfg)
    if rows is not None:
        out["expert_rows"] = rows
    return x, out


def lm_step_rows(new_cache, cfg) -> dict:
    """A layer's cache output of a decode step, by leaf: the pending rows
    (``<leaf>_new``) of a paged cache, else the updated leaves."""
    names = lm_attn_leaves(cfg)
    if names[0] + "_new" in new_cache:  # paged: pending row writes, not a full cache
        return {n + "_new": new_cache[n + "_new"] for n in names}
    return {n: new_cache[n] for n in names}


def lm_scan_stacks(x, cache, cfg, body, stack_inputs, repeat=None):
    """Run ``body(x, (params, cache leaves, *extra)) -> (x, outputs)`` over
    each layer stack in turn (``stack_inputs(key, dense)`` gives the stack's
    params and extra scanned inputs) and gather the outputs under the
    cache's leaf names.  Per-layer ``expert_rows`` are summed into one
    count.  ``repeat(n)`` is entered around each stack's scan."""
    out, rows = {}, None
    for key, n, pre, dense in _lm_stacks(cfg):
        params_s, *extra = stack_inputs(key, dense)
        leaves = {name: cache[pre + name] for name in lm_attn_leaves(cfg)}
        with repeat(n) if repeat is not None else contextlib.nullcontext():
            x, new = jax.lax.scan(partial(body, dense=dense), x, (params_s, leaves, *extra))
        r = new.pop("expert_rows", None)
        if r is not None:
            rows = r.sum() if rows is None else rows + r.sum()
        out.update({pre + k: v for k, v in new.items()})
    if rows is not None:
        out["expert_rows"] = rows
    return x, out


def lm_decode_step(params, token, cache, cfg):
    """token: (B, s) int32 (s = 1 normal decode; s > 1 speculative verify).
    Returns (logits (B, s, V), new cache).

    With ``s > 1`` the step runs as an unrolled chain of the exact
    single-token step inside the one dispatch.  This is deliberate: a
    batched ``(B, s)`` pass through the layers is NOT bit-identical to
    ``s`` sequential steps — XLA picks different gemm accumulation orders
    for different row counts (measured: the lm-head gemm with M=6 vs M=1
    under jit differs in the last ulp) — while chaining the identical
    ``s = 1`` graph is parity by construction.  ``s`` is small and static
    (``draft_k + 1``), so the unroll is cheap to trace; the speculative win
    is one host dispatch per *round* instead of per token (DESIGN.md §13).
    Multi-token mode requires a contiguous cache (not paged) — the paged
    scheduler gathers a contiguous per-slot view first.

    ``cache`` may be the paged per-slot view (DESIGN.md §11): ``{"k"/"v":
    (L, n_blocks, page, ...) arena leaves, "table": (n_pages,), "pos": ()}``.
    The layer scan then slices the arena per layer exactly as it slices the
    dense cache, and the returned tree carries the pending KV rows
    (``k_new``/``v_new``, stacked (L, 1, 1, ...)) for the caller to scatter
    into the shared arena — the step itself never writes arena state.  The
    leaves are those of :func:`lm_cache_specs` (``latent`` under latent
    attention, ``dense_``-prefixed for a leading dense stack).  A model with
    held experts adds ``expert_rows``: the (row, held expert) pairs its
    routing chose in this step."""
    if token.shape[1] > 1:
        assert "table" not in cache, (
            "multi-token decode needs a contiguous cache; gather the paged "
            "view first (serve/scheduler.py)"
        )
        logits = []
        for i in range(token.shape[1]):
            lg, cache = lm_decode_step(params, token[:, i : i + 1], cache, cfg)
            logits.append(lg)
        return jnp.concatenate(logits, axis=1), cache
    x = _embed_tokens(params, token, cfg)
    pos = cache["pos"]
    table = cache.get("table")

    def body(x, layer_in, dense):
        lp, cache_l = layer_in
        if table is not None:
            cache_l = {**cache_l, "table": table}
        return _lm_decode_layer(lp, x, cache_l, cfg, pos, dense)

    x, new_kv = lm_scan_stacks(x, cache, cfg, body, lambda key, dense: (params[key],))
    with jax.named_scope("decode.head"):
        x = rms_norm(x, params["final_norm"])
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = jnp.einsum("bsd,dv->bsv", x, head.astype(x.dtype))
    if table is not None:
        return logits, {**new_kv, "table": table, "pos": pos + 1}
    return logits, {**new_kv, "pos": pos + 1}


def _lm_cache_rows(p, h, cfg, positions) -> dict:
    """A layer's cache rows for the prompt ``h``, by leaf."""
    if cfg.mla:
        return {"latent": mla_latent(p, h, cfg, positions)}
    _, k, v = _project_qkv(p, h, cfg, positions)
    return {"k": k, "v": v}


def lm_prefill(params, batch, cfg, max_len: int, lengths=None):
    """Run the prompt through the train path, then bulk-write the KV cache.

    For lowering/runtime simplicity we recompute K/V per layer into the cache
    (prefill is compute-bound anyway; the flash path already produced the
    hidden states).

    ``lengths`` (B,) enables *masked* bucketed prefill (DESIGN.md §6):
    ``tokens`` are right-padded to a shared bucket length and each row's true
    prompt length is given instead.  Logits are gathered at each row's last
    real token and are bit-identical to an unpadded prefill of that row —
    right-padding keeps every real token's causal window unchanged, and
    ``kv_valid`` masks padded keys to exactly-zero probability.  Cache rows at
    positions >= length hold garbage the decode-side occupancy mask
    (``slots <= pos``) never reads, so callers must set each row's true
    ``pos`` (``cache["pos"]`` stays the scalar padded length; the serve
    scheduler overrides it per slot via ``write_slots``).

    Caveat (moe): capacity-bounded dispatch couples rows — padding and
    co-batched tokens consume shared expert capacity — so bit-exactness
    additionally requires a dropless layer (``ArchConfig.moe_dropless``);
    the serve engine only enables batched admission for moe under that
    condition."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    cache = lm_init_cache(cfg, b, max_len)
    x, mask, positions = _lm_inputs(params, batch, cfg)
    kv_valid = None
    patch_off = cfg.patch_tokens if cfg.family == "vlm" else 0
    if lengths is not None:
        kv_valid = jnp.arange(x.shape[1])[None, :] < (lengths[:, None] + patch_off)

    rows = {}
    for key, _, pre, dense in _lm_stacks(cfg):

        def body(x, lp, dense=dense):
            h = rms_norm(x, lp["norm1"])
            out = _lm_cache_rows(lp["attn"], h, cfg, positions)
            x, _ = _lm_layer(lp, x, cfg, mask, positions, kv_valid, dense)
            return x, out

        x, out = jax.lax.scan(body, x, params[key])
        rows.update({pre + name: r for name, r in out.items()})
    xf = rms_norm(x, params["final_norm"])
    for name, r in rows.items():
        cache[name] = jax.lax.dynamic_update_slice(
            cache[name], r.astype(cache[name].dtype), (0,) * r.ndim
        )
    cache["pos"] = jnp.int32(x.shape[1])
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    if lengths is None:
        last = xf[:, -1]
    else:  # each row's last real token (bucket padding sits after it)
        idx = (lengths - 1 + patch_off)[:, None, None]
        last = jnp.take_along_axis(xf, idx, axis=1)[:, 0]
    logits = jnp.einsum("bd,dv->bv", last, head.astype(xf.dtype))
    return logits, cache


def lm_prefill_chunk(params, tokens, cfg, arena, table_row, start, true_len,
                     write_from):
    """One chunk of a paged chunked prefill (Sarathi-style, DESIGN.md §11).

    ``tokens`` (1, C) is a chunk of a single prompt whose first token sits at
    absolute position ``start`` (traced — one compiled program per static C);
    ``true_len`` counts real tokens (the final chunk is right-padded to C).
    Each layer attends the chunk causally over everything resident in
    ``table_row``'s blocks plus itself, then the chunk's rows scatter into
    every leaf of ``arena`` at block-table addresses.  Rows below
    ``write_from`` are *not* written — prefix-shared pages are already
    resident and must stay read-only (setting ``write_from = start +
    true_len`` turns the call into a pure re-peek, e.g. recovering the
    first-token logits after a fully-matched prefix hit without touching
    shared blocks).

    Returns ``(logits (1, V), arena')`` — logits at the chunk's last real
    token, meaningful on the final chunk only."""
    from .layers import attention_chunk  # noqa: PLC0415

    x = _embed_tokens(params, tokens, cfg)
    c = x.shape[1]
    attend = mla_chunk if cfg.mla else attention_chunk

    def body(x, layer_in, dense):
        lp, arena_l = layer_in
        h = rms_norm(x, lp["norm1"])
        y, rows = attend(lp["attn"], h, cfg, arena_l, table_row, start, true_len)
        x = x + y
        h = rms_norm(x, lp["norm2"])
        y, _ = _lm_ffn(lp["ffn"], h, cfg, dense)
        return x + y, rows

    x, rows = lm_scan_stacks(x, arena, cfg, body, lambda key, dense: (params[key],))
    some = next(iter(arena.values()))
    page, n_blocks = some.shape[2], some.shape[1]
    pos = start + jnp.arange(c)  # absolute positions of chunk tokens
    writable = (jnp.arange(c) < true_len) & (pos >= write_from)
    pg = jnp.clip(pos // page, 0, table_row.shape[0] - 1)
    blk = jnp.where(writable, table_row[pg], n_blocks)  # sentinel -> dropped
    off = pos % page
    new_arena = {}
    for name, stacked in rows.items():
        a = arena[name]
        new_arena[name] = a.at[:, blk, off].set(
            stacked[:, 0].astype(a.dtype), mode="drop"
        )
    xf = rms_norm(x, params["final_norm"])
    last = xf[0, jnp.clip(true_len - 1, 0, c - 1)][None]  # (1, d)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bd,dv->bv", last, head.astype(xf.dtype))
    return logits, new_arena


# --------------------------------------------------------------------------
# Hybrid (Griffin / recurrentgemma): pattern of RG-LRU and local-attention
# blocks, each followed by an MLP block.
# --------------------------------------------------------------------------


def _hybrid_layer_specs(cfg, kind: str) -> dict:
    d = cfg.d_model
    mixer = ssm_mod.rglru_specs(cfg) if kind == "rglru" else attention_specs(cfg)
    return {
        "norm1": ParamSpec((d,), ("embed",), init="zeros"),
        "mixer": mixer,
        "norm2": ParamSpec((d,), ("embed",), init="zeros"),
        "ffn": mlp_specs(cfg),
    }


def _hybrid_pattern(cfg):
    reps = (cfg.n_layers + len(cfg.block_pattern) - 1) // len(cfg.block_pattern)
    return (cfg.block_pattern * reps)[: cfg.n_layers]


def hybrid_specs(cfg) -> dict:
    d, v = cfg.d_model, cfg.padded_vocab
    pat = cfg.block_pattern
    n_groups = cfg.n_layers // len(pat)
    tail = _hybrid_pattern(cfg)[n_groups * len(pat) :]
    specs = {
        "embed": ParamSpec((v, d), ("vocab", "embed")),
        "groups": {
            f"p{i}_{kind}": _stack_specs(_hybrid_layer_specs(cfg, kind), n_groups)
            for i, kind in enumerate(pat)
        },
        "tail": {
            f"t{i}_{kind}": _hybrid_layer_specs(cfg, kind) for i, kind in enumerate(tail)
        },
        "final_norm": ParamSpec((d,), ("embed",), init="zeros"),
        "lm_head": ParamSpec((d, v), ("embed", "vocab")),
    }
    return specs


def _hybrid_layer(lp, x, kind, cfg, positions):
    h = rms_norm(x, lp["norm1"])
    if kind == "rglru":
        y = ssm_mod.rglru_block(lp["mixer"], h, cfg)
    else:
        y = attention(lp["mixer"], h, cfg, MaskSpec("local", window=cfg.local_window), positions)
    x = x + y
    x = shard(x, "batch", None, "embed")
    h = rms_norm(x, lp["norm2"])
    return x + mlp(lp["ffn"], h)


def hybrid_forward(params, batch, cfg):
    tokens = batch["tokens"]
    x = params["embed"][tokens].astype(_act_dtype(cfg))
    positions = jnp.arange(x.shape[1])
    pat = cfg.block_pattern

    def group_body(x, gp):
        for i, kind in enumerate(pat):
            fn = partial(_hybrid_layer, kind=kind, cfg=cfg, positions=positions)
            if cfg.remat:
                fn = jax.checkpoint(fn)
            x = fn(gp[f"p{i}_{kind}"], x)
        return x, None

    x, _ = jax.lax.scan(group_body, x, params["groups"])
    for name, lp in params["tail"].items():
        kind = name.split("_", 1)[1]
        x = _hybrid_layer(lp, x, kind, cfg, positions)
    x = rms_norm(x, params["final_norm"])
    logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"].astype(x.dtype))
    return logits, 0.0


def hybrid_loss(params, batch, cfg):
    logits, _ = hybrid_forward(params, batch, cfg)
    return _xent(logits[:, :-1], batch["tokens"][:, 1:], cfg.vocab)


def hybrid_cache_specs(cfg, batch: int, max_len: int):
    """Per pattern-position caches (stacked over groups) + tail caches.

    Attention layers keep a ring cache bounded by the local window — this is
    what makes long_500k decode O(window), not O(seq)."""
    kvh, hd, r = cfg.kv_heads, cfg.hd, cfg.rglru_dim
    ring = min(cfg.local_window, max_len)
    n_groups = cfg.n_layers // len(cfg.block_pattern)
    adt = _act_dtype(cfg)

    def mixer_cache(kind, n=None):
        lead = (n,) if n else ()
        if kind == "rglru":
            return {
                "conv": jax.ShapeDtypeStruct(lead + (batch, 3, r), adt),
                "h": jax.ShapeDtypeStruct(lead + (batch, r), jnp.float32),
            }
        return {
            "k": jax.ShapeDtypeStruct(lead + (batch, ring, kvh, hd), adt),
            "v": jax.ShapeDtypeStruct(lead + (batch, ring, kvh, hd), adt),
        }

    pat = cfg.block_pattern
    tail = _hybrid_pattern(cfg)[n_groups * len(pat) :]
    return {
        "groups": {
            f"p{i}_{kind}": mixer_cache(kind, n_groups) for i, kind in enumerate(pat)
        },
        "tail": {f"t{i}_{kind}": mixer_cache(kind) for i, kind in enumerate(tail)},
        "pos": jax.ShapeDtypeStruct((), jnp.int32),
    }


def hybrid_init_cache(cfg, batch: int, max_len: int):
    return jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), hybrid_cache_specs(cfg, batch, max_len)
    )


def _hybrid_decode_layer(lp, x, cache_l, kind, cfg, pos):
    h = rms_norm(x, lp["norm1"])
    if kind == "rglru":
        y, new_c = ssm_mod.rglru_decode(lp["mixer"], h, cfg, cache_l)
    else:
        y, new_c = attention_decode(
            lp["mixer"], h, cfg, {**cache_l, "pos": pos}, window=cfg.local_window
        )
        new_c = {"k": new_c["k"], "v": new_c["v"]}
    x = x + y
    h = rms_norm(x, lp["norm2"])
    return x + mlp(lp["ffn"], h), new_c


def hybrid_decode_step(params, token, cache, cfg):
    x = params["embed"][token].astype(_act_dtype(cfg))
    pos = cache["pos"]
    pat = cfg.block_pattern

    def group_body(x, inp):
        gp, gc = inp
        new_caches = {}
        for i, kind in enumerate(pat):
            key = f"p{i}_{kind}"
            x, new_caches[key] = _hybrid_decode_layer(gp[key], x, gc[key], kind, cfg, pos)
        return x, new_caches

    x, new_group_cache = jax.lax.scan(group_body, x, (params["groups"], cache["groups"]))
    new_tail = {}
    for name, lp in params["tail"].items():
        kind = name.split("_", 1)[1]
        x, new_tail[name] = _hybrid_decode_layer(lp, x, cache["tail"][name], kind, cfg, pos)
    x = rms_norm(x, params["final_norm"])
    logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"].astype(x.dtype))
    return logits, {"groups": new_group_cache, "tail": new_tail, "pos": pos + 1}


# --------------------------------------------------------------------------
# SSM (Mamba-2)
# --------------------------------------------------------------------------


def ssm_specs(cfg) -> dict:
    d, v = cfg.d_model, cfg.padded_vocab
    layer = {
        "norm": ParamSpec((d,), ("embed",), init="zeros"),
        "mixer": ssm_mod.mamba2_specs(cfg),
    }
    return {
        "embed": ParamSpec((v, d), ("vocab", "embed")),
        "layers": _stack_specs(layer, cfg.n_layers),
        "final_norm": ParamSpec((d,), ("embed",), init="zeros"),
        "lm_head": ParamSpec((d, v), ("embed", "vocab")),
    }


def ssm_forward(params, batch, cfg):
    x = params["embed"][batch["tokens"]].astype(_act_dtype(cfg))

    def layer(lp, x):
        return x + ssm_mod.mamba2_block(lp["mixer"], rms_norm(x, lp["norm"]), cfg)

    fn = jax.checkpoint(layer) if cfg.remat else layer

    def body(x, lp):
        return fn(lp, x), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    x = rms_norm(x, params["final_norm"])
    return jnp.einsum("bsd,dv->bsv", x, params["lm_head"].astype(x.dtype)), 0.0


def ssm_loss(params, batch, cfg):
    logits, _ = ssm_forward(params, batch, cfg)
    return _xent(logits[:, :-1], batch["tokens"][:, 1:], cfg.vocab)


def ssm_cache_specs(cfg, batch: int, max_len: int):
    din = cfg.expand * cfg.d_model
    n, h = cfg.ssm_state, cfg.ssm_heads
    conv_dim = din + 2 * n
    adt = _act_dtype(cfg)
    return {
        "conv": jax.ShapeDtypeStruct((cfg.n_layers, batch, cfg.d_conv - 1, conv_dim), adt),
        "state": jax.ShapeDtypeStruct((cfg.n_layers, batch, h, din // h, n), jnp.float32),
        "pos": jax.ShapeDtypeStruct((), jnp.int32),
    }


def ssm_init_cache(cfg, batch: int, max_len: int):
    return jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), ssm_cache_specs(cfg, batch, max_len)
    )


def ssm_decode_step(params, token, cache, cfg):
    x = params["embed"][token].astype(_act_dtype(cfg))

    def body(x, inp):
        lp, conv_c, state_c = inp
        y, new_c = ssm_mod.mamba2_decode(
            lp["mixer"], rms_norm(x, lp["norm"]), cfg, {"conv": conv_c, "state": state_c}
        )
        return x + y, (new_c["conv"], new_c["state"])

    x, (conv, state) = jax.lax.scan(body, x, (params["layers"], cache["conv"], cache["state"]))
    x = rms_norm(x, params["final_norm"])
    logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"].astype(x.dtype))
    return logits, {"conv": conv, "state": state, "pos": cache["pos"] + 1}


# --------------------------------------------------------------------------
# Encoder-decoder (Whisper): stub conv frontend — the encoder consumes
# precomputed frame embeddings (assignment spec), then full self-attention.
# --------------------------------------------------------------------------


def encdec_specs(cfg) -> dict:
    d, v = cfg.d_model, cfg.padded_vocab
    enc_layer = {
        "norm1": ParamSpec((d,), ("embed",), init="zeros"),
        "attn": attention_specs(cfg),
        "norm2": ParamSpec((d,), ("embed",), init="zeros"),
        "ffn": mlp_specs(cfg),
    }
    dec_layer = {
        "norm1": ParamSpec((d,), ("embed",), init="zeros"),
        "self_attn": attention_specs(cfg),
        "norm_x": ParamSpec((d,), ("embed",), init="zeros"),
        "cross_attn": attention_specs(cfg),
        "norm2": ParamSpec((d,), ("embed",), init="zeros"),
        "ffn": mlp_specs(cfg),
    }
    return {
        "embed": ParamSpec((v, d), ("vocab", "embed")),
        "enc_pos": ParamSpec((cfg.enc_frames, d), (None, "embed"), scale=0.02),
        "enc_layers": _stack_specs(enc_layer, cfg.enc_layers),
        "enc_norm": ParamSpec((d,), ("embed",), init="zeros"),
        "dec_layers": _stack_specs(dec_layer, cfg.n_layers),
        "final_norm": ParamSpec((d,), ("embed",), init="zeros"),
    }  # lm_head tied to embed (Whisper convention)


def encdec_encode(params, frames, cfg):
    """frames: (B, F, d) stub frame embeddings -> encoder states."""
    x = frames.astype(_act_dtype(cfg)) + params["enc_pos"][None, : frames.shape[1]].astype(
        _act_dtype(cfg)
    )
    positions = jnp.arange(x.shape[1])

    def layer(lp, x):
        h = rms_norm(x, lp["norm1"])
        x = x + attention(lp["attn"], h, cfg, MaskSpec("full"), positions)
        h = rms_norm(x, lp["norm2"])
        return x + mlp(lp["ffn"], h)

    fn = jax.checkpoint(layer) if cfg.remat else layer

    def body(x, lp):
        return fn(lp, x), None

    x, _ = jax.lax.scan(body, x, params["enc_layers"])
    return rms_norm(x, params["enc_norm"])


def encdec_forward(params, batch, cfg):
    """batch: {"frames": (B,F,d), "tokens": (B,S)}."""
    enc = encdec_encode(params, batch["frames"], cfg)
    tokens = batch["tokens"]
    x = params["embed"][tokens].astype(_act_dtype(cfg))
    positions = jnp.arange(x.shape[1])

    def layer(lp, x):
        h = rms_norm(x, lp["norm1"])
        x = x + attention(lp["self_attn"], h, cfg, MaskSpec("causal"), positions)
        h = rms_norm(x, lp["norm_x"])
        kv = encode_cross_kv(lp["cross_attn"], enc, cfg)
        x = x + cross_attention(lp["cross_attn"], h, kv, cfg)
        h = rms_norm(x, lp["norm2"])
        return x + mlp(lp["ffn"], h)

    fn = jax.checkpoint(layer) if cfg.remat else layer

    def body(x, lp):
        return fn(lp, x), None

    x, _ = jax.lax.scan(body, x, params["dec_layers"])
    x = rms_norm(x, params["final_norm"])
    logits = jnp.einsum("bsd,dv->bsv", x, params["embed"].T.astype(x.dtype))
    return logits, 0.0


def encdec_loss(params, batch, cfg):
    logits, _ = encdec_forward(params, batch, cfg)
    return _xent(logits[:, :-1], batch["tokens"][:, 1:], cfg.vocab)


def encdec_cache_specs(cfg, batch: int, max_len: int):
    kvh, hd = cfg.kv_heads, cfg.hd
    adt = _act_dtype(cfg)
    L = cfg.n_layers
    return {
        "k": jax.ShapeDtypeStruct((L, batch, max_len, kvh, hd), adt),
        "v": jax.ShapeDtypeStruct((L, batch, max_len, kvh, hd), adt),
        # precomputed cross-attention K/V over encoder frames
        "xk": jax.ShapeDtypeStruct((L, batch, cfg.enc_frames, kvh, hd), adt),
        "xv": jax.ShapeDtypeStruct((L, batch, cfg.enc_frames, kvh, hd), adt),
        "pos": jax.ShapeDtypeStruct((), jnp.int32),
    }


def encdec_init_cache(cfg, batch: int, max_len: int):
    return jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), encdec_cache_specs(cfg, batch, max_len)
    )


def encdec_decode_step(params, token, cache, cfg):
    """Decode one token; cross K/V must have been filled by encdec_prefill."""
    x = params["embed"][token].astype(_act_dtype(cfg))
    pos = cache["pos"]

    def body(x, inp):
        lp, k, v, xk, xv = inp
        h = rms_norm(x, lp["norm1"])
        y, new_c = attention_decode(lp["self_attn"], h, cfg, {"k": k, "v": v, "pos": pos})
        x = x + y
        h = rms_norm(x, lp["norm_x"])
        x = x + cross_attention(lp["cross_attn"], h, (xk, xv), cfg)
        h = rms_norm(x, lp["norm2"])
        return x + mlp(lp["ffn"], h), (new_c["k"], new_c["v"])

    x, (k, v) = jax.lax.scan(
        body, x, (params["dec_layers"], cache["k"], cache["v"], cache["xk"], cache["xv"])
    )
    x = rms_norm(x, params["final_norm"])
    logits = jnp.einsum("bsd,dv->bsv", x, params["embed"].T.astype(x.dtype))
    return logits, {**cache, "k": k, "v": v, "pos": pos + 1}


def encdec_prefill(params, batch, cfg, max_len: int):
    """Encode frames, fill cross-attn K/V, return cache ready for decode."""
    enc = encdec_encode(params, batch["frames"], cfg)
    b = enc.shape[0]
    cache = encdec_init_cache(cfg, b, max_len)

    def body(_, lp):
        return None, encode_cross_kv(lp["cross_attn"], enc, cfg)

    _, (xk, xv) = jax.lax.scan(body, None, params["dec_layers"])
    cache["xk"], cache["xv"] = xk.astype(cache["xk"].dtype), xv.astype(cache["xv"].dtype)
    logits = jnp.zeros((b, cfg.padded_vocab), _act_dtype(cfg))
    return logits, cache
