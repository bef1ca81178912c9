"""Transformer layer substrate: GQA attention (flash-style chunked softmax,
causal / local / prefix / full masks, KV + ring caches), SwiGLU MLP, MoE.

All functions are pure; parameters are pytrees described by ParamSpec (see
``common.py``).  Layer-stacked parameters carry a leading "layers" axis and
are consumed through ``jax.lax.scan`` by the model families.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .common import ParamSpec, apply_rope, rms_norm, rope, shard

# --------------------------------------------------------------------------
# Specs
# --------------------------------------------------------------------------


def attention_specs(cfg, cross: bool = False) -> dict:
    """Head-granular parameter shapes: TP shards the *head* axis, so the
    divisibility check in dist.sharding degrades gracefully — archs whose
    head counts don't divide the model axis get replicated attention weights
    (data-parallel attention) instead of sub-head shards that force GSPMD to
    emit per-chunk collectives inside the flash loops (§Perf iteration 2)."""
    d, nh, kvh, hd = cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.hd
    specs = {
        "wq": ParamSpec((d, nh, hd), ("embed", "heads", None)),
        "wk": ParamSpec((d, kvh, hd), ("embed", "kv_heads", None)),
        "wv": ParamSpec((d, kvh, hd), ("embed", "kv_heads", None)),
        "wo": ParamSpec((nh, hd, d), ("heads", None, "embed")),
    }
    if cfg.qkv_bias:
        specs["bq"] = ParamSpec((nh, hd), ("heads", None), init="zeros")
        specs["bk"] = ParamSpec((kvh, hd), ("kv_heads", None), init="zeros")
        specs["bv"] = ParamSpec((kvh, hd), ("kv_heads", None), init="zeros")
    if cfg.qk_norm:
        specs["q_norm"] = ParamSpec((hd,), (None,), init="zeros")
        specs["k_norm"] = ParamSpec((hd,), (None,), init="zeros")
    return specs


def mlp_specs(cfg, f: Optional[int] = None) -> dict:
    d, f = cfg.d_model, f or cfg.d_ff
    return {
        "w_gate": ParamSpec((d, f), ("embed", "ff")),
        "w_up": ParamSpec((d, f), ("embed", "ff")),
        "w_down": ParamSpec((f, d), ("ff", "embed")),
    }


def moe_specs(cfg) -> dict:
    """Softmax routing: every expert.  Sigmoid routing (``moe_held``): the
    router over all experts, its correction bias, the held experts and the
    shared experts as one MLP."""
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    held = cfg.held_experts if cfg.score_func == "sigmoid" else e
    specs = {
        "router": ParamSpec((d, e), ("embed", None)),
        "w_gate": ParamSpec((held, d, f), ("experts", "embed", "ff")),
        "w_up": ParamSpec((held, d, f), ("experts", "embed", "ff")),
        "w_down": ParamSpec((held, f, d), ("experts", "ff", "embed")),
    }
    if cfg.score_func == "sigmoid":
        specs["router_bias"] = ParamSpec((e,), (None,), init="zeros")
    if cfg.n_shared_experts:
        specs["shared"] = mlp_specs(cfg, cfg.n_shared_experts * f)
    return specs


def mla_specs(cfg) -> dict:
    """Latent attention: ``wq`` (d, H, nope + rope), ``wkv_a`` (d, latent +
    rope) giving the cached row, ``kv_norm`` on its latent part, ``wkv_b``
    (latent, H, nope + v) expanding keys and values, ``wo`` (H, v, d)."""
    d, nh, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    return {
        "wq": ParamSpec((d, nh, cfg.qk_head_dim), ("embed", "heads", None)),
        "wkv_a": ParamSpec((d, r + cfg.qk_rope_head_dim), ("embed", None)),
        "kv_norm": ParamSpec((r,), (None,), init="zeros"),
        "wkv_b": ParamSpec(
            (r, nh, cfg.qk_nope_head_dim + cfg.v_head_dim), (None, "heads", None)
        ),
        "wo": ParamSpec((nh, cfg.v_head_dim, d), ("heads", None, "embed")),
    }


# --------------------------------------------------------------------------
# Masks
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MaskSpec:
    """Declarative attention mask: evaluated blockwise inside the kernel."""

    kind: str  # causal | local | prefix | full
    window: int = 0  # for local
    prefix_len: int = 0  # for prefix (first prefix_len tokens attend fully)

    def __call__(self, q_pos: jax.Array, k_pos: jax.Array) -> jax.Array:
        """(Q,) x (K,) int positions -> (Q, K) bool allow-mask."""
        q = q_pos[:, None]
        k = k_pos[None, :]
        if self.kind == "full":
            return jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
        causal = k <= q
        if self.kind == "causal":
            return causal
        if self.kind == "local":
            return causal & (k > q - self.window)
        if self.kind == "prefix":
            return causal | (k < self.prefix_len)
        raise ValueError(self.kind)


# --------------------------------------------------------------------------
# Flash-style chunked attention (pure JAX; the Pallas twin lives in
# repro/kernels — this version is the oracle and the CPU/compile path)
# --------------------------------------------------------------------------

_NEG_INF = -1e30

from .opt_flags import FLAGS  # noqa: E402  beyond-paper perf switches (see §Perf)


def _flash_attend(
    q: jax.Array,  # (B, Sq, KVH, G, hd)
    k: jax.Array,  # (B, Sk, KVH, hd)
    v: jax.Array,  # (B, Sk, KVH, hd)
    mask: MaskSpec,
    q_pos: jax.Array,  # (Sq,)
    k_pos: jax.Array,  # (Sk,)
    kv_valid: Optional[jax.Array] = None,  # (Sk,) or (B, Sk) bool; cache occupancy / padding
    q_chunk: int = 512,
    kv_chunk: int = 512,
) -> jax.Array:
    """Online-softmax attention, O(chunk^2) memory.  Returns (B,Sq,KVH,G,hd).

    ``kv_valid`` may be shared across the batch ``(Sk,)`` (cache occupancy)
    or per-row ``(B, Sk)`` (ragged true lengths under bucketed prefill,
    DESIGN.md §6) — invalid keys get exactly-zero probability either way."""
    b, sq, kvh, g, hd = q.shape
    sk, dv = k.shape[1], v.shape[-1]  # dv != hd under latent attention
    q_chunk = min(q_chunk, sq)
    kv_chunk = min(kv_chunk, sk)
    scale = hd ** -0.5

    # Pad both sequence dims to chunk multiples; padded KV is masked invalid,
    # padded Q rows are sliced off at the end.
    sq_pad = (-sq) % q_chunk
    sk_pad = (-sk) % kv_chunk
    if kv_valid is None:
        kv_valid = jnp.ones((sk,), bool)
    per_row_valid = kv_valid.ndim == 2
    if sq_pad:
        q = jnp.pad(q, ((0, 0), (0, sq_pad), (0, 0), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, (0, sq_pad))
    if sk_pad:
        k = jnp.pad(k, ((0, 0), (0, sk_pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, sk_pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, (0, sk_pad))
        kv_valid = jnp.pad(
            kv_valid, ((0, 0), (0, sk_pad)) if per_row_valid else (0, sk_pad)
        )
    sq_full, sk_full = sq + sq_pad, sk + sk_pad

    qs = q.reshape(b, sq_full // q_chunk, q_chunk, kvh, g, hd)
    ks = k.reshape(b, sk_full // kv_chunk, kv_chunk, kvh, hd)
    vs = v.reshape(b, sk_full // kv_chunk, kv_chunk, kvh, dv)
    qps = q_pos.reshape(sq_full // q_chunk, q_chunk)
    kps = k_pos.reshape(sk_full // kv_chunk, kv_chunk)
    if per_row_valid:
        # scan axis leads: (nk, B, kv_chunk)
        valid = kv_valid.reshape(b, sk_full // kv_chunk, kv_chunk).swapaxes(0, 1)
    else:
        valid = kv_valid.reshape(sk_full // kv_chunk, kv_chunk)

    def q_step(_, qc):
        qi, qp = qc  # (b, qc, kvh, g, hd), (qc,)

        def kv_step(carry, kc):
            m, l, acc = carry
            ki, vi, kp, va = kc
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qi, ki, preferred_element_type=jnp.float32)
            s = s * scale
            if per_row_valid:
                allow = mask(qp, kp)[None] & va[:, None, :]  # (B, Q, K)
                s = jnp.where(allow[:, None, None], s, _NEG_INF)
            else:
                allow = mask(qp, kp) & va[None, :]
                s = jnp.where(allow[None, None, None], s, _NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            if FLAGS["attn_bf16_probs"]:
                # halve the largest flash intermediate: P and V stream through
                # the MXU in bf16, accumulation stays fp32
                av = jnp.einsum(
                    "bhgqk,bkhd->bhgqd",
                    p.astype(jnp.bfloat16),
                    vi.astype(jnp.bfloat16),
                    preferred_element_type=jnp.float32,
                )
            else:
                av = jnp.einsum("bhgqk,bkhd->bhgqd", p, vi.astype(jnp.float32))
            acc_new = acc * corr[..., None] + av
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, kvh, g, qi.shape[1]), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kvh, g, qi.shape[1]), jnp.float32)
        a0 = jnp.zeros((b, kvh, g, qi.shape[1], dv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0), (ks.swapaxes(0, 1), vs.swapaxes(0, 1), kps, valid)
        )
        out = acc / jnp.maximum(l, 1e-30)[..., None]  # (b, kvh, g, qc, dv)
        return None, out.transpose(0, 3, 1, 2, 4)  # (b, qc, kvh, g, dv)

    _, outs = jax.lax.scan(q_step, None, (qs.swapaxes(0, 1), qps))
    # outs: (nq, b, qc, kvh, g, dv)
    out = outs.swapaxes(0, 1).reshape(b, sq_full, kvh, g, dv)
    return out[:, :sq].astype(q.dtype)


# --------------------------------------------------------------------------
# Flash attention with a hand-written VJP (perf flag "flash_custom_vjp").
#
# Plain jax.grad of the chunked scan stores every per-chunk probability
# tensor (B,H,G,Qc,Kc) as a scan residual — O(Sq*Sk) HBM, exactly what flash
# attention exists to avoid.  The custom VJP saves only (out, m, l) and
# recomputes scores chunk-by-chunk in the backward, the standard
# flash-attention-2 derivation.
# --------------------------------------------------------------------------


def _flash_fwd_chunks(q, k, v, mask, q_pos, k_pos, kv_valid, q_chunk, kv_chunk):
    """Chunked forward that also returns the log-sum-exp stats (m, l)."""
    b, sq, kvh, g, hd = q.shape
    sk = k.shape[1]
    scale = hd**-0.5
    nq, nk = sq // q_chunk, sk // kv_chunk
    qs = q.reshape(b, nq, q_chunk, kvh, g, hd)
    ks = k.reshape(b, nk, kv_chunk, kvh, hd)
    vs = v.reshape(b, nk, kv_chunk, kvh, hd)
    qps = q_pos.reshape(nq, q_chunk)
    kps = k_pos.reshape(nk, kv_chunk)
    valid = kv_valid.reshape(nk, kv_chunk)

    def q_step(_, qc):
        qi, qp = qc

        def kv_step(carry, kc):
            m, l, acc = carry
            ki, vi, kp, va = kc
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qi, ki, preferred_element_type=jnp.float32) * scale
            allow = mask(qp, kp) & va[None, :]
            s = jnp.where(allow[None, None, None], s, _NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            if FLAGS["attn_bf16_probs"]:
                av = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(jnp.bfloat16),
                                vi.astype(jnp.bfloat16), preferred_element_type=jnp.float32)
            else:
                av = jnp.einsum("bhgqk,bkhd->bhgqd", p, vi.astype(jnp.float32))
            return (m_new, l_new, acc * corr[..., None] + av), None

        m0 = jnp.full((b, kvh, g, q_chunk), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kvh, g, q_chunk), jnp.float32)
        a0 = jnp.zeros((b, kvh, g, q_chunk, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0), (ks.swapaxes(0, 1), vs.swapaxes(0, 1), kps, valid)
        )
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, (out.transpose(0, 3, 1, 2, 4), m, l)  # (b,qc,kvh,g,hd)

    _, (outs, ms, ls) = jax.lax.scan(q_step, None, (qs.swapaxes(0, 1), qps))
    out = outs.swapaxes(0, 1).reshape(b, sq, kvh, g, hd)
    # stats shaped (nq, b, kvh, g, q_chunk)
    return out, ms, ls


def _make_flash_vjp(mask, q_chunk, kv_chunk):
    """Build the custom-VJP flash attention for a static (mask, chunking).

    Positions/validity are array *arguments* (zero float0 cotangents), never
    closure captures — closures over tracers leak out of custom_vjp."""

    import numpy as _np

    @jax.custom_vjp
    def flash(q, k, v, q_pos, k_pos, kv_valid):
        out, _, _ = _flash_fwd_chunks(q, k, v, mask, q_pos, k_pos, kv_valid, q_chunk, kv_chunk)
        return out

    def fwd(q, k, v, q_pos, k_pos, kv_valid):
        out, m, l = _flash_fwd_chunks(q, k, v, mask, q_pos, k_pos, kv_valid, q_chunk, kv_chunk)
        return out, (q, k, v, q_pos, k_pos, kv_valid, out, m, l)

    def bwd(res, dout):
        q, k, v, q_pos, k_pos, kv_valid, out, ms, ls = res
        b, sq, kvh, g, hd = q.shape
        sk = k.shape[1]
        scale = hd**-0.5
        nq, nk = sq // q_chunk, sk // kv_chunk
        qs = q.reshape(b, nq, q_chunk, kvh, g, hd).swapaxes(0, 1)
        ks = k.reshape(b, nk, kv_chunk, kvh, hd).swapaxes(0, 1)
        vs = v.reshape(b, nk, kv_chunk, kvh, hd).swapaxes(0, 1)
        dos = dout.reshape(b, nq, q_chunk, kvh, g, hd).swapaxes(0, 1)
        outs = out.reshape(b, nq, q_chunk, kvh, g, hd).swapaxes(0, 1)
        qps = q_pos.reshape(nq, q_chunk)
        kps = k_pos.reshape(nk, kv_chunk)
        valid = kv_valid.reshape(nk, kv_chunk)
        # D_i = rowsum(dO * O): (nq, b, kvh, g, q_chunk)
        ds_stat = jnp.einsum(
            "nbqhgd,nbqhgd->nbhgq", dos.astype(jnp.float32), outs.astype(jnp.float32)
        )

        def kv_step(dq_acc, kc):
            ki, vi, kp, va = kc

            def q_step(carry, qc):
                dkj, dvj = carry
                qi, doi, m, l, di, qp, dqi_prev = qc
                s = scale * jnp.einsum(
                    "bqhgd,bkhd->bhgqk", qi, ki, preferred_element_type=jnp.float32
                )
                allow = mask(qp, kp) & va[None, :]
                s = jnp.where(allow[None, None, None], s, _NEG_INF)
                p = jnp.exp(s - m[..., None]) / jnp.maximum(l, 1e-30)[..., None]
                dp = jnp.einsum(
                    "bqhgd,bkhd->bhgqk", doi.astype(jnp.float32), vi.astype(jnp.float32)
                )
                dsv = p * (dp - di[..., None]) * scale
                if FLAGS["attn_bf16_probs"]:
                    pc, dc = p.astype(jnp.bfloat16), dsv.astype(jnp.bfloat16)
                    dvj = dvj + jnp.einsum("bhgqk,bqhgd->bkhd", pc, doi.astype(jnp.bfloat16),
                                           preferred_element_type=jnp.float32)
                    dkj = dkj + jnp.einsum("bhgqk,bqhgd->bkhd", dc, qi.astype(jnp.bfloat16),
                                           preferred_element_type=jnp.float32)
                    dqi = jnp.einsum("bhgqk,bkhd->bqhgd", dc, ki.astype(jnp.bfloat16),
                                     preferred_element_type=jnp.float32)
                else:
                    dvj = dvj + jnp.einsum("bhgqk,bqhgd->bkhd", p, doi.astype(jnp.float32))
                    dkj = dkj + jnp.einsum("bhgqk,bqhgd->bkhd", dsv, qi.astype(jnp.float32))
                    dqi = jnp.einsum("bhgqk,bkhd->bqhgd", dsv, ki.astype(jnp.float32))
                return (dkj, dvj), dqi_prev + dqi

            dk0 = jnp.zeros((b, kv_chunk, kvh, hd), jnp.float32)
            dv0 = jnp.zeros((b, kv_chunk, kvh, hd), jnp.float32)
            (dkj, dvj), dq_new = jax.lax.scan(
                q_step, (dk0, dv0), (qs, dos, ms, ls, ds_stat, qps, dq_acc)
            )
            return dq_new, (dkj, dvj)

        dq0 = jnp.zeros((nq, b, q_chunk, kvh, g, hd), jnp.float32)
        dq, (dks, dvs) = jax.lax.scan(kv_step, dq0, (ks, vs, kps, valid))
        dq = dq.swapaxes(0, 1).reshape(b, sq, kvh, g, hd).astype(q.dtype)
        dk = dks.swapaxes(0, 1).reshape(b, sk, kvh, hd).astype(k.dtype)
        dv = dvs.swapaxes(0, 1).reshape(b, sk, kvh, hd).astype(v.dtype)
        f0 = lambda a: _np.zeros(a.shape, dtype=jax.dtypes.float0)
        return dq, dk, dv, f0(q_pos), f0(k_pos), f0(kv_valid)

    flash.defvjp(fwd, bwd)
    return flash


def _direct_attend(
    q: jax.Array,  # (B, 1, KVH, G, hd) — single decode token
    k: jax.Array,  # (B, Sk, KVH, hd)
    v: jax.Array,  # (B, Sk, KVH, hd)
    mask: MaskSpec,
    q_pos: jax.Array,  # (1,)
    k_pos: jax.Array,  # (Sk,)
    kv_valid: jax.Array,  # (Sk,)
) -> jax.Array:
    """Unchunked decode attention (beyond-paper perf path).

    Why not the flash scan for decode: chunking reshapes the cache's seq dim,
    and under a seq-sharded KV cache GSPMD must all-gather the whole cache to
    re-chunk it (~GBs per token).  Computed directly, seq stays a *free* dim
    in the QK einsum and a *contracted* dim in the AV einsum, so the only
    collectives are the tiny (B,H,1) softmax reductions and the (B,H,1,hd)
    partial-sum all-reduce — bytes drop by ~3 orders of magnitude."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q, k, preferred_element_type=jnp.float32) * scale
    allow = mask(q_pos, k_pos) & kv_valid[None, :]
    s = jnp.where(allow[None, None, None], s, _NEG_INF)
    m = s.max(axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = p.sum(axis=-1, keepdims=True)
    out = jnp.einsum("bhgqk,bkhd->bhgqd", (p / jnp.maximum(l, 1e-30)), v.astype(jnp.float32))
    return out.transpose(0, 3, 1, 2, 4).astype(q.dtype)  # (B, 1, KVH, G, hd)


# --------------------------------------------------------------------------
# Attention apply (train/prefill + decode-with-cache)
# --------------------------------------------------------------------------


def _project_qkv(p, x, cfg, positions, wmm=None):
    """QKV projection.  ``wmm`` optionally overrides the weight matmuls:
    ``wmm(name, x) -> x @ W_name`` on the flattened head dim — the hook the
    VUSA-packed decode path (serve/packed.py) uses to run the projections
    through the row-packed kernel without forking the rope/bias/norm glue."""
    b, s, d = x.shape
    nh, kvh, hd = cfg.n_heads, cfg.kv_heads, cfg.hd
    if wmm is None:
        q = jnp.einsum("bsd,dnh->bsnh", x, p["wq"].astype(x.dtype))
        k = jnp.einsum("bsd,dnh->bsnh", x, p["wk"].astype(x.dtype))
        v = jnp.einsum("bsd,dnh->bsnh", x, p["wv"].astype(x.dtype))
    else:
        q = wmm("wq", x).reshape(b, s, nh, hd).astype(x.dtype)
        k = wmm("wk", x).reshape(b, s, kvh, hd).astype(x.dtype)
        v = wmm("wv", x).reshape(b, s, kvh, hd).astype(x.dtype)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)[None, None]
        k = k + p["bk"].astype(x.dtype)[None, None]
        v = v + p["bv"].astype(x.dtype)[None, None]
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    sin, cos = rope(positions, hd, cfg.rope_theta)
    q = apply_rope(q, sin, cos)
    k = apply_rope(k, sin, cos)
    q = shard(q, "batch", None, "kv_heads", None)
    return q, k, v




def _maybe_seq_sharded_attention(q, k, v, mask, positions, cfg):
    """Sequence-parallel attention (perf flag "attn_seq_shard").

    When the head count does not divide the model axis, GSPMD either shards
    sub-head (collective storm inside the flash loops) or replicates the
    whole score computation.  Instead: shard_map over the model axis on the
    q-sequence dim — each device runs flash attention for its contiguous
    q-slice against the (small, replicated) K/V.  Returns None when not
    applicable (no mesh / divisible heads / indivisible shapes)."""
    from .common import current_mesh_rules

    mesh, _ = current_mesh_rules()
    b, s, kvh, g, hd = q.shape
    nh = kvh * g
    if (
        not FLAGS["attn_seq_shard"]
        or mesh is None
        or "model" not in mesh.shape
        or mesh.shape["model"] == 1
        # head TP handles it better only when BOTH q-heads and kv-heads
        # shard cleanly; a GQA reshape that splits heads across devices
        # (e.g. kvh=8 on tp=16) reintroduces per-chunk collectives
        or (nh % mesh.shape["model"] == 0 and kvh % mesh.shape["model"] == 0)
        or s % mesh.shape["model"] != 0
    ):
        return None
    tp = mesh.shape["model"]
    dp = [a for a in ("pod", "data") if a in mesh.shape]
    if b % int(np.prod([mesh.shape[a] for a in dp])) != 0:
        return None
    from jax.sharding import PartitionSpec as P

    dp_spec = tuple(dp) if len(dp) > 1 else (dp[0] if dp else None)
    s_loc = s // tp
    chunk = min(512, s_loc)

    def local_attn(q_l, k_l, v_l, qpos_l, kpos_l):
        flash = _make_flash_vjp(mask, chunk, min(512, s))
        valid = jnp.ones((s,), bool)
        return flash(q_l, k_l, v_l, qpos_l, kpos_l, valid)

    fn = jax.shard_map(
        local_attn,
        mesh=mesh,
        in_specs=(
            P(dp_spec, "model", None, None, None),
            P(dp_spec, None, None, None),
            P(dp_spec, None, None, None),
            P("model"),
            P(None),
        ),
        out_specs=P(dp_spec, "model", None, None, None),
        check_vma=False,  # scan carries start as unvarying constants
    )
    return fn(q, k, v, positions, positions).astype(q.dtype)


def attention(
    p: dict,
    x: jax.Array,  # (B, S, d)
    cfg,
    mask: MaskSpec,
    positions: Optional[jax.Array] = None,  # (S,) token positions
    kv_valid: Optional[jax.Array] = None,  # (B, S) bool; padded keys under bucketed prefill
) -> jax.Array:
    """Full-sequence self-attention (train / prefill).

    ``kv_valid`` marks real (non-padding) keys per batch row for masked
    bucketed prefill (DESIGN.md §6).  With right-padding the causal mask
    already hides padding from every valid query, so this is defence in
    depth (and load-bearing for non-causal mask kinds); it is an
    inference-only path and skips the custom-VJP / seq-sharded variants."""
    b, s, _ = x.shape
    nh, kvh, hd = cfg.n_heads, cfg.kv_heads, cfg.hd
    if positions is None:
        positions = jnp.arange(s)
    q, k, v = _project_qkv(p, x, cfg, positions)
    q = q.reshape(b, s, kvh, nh // kvh, hd)
    if kv_valid is not None:
        out = _flash_attend(q, k, v, mask, positions, positions, kv_valid=kv_valid)
        out = out.reshape(b, s, nh, hd)
        return jnp.einsum("bsnh,nhd->bsd", out, p["wo"].astype(x.dtype))
    out = _maybe_seq_sharded_attention(q, k, v, mask, positions, cfg)
    if out is not None:
        pass
    elif FLAGS["flash_custom_vjp"]:
        chunk = min(512, s)
        pad = (-s) % chunk
        qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0))) if pad else q
        kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else k
        vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else v
        pos = jnp.pad(positions, (0, pad)) if pad else positions
        kv_valid = (
            jnp.pad(jnp.ones((s,), bool), (0, pad)) if pad else jnp.ones((s,), bool)
        )
        flash = _make_flash_vjp(mask, chunk, chunk)
        out = flash(qp, kp, vp, pos, pos, kv_valid)[:, :s].astype(x.dtype)
    else:  # baseline: scan autodiff stores per-chunk residuals (see §Perf)
        out = _flash_attend(q, k, v, mask, positions, positions)
    out = out.reshape(b, s, nh, hd)
    return jnp.einsum("bsnh,nhd->bsd", out, p["wo"].astype(x.dtype))


@jax.named_scope("decode.attention")
def attention_decode(
    p: dict,
    x: jax.Array,  # (B, s, d) — s = 1 normal decode; s > 1 speculative verify
    cfg,
    cache: dict,  # {"k": (B, S_max, kvh, hd), "v": ..., "pos": int32 scalar}
    window: int = 0,  # >0: ring cache of this size (local attention)
    chunked: bool = False,  # True = paper-baseline flash scan (see DECODE_CHUNKED)
    wmm=None,  # optional weight-matmul override (see _project_qkv)
) -> tuple[jax.Array, dict]:
    """Decode against a (ring) KV cache.

    The cache may also be *paged* (DESIGN.md §11): ``{"k": (n_blocks, page,
    kvh, hd), "v": ..., "table": (n_pages,) int32, "pos": scalar}``.  The
    block-table gather reconstructs exactly the contiguous ``(1, max_len,
    ...)`` view the slot pool holds (``page`` divides ``max_len``), so from
    here down the math — update slice, validity mask, attend — is the same
    compiled program and tokens stay bit-identical.  Paged mode returns the
    new K/V row as pending writes (``k_new``/``v_new``) instead of a full
    cache: the caller scatters them into the shared arena outside its slot
    vmap.  Ring caches (``window > 0``) are never paged — recurrent/local
    families keep the dense per-slot pool.

    With ``s > 1`` (speculative verify, DESIGN.md §13) the ``s`` tokens
    occupy positions ``pos .. pos+s-1`` and their K/V rows are all written
    before attending.  The attend itself runs one query row at a time with
    exactly the single-token shapes: a batched multi-row attend accumulates
    its contractions in a different order than the Sq=1 dispatch and is NOT
    bitwise against sequential decode (measured: last-ulp drift at Sq=6).
    Per row ``i`` the causal mask at ``q_pos = pos+i`` intersects the
    shared ``slots <= pos+s-1`` validity down to ``slots <= pos+i`` —
    exactly the sequential step's allow set — and masked-but-already-
    written future rows contribute exact zeros (``exp(-inf - m) == 0``),
    so each row is bit-identical to the sequential single-token step.
    Bit-parity of the *surrounding* matmuls is the caller's contract:
    ``wmm`` must be row-stable across row counts (the VUSA Pallas appliers
    are; XLA gemms in general are not — the dense path chains single-token
    steps instead, see ``lm_decode_step``).  Multi-token mode requires a
    contiguous cache (``window == 0``, not paged); the paged scheduler
    gathers a contiguous view first."""
    b, s, d = x.shape
    nh, kvh, hd = cfg.n_heads, cfg.kv_heads, cfg.hd
    pos = cache["pos"]  # scalar int32: number of tokens already in cache
    paged = "table" in cache
    if paged:
        assert window == 0, "paged cache does not support ring/local attention"
        table = cache["table"]  # (n_pages,) int32 block ids
        kb, vb = cache["k"], cache["v"]  # (n_blocks, page, kvh, hd)
        s_max = table.shape[0] * kb.shape[1]
        k_cache = kb[table].reshape(1, s_max, *kb.shape[2:])
        v_cache = vb[table].reshape(1, s_max, *vb.shape[2:])
    else:
        k_cache, v_cache = cache["k"], cache["v"]
        s_max = k_cache.shape[1]
    if s > 1:
        assert window == 0 and not paged, (
            "multi-token decode needs a contiguous full-attention cache"
        )
        positions = pos + jnp.arange(s)
        q, k_new, v_new = _project_qkv(p, x, cfg, positions, wmm=wmm)
        # row-index writes (drop past max_len) — a clamped dynamic slice near
        # the cache end would silently shift the whole write window
        k = k_cache.at[:, positions].set(k_new.astype(k_cache.dtype), mode="drop")
        v = v_cache.at[:, positions].set(v_new.astype(v_cache.dtype), mode="drop")
        slots = jnp.arange(s_max)
        q = q.reshape(b, s, kvh, nh // kvh, hd)
        mask = MaskSpec("causal")
        rows = []
        for i in range(s):  # s = draft_k + 1: small, static — unroll is free
            qi = q[:, i : i + 1]
            pi = positions[i][None]
            valid_i = slots <= pos + i
            if chunked or not FLAGS["decode_direct"]:
                rows.append(_flash_attend(
                    qi, k, v, mask, pi, slots, kv_valid=valid_i,
                    q_chunk=1, kv_chunk=min(512, s_max),
                ))
            else:
                rows.append(_direct_attend(qi, k, v, mask, pi, slots, valid_i))
        out = jnp.concatenate(rows, axis=1)
        out = out.reshape(b, s, nh, hd)
        if wmm is None:
            y = jnp.einsum("bsnh,nhd->bsd", out, p["wo"].astype(x.dtype))
        else:
            y = wmm("wo", out.reshape(b, s, nh * hd)).astype(x.dtype)
        return y, {"k": k, "v": v, "pos": pos + s}
    q, k_new, v_new = _project_qkv(p, x, cfg, pos[None], wmm=wmm)
    slot = jnp.where(window > 0, pos % s_max, pos)
    k = jax.lax.dynamic_update_slice(k_cache, k_new.astype(k_cache.dtype), (0, slot, 0, 0))
    v = jax.lax.dynamic_update_slice(v_cache, v_new.astype(v_cache.dtype), (0, slot, 0, 0))
    # absolute positions of each cache slot
    slots = jnp.arange(s_max)
    if window > 0:
        # ring: slot i holds position p where p % s_max == i and p <= pos
        k_pos = pos - ((pos - slots) % s_max)
        valid = k_pos >= 0
    else:
        k_pos = slots
        valid = slots <= pos
    q = q.reshape(b, 1, kvh, nh // kvh, hd)
    mask = MaskSpec("causal") if window == 0 else MaskSpec("local", window=window)
    if chunked or not FLAGS["decode_direct"]:  # paper-baseline flash path
        out = _flash_attend(
            q, k, v, mask, pos[None], k_pos, kv_valid=valid, q_chunk=1, kv_chunk=min(512, s_max)
        )
    else:
        out = _direct_attend(q, k, v, mask, pos[None], k_pos, valid)
    out = out.reshape(b, 1, nh, hd)
    if wmm is None:
        y = jnp.einsum("bsnh,nhd->bsd", out, p["wo"].astype(x.dtype))
    else:
        y = wmm("wo", out.reshape(b, 1, nh * hd)).astype(x.dtype)
    if paged:
        return y, {
            "k_new": k_new.astype(cache["k"].dtype),
            "v_new": v_new.astype(cache["v"].dtype),
            "pos": pos + 1,
        }
    return y, {"k": k, "v": v, "pos": pos + 1}


def attention_chunk(
    p: dict,
    x: jax.Array,  # (1, C, d) — one prefill chunk of a single request
    cfg,
    arena: dict,  # {"k": (n_blocks, page, kvh, hd), "v": ...} — this layer's blocks
    table: jax.Array,  # (n_pages,) int32 — the request's block table
    start: jax.Array,  # scalar int32: absolute position of the chunk's first token
    true_len: jax.Array,  # scalar int32: real (non-padding) tokens in the chunk
) -> tuple[jax.Array, dict]:
    """Chunked-prefill attention (Sarathi-style, DESIGN.md §11): one chunk of
    a long prompt attends causally over everything already resident in the
    request's block table plus itself.  ``start`` is traced, so one compiled
    program serves every chunk of every prompt at a given static ``C``.

    The chunk's K/V splice into the gathered table view by *row index*
    (padding rows past ``s_max`` drop) rather than a dynamic slice — a
    clamped slice near the cache end would silently shift the write window.
    Returns ``(y, {"k": k_chunk, "v": v_chunk})``, the chunk's rows by
    cache leaf; the caller scatters them into the arena (masking padding and
    prefix-shared rows)."""
    b, c, d = x.shape
    nh, kvh, hd = cfg.n_heads, cfg.kv_heads, cfg.hd
    positions = start + jnp.arange(c)
    q, k_new, v_new = _project_qkv(p, x, cfg, positions)
    arena_k, arena_v = arena["k"], arena["v"]
    s_max = table.shape[0] * arena_k.shape[1]
    k_all = arena_k[table].reshape(1, s_max, *arena_k.shape[2:])
    v_all = arena_v[table].reshape(1, s_max, *arena_v.shape[2:])
    k_all = k_all.at[:, positions].set(k_new.astype(k_all.dtype), mode="drop")
    v_all = v_all.at[:, positions].set(v_new.astype(v_all.dtype), mode="drop")
    rows = jnp.arange(s_max)
    valid = rows < start + true_len
    q = q.reshape(b, c, kvh, nh // kvh, hd)
    out = _flash_attend(
        q, k_all, v_all, MaskSpec("causal"), positions, rows,
        kv_valid=valid, kv_chunk=min(512, s_max),
    )
    out = out.reshape(b, c, nh, hd)
    y = jnp.einsum("bsnh,nhd->bsd", out, p["wo"].astype(x.dtype))
    return y, {"k": k_new, "v": v_new}


# --------------------------------------------------------------------------
# Latent attention (MLA, DeepSeek-V2/V3)
#
# A token's cache row is ``wkv_a``'s output: the normalised latent ``c_kv``
# (kv_lora_rank wide) and one roped key part ``k_pe`` shared by every head.
# Train and prefill expand it through ``wkv_b`` into per-head keys
# [k_nope, k_pe] and values and run the flash path; decode absorbs ``wkv_b``
# into the query and the output instead, so it reads the latent rows as they
# are cached and never forms keys or values for the context.
# --------------------------------------------------------------------------


def _mla_query(p, x, cfg, positions, wmm=None):
    """(q_nope (B, S, H, nope), q_pe (B, S, H, rope) roped)."""
    b, s, _ = x.shape
    nh, dn = cfg.n_heads, cfg.qk_nope_head_dim
    if wmm is None:
        q = jnp.einsum("bsd,dnh->bsnh", x, p["wq"].astype(x.dtype))
    else:
        q = wmm("wq", x).reshape(b, s, nh, cfg.qk_head_dim).astype(x.dtype)
    sin, cos = rope(positions, cfg.qk_rope_head_dim, cfg.rope_theta)
    return q[..., :dn], apply_rope(q[..., dn:], sin, cos)


def mla_latent(p, x, cfg, positions, wmm=None):
    """The cache rows of ``x`` (B, S, d): (B, S, latent + rope) holding the
    normalised ``c_kv`` and the roped shared ``k_pe``."""
    r = cfg.kv_lora_rank
    kv = x @ p["wkv_a"].astype(x.dtype) if wmm is None else wmm("wkv_a", x).astype(x.dtype)
    sin, cos = rope(positions, cfg.qk_rope_head_dim, cfg.rope_theta)
    k_pe = apply_rope(kv[..., None, r:], sin, cos)[..., 0, :]
    return jnp.concatenate([rms_norm(kv[..., :r], p["kv_norm"]), k_pe], axis=-1)


def _mla_expand(p, latent, cfg):
    """Latent rows (B, S, latent + rope) -> per-head keys (B, S, H, nope +
    rope) and values (B, S, H, v)."""
    r, dn = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    kv = jnp.einsum("bsr,rnh->bsnh", latent[..., :r], p["wkv_b"].astype(latent.dtype))
    k_pe = jnp.broadcast_to(
        latent[:, :, None, r:], kv.shape[:3] + (cfg.qk_rope_head_dim,)
    )
    return jnp.concatenate([kv[..., :dn], k_pe], axis=-1), kv[..., dn:]


def _mla_attend(p, x, cfg, q_pos, latent, k_pos, valid, mask):
    """Decompressed attention of ``x``'s queries over ``latent`` rows."""
    b, s, _ = x.shape
    nh = cfg.n_heads
    q_nope, q_pe = _mla_query(p, x, cfg, q_pos)
    q = jnp.concatenate([q_nope, q_pe], axis=-1).reshape(b, s, nh, 1, cfg.qk_head_dim)
    k, v = _mla_expand(p, latent, cfg)
    out = _flash_attend(
        q, k, v, mask, q_pos, k_pos, kv_valid=valid, kv_chunk=min(512, k.shape[1])
    )
    out = out.reshape(b, s, nh, cfg.v_head_dim)
    return jnp.einsum("bsnh,nhd->bsd", out, p["wo"].astype(x.dtype))


def mla_attention(p, x, cfg, mask: MaskSpec, positions=None, kv_valid=None):
    """Full-sequence latent attention (train / prefill), keys and values
    decompressed; ``kv_valid`` as in :func:`attention`."""
    if positions is None:
        positions = jnp.arange(x.shape[1])
    latent = mla_latent(p, x, cfg, positions)
    return _mla_attend(p, x, cfg, positions, latent, positions, kv_valid, mask)


def mla_chunk(p, x, cfg, arena: dict, table, start, true_len):
    """Chunked-prefill latent attention: :func:`attention_chunk` for a
    ``{"latent": (n_blocks, page, latent + rope)}`` arena; the gathered rows
    are decompressed like a prefill's.  Returns ``(y, {"latent": rows})``."""
    c = x.shape[1]
    positions = start + jnp.arange(c)
    new = mla_latent(p, x, cfg, positions)
    a = arena["latent"]
    s_max = table.shape[0] * a.shape[1]
    latent = a[table].reshape(1, s_max, a.shape[-1])
    latent = latent.at[:, positions].set(new.astype(a.dtype), mode="drop")
    rows = jnp.arange(s_max)
    y = _mla_attend(
        p, x, cfg, positions, latent, rows, rows < start + true_len, MaskSpec("causal")
    )
    return y, {"latent": new}


@jax.named_scope("decode.attention")
def mla_decode(p, x, cfg, cache: dict, wmm=None):
    """One-token latent attention against the latent cache, absorbed: the
    scores are ``(q_nope W_UK) . c_kv + q_pe . k_pe`` over the cached rows,
    the output ``(p . c_kv) W_UV`` then ``wo``, with ``W_UK``/``W_UV`` the
    key and value halves of ``wkv_b`` (a bf16 einsum, not packed).

    ``cache`` is ``{"latent": (B, S_max, w), "pos"}`` or paged, ``{"latent":
    (n_blocks, page, w), "table", "pos"}``, the same contract as
    :func:`attention_decode`: the paged form returns the new row as a
    pending write ``latent_new``."""
    b, s, _ = x.shape
    assert s == 1, "latent attention decodes one token per step"
    r, dn, nh = cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.n_heads
    pos = cache["pos"]
    paged = "table" in cache
    a = cache["latent"]
    lat = a[cache["table"]].reshape(1, -1, a.shape[-1]) if paged else a
    q_nope, q_pe = _mla_query(p, x, cfg, pos[None], wmm)
    new = mla_latent(p, x, cfg, pos[None], wmm).astype(a.dtype)
    lat = jax.lax.dynamic_update_slice(lat, new, (0, pos, 0))
    w_kv = p["wkv_b"].astype(x.dtype)
    q_lat = jnp.einsum("bsnh,rnh->bsnr", q_nope, w_kv[..., :dn])
    sc = jnp.einsum("bsnr,btr->bnst", q_lat, lat[..., :r], preferred_element_type=jnp.float32)
    sc = sc + jnp.einsum(
        "bsnh,bth->bnst", q_pe, lat[..., r:], preferred_element_type=jnp.float32
    )
    sc = sc * cfg.qk_head_dim ** -0.5
    valid = jnp.arange(lat.shape[1]) <= pos
    sc = jnp.where(valid, sc, _NEG_INF)
    prob = jax.nn.softmax(sc, axis=-1)
    o_lat = jnp.einsum("bnst,btr->bsnr", prob, lat[..., :r].astype(jnp.float32))
    out = jnp.einsum("bsnr,rnh->bsnh", o_lat.astype(x.dtype), w_kv[..., dn:])
    if wmm is None:
        y = jnp.einsum("bsnh,nhd->bsd", out, p["wo"].astype(x.dtype))
    else:
        y = wmm("wo", out.reshape(b, 1, nh * cfg.v_head_dim)).astype(x.dtype)
    if paged:
        return y, {"latent_new": new, "pos": pos + 1}
    return y, {"latent": lat, "pos": pos + 1}


def cross_attention(
    p: dict,
    x: jax.Array,  # (B, Sq, d) decoder states
    enc_kv: tuple[jax.Array, jax.Array],  # precomputed (k, v): (B, Sk, kvh, hd)
    cfg,
) -> jax.Array:
    b, s, _ = x.shape
    nh, kvh, hd = cfg.n_heads, cfg.kv_heads, cfg.hd
    q = jnp.einsum("bsd,dnh->bsnh", x, p["wq"].astype(x.dtype))
    k, v = enc_kv
    q = q.reshape(b, s, kvh, nh // kvh, hd)
    sk = k.shape[1]
    out = _flash_attend(
        q, k, v, MaskSpec("full"), jnp.arange(s), jnp.arange(sk)
    )
    out = out.reshape(b, s, nh, hd)
    return jnp.einsum("bsnh,nhd->bsd", out, p["wo"].astype(x.dtype))


def encode_cross_kv(p: dict, enc_out: jax.Array, cfg):
    """Precompute cross-attention K/V from encoder output (done once)."""
    b, s, _ = enc_out.shape
    kvh, hd = cfg.kv_heads, cfg.hd
    k = jnp.einsum("bsd,dnh->bsnh", enc_out, p["wk"].astype(enc_out.dtype))
    v = jnp.einsum("bsd,dnh->bsnh", enc_out, p["wv"].astype(enc_out.dtype))
    return k, v


# --------------------------------------------------------------------------
# MLP / MoE
# --------------------------------------------------------------------------


def mlp(p: dict, x: jax.Array) -> jax.Array:
    h = jax.nn.silu(x @ p["w_gate"].astype(x.dtype)) * (x @ p["w_up"].astype(x.dtype))
    h = shard(h, "batch", None, "ff")
    return h @ p["w_down"].astype(x.dtype)


def moe(p: dict, x: jax.Array, cfg, capacity_factor: float | None = None):
    """Top-k MoE with capacity-bounded scatter dispatch (token-dropping).

    Returns (y, aux_loss).  Expert dim shards over "model" (EP); the
    scatter/gather pair is what GSPMD turns into all-to-alls.
    """
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    t = b * s
    xf = x.reshape(t, d)
    logits = (xf @ p["router"].astype(jnp.float32)).astype(jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_w, eids = jax.lax.top_k(probs, k)  # (T, k)
    gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)

    # load-balance aux loss (Switch): E * sum_e f_e * p_e
    me = probs.mean(0)
    ce = jnp.zeros((e,), jnp.float32).at[eids.reshape(-1)].add(1.0) / (t * k)
    aux = e * jnp.sum(me * ce)

    cf = cfg.moe_cf if capacity_factor is None else capacity_factor
    cap = int(cf * t * k / e) + 1
    flat_e = eids.reshape(-1)  # (T*k,)
    if FLAGS["moe_sort_positions"]:
        # position-in-expert via stable sort: O(T log T) int32 traffic vs the
        # O(T*E) one-hot cumsum of the baseline
        order = jnp.argsort(flat_e, stable=True)  # (T*k,)
        sorted_e = flat_e[order]
        run_start = jnp.searchsorted(sorted_e, sorted_e, side="left")
        pos_sorted = jnp.arange(flat_e.shape[0], dtype=jnp.int32) - run_start.astype(jnp.int32)
        pos = jnp.zeros_like(pos_sorted).at[order].set(pos_sorted)
    else:
        onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)  # (T*k, E)
        pos = (jnp.cumsum(onehot, axis=0) - 1)  # running count per expert
        pos = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]  # (T*k,)
    if FLAGS["moe_shard_capacity"]:
        # round the buffer so the capacity dim shards over the data axis —
        # otherwise every data-row recomputes all experts (16x waste); the
        # final 256-slot block is dump space for dropped tokens
        cap = ((cap + 255) // 256) * 256
        n_slots = cap + 256
    else:
        n_slots = cap + 1  # baseline: single dump slot (indivisible!)
    dropped = pos >= cap
    pos = jnp.where(dropped, cap, pos)  # dump slot

    buf = jnp.zeros((e, n_slots, d), x.dtype)
    xk = jnp.repeat(xf, k, axis=0)  # (T*k, d)
    if FLAGS["moe_shard_capacity"]:
        # two-step dispatch: scatter into model-sharded per-expert partials
        # (local, no comm), then constrain to (experts x capacity) sharding —
        # GSPMD lowers the transition as a reduce-scatter over data instead
        # of materialising full replicas
        xk = shard(xk, "batch", None)
        buf = buf.at[flat_e, pos].add(xk)
        buf = shard(buf, "experts", None, None)
        # barrier stops GSPMD from propagating the 2-D sharding back into
        # the scatter (which would materialise full replicas + all-reduce);
        # the transition below is then a *local slice* per data-row
        buf = jax.lax.optimization_barrier(buf)
        buf = shard(buf, "experts", "batch", None)
    else:
        buf = buf.at[flat_e, pos].add(xk)
        buf = shard(buf, "experts", None, None)

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["w_gate"].astype(x.dtype)))
    h = h * jnp.einsum("ecd,edf->ecf", buf, p["w_up"].astype(x.dtype))
    if FLAGS["moe_shard_capacity"]:
        h = shard(h, "experts", "batch", "ff")
    out = jnp.einsum("ecf,efd->ecd", h, p["w_down"].astype(x.dtype))  # (E, slots, d)
    if FLAGS["moe_shard_capacity"]:
        out = jax.lax.optimization_barrier(out)
        out = shard(out, "experts", None, None)  # all-gather once for the token gather

    y = out[flat_e, pos]  # (T*k, d)
    w = jnp.where(dropped, 0.0, gate_w.reshape(-1)).astype(x.dtype)
    y = (y * w[:, None]).reshape(t, k, d).sum(axis=1)
    return y.reshape(b, s, d), aux


def route(p: dict, x: jax.Array, cfg):
    """Sigmoid routing (DeepSeek-V3 ``noaux_tc``, one group) of rows ``x``
    (T, d), in float32 over all ``n_experts``: experts chosen by top-k of
    ``s + router_bias``, each weighted by its score ``s`` alone, normalised
    over the chosen (``norm_topk_prob``), times ``routed_scale``.  Returns
    ``(gates (T, held), rows)``: each held expert's weight per row (0 where
    it was not chosen) and the number of (row, held expert) pairs chosen."""
    s = jax.nn.sigmoid(x.astype(jnp.float32) @ p["router"].astype(jnp.float32))
    _, ids = jax.lax.top_k(s + p["router_bias"].astype(jnp.float32), cfg.top_k)
    w = jnp.take_along_axis(s, ids, axis=-1)
    if cfg.norm_topk_prob:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    w = w * cfg.routed_scale
    mine = ids[..., None] == jnp.arange(cfg.held_experts)  # (T, k, held)
    gates = jnp.sum(jnp.where(mine, w[..., None], 0.0), axis=-2)
    return gates, jnp.sum(mine, dtype=jnp.int32)


def held_experts(p: dict, x: jax.Array, gates: jax.Array) -> jax.Array:
    """Sum over the held experts of each expert's SwiGLU output on ``x``
    (T, d) times its gate (T, held), in float32.  Dropless: every row runs
    through every held expert, a zero gate leaving it out."""

    def one(y, e):
        wg, wu, wd, g = e
        h = jax.nn.silu(x @ wg.astype(x.dtype)) * (x @ wu.astype(x.dtype))
        return y + (h @ wd.astype(x.dtype)).astype(jnp.float32) * g[:, None], None

    y0 = jnp.zeros(x.shape, jnp.float32)
    y, _ = jax.lax.scan(one, y0, (p["w_gate"], p["w_up"], p["w_down"], gates.T))
    return y


def moe_held(p: dict, x: jax.Array, cfg):
    """Expert layer of an expert-parallel deployment, on the chip that holds
    the first ``n_experts / ep_size`` experts: route over all experts
    (:func:`route`), add the held experts' part for the rows routed to them
    and the shared experts once.  The result is this chip's partial sum; the
    other chips' experts, and the exchange that would add their parts, are
    not here.  Returns ``(y, 0.0)``: the routing has no auxiliary loss."""
    b, s, d = x.shape
    xf = x.reshape(b * s, d)
    gates, _ = route(p, xf, cfg)
    y = held_experts(p, xf, gates).astype(x.dtype).reshape(b, s, d)
    if "shared" in p:
        y = y + mlp(p["shared"], x)
    return y, 0.0
