"""Plain float32 reference of a dense decoder-only LM (pre-norm, RMSNorm,
rotary attention with grouped KV heads, SwiGLU MLP, untied head).

It follows the layer equations of the configuration it is given and nothing
of the program under test: no import of it, no cache, no kernels, no
batching.  ``logits(weights, tokens, model)`` runs one whole sequence
causally and returns the logits at every position, in float32 with
``highest`` matmul precision (on the TPU a float32 matmul otherwise runs in
bf16 passes).  Weights are read in the layout ``weights.py`` documents; the
layers run one at a time under a scan, so only one layer is ever expanded.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def rope(x, positions, theta):
    """Rotate (S, H, hd) by position; halves convention (x1, x2) -> (x1 c - x2 s, x2 c + x1 s)."""
    hd = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, hd // 2, dtype=jnp.float32) / (hd // 2))
    ang = positions[:, None].astype(jnp.float32) * freqs
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def layer(x, lw, positions, m):
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    h_, kv_ = m["num_attention_heads"], m["num_key_value_heads"]
    s = x.shape[0]
    a = lw["attn"]
    h = rms_norm(x, lw["norm1"], eps)
    q = rope(jnp.einsum("sd,dnh->snh", h, a["wq"]), positions, theta)
    k = rope(jnp.einsum("sd,dnh->snh", h, a["wk"]), positions, theta)
    v = jnp.einsum("sd,dnh->snh", h, a["wv"])
    g = h_ // kv_
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    sc = jnp.einsum("qnh,knh->nqk", q, k) / jnp.sqrt(jnp.float32(q.shape[-1]))
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    sc = jnp.where(causal[None], sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("nqk,knh->qnh", p, v)
    x = x + jnp.einsum("snh,nhd->sd", o, a["wo"])
    f = lw["ffn"]
    h = rms_norm(x, lw["norm2"], eps)
    y = (jax.nn.silu(h @ f["w_gate"]) * (h @ f["w_up"])) @ f["w_down"]
    return x + y


@partial(jax.jit, static_argnums=(2,))
def _logits(weights, tokens, mkey):
    m = dict(mkey)
    w = jax.tree.map(lambda a: a.astype(jnp.float32), weights)
    positions = jnp.arange(tokens.shape[0])
    x = w["embed"][tokens]

    def body(x, lw):
        return layer(x, lw, positions, m), None

    x, _ = jax.lax.scan(body, x, w["layers"])
    x = rms_norm(x, w["final_norm"], m["rms_norm_eps"])
    return x @ w["lm_head"]


def logits(weights, tokens, model: dict):
    """(S, V) float32 logits of one causal sequence ``tokens`` (S,)."""
    keys = ("num_attention_heads", "num_key_value_heads", "rms_norm_eps", "rope_theta")
    with jax.default_matmul_precision("highest"):
        return _logits(weights, tokens, tuple((k, model[k]) for k in keys))
