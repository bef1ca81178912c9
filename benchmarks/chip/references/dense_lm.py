"""Plain float32 reference of a dense decoder-only LM (pre-norm, RMSNorm,
rotary attention with grouped KV heads, SwiGLU MLP, untied head).

It follows the layer equations of the configuration it is given and nothing
of the program under test: no import of it, no cache, no kernels, no
batching.  ``logits(weights, tokens, model)`` runs one whole sequence
causally and returns the logits at every position, in float32 with
``highest`` matmul precision (on the TPU a float32 matmul otherwise runs in
bf16 passes).  Weights are read in the tree ``layout`` describes; the
layers run one at a time under a scan, so only one layer is ever expanded.

The module is also the benchmark's whole description of the family: the
harness knows it only through ``PROGRAM_KEYS`` and ``check_program``
(which program configurations it stands for), ``layout`` (the weight tree,
for ``weights.py``), ``packed_calls`` (the packed-kernel calls of a decode
step by kernel name, for the rooflines and for finding the kernels in a
trace) and ``decode_flops`` / ``prefill_flops`` (model FLOPs, for
``step_mfu``).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

import work
from weights import Leaf, pad_vocab

PROGRAM_KEYS = {  # configuration key -> ArchConfig field it must equal
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "num_attention_heads": "n_heads", "num_key_value_heads": "kv_heads",
    "head_dim": "hd", "intermediate_size": "d_ff", "vocab_size": "vocab",
    "rope_theta": "rope_theta", "tie_word_embeddings": "tie_embeddings",
}


def check_program(arch) -> None:
    """Refuse a program configuration this reference does not compute."""
    if arch.family != "dense" or arch.qkv_bias or arch.qk_norm:
        raise ValueError(f"{arch.name}: the dense reference covers no qkv bias or qk norm")


def layout(m: dict) -> dict:
    """{path: Leaf} of the weight tree the served program takes
    (``Engine(cfg, params)``) and ``logits`` reads: ``embed (V, d)``,
    ``lm_head (d, V)``, ``final_norm (d,)`` and per-layer stacks
    ``layers/attn/{wq, wk, wv} (L, d, H, hd)``, ``wo (L, H, hd, d)``,
    ``layers/ffn/{w_gate, w_up} (L, d, f)``, ``w_down (L, f, d)``,
    ``norm1``/``norm2 (L, d)``.  ``V`` is the vocabulary padded to a
    multiple of 256, as the program lays it out."""
    L, d, f = m["num_hidden_layers"], m["hidden_size"], m["intermediate_size"]
    h, kv, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    v = pad_vocab(m["vocab_size"])

    def stack(shape, fan_in=None, init="pruned"):
        return Leaf((L, *shape), fan_in, init, stacks=1)

    return {
        "embed": Leaf((v, d), None, "normal", vocab_axis=0),
        "final_norm": Leaf((d,), None, "zeros"),
        "lm_head": Leaf((d, v), d, "pruned", vocab_axis=1),
        "layers/norm1": stack((d,), init="zeros"),
        "layers/norm2": stack((d,), init="zeros"),
        "layers/attn/wq": stack((d, h, hd), d),
        "layers/attn/wk": stack((d, kv, hd), d),
        "layers/attn/wv": stack((d, kv, hd), d),
        "layers/attn/wo": stack((h, hd, d), h * hd),
        "layers/ffn/w_gate": stack((d, f), d),
        "layers/ffn/w_up": stack((d, f), d),
        "layers/ffn/w_down": stack((f, d), f),
    }


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
            packed.append(work.linear(rows, k, n, float(nnz[f"layers/attn/{name}"][layer]),
                                      values))
        z = sum(float(nnz[f"layers/ffn/{n}"][layer]) for n in ("w_gate", "w_up", "w_down"))
        fused.append(work.fused_mlp(rows, d, f, z, values))
    packed.append(work.linear(rows, d, model["vocab_size"], float(nnz["lm_head"]), values))
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
    """One decoded token at context ``ctx``: 2 per matmul parameter, and
    attention over the context."""
    body, head = dense_params(model)
    return 2.0 * (body + head) + attn_flops(model, ctx)


def prefill_flops(model: dict, n: int) -> float:
    """A prompt of ``n`` tokens: every token through the layers, causal
    attention, and the head at the last position only."""
    body, head = dense_params(model)
    return 2.0 * body * n + 2.0 * head + attn_flops(model, n * (n + 1) / 2)


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
