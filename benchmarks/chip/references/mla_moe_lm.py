"""Plain float32 reference of a decoder LM with latent attention and
sigmoid-routed experts, as DeepSeek-V3 describes it and Moonlight-16B-A3B
configures it: leading dense layers, then expert layers of routed and
shared experts; pre-norm RMSNorm throughout, untied head.

- Latent attention (MLA, no query compression): ``q = h wq``, per head
  ``nope`` dims without rotary and ``rope`` dims with it; ``h wkv_a`` gives
  the latent ``c_kv`` (RMS-normalised) and one roped key part ``k_pe``
  shared by the heads; ``c_kv wkv_b`` gives each head's ``k_nope`` and
  ``v``; keys are ``[k_nope, k_pe]``, scores scaled by (nope + rope)^-1/2,
  the heads' outputs through ``wo``.  Keys and values are decompressed here
  for every position.
- Routing (``noaux_tc`` with one group): ``s = sigmoid(h router)`` in
  float32; the experts are the top ``num_experts_per_tok`` of ``s +
  router_bias`` (``e_score_correction_bias``); each is weighted by its
  ``s`` alone, normalised over the chosen, times
  ``routed_scaling_factor``.
- The chip's share of an expert-parallel deployment (``ep_size`` chips
  share each expert layer): the weights hold the first ``n_routed_experts /
  ep_size`` experts, and each token gets their part of the routed sum; the
  shared experts, one SwiGLU of ``n_shared_experts x moe_intermediate_size``,
  are added once.  What the other chips' experts add is left out, as in the
  program, and that partial sum goes on to the next layer.

Departures from the published description, each a relabelling the result
does not depend on: rotary embeddings rotate the two halves of the rope
dims, where DeepSeek-V3 rotates interleaved pairs (a fixed permutation of
``wq``'s and ``wkv_a``'s rope columns, which a weight loader would apply);
norm scales are stored as ``1 + w``.  It imports nothing of the program
under test: no cache, no kernels, no batching.  ``logits(weights, tokens,
model)`` runs one whole sequence causally in float32 with ``highest``
matmul precision, one layer at a time under a scan per layer stack.

Like every family module it is also the benchmark's whole description of
the family: ``PROGRAM_KEYS``, ``check_program``, ``layout``,
``packed_calls`` (under the kernel names the program's calls carry) and
``decode_flops`` / ``prefill_flops``.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

import work
from weights import Leaf, pad_vocab

EXPERT_KERNEL = "vusa_fused_moe_matmul"

PROGRAM_KEYS = {  # configuration key -> ArchConfig field it must equal
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "num_attention_heads": "n_heads", "num_key_value_heads": "kv_heads",
    "intermediate_size": "dense_d_ff", "moe_intermediate_size": "d_ff",
    "first_k_dense_replace": "n_dense_layers", "n_routed_experts": "n_experts",
    "num_experts_per_tok": "top_k", "n_shared_experts": "n_shared_experts",
    "ep_size": "ep_size", "scoring_func": "score_func", "norm_topk_prob": "norm_topk_prob",
    "routed_scaling_factor": "routed_scale", "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim", "qk_rope_head_dim": "qk_rope_head_dim",
    "v_head_dim": "v_head_dim", "vocab_size": "vocab", "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings",
}


def check_program(arch) -> None:
    """Refuse a program configuration this reference does not compute."""
    if arch.family != "moe" or arch.score_func != "sigmoid" or not arch.mla:
        raise ValueError(f"{arch.name}: the reference covers latent attention with "
                         "sigmoid-routed held experts")
    if arch.qkv_bias or arch.qk_norm:
        raise ValueError(f"{arch.name}: the reference covers no qkv bias or qk norm")


def sizes(m: dict) -> dict:
    ld = m["first_k_dense_replace"]
    return {
        "ld": ld, "le": m["num_hidden_layers"] - ld, "d": m["hidden_size"],
        "h": m["num_attention_heads"], "r": m["kv_lora_rank"], "dn": m["qk_nope_head_dim"],
        "dr": m["qk_rope_head_dim"], "dv": m["v_head_dim"], "fd": m["intermediate_size"],
        "fe": m["moe_intermediate_size"], "e": m["n_routed_experts"],
        "held": m["n_routed_experts"] // m["ep_size"], "k": m["num_experts_per_tok"],
        "fs": m["n_shared_experts"] * m["moe_intermediate_size"],
    }


def layout(m: dict) -> dict:
    """{path: Leaf} of the served program's weight tree: ``embed``,
    ``lm_head``, ``final_norm``; per layer stack (``dense_layers`` of
    ``first_k_dense_replace`` layers, then ``layers``) ``norm1``, ``norm2``,
    ``attn/{wq (d, H, nope+rope), wkv_a (d, latent+rope), kv_norm,
    wkv_b (latent, H, nope+v), wo (H, v, d)}``; the dense stack's
    ``ffn/{w_gate, w_up, w_down}`` of ``intermediate_size``; the expert
    stack's ``ffn/router (d, E)``, ``ffn/router_bias (E,)``, the held
    experts ``ffn/{w_gate, w_up} (E_held, d, f)``, ``w_down (E_held, f, d)``
    (two stacked axes) and ``ffn/shared/*``."""
    z = sizes(m)
    d, h, r, dr = z["d"], z["h"], z["r"], z["dr"]
    v = pad_vocab(m["vocab_size"])
    out = {
        "embed": Leaf((v, d), None, "normal", vocab_axis=0),
        "final_norm": Leaf((d,), None, "zeros"),
        "lm_head": Leaf((d, v), d, "pruned", vocab_axis=1),
    }
    for key, n in (("dense_layers", z["ld"]), ("layers", z["le"])):

        def stack(shape, fan_in=None, init="pruned", stacks=1):
            return Leaf((n, *shape), fan_in, init, stacks=stacks)

        out.update({
            f"{key}/norm1": stack((d,), init="zeros"),
            f"{key}/norm2": stack((d,), init="zeros"),
            f"{key}/attn/wq": stack((d, h, z["dn"] + dr), d),
            f"{key}/attn/wkv_a": stack((d, r + dr), d),
            f"{key}/attn/kv_norm": stack((r,), init="zeros"),
            f"{key}/attn/wkv_b": stack((r, h, z["dn"] + z["dv"]), r),
            f"{key}/attn/wo": stack((h, z["dv"], d), h * z["dv"]),
        })
    for key, n, f in (("dense_layers/ffn", z["ld"], z["fd"]),
                      ("layers/ffn/shared", z["le"], z["fs"])):
        out.update({
            f"{key}/w_gate": Leaf((n, d, f), d, "pruned", stacks=1),
            f"{key}/w_up": Leaf((n, d, f), d, "pruned", stacks=1),
            f"{key}/w_down": Leaf((n, f, d), f, "pruned", stacks=1),
        })
    le, e, held, fe = z["le"], z["e"], z["held"], z["fe"]
    out.update({
        "layers/ffn/router": Leaf((le, d, e), d, "scaled", stacks=1),
        "layers/ffn/router_bias": Leaf((le, e), e, "scaled", stacks=1),
        "layers/ffn/w_gate": Leaf((le, held, d, fe), d, "pruned", stacks=2),
        "layers/ffn/w_up": Leaf((le, held, d, fe), d, "pruned", stacks=2),
        "layers/ffn/w_down": Leaf((le, held, fe, d), fe, "pruned", stacks=2),
    })
    return out


def packed_calls(model: dict, nnz: dict, rows: int, values: str) -> dict:
    """The calls of one decode step, per kernel, at ``rows`` rows (one per
    slot): ``vusa_packed_matmul`` runs each layer's wq, wkv_a and wo and the
    head; ``vusa_fused_mlp_matmul`` the dense layers' MLP and each expert
    layer's shared experts; the grouped kernel one call per expert layer,
    counted as the held experts' fused MLPs at the rows routing gives each
    (``ceil(rows x experts per token / experts)``), each pack once.  The
    pack's bytes are counted once per call, however many rows it serves."""
    z = sizes(model)
    d, h, r, dr = z["d"], z["h"], z["r"], z["dr"]
    packed, fused, grouped = [], [], []
    per_expert = -(-rows * z["k"] // z["e"])
    for key, n, f in (("dense_layers", z["ld"], z["fd"]), ("layers", z["le"], z["fs"])):
        ffn = "dense_layers/ffn" if key == "dense_layers" else "layers/ffn/shared"
        for i in range(n):
            for name, k, c in (("wq", d, h * (z["dn"] + dr)), ("wkv_a", d, r + dr),
                               ("wo", h * z["dv"], d)):
                packed.append(work.linear(rows, k, c, float(nnz[f"{key}/attn/{name}"][i]),
                                          values))
            nz = sum(float(nnz[f"{ffn}/{w}"][i]) for w in ("w_gate", "w_up", "w_down"))
            fused.append(work.fused_mlp(rows, d, f, nz, values))
    for i in range(z["le"]):
        calls = [work.fused_mlp(per_expert, d, z["fe"],
                                sum(float(nnz[f"layers/ffn/{w}"][i, j])
                                    for w in ("w_gate", "w_up", "w_down")), values)
                 for j in range(z["held"])]
        grouped.append(work.Call(flops=sum(c.flops for c in calls),
                                 bytes=sum(c.bytes for c in calls)))
    packed.append(work.linear(rows, d, model["vocab_size"], float(nnz["lm_head"]), values))
    return {"vusa_packed_matmul": packed, "vusa_fused_mlp_matmul": fused,
            EXPERT_KERNEL: grouped}


def dense_params(model: dict) -> tuple:
    """(matmul parameters a token meets in the layers on this chip, of the
    head), dense-equivalent: every attention matrix, the dense MLPs, and in
    each expert layer the router, the shared experts and the held experts'
    share of the routed ones (experts per token x held / experts)."""
    z = sizes(model)
    d, h = z["d"], z["h"]
    attn = (d * h * (z["dn"] + z["dr"]) + d * (z["r"] + z["dr"])
            + z["r"] * h * (z["dn"] + z["dv"]) + h * z["dv"] * d)
    routed = z["k"] * z["held"] / z["e"] * 3 * d * z["fe"]
    body = (z["ld"] * (attn + 3 * d * z["fd"])
            + z["le"] * (attn + d * z["e"] + 3 * d * z["fs"] + routed))
    return body, d * model["vocab_size"]


def attn_flops(model: dict, ctx: float) -> float:
    """Scores and values of one token against ``ctx`` positions, all
    layers, with keys and values decompressed."""
    z = sizes(model)
    return 2.0 * model["num_hidden_layers"] * z["h"] * (z["dn"] + z["dr"] + z["dv"]) * ctx


def decode_flops(model: dict, ctx: int) -> float:
    body, head = dense_params(model)
    return 2.0 * (body + head) + attn_flops(model, ctx)


def prefill_flops(model: dict, n: int) -> float:
    body, head = dense_params(model)
    return 2.0 * body * n + 2.0 * head + attn_flops(model, n * (n + 1) / 2)


def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def rope(x, positions, theta):
    """Rotate (S, ..., dr) by position; halves convention (x1, x2) -> (x1 c - x2 s, x2 c + x1 s)."""
    dr = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, dr // 2, dtype=jnp.float32) / (dr // 2))
    ang = positions.astype(jnp.float32)[:, None] * freqs
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (dr // 2,)
    sin, cos = jnp.sin(ang).reshape(shape), jnp.cos(ang).reshape(shape)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def swiglu(h, f):
    return (jax.nn.silu(h @ f["w_gate"]) * (h @ f["w_up"])) @ f["w_down"]


def attention(h, a, positions, m):
    z = sizes(m)
    s = h.shape[0]
    q = jnp.einsum("sd,dnh->snh", h, a["wq"])
    q = jnp.concatenate([q[..., :z["dn"]], rope(q[..., z["dn"]:], positions, m["rope_theta"])],
                        axis=-1)
    kv_a = h @ a["wkv_a"]
    c = rms_norm(kv_a[:, :z["r"]], a["kv_norm"], m["rms_norm_eps"])
    k_pe = rope(kv_a[:, z["r"]:], positions, m["rope_theta"])
    kv = jnp.einsum("sr,rnh->snh", c, a["wkv_b"])
    k = jnp.concatenate(
        [kv[..., :z["dn"]], jnp.broadcast_to(k_pe[:, None], (s, z["h"], z["dr"]))], axis=-1)
    v = kv[..., z["dn"]:]
    sc = jnp.einsum("qnh,knh->nqk", q, k) / math.sqrt(z["dn"] + z["dr"])
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
    return jnp.einsum("snh,nhd->sd", jnp.einsum("nqk,knh->qnh", p, v), a["wo"])


def experts(h, f, m):
    """The held experts' part of the routed sum, and the shared experts."""
    z = sizes(m)
    s = jax.nn.sigmoid(h @ f["router"])
    _, ids = jax.lax.top_k(s + f["router_bias"], z["k"])
    w = jnp.take_along_axis(s, ids, axis=-1)
    if m["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    w = w * m["routed_scaling_factor"]
    y = swiglu(h, f["shared"])
    for j in range(z["held"]):
        g = jnp.sum(jnp.where(ids == j, w, 0.0), axis=-1)
        y = y + g[:, None] * swiglu(h, {n: f[n][j] for n in ("w_gate", "w_up", "w_down")})
    return y


def layer(x, lw, positions, m, dense):
    eps = m["rms_norm_eps"]
    x = x + attention(rms_norm(x, lw["norm1"], eps), lw["attn"], positions, m)
    h = rms_norm(x, lw["norm2"], eps)
    return x + (swiglu(h, lw["ffn"]) if dense else experts(h, lw["ffn"], m))


@partial(jax.jit, static_argnums=(2,))
def _logits(weights, tokens, mkey):
    m = dict(mkey)
    w = jax.tree.map(lambda a: a.astype(jnp.float32), weights)
    positions = jnp.arange(tokens.shape[0])
    x = w["embed"][tokens]
    for key, dense in (("dense_layers", True), ("layers", False)):

        def body(x, lw, dense=dense):
            return layer(x, lw, positions, m, dense), None

        x, _ = jax.lax.scan(body, x, w[key])
    x = rms_norm(x, w["final_norm"], m["rms_norm_eps"])
    return x @ w["lm_head"]


def logits(weights, tokens, model: dict):
    """(S, V) float32 logits of one causal sequence ``tokens`` (S,)."""
    keys = ("first_k_dense_replace", "num_hidden_layers", "hidden_size",
            "num_attention_heads", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "intermediate_size", "moe_intermediate_size", "n_routed_experts",
            "ep_size", "num_experts_per_tok", "n_shared_experts", "norm_topk_prob",
            "routed_scaling_factor", "rms_norm_eps", "rope_theta")
    with jax.default_matmul_precision("highest"):
        return _logits(weights, tokens, tuple((k, model[k]) for k in keys))
