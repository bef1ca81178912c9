"""Seeded, magnitude-pruned weights for a dense decoder LM, made on the
device in one jitted call.

The layout is the one the served program takes (``Engine(cfg, params)``)
and the one the plain reference reads: ``embed (V, d)``, ``lm_head (d, V)``,
``final_norm (d,)`` and per-layer stacks ``layers/attn/{wq, wk, wv}
(L, d, H, hd)``, ``wo (L, H, hd, d)``, ``layers/ffn/{w_gate, w_up} (L, d, f)``,
``w_down (L, f, d)``, ``norm1``/``norm2 (L, d)``.  ``V`` is the vocabulary
padded to a multiple of 256, as the program lays it out.

Weights are stored in the configuration's ``torch_dtype`` (bf16 for both
models, as they are published and served).  Every matrix is drawn
N(0, s^2) in float32 and pruned to the configured unstructured
sparsity by magnitude: entries with |w| < t * s are zeroed, t being the
two-sided normal quantile of the sparsity (what a magnitude prune of a
Gaussian matrix keeps).  ``s`` is chosen so that the kept entries have
variance 1 / fan_in, the fan-in being the matrix's true input width, so the
random network keeps its activations at unit scale through depth instead of
turning chaotic: with attention logits of order 1, bf16 rounding moves the
served logits by a few hundredths of their spread, and a comparison with a
float32 reference has something to separate.  Norm scales are zero (the
program multiplies by ``1 + scale``); the padded vocabulary rows and columns
are zero, so no served token falls outside the vocabulary.
"""

from __future__ import annotations

import math
from functools import partial
from statistics import NormalDist

import jax
import jax.numpy as jnp
import numpy as np


def pad_vocab(v: int, multiple: int = 256) -> int:
    return -(-v // multiple) * multiple


def prune_threshold(sparsity: float) -> float:
    """|z| below which a standard normal entry is pruned at ``sparsity``."""
    return NormalDist().inv_cdf(0.5 + sparsity / 2) if sparsity > 0 else 0.0


def kept_variance(t: float) -> float:
    """E[z^2 ; |z| >= t] for a standard normal z."""
    nd = NormalDist()
    return 2.0 * (t * nd.pdf(t) + (1.0 - nd.cdf(t))) if t > 0 else 1.0


def shapes(m: dict) -> dict:
    """{path: (shape, fan_in or None)} of every leaf; fan_in None = not pruned."""
    L, d, f = m["num_hidden_layers"], m["hidden_size"], m["intermediate_size"]
    h, kv, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    v = pad_vocab(m["vocab_size"])
    return {
        "embed": ((v, d), None),
        "final_norm": ((d,), None),
        "lm_head": ((d, v), d),
        "layers/norm1": ((L, d), None),
        "layers/norm2": ((L, d), None),
        "layers/attn/wq": ((L, d, h, hd), d),
        "layers/attn/wk": ((L, d, kv, hd), d),
        "layers/attn/wv": ((L, d, kv, hd), d),
        "layers/attn/wo": ((L, h, hd, d), h * hd),
        "layers/ffn/w_gate": ((L, d, f), d),
        "layers/ffn/w_up": ((L, d, f), d),
        "layers/ffn/w_down": ((L, f, d), f),
    }


def nest(flat: dict) -> dict:
    out: dict = {}
    for path, leaf in flat.items():
        node = out
        *head, last = path.split("/")
        for k in head:
            node = node.setdefault(k, {})
        node[last] = leaf
    return out


def flatten(tree: dict, prefix: str = "") -> dict:
    out = {}
    for k, v in tree.items():
        p = f"{prefix}/{k}" if prefix else k
        out.update(flatten(v, p) if isinstance(v, dict) else {p: v})
    return out


@partial(jax.jit, static_argnums=(1, 2, 3))
def _make(key, spec: tuple, sparsity: float, dtype: str):
    spec = dict(spec)
    t = prune_threshold(sparsity)
    scale_kept = math.sqrt(kept_variance(t))
    vocab = spec.pop("__vocab__")
    out = {}
    for i, (path, (shape, fan_in)) in enumerate(sorted(spec.items())):
        k = jax.random.fold_in(key, i)
        if path.endswith("norm") or "norm" in path.split("/")[-1]:
            out[path] = jnp.zeros(shape, dtype)
            continue
        z = jax.random.normal(k, shape, jnp.float32)
        if fan_in is None:  # the embedding: unit entries, not pruned
            w = z
        else:
            w = jnp.where(jnp.abs(z) >= t, z, 0.0) / (scale_kept * math.sqrt(fan_in))
        if path == "embed":
            w = jnp.where(jnp.arange(shape[0])[:, None] < vocab, w, 0.0)
        elif path == "lm_head":
            w = jnp.where(jnp.arange(shape[1])[None, :] < vocab, w, 0.0)
        out[path] = w.astype(dtype)
    return out


def make_weights(model: dict, sparsity: float, seed32: int) -> dict:
    """The whole weight tree, in the model's stored dtype (``torch_dtype``)
    on the default device, from one seed."""
    spec = dict(shapes(model))
    spec["__vocab__"] = model["vocab_size"]
    flat = _make(jax.random.key(seed32), tuple(sorted(spec.items(), key=lambda kv: kv[0])),
                 float(sparsity), model["torch_dtype"])
    return nest(flat)


@jax.jit
def _nnz(flat):
    return {k: jnp.count_nonzero(v, axis=tuple(range(1, v.ndim)) if k.startswith("layers/")
                                 else None) for k, v in flat.items()}


def nonzeros(weights: dict) -> dict:
    """{path: non-zeros} of every pruned matrix; per layer for the stacks."""
    flat = {k: v for k, v in flatten(weights).items() if "norm" not in k and k != "embed"}
    return {k: np.asarray(v) for k, v in jax.device_get(_nnz(flat)).items()}
