"""Seeded weights for a model, made on the device in one jitted call from
the layout its family module gives (``references/<family>.py``:
``layout(model)``).

A layout maps each leaf's path (``/``-separated, as the served program's
weight tree nests it) to a :class:`Leaf`: its shape, its fan-in, how many
of its leading axes are stacks (layers, experts), how it is drawn, and
which axis, if any, runs over the padded vocabulary.  Leaves are drawn in
the sorted order of their paths, each from ``fold_in(key, index)``, so a
layout that keeps its paths keeps every leaf's numbers.

Weights are stored in the configuration's ``torch_dtype``.  The draws:

- ``zeros``: norm scales (the program multiplies by ``1 + scale``) and
  biases.
- ``normal``: N(0, 1), not pruned (an embedding).
- ``scaled``: N(0, 1 / fan_in), not pruned (a router).
- ``pruned``: N(0, s^2) in float32, pruned to the configured unstructured
  sparsity by magnitude: entries with |w| < t * s are zeroed, t being the
  two-sided normal quantile of the sparsity (what a magnitude prune of a
  Gaussian matrix keeps).  ``s`` is chosen so that the kept entries have
  variance 1 / fan_in, the fan-in being the matrix's true input width, so
  the random network keeps its activations at unit scale through depth
  instead of turning chaotic: with attention logits of order 1, bf16
  rounding moves the served logits by a few hundredths of their spread,
  and a comparison with a float32 reference has something to separate.

Along a leaf's vocabulary axis the entries past the vocabulary are zero,
so no served token falls outside it.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from statistics import NormalDist

import jax
import jax.numpy as jnp
import numpy as np

INITS = ("zeros", "normal", "scaled", "pruned")


@dataclass(frozen=True)
class Leaf:
    shape: tuple
    fan_in: int | None  # the input width that sets a ``scaled`` or ``pruned`` variance
    init: str  # one of INITS
    stacks: int = 0  # leading axes that are stacks: ``nonzeros`` counts per entry of them
    vocab_axis: int | None = None  # zeroed past the vocabulary along this axis

    def __post_init__(self):
        if self.init not in INITS:
            raise ValueError(f"init {self.init!r} is none of {INITS}")
        if self.init in ("scaled", "pruned") and not self.fan_in:
            raise ValueError(f"a {self.init} leaf needs its fan-in")


def pad_vocab(v: int, multiple: int = 256) -> int:
    return -(-v // multiple) * multiple


def prune_threshold(sparsity: float) -> float:
    """|z| below which a standard normal entry is pruned at ``sparsity``."""
    return NormalDist().inv_cdf(0.5 + sparsity / 2) if sparsity > 0 else 0.0


def kept_variance(t: float) -> float:
    """E[z^2 ; |z| >= t] for a standard normal z."""
    nd = NormalDist()
    return 2.0 * (t * nd.pdf(t) + (1.0 - nd.cdf(t))) if t > 0 else 1.0


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


@partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _make(key, spec: tuple, sparsity: float, dtype: str, vocab: int):
    t = prune_threshold(sparsity)
    scale_kept = math.sqrt(kept_variance(t))
    out = {}
    for i, (path, leaf) in enumerate(spec):
        k = jax.random.fold_in(key, i)
        if leaf.init == "zeros":
            out[path] = jnp.zeros(leaf.shape, dtype)
            continue
        z = jax.random.normal(k, leaf.shape, jnp.float32)
        if leaf.init == "normal":
            w = z
        elif leaf.init == "scaled":
            w = z / math.sqrt(leaf.fan_in)
        else:
            w = jnp.where(jnp.abs(z) >= t, z, 0.0) / (scale_kept * math.sqrt(leaf.fan_in))
        if leaf.vocab_axis is not None:
            at = [1] * len(leaf.shape)
            at[leaf.vocab_axis] = leaf.shape[leaf.vocab_axis]
            w = jnp.where(jnp.arange(leaf.shape[leaf.vocab_axis]).reshape(at) < vocab, w, 0.0)
        out[path] = w.astype(dtype)
    return out


def make_weights(layout: dict, sparsity: float, seed32: int, dtype: str, vocab: int) -> dict:
    """The whole weight tree of ``layout`` ({path: Leaf}), in ``dtype``
    (the configuration's ``torch_dtype``) on the default device, from one
    seed; ``vocab`` is the vocabulary's true size."""
    flat = _make(jax.random.key(seed32), tuple(sorted(layout.items())),
                 float(sparsity), dtype, int(vocab))
    return nest(flat)


@partial(jax.jit, static_argnums=(1,))
def _nnz(flat, stacks: tuple):
    stacks = dict(stacks)
    return {k: jnp.count_nonzero(v, axis=tuple(range(stacks[k], v.ndim)))
            for k, v in flat.items()}


def nonzeros(weights: dict, layout: dict) -> dict:
    """{path: non-zeros} of every pruned leaf of ``layout``, per entry of
    its stacked axes (``(L,)`` for a layer stack, ``(L, E)`` for experts)."""
    flat = {k: v for k, v in flatten(weights).items() if layout[k].init == "pruned"}
    stacks = tuple(sorted((k, layout[k].stacks) for k in flat))
    return {k: np.asarray(v) for k, v in jax.device_get(_nnz(flat, stacks)).items()}
