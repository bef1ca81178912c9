"""The comparison that decides ``correct``: served tokens against a plain
float32 reference.

After the window closes, a sample of the requests that got tokens in the
window (finished, or cut at the close with the tokens they had), drawn from
the seed with the longest always in it, is scored: the reference runs once
over each prompt followed by its served tokens, and at
the position of each served token we read how far that token's reference
logit lies below the reference's best, in units of the standard deviation
of the reference logits there.  Greedy decoding serves the argmax of the
program's logits, so a program that computes what the configuration states
serves tokens whose gap is small (bf16 activations, int8 weights), and one
that computes something else serves tokens the reference ranks low.  The
widest gap over the sample is compared with the cell's limit.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

@partial(jax.jit, static_argnums=(2,))
def _gaps(logits, targets, vocab: int):
    lg = logits[:, :vocab]
    top = lg.max(-1)
    got = jnp.take_along_axis(lg, targets[:, None], axis=-1)[:, 0]
    return (top - got) / lg.std(-1), got >= top


def sample(served: list, seed: int, tokens: int, most: int = 32) -> list:
    """``served`` = [(prompt, served tokens)]; the longest first, then a
    seeded draw of the rest, until ``tokens`` served tokens are covered."""
    if not served:
        return []
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0xC4EC]))
    longest = max(range(len(served)), key=lambda i: (len(served[i][1]), -i))
    order = [longest] + [int(i) for i in rng.permutation(len(served)) if i != longest]
    out, n = [], 0
    for i in order:
        if n >= tokens or len(out) >= most:
            break
        out.append(served[i])
        n += len(served[i][1])
    return out


def served_gap(reference, weights, model: dict, samples: list, size: int) -> dict:
    """Widest reference-logit gap of the served tokens, their count, and the
    share of them that are the reference's own argmax.  Every sequence is
    padded to ``size`` (the configuration's ``max_len``): one compiled
    reference; causal attention leaves the padding out of every score."""
    worst, hits, n = 0.0, 0, 0
    for prompt, served in samples:
        prompt, served = np.asarray(prompt, np.int32), np.asarray(served, np.int32)
        seq = np.concatenate([prompt, served[:-1]])
        toks = np.zeros(size, np.int32)
        toks[: len(seq)] = seq
        targets = np.zeros(size, np.int32)
        at = len(prompt) - 1  # logits at position p score the token served at p + 1
        targets[at : at + len(served)] = served
        g, h = jax.device_get(_gaps(reference.logits(weights, jnp.asarray(toks), model),
                                    jnp.asarray(targets), model["vocab_size"]))
        g, h = g[at : at + len(served)], h[at : at + len(served)]
        worst = max(worst, float(np.max(g)))
        hits += int(np.sum(h))
        n += len(served)
    return {"served_gap_max": worst, "checked_tokens": n,
            "argmax_share": hits / n if n else float("nan")}
