"""A second model family, for the tests only: what a family module gives
the harness, for a layout the dense one does not have.  A leading group of
dense MLP layers, then expert layers: a ``scaled`` router with a ``zeros``
bias over an expert stack ``(L, E, d, f)`` with two stacked axes, run by a
kernel of its own whose name extends the packed kernel's.  It has no
``logits``: no run of the program serves it."""

import work
from weights import Leaf, pad_vocab

EXPERT_KERNEL = "vusa_packed_matmul_experts"

PROGRAM_KEYS = {
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "num_attention_heads": "n_heads", "num_key_value_heads": "kv_heads",
    "moe_intermediate_size": "d_ff", "num_experts": "n_experts",
    "num_experts_per_tok": "top_k", "vocab_size": "vocab",
}


def check_program(arch) -> None:
    if arch.family != "moe":
        raise ValueError(f"{arch.name}: not an expert model")


def sizes(m: dict) -> tuple:
    """(dense layers, expert layers, d, dense width, expert width, experts)."""
    dense = m["first_k_dense_replace"]
    return (dense, m["num_hidden_layers"] - dense, m["hidden_size"], m["intermediate_size"],
            m["moe_intermediate_size"], m["num_experts"])


def layout(m: dict) -> dict:
    ld, le, d, fd, fe, e = sizes(m)
    v = pad_vocab(m["vocab_size"])
    return {
        "embed": Leaf((v, d), None, "normal", vocab_axis=0),
        "final_norm": Leaf((d,), None, "zeros"),
        "lm_head": Leaf((d, v), d, "pruned", vocab_axis=1),
        "dense/w_up": Leaf((ld, d, fd), d, "pruned", stacks=1),
        "dense/w_down": Leaf((ld, fd, d), fd, "pruned", stacks=1),
        "moe/router": Leaf((le, d, e), d, "scaled", stacks=1),
        "moe/router_bias": Leaf((le, e), None, "zeros", stacks=1),
        "moe/experts/w_up": Leaf((le, e, d, fe), d, "pruned", stacks=2),
        "moe/experts/w_down": Leaf((le, e, fe, d), fe, "pruned", stacks=2),
    }


def packed_calls(model: dict, nnz: dict, rows: int, values: str) -> dict:
    """Per step: each dense layer's MLP as one fused call, the head as one
    packed call, and each expert's two matrices as two calls of the expert
    kernel on its share of the rows."""
    ld, le, d, fd, fe, e = sizes(model)
    per_expert = -(-rows * model["num_experts_per_tok"] // e)
    fused = [work.fused_mlp(rows, d, fd, float(nnz["dense/w_up"][i] + nnz["dense/w_down"][i]),
                            values) for i in range(ld)]
    experts = [work.linear(per_expert, k, n, float(nnz[f"moe/experts/{w}"][i, j]), values)
               for i in range(le) for j in range(e)
               for w, k, n in (("w_up", d, fe), ("w_down", fe, d))]
    head = [work.linear(rows, d, model["vocab_size"], float(nnz["lm_head"]), values)]
    return {"vusa_fused_mlp_matmul": fused, "vusa_packed_matmul": head, EXPERT_KERNEL: experts}


def decode_flops(model: dict, ctx: int) -> float:
    ld, le, d, fd, fe, _ = sizes(model)
    return 2.0 * (ld * 2 * d * fd + le * model["num_experts_per_tok"] * 2 * d * fe
                  + d * model["vocab_size"]) + ctx


def prefill_flops(model: dict, n: int) -> float:
    return n * decode_flops(model, 0)
