"""Architecture + run configuration.

Every assigned architecture is one ``ArchConfig`` in ``repro/configs/<id>.py``.
``--arch <id>`` anywhere in the launchers resolves through
:func:`repro.configs.get_config`.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

__all__ = ["ArchConfig", "ShapeConfig", "SHAPES", "pad_vocab"]


def pad_vocab(v: int, multiple: int = 256) -> int:
    """Pad vocab to a shardable multiple (loss masks the padding ids)."""
    return ((v + multiple - 1) // multiple) * multiple


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str  # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    kv_heads: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None  # default d_model // n_heads
    # options
    qk_norm: bool = False
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    tie_embeddings: bool = False
    # MoE
    n_experts: int = 0
    top_k: int = 0
    moe_cf: float = 1.25  # capacity factor; >= n_experts/top_k == dropless
    # routing: "softmax" = top-k of a softmax, capacity-bounded dispatch
    # (``layers.moe``); "sigmoid" = DeepSeek-V3 ``noaux_tc``: experts chosen
    # by top-k of sigmoid scores plus a learned correction bias, weighted by
    # the scores alone, dropless, over the held experts (``layers.moe_held``)
    score_func: str = "softmax"
    norm_topk_prob: bool = True  # renormalise the chosen experts' weights
    routed_scale: float = 1.0  # routed_scaling_factor on the routed sum
    n_shared_experts: int = 0  # always-on experts, run as one MLP of n x d_ff
    # expert parallelism: ``ep_size`` chips share each expert layer and this
    # one holds the first n_experts / ep_size experts (rank 0)
    ep_size: int = 1
    # leading dense layers (first_k_dense_replace) and their SwiGLU width;
    # in a moe model d_ff is the expert width
    n_dense_layers: int = 0
    dense_d_ff: int = 0
    # latent attention (MLA, DeepSeek-V2/V3): kv_lora_rank > 0 replaces every
    # layer's attention.  Scores use qk_nope + qk_rope dims per head, values
    # v_head_dim; the cache holds kv_lora_rank + qk_rope numbers a token.
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # SSM (mamba2)
    ssm_state: int = 0
    ssm_heads: int = 0
    ssm_chunk: int = 256
    d_conv: int = 4
    expand: int = 2
    # hybrid (recurrentgemma / griffin)
    block_pattern: Tuple[str, ...] = ()  # e.g. ("rglru", "rglru", "attn")
    local_window: int = 0  # sliding-window size for local attention
    rglru_dim: int = 0  # recurrent width (griffin: ~ d_model)
    # enc-dec (whisper)
    enc_layers: int = 0
    enc_frames: int = 1500  # stub audio frontend: precomputed frame embeddings
    # vlm (paligemma)
    patch_tokens: int = 0  # stub vision frontend: precomputed patch embeddings
    # sparsity (the paper's technique, first-class)
    sparsity: float = 0.0  # target unstructured weight sparsity
    vusa_m_over_a: int = 4  # block-VUSA max virtual growth M_blk/A_blk
    # misc
    dtype: str = "bfloat16"
    remat: bool = True

    @property
    def hd(self) -> int:
        if self.head_dim:
            return self.head_dim
        return self.d_model // self.n_heads if self.n_heads else 0

    @property
    def padded_vocab(self) -> int:
        return pad_vocab(self.vocab)

    @property
    def mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def qk_head_dim(self) -> int:
        """Per-head score width of latent attention (no-rope + rope parts)."""
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def held_moe(self) -> bool:
        """Expert layers of held experts (``layers.moe_held``): sigmoid
        routing over all experts, this chip computing its own."""
        return self.family == "moe" and self.score_func == "sigmoid"

    @property
    def held_experts(self) -> int:
        """Routed experts this chip computes: the first n_experts / ep_size."""
        return self.n_experts // self.ep_size

    @property
    def moe_dropless(self) -> bool:
        """No token can ever be dropped: the sigmoid layer is dropless by
        construction, the capacity layer when its factor covers every
        assignment.  Co-batched rows then never interact."""
        return self.score_func == "sigmoid" or self.moe_cf >= self.n_experts / self.top_k

    def _attn_params(self) -> int:
        d, nh = self.d_model, self.n_heads
        if self.mla:
            r, dr = self.kv_lora_rank, self.qk_rope_head_dim
            return (d * nh * self.qk_head_dim + d * (r + dr) + r
                    + r * nh * (self.qk_nope_head_dim + self.v_head_dim)
                    + nh * self.v_head_dim * d)
        hd = self.hd
        return d * (nh * hd) + 2 * d * (self.kv_heads * hd) + (nh * hd) * d

    def _moe_layer_params(self, experts: int) -> int:
        """One expert layer with ``experts`` routed experts computed: those,
        the shared experts and, for the sigmoid router, its weights and
        correction bias."""
        d = self.d_model
        ffn = 3 * d * self.d_ff * (experts + self.n_shared_experts)
        if self.score_func == "sigmoid":
            ffn += (d + 1) * self.n_experts
        return self._attn_params() + ffn

    def param_count(self) -> int:
        """Approximate parameter count (used for 6ND model-FLOPs)."""
        d, v, L = self.d_model, self.padded_vocab, self.n_layers
        emb = v * d * (1 if self.tie_embeddings else 2)
        attn = self._attn_params()
        if self.family == "moe":
            ld = self.n_dense_layers
            dense = ld * (attn + 3 * d * self.dense_d_ff)
            return emb + dense + (L - ld) * self._moe_layer_params(self.n_experts)
        ffn = 3 * d * self.d_ff
        if self.family == "ssm":
            din = self.expand * d
            # in_proj(z,x,B,C,dt) + out_proj + conv
            attn = 0
            ffn = d * (2 * din + 2 * self.ssm_state + self.ssm_heads) + din * d + din * self.d_conv
        body = L * (attn + ffn)
        if self.family == "hybrid":
            n_attn = sum(1 for b in self._pattern() if b == "attn")
            n_rec = L - n_attn
            rec = (
                d * (2 * self.rglru_dim) + self.rglru_dim * d
                + 2 * self.rglru_dim * self.rglru_dim // 1
            )
            body = n_attn * (attn + ffn) + n_rec * (rec + ffn)
        if self.family == "encdec":
            body = self.enc_layers * (attn + ffn) + L * (2 * attn + ffn)
        return emb + body

    def active_param_count(self) -> int:
        """Params touched per token (MoE: top_k of n_experts FFNs)."""
        if self.family != "moe":
            return self.param_count()
        d, L, ld = self.d_model, self.n_layers, self.n_dense_layers
        emb = self.padded_vocab * d * (1 if self.tie_embeddings else 2)
        dense = ld * (self._attn_params() + 3 * d * self.dense_d_ff)
        return emb + dense + (L - ld) * self._moe_layer_params(self.top_k)

    def _pattern(self) -> Tuple[str, ...]:
        if not self.block_pattern:
            return ()
        reps = (self.n_layers + len(self.block_pattern) - 1) // len(self.block_pattern)
        return (self.block_pattern * reps)[: self.n_layers]


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}
