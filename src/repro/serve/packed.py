"""VUSA-packed decode path for the decoder LMs (DESIGN.md §7, §14).

``pack_lm_weights`` packs the decode-step weights into the row-wise VUSA
format: per-layer MLP matrices (``w_gate``/``w_up`` plain, ``w_down``
*transposed* so the fused megakernel can window its reduction dim), and —
with ``scope="all"`` — the attention projections ``wq/wk/wv/wo`` and the
untied LM head.  One static sparse format serves every GEMM of the decode
step, the paper's application-independence claim on the serving path.  A
model of held experts adds their per-expert packs for the grouped kernel,
its shared experts and leading dense layers taking the fused MLP's layout.

``lm_decode_step_packed`` is a twin of ``families.lm_decode_step`` whose
packed matmuls run through the Pallas kernels: the MLP through the fused
megakernel (``kernels.ops.apply_fused_mlp`` — one dispatch per layer, the
``(B, ff)`` intermediate never leaves VMEM) or, with ``fused_mlp=False``,
through the measured 3-dispatch baseline; attention projections and the
vocab-wide head reuse the multi-window row-packed kernel.  Layer packs are
stacked on a leading axis so the layer loop stays a scan.

``pack_lm_mlps`` survives as the legacy MLP-only packer (flat dict, dense
``w_down``); ``lm_decode_step_packed`` accepts both layouts.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ArchConfig
from ..kernels.ops import (
    RowPackedLinear,
    apply_fused_mlp,
    apply_fused_mlp_sharded,
    apply_fused_moe,
    apply_row_packed,
    apply_row_packed_sharded,
    mesh_axis_size,
    pack_linear_rows,
    pad_slots,
    pack_linear_rows_t,
    shard_linear_windows,
)
from ..kernels.vusa_packed import calls_repeat
from ..models import families as F
from ..models.common import rms_norm

__all__ = [
    "pack_lm_mlps",
    "pack_lm_weights",
    "shard_packed",
    "lm_decode_step_packed",
    "packed_byte_ratios",
    "validate_packed",
    "pack_fingerprint",
    "qdq_lm_params",
]

ATTN_NAMES = ("wq", "wk", "wv", "wo")
MLA_NAMES = ("wq", "wkv_a", "wo")  # latent attention's packed projections


# --------------------------------------------------------------------------
# packers
# --------------------------------------------------------------------------


def _stack_packs(packs) -> Dict:
    """Stack per-layer RowPackedLinear into one (L, ...) device dict.

    Slots are padded to the max across layers so the stack is rectangular
    (``kernels.ops.pad_slots``: idle slots, exact no-ops), and quantized
    packs stack their (T, K) scales unpadded."""
    smax = max(p.slots for p in packs)
    padded = [pad_slots(p, smax) for p in packs]
    out = {
        "values": jnp.stack([p.values for p in padded]),
        "positions": jnp.stack([p.positions for p in padded]),
        "k": packs[0].k,
        "c": packs[0].c,
        "m": packs[0].m,
        "a": packs[0].a,
    }
    if packs[0].value_dtype != "dense":
        out["scales"] = jnp.stack([p.scales for p in packs])
        out["value_dtype"] = packs[0].value_dtype
        out["dense_itemsize"] = packs[0].dense_itemsize
    return out


def _stack_layers(
    ws: np.ndarray,
    m: int,
    a: int,
    pack_fn=pack_linear_rows,
    shards: int = 1,
    value_dtype: str = "dense",
) -> Dict:
    """Pack every layer of a stacked (L, K, C) weight and stack the packs.
    ``shards`` pads each pack's window axis to a multiple (no-op windows) so
    the stacked window axis splits evenly over a TP mesh axis."""
    return _stack_packs([
        shard_linear_windows(pack_fn(ws[layer], m=m, a=a, value_dtype=value_dtype), shards)
        for layer in range(ws.shape[0])
    ])


def _pack_one(p: RowPackedLinear) -> Dict:
    out = {
        "values": p.values,
        "positions": p.positions,
        "k": p.k,
        "c": p.c,
        "m": p.m,
        "a": p.a,
    }
    if p.value_dtype != "dense":
        out["scales"] = p.scales
        out["value_dtype"] = p.value_dtype
        out["dense_itemsize"] = p.dense_itemsize
    return out


def _as_linear(entry: Dict, values, positions, scales=None) -> RowPackedLinear:
    """Rebuild a RowPackedLinear from scanned per-layer leaves + static meta."""
    return RowPackedLinear(
        values=values, positions=positions,
        k=entry["k"], c=entry["c"], a=entry["a"], m=entry["m"],
        scales=scales,
        value_dtype=entry.get("value_dtype", "dense"),
        dense_itemsize=entry.get("dense_itemsize"),
    )


def pack_lm_mlps(cfg: ArchConfig, params, m: int = 128, a: int = 16) -> Dict:
    """Legacy MLP-only pack (flat dict, dense-orientation ``w_down``): the
    operands of the 3-dispatch baseline path."""
    layers = params["layers"]["ffn"]
    return {
        name: _stack_layers(np.asarray(layers[name]), m, a)
        for name in ("w_gate", "w_up", "w_down")
    }


def _pack_mlp(ffn, m, a, shards, value_dtype, fused_mlp: bool) -> Dict:
    """The SwiGLU trio of a layer stack: ``w_down`` packed transposed for
    the fused megakernel, plain for the 3-dispatch baseline."""
    mlp: Dict = {
        name: _stack_layers(np.asarray(ffn[name]), m, a, shards=shards, value_dtype=value_dtype)
        for name in ("w_gate", "w_up")
    }
    if fused_mlp:
        mlp["w_down_t"] = _stack_layers(
            np.asarray(ffn["w_down"]), m, a, pack_linear_rows_t, shards=shards,
            value_dtype=value_dtype,
        )
    else:
        mlp["w_down"] = _stack_layers(
            np.asarray(ffn["w_down"]), m, a, shards=shards, value_dtype=value_dtype
        )
    return mlp


def _pack_attn(cfg, attn_p, m, a, shards, value_dtype) -> Dict:
    """A layer stack's attention projections, head dims flattened to 2-D;
    latent attention packs ``wq``, ``wkv_a`` and ``wo`` (``wkv_b`` stays a
    bf16 einsum of the absorbed decode)."""
    attn: Dict = {}
    for name in MLA_NAMES if cfg.mla else ATTN_NAMES:
        w = np.asarray(attn_p[name])  # (L, d, ...) or wo (L, nh, hd, d)
        flat = (
            w.reshape(w.shape[0], -1, w.shape[-1])  # wo: (L, nh*hd, d)
            if name == "wo"
            else w.reshape(w.shape[0], w.shape[1], -1)  # (L, d, n)
        )
        attn[name] = _stack_layers(flat, m, a, shards=shards, value_dtype=value_dtype)
    return attn


def _pack_experts(ffn, m, a, shards, value_dtype) -> Dict:
    """Every layer's held experts as fused-MLP packs stacked (L, E, ...) and
    padded to one slot count: the grouped kernel's operands."""
    out: Dict = {}
    for name, src, fn in (("w_gate", "w_gate", pack_linear_rows),
                          ("w_up", "w_up", pack_linear_rows),
                          ("w_down_t", "w_down", pack_linear_rows_t)):
        w = np.asarray(ffn[src])  # (L, E, ...)
        st = _stack_packs([
            shard_linear_windows(fn(w[i, e], m=m, a=a, value_dtype=value_dtype), shards)
            for i in range(w.shape[0]) for e in range(w.shape[1])
        ])
        for leaf in ("values", "positions", "scales"):
            if leaf in st:
                st[leaf] = st[leaf].reshape(w.shape[:2] + st[leaf].shape[1:])
        out[name] = st
    return out


def pack_lm_weights(
    cfg: ArchConfig,
    params,
    m: int = 128,
    a: int = 16,
    scope: str = "all",
    fused_mlp: bool = True,
    shards: int = 1,
    value_dtype: str = "dense",
) -> Dict:
    """Pack the decode-step weights of a decoder LM; returns a structured dict.

    ``scope="mlp"`` packs only the feed-forward weights; ``scope="all"``
    adds the attention projections (head dims flattened to 2-D) and the
    untied LM head (tied embeddings stay a gather + transpose-einsum — there
    is no separate weight to pack).  ``fused_mlp`` selects the megakernel
    operand layout (``w_down`` packed transposed via ``pack_linear_rows_t``)
    vs the 3-dispatch baseline layout (``w_down`` packed plain).  ``shards``
    pads every window axis to a multiple (no-op windows, exact) so the packs
    can be split over a TP mesh axis of that size — place them with
    :func:`shard_packed` (DESIGN.md §8).  ``value_dtype="int8"``/``"int4"``
    quantizes every pack's value slots with per-(window, row) fp32 scales
    (DESIGN.md §10); ``"dense"`` keeps the native float dtype.

    ``mlp`` and ``attn`` are the main layer stack's.  A model of held
    experts (sigmoid routing) packs, besides, ``experts``: the grouped
    kernel's per-expert packs, stacked (L, E, ...); its ``mlp`` is then the
    shared experts, and its leading dense layers pack as ``dense_layers``
    (``{"mlp", "attn"}``, the same layouts)."""
    held = cfg.held_moe
    assert cfg.family == "dense" or held, (
        "packed decode serves the dense family and held-expert models"
    )
    assert scope in ("mlp", "all"), scope
    assert fused_mlp or not held, "held experts run through the fused kernels"
    geo = (m, a, shards, value_dtype)
    ffn = params["layers"]["ffn"]
    out: Dict = {
        "mlp": _pack_mlp(ffn["shared"] if held else ffn, *geo, fused_mlp),
        "attn": None,
        "head": None,
        "scope": scope,
        "fused_mlp": fused_mlp,
    }
    if held:
        out["experts"] = _pack_experts(ffn, *geo)
    if cfg.n_dense_layers:
        dense = params["dense_layers"]
        out["dense_layers"] = {
            "mlp": _pack_mlp(dense["ffn"], *geo, fused_mlp),
            "attn": _pack_attn(cfg, dense["attn"], *geo) if scope == "all" else None,
        }
    if scope == "all":
        out["attn"] = _pack_attn(cfg, params["layers"]["attn"], *geo)
        if not cfg.tie_embeddings:
            out["head"] = _pack_one(
                shard_linear_windows(
                    pack_linear_rows(
                        np.asarray(params["lm_head"]), m=m, a=a, value_dtype=value_dtype
                    ),
                    shards,
                )
            )
    validate_packed(out)  # pack-time guard: never hand out a malformed pack
    return out


def shard_packed(packed: Dict, mesh) -> Dict:
    """Place a ``pack_lm_weights`` dict on a mesh: window axes split over the
    ``model`` mesh axis via ``dist.sharding.window_sharding`` (values *and*
    the int8 positions metadata — identical specs, a positions array sharded
    differently from its values would index the wrong shard's lanes).  Layer
    stacks ``(L, T, K, S)`` shard axis 1, the single LM-head pack ``(T, K,
    S)`` axis 0.  Window counts the axis does not divide (pack without
    ``shards=tp``) replicate — never an error.  Degenerate meshes return the
    dict as-is."""
    if mesh_axis_size(mesh, "model") == 1:
        return packed
    from ..dist.sharding import window_sharding

    def place(entry: Dict, axis: int) -> Dict:
        t = entry["values"].shape[axis]
        out = dict(entry)
        # scales share the window axis and must split identically — a scale
        # sharded differently from its values would rescale the wrong windows
        leaves = ("values", "positions") + (("scales",) if "scales" in entry else ())
        for leaf in leaves:
            sh = window_sharding(mesh, t, entry[leaf].ndim, axis=axis)
            out[leaf] = jax.device_put(entry[leaf], sh)
        return out

    out = dict(packed)
    if "mlp" not in packed:  # legacy flat pack_lm_mlps layout
        return {name: place(entry, 1) for name, entry in packed.items()}
    out["mlp"] = {name: place(e, 1) for name, e in packed["mlp"].items()}
    if packed.get("attn"):
        out["attn"] = {name: place(e, 1) for name, e in packed["attn"].items()}
    if packed.get("experts"):
        out["experts"] = {name: place(e, 2) for name, e in packed["experts"].items()}
    if packed.get("dense_layers"):
        out["dense_layers"] = {
            group: {name: place(e, 1) for name, e in entries.items()} if entries else None
            for group, entries in packed["dense_layers"].items()
        }
    if packed.get("head") is not None:
        out["head"] = place(packed["head"], 0)
    return out


def _flat_entries(packed: Dict) -> Dict[str, Dict]:
    """Flatten a pack dict (structured ``pack_lm_weights`` or legacy flat
    ``pack_lm_mlps``) into ``{name: entry}``."""
    flat: Dict[str, Dict] = {}
    if "mlp" in packed:
        flat.update(packed["mlp"])
        if packed.get("attn"):
            flat.update(packed["attn"])
        for name, e in (packed.get("experts") or {}).items():
            flat["experts/" + name] = e
        for group in (packed.get("dense_layers") or {}).values():
            for name, e in (group or {}).items():
                flat["dense_layers/" + name] = e
        if packed.get("head") is not None:
            flat["lm_head"] = packed["head"]
    else:
        flat.update(packed)
    return flat


def pack_fingerprint(packed: Dict) -> int:
    """CRC32 over every pack entry's arrays and geometry — a cheap identity
    for a loaded pack.  Hot swaps journal it (DESIGN.md §12) so an operator
    can tell from the journal alone *which* pack served which tokens; two
    packs built from the same params at the same config fingerprint the
    same.  One host fetch per entry; never on the decode path."""
    import zlib

    crc = 0
    flat = _flat_entries(packed)
    for name in sorted(flat):
        e = flat[name]
        crc = zlib.crc32(name.encode(), crc)
        crc = zlib.crc32(
            repr((e["m"], e["a"], e["k"], e["c"], e.get("value_dtype", "dense"))).encode(),
            crc,
        )
        for leaf in ("values", "positions", "scales"):
            if leaf in e:
                arr = np.asarray(jax.device_get(e[leaf]))
                crc = zlib.crc32(np.ascontiguousarray(arr).tobytes(), crc)
    return crc


def validate_packed(packed: Dict) -> None:
    """Check every pack entry's structural invariants at pack/load time;
    raise ``ValueError`` naming the entry and the first violation.

    Position metadata is the pack's wiring diagram: a corrupt byte silently
    reconstructs weight values into the wrong lanes — finite, plausible, and
    wrong — which the runtime ``isfinite`` guard cannot see.  Bounds, dtype
    and shape are checkable *before* serving, so the Engine refuses a pack
    that fails here (DESIGN.md §9).  The scan runs on device with one scalar
    sync per entry; the offending index is fetched only on failure."""
    flat = _flat_entries(packed)
    if not flat:
        raise ValueError("empty pack: no entries to serve")
    for name, e in flat.items():
        v, q = e["values"], e["positions"]
        m, a, k, c = e["m"], e["a"], e["k"], e["c"]
        vdt = e.get("value_dtype", "dense")
        nib = 2 if vdt == "int4" else 1
        if vdt == "dense":
            if tuple(v.shape) != tuple(q.shape):
                raise ValueError(
                    f"{name}: values shape {tuple(v.shape)} != positions {tuple(q.shape)}"
                )
        else:
            # quantized: values are raw bytes (nibble-packed for int4); they
            # must decode to exactly the position slots
            if v.dtype != jnp.int8:
                raise ValueError(f"{name}: quantized values dtype must be int8, got {v.dtype}")
            if tuple(v.shape[:-1]) != tuple(q.shape[:-1]) or v.shape[-1] * nib != q.shape[-1]:
                raise ValueError(
                    f"{name}: {vdt} values shape {tuple(v.shape)} does not decode to "
                    f"positions {tuple(q.shape)}"
                )
            s = e.get("scales")
            if s is None:
                raise ValueError(f"{name}: {vdt} pack is missing its scales")
            if tuple(s.shape) != tuple(q.shape[:-1]):
                raise ValueError(
                    f"{name}: scales shape {tuple(s.shape)} != window/row "
                    f"shape {tuple(q.shape[:-1])}"
                )
            if not bool(jnp.isfinite(s).all()):
                i = tuple(int(x) for x in np.argwhere(~np.isfinite(np.asarray(s)))[0])
                raise ValueError(f"{name}: non-finite dequant scale at {i}")
            if bool((s <= 0).any()):
                i = tuple(int(x) for x in np.argwhere(np.asarray(s) <= 0)[0])
                raise ValueError(f"{name}: non-positive dequant scale at {i}")
        if q.dtype != jnp.int8:
            raise ValueError(f"{name}: positions dtype must be int8, got {q.dtype}")
        if v.ndim not in (3, 4, 5):
            raise ValueError(
                f"{name}: expected (T, K, S), (L, T, K, S) or (L, E, T, K, S), "
                f"got {tuple(v.shape)}"
            )
        if m < 1 or a < 1 or m > 128:
            raise ValueError(f"{name}: window m={m} / slots a={a} out of range (int8 lanes)")
        if v.shape[-2] != k:
            raise ValueError(f"{name}: pack rows {v.shape[-2]} != declared k={k}")
        # int4 pads the slot axis to even at quantize time, which can break
        # the a-multiple; the kernel never consumes ``a``, so only dense and
        # int8 packs (slot count unchanged by quantization) keep the check
        if vdt != "int4" and v.shape[-1] % a:
            raise ValueError(f"{name}: slot count {v.shape[-1]} not a multiple of a={a}")
        if v.shape[-3] * m < c:
            raise ValueError(
                f"{name}: {v.shape[-3]} windows of {m} lanes cover "
                f"{v.shape[-3] * m} < c={c} columns"
            )
        # widen before comparing: m=128 does not fit int8, and int8 promotion
        # would wrap it to -128, flagging every position
        qw = q.astype(jnp.int32)
        bad_pos = (qw < -1) | (qw >= m)
        if bool(bad_pos.any()):
            qn = np.asarray(q)
            i = tuple(int(x) for x in np.argwhere(np.asarray(bad_pos))[0])
            raise ValueError(
                f"{name}: position {int(qn[i])} at {i} outside [-1, {m}) — corrupt metadata"
            )
        if vdt == "dense" and not bool(jnp.isfinite(v).all()):
            i = tuple(int(x) for x in np.argwhere(~np.isfinite(np.asarray(v)))[0])
            raise ValueError(f"{name}: non-finite packed value at {i}")


def packed_byte_ratios(packed: Dict, value_bytes: Optional[int] = None) -> Dict[str, float]:
    """Per-weight and total packed/dense HBM byte ratios (int8 positions).

    Accepts both the structured ``pack_lm_weights`` dict and the legacy flat
    ``pack_lm_mlps`` dict.  ``value_bytes`` defaults to the packed value
    itemsize.  Quantized entries count their real bytes — nibble-packed
    value bytes, full int8 positions, fp32 scales — against the *original*
    dense weight's bytes (``dense_itemsize``), not the quantized itemsize:
    the dense baseline being displaced did not shrink when the pack did."""
    flat = _flat_entries(packed)
    ratios: Dict[str, float] = {}
    tot_packed = tot_dense = 0
    for name, e in flat.items():
        v = e["values"]
        n_layers = int(np.prod(v.shape[:-3]))  # matrices stacked: layers (x experts)
        if e.get("value_dtype", "dense") == "dense":
            vb = v.dtype.itemsize if value_bytes is None else value_bytes
            pb = v.size * (vb + 1)  # values + int8 positions
            db = n_layers * e["k"] * e["c"] * vb
        else:
            pb = (
                v.size * v.dtype.itemsize
                + e["positions"].size
                + e["scales"].size * e["scales"].dtype.itemsize
            )
            dense_b = e["dense_itemsize"] if value_bytes is None else value_bytes
            db = n_layers * e["k"] * e["c"] * dense_b
        ratios[name] = pb / db
        tot_packed += pb
        tot_dense += db
    ratios["total"] = tot_packed / max(tot_dense, 1)
    return ratios


# --------------------------------------------------------------------------
# quantize-dequantize dense oracle
# --------------------------------------------------------------------------


def _qdq_matrix(w2d: np.ndarray, m: int, a: int, value_dtype: str, transposed: bool = False):
    """Quantize->dequantize one 2-D matrix under the *same* window geometry
    the packer uses (``pack_rows_t`` for transposed-orientation packs), so
    the roundtripped values are bitwise the fp32 products the kernel's fused
    dequant reconstructs in VMEM."""
    from ..core.packing import dequantize_rows, pack_rows, pack_rows_t, quantize_rows, unpack_rows

    pack = (pack_rows_t if transposed else pack_rows)(w2d, m=m, a=a)
    dense = unpack_rows(dequantize_rows(quantize_rows(pack, value_dtype)))
    return np.ascontiguousarray(dense.T) if transposed else dense


def qdq_lm_params(
    cfg: ArchConfig,
    params,
    m: int = 128,
    a: int = 16,
    scope: str = "all",
    fused_mlp: bool = True,
    value_dtype: str = "int8",
):
    """Dense-oracle params: every matrix ``pack_lm_weights`` would quantize
    is replaced by its quantize-dequantize roundtrip under identical window
    geometry and orientation.  Running the *dense* decode path on these
    params is the correctness oracle for the quantized packed path: the
    kernel's VMEM dequant computes the same ``q * scale`` fp32 values, so
    greedy token streams must match."""
    assert scope in ("mlp", "all"), scope

    def qdq_stack(ws: np.ndarray, transposed: bool = False) -> jnp.ndarray:
        out = np.stack([
            _qdq_matrix(ws[layer], m, a, value_dtype, transposed)
            for layer in range(ws.shape[0])
        ])
        return jnp.asarray(out.astype(ws.dtype))

    ffn = dict(params["layers"]["ffn"])
    for name in ("w_gate", "w_up", "w_down"):
        w = np.asarray(ffn[name])
        ffn[name] = qdq_stack(w, transposed=(name == "w_down" and fused_mlp))
    layers = {**params["layers"], "ffn": ffn}
    out = {**params, "layers": layers}
    if scope == "all":
        attn = dict(params["layers"]["attn"])
        for name in ATTN_NAMES:
            w = np.asarray(attn[name])
            flat = (
                w.reshape(w.shape[0], -1, w.shape[-1])
                if name == "wo"
                else w.reshape(w.shape[0], w.shape[1], -1)
            )
            attn[name] = jnp.asarray(
                np.asarray(qdq_stack(flat)).reshape(w.shape).astype(w.dtype)
            )
        layers["attn"] = attn
        if not cfg.tie_embeddings:
            w = np.asarray(params["lm_head"])
            out["lm_head"] = jnp.asarray(_qdq_matrix(w, m, a, value_dtype).astype(w.dtype))
    return out


# --------------------------------------------------------------------------
# decode step
# --------------------------------------------------------------------------


def lm_decode_step_packed(params, packed, token, cache, cfg, mesh=None):
    """Decode step with VUSA-packed weights: the dense family, and models of
    held experts (``pack_lm_weights``), whose layer stacks run in turn with
    the same step as the dense family's plus the expert layer: routing
    (``decode.router``), the grouped kernel (``decode.experts``) and the
    shared experts on the fused kernel (``decode.mlp``); the step's cache
    output then carries ``expert_rows`` (``lm_decode_step``).  ``token``
    is (B, 1) for normal decode or (B, s) for a speculative multi-token
    verify (contiguous cache only).  A *fully* packed step (scope="all"
    with an untied, packed LM head) runs the s-row verify genuinely
    batched: every matmul goes through the VUSA Pallas appliers, which are
    row-bitwise across row counts AND ~flat-cost in rows (the grid scans
    jobs, not rows), and ``attention_decode`` attends per query row — so
    the batched verify is bit-identical to s sequential steps at roughly
    single-step cost, which is where the speculative speedup comes from
    (DESIGN.md §13).  A partial pack (scope="mlp" or tied embeddings)
    still routes rows through XLA gemms, which are NOT row-stable, so it
    falls back to chaining s single-token steps inside the one dispatch —
    same bit-parity argument as :func:`repro.models.families.lm_decode_step`.

    ``packed`` is a ``pack_lm_weights`` dict (fused megakernel MLP and,
    with ``scope="all"``, packed attention projections + LM head) or a
    legacy ``pack_lm_mlps`` flat dict (MLP-only, 3-dispatch baseline).

    ``mesh`` routes every packed matmul through the window-sharded appliers
    (``kernels.ops.apply_*_sharded``): each device of the ``model`` axis
    reconstructs only its windows and the partial outputs are reassembled
    with a psum (fused MLP — ff is the reduction dim) or a tiled all-gather
    (column windows: gate/up/qkv/o/head).  A mesh whose ``model`` axis is
    absent or size 1 is the degenerate case — identical program to
    ``mesh=None`` (DESIGN.md §8)."""
    assert cfg.family == "dense" or cfg.held_moe, (
        "packed decode serves the dense family and held-expert models"
    )
    if token.shape[1] > 1:
        assert "table" not in cache, (
            "multi-token decode needs a contiguous cache; gather the paged "
            "view first (serve/scheduler.py)"
        )
        full = (
            "mlp" in packed
            and packed.get("attn") is not None
            and packed.get("head") is not None
        )
        if not full:  # partial pack: XLA gemms are not row-stable — chain
            logits = []
            for i in range(token.shape[1]):
                lg, cache = lm_decode_step_packed(
                    params, packed, token[:, i : i + 1], cache, cfg, mesh=mesh
                )
                logits.append(lg)
            return jnp.concatenate(logits, axis=1), cache
    if "mlp" not in packed:  # legacy flat layout
        packed = {"mlp": packed, "attn": None, "head": None, "fused_mlp": False}
    fused = packed.get("fused_mlp", "w_down_t" in packed["mlp"])
    if mesh_axis_size(mesh, "model") == 1:
        mesh = None  # degenerate: plain single-device appliers

    x = F._embed_tokens(params, token, cfg)
    pos = cache["pos"]
    # paged per-slot view (DESIGN.md §11): same contract as lm_decode_step —
    # arena leaves scan per layer, the step returns pending rows (<leaf>_new)
    table = cache.get("table")

    from ..models.layers import attention_decode, mla_decode, route  # noqa: PLC0415

    def papply(entry, vals, poss, x2, scales=None, name="vusa_packed_matmul"):
        lin = _as_linear(entry, vals, poss, scales)
        if mesh is not None:
            return apply_row_packed_sharded(x2, lin, mesh, name=name)
        return apply_row_packed(x2, lin, name=name)

    def arrays(group):  # scanned leaves only; meta stays static
        return {
            n: {
                leaf: e[leaf]
                for leaf in ("values", "positions", "scales")
                if leaf in e
            }
            for n, e in (group or {}).items()
        }

    def lin(group, leaves, name):
        return _as_linear(
            group[name], leaves[name]["values"], leaves[name]["positions"],
            leaves[name].get("scales"),
        )

    def groups(key):  # a layer stack's packs
        if key == "layers":
            return packed["mlp"], packed["attn"], packed.get("experts")
        return packed[key]["mlp"], packed[key]["attn"], None

    def body(x, layer_in, dense):
        lp, cache_l, mlp_l, attn_l, exp_l = layer_in
        mlp, attn, experts = groups("dense_layers" if dense else "layers")
        if table is not None:
            cache_l = {**cache_l, "table": table}
        h = rms_norm(x, lp["norm1"])
        wmm = (
            (
                lambda name, x2: papply(
                    attn[name], attn_l[name]["values"], attn_l[name]["positions"], x2,
                    attn_l[name].get("scales"),
                )
            )
            if attn is not None
            else None
        )
        attend = mla_decode if cfg.mla else attention_decode
        y, new_cache = attend(lp["attn"], h, cfg, {**cache_l, "pos": pos}, wmm=wmm)
        x = x + y
        out = F.lm_step_rows(new_cache, cfg)
        with jax.named_scope("decode.mlp"):
            h = rms_norm(x, lp["norm2"])
            b, s, d = h.shape
            hf = h.reshape(b * s, d)
        if experts is not None:
            with jax.named_scope("decode.router"):
                gates, out["expert_rows"] = route(lp["ffn"], hf, cfg)
            with jax.named_scope("decode.experts"):
                y2 = apply_fused_moe(
                    hf, gates, lin(experts, exp_l, "w_gate"), lin(experts, exp_l, "w_up"),
                    lin(experts, exp_l, "w_down_t"),
                )
                x = x + y2.reshape(b, s, d).astype(x.dtype)
        with jax.named_scope("decode.mlp"):
            if fused:
                g, u, dn = (lin(mlp, mlp_l, n) for n in ("w_gate", "w_up", "w_down_t"))
                if mesh is not None:
                    y2 = apply_fused_mlp_sharded(hf, g, u, dn, mesh)
                else:
                    y2 = apply_fused_mlp(hf, g, u, dn)
            else:  # 3-dispatch baseline: gate/up/down round-trip the (B, ff)

                def pap(name, x2):
                    return papply(
                        mlp[name], mlp_l[name]["values"], mlp_l[name]["positions"], x2,
                        mlp_l[name].get("scales"),
                    )

                gate = jax.nn.silu(pap("w_gate", hf))
                up = pap("w_up", hf)
                y2 = pap("w_down", (gate * up).astype(hf.dtype))
            x = x + y2.reshape(b, s, d).astype(x.dtype)
        return x, out

    def stack_inputs(key, dense):
        mlp, attn, experts = groups(key)
        return params[key], arrays(mlp), arrays(attn), arrays(experts)

    x, new_kv = F.lm_scan_stacks(x, cache, cfg, body, stack_inputs, repeat=calls_repeat)
    with jax.named_scope("decode.head"):
        x = rms_norm(x, params["final_norm"])
        if packed.get("head") is not None:
            b, s, d = x.shape
            head = packed["head"]
            logits = papply(
                head, head["values"], head["positions"], x.reshape(b * s, d),
                head.get("scales"), name="vusa_packed_matmul_head",
            )
            logits = logits.reshape(b, s, -1)
        else:
            head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
            logits = jnp.einsum("bsd,dv->bsv", x, head.astype(x.dtype))
    if table is not None:
        return logits, {**new_kv, "table": table, "pos": pos + 1}
    return logits, {**new_kv, "pos": pos + token.shape[1]}
