"""Serving engine: batched prefill + decode with per-family caches, greedy /
temperature sampling, and optional VUSA-packed decode execution — MLP-only
or the whole decode step, see ``ServeConfig.packed_weights`` and DESIGN.md
§7 (the paper's technique on the inference path, where weight-byte savings
pay off).

The decode loop is *fused on device* (DESIGN.md §4): one jitted
``lax.scan`` steps the model ``max_new - 1`` times, deriving per-token
sampling keys on device and stacking tokens into a pre-allocated output
buffer, so generation costs a single dispatch and a single
``block_until_ready`` — no per-token host round-trip.  The seed per-token
host loop is kept behind ``ServeConfig.fused = False`` as the measured
baseline (benchmarks/run.py bench_decode_fused) and as a parity oracle:
both paths split the PRNG key identically, so for a fixed seed they emit
identical tokens.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ArchConfig
from ..models import build_model
from .faults import FaultConfig
from .metrics import span

__all__ = ["ServeConfig", "Engine", "pop_expert_rows"]


def pop_expert_rows(cache):
    """Split a decode step's cache from the ``expert_rows`` it may carry:
    the (row, held expert) pairs the step's routing chose (0 for a model
    without held experts).  A cache that goes round a loop leaves it out."""
    cache = dict(cache)
    return cache, cache.pop("expert_rows", jnp.int32(0))


@dataclasses.dataclass
class ServeConfig:
    max_len: int = 256
    temperature: float = 0.0  # 0 => greedy
    seed: int = 0
    # VUSA-packed decode (dense family, DESIGN.md §7): False = dense, "mlp"
    # packs the per-layer MLP trio, "all" (or True) additionally packs
    # wq/wk/wv/wo and the untied LM head — the whole dense-family decode step
    packed_weights: bool | str = False
    packed_mlp: bool = False  # deprecated alias for packed_weights="mlp"
    fused_mlp: bool = True  # megakernel MLP (False = 3-dispatch measured baseline)
    # packed value precision (DESIGN.md §10): "bf16" keeps the pack's native
    # float values (byte-identical program to before the knob existed);
    # "int8"/"int4" quantize value slots with per-(window, row) fp32 scales
    # and fuse dequant into the kernels' VMEM reconstruction
    packed_values: str = "bf16"
    vusa_m: int = 128  # window lanes (kernel tile)
    vusa_a: int = 16  # physical slots per row per job
    fused: bool = True  # on-device lax.scan decode loop (False = seed host loop)
    # prompt-length buckets for batched masked prefill (DESIGN.md §6); empty
    # tuple = powers of two from 8 up to max_len.  One compiled prefill
    # program per (bucket, batch-bucket) serves any prompt length.
    prefill_buckets: tuple = ()
    # paged KV pool (DESIGN.md §11): 0 = slot-stacked contiguous pool (the
    # pre-§11 layout); > 0 = fixed-size blocks of this many rows in a shared
    # arena with per-request block tables.  Must divide max_len (the gathered
    # block view must equal the slot-pool cache shape for bit-parity).
    page_size: int = 0
    # share identical prompt prefixes between requests: full pages by
    # refcounted block reuse, partial tail pages by copy-on-write.  Only
    # meaningful with page_size > 0.
    prefix_cache: bool = True
    # arena capacity in user blocks; 0 = worst case (slots * max_len/page,
    # no oversubscription).  Smaller values oversubscribe: admission checks
    # the worst case per request, mid-flight exhaustion preempts.
    arena_blocks: int = 0
    # chunked prefill (Sarathi-style, DESIGN.md §11): > 0 = split prompts
    # longer than this into chunks of this many tokens, co-scheduled with
    # decode segments so a long admission never stalls decoding slots.
    # Requires page_size > 0 and a page-multiple chunk.  0 = whole-prompt
    # prefill (the pre-§11 behaviour).
    prefill_chunk: int = 0
    # seeded fault-injection plan (DESIGN.md §9); None = no faults.  Pack
    # corruption is applied at Engine init (position flips before load
    # validation, value NaNs after); cache poisoning and admission stalls
    # are consumed by the Scheduler per admitted request.
    faults: Optional[FaultConfig] = None
    # self-speculative decoding via sparsity tiers (DESIGN.md §13): the SAME
    # weights magnitude-pruned at ``draft_sparsity`` and packed at
    # scope="all" draft ``draft_k`` greedy tokens per round; the configured
    # full-quality path verifies all of them in ONE multi-token dispatch and
    # the longest matching prefix is accepted.  Greedy speculative decode is
    # token-bit-identical to non-speculative decode.
    speculative: bool = False
    draft_k: int = 4
    draft_sparsity: float = 0.99

    def __post_init__(self):
        if self.packed_weights is True:
            self.packed_weights = "all"
        if self.packed_mlp and not self.packed_weights:
            self.packed_weights = "mlp"  # legacy spelling keeps its MLP-only scope
        if self.packed_weights not in (False, "mlp", "all"):
            raise ValueError(
                f"packed_weights must be False, 'mlp' or 'all', got {self.packed_weights!r}"
            )
        if self.packed_values not in ("bf16", "int8", "int4"):
            raise ValueError(
                f"packed_values must be 'bf16', 'int8' or 'int4', got {self.packed_values!r}"
            )
        if self.page_size < 0 or (self.page_size and self.max_len % self.page_size):
            raise ValueError(
                f"page_size {self.page_size} must be 0 (slot pool) or divide "
                f"max_len {self.max_len} (DESIGN.md §11 bit-parity contract)"
            )
        if self.prefill_chunk:
            if not self.page_size:
                raise ValueError("prefill_chunk requires page_size > 0 "
                                 "(chunks write through block tables)")
            if self.prefill_chunk % self.page_size:
                raise ValueError(
                    f"prefill_chunk {self.prefill_chunk} must be a multiple of "
                    f"page_size {self.page_size}"
                )
        if self.speculative:
            if self.draft_k < 1:
                raise ValueError(f"draft_k must be >= 1, got {self.draft_k}")
            if not (0.0 <= self.draft_sparsity < 1.0):
                raise ValueError(
                    f"draft_sparsity must be in [0, 1), got {self.draft_sparsity}"
                )
            if not self.fused:
                raise ValueError(
                    "speculative decoding requires the fused decode path"
                )


class Engine:
    def __init__(
        self, cfg: ArchConfig, params, sc: Optional[ServeConfig] = None, mesh=None,
        clock=None,
    ):
        """``mesh`` makes the whole decode/serve path mesh-aware (DESIGN.md
        §8): parameters are placed under ``dist.sharding.params_shardings``
        (TP on ``model``, FSDP on ``data``), decode caches shard their batch
        dim over ``data``, and VUSA packs shard their window axis over
        ``model`` with the kernels running per-shard under ``shard_map``.  A
        1x1 mesh (or ``mesh=None``) is the degenerate single-device path —
        same program, bit-identical tokens.

        ``clock`` injects the timing source (default ``time.monotonic`` —
        never wall clock, which jumps under NTP adjustment).  The Scheduler
        inherits it, so engine and scheduler timings share one timeline."""
        sc = ServeConfig() if sc is None else sc
        self.cfg, self.sc = cfg, sc
        self.model = build_model(cfg)
        self.mesh = mesh
        self._clock = clock or time.monotonic
        self._packed = None
        self._quarantined = False
        if sc.packed_weights:
            self._packed = self._build_pack(params, faults=sc.faults)
        self._draft_packed = None
        if sc.speculative:
            if cfg.family != "dense":
                raise ValueError(
                    "speculative decoding requires the dense family "
                    "(the drafter is a VUSA pack of the same weights)"
                )
            self._draft_packed = self._build_draft_pack(params)
        if mesh is not None:
            from ..dist.sharding import act_rules, params_shardings

            self._act_rules = act_rules(mesh)
            self._cache_axes = self.model.cache_batch_axes(sc.max_len)
            params = jax.device_put(params, params_shardings(self.model.specs(), mesh))
        self.params = params
        self._decode = jax.jit(self._decode_fn)
        self._decode_loop = jax.jit(self._decode_loop_fn, static_argnums=(4,))
        self._spec_loop = jax.jit(self._spec_loop_fn, static_argnums=(4,))
        self._prime_loop = jax.jit(self._prime_loop_fn)
        self._prefill = jax.jit(self._prefill_fn) if cfg.family in (
            "dense", "moe", "vlm", "encdec") else None
        # masked bucketed prefill — dense, and moe only when dropless:
        # capacity-bounded MoE dispatch couples co-batched rows (padding and
        # neighbour tokens consume shared expert capacity, changing which
        # tokens drop), so batching is only bit-exact when no token can ever
        # drop (``ArchConfig.moe_dropless``: moe_cf >= n_experts/top_k, or
        # the held-expert layer, dropless by construction).  encdec consumes
        # frames, and vlm needs per-request patch extras prime_many has no
        # way to carry (and whose patch-prefix KV rows the token-length slot
        # ``pos`` would disown).  Everything else falls back to per-request
        # admission.
        batchable = cfg.family == "dense" or (cfg.family == "moe" and cfg.moe_dropless)
        self._prefill_masked = jax.jit(self._prefill_masked_fn) if batchable else None
        # chunked-prefill entry for the paged pool (DESIGN.md §11): donates
        # the arena; jax.jit re-specializes per static chunk length, so one
        # wrapper serves every configured chunk/bucket size
        self._chunk = (
            jax.jit(self._chunk_fn, donate_argnums=(2,)) if batchable else None
        )
        self._buckets = self._make_buckets(sc)

    def _build_pack(self, params, faults: Optional[FaultConfig] = None):
        """Build (and optionally fault-corrupt, validate, and shard) a VUSA
        pack from host ``params`` per the engine's ServeConfig.  Used at init
        and by :meth:`reload_packed` for hot weight swaps."""
        from ..kernels.ops import mesh_axis_size  # local import: needs kernels
        from .packed import pack_lm_weights, shard_packed, validate_packed

        sc = self.sc
        # pack from the host params before any device placement, then
        # split the window axes over the model mesh axis
        packed = pack_lm_weights(
            self.cfg, params, sc.vusa_m, sc.vusa_a,
            scope=sc.packed_weights, fused_mlp=sc.fused_mlp,
            shards=mesh_axis_size(self.mesh, "model"),
            # "bf16" = unquantized passthrough: the pack keeps the native
            # param dtype, same program as before the knob existed
            value_dtype="dense" if sc.packed_values == "bf16" else sc.packed_values,
        )
        f = faults
        if f is not None and (f.pack_position_flips or f.pack_value_nans):
            from .faults import corrupt_pack_positions, corrupt_pack_values

            # position flips land *before* load validation — a corrupted
            # metadata byte must make the Engine refuse the pack here,
            # never serve from it.  Value NaNs land *after* validation,
            # modelling post-load in-memory corruption that only the
            # runtime isfinite guard can catch.
            packed = corrupt_pack_positions(packed, f)
            validate_packed(packed)
            packed = corrupt_pack_values(packed, f)
        if self.mesh is not None:
            packed = shard_packed(packed, self.mesh)
        return packed

    def _build_draft_pack(self, params):
        """Build the drafter: the SAME weights magnitude-pruned at
        ``draft_sparsity`` and packed whole (scope="all") — a fraction of the
        verifier pack's bytes, since the job count per window row scales
        with the surviving nonzeros (the paper's virtual upscaling).
        Magnitude pruning nests, so the drafter's weights are a subset of an
        already-pruned verifier's.  Values stay unquantized: drafter
        precision only moves the acceptance rate, never correctness (every
        emitted token comes out of the verifier), and no fault corruption is
        ever applied — the drafter is not the pack the fault plan targets."""
        from ..core.pruning import prune_tree
        from ..kernels.ops import mesh_axis_size
        from .packed import pack_lm_weights, shard_packed

        sc = self.sc
        drafted = prune_tree(params, sc.draft_sparsity)
        packed = pack_lm_weights(
            self.cfg, drafted, sc.vusa_m, sc.vusa_a,
            scope="all", fused_mlp=sc.fused_mlp,
            shards=mesh_axis_size(self.mesh, "model"),
            value_dtype="dense",
        )
        if self.mesh is not None:
            packed = shard_packed(packed, self.mesh)
        return packed

    # -- mesh helpers ---------------------------------------------------------
    def _mesh_ctx(self):
        """Activation-sharding context for the jitted bodies: installs the
        mesh + act_rules so ``models.common.shard`` constraints bind during
        tracing; a no-op context without a mesh."""
        if self.mesh is None:
            return contextlib.nullcontext()
        from ..models.common import mesh_context

        return mesh_context(self.mesh, self._act_rules)

    def shard_cache(self, cache, batch: int):
        """Place a decode cache on the mesh: batch dim over the DP axes
        (structurally located per leaf via ``cache_batch_axes``), everything
        else replicated.  No-op without a mesh."""
        if self.mesh is None:
            return cache
        from ..dist.sharding import serve_shardings

        return jax.device_put(
            cache, serve_shardings(cache, self.mesh, batch, batch_axes=self._cache_axes)
        )

    def _shard_batch(self, arr):
        """Shard an input's leading batch dim over the DP axes (no-op without
        a mesh; replicates when the batch does not divide)."""
        if self.mesh is None:
            return arr
        from ..dist.sharding import batch_sharding

        return jax.device_put(
            arr, batch_sharding(self.mesh, arr.shape[0], arr.ndim)
        )

    # -- jitted bodies --------------------------------------------------------
    def _decode_impl(self, params, token, cache, key, packed):
        """One decode step through ``packed`` (or dense when None).  Returns
        ``(next_token (B, 1), cache, ok (B,))`` where ``ok`` is the per-row
        integrity guard — ``isfinite`` over the fp32 logits (DESIGN.md §9).
        Computed on device and carried through the fused scan, it costs no
        extra host sync: the scheduler fetches it with the segment tokens.
        A model of held experts' cache carries the step's ``expert_rows``
        (``pop_expert_rows``), fetched the same way."""
        with self._mesh_ctx():
            if packed is not None:
                from .packed import lm_decode_step_packed

                logits, cache = lm_decode_step_packed(
                    params, packed, token, cache, self.cfg, mesh=self.mesh
                )
            else:
                logits, cache = self.model.decode_step(params, token, cache)
        with jax.named_scope("decode.sample"):
            logits = logits[:, -1].astype(jnp.float32)
            ok = jnp.isfinite(logits).all(axis=-1)
            if self.mesh is not None:
                # Pin the sampling computation replicated.  Under the default
                # (non-partitionable) threefry lowering, random bits generated
                # for a *sharded* (B, V) block differ from the single-device
                # stream — GSPMD offsets each shard's counter — so a sharded
                # categorical would emit different tokens than mesh=None for the
                # same seed.  Replicating the tiny logits block first keeps the
                # whole draw bit-identical at every mesh shape (DESIGN.md §8).
                from jax.sharding import NamedSharding, PartitionSpec

                logits = jax.lax.with_sharding_constraint(
                    logits, NamedSharding(self.mesh, PartitionSpec())
                )
            if self.sc.temperature > 0:
                nxt = jax.random.categorical(key, logits / self.sc.temperature)
            else:
                nxt = jnp.argmax(logits, axis=-1)
            return nxt.astype(jnp.int32)[:, None], cache, ok

    def _decode_fn(self, params, token, cache, key):
        """Decode step on the engine's configured path: packed when a pack is
        loaded and not quarantined, dense otherwise.  The branch binds at
        trace time; ``quarantine_packed`` re-jits so it re-binds."""
        packed = None if self._quarantined else self._packed
        return self._decode_impl(params, token, cache, key, packed)

    def _decode_dense_fn(self, params, token, cache, key):
        """Decode step forced onto the dense path regardless of pack state —
        the fallback the scheduler re-serves guard-tripped requests on."""
        return self._decode_impl(params, token, cache, key, None)

    def _decode_loop_fn(self, params, token, cache, key, steps: int):
        """Fused decode: ``steps`` model steps in one on-device scan.

        The scan's stacked output is the pre-allocated (steps, B) token
        buffer plus the per-step (B,) integrity flags; sampling keys are
        split on device each step, mirroring the host loop's
        ``jax.random.split`` sequence exactly.
        """

        def body(carry, _):
            token, cache, key = carry
            key, sub = jax.random.split(key)
            token, cache, ok = self._decode_fn(params, token, cache, sub)
            cache, _ = pop_expert_rows(cache)
            return (token, cache, key), (token[:, 0], ok)

        (token, cache, key), (toks, okg) = jax.lax.scan(
            body, (token, cache, key), None, length=steps
        )
        return toks.T, okg.T, token, cache, key  # (B, steps) each

    # -- self-speculative decoding (DESIGN.md §13) ----------------------------
    def _spec_round_impl(self, params, token, cache, kd, packed):
        """One draft/verify round at B=1: draft ``draft_k`` greedy tokens
        with the cheap high-sparsity pack, verify the whole draft (pending
        token + k drafts) in ONE multi-token dispatch of the configured
        full-quality path, accept the longest matching prefix.

        Returns ``(pending (1,1), cache, kd, emit (S,), nem (), okp (S,))``
        with ``S = draft_k + 1``: ``emit[:nem]`` are the tokens emitted this
        round (1 <= nem <= S; the final one is the verifier's own sample
        past the matched prefix and becomes the next pending token), ``okp``
        the per-position verifier integrity flags.

        Bit-parity with non-speculative decode is by construction:

        * The drafter writes its KV rows at ``pos..pos+k-1``, but the
          verifier — after rewinding ``pos`` — rewrites ALL of rows
          ``pos..pos+k`` before attending, so verifier logits are provably
          independent of drafter cache content (a corrupt drafter can only
          lower the acceptance rate, never change an emitted token).
        * Rejected positions need no KV rollback: setting the new ``pos`` to
          ``pos + nem`` masks rows past it via the ``slots <= pos`` validity
          (stale rows are finite and get overwritten when reached again).
        * The PRNG key splits once per EMITTED token — exactly the
          non-speculative sequence — so sampled decode is bit-identical too:
          position i's logits equal the sequential step's (multi-token
          parity) and its draw consumes the same subkey.
        """
        from ..kernels.vusa_packed import calls_repeat
        from .packed import lm_decode_step_packed

        k = self.sc.draft_k
        S = k + 1
        pos0 = cache["pos"]
        with self._mesh_ctx():

            def draft_body(carry, _):
                tok, c = carry
                logits, c = lm_decode_step_packed(
                    params, self._draft_packed, tok, c, self.cfg, mesh=self.mesh
                )
                nxt = jnp.argmax(
                    logits[:, -1].astype(jnp.float32), axis=-1
                ).astype(jnp.int32)
                return (nxt[:, None], c), nxt

            with calls_repeat(k):
                (_, cache), drafts = jax.lax.scan(
                    draft_body, (token, cache), None, length=k
                )
            seq = jnp.concatenate([token, jnp.moveaxis(drafts, 0, 1)], axis=1)
            cache = {**cache, "pos": pos0}  # rewind: verifier rewrites rows pos0..pos0+k
            if packed is not None:
                logits, cache = lm_decode_step_packed(
                    params, packed, seq, cache, self.cfg, mesh=self.mesh
                )
            else:
                logits, cache = self.model.decode_step(params, seq, cache)
        logits = logits.astype(jnp.float32)  # (1, S, V)
        okp = jnp.isfinite(logits).all(axis=-1)[0]  # (S,)
        if self.mesh is not None:
            # same replication pin as _decode_impl: sampling must stay
            # bit-identical at every mesh shape (DESIGN.md §8)
            from jax.sharding import NamedSharding, PartitionSpec

            logits = jax.lax.with_sharding_constraint(
                logits, NamedSharding(self.mesh, PartitionSpec())
            )
        # sequential accept loop (unrolled, S is small): position i is
        # emitted iff drafts 1..i all matched; the key only advances for
        # emitted positions, so the surviving stream replays the
        # non-speculative split sequence exactly
        emit = jnp.zeros((S,), jnp.int32)
        accept = jnp.bool_(True)
        nem = jnp.int32(0)
        for i in range(S):
            nk, sub = jax.random.split(jax.random.wrap_key_data(kd))
            if self.sc.temperature > 0:
                v = jax.random.categorical(sub, logits[0, i] / self.sc.temperature)
            else:
                v = jnp.argmax(logits[0, i], axis=-1)
            v = v.astype(jnp.int32)
            emit = emit.at[i].set(jnp.where(accept, v, 0))
            kd = jnp.where(accept, jax.random.key_data(nk), kd)
            nem = nem + accept.astype(jnp.int32)
            if i < k:
                accept = jnp.logical_and(accept, v == seq[0, i + 1])
            else:
                accept = jnp.bool_(False)
        cache = {**cache, "pos": pos0 + nem}
        pending = jnp.take(emit, nem - 1)[None, None]  # (1, 1)
        return pending, cache, kd, emit, nem, okp

    def _spec_round_fn(self, params, token, cache, kd):
        """Speculative round on the engine's configured verifier path:
        packed when loaded and not quarantined, dense otherwise."""
        packed = None if self._quarantined else self._packed
        return self._spec_round_impl(params, token, cache, kd, packed)

    def _spec_round_dense_fn(self, params, token, cache, kd):
        """Speculative round with the verifier forced dense (quarantine
        fallback).  The drafter keeps its own pack — it was built and
        validated separately, and verification guards every emission —
        so fallback tokens stay dense-bit-identical while still drafting."""
        return self._spec_round_impl(params, token, cache, kd, None)

    def _spec_loop_fn(self, params, token, cache, kd, budget: int):
        """Fused speculative decode: while_loop over draft/verify rounds
        until ``budget`` tokens are emitted — ONE dispatch for the whole
        generation, like the non-speculative fused scan.  Each round emits
        at least one token, so the loop is bounded by ``budget`` rounds.

        The emit/ok buffers carry ``budget + S`` entries: a round writes its
        full S-wide window at the current count and only the first ``nem``
        entries are valid — the next round's window starts there and
        overwrites the rejected tail, so garbage only ever lives past the
        final count, beyond what the host reads."""
        S = self.sc.draft_k + 1
        buf = jnp.zeros((budget + S,), jnp.int32)
        okb = jnp.ones((budget + S,), bool)

        def cond(st):
            return st[4] < budget

        def body(st):
            token, cache, kd, buf, count, okb, rounds = st
            token, cache, kd, emit, nem, okp = self._spec_round_fn(
                params, token, cache, kd
            )
            buf = jax.lax.dynamic_update_slice(buf, emit, (count,))
            okb = jax.lax.dynamic_update_slice(okb, okp, (count,))
            return (token, cache, kd, buf, count + nem, okb, rounds + 1)

        st = (token, cache, kd, buf, jnp.int32(0), okb, jnp.int32(0))
        token, cache, kd, buf, count, okb, rounds = jax.lax.while_loop(cond, body, st)
        return buf, okb, count, rounds, token, cache, kd

    def _prime_loop_fn(self, params, prompts, cache, key):
        """Recurrent-family prompt priming: scan the prompt through decode
        steps on device (state capture is O(1) per token)."""

        def body(carry, tok):
            _, cache, key = carry
            key, sub = jax.random.split(key)
            nxt, cache, _ = self._decode_fn(params, tok[:, None], cache, sub)
            cache, _ = pop_expert_rows(cache)
            return (nxt, cache, key), None

        init = (prompts[:, :1], cache, key)
        (nxt, cache, key), _ = jax.lax.scan(body, init, prompts.T)
        return nxt, cache, key

    def _prefill_fn(self, params, batch):
        with self._mesh_ctx():
            return self.model.prefill(params, batch, self.sc.max_len)

    def _prefill_masked_fn(self, params, batch, lengths):
        """Masked bucketed prefill: right-padded (B, bucket) tokens with true
        ``lengths`` (B,) — per-row logits/KV bit-identical to unpadded
        prefill (DESIGN.md §6).  Returns the greedy first token too, so
        admission needs no extra dispatch."""
        with self._mesh_ctx():
            logits, cache = self.model.prefill(params, batch, self.sc.max_len, lengths=lengths)
        nxt = jnp.argmax(logits.astype(jnp.float32), axis=-1)[:, None].astype(jnp.int32)
        return nxt, cache

    def _chunk_fn(self, params, tokens, arena, table_row, start, true_len, write_from):
        with self._mesh_ctx():
            return self.model.prefill_chunk(
                params, tokens, arena, table_row, start, true_len, write_from
            )

    # -- prompt-length buckets -------------------------------------------------
    @staticmethod
    def _make_buckets(sc: ServeConfig):
        if sc.prefill_buckets:
            bks = sorted(set(int(b) for b in sc.prefill_buckets))
            if bks[0] < 1 or bks[-1] > sc.max_len:
                raise ValueError(f"prefill_buckets {bks} outside [1, max_len={sc.max_len}]")
            if bks[-1] < sc.max_len:
                # always cover max_len: a prompt longer than the largest
                # bucket would otherwise fall back to exact-length compiles,
                # silently unbounding the compile count under ragged traffic
                bks.append(sc.max_len)
            return bks
        bks, b = [], 8
        while b < sc.max_len:
            bks.append(b)
            b *= 2
        bks.append(sc.max_len)
        return bks

    @property
    def prefill_buckets(self):
        return tuple(self._buckets)

    @property
    def batched_prefill(self) -> bool:
        """True when the family supports one-dispatch bucketed admission."""
        return self._prefill_masked is not None

    @property
    def paged_supported(self) -> bool:
        """True when the family can serve from a paged KV pool (DESIGN.md
        §11): a KV-shaped cache *and* batching-exact prefill (dense, or
        dropless moe — the same condition as bucketed admission, because
        prefix-suffix recompute and chunking re-batch prompt tokens).
        Recurrent/vlm families silently keep the dense per-slot pool."""
        return (
            self._prefill_masked is not None
            and self.model.paged_seq_len(self.sc.max_len) is not None
        )

    def prefill_chunk(self, tokens, arena, table_row, start, true_len, write_from):
        """One chunk of a paged chunked prefill (B=1): see
        ``families.lm_prefill_chunk``.  Donates ``arena``; returns
        ``(logits (1, V), arena')``."""
        self._validate_tokens(tokens)
        with span("serve.prefill", rows=1, bucket=tokens.shape[1]):
            return self._chunk(
                self.params, jnp.asarray(tokens, jnp.int32), arena, table_row,
                jnp.int32(start), jnp.int32(true_len), jnp.int32(write_from),
            )

    def bucket_len(self, n: int) -> int:
        """Smallest configured bucket >= n (the bucket set always covers
        max_len, and prime/prime_many reject prompts past it)."""
        for b in self._buckets:
            if b >= n:
                return b
        return n  # unreachable for admitted prompts; keeps the helper total

    # -- integrity / degradation ----------------------------------------------
    @property
    def quarantined(self) -> bool:
        return self._quarantined

    @property
    def packed_active(self) -> bool:
        """True while decode actually runs through the packed path."""
        return self._packed is not None and not self._quarantined

    def quarantine_packed(self) -> bool:
        """Permanently drop the packed decode path for this engine (called by
        the scheduler when a slot trips the non-finite guard under packed
        weights — DESIGN.md §9).  Dense weights are always resident, so the
        dense path needs no reload; the jitted entry points are re-wrapped so
        the trace-time packed/dense branch re-binds.  Returns True if the
        engine transitioned, False if there was nothing to quarantine."""
        if not self.packed_active:
            return False
        self._quarantined = True
        self._rejit_decode()
        return True

    def _rejit_decode(self) -> None:
        """Re-wrap the jitted decode entry points so the trace-time pack
        binding (the pack's arrays are closed over as constants) re-binds to
        the engine's current ``_packed`` / ``_quarantined`` state."""
        self._decode = jax.jit(self._decode_fn)
        self._decode_loop = jax.jit(self._decode_loop_fn, static_argnums=(4,))
        self._spec_loop = jax.jit(self._spec_loop_fn, static_argnums=(4,))
        self._prime_loop = jax.jit(self._prime_loop_fn)

    def reload_packed(self, params=None) -> bool:
        """Hot-swap the packed decode path (DESIGN.md §12): rebuild the pack
        from ``params`` (default: the engine's current params — e.g. after a
        quarantine, to re-arm the packed path from known-good weights),
        validate it, clear any quarantine, and re-jit the decode entry points
        so the new pack binds.  No fault corruption is applied — swapped-in
        packs are presumed clean; the runtime isfinite guard still covers
        them.  The caller must ensure no segment is in flight (the async
        engine drains first).  Returns False when the engine is not
        configured for packed weights (nothing to swap)."""
        if not self.sc.packed_weights:
            return False
        from .packed import validate_packed

        if params is not None:
            if self.mesh is not None:
                from ..dist.sharding import params_shardings

                params = jax.device_put(
                    params, params_shardings(self.model.specs(), self.mesh)
                )
            self.params = params
        host_params = jax.device_get(self.params)
        packed = self._build_pack(host_params)
        validate_packed(packed)
        self._packed = packed
        self._quarantined = False
        self._rejit_decode()
        return True

    def _validate_tokens(self, tokens) -> None:
        """Reject out-of-range token ids before they reach the embedding
        gather.  ``params["embed"][tokens]`` silently wraps negative ids and
        clamps ids >= vocab on accelerator backends, so a malformed prompt
        would otherwise generate from the wrong embedding row with no error
        anywhere downstream."""
        toks = np.asarray(tokens)
        bad = (toks < 0) | (toks >= self.cfg.vocab)
        if bad.any():
            idx = tuple(int(x) for x in np.argwhere(bad)[0])
            raise ValueError(
                f"token id {int(toks[idx])} at position {idx} is outside "
                f"[0, vocab={self.cfg.vocab})"
            )

    # -- reusable entry points (used by generate and serve/scheduler.py) ------
    def prime(self, prompts, key, extras: Optional[Dict] = None):
        """Run the prompt through the model: returns ``(first_token, cache,
        key)`` ready for decode.  ``prompts``: (B, S) int32.

        Prefill families (dense/moe/vlm/encdec) bulk-fill the KV cache and
        emit the argmax first token without consuming the key; recurrent
        families scan the prompt through decode steps, splitting the key per
        prompt token — both exactly as the seed host loop did, so the key
        stream stays bit-compatible across paths.
        """
        if self._prefill is not None and prompts.shape[1] > self.sc.max_len:
            raise ValueError(
                f"prompt length {prompts.shape[1]} exceeds max_len {self.sc.max_len}"
            )
        self._validate_tokens(prompts)
        with span("serve.prefill", rows=prompts.shape[0], bucket=prompts.shape[1]):
            batch = {"tokens": self._shard_batch(jnp.asarray(prompts))}
            if extras:
                batch.update({k: self._shard_batch(jnp.asarray(v)) for k, v in extras.items()})
            if self._prefill is not None:
                logits, cache = self._prefill(self.params, batch)
                nxt = jnp.argmax(logits.astype(jnp.float32), axis=-1)[:, None].astype(jnp.int32)
                # the recurrent paths below place their cache at init and keep
                # that placement through the loop; only the prefill output needs
                # an explicit move onto the serve shardings
                cache = self.shard_cache(cache, prompts.shape[0])
            elif self.sc.fused:
                cache = self.shard_cache(
                    self.model.init_cache(prompts.shape[0], self.sc.max_len), prompts.shape[0]
                )
                nxt, cache, key = self._prime_loop(self.params, batch["tokens"], cache, key)
            else:
                # seed path: prime the state by stepping through the prompt
                cache = self.shard_cache(
                    self.model.init_cache(prompts.shape[0], self.sc.max_len), prompts.shape[0]
                )
                nxt = jnp.asarray(prompts[:, :1])
                for t in range(prompts.shape[1]):
                    key, sub = jax.random.split(key)
                    tok = jnp.asarray(prompts[:, t : t + 1])
                    nxt, cache, _ = self._decode(self.params, tok, cache, sub)
        return nxt, cache, key

    def prime_many(self, prompts, lengths):
        """Batched masked prefill of one length bucket: ``prompts`` (N, Sb)
        int32 right-padded to a shared bucket length, ``lengths`` (N,) true
        prompt lengths.  Returns ``(first_tokens (N, 1), batched cache)`` in a
        single dispatch; each row is bit-identical to ``prime`` of that row's
        unpadded prompt.  The cache's scalar ``pos`` holds the padded bucket
        length — scatter it with ``write_slots`` (which sets per-slot true
        ``pos``) before decoding.  Prefill LM families only (prefill ignores
        the PRNG key there; recurrent families prime per request)."""
        if self._prefill_masked is None:
            raise NotImplementedError(
                f"batched masked prefill unsupported for family {self.cfg.family!r}"
            )
        prompts = np.asarray(prompts, np.int32)
        if prompts.shape[1] > self.sc.max_len:
            raise ValueError(
                f"bucket length {prompts.shape[1]} exceeds max_len {self.sc.max_len}"
            )
        self._validate_tokens(prompts)
        with span("serve.prefill", rows=prompts.shape[0], bucket=prompts.shape[1]):
            return self._prefill_masked(
                self.params,
                {"tokens": self._shard_batch(jnp.asarray(prompts))},
                self._shard_batch(jnp.asarray(lengths, jnp.int32)),
            )

    def decode_segment(self, token, cache, key, steps: int):
        """``steps`` fused decode steps in one dispatch: returns
        ``(tokens (B, steps), ok (B, steps), last_token, cache, key)`` where
        ``ok[b, t]`` is the on-device integrity flag for row ``b`` at step
        ``t`` (False once logits go non-finite)."""
        return self._decode_loop(self.params, token, cache, key, steps)

    # -- public API -----------------------------------------------------------
    def generate(self, prompts: np.ndarray, max_new: int = 32, extras: Optional[Dict] = None):
        """prompts: (B, S) int32.  Returns dict with tokens and timing.

        Thin wrapper over ``prime`` + one full-length ``decode_segment``
        (a single-request schedule with one segment); the seed per-token
        host loop survives behind ``ServeConfig.fused = False`` as the
        parity oracle.  ``tok_per_s`` is the canonical serve metric
        (``serve.metrics.tok_per_s``): ACCEPTED tokens beyond the first
        (prefill-billed) one over decode wall time — identical on every
        path, speculative included.

        With ``ServeConfig.speculative`` (B=1 only) decode runs the fused
        draft/verify while_loop — still one dispatch — and the result dict
        additionally reports ``spec_rounds`` / ``spec_proposed`` /
        ``spec_accepted`` / ``acceptance_rate``.
        """
        from .metrics import acceptance_rate, tok_per_s

        b = prompts.shape[0]
        headroom = self.sc.draft_k if self.sc.speculative else 0
        if self._prefill is not None and (
            prompts.shape[1] + max_new + headroom > self.sc.max_len
        ):
            # without this, decode past max_len silently overwrites the last
            # KV row (attention_decode's dynamic_update_slice clamps its
            # write index) and corrupts every later token; a speculative
            # round additionally writes up to draft_k rows past the budget
            raise ValueError(
                f"prompt({prompts.shape[1]}) + max_new({max_new}) + "
                f"spec headroom({headroom}) = "
                f"{prompts.shape[1] + max_new + headroom} exceeds max_len "
                f"{self.sc.max_len}"
            )
        if self.sc.speculative and b != 1:
            raise ValueError(
                f"speculative generate serves B=1 (got batch {b}); the "
                "accept length is per-request — batch through the Scheduler"
            )
        key = jax.random.key(self.sc.seed)
        t0 = self._clock()
        nxt, cache, key = self.prime(prompts, key, extras)
        jax.block_until_ready(nxt)
        t_prefill = self._clock() - t0

        t0 = self._clock()
        if self.sc.speculative:
            buf, okb, count, rounds, *_ = self._spec_loop(
                self.params, nxt, cache, jax.random.key_data(key), max_new - 1
            )
            jax.block_until_ready(buf)
            t_decode = self._clock() - t0
            toks = np.asarray(buf)[: max_new - 1]
            tokens = np.concatenate([np.asarray(nxt), toks[None]], axis=1)
            finite = bool(np.asarray(okb)[: max_new - 1].all())
            count, rounds = int(count), int(rounds)
            k = self.sc.draft_k
            return {
                "tokens": tokens,
                "finite": finite,
                "prefill_s": t_prefill,
                "decode_s": t_decode,
                "tok_per_s": tok_per_s(max_new - 1, t_decode),
                "spec_rounds": rounds,
                "spec_proposed": rounds * k,
                # each round emits 1 verifier token + (nem-1) accepted drafts
                "spec_accepted": count - rounds,
                "acceptance_rate": acceptance_rate(count - rounds, rounds * k),
            }
        if self.sc.fused:
            toks, okg, _, cache, key = self.decode_segment(nxt, cache, key, max_new - 1)
            jax.block_until_ready(toks)
            t_decode = self._clock() - t0
            tokens = np.concatenate([np.asarray(nxt), np.asarray(toks)], axis=1)
            finite = bool(np.asarray(okg).all())
        else:
            out, finite = [np.asarray(nxt)], True
            for _ in range(max_new - 1):
                key, sub = jax.random.split(key)
                nxt, cache, ok = self._decode(self.params, nxt, cache, sub)
                out.append(np.asarray(nxt))
                finite = finite and bool(np.asarray(ok).all())
            jax.block_until_ready(nxt)
            t_decode = self._clock() - t0
            tokens = np.concatenate(out, axis=1)
        return {
            "tokens": tokens,
            "finite": finite,
            "prefill_s": t_prefill,
            "decode_s": t_decode,
            "tok_per_s": tok_per_s(b * (max_new - 1), t_decode),
        }
