"""Continuous-batching scheduler over the fused decode loop.

``Engine.generate`` serves one fixed batch of equal-length prompts for a
fixed ``max_new``; real traffic is ragged.  :class:`Scheduler` keeps a fixed
pool of in-flight *slots* and alternates two phases (DESIGN.md §5, §6):

  admission   free slots are filled with queued requests whose arrival time
              has passed, highest priority first (arrival order breaks
              ties).  Arrivals are coalesced per round and grouped into
              prompt-length buckets: each bucket is primed in ONE batched
              masked-prefill dispatch (``Engine.prime_many``) and scattered
              into its slots with ONE donated multi-slot write
              (``models.cache.write_slots``) — admission of N same-bucket
              requests costs O(1) dispatches and zero host syncs.
              Recurrent families (and ``admission="sequential"``, the
              measured baseline) fall back to per-request exact-length
              priming.
  decode      one jitted *segment* — ``segment`` fused ``lax.scan`` steps
              of the whole pool, vmapped over the slot axis — runs on
              device, then syncs once; finished slots (EOS or budget)
              retire and free up for the next admission round.  First-token
              EOS/budget checks are deferred to this sync too, so admission
              itself never blocks on a device->host transfer.

Each slot is an independent B=1 decode cache stacked on a leading slot axis
(:mod:`repro.models.cache`), with its own scalar ``pos`` and its own PRNG
key stream seeded from the request.  That makes every completed request's
tokens bit-identical to a one-shot ``Engine.generate`` of the same prompt,
seed and temperature at batch 1 — the scheduler changes *when* work runs,
never *what* it computes.  Bucketed prefill preserves this bit-for-bit:
right-padding keeps every real token's causal window unchanged and padded
keys are masked to exactly-zero probability (DESIGN.md §6).  Free slots
decode along with the pool (cheaper than masking the hot path); their
output is discarded and their state is replaced wholesale at the next
admission.

The segment length trades sync overhead against retirement latency: the
pool only retires/admits at segment boundaries, so a slot whose request
finished mid-segment decodes (and discards) at most ``segment - 1`` extra
tokens.  The segment shape is static — one compiled program serves the
whole run regardless of arrival pattern, and the bucketed prefill programs
(one per length bucket x batch bucket) serve any traffic shape without
recompiling.

Production hardening (DESIGN.md §9) rides the same sync points, so none of
it adds host transfers:

* **deadlines / cancellation** — ``Request.deadline_s`` is enforced at the
  segment sync (and at admission: a request whose queue wait already blew
  its deadline is shed without ever being primed); ``cancel(rid)`` removes
  queued requests immediately and flags in-flight ones for retirement at
  the next sync.  Every terminal path lands in ``Completion.status``.
* **backpressure** — ``queue_cap`` bounds the queue; ``shed_policy``
  decides who pays: ``"reject"`` the new request, ``"shed-oldest"`` the
  longest-waiting queued one, or ``"shed-lowest-priority"`` the lowest-
  priority queued one (only when the newcomer outranks it).
* **integrity guard + dense fallback** — the engine's per-row ``isfinite``
  flag is carried through the segment scan and fetched with the token grid
  in the same ``device_get``.  A slot that trips the guard truncates its
  tokens at the first bad step; under active packed weights the pack is
  quarantined (``Engine.quarantine_packed``) and the request is re-admitted
  ONCE on the dense path — completing as ``FAILED_FALLBACK_OK`` with tokens
  bit-identical to a clean dense run, since re-admission re-primes from the
  prompt with the request's own seed.  A second trip fails the request for
  good: the retry is bounded, never a loop.

Paged KV pool (DESIGN.md §11, ``ServeConfig.page_size > 0``): the
slot-stacked contiguous pool is replaced by a shared block arena plus
per-slot block tables (``models.cache``).  The run loop is unchanged —
admission, one fused segment, one sync — but admission allocates blocks
lazily (pages covering the prompt up front, decode pages extended at each
sync), retirement refcount-frees them, and identical prompt prefixes share
read-only blocks through the allocator's hash registry (full pages by
refcount, partial tail pages by copy-on-write).  Mid-flight arena
exhaustion preempts the latest-admitted slot (its request re-queues and
re-primes — same seed, identical tokens), so the earliest admission always
progresses.  With ``prefill_chunk > 0`` long prompts prefill in chunks
co-scheduled between decode segments: one chunk per round per admitting
slot, so decoding slots keep stepping through an arbitrarily long
admission.  The decode math is untouched — the gathered block view is
shape-identical to the slot-pool cache — so paged decode stays
bit-identical to the slot pool (tests/test_paged.py).
"""

from __future__ import annotations

import bisect
import dataclasses
import enum
import math
import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.vusa_packed import fold_tally
from .engine import Engine, pop_expert_rows
from .metrics import SpanTimes, acceptance_rate, tok_per_s

__all__ = ["Request", "Completion", "Scheduler", "Status"]


class Status(str, enum.Enum):
    """Terminal state of a request (``Completion.status``)."""

    OK = "OK"
    TIMEOUT = "TIMEOUT"  # deadline blown — queued (never primed) or in flight
    CANCELLED = "CANCELLED"
    REJECTED = "REJECTED"  # backpressure: refused at submit, or shed from the queue
    FAILED_FALLBACK_OK = "FAILED_FALLBACK_OK"  # guard trip, dense retry delivered
    FAILED = "FAILED"  # guard trip, bounded retry also tripped
    STALLED = "STALLED"  # watchdog abort: segment hung past the stall timeout


@dataclasses.dataclass
class Request:
    """One generation request.  ``arrival_s`` is an offset from ``run()``
    start (0 = already queued); ``seed`` seeds this request's private PRNG
    stream, mirroring ``ServeConfig.seed`` in one-shot generate.
    ``deadline_s`` is relative to arrival (None = no deadline); higher
    ``priority`` admits first and survives ``shed-lowest-priority``."""

    prompt: np.ndarray  # (S,) int32
    max_new: int = 32
    eos_id: Optional[int] = None
    seed: int = 0
    arrival_s: float = 0.0
    deadline_s: Optional[float] = None
    priority: int = 0


@dataclasses.dataclass
class Completion:
    rid: int
    tokens: np.ndarray  # (<= max_new,) int32, truncated just after eos_id
    arrival_s: float
    admit_s: float
    finish_s: float
    status: Status = Status.OK
    ttft_s: float = float("nan")  # time to first token, from arrival

    @property
    def latency_s(self) -> float:
        return self.finish_s - self.arrival_s


@dataclasses.dataclass
class _PrefillJob:
    """Host state of an in-progress paged prefill (DESIGN.md §11): chunked
    long-prompt admission, prefix-suffix recompute after a partial prefix
    hit, or the 1-token re-peek of a fully prefix-matched prompt
    (``write_from == L``: attention over the shared blocks, zero writes)."""

    prompt: np.ndarray
    L: int
    start: int  # next chunk's first sequence position
    write_from: int  # first row this request may write (rows below are shared)
    chunk: int  # chunk width (one compiled chunk program per width)
    seed: int
    poisoned: bool = False  # fault plan: poison fires at completion, not admission


@dataclasses.dataclass
class _Slot:
    """Host-side bookkeeping for one in-flight slot."""

    rid: int = -1
    tokens: Optional[List[int]] = None
    first: Optional[jax.Array] = None  # deferred first token (device, (1, 1))
    remaining: int = 0
    eos_id: Optional[int] = None
    arrival_s: float = 0.0
    admit_s: float = 0.0
    deadline: float = float("inf")  # absolute run-relative deadline
    ttft_s: float = float("nan")
    req: Optional[Request] = None  # kept for the bounded dense-retry requeue
    prefill: Optional[_PrefillJob] = None  # paged: chunked admission in flight
    last_emit_t: float = float("nan")  # last sync that emitted tokens (ITL)

    @property
    def active(self) -> bool:
        return self.rid >= 0


_SHED_POLICIES = ("reject", "shed-oldest", "shed-lowest-priority")


class Scheduler:
    """Continuous-batching run loop over a fused-decode :class:`Engine`."""

    def __init__(
        self,
        engine: Engine,
        slots: int = 4,
        segment: int = 8,
        admission: str = "batched",
        queue_cap: Optional[int] = None,
        shed_policy: str = "reject",
        clock: Optional[Callable[[], float]] = None,
        sleep: Optional[Callable[[float], None]] = None,
    ):
        if not engine.sc.fused:
            raise ValueError("Scheduler requires a fused-decode engine (ServeConfig.fused)")
        if slots < 1 or segment < 1:
            raise ValueError(f"need slots >= 1 and segment >= 1, got {slots}, {segment}")
        if admission not in ("batched", "sequential"):
            raise ValueError(f"admission must be 'batched' or 'sequential', got {admission!r}")
        if queue_cap is not None and queue_cap < 1:
            raise ValueError(f"queue_cap must be None or >= 1, got {queue_cap}")
        if shed_policy not in _SHED_POLICIES:
            raise ValueError(f"shed_policy must be one of {_SHED_POLICIES}, got {shed_policy!r}")
        self.eng = engine
        # clock defaults to the ENGINE's injectable clock (monotonic unless a
        # test swapped it), so one injection point covers engine timings and
        # scheduler deadlines alike; an explicit `clock=` still wins
        clock = clock or engine._clock
        self.model = engine.model
        self.slots = slots
        self.segment = segment
        # "batched" coalesces arrivals into bucketed one-dispatch prefills
        # (when the family supports masked prefill); "sequential" keeps the
        # per-request exact-length path as the measured baseline
        self.admission = admission
        self.queue_cap = queue_cap
        self.shed_policy = shed_policy
        # injectable time sources: tests drive deadlines/cancellation with a
        # fake clock instead of real sleeps, keeping the suite fast and exact
        self._clock = clock or time.monotonic
        self._sleep = sleep or time.sleep
        # (arrival_s, rid, Request), kept sorted by (arrival_s, rid) at
        # submit time so arrived requests are always a front prefix —
        # admission pops O(k) per round instead of re-scanning the backlog
        self._queue: List[tuple] = []
        self._completions: Dict[int, Completion] = {}
        self._next_rid = 0
        self._slot: List[_Slot] = [_Slot() for _ in range(slots)]
        # device state: slot-stacked cache, per-slot tokens and raw key data.
        # Under a mesh the slot axis — the serve path's batch dim — is
        # sharded over the DP mesh axes (DESIGN.md §8): the KV pool's bytes
        # scale out with ``data`` while the packed weights scale out with
        # ``model`` inside the engine's decode step.
        kshape = jax.random.key_data(jax.random.key(0)).shape
        self._token = jnp.zeros((slots, 1, 1), jnp.int32)
        self._kdata = jnp.zeros((slots,) + kshape, jnp.uint32)
        # paged KV pool (DESIGN.md §11): page_size > 0 swaps the slot-stacked
        # contiguous pool for a block arena + per-slot tables.  Families the
        # paged layout can't host (recurrent state, vlm patch rows) silently
        # keep the slot pool — same knob, same scheduler, dense fallback.
        self.paged = bool(engine.sc.page_size) and engine.paged_supported
        self._prefix_on = self.paged and engine.sc.prefix_cache
        self._chunk_cfg = engine.sc.prefill_chunk if self.paged else 0
        if self.paged:
            from ..models.cache import (
                BlockAllocator,
                PagedLayout,
                paged_block_bytes,
                paged_pool_bytes,
            )

            self._layout = PagedLayout.build(
                slots, engine.sc.max_len, engine.sc.page_size, engine.sc.arena_blocks
            )
            self._alloc = BlockAllocator(self._layout)
            self._pstate = self.model.init_paged_pool(self._layout, engine.sc.max_len)
            if engine.mesh is not None:
                from ..models.cache import paged_shardings

                self._pstate = jax.device_put(
                    self._pstate, paged_shardings(self._pstate, engine.mesh)
                )
            self._arena_names = tuple(sorted(self._pstate["arena"].keys()))
            self._block_bytes = paged_block_bytes(self._pstate)
            self._arena_bytes = paged_pool_bytes(self._pstate)
            # host mirrors of the device tables/positions — kept exact (every
            # pos/table mutation happens at a host-driven event), so table
            # extension and page accounting never read the device back
            self._rows = np.stack(
                [np.full(self._layout.n_pages, self._layout.scratch_block(i), np.int32)
                 for i in range(slots)]
            )
            self._pos = [0] * slots
            self._slot_blocks: List[List[int]] = [[] for _ in range(slots)]
            self._slot_private: List[List[int]] = [[] for _ in range(slots)]
            self._slot_npages = [0] * slots
            self._seg_paged = jax.jit(
                self._segment_paged_fn, static_argnums=(4, 5), donate_argnums=(1, 2, 3)
            )
            self._bind = jax.jit(self._bind_fn, donate_argnums=(0, 1, 2))
            self._fill = jax.jit(self._fill_fn, donate_argnums=(0,))
            self._rebind = jax.jit(self._rebind_fn, donate_argnums=(0,))
            self._zero = jax.jit(self._zero_fn, donate_argnums=(0,))
            self._copyb = jax.jit(self._copy_fn, donate_argnums=(0,))
            self._poisonb = jax.jit(self._poison_blk_fn, donate_argnums=(0,))
            self._resetp = jax.jit(self._reset_fn, donate_argnums=(0,))
            self._cache = None
            self._batch_axes = None
            self._slot_bytes = self._arena_bytes // max(slots, 1)
        else:
            self._cache = self.model.init_slot_cache(slots, engine.sc.max_len)
            if engine.mesh is not None:
                from ..models.cache import slot_shardings

                self._cache = jax.device_put(
                    self._cache, slot_shardings(self._cache, engine.mesh)
                )
            self._batch_axes = self.model.cache_batch_axes(engine.sc.max_len)
            self._slot_bytes = sum(
                int(np.prod(leaf.shape)) * leaf.dtype.itemsize
                for leaf in jax.tree.leaves(self._cache)
            ) // max(slots, 1)
        if engine.mesh is not None:
            from ..dist.sharding import batch_sharding

            self._token = jax.device_put(
                self._token, batch_sharding(engine.mesh, slots, self._token.ndim)
            )
            self._kdata = jax.device_put(
                self._kdata, batch_sharding(engine.mesh, slots, self._kdata.ndim)
            )
        # self-speculative decoding (DESIGN.md §13): each scan step of the
        # segment dispatch becomes one draft/verify ROUND, advancing a slot
        # by 1..draft_k+1 tokens, so every worst-case KV-growth bound that
        # used ``segment`` must use ``span = segment * (draft_k + 1)``
        self.speculative = bool(engine.sc.speculative)
        self._draft_k = engine.sc.draft_k if self.speculative else 0
        self._span = segment * (self._draft_k + 1)
        # donate the pool state: segments and admissions update it in place.
        # ``dense`` is static: quarantining the pack flips it, forcing the
        # retrace that rebinds the decode step onto the dense path.
        self._seg = jax.jit(
            self._segment_fn, static_argnums=(4, 5), donate_argnums=(1, 2, 3)
        )
        if self.speculative:
            self._seg_spec = jax.jit(
                self._segment_spec_fn, static_argnums=(4, 5), donate_argnums=(1, 2, 3)
            )
            if self.paged:
                self._seg_spec_paged = jax.jit(
                    self._segment_spec_paged_fn,
                    static_argnums=(4, 5), donate_argnums=(1, 2, 3),
                )
        self._write = jax.jit(self._write_fn, donate_argnums=(0, 1, 2))
        self._write_many = jax.jit(self._write_many_fn, donate_argnums=(0, 1, 2))
        self._derive_keys = jax.jit(
            jax.vmap(lambda s: jax.random.key_data(jax.random.key(s)))
        )
        from ..models.cache import poison_slot

        self._poison = jax.jit(poison_slot, donate_argnums=(0,))
        # hardening state (reset per run epoch by _maybe_reset)
        self._cancel: set = set()  # in-flight rids to retire at the next sync
        self._retried: set = set()  # rids that used their bounded dense retry
        self._fallback_rids: set = set()  # rids currently on the dense retry
        self._fault_fired: set = set()  # rids whose one-shot cache fault ran
        self._counters: Dict[str, int] = dict(
            rejected=0, shed=0, timed_out=0, cancelled=0,
            fallback=0, failed=0, quarantined=0, preempted=0, stalled=0,
            # speculative accounting (host-consumed view): drafts proposed in
            # rounds a slot consumed from, and how many of them were accepted
            spec_proposed=0, spec_accepted=0,
            # segment syncs, and the prefill programs admission dispatched:
            # real prompt tokens against the positions they computed
            # (bucket length x padded batch rows)
            syncs=0, prefill_dispatches=0, prefill_tokens=0, prefill_positions=0,
            # (row, held expert) pairs the plain segments' routing chose, over
            # the slots that held a request (paged pool) or every slot
            expert_rows=0,
        )
        # packed-kernel calls per decode step of the last traced segment
        # program: folded into the slot rows, and left to the grid-axis
        # fallback (``_vmap_slots``); set at trace time, not per run epoch
        self._kernel_calls = (0, 0)
        # streaming/watchdog state (DESIGN.md §12).  `_abort_status` is the
        # fail-fast flag another thread (the async engine's watchdog) sets:
        # the run loop checks it at every sync and inside every injected
        # stall wait, retires or re-queues the in-flight work, and returns.
        # `_draining` stops admission — in-flight work finishes, the queue
        # survives — for clean shutdown and hot pack swaps.  `_stall_fired`
        # makes seeded decode stalls one-shot per rid; `_stall_retried`
        # bounds the watchdog re-queue exactly like `_retried` bounds the
        # dense retry: a rid aborted twice is terminal STALLED, never a loop.
        self._abort_status: Optional[Status] = None
        self._draining = False
        self._stall_fired: set = set()
        self._stall_retried: set = set()
        self._itl: List[float] = []  # per-token inter-token latency samples
        self._ran = False  # epoch flag: True after run() so the next
        # submit()/cancel()/run() starts a fresh completion/counter epoch
        self._run_now: Optional[Callable[[], float]] = None
        # run stats
        self._seg_steps = 0
        self._active_slot_steps = 0
        # host seconds per phase of the run loop (serve.* spans)
        self._spans = SpanTimes(self._clock)
        # cache observability (DESIGN.md §11): Σ used-KV bytes and Σ active
        # slots, sampled once per segment sync — their ratio is the
        # HBM-bytes-per-active-request gauge the paged bench gates on
        self._kv_used_acc = 0
        self._kv_active_acc = 0
        self._alloc_snap = (0, 0, 0, 0)  # (hits, lookups, cow, evictions) at epoch start

    # -- epoch ----------------------------------------------------------------

    def _maybe_reset(self) -> None:
        """Start a fresh stats/completions epoch on the first mutation after a
        finished run.  Resetting lazily (instead of at the top of ``run``)
        lets submit-time rejections land in the same epoch as the run that
        follows them — the REJECTED completion must survive into the
        ``run()`` result, not be wiped by it."""
        if not self._ran:
            return
        self._ran = False
        self._completions = {}
        self._cancel = set()
        self._retried = set()
        self._fallback_rids = set()
        self._fault_fired = set()
        # _stall_fired/_stall_retried deliberately survive the epoch reset: a
        # watchdog abort ENDS the run, so the bounded re-queue it leaves in
        # the queue is consumed by the NEXT run() — wiping the sets here
        # would re-fire one-shot stalls and unbound the stall retry.  Rids
        # never reuse, so stale entries can never collide.
        self._itl = []
        for k in self._counters:
            self._counters[k] = 0
        self._seg_steps = 0
        self._active_slot_steps = 0
        self._spans.reset()
        self._kv_used_acc = self._kv_active_acc = 0
        if self.paged:
            # the prefix registry itself persists across epochs (warm cache is
            # the point); only the rate counters snapshot per epoch
            self._alloc_snap = (
                self._alloc.hits, self._alloc.lookups,
                self._alloc.cow_copies, self._alloc.evictions,
            )

    # -- submission -----------------------------------------------------------

    def submit(self, req: Request, rid: Optional[int] = None) -> int:
        """Queue a request; returns its request id.  Under a full queue
        (``queue_cap``) the shed policy decides who pays: the newcomer is
        REJECTED, or a queued victim is shed (also REJECTED, counted under
        ``shed``) to make room.  ``rid`` pins the id explicitly — journal
        recovery re-queues crashed requests under their ORIGINAL rids so the
        journal stream stays contiguous across the crash (DESIGN.md §12)."""
        self._maybe_reset()
        prompt = np.asarray(req.prompt, np.int32).reshape(-1)
        if req.max_new < 1:  # before the budget check: a negative max_new
            raise ValueError("max_new must be >= 1")  # could slip past it
        # worst-case KV rows this request can occupy: a slot decodes whole
        # segments, and under speculation each segment step is a round that
        # can write up to draft_k+1 rows (self._span == segment otherwise)
        budget = prompt.shape[0] + req.max_new + self._span
        if budget > self.eng.sc.max_len:
            raise ValueError(
                f"prompt({prompt.shape[0]}) + max_new({req.max_new}) + "
                f"segment span({self._span}) = {budget} exceeds max_len "
                f"{self.eng.sc.max_len}"
            )
        if self.paged:
            worst = -(-budget // self._layout.page)
            if worst > self._layout.user_blocks:
                raise ValueError(
                    f"worst-case pages {worst} for this request exceed the "
                    f"arena's {self._layout.user_blocks} user blocks "
                    f"(page_size={self._layout.page}, "
                    f"arena_blocks={self.eng.sc.arena_blocks}) — even an "
                    "empty pool could never hold it"
                )
        if rid is None:
            rid = self._next_rid
            self._next_rid += 1
        else:
            if rid in self._completions or any(r == rid for _, r, _ in self._queue) or any(
                s.active and s.rid == rid for s in self._slot
            ):
                raise ValueError(f"rid {rid} is already live or terminal")
            self._next_rid = max(self._next_rid, rid + 1)
        req = dataclasses.replace(req, prompt=prompt)
        if self.queue_cap is not None and len(self._queue) >= self.queue_cap:
            if not self._make_room(req):
                self._finish_unadmitted(rid, req, Status.REJECTED)
                self._counters["rejected"] += 1
                return rid
        bisect.insort(self._queue, (req.arrival_s, rid, req))
        return rid

    def _make_room(self, req: Request) -> bool:
        """Apply the shed policy to a full queue; True if a slot was freed
        for ``req``.  ``shed-oldest`` evicts the longest-waiting entry;
        ``shed-lowest-priority`` evicts the lowest-priority one (latest
        arrival breaks ties — it would have been served last anyway) and
        only when the newcomer strictly outranks it, so equal-priority
        traffic cannot churn the queue."""
        if self.shed_policy == "reject":
            return False
        if self.shed_policy == "shed-oldest":
            j = 0
        else:  # shed-lowest-priority
            j = min(
                range(len(self._queue)),
                key=lambda t: (
                    self._queue[t][2].priority,
                    -self._queue[t][0],
                    -self._queue[t][1],
                ),
            )
            if self._queue[j][2].priority >= req.priority:
                return False
        _, vrid, vreq = self._queue.pop(j)
        self._finish_unadmitted(vrid, vreq, Status.REJECTED)
        self._counters["shed"] += 1
        return True

    def cancel(self, rid: int) -> bool:
        """Cancel a request: queued requests complete CANCELLED immediately;
        in-flight ones retire (with their partial tokens) at the next
        segment sync.  Returns False when ``rid`` is unknown or already
        terminal — cancellation never raises."""
        self._maybe_reset()
        for j, (_, r, req) in enumerate(self._queue):
            if r == rid:
                del self._queue[j]
                now = self._run_now() if self._run_now is not None else float("nan")
                self._finish_unadmitted(rid, req, Status.CANCELLED, finish=now)
                self._counters["cancelled"] += 1
                return True
        for s in self._slot:
            if s.active and s.rid == rid:
                self._cancel.add(rid)
                return True
        return False

    # -- streaming control plane (DESIGN.md §12) ------------------------------

    def drain(self) -> None:
        """Stop admission: in-flight requests finish normally, queued ones
        stay queued, and ``run()`` returns once no slot is active.  The
        clean-shutdown / hot-swap primitive — nothing is dropped.  Safe to
        call from another thread mid-run (a bool flag read at sync points)."""
        self._draining = True

    def resume_admission(self) -> None:
        """Re-open admission after :meth:`drain` (e.g. once a hot pack swap
        finished re-jitting)."""
        self._draining = False

    @property
    def draining(self) -> bool:
        return self._draining

    def abort(self, status: Status = Status.STALLED) -> None:
        """Fail-fast escape hatch, set by the watchdog when a segment stalls
        past its timeout: the run loop notices at the next interruptible
        point (sync boundaries and every injected stall/sleep wait), deals
        with the in-flight work and returns instead of hanging the caller.

        Each in-flight request gets ONE bounded re-queue (its re-execution
        under its own seed emits a bit-identical stream, so the consumer
        just sees the tail arrive late); a request caught in a second abort
        retires terminally with ``status`` — a persistent hang cannot loop.
        Safe to call from another thread."""
        self._abort_status = status

    @property
    def has_work(self) -> bool:
        """True while anything is queued or in flight."""
        return bool(self._queue) or any(s.active for s in self._slot)

    def inflight_tokens(self) -> Dict[int, List[int]]:
        """Host snapshot of every in-flight request's tokens emitted so far
        — what a streaming frontend diffs at each ``on_sync`` to push new
        tokens (zero device traffic: these lists are already on the host)."""
        return {
            s.rid: list(s.tokens)
            for s in self._slot
            if s.active and s.tokens is not None
        }

    def completions_so_far(self) -> Dict[int, Completion]:
        """Snapshot of this epoch's terminal completions (usable mid-run
        from ``on_sync``, unlike the dict ``run`` eventually returns)."""
        return dict(self._completions)

    def itl_samples(self) -> List[float]:
        """This epoch's inter-token-latency samples — one per *emission
        event*.  Tokens are observable only at segment syncs, so everything
        a slot emits at one sync surfaces at the same wall-clock instant:
        that event contributes exactly one ``t - last_emit_t`` interval (see
        :meth:`_note_emission`), never ``k`` copies of an average.  The
        first-ever emission sets the baseline and samples nothing — TTFT
        owns the first token."""
        return list(self._itl)

    def refresh_decode(self) -> None:
        """Re-jit the segment dispatchers after an ``Engine.reload_packed``
        hot swap.  The jitted segment bodies close over the engine's pack
        arrays as trace-time constants — the static ``dense`` flag only
        covers quarantine transitions, not a *new* pack — so without this a
        swapped engine would keep serving the old pack's trace.  Only call
        between runs or while drained (no segment in flight)."""
        self._seg = jax.jit(
            self._segment_fn, static_argnums=(4, 5), donate_argnums=(1, 2, 3)
        )
        if self.paged:
            self._seg_paged = jax.jit(
                self._segment_paged_fn, static_argnums=(4, 5), donate_argnums=(1, 2, 3)
            )
        if self.speculative:
            self._seg_spec = jax.jit(
                self._segment_spec_fn, static_argnums=(4, 5), donate_argnums=(1, 2, 3)
            )
            if self.paged:
                self._seg_spec_paged = jax.jit(
                    self._segment_spec_paged_fn,
                    static_argnums=(4, 5),
                    donate_argnums=(1, 2, 3),
                )

    def verify_paged_mirror(self) -> bool:
        """Recovery invariant check (DESIGN.md §12): the host-side block
        table / position mirrors must agree with the device arena's control
        plane.  One tiny device_get of table+pos — debug/test tool, never on
        the hot path.  True (or raises) on slot-pool schedulers."""
        if not self.paged:
            return True
        from ..models.cache import paged_host_mirror

        table, pos = paged_host_mirror(self._pstate)
        for i, s in enumerate(self._slot):
            if not (s.active and s.prefill is None):
                continue  # free/admitting slots legitimately drift
            if not np.array_equal(table[i], self._rows[i]) or int(pos[i]) != self._pos[i]:
                raise AssertionError(
                    f"paged host mirror diverged for slot {i}: "
                    f"host pos {self._pos[i]} vs device {int(pos[i])}"
                )
        return True

    def _finish_unadmitted(
        self, rid: int, req: Request, status: Status, finish: float = float("nan")
    ) -> None:
        """Record a terminal completion for a request that never held a slot
        (rejected / shed / queue-cancelled / deadline-shed).  Timing fields
        that never happened stay NaN, per the stats convention."""
        self._completions[rid] = Completion(
            rid=rid,
            tokens=np.zeros(0, np.int32),
            arrival_s=req.arrival_s,
            admit_s=float("nan"),
            finish_s=finish,
            status=status,
        )

    # -- jitted segment body --------------------------------------------------

    def _vmap_slots(self, one, *args, in_axes=0):
        """``jax.vmap(one, in_axes)(*args)`` over the slot axis, recording
        how its packed-kernel calls met the vmap (``stats()``'s
        ``packed_calls_folded`` and ``packed_calls_fallback``)."""
        with fold_tally() as tally:
            out = jax.vmap(one, in_axes=in_axes)(*args)
        self._kernel_calls = (tally.folded, tally.fallback)
        return out

    def _segment_fn(self, params, token, kdata, cache, steps: int, dense: bool):
        """``steps`` decode steps of all slots; returns the emitted token grid
        and per-step integrity flags, both ``(steps, slots)``, plus the
        advanced state.  Each slot splits its own key and samples at batch
        1, exactly as one-shot generate does.  ``dense`` (static) forces the
        dense decode path — flipped by pack quarantine, it keys a retrace so
        the packed/dense branch rebinds.

        Free slots decode along with the pool (their output is discarded and
        their whole state is replaced at the next admission), so the hot
        path carries no per-slot masking — a free slot's ``pos`` merely
        drifts until re-admission, and ``attention_decode`` clamps its cache
        writes at ``max_len``."""
        decode = self.eng._decode_dense_fn if dense else self.eng._decode_fn

        def body(carry, _):
            token, kdata, cache = carry

            def one(tok, kd, c):
                key = jax.random.wrap_key_data(kd)
                key, sub = jax.random.split(key)
                nxt, c2, ok = decode(params, tok, c, sub)
                c2, rows = pop_expert_rows(c2)
                return nxt, jax.random.key_data(key), c2, ok, rows

            token, kdata, cache, ok, rows = self._vmap_slots(one, token, kdata, cache)
            return (token, kdata, cache), (token[:, 0, 0], ok[:, 0], rows.sum())

        (token, kdata, cache), (toks, okg, rows) = jax.lax.scan(
            body, (token, kdata, cache), None, length=steps
        )
        return token, kdata, cache, toks, okg, rows.sum()

    def _segment_paged_fn(self, params, token, kdata, pstate, steps: int, dense: bool):
        """Paged twin of :meth:`_segment_fn`.  Each slot decodes against the
        *shared* arena (a vmap constant — only its block table and ``pos``
        carry the slot axis) and returns its new KV row as a pending write;
        the conflict-free scatter into the arena happens once per step,
        outside the slot vmap.  The gathered block view inside the model is
        shape-identical to the slot-pool cache, so the math — and the emitted
        tokens — are bit-identical to :meth:`_segment_fn`."""
        from ..models.cache import paged_in_axes, paged_scatter_token, paged_view

        decode = self.eng._decode_dense_fn if dense else self.eng._decode_fn
        names = self._arena_names

        def body(carry, _):
            token, kdata, pstate = carry

            def one(tok, kd, c):
                key = jax.random.wrap_key_data(kd)
                key, sub = jax.random.split(key)
                nxt, c2, ok = decode(params, tok, c, sub)
                rows = {n + "_new": c2[n + "_new"] for n in names}
                return nxt, jax.random.key_data(key), rows, ok, pop_expert_rows(c2)[1]

            # a slot holds a request while its table points past the
            # reserved blocks; free slots' routing is not counted
            live = pstate["table"][:, 0] >= self._layout.reserved
            token, kdata, rows, ok, er = self._vmap_slots(
                one, token, kdata, paged_view(pstate), in_axes=(0, 0, paged_in_axes(pstate))
            )
            with jax.named_scope("decode.attention"):
                pstate = paged_scatter_token(pstate, rows)
            return (token, kdata, pstate), (token[:, 0, 0], ok[:, 0], (er * live).sum())

        (token, kdata, pstate), (toks, okg, er) = jax.lax.scan(
            body, (token, kdata, pstate), None, length=steps
        )
        return token, kdata, pstate, toks, okg, er.sum()

    def _segment_spec_fn(self, params, token, kdata, cache, steps: int, dense: bool):
        """Speculative twin of :meth:`_segment_fn` (DESIGN.md §13): each scan
        step runs one draft/verify ROUND per slot instead of one decode step,
        so a slot advances by 1..S tokens per step (S = draft_k+1).  Returns
        per-round grids: tokens (steps, slots, S), accepted counts ``nem``
        (steps, slots), and per-position integrity flags (steps, slots, S) —
        the host consumes ``tokens[r, i, :nem[r, i]]`` of each round.  The
        PRNG key advances once per EMITTED token inside the round, so the
        surviving key/token stream is bit-identical to :meth:`_segment_fn`'s
        one-split-per-step stream."""
        spec = self.eng._spec_round_dense_fn if dense else self.eng._spec_round_fn

        def body(carry, _):
            token, kdata, cache = carry

            def one(tok, kd, c):
                pending, c2, kd2, emit, nem, okp = spec(params, tok, c, kd)
                return pending, kd2, c2, emit, nem, okp

            token, kdata, cache, emit, nem, okp = self._vmap_slots(one, token, kdata, cache)
            return (token, kdata, cache), (emit, nem, okp)

        (token, kdata, cache), (toks, nems, okg) = jax.lax.scan(
            body, (token, kdata, cache), None, length=steps
        )
        return token, kdata, cache, toks, nems, okg

    def _segment_spec_paged_fn(
        self, params, token, kdata, pstate, steps: int, dense: bool
    ):
        """Paged twin of :meth:`_segment_spec_fn`.  A speculative round needs
        a contiguous multi-token cache, so each slot first gathers its block
        table into exactly the ``(1, max_len)`` view the slot pool holds
        (same math as ``attention_decode``'s paged branch — bit-identical
        tokens), runs the round on it, and hands back the S verifier KV rows
        it wrote at ``pos..pos+S-1``; the conflict-free scatter into the
        shared arena happens once per round, outside the slot vmap
        (:func:`repro.models.cache.paged_scatter_rows`).  Rejected-tail rows
        are scattered too — they mirror the contiguous pool's
        stale-but-finite rows, masked past ``pos`` until overwritten."""
        from ..models.cache import paged_in_axes, paged_scatter_rows, paged_view

        spec = self.eng._spec_round_dense_fn if dense else self.eng._spec_round_fn
        names = self._arena_names
        S = self._draft_k + 1
        max_len = self.eng.sc.max_len

        def body(carry, _):
            token, kdata, pstate = carry
            start = pstate["pos"]  # (slots,) round-start positions

            def one(tok, kd, c):
                pos0 = c["pos"]
                row = c["table"]
                contig = {"pos": pos0}
                for n in names:
                    a = c[n]  # (L, n_blocks, page, ...) arena leaf (vmap const)
                    g = a[:, row]  # (L, n_pages, page, ...)
                    contig[n] = g.reshape(a.shape[0], 1, -1, *a.shape[3:])[
                        :, :, :max_len
                    ]
                pending, c2, kd2, emit, nem, okp = spec(params, tok, contig, kd)
                rows = {
                    n + "_new": jax.lax.dynamic_slice_in_dim(c2[n], pos0, S, axis=2)
                    for n in names
                }
                return pending, kd2, rows, emit, nem, okp

            token, kdata, rows, emit, nem, okp = self._vmap_slots(
                one, token, kdata, paged_view(pstate), in_axes=(0, 0, paged_in_axes(pstate))
            )
            with jax.named_scope("decode.attention"):
                pstate = paged_scatter_rows(pstate, rows, start, nem)
            return (token, kdata, pstate), (emit, nem, okp)

        (token, kdata, pstate), (toks, nems, okg) = jax.lax.scan(
            body, (token, kdata, pstate), None, length=steps
        )
        return token, kdata, pstate, toks, nems, okg

    # -- jitted paged-pool mutations (all donate the pool state) --------------

    @staticmethod
    def _bind_fn(pstate, token, kdata, idx, rows, lengths, nxt, kds):
        """Donated one-dispatch bind of prefilled requests into slots ``idx``:
        block-table rows, positions, first tokens and PRNG key data.  Padding
        rows carry an out-of-range index and drop — the paged counterpart of
        ``_write_many_fn``."""
        from ..models.cache import bind_slot_pages

        table, pos = bind_slot_pages(pstate["table"], pstate["pos"], idx, rows, lengths)
        token = token.at[idx].set(nxt[:, :, None], mode="drop")
        kdata = kdata.at[idx].set(kds.astype(kdata.dtype), mode="drop")
        return {"arena": pstate["arena"], "table": table, "pos": pos}, token, kdata

    @staticmethod
    def _fill_fn(pstate, page_tables, primed):
        """Donated scatter of a primed contiguous cache into arena blocks
        (sentinel table entries — padding rows, shared pages — drop)."""
        from ..models.cache import write_prefill_pages

        return {**pstate, "arena": write_prefill_pages(pstate["arena"], page_tables, primed)}

    @staticmethod
    def _rebind_fn(pstate, idx, rows, lengths):
        """Donated table-row rewrite (lazy decode-page extension): repoint
        slots ``idx`` at ``rows`` without touching tokens or keys."""
        from ..models.cache import bind_slot_pages

        table, pos = bind_slot_pages(pstate["table"], pstate["pos"], idx, rows, lengths)
        return {"arena": pstate["arena"], "table": table, "pos": pos}

    @staticmethod
    def _zero_fn(pstate, ids):
        from ..models.cache import zero_blocks

        return {**pstate, "arena": zero_blocks(pstate["arena"], ids)}

    @staticmethod
    def _copy_fn(pstate, src, dst):
        from ..models.cache import copy_block

        return {**pstate, "arena": copy_block(pstate["arena"], src, dst)}

    @staticmethod
    def _poison_blk_fn(pstate, blk):
        from ..models.cache import paged_poison_block

        return {**pstate, "arena": paged_poison_block(pstate["arena"], blk)}

    @staticmethod
    def _reset_fn(pstate, i, scratch_id):
        from ..models.cache import paged_reset_slot

        return paged_reset_slot(pstate, i, scratch_id)

    def _zero_ids(self, ids) -> None:
        """Zero arena blocks ``ids`` host-side list, chunked to a fixed jit
        width (out-of-range padding entries are no-ops on device)."""
        w = self._layout.n_pages
        ids = list(ids)
        for j in range(0, len(ids), w):
            grp = ids[j : j + w]
            grp += [self._layout.oob] * (w - len(grp))
            self._pstate = self._zero(self._pstate, jnp.asarray(grp, jnp.int32))

    # -- admission / retirement ----------------------------------------------

    @staticmethod
    def _write_fn(cache, token, kdata, i, sub, nxt, kd):
        """Donated single-dispatch write of a primed request into slot ``i``
        (cache + first token + key data in one go); ``i`` is traced, so one
        compilation covers every slot."""
        from ..models.cache import write_slot

        return write_slot(cache, i, sub), token.at[i].set(nxt), kdata.at[i].set(kd)

    def _write_many_fn(self, cache, token, kdata, idx, sub, nxt, kds, lengths):
        """Donated one-dispatch scatter of a whole primed bucket into slots
        ``idx``: batched caches (per-slot true ``pos`` = lengths), first
        tokens, and per-request PRNG key data ``kds``.  Batch-bucket padding
        rows carry an out-of-range index and are dropped; one compilation
        covers every batch bucket."""
        from ..models.cache import write_slots

        cache = write_slots(cache, idx, sub, self._batch_axes, lengths)
        token = token.at[idx].set(nxt[:, :, None], mode="drop")
        kdata = kdata.at[idx].set(kds.astype(kdata.dtype), mode="drop")
        return cache, token, kdata

    def _kds_for(self, seeds, nb: int):
        """Per-request PRNG key data, padded to batch ``nb``: one vmapped
        derivation when every seed fits the uint32 word jax.random.key folds
        it into (bit-exact there, verified in tests); anything else — wide
        seeds an int32 array would overflow on, negative seeds whose x64
        folding differs from the uint32 cast — falls back to eager
        per-request key creation (still no host sync)."""
        seeds = list(seeds)
        if all(0 <= s < 2**32 for s in seeds):
            packed = np.asarray(seeds + [0] * (nb - len(seeds)), np.uint32)
            return self._derive_keys(jnp.asarray(packed))
        zero = jnp.zeros(self._kdata.shape[1:], self._kdata.dtype)
        return jnp.stack(
            [jax.random.key_data(jax.random.key(s)) for s in seeds]
            + [zero] * (nb - len(seeds))
        )

    def _bind_slot(self, i: int, rid: int, req: Request, first, now: float) -> None:
        slot = self._slot[i]
        slot.rid, slot.tokens, slot.first = rid, [], first
        slot.remaining = req.max_new - 1
        slot.arrival_s, slot.admit_s = req.arrival_s, now
        slot.eos_id = req.eos_id
        slot.deadline = (
            req.arrival_s + req.deadline_s if req.deadline_s is not None else float("inf")
        )
        slot.ttft_s = float("nan")
        slot.req = req
        slot.prefill = None

    def _admit(self, i: int, rid: int, req: Request, now: float) -> None:
        """Per-request exact-length admission (recurrent families, and the
        ``admission="sequential"`` baseline): B=1 prime + single-slot write.
        First-token EOS/budget checks are deferred to the segment sync, so
        no device->host transfer happens here."""
        key = jax.random.key(req.seed)
        nxt, cache, key = self.eng.prime(req.prompt[None], key)
        self._note_prefill(len(req.prompt), len(req.prompt))
        self._cache, self._token, self._kdata = self._write(
            self._cache, self._token, self._kdata,
            jnp.int32(i), cache, nxt, jax.random.key_data(key),
        )
        self._bind_slot(i, rid, req, nxt, now)

    def _admit_batched(self, free: List[int], picked, now: float) -> None:
        """Coalesced bucketed admission: group this round's arrivals by
        prompt-length bucket, prime each bucket in one batched masked
        prefill, scatter each into its slots in one donated write.  The
        batch dim is padded to a power of two so compile count stays
        O(len buckets x log2 slots), not O(distinct traffic shapes)."""
        groups: Dict[int, list] = {}
        for i, (rid, req) in zip(free, picked):
            groups.setdefault(self.eng.bucket_len(len(req.prompt)), []).append((i, rid, req))
        for blen, group in groups.items():
            nb = 1 << (len(group) - 1).bit_length()
            tokens = np.zeros((nb, blen), np.int32)
            lengths = np.ones(nb, np.int32)  # padding rows: 1-token dummy
            idx = np.full(nb, self.slots, np.int32)  # OOB -> dropped by the scatter
            for j, (i, rid, req) in enumerate(group):
                tokens[j, : len(req.prompt)] = req.prompt
                lengths[j] = len(req.prompt)
                idx[j] = i
            kds = self._kds_for([req.seed for _, _, req in group], nb)
            nxt, cache = self.eng.prime_many(tokens, lengths)
            self._note_prefill(int(lengths[: len(group)].sum()), nb * blen)
            self._cache, self._token, self._kdata = self._write_many(
                self._cache, self._token, self._kdata,
                jnp.asarray(idx), cache, nxt, kds, jnp.asarray(lengths),
            )
            for j, (i, rid, req) in enumerate(group):
                self._bind_slot(i, rid, req, nxt[j : j + 1], now)

    def _note_prefill(self, tokens: int, positions: int) -> None:
        """Count one prefill dispatch: ``tokens`` real prompt tokens over the
        ``positions`` its program computes."""
        self._counters["prefill_dispatches"] += 1
        self._counters["prefill_tokens"] += tokens
        self._counters["prefill_positions"] += positions

    # -- paged admission (DESIGN.md §11) --------------------------------------

    def _admit_paged(self, free: List[int], picked, now: float) -> list:
        """Paged admission round: per request, consult the prefix cache,
        allocate the prompt's blocks, then either join this round's bucketed
        whole-prefill (no prefix hit, short prompt) or start a chunked
        prefill job (long prompt, or a prefix hit whose suffix must be
        recomputed against the shared blocks).  If the arena can't cover a
        request *right now* it re-queues — no admission-time preemption, so
        two big prompts can never thrash each other out; mid-flight
        extension is where preemption lives.  Returns the ``(slot, rid,
        req)`` triples actually admitted (fault injection targets only
        those)."""
        admitted, whole = [], []
        pairs = list(zip(free, picked))
        for n_done, (i, (rid, req)) in enumerate(pairs):
            if not self._plan_paged_one(i, rid, req, now, whole):
                for j, (rid2, req2) in pairs[n_done:]:
                    bisect.insort(self._queue, (req2.arrival_s, rid2, req2))
                break
            admitted.append((i, rid, req))
        if whole:
            self._prime_whole_paged(whole)
        return admitted

    def _plan_paged_one(self, i: int, rid: int, req: Request, now: float, whole) -> bool:
        """Allocate/share blocks for one request and decide its prefill path.
        False = arena cannot cover its prompt pages right now (matched
        references are returned before bailing)."""
        from ..models.cache import prefix_page_digests, prefix_tail_digests

        prompt, L = req.prompt, len(req.prompt)
        page = self._layout.page
        f = self.eng.sc.faults
        poisoned = (
            f is not None
            and f.wants_cache_nan(rid)
            and (not f.cache_nan_once or rid not in self._fault_fired)
        )
        full = prefix_page_digests(prompt, page) if self._prefix_on else []
        shared = self._alloc.match_pages(full) if self._prefix_on else []
        k = len(shared)
        cow = None
        if self._prefix_on and L % page and k == L // page:
            # every full page matched — probe the partial tail for a COW source
            seed = full[-1] if full else b""
            cow = self._alloc.match_tail(prefix_tail_digests(seed, prompt[k * page :]))
        n_prompt_pages = -(-L // page)
        got = self._alloc.alloc(n_prompt_pages - k)
        if got is None:
            if shared:
                self._alloc.free(shared)  # hashed: parked back in the cached pool
            return False
        priv, scrub = got
        if scrub:
            self._zero_ids(scrub)
        row = self._rows[i]
        row[:] = self._layout.scratch_block(i)
        row[:k] = shared
        row[k:n_prompt_pages] = priv
        self._slot_blocks[i] = list(shared) + list(priv)
        self._slot_private[i] = list(priv)
        self._slot_npages[i] = n_prompt_pages
        start = k * page
        if cow is not None:
            src, rows_m = cow
            # copy the matched tail rows into our private tail page; the
            # sharer keeps reading the original — divergence is free
            self._pstate = self._copyb(self._pstate, jnp.int32(src), jnp.int32(priv[0]))
            start += rows_m
        self._bind_slot(i, rid, req, None, now)
        if start == 0 and (self._chunk_cfg == 0 or L <= self._chunk_cfg):
            whole.append((i, rid, req, poisoned))
            return True
        if start >= L:
            # fully matched prompt: skip re-prefill entirely — one 1-token
            # "re-peek" chunk recomputes the last position's logits against
            # the shared blocks (write_from = L: zero arena writes)
            job = _PrefillJob(prompt, L, start=L - 1, write_from=L,
                              chunk=self._chunk_cfg or self.eng.bucket_len(1),
                              seed=req.seed, poisoned=poisoned)
        else:
            cw = self._chunk_cfg or self.eng.bucket_len(max(L - start, 1))
            job = _PrefillJob(prompt, L, start=start, write_from=start,
                              chunk=cw, seed=req.seed, poisoned=poisoned)
        self._slot[i].prefill = job
        return True

    def _prime_whole_paged(self, whole) -> None:
        """Bucketed one-dispatch whole-prompt prefill for this round's
        no-prefix-hit requests, scattered into their arena pages and bound in
        one donated write each — the paged mirror of ``_admit_batched``
        (bit-exact page scatter keeps slot-pool parity)."""
        groups: Dict[int, list] = {}
        for i, rid, req, poisoned in whole:
            groups.setdefault(self.eng.bucket_len(len(req.prompt)), []).append(
                (i, rid, req, poisoned)
            )
        n_pages = self._layout.n_pages
        for blen, group in groups.items():
            nb = 1 << (len(group) - 1).bit_length()
            tokens = np.zeros((nb, blen), np.int32)
            lengths = np.ones(nb, np.int32)
            idx = np.full(nb, self.slots, np.int32)  # OOB -> dropped binds
            # the primed cache spans max_len rows (right-padded); pages past
            # the prompt carry the sentinel and drop in the scatter
            pt = np.full((nb, n_pages), self._layout.oob, np.int32)
            rows_arr = np.zeros((nb, n_pages), np.int32)
            for j, (i, rid, req, poisoned) in enumerate(group):
                tokens[j, : len(req.prompt)] = req.prompt
                lengths[j] = len(req.prompt)
                idx[j] = i
                npp = self._slot_npages[i]
                pt[j, :npp] = self._rows[i][:npp]
                rows_arr[j] = self._rows[i]
            kds = self._kds_for([req.seed for _, _, req, _ in group], nb)
            nxt, cache = self.eng.prime_many(tokens, lengths)
            self._note_prefill(int(lengths[: len(group)].sum()), nb * blen)
            primed = {name: cache[name] for name in self._arena_names}
            self._pstate = self._fill(self._pstate, jnp.asarray(pt), primed)
            self._pstate, self._token, self._kdata = self._bind(
                self._pstate, self._token, self._kdata,
                jnp.asarray(idx), jnp.asarray(rows_arr), jnp.asarray(lengths),
                nxt, kds,
            )
            for j, (i, rid, req, poisoned) in enumerate(group):
                self._slot[i].first = nxt[j : j + 1]
                self._pos[i] = len(req.prompt)
                if not poisoned:
                    self._register_prompt(i, req.prompt)

    def _register_prompt(self, i: int, prompt: np.ndarray) -> None:
        """Hash-register slot ``i``'s prompt pages for future prefix sharing
        (first writer wins; already-shared pages re-register as no-ops).
        Never called for fault-poisoned requests — a poisoned block must not
        be matchable."""
        if not self._prefix_on:
            return
        from ..models.cache import prefix_page_digests, prefix_tail_digests

        page = self._layout.page
        full = prefix_page_digests(prompt, page)
        row = self._rows[i]
        for p, d in enumerate(full):
            self._alloc.register_page(d, int(row[p]))
        tail_len = len(prompt) % page
        if tail_len:
            seed = full[-1] if full else b""
            td = prefix_tail_digests(seed, prompt[len(full) * page :])
            self._alloc.register_tail(td[-1], int(row[len(full)]), tail_len)

    def _step_prefills(self) -> None:
        """Advance every in-flight prefill job by ONE chunk — co-scheduled
        between decode segments, so a long admission never stalls decoding
        slots (Sarathi-style chunked prefill, DESIGN.md §11).  A completed
        job binds its slot (table row, position, deferred first token, PRNG
        stream) and registers its prefix hashes."""
        if not self.paged:
            return
        for i, slot in enumerate(self._slot):
            job = slot.prefill
            if job is None or not slot.active:
                continue
            s = job.start
            n = min(job.chunk, job.L - s)
            toks = np.zeros((1, job.chunk), np.int32)
            toks[0, :n] = job.prompt[s : s + n]
            logits, arena = self.eng.prefill_chunk(
                toks, self._pstate["arena"], jnp.asarray(self._rows[i]),
                s, n, job.write_from,
            )
            self._note_prefill(n, job.chunk)
            self._pstate = {**self._pstate, "arena": arena}
            job.start = s + n
            if job.start >= job.L:
                first = (
                    jnp.argmax(logits.astype(jnp.float32), axis=-1)[:, None]
                    .astype(jnp.int32)
                )
                self._complete_prefill(i, job, first)

    def _complete_prefill(self, i: int, job: _PrefillJob, first) -> None:
        slot = self._slot[i]
        self._pstate, self._token, self._kdata = self._bind(
            self._pstate, self._token, self._kdata,
            jnp.asarray([i], jnp.int32), jnp.asarray(self._rows[i][None]),
            jnp.asarray([job.L], jnp.int32), first,
            self._kds_for([job.seed], 1),
        )
        self._pos[i] = job.L
        slot.first = first
        slot.prefill = None
        if job.poisoned:
            self._fault_fired.add(slot.rid)
            self._apply_paged_poison(i)
        else:
            self._register_prompt(i, job.prompt)

    def _extend_paged(self) -> None:
        """Lazy decode-page extension before each segment: make sure every
        decoding slot's table covers the rows this segment will write.
        Arena exhaustion preempts the latest-admitted other slot — its
        request re-queues and re-primes later with its own seed (identical
        tokens), and the earliest admission is never the victim, so the pool
        always makes progress."""
        if not self.paged:
            return
        for i in range(self.slots):
            slot = self._slot[i]
            if not slot.active or slot.prefill is not None:
                continue
            needed = min(
                # span, not segment: a speculative round writes up to
                # draft_k+1 rows per step (DESIGN.md §13)
                -(-(self._pos[i] + self._span) // self._layout.page),
                self._layout.n_pages,
            )
            cur = self._slot_npages[i]
            if needed <= cur:
                continue
            ids = self._alloc_or_preempt(needed - cur, protect=i)
            self._rows[i][cur:needed] = ids
            self._slot_blocks[i] += list(ids)
            self._slot_private[i] += list(ids)
            self._slot_npages[i] = needed
            self._rebind_row(i)

    def _alloc_or_preempt(self, n: int, protect: int) -> list:
        got = self._alloc.alloc(n)
        while got is None:
            cands = [j for j, s in enumerate(self._slot) if s.active and j != protect]
            if not cands:
                raise RuntimeError(
                    "paged arena exhausted with nothing left to preempt "
                    "(submit-time worst-case check should make this unreachable)"
                )
            victim = max(cands, key=lambda j: (self._slot[j].admit_s, j))
            self._preempt(victim)
            got = self._alloc.alloc(n)
        ids, scrub = got
        if scrub:
            self._zero_ids(scrub)
        return ids

    def _preempt(self, j: int) -> None:
        """Evict slot ``j`` mid-flight: free its blocks and re-queue its
        request.  Re-admission re-primes from the prompt with the request's
        own seed, so the eventual tokens are identical to an uninterrupted
        run — preemption changes *when*, never *what*."""
        slot = self._slot[j]
        rid, req = slot.rid, slot.req
        self._release_slot_pages(j)
        self._slot[j] = _Slot()
        self._counters["preempted"] += 1
        bisect.insort(self._queue, (req.arrival_s, rid, req))

    def _rebind_row(self, i: int) -> None:
        self._pstate = self._rebind(
            self._pstate, jnp.asarray([i], jnp.int32),
            jnp.asarray(self._rows[i][None]),
            jnp.asarray([self._pos[i]], jnp.int32),
        )

    def _release_slot_pages(self, i: int) -> None:
        """Return slot ``i``'s blocks to the allocator (hashed blocks park in
        the cached pool keeping their bytes; unhashed dead blocks are zeroed
        on the spot) and detach its table back to scratch."""
        blocks = self._slot_blocks[i]
        if blocks:
            dead = self._alloc.free(blocks)
            if dead:
                self._zero_ids(dead)
        self._slot_blocks[i] = []
        self._slot_private[i] = []
        self._slot_npages[i] = 0
        self._rows[i][:] = self._layout.scratch_block(i)
        self._pos[i] = 0
        self._pstate = self._resetp(
            self._pstate, jnp.int32(i), jnp.int32(self._layout.scratch_block(i))
        )

    def _apply_paged_poison(self, i: int) -> None:
        """§9 cache poisoning ported to the paged layout: NaN the slot's
        first PRIVATE block.  A fully prefix-shared prompt owns none, so one
        is privatized first (COW) — poison never reaches a block another
        request reads, keeping the blast radius at one request even under
        sharing.  The block's hash registration (if any) is dropped so no
        future prompt can match into the poisoned bytes."""
        if not self._slot_blocks[i]:
            return
        if self._slot_private[i]:
            blk = self._slot_private[i][0]
        else:
            [blk] = self._alloc_or_preempt(1, protect=i)
            old = int(self._rows[i][0])
            self._pstate = self._copyb(self._pstate, jnp.int32(old), jnp.int32(blk))
            self._rows[i][0] = blk
            bl = self._slot_blocks[i]
            bl[bl.index(old)] = blk
            self._slot_private[i].insert(0, blk)
            dead = self._alloc.free([old])
            if dead:
                self._zero_ids(dead)
            self._rebind_row(i)
        dead = self._alloc.forget(blk)
        if dead:
            self._zero_ids(dead)
        self._pstate = self._poisonb(self._pstate, jnp.int32(blk))

    def _inject_admission_faults(self, free: List[int], picked) -> None:
        """Apply the seeded fault plan to this admission round: admission
        stalls (slow-host model) and per-request slot-cache NaN poisoning
        (``models.cache.poison_slot``).  ``cache_nan_once`` makes a rid's
        fault fire only on its first admission, so its bounded dense retry
        runs clean; ``False`` re-fires on the retry, modelling a persistent
        fault the bounded retry cannot outrun."""
        f = self.eng.sc.faults
        if f is None:
            return
        for i, (rid, req) in zip(free, picked):
            if f.wants_stall(rid):
                self._sleep(f.stall_s)
            if f.wants_cache_nan(rid) and (
                not f.cache_nan_once or rid not in self._fault_fired
            ):
                if self.paged:
                    if self._slot[i].prefill is not None:
                        # chunked admission: the chunks would overwrite poison
                        # injected now — the job carries the fault plan and
                        # fires it at completion (_complete_prefill)
                        continue
                    self._fault_fired.add(rid)
                    self._apply_paged_poison(i)
                else:
                    self._fault_fired.add(rid)
                    self._cache = self._poison(self._cache, jnp.int32(i))

    def _stall_wait(self, secs: float) -> None:
        """Sleep ``secs`` (possibly inf — a hang) in small interruptible
        chunks, bailing the moment :meth:`abort` fires.  This is what makes
        an injected device stall escapable: the watchdog's abort lands
        between chunks instead of behind one long uninterruptible sleep."""
        t0 = self._clock()
        while self._abort_status is None:
            left = secs - (self._clock() - t0)
            if left <= 0:
                return
            self._sleep(min(left, 0.02) if math.isfinite(left) else 0.02)

    def _inject_decode_stall(self, active_idx: List[int]) -> None:
        """Seeded decode-segment stall/hang injection (DESIGN.md §12): if any
        active rid is selected by the fault plan, the segment dispatch is
        preceded by a host-visible stall — finite (``decode_stall_s``, the
        slow-device model) or infinite (``decode_hang_rids``, the hung-device
        model that only the watchdog's abort can end).  One-shot per rid by
        default (``decode_stall_once``), so the bounded re-queue after a
        watchdog abort runs clean — exactly like ``cache_nan_once``."""
        f = self.eng.sc.faults
        if f is None or not f.stalls_decode():
            return
        for i in active_idx:
            rid = self._slot[i].rid
            if f.decode_stall_once and rid in self._stall_fired:
                continue
            hang = f.wants_decode_hang(rid)
            if hang or f.wants_decode_stall(rid):
                self._stall_fired.add(rid)
                self._stall_wait(math.inf if hang else f.decode_stall_s)
                if self._abort_status is not None:
                    return

    def _abort_epilogue(self, now: float) -> None:
        """The fail-fast exit path: deal with every in-flight slot, then
        clear the flag so the next ``run`` starts clean.  First abort per
        rid re-queues it (same seed => the re-executed stream is
        bit-identical, consumers just see the tail late); second abort is
        terminal ``_abort_status`` — the retry is bounded, never a loop."""
        status = self._abort_status or Status.STALLED
        for i, slot in enumerate(self._slot):
            if not slot.active:
                continue
            if slot.rid in self._stall_retried:
                self._counters["stalled"] += 1
                self._retire(i, now, status)
            else:
                self._stall_retried.add(slot.rid)
                rid, req = slot.rid, slot.req
                if self.paged:
                    self._release_slot_pages(i)
                self._slot[i] = _Slot()
                self._counters["preempted"] += 1
                bisect.insort(self._queue, (req.arrival_s, rid, req))
        self._abort_status = None

    def _pop_arrived(self, k: int, now: float) -> list:
        """Take up to ``k`` queued requests whose arrival time has passed:
        highest priority first, earliest arrival breaking ties (a strict
        FIFO-by-submit pop would head-of-line block behind a queue head
        whose ``arrival_s`` is still in the future).  The queue is
        arrival-sorted, so the arrived set is a front prefix.  Requests
        whose queue wait already blew their deadline are shed here as
        TIMEOUT — priming a request that cannot finish in time would only
        steal a slot from one that can."""
        n = 0
        while n < len(self._queue) and self._queue[n][0] <= now:
            n += 1
        arrived, ready = self._queue[:n], []
        del self._queue[:n]
        for entry in arrived:
            _, rid, req = entry
            if req.deadline_s is not None and now > req.arrival_s + req.deadline_s:
                self._finish_unadmitted(rid, req, Status.TIMEOUT, finish=now)
                self._counters["timed_out"] += 1
                continue
            ready.append(entry)
        ready.sort(key=lambda e: (-e[2].priority, e[0], e[1]))
        take, leftover = ready[:k], ready[k:]
        for e in leftover:  # back into arrival order for the next round
            bisect.insort(self._queue, e)
        return [(rid, req) for _, rid, req in take]

    def _note_emission(self, slot: _Slot, n_before: int, t: float) -> None:
        """Record an ITL sample for this sync's emission event.  Tokens that
        surface together at one sync were observable at the same wall-clock
        instant, so the event contributes exactly ONE interval sample —
        ``t - last_emit_t`` — not ``emitted`` copies of its average, and
        nothing for same-instant followers (spreading one gap uniformly over
        a variable 1..k+1 speculative emission would make the percentiles
        meaningless).  The stream's first-ever emission only sets the
        baseline (TTFT owns the first token).  A ``_fail_slot`` truncation
        can shrink ``tokens`` below ``n_before`` — that is not an
        emission."""
        emitted = (len(slot.tokens) if slot.tokens is not None else 0) - n_before
        if emitted <= 0:
            return
        if not math.isnan(slot.last_emit_t):
            self._itl.append(t - slot.last_emit_t)
        slot.last_emit_t = t

    def _retire(self, i: int, now: float, status: Status = Status.OK) -> Completion:
        slot = self._slot[i]
        if status is Status.OK and slot.rid in self._fallback_rids:
            status = Status.FAILED_FALLBACK_OK
        done = Completion(
            rid=slot.rid,
            tokens=np.asarray(slot.tokens, np.int32),
            arrival_s=slot.arrival_s,
            admit_s=slot.admit_s,
            finish_s=now,
            status=status,
            ttft_s=slot.ttft_s,
        )
        self._completions[slot.rid] = done
        self._cancel.discard(slot.rid)
        if self.paged:
            self._release_slot_pages(i)
        self._slot[i] = _Slot()
        return done

    def _fail_slot(self, i: int, now: float) -> None:
        """Slot ``i`` tripped the non-finite guard.  Under active packed
        weights the pack is quarantined (the corrupt bytes may be anywhere
        in it — DESIGN.md §9) and the whole pool falls back dense.  The
        request gets ONE re-admission, re-primed from its prompt with its
        own seed so the retry's tokens are bit-identical to a clean dense
        run; a second trip is terminal FAILED — never an unbounded loop."""
        slot = self._slot[i]
        rid, req = slot.rid, slot.req
        if self.eng.packed_active and self.eng.quarantine_packed():
            self._counters["quarantined"] += 1
        if rid in self._retried:
            self._counters["failed"] += 1
            self._retire(i, now, Status.FAILED)
            return
        self._retried.add(rid)
        self._fallback_rids.add(rid)
        self._counters["fallback"] += 1
        if self.paged:
            # the poisoned private block dies unhashed here and is zeroed —
            # shared blocks just drop a reference, their bytes stay clean
            self._release_slot_pages(i)
        self._slot[i] = _Slot()  # slot cache is replaced wholesale on re-admission
        bisect.insort(self._queue, (req.arrival_s, rid, req))

    # -- run loop -------------------------------------------------------------

    def run(
        self,
        requests: Optional[List[Request]] = None,
        on_sync: Optional[Callable[["Scheduler"], None]] = None,
    ) -> Dict[int, Completion]:
        """Drain the queue (plus ``requests``), honouring arrival times.
        Returns ``{rid: Completion}`` — every submitted rid appears, whatever
        its terminal status; aggregate numbers via :meth:`stats`.
        ``on_sync`` (if given) fires after each segment sync — the hook
        tests use to cancel in-flight requests or advance an injected
        clock at a deterministic point.

        Each loop round is one ``serve.round`` span holding, in order,
        ``serve.admit`` (pop, page planning, prefill and bind dispatches),
        ``serve.dispatch`` (the segment program's call), ``serve.fetch``
        (the blocking token fetch), ``serve.consume`` (token bookkeeping,
        retirement, deadlines) and ``serve.hook`` (``on_sync``)."""
        self._maybe_reset()
        for r in requests or []:
            self.submit(r)
        t_start = self._clock()

        def now() -> float:
            return self._clock() - t_start

        span = self._spans.span
        self._run_now = now
        try:
            while self._queue or any(s.active for s in self._slot):
                if self._abort_status is not None:
                    self._abort_epilogue(now())
                    break
                if self._draining and not any(s.active for s in self._slot):
                    break  # drained: queued requests survive for the next run
                with span("serve.round"):
                    with span("serve.admit"):
                        self._admission_round(now())
                    active_idx = [i for i, s in enumerate(self._slot) if s.active]
                    if not active_idx:
                        if not self._queue:
                            continue  # drained; loop condition exits
                        # nothing in flight: sleep until the next request
                        # arrives (the queue head, since the queue is
                        # arrival-sorted) — chunked so drain()/abort() from
                        # another thread can interrupt an arbitrarily long
                        # idle wait
                        wait = self._queue[0][0] - now()
                        while (
                            wait > 0
                            and self._abort_status is None
                            and not self._draining
                        ):
                            self._sleep(min(wait, 0.02))
                            wait = self._queue[0][0] - now()
                        continue
                    # seeded decode stall/hang injection rides immediately
                    # before the dispatch; a watchdog abort fired during the
                    # stall exits here instead of dispatching the segment
                    self._inject_decode_stall(active_idx)
                    if self._abort_status is not None:
                        self._abort_epilogue(now())
                        break
                    # decode one segment and sync once: tokens + integrity
                    # flags come back in the same device_get — the guard
                    # costs no extra host transfer
                    with span("serve.dispatch", slots=len(active_idx)):
                        grids = self._dispatch_segment()
                    with span("serve.fetch"):
                        toks_np, nem_np, ok_np = self._fetch(grids)
                    with span("serve.consume"):
                        self._consume(active_idx, toks_np, nem_np, ok_np, now())
                    if on_sync is not None:
                        with span("serve.hook"):
                            on_sync(self)
        finally:
            self._run_now = None
        self._ran = True
        return self._completions

    def _admission_round(self, t: float) -> None:
        """Coalesce this round's arrived requests into free slots; in paged
        mode also advance every chunked prefill by one chunk and extend the
        decoding slots' tables over this segment's rows."""
        free = [i for i, s in enumerate(self._slot) if not s.active]
        if free and self._queue and not self._draining:
            picked = self._pop_arrived(len(free), t)
            if picked:
                if self.paged:
                    admitted = self._admit_paged(free[: len(picked)], picked, t)
                    if admitted:
                        self._inject_admission_faults(
                            [i for i, _, _ in admitted],
                            [(rid, req) for _, rid, req in admitted],
                        )
                else:
                    if self.admission == "batched" and self.eng.batched_prefill:
                        self._admit_batched(free[: len(picked)], picked, t)
                    else:
                        for i, (rid, req) in zip(free, picked):
                            self._admit(i, rid, req, t)
                    self._inject_admission_faults(free, picked)
        if self.paged:
            # one prefill chunk per admitting slot, then make sure every
            # decoding slot's table covers this segment's rows
            self._step_prefills()
            self._extend_paged()

    def _dispatch_segment(self) -> tuple:
        """Dispatch one decode segment of the whole pool; returns its device
        grids, not yet fetched."""
        args = (self.eng.params, self._token, self._kdata)
        dense = bool(self.eng.quarantined)
        if self.speculative:
            # each scan step is one draft/verify ROUND: grids come back
            # S-wide (S = draft_k + 1) with per-round accepted counts — the
            # host consumes tokens[r, i, :nem[r, i]]
            if self.paged:
                self._token, self._kdata, self._pstate, toks, nems, okg = self._seg_spec_paged(
                    *args, self._pstate, self.segment, dense
                )
            else:
                self._token, self._kdata, self._cache, toks, nems, okg = self._seg_spec(
                    *args, self._cache, self.segment, dense
                )
            return toks, nems, okg
        if self.paged:
            self._token, self._kdata, self._pstate, toks, okg, rows = self._seg_paged(
                *args, self._pstate, self.segment, dense
            )
        else:
            self._token, self._kdata, self._cache, toks, okg, rows = self._seg(
                *args, self._cache, self.segment, dense
            )
        return toks, None, okg, rows

    def _fetch(self, grids: tuple) -> tuple:
        """The segment's one sync: ``(tokens, accepted counts, flags)`` on
        the host, shaped ``(segment, slots, S)``, ``(segment, slots)``,
        ``(segment, slots, S)``; a plain segment reads as degenerate S=1
        rounds, so one consumption loop serves both modes.  The segment's
        expert-row count comes in the same transfer and is counted here."""
        toks, nems, okg, *rows = grids
        if nems is not None:
            return jax.device_get((toks, nems, okg))
        toks_np, ok_np, n = jax.device_get((toks, okg, rows[0]))  # (segment, slots) x2, ()
        self._counters["expert_rows"] += int(n)
        return toks_np[:, :, None], np.ones(toks_np.shape, np.int64), ok_np[:, :, None]

    def _consume(self, active_idx: List[int], toks_np, nem_np, ok_np, t: float) -> None:
        """Account one synced segment: hand each active slot its tokens,
        then retire what finished, was cancelled or blew its deadline."""
        self._counters["syncs"] += 1
        self._seg_steps += self.segment
        self._active_slot_steps += len(active_idx) * self.segment
        if self.paged:
            # each slot advanced by its own accepted-token total
            # (uniformly ``segment`` when not speculative)
            self._pos = [p + int(nem_np[:, i].sum()) for i, p in enumerate(self._pos)]
        self._kv_active_acc += len(active_idx)
        self._kv_used_acc += (
            self._alloc.live_blocks * self._block_bytes
            if self.paged
            else len(active_idx) * self._slot_bytes
        )
        for i in active_idx:
            slot = self._slot[i]
            n_before = len(slot.tokens) if slot.tokens is not None else 0
            if slot.prefill is not None:
                # mid-chunked-prefill: no tokens yet; only deadlines and
                # cancellation apply at this sync
                if slot.rid in self._cancel:
                    self._counters["cancelled"] += 1
                    self._retire(i, t, Status.CANCELLED)
                elif t > slot.deadline:
                    self._counters["timed_out"] += 1
                    self._retire(i, t, Status.TIMEOUT)
                continue
            if slot.rid in self._cancel:
                self._counters["cancelled"] += 1
                self._retire(i, t, Status.CANCELLED)
                continue
            if slot.first is not None:
                # deferred first token: EOS/budget checked here, at the
                # segment sync, never in the admission path
                first = int(np.asarray(slot.first).reshape(-1)[0])
                slot.tokens.append(first)
                slot.first = None
                slot.ttft_s = t - slot.arrival_s
                if slot.remaining == 0 or (slot.eos_id is not None and first == slot.eos_id):
                    self._note_emission(slot, n_before, t)
                    self._retire(i, t)
                    continue
            stop = False
            for step in range(self.segment):
                if stop or slot.remaining <= 0:
                    break
                used = 0
                for j in range(int(nem_np[step, i])):
                    if not ok_np[step, i, j]:
                        # non-finite logits: every token from this position
                        # on is garbage — truncate and fail
                        self._fail_slot(i, t)
                        stop = True
                        break
                    tok = toks_np[step, i, j]
                    slot.tokens.append(int(tok))
                    slot.remaining -= 1
                    used += 1
                    if (slot.eos_id is not None and tok == slot.eos_id) or slot.remaining == 0:
                        self._retire(i, t)
                        stop = True
                        break
                if self.speculative and used:
                    # acceptance accounting per consumed round: the round
                    # proposed draft_k tokens and used-1 of them survived
                    # verification (the first emission is the round's
                    # pending token, not a draft)
                    self._counters["spec_proposed"] += self._draft_k
                    self._counters["spec_accepted"] += used - 1
            self._note_emission(slot, n_before, t)
            slot = self._slot[i]  # may have retired/failed above
            if slot.active and t > slot.deadline:
                self._counters["timed_out"] += 1
                self._retire(i, t, Status.TIMEOUT)

    def stats(self) -> Dict[str, float]:
        """Aggregate serve metrics for the most recent :meth:`run` epoch.
        Latency/TTFT percentiles are computed over the completions that have
        the timing (NaN entries — never-admitted or never-emitted requests —
        are excluded) and are NaN when none do: an empty run must not read
        as an infinitely fast one.  The counters account every terminal
        path; ``quarantined`` counts pack-quarantine transitions (0 or 1 per
        engine lifetime).  ``admit_s``, ``dispatch_s``, ``fetch_s``,
        ``consume_s`` and ``hook_s`` are the host seconds of the run loop's
        ``serve.*`` spans (``decode_s`` = dispatch + fetch); ``syncs``
        counts segment syncs and ``prefill_*`` the prefill dispatches.
        ``packed_calls_folded`` and ``packed_calls_fallback`` count the
        packed-kernel calls per decode step (per round, speculative) of the
        last traced segment program that ran the slots as kernel rows, and
        that took the grid-axis fallback.  ``expert_rows`` counts the
        (row, held expert) pairs the decode steps routed, ``decode_steps``
        the pool's steps (``slot_occupancy``'s), ``held_experts`` the
        experts each of the ``expert_layers`` holds (0 and 0 without)."""
        done = sorted(self._completions.values(), key=lambda c: c.rid)
        lat = np.asarray([c.latency_s for c in done], np.float64)
        lat = lat[np.isfinite(lat)]
        ttft = np.asarray([c.ttft_s for c in done], np.float64)
        ttft = ttft[np.isfinite(ttft)]
        itl = np.asarray(self._itl, np.float64)
        itl = itl[np.isfinite(itl)]
        decoded = sum(max(len(c.tokens) - 1, 0) for c in done)
        # .get: AsyncEngine reads stats from another thread mid-run
        sec = {k: self._spans.seconds.get("serve." + k, 0.0)
               for k in ("admit", "dispatch", "fetch", "consume", "hook")}
        decode_s = sec["dispatch"] + sec["fetch"]
        busy = decode_s + sec["admit"]
        cnt = self._counters

        def pct(a, q):
            return float(np.percentile(a, q)) if a.size else float("nan")

        out = {
            "requests": len(done),
            "decoded_tokens": decoded,
            "sustained_tok_per_s": decoded / max(busy, 1e-9),
            "decode_s": decode_s,
            # host seconds of the run loop's phases (serve.* spans)
            **{k + "_s": v for k, v in sec.items()},
            # the padding share of the prefill programs' positions
            "prefill_pad_share": (
                1.0 - cnt["prefill_tokens"] / cnt["prefill_positions"]
                if cnt["prefill_positions"] else float("nan")
            ),
            "latency_p50_s": pct(lat, 50),
            "latency_p95_s": pct(lat, 95),
            "latency_p99_s": pct(lat, 99),
            "ttft_p50_s": pct(ttft, 50),
            "ttft_p95_s": pct(ttft, 95),
            "ttft_p99_s": pct(ttft, 99),
            "itl_p50_s": pct(itl, 50),
            "itl_p95_s": pct(itl, 95),
            "itl_p99_s": pct(itl, 99),
            "slot_occupancy": self._active_slot_steps / max(self.slots * self._seg_steps, 1),
            # unified accounting (DESIGN.md §13): accepted tokens over decode
            # wall time — the same definition Engine.generate reports, so
            # speculative and plain runs compare on one axis
            "tok_per_s": tok_per_s(decoded, decode_s),
            "acceptance_rate": acceptance_rate(cnt["spec_accepted"], cnt["spec_proposed"]),
        }
        # cache observability (DESIGN.md §11) — always present, NaN where the
        # gauge doesn't apply (slot-pool mode, or an epoch with no traffic),
        # so an empty run never reads as an infinitely cheap one
        if self.paged:
            h0, l0, c0, e0 = self._alloc_snap
            hits = self._alloc.hits - h0
            lookups = self._alloc.lookups - l0
            out.update({
                "kv_pool_bytes": float(self._arena_bytes),
                "kv_block_bytes": float(self._block_bytes),
                "blocks_total": float(self._layout.user_blocks),
                "blocks_live": float(self._alloc.live_blocks),
                "blocks_free": float(self._alloc.free_blocks),
                "blocks_cached": float(self._alloc.cached_blocks),
                "prefix_lookups": float(lookups),
                "prefix_hits": float(hits),
                "prefix_hit_rate": hits / lookups if lookups else float("nan"),
                "cow_copies": float(self._alloc.cow_copies - c0),
                "cache_evictions": float(self._alloc.evictions - e0),
            })
        else:
            out.update({
                "kv_pool_bytes": float(self._slot_bytes * self.slots),
                "kv_block_bytes": float(self._slot_bytes),
                "blocks_total": float("nan"),
                "blocks_live": float("nan"),
                "blocks_free": float("nan"),
                "blocks_cached": float("nan"),
                "prefix_lookups": 0.0,
                "prefix_hits": 0.0,
                "prefix_hit_rate": float("nan"),
                "cow_copies": 0.0,
                "cache_evictions": 0.0,
            })
        out["hbm_bytes_per_active_request"] = (
            self._kv_used_acc / self._kv_active_acc
            if self._kv_active_acc
            else float("nan")
        )
        out.update(self._counters)
        out["packed_calls_folded"], out["packed_calls_fallback"] = self._kernel_calls
        cfg = self.eng.cfg
        out["decode_steps"] = self._seg_steps
        out["held_experts"] = cfg.held_experts if cfg.held_moe else 0
        out["expert_layers"] = cfg.n_layers - cfg.n_dense_layers if cfg.held_moe else 0
        return out
