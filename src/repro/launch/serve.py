"""Serving launcher: load a checkpoint (or random init), optionally prune +
VUSA-pack, and serve batched synthetic requests.

    PYTHONPATH=src python -m repro.launch.serve --arch vusa_edge --smoke --packed

With ``--requests N`` the launcher drives the continuous-batching Scheduler
instead of one-shot generate, exposing the reliability knobs: per-request
``--deadline-s``, a bounded queue via ``--queue-cap`` with ``--shed-policy``,
and a seeded chaos mode (``--fault-rate``) that NaN-poisons that fraction of
requests' slot caches to exercise the guard + dense-fallback path.

Streaming / crash-safety (DESIGN.md §12): ``--stream`` serves the same
requests through the asyncio AsyncEngine; ``--journal PATH`` write-ahead
journals every request event (implies ``--stream``), ``--recover`` replays a
crashed journal first — proven completions come back verbatim, in-flight
requests re-execute bit-identically — and ``--watchdog-s`` arms stall
detection.  SIGINT/SIGTERM drain instead of dying mid-segment: admission
stops, in-flight requests finish (bounded by ``--drain-timeout-s``), final
stats print, and the journal closes clean.
"""

import argparse
import asyncio
import signal

import jax
import numpy as np

from ..checkpoint import latest_step, restore
from ..configs import get_config, get_smoke_config
from ..core.pruning import prune_tree
from ..models import build_model
from ..serve import (
    AsyncEngine,
    Engine,
    FaultConfig,
    Journal,
    Request,
    Scheduler,
    ServeConfig,
)
from .compile_cache import enable_compile_cache


async def _serve_streaming(args, cfg, sched):
    """Drive the synthetic workload through the AsyncEngine: streaming
    consumption, optional journaling/recovery, and signal-driven drain.
    The workload is a pure function of the rng seed, so a recovered run
    submits exactly the requests the journal does not already prove."""
    import os

    if args.recover and os.path.exists(args.journal):
        engine = AsyncEngine.recover(args.journal, sched, watchdog_s=args.watchdog_s)
        print(f"recovered journal {args.journal}: "
              f"{len(engine._completed)} completions proven, "
              f"{len(engine.recovered_rids)} requests re-queued")
    else:
        journal = Journal(args.journal) if args.journal else None
        engine = AsyncEngine(sched, journal=journal, watchdog_s=args.watchdog_s)

    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        # drain instead of dying mid-segment: admission stops, in-flight
        # work finishes (bounded), stats print, the journal closes clean
        loop.add_signal_handler(sig, stop.set)

    async with engine:
        known = set(engine.recovered_rids) | set(
            rid for rid in range(args.requests) if engine.completion_for(rid) is not None
        )
        rng = np.random.default_rng(0)
        streams = []
        for r in range(args.requests):
            prompt = rng.integers(1, cfg.vocab, size=args.prompt_len).astype(np.int32)
            if r in known:  # journal already owns this rid (done or re-queued)
                streams.append(engine.stream_for(r))
            else:
                streams.append(engine.submit(Request(
                    prompt=prompt, max_new=args.max_new, seed=r,
                    deadline_s=args.deadline_s,
                ), rid=r))

        async def consume():
            total = 0
            for s in streams:
                async for _ in s:
                    total += 1
            return total

        work = asyncio.ensure_future(consume())
        interrupt = asyncio.ensure_future(stop.wait())
        done, _ = await asyncio.wait(
            {work, interrupt}, return_when=asyncio.FIRST_COMPLETED
        )
        if interrupt in done:
            print("signal: draining...")
            clean = await engine.drain(args.drain_timeout_s)
            print("drained clean" if clean
                  else f"drain blew {args.drain_timeout_s}s; in-flight work aborted")
            work.cancel()
        else:
            interrupt.cancel()
            print(f"streamed {work.result()} tokens")
        st = engine.stats()
        print(f"{st['requests_completed']:.0f} completions  "
              f"ttft p50/p99 {st['ttft_p50_s']*1e3:.0f}/{st['ttft_p99_s']*1e3:.0f}ms  "
              f"itl p50/p99 {st['itl_p50_s']*1e3:.0f}/{st['itl_p99_s']*1e3:.0f}ms  "
              f"journal records={st['journal_records']:.0f} syncs={st['journal_syncs']:.0f}")
        print("  " + _host_line(st))
    for sig in (signal.SIGINT, signal.SIGTERM):
        loop.remove_signal_handler(sig)


def _host_line(st) -> str:
    """The run loop's host seconds per phase and the prefill padding share
    (``Scheduler.stats()``, latest run epoch)."""
    phases = "  ".join(f"{k} {st[k + '_s']:.3f}"
                       for k in ("admit", "dispatch", "fetch", "consume", "hook"))
    return (f"host s over {st['syncs']} syncs: {phases}  prefill pad "
            f"{100 * st['prefill_pad_share']:.1f}% of {st['prefill_positions']} positions")


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument(
        "--packed", nargs="?", const="mlp", default=False, choices=("mlp", "all"),
        help="VUSA-pack the decode step: bare flag or 'mlp' = MLP trio only "
        "(the pre-§7 behaviour), 'all' = + qkv/o and untied LM head",
    )
    ap.add_argument(
        "--packed-values", default="bf16", choices=("bf16", "int8", "int4"),
        help="packed value precision (DESIGN.md §10): bf16 = native float "
        "values (default), int8/int4 = quantized value slots with "
        "per-window fp32 scales and dequant fused into the kernels",
    )
    ap.add_argument("--sparsity", type=float, default=None)
    ap.add_argument(
        "--speculative", action="store_true",
        help="self-speculative decoding (DESIGN.md §13): a ~99%%-sparsity "
        "pack of the SAME weights drafts --draft-k greedy tokens per round "
        "and one batched dispatch of the configured path verifies them; "
        "greedy output is bit-identical to non-speculative decode",
    )
    ap.add_argument(
        "--draft-k", type=int, default=4,
        help="speculative draft length (tokens drafted per verify round)",
    )
    ap.add_argument(
        "--draft-sparsity", type=float, default=0.99,
        help="magnitude-pruning sparsity of the drafter pack",
    )
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument(
        "--mesh", default=None, metavar="DP,TP",
        help="serve on a data x model device mesh (e.g. '2,4'): params/KV "
        "shard over 'data', packed-weight windows over 'model'; '1,1' (or "
        "omitting the flag) is the single-device path",
    )
    ap.add_argument(
        "--requests", type=int, default=0,
        help="serve N synthetic requests through the continuous-batching "
        "Scheduler (0 = one-shot batched generate)",
    )
    ap.add_argument("--slots", type=int, default=4, help="scheduler slot pool size")
    ap.add_argument(
        "--deadline-s", type=float, default=None,
        help="per-request deadline (from arrival); blown deadlines finish TIMEOUT",
    )
    ap.add_argument(
        "--queue-cap", type=int, default=None,
        help="bound the scheduler queue; overflow handled per --shed-policy",
    )
    ap.add_argument(
        "--shed-policy", default="reject",
        choices=("reject", "shed-oldest", "shed-lowest-priority"),
        help="who pays when the queue is full",
    )
    ap.add_argument(
        "--fault-rate", type=float, default=0.0,
        help="chaos mode: seeded fraction of requests whose slot cache gets "
        "NaN-poisoned at admission (exercises guard + dense fallback)",
    )
    ap.add_argument(
        "--page-size", type=int, default=0,
        help="KV block size in tokens; >0 switches the scheduler to the paged "
        "arena pool with prefix sharing (DESIGN.md §11), 0 = slot pool",
    )
    ap.add_argument(
        "--arena-blocks", type=int, default=0,
        help="total paged-arena blocks (0 = auto: enough for every slot at "
        "max_len); smaller arenas admit lazily and preempt under pressure",
    )
    ap.add_argument(
        "--prefix-cache", action=argparse.BooleanOptionalAction, default=True,
        help="share identical prompt-prefix pages between requests "
        "(--no-prefix-cache disables; only meaningful with --page-size)",
    )
    ap.add_argument(
        "--prefill-chunk", type=int, default=0,
        help="chunk long prompt prefills to this many tokens and co-schedule "
        "the chunks with decode segments (0 = whole-prompt prefill; "
        "requires --page-size)",
    )
    ap.add_argument(
        "--stream", action="store_true",
        help="serve through the asyncio AsyncEngine (token streaming, "
        "watchdog, clean drain on SIGINT/SIGTERM); requires --requests",
    )
    ap.add_argument(
        "--journal", default=None, metavar="PATH",
        help="write-ahead journal every request event to PATH (CRC32-framed, "
        "fsync'd at segment syncs); implies --stream",
    )
    ap.add_argument(
        "--recover", action="store_true",
        help="replay --journal before serving: journaled completions are "
        "honoured, in-flight requests re-execute under their original seeds",
    )
    ap.add_argument(
        "--watchdog-s", type=float, default=None,
        help="abort a segment that syncs nothing for this long as STALLED "
        "(default: watchdog off)",
    )
    ap.add_argument(
        "--drain-timeout-s", type=float, default=30.0,
        help="on SIGINT/SIGTERM, give in-flight requests this long to finish "
        "before aborting them (CANCELLED)",
    )
    args = ap.parse_args()
    if args.recover and not args.journal:
        ap.error("--recover requires --journal")
    if args.journal:
        args.stream = True
    if args.stream and args.requests <= 0:
        ap.error("--stream/--journal require --requests N")
    if args.speculative and args.requests == 0 and args.batch != 1:
        ap.error("--speculative one-shot generate serves --batch 1 "
                 "(use --requests N for batched speculative serving)")

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    if args.ckpt:
        step = latest_step(args.ckpt)
        if step is not None:
            params = restore(args.ckpt, step, {"params": params})["params"]
            print(f"restored step {step} from {args.ckpt}")
    sp = cfg.sparsity if args.sparsity is None else args.sparsity
    if sp > 0:
        params = prune_tree(params, sp)
    mesh = None
    if args.mesh:
        from .mesh import make_serve_mesh

        mesh = make_serve_mesh(args.mesh)
        print(f"mesh {dict(mesh.shape)} over {len(mesh.devices.flat)} devices")
    faults = FaultConfig(cache_nan_rate=args.fault_rate) if args.fault_rate > 0 else None
    max_len = args.prompt_len + args.max_new + 8
    if args.speculative:
        # speculative rounds write up to draft_k rows past the emission
        # budget before rejection masks them; the scheduler additionally
        # budgets a full segment span (segment * (draft_k + 1) rows) of
        # worst-case growth per sync
        max_len += 8 * args.draft_k if args.requests > 0 else args.draft_k
    if args.page_size > 0:  # §11: page size must divide max_len
        max_len = -(-max_len // args.page_size) * args.page_size
    eng = Engine(cfg, params, ServeConfig(max_len=max_len,
                                          packed_weights=args.packed,
                                          packed_values=args.packed_values,
                                          page_size=args.page_size,
                                          arena_blocks=args.arena_blocks,
                                          prefix_cache=args.prefix_cache,
                                          prefill_chunk=args.prefill_chunk,
                                          speculative=args.speculative,
                                          draft_k=args.draft_k,
                                          draft_sparsity=args.draft_sparsity,
                                          faults=faults),
                 mesh=mesh)
    if args.requests > 0:
        sched = Scheduler(
            eng, slots=args.slots, queue_cap=args.queue_cap,
            shed_policy=args.shed_policy,
        )
        if args.stream:
            asyncio.run(_serve_streaming(args, cfg, sched))
            return
        rng = np.random.default_rng(0)
        for r in range(args.requests):
            sched.submit(Request(
                prompt=rng.integers(1, cfg.vocab, size=args.prompt_len).astype(np.int32),
                max_new=args.max_new, seed=r, deadline_s=args.deadline_s,
            ))
        done = sched.run()
        st = sched.stats()
        print(f"{st['requests']} completions  {st['sustained_tok_per_s']:.0f} tok/s  "
              f"latency p50 {st['latency_p50_s']*1e3:.0f}ms  "
              f"ttft p50 {st['ttft_p50_s']*1e3:.0f}ms")
        if args.speculative:
            print(f"  speculative: acceptance {st['acceptance_rate']:.2f}  "
                  f"accepted tok/s {st['tok_per_s']:.0f}  "
                  f"proposed={st['spec_proposed']} accepted={st['spec_accepted']}")
        print("  " + "  ".join(
            f"{k}={st[k]}" for k in
            ("rejected", "shed", "timed_out", "cancelled", "fallback", "failed",
             "quarantined")
        ))
        print("  " + _host_line(st))
        if args.page_size > 0:
            print(f"  arena {st['kv_pool_bytes']/2**20:.1f}MiB "
                  f"blocks live={st['blocks_live']:.0f} free={st['blocks_free']:.0f} "
                  f"cached={st['blocks_cached']:.0f}  "
                  f"prefix hit rate {st['prefix_hit_rate']:.2f}  "
                  f"cow={st['cow_copies']}  preempted={st['preempted']}  "
                  f"hbm/req {st['hbm_bytes_per_active_request']/2**10:.1f}KiB")
        bad = sum(1 for c in done.values() if c.status.value not in ("OK", "FAILED_FALLBACK_OK"))
        if bad:
            raise SystemExit(f"{bad} requests did not deliver tokens")
        return
    prompts = np.ones((args.batch, args.prompt_len), np.int32)
    out = eng.generate(prompts, max_new=args.max_new)
    print(f"prefill {out['prefill_s']*1e3:.1f}ms  decode {out['decode_s']*1e3:.1f}ms  "
          f"{out['tok_per_s']:.0f} tok/s")
    if args.speculative:
        print(f"speculative: acceptance {out['acceptance_rate']:.2f}  "
              f"rounds={out['spec_rounds']} proposed={out['spec_proposed']} "
              f"accepted={out['spec_accepted']}")


if __name__ == "__main__":
    main()
