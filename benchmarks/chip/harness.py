"""One run of one benchmark cell: build, warm up, measure a window, check,
reduce.

A cell (an entry of ``BENCHMARK.json``'s ``workloads``) names a
configuration file (``configs/<name>.json``: the model's sizes as run, the
``repro.configs`` module they must equal but for the keys it lists under
``reduced``, its family module, sparsity and serve settings), a traffic mix
(``traffic/<name>.json``, read by ``traffic.py``) and its limits
(``limits/<cell>.json``).  Per-layer metrics are read by
``metrics/<metric>.py``.  The family module (``references/<name>.py``,
the configuration's ``reference``) is the plain reference and all that is
known of the model's family: its program keys, weight layout, kernel calls
and FLOPs.  Nothing here names a cell, a mix, a metric, a family or a
kernel: a new one is new files and a new entry.

One run, in one process:

1. Set-up (``setup_s``): weights from the seed on the device
   (``weights.py``, in the family's layout), the ``Engine`` and
   ``Scheduler`` the configuration states, and a warm-up:
   ``Engine.prime_many`` at every prefill bucket x batch bucket the mix's
   prompt lengths can reach, and as few ``Scheduler`` rounds as put
   every batch bucket through admission and run the decode segment.
2. The window: the mix's requests are submitted and ``Scheduler.run``
   serves them.  An ``on_sync`` hook stamps each request's tokens as they
   reach the host.  The window closes at the first segment sync at or past
   ``seconds``: the tokens that sync delivers are the window's last, and
   the hook cancels what is in flight and drains.  Compilations inside the
   window are counted.
3. With tracing on, a few seconds in the middle of the window are traced
   with the JAX profiler, and the per-layer metrics read the trace.
4. The check: the device's peak memory is read, the program is freed, and
   the tokens served in the window, of every request that got any (cut at
   the close or not), are scored against the plain reference
   (``check.py``).
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from functools import partial
from pathlib import Path
from types import SimpleNamespace

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
for _p in (str(ROOT / "src"), str(HERE)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import check  # noqa: E402
import traffic  # noqa: E402
import weights as weights_mod  # noqa: E402
import work  # noqa: E402
import xplane  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_AT = 0.4  # the traced part of the window starts at this share of it
TRACE_S = 4.0  # and lasts this long at most (a quarter of the window at most)
# the compile cache's size: a larger executable is not written, and the entries used
# longest ago are deleted once the cache holds more
CACHE_MAX_BYTES = 1 << 30


class NoAccelerator(RuntimeError):
    pass


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    mix: dict
    chips: int
    limits: dict
    end_to_end: list
    per_layer: list


def load_cell(name: str, spec: dict | None = None) -> Cell:
    spec = spec or json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next((w for w in spec["workloads"] if w["name"] == name), None)
    if cell is None:
        raise SystemExit(f"unknown workload {name!r}")
    conf = next(c for c in spec["configs"] if c["name"] == cell["config"])
    e2e = [m for m in spec["end_to_end"] if name in m.get("workloads", [name])]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if name in m.get("workloads", [name] if m["moves"] in names else [])]
    return Cell(
        name=name,
        config=json.loads((ROOT / conf["file"]).read_text()),
        mix=traffic.load(cell["traffic"]),
        chips=int(cell["chips"]),
        limits=json.loads((HERE / "limits" / f"{name}.json").read_text()),
        end_to_end=e2e,
        per_layer=per_layer,
    )


def log(*a):
    print(*a, file=sys.stderr, flush=True)


# -- configuration --------------------------------------------------------

def family(config: dict):
    """The configuration's family module, ``references/<reference>.py``."""
    return _load("references", config["reference"])


def program_config(config: dict, smoke: bool, fam):
    """(ArchConfig, model sizes).  The program's configuration module must
    be one the family module ``fam`` computes (``check_program``) and state
    the file's sizes (``PROGRAM_KEYS``), but for the keys the file lists
    under ``reduced``, which are set to the file's; ``smoke`` takes the
    module's small preset's sizes instead (CPU rehearsal only)."""
    from repro.configs import get_config, get_smoke_config

    mod = config["program_config"]
    arch = (get_smoke_config if smoke else get_config)(mod)
    fam.check_program(arch)
    keys = fam.PROGRAM_KEYS
    if smoke:
        return arch, {**config["model"], **{k: getattr(arch, f) for k, f in keys.items()}}
    model = config["model"]
    cut = {f: model[k] for k, f in keys.items() if k in config["reduced"]}
    arch = dataclasses.replace(arch, **cut)
    bad = {k: (model[k], getattr(arch, f)) for k, f in keys.items()
           if model[k] != getattr(arch, f)}
    if bad:
        raise ValueError(f"repro.configs.{mod} differs from {config['name']}: {bad}")
    return arch, model


def counts(fam, model: dict, nnz: dict, serve: dict) -> dict:
    """What the family module ``fam`` counts of the served work: ``calls``,
    the packed-kernel calls of one decode step by kernel name (at one row
    per slot, in the configuration's packed values), and ``decode_flops``
    (of a token at a context length) and ``prefill_flops`` (of a prompt of
    a length), the model FLOPs."""
    return {"calls": fam.packed_calls(model, nnz, serve["slots"], serve["packed_values"]),
            "decode_flops": partial(fam.decode_flops, model),
            "prefill_flops": partial(fam.prefill_flops, model)}


# -- the window -----------------------------------------------------------

class Tracker:
    """When each request's tokens reached the host, stamped at the segment
    syncs of the window."""

    def __init__(self):
        self.first: dict = {}
        self.n_in: dict = {}
        self.last_in: dict = {}
        self.tokens_in = 0

    def see(self, rid: int, n: int, t: float) -> None:
        prev = self.n_in.get(rid, 0)
        if n <= prev:
            return
        self.first.setdefault(rid, t)
        self.tokens_in += n - prev
        self.n_in[rid] = n
        self.last_in[rid] = t


class CompileCounter:
    def __init__(self):
        self.active = False
        self.count = 0

    def __call__(self, event: str, duration_s: float, **_):
        if self.active and event == COMPILE_EVENT:
            self.count += 1


def batch_buckets(slots: int) -> list:
    """The batch sizes an admission of 1 to ``slots`` arrivals prefills at."""
    return sorted({1 << (g - 1).bit_length() for g in range(1, slots + 1)})


def warm_up_rounds(slots: int, buckets: list) -> list:
    """Scheduler rounds, each a list of (bucket, arrivals), in which every
    batch bucket an admission can form appears once: g arrivals in one
    bucket prefill as the power of two >= g.  Groups of one round lie in
    different buckets, so each is its own dispatch, and fill at most
    ``slots``.  Each round runs one decode segment, so there are few."""
    groups = sorted({min(nb, slots) for nb in batch_buckets(slots)}, reverse=True)
    rounds: list = []
    for g in groups:
        for r in rounds:
            used = {b for b, _ in r}
            free = [b for b in buckets if b not in used]
            if free and sum(n for _, n in r) + g <= slots:
                r.append((free[0], g))
                break
        else:
            rounds.append([(buckets[0], g)])
    return rounds


def warm_up(sched, eng, mix: dict, vocab: int, seed: int) -> int:
    """Compile every program the window runs: the prefill at every bucket
    the mix's prompts reach and every batch bucket the pool can form
    (``Engine.prime_many``, no decoding), then a few rounds through the
    window's own Scheduler for its admission programs and the decode
    segment.  Returns the rounds served."""
    import jax
    from repro.serve import Request

    rng = np.random.default_rng(np.random.SeedSequence([seed, 0x3a4]))
    lo, hi = int(mix["prompt_tokens"]["min"]), int(mix["prompt_tokens"]["max"])
    buckets = sorted({eng.bucket_len(n) for n in range(lo, hi + 1)})
    for b in buckets:
        for nb in batch_buckets(sched.slots):
            toks = rng.integers(1, vocab, size=(nb, b)).astype(np.int32)
            jax.block_until_ready(eng.prime_many(toks, np.full(nb, min(b, hi), np.int32)))
    rounds = warm_up_rounds(sched.slots, buckets)
    for r in rounds:
        sched.run([Request(prompt=rng.integers(1, vocab, size=min(b, hi)).astype(np.int32),
                           max_new=2, seed=i)
                   for b, g in r for i in range(g)])
    return len(rounds)


def _quantile(xs, q):
    xs = np.asarray([x for x in xs if np.isfinite(x)], np.float64)
    return float(np.percentile(xs, q)) if xs.size else float("nan")


def _load(kind: str, name: str):
    """``<kind>/<name>.py`` of the benchmark, as a module."""
    path = HERE / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def run(name: str, seed: int, seconds: float, trace: bool, **kw) -> dict:
    return run_cell(load_cell(name), seed, seconds, trace, **kw)


class Bench:
    """The program as a cell states it, built from a seed: weights on the
    device, ``Engine``, ``Scheduler``, and the benchmark's own span around
    the program's prefill entry (``Engine.prime_many``)."""

    def __init__(self, cell: Cell, seed: int, *, smoke: bool = False,
                 overrides: dict | None = None, require_accelerator: bool = True):
        from repro.launch.compile_cache import enable_compile_cache

        self.cache_dir = enable_compile_cache()
        import jax

        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_compilation_cache_max_size", CACHE_MAX_BYTES)
        self.devices = jax.devices()
        self.dev = self.devices[0]
        if require_accelerator and (self.dev.platform == "cpu"
                                    or len(self.devices) < cell.chips):
            raise NoAccelerator(
                f"{cell.name} needs {cell.chips} accelerator chip(s); JAX found "
                f"{len(self.devices)} {self.dev.platform} device(s)")
        from repro.serve import Engine, Scheduler, ServeConfig

        self.family = family(cell.config)
        self.arch, self.model = program_config(cell.config, smoke, self.family)
        self.serve = serve = {**cell.config["serve"], **(overrides or {})}
        self.sparsity = float(cell.config["sparsity"])
        log(f"{cell.name} seed {seed}: {self.dev.device_kind} x{len(self.devices)}, "
            f"compile cache {self.cache_dir}")
        log(f"  model {json.dumps(self.model)}, sparsity {self.sparsity}, "
            f"serve {json.dumps(serve)}")
        t = time.monotonic()
        seed32 = int(np.random.SeedSequence(seed).generate_state(1)[0])
        layout = self.family.layout(self.model)
        self.weights = jax.block_until_ready(weights_mod.make_weights(
            layout, self.sparsity, seed32, self.model["torch_dtype"], self.model["vocab_size"]))
        self.nnz = weights_mod.nonzeros(self.weights, layout)
        self.counts = counts(self.family, self.model, self.nnz, serve)
        self.t_weights = time.monotonic() - t
        t = time.monotonic()
        self.eng = Engine(self.arch, self.weights, ServeConfig(
            max_len=serve["max_len"], page_size=serve["page_size"],
            packed_weights=serve["packed_weights"], packed_values=serve["packed_values"]))
        self.sched = Scheduler(self.eng, slots=serve["slots"], segment=serve["segment"])
        self.t_engine = time.monotonic() - t
        self.t0 = None  # the window's start, when one is open
        self.prefills: list = []  # (s from window start, real prompt tokens) per dispatch
        prime_many = self.eng.prime_many

        def spanned_prime_many(prompts, lengths):
            with jax.profiler.TraceAnnotation("bench.prefill"):
                if self.t0 is not None:
                    self.prefills.append((time.monotonic() - self.t0, int(np.sum(lengths))))
                return prime_many(prompts, lengths)

        self.eng.prime_many = spanned_prime_many

    def warm_up(self, mix: dict, seed: int) -> None:
        t = time.monotonic()
        self.rounds = warm_up(self.sched, self.eng, mix, self.model["vocab_size"], seed)
        self.t_warm = time.monotonic() - t

    def free(self) -> None:
        """Drop the program and its compiled state; the weights stay."""
        import jax

        del self.sched, self.eng
        gc.collect()
        jax.clear_caches()


@dataclasses.dataclass
class Window:
    seconds: float  # from the window's start to the sync that closed it
    items: list
    rids: list
    records: list
    completions: dict
    tracker: Tracker
    stats: dict
    compiles: int
    closed: bool  # the window closed at a sync, cutting what was in flight
    summary: object  # xplane.Summary of the traced part, or None
    trace_t: tuple  # (start, end) of the traced part, s from the window's start
    started: float  # time.monotonic() at the window's start
    run_s: float


def serve_window(b: Bench, mix: dict, seed: int, seconds: float, trace: bool,
                 keep_trace: str | None = None, tag: str = "") -> Window:
    """Submit the mix's requests and serve them until the first segment sync
    at or past ``seconds``."""
    import jax
    from repro.serve import Request

    items = traffic.generate(mix, seed, b.model["vocab_size"])
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0x5eed]))
    req_seeds = rng.integers(0, 2**31 - 1, size=len(items))
    rids = [b.sched.submit(Request(prompt=it.prompt, max_new=it.max_new, seed=int(s)))
            for it, s in zip(items, req_seeds)]

    counter = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    tr = Tracker()
    seen_final: set = set()
    state = {"closed_at": None, "tracing": None, "trace_t": None}
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    trace_from = seconds * TRACE_AT
    trace_len = min(TRACE_S, seconds / 4)

    def stop_trace(t_now):
        state["tracing"].__exit__(None, None, None)
        jax.profiler.stop_trace()
        state["trace_t"] = (state["trace_t"], t_now)
        state["tracing"] = None

    def on_sync(s):
        now = time.monotonic() - b.t0
        if state["closed_at"] is not None:
            return
        with jax.profiler.TraceAnnotation("bench.on_sync"):
            for rid, toks in s.inflight_tokens().items():
                tr.see(rid, len(toks), now)
            for rid, c in s.completions_so_far().items():
                if rid not in seen_final:
                    tr.see(rid, len(c.tokens), now)
                    seen_final.add(rid)
        if trace_dir and state["trace_t"] is None and now >= trace_from:
            jax.profiler.start_trace(trace_dir)
            ann = jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN)
            ann.__enter__()
            state["tracing"], state["trace_t"] = ann, now
        elif state["tracing"] is not None and now >= state["trace_t"] + trace_len:
            stop_trace(now)
        if now < seconds:
            return
        if state["tracing"] is not None:
            stop_trace(now)
        for rid in s.inflight_tokens():
            s.cancel(rid)
        s.drain()
        state["closed_at"] = now

    b.prefills = []
    counter.active = True
    b.t0 = started = time.monotonic()
    done = b.sched.run(on_sync=on_sync)
    run_s = time.monotonic() - started
    counter.active = False
    b.t0 = None
    if state["tracing"] is not None:
        stop_trace(run_s)
    b.sched.resume_admission()
    closed = state["closed_at"] is not None
    records = []
    for rid, it in zip(rids, items):
        c = done.get(rid)
        records.append({
            "rid": rid, "prompt": len(it.prompt),
            "first": tr.first.get(rid, float("nan")), "n_in": tr.n_in.get(rid, 0),
            "last_in": tr.last_in.get(rid, float("nan")),
            "admit": c.admit_s if c is not None else float("nan"),
            "status": c.status.value if c is not None else None,
        })
    stats = b.sched.stats()
    for rid in rids:  # what the window never admitted leaves the queue
        if rid not in done:
            b.sched.cancel(rid)
    summary = None
    if trace_dir:
        files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
        if files:
            summary = xplane.summarize(str(files[-1]), list(b.counts["calls"]))
            if keep_trace:
                out = Path(keep_trace)
                out.mkdir(parents=True, exist_ok=True)
                shutil.copy(files[-1], out / f"{tag}.xplane.pb")
                (out / f"{tag}.describe.json").write_text(
                    json.dumps(xplane.describe_file(str(files[-1])), indent=1))
        shutil.rmtree(trace_dir, ignore_errors=True)
    return Window(seconds=state["closed_at"] if closed else run_s, items=items, rids=rids,
                  records=records, completions=dict(done), tracker=tr, stats=stats,
                  compiles=counter.count, closed=closed, summary=summary,
                  trace_t=state["trace_t"], started=started, run_s=run_s)


def outcome(win: Window) -> tuple:
    """(attempted, failed, served [(prompt, tokens served in the window)]).
    A request cut at the close is not a failure, and its tokens are served
    tokens like a finished request's."""
    from repro.serve import Status

    ok, cut = Status.OK.value, Status.CANCELLED.value
    recs = win.records
    bad = {r["rid"] for r in recs if r["status"] not in (None, ok, cut)}
    if not win.closed:  # nothing was cut at the close: a cancellation is a failure
        bad |= {r["rid"] for r in recs if r["status"] == cut}
    attempted = sum(r["status"] is not None for r in recs)
    served = [(it.prompt, np.asarray(c.tokens))
              for rid, it in zip(win.rids, win.items)
              if (c := win.completions.get(rid)) is not None
              and c.status.value in (ok, cut) and len(c.tokens)]
    return attempted, len(bad), served


def end_to_end(win: Window, setup_s: float) -> dict:
    tpot = [(r["last_in"] - r["first"]) / (r["n_in"] - 1) for r in win.records
            if r["n_in"] >= 2 and r["last_in"] > r["first"]]
    return {
        "tok_s": win.tracker.tokens_in / win.seconds,
        "tpot_p95_ms": 1000 * _quantile(tpot, 95),
        "setup_s": setup_s,
    }


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *, t_start=None,
             smoke: bool = False, overrides: dict | None = None,
             require_accelerator: bool = True, keep_trace: str | None = None) -> dict:
    """One run; returns the result object (see ``run.py``)."""
    t_start = time.monotonic() if t_start is None else t_start
    b = Bench(cell, seed, smoke=smoke, overrides=overrides,
              require_accelerator=require_accelerator)
    log(f"  traffic {traffic.describe(cell.mix)}")
    b.warm_up(cell.mix, seed)
    win = serve_window(b, cell.mix, seed, seconds, trace, keep_trace, tag=f"{cell.name}.{seed}")
    setup_s = win.started - t_start
    log(f"  set-up {setup_s:.2f}s (weights {b.t_weights:.2f}s, engine+pack {b.t_engine:.2f}s, "
        f"warm-up {b.t_warm:.2f}s in {b.rounds} rounds); window {win.seconds:.2f}s, run "
        f"returned after {win.run_s:.2f}s")
    log(f"  compilations inside the window: {win.compiles}")
    mem = (b.dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    attempted, failed, served = outcome(win)
    ctx = SimpleNamespace(
        window_s=win.seconds, stats=win.stats, records=win.records, prefills=b.prefills,
        trace=win.summary, trace_t=win.trace_t, model=b.model, serve=b.serve,
        peaks=work.peaks(b.dev.device_kind) if b.dev.platform != "cpu" else None,
        tokens_in=win.tracker.tokens_in, **b.counts,
    )
    b.free()

    t = time.monotonic()
    picked = check.sample(served, seed, int(cell.limits["sample_tokens"]))
    got = check.served_gap(b.family, b.weights, b.model, picked, b.serve["max_len"])
    log(f"  reference check of {len(picked)} requests took {time.monotonic() - t:.2f}s; "
        f"argmax share {got['argmax_share']:.4f}")
    checks = {}
    for key, lim in cell.limits["checks"].items():
        v = got[key]
        checks[key] = {"value": v, "limit": lim["max"] if "max" in lim else lim["min"],
                       "op": "<=" if "max" in lim else ">=",
                       "ok": bool(v <= lim["max"] if "max" in lim else v >= lim["min"])}

    if trace:
        metrics = {}
        for m in cell.per_layer:
            v = _load("metrics", m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e = end_to_end(win, setup_s)
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    log(f"  attempted {attempted}, failed {failed}, tokens in window {win.tracker.tokens_in}, "
        f"slot occupancy {win.stats['slot_occupancy']:.4f}")
    for k, v in metrics.items():
        log(f"  {k} = {v['value']!r} {v['unit']}")
    device = {"platform": b.dev.platform, "kind": b.dev.device_kind,
              "count": len(b.devices), "memory_peak_bytes": int(mem)}
    result = {"correct": all(c["ok"] for c in checks.values()), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if trace and win.summary is not None:
        device.update(busy_s=win.summary.busy_s, window_s=win.summary.window_s)
        result["breakdown"] = xplane.breakdown(win.summary)
    result["checks"] = {k: {"value": c["value"], "limit": c["limit"]} for k, c in checks.items()}
    for k, c in checks.items():
        log(f"check {k} {c['value']!r} (limit {c['op']} {c['limit']!r}) "
            f"{'ok' if c['ok'] else 'FAILED'}")
    return result
