"""Spans and counters of the serving loop: the prefill counters are exact
for known prompt lengths and buckets and reset with each run epoch; the
run loop's host phases land in their own ``serve.*`` spans on the
Scheduler's injected clock (``admit_s`` and ``decode_s`` are sums of
them); a profiler trace holds the spans nested round > phase, with
``serve.prefill`` inside ``serve.admit``; and the decode segment's
program carries the ``decode.*`` scopes under a module name holding
``segment``."""

import math
import re
from pathlib import Path

import numpy as np
import pytest

import jax

from repro.configs import get_smoke_config
from repro.core.pruning import prune_tree
from repro.models import build_model
from repro.serve import Engine, FaultConfig, Request, Scheduler, ServeConfig

PHASES = ("serve.admit", "serve.dispatch", "serve.fetch", "serve.consume", "serve.hook")


@pytest.fixture(scope="module")
def vusa_pruned():
    cfg = get_smoke_config("vusa_edge")
    params = prune_tree(build_model(cfg).init(jax.random.key(0)), 0.85)
    return cfg, params


def _reqs(lengths, max_new=6, seed=0, vocab=100):
    rng = np.random.default_rng(seed)
    return [Request(prompt=rng.integers(1, vocab, n).astype(np.int32), max_new=max_new, seed=i)
            for i, n in enumerate(lengths)]


class _Clock:
    """Injected clock that moves only when the test moves it."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t

    def sleep(self, s: float) -> None:
        self.t += s


# max_len 64 buckets prompts at 8, 16, 32, 64; four slots admit all four
# prompts in the first round: bucket 8 holds 5 and 7 (2 rows), bucket 16
# holds 12 (1 row), bucket 32 holds 30 (1 row).  With 16-token chunks the
# paged pool prefills 30 as two chunks of 16 positions (16 + 14 tokens).
LENGTHS = (5, 7, 12, 30)
CASES = {
    "slot_pool": (dict(), 3, 54, 2 * 8 + 16 + 32),
    "paged": (dict(page_size=8), 3, 54, 2 * 8 + 16 + 32),
    "paged_chunked": (dict(page_size=8, prefill_chunk=16), 4, 54, 2 * 8 + 16 + 2 * 16),
}


@pytest.mark.parametrize("case", list(CASES))
def test_prefill_counters_exact_and_reset_per_epoch(vusa_pruned, case):
    cfg, params = vusa_pruned
    kw, dispatches, tokens, positions = CASES[case]
    sched = Scheduler(Engine(cfg, params, ServeConfig(max_len=64, **kw)), slots=4, segment=4)
    done = sched.run(_reqs(LENGTHS))
    assert len(done) == len(LENGTHS)
    st = sched.stats()
    assert (st["prefill_dispatches"], st["prefill_tokens"], st["prefill_positions"]) == (
        dispatches, tokens, positions)
    assert st["prefill_pad_share"] == pytest.approx(1 - tokens / positions)
    assert st["syncs"] >= 2 and st["syncs"] * sched.segment == sched._seg_steps

    sched.run(_reqs((3,), max_new=2, seed=1))  # a new epoch: one request in bucket 8
    st = sched.stats()
    assert (st["prefill_dispatches"], st["prefill_tokens"], st["prefill_positions"]) == (1, 3, 8)
    assert st["syncs"] == 1


def test_stats_are_sums_of_spans_on_the_injected_clock(vusa_pruned, monkeypatch):
    """Each phase's time, injected where that phase runs, lands in its own
    span and nowhere else; ``admit_s`` is the admission span and
    ``decode_s`` the dispatch plus fetch spans."""
    cfg, params = vusa_pruned
    clk = _Clock()
    sc = ServeConfig(max_len=64, faults=FaultConfig(stall_s=0.5, stall_rids=(0, 1)))
    sched = Scheduler(Engine(cfg, params, sc), slots=2, segment=4, clock=clk, sleep=clk.sleep)
    dispatch, fetch = sched._dispatch_segment, sched._fetch

    def slow_dispatch():
        clk.sleep(0.125)
        return dispatch()

    def slow_fetch(grids):
        clk.sleep(0.0625)
        return fetch(grids)

    monkeypatch.setattr(sched, "_dispatch_segment", slow_dispatch)
    monkeypatch.setattr(sched, "_fetch", slow_fetch)
    sched.run(_reqs((5, 9, 6)), on_sync=lambda s: clk.sleep(0.25))
    st = sched.stats()
    n = st["syncs"]
    assert n >= 3
    assert st["admit_s"] == 1.0  # two stalled admissions of 0.5 s
    assert (st["dispatch_s"], st["fetch_s"], st["hook_s"]) == (0.125 * n, 0.0625 * n, 0.25 * n)
    assert st["consume_s"] == 0.0
    assert st["decode_s"] == st["dispatch_s"] + st["fetch_s"]
    assert st["tok_per_s"] == pytest.approx(st["decoded_tokens"] / st["decode_s"])
    assert sched._spans.counts["serve.dispatch"] == n

    sched.run(_reqs((4,), max_new=2))  # the next epoch starts from zero
    st = sched.stats()
    assert (st["admit_s"], st["hook_s"]) == (0.0, 0.0)
    assert st["dispatch_s"] == 0.125 * st["syncs"]


def _host_spans(trace_dir: Path) -> list:
    from jax.profiler import ProfileData

    [path] = sorted(trace_dir.rglob("*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            out += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns, line.name)
                    for ev in line.events if ev.name.startswith("serve.")]
    return out


def _inside(ev, outer) -> bool:
    return ev[3] == outer[3] and outer[1] <= ev[1] and ev[2] <= outer[2]


def test_profiler_trace_holds_the_spans_nested(vusa_pruned, tmp_path):
    cfg, params = vusa_pruned
    sched = Scheduler(Engine(cfg, params, ServeConfig(max_len=64, page_size=8)),
                      slots=2, segment=4)
    sched.run(_reqs((5,), max_new=2, seed=1))  # compile outside the trace
    syncs = []
    jax.profiler.start_trace(str(tmp_path))
    try:
        sched.run(_reqs((5, 7, 12)), on_sync=lambda s: syncs.append(1))
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(tmp_path)
    by = {name: [e for e in spans if e[0] == name]
          for name in ("serve.round", "serve.prefill") + PHASES}
    assert len(by["serve.dispatch"]) == len(by["serve.fetch"]) == len(syncs)
    assert len(by["serve.hook"]) == len(syncs) == sched.stats()["syncs"]
    assert len(by["serve.round"]) >= len(syncs)
    for name in PHASES:
        assert by[name], name
        for ev in by[name]:
            assert any(_inside(ev, r) for r in by["serve.round"]), (name, ev)
    # three prompts in two slots: the first admission and one re-admission
    assert len(by["serve.prefill"]) == sched.stats()["prefill_dispatches"] == 2
    for ev in by["serve.prefill"]:
        assert any(_inside(ev, a) for a in by["serve.admit"]), ev
    # phases of one round follow each other: admit < dispatch < fetch < consume < hook
    for r in by["serve.round"]:
        starts = [next((e[1] for e in by[n] if _inside(e, r)), None) for n in PHASES]
        if all(s is not None for s in starts):
            assert starts == sorted(starts)


@pytest.mark.parametrize("page_size", [0, 8], ids=["slot_pool", "paged"])
def test_segment_program_carries_the_decode_scopes(vusa_pruned, page_size):
    cfg, params = vusa_pruned
    eng = Engine(cfg, params, ServeConfig(max_len=64, page_size=page_size,
                                          packed_weights="all", packed_values="int8"))
    sched = Scheduler(eng, slots=2, segment=4)
    seg, state = (sched._seg_paged, sched._pstate) if sched.paged else (sched._seg, sched._cache)
    lowered = seg.lower(eng.params, sched._token, sched._kdata, state, sched.segment, False)
    text = lowered.as_text(debug_info=True)
    module = text.split("module @", 1)[1].split(" ", 1)[0]
    assert "segment" in module, module
    for scope in ("decode.attention", "decode.mlp", "decode.head", "decode.sample"):
        # a location name starts with the scope, or holds it in its path
        # (under the slot vmap as vmap(decode.x))
        assert re.search(rf'["/(]{re.escape(scope)}[/)]', text), scope
    assert "vusa_packed_matmul_head" in text and "vusa_fused_mlp_matmul" in text
    prefill = eng._prefill_masked.lower(
        eng.params, {"tokens": np.ones((1, 8), np.int32)}, np.ones(1, np.int32))
    assert "prefill" in prefill.as_text().split("module @", 1)[1].split(" ", 1)[0]
    assert math.isnan(sched.stats()["prefill_pad_share"])  # nothing prefilled yet
