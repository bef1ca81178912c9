"""CPU rehearsal of every cell's code path at the program's SMOKE preset
(Pallas kernels in interpret mode), and of the comparison that decides
``correct``: the sound program passes, the correctness control (the
program's own int4 path) and planted faults fail.

Run by hand (the repository's tier-1 suite collects only ``tests/``):

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests

The limit here is the smoke preset's, not a cell's: the preset computes in
float32, so a sound run reads a gap of about 1e-2 standard deviations and
the int4 control about 0.5 (readings in PERF.md).  These tests are the only
callers that skip the harness's look for an accelerator.
"""

import json

import pytest

import harness

SMOKE_GAP_LIMIT = 0.1
SERVE = {"slots": 4, "max_len": 256}


def smoke_cell(name: str, limit: float = SMOKE_GAP_LIMIT):
    cell = harness.load_cell(name)
    mix = json.loads(json.dumps(cell.mix))
    mix["prompt_tokens"].update(min=8, max=64, median=24)
    mix["output_tokens"].update(min=4, max=24)
    if "median" in mix["output_tokens"]:
        mix["output_tokens"]["median"] = 12
    mix["requests"] = 48
    cell.mix = mix
    cell.limits = {"sample_tokens": 300,
                   "checks": {"served_gap_max": {"max": limit}, "checked_tokens": {"min": 150}}}
    return cell


def run(cell, seed=3_000_000_019, trace=False, **over):
    return harness.run_cell(cell, seed, 5.0, trace, smoke=True, overrides={**SERVE, **over},
                            require_accelerator=False)


CELLS = [w["name"] for w in json.loads((harness.ROOT / "BENCHMARK.json").read_text())["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_path_is_correct(name):
    cell = smoke_cell(name)
    r = run(cell)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert list(r)[-1] == "checks"


def test_traced_run_reports_per_layer_metrics_it_can_read():
    cell = smoke_cell(CELLS[0])
    r = run(cell, trace=True)
    assert r["correct"], r["checks"]
    # on the CPU there is no device plane and no peak: only the program counters answer
    assert set(r["metrics"]) == {"slot_occupancy", "sync_host_ms", "prefill_pad_share"}


def test_int4_control_is_not_correct():
    r = run(smoke_cell(CELLS[0]), packed_values="int4")
    assert not r["correct"], r["checks"]


def test_kv_state_left_unchanged_is_not_correct(monkeypatch):
    import repro.models.cache as cache

    def frozen(pstate, new_rows):  # the step's KV rows never reach the arena
        return {**pstate, "pos": pstate["pos"] + 1}

    monkeypatch.setattr(cache, "paged_scatter_token", frozen)
    r = run(smoke_cell(CELLS[0]))
    assert not r["correct"], r["checks"]


def test_token_altered_where_produced_is_not_correct(monkeypatch):
    from repro.serve.engine import Engine

    decode = Engine._decode_impl

    def altered(self, *a, **k):
        nxt, cache, ok = decode(self, *a, **k)
        return (nxt + 1) % self.cfg.vocab, cache, ok

    monkeypatch.setattr(Engine, "_decode_impl", altered)
    r = run(smoke_cell(CELLS[0]))
    assert not r["correct"], r["checks"]


def test_no_accelerator_is_refused():
    with pytest.raises(harness.NoAccelerator):
        harness.run_cell(smoke_cell(CELLS[0]), 1, 1.0, False, smoke=True,
                         overrides=SERVE)


@pytest.mark.parametrize("slots, buckets, rounds", [
    (8, [256, 512, 1024, 2048], [[(256, 8)], [(256, 4), (512, 2), (1024, 1)]]),
    (4, [64], [[(64, 4)], [(64, 2)], [(64, 1)]]),
    (12, [8, 16], [[(8, 12)], [(8, 8), (16, 4)], [(8, 2), (16, 1)]]),
])
def test_warm_up_puts_every_batch_bucket_through_admission(slots, buckets, rounds):
    got = harness.warm_up_rounds(slots, buckets)
    assert got == rounds
    assert all(sum(g for _, g in r) <= slots for r in got)
    formed = {1 << (g - 1).bit_length() for r in got for _, g in r}
    assert formed == set(harness.batch_buckets(slots))
