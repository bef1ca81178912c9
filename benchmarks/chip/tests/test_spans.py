"""The program's spans and scopes read from a small trace of known shape:
``test_xplane``'s trace with the serving loop's host spans and scoped
operation stats added.  Times in ms, from the trace's start:

    host   serve.round [0.2, 7.5): dispatch [0.2, 0.9), fetch [0.9, 5.6),
           consume [5.6, 5.8), hook [5.8, 7.4) over bench.on_sync [6, 7)
           serve.round [7.5, 10): admit [7.6, 9.6) over serve.prefill [7.7, 8)
    device while.39 and copy-start.2 [1, 5.5) under decode.attention;
           fusion.1 under decode.attention, vusa_packed_matmul.24 under
           decode.attention, vusa_fused_mlp_matmul.6 under decode.mlp
"""

import importlib.util
from functools import partial
from pathlib import Path
from types import SimpleNamespace

import pytest
from jax.profiler import ProfileData

import harness
import spans
import xplane
from test_xplane import DEV, HOST, KERNELS, TEXT, event, meta

SEG = "jit(_segment_paged_fn)/while/body"
HOST2 = {**HOST, 3: "serve.round", 4: "serve.dispatch", 5: "serve.fetch", 6: "serve.consume",
         7: "serve.hook", 8: "serve.admit", 9: "serve.prefill#rows=1,bucket=8#"}
DEV2 = {**DEV, 7: "%while.39 = (s32[], bf16[8,1,2048]) while((s32[], bf16[8,1,2048]) %tuple.1)",
        8: "copy-start.2"}
TF_OP = {  # event metadata id -> the scope path in its tf_op stat
    3: f"{SEG}/vmap(decode.attention)/dot_general",
    4: f"{SEG}/decode.attention/jit(vusa_packed_matmul)/vusa_packed_matmul/pallas_call",
    5: f"{SEG}/decode.mlp/jit(vusa_fused_mlp_matmul)/vusa_fused_mlp_matmul/pallas_call",
    6: "jit(_prefill_masked_fn)/dot_general",
    7: f"{SEG}/decode.attention/while",
    8: f"{SEG}/decode.attention/copy",
}


def scoped(mid, start_ms, dur_ms):
    e = event(mid, start_ms, dur_ms)
    return e[:-1] + f'stats {{ metadata_id: 1 str_value: "{TF_OP[mid]}" }} }}'


TEXT2 = f"""
planes {{ name: "/host:CPU" lines {{ name: "python3" timestamp_ns: 0
  {event(1, 0, 10)} {event(2, 6, 1)} {event(3, 0.2, 7.3)} {event(4, 0.2, 0.7)}
  {event(5, 0.9, 4.7)} {event(6, 5.6, 0.2)} {event(7, 5.8, 1.6)} {event(3, 7.5, 2.5)}
  {event(8, 7.6, 2)} {event(9, 7.7, 0.3)} }} {meta(HOST2)} }}
planes {{ name: "/device:TPU:0"
  lines {{ name: "XLA Modules" timestamp_ns: 0 {event(1, 1, 4.5)} {event(2, 8, 1.5)} }}
  lines {{ name: "XLA Ops" timestamp_ns: 0 {scoped(7, 1, 4.5)} {scoped(8, 1, 4.5)}
    {scoped(3, 1, 1)} {scoped(4, 2, 1)} {scoped(5, 3, 2.5)} {scoped(6, 8, 1.5)} }}
  {meta(DEV2)} stat_metadata {{ key: 1 value {{ id: 1 name: "tf_op" }} }} }}
"""
MS = 1e-3


@pytest.fixture(scope="module")
def traces():
    return ProfileData.from_text_proto(TEXT), ProfileData.from_text_proto(TEXT2)


@pytest.fixture(scope="module")
def summary(traces):
    return spans.summarize(traces[1], KERNELS)


def test_idle_gaps_are_named_after_the_innermost_span(summary):
    want = {"host (unannotated)": 0.2, "serve.dispatch": 0.7, "serve.fetch": 0.2,
            "serve.consume": 0.2, "serve.hook": 0.6, "bench.on_sync": 1.0,
            "serve.round": 0.6, "serve.admit": 0.2, "serve.prefill": 0.3}
    assert summary.gaps == {k: pytest.approx(v * MS) for k, v in want.items()}
    assert summary.idle_s == pytest.approx(4 * MS)  # xplane's 10 - 6 ms busy


def test_host_spans_per_sync(summary):
    assert summary.host["serve.round"] == [2, pytest.approx(9.8 * MS)]
    assert summary.host["serve.prefill"] == [1, pytest.approx(0.3 * MS)]
    per = summary.per_sync_ms()
    assert per["serve.fetch"] == pytest.approx(4.7)
    assert per["bench.on_sync"] == pytest.approx(1.0)


def test_scopes_leave_out_loops_and_async_copies(summary):
    assert summary.scopes == {"decode.attention": [2, pytest.approx(2 * MS)],
                              "decode.mlp": [1, pytest.approx(2.5 * MS)]}
    assert summary.scope_kernels == {"decode.attention": pytest.approx(1 * MS),
                                     "decode.mlp": pytest.approx(2.5 * MS)}
    assert summary.segments == 1
    r = spans.report(summary, steps_per_segment=8)
    assert r["decode_attention_ms"] == pytest.approx(1.0 / 8)  # fusion.1 alone
    assert r["scope_ms_per_step"]["decode.mlp"]["other"] == pytest.approx(0)


@pytest.mark.parametrize("name, stats, scope", [
    ("fusion.7", {"tf_op": "jit(f)/while/body/vmap(decode.head)/convert"}, "decode.head"),
    ("%vusa_packed_matmul_head.3 = f32[8,1,92672] custom-call(), "
     'metadata={op_name="jit(f)/decode.head/jit(vusa_packed_matmul)/pallas_call"}', {},
     "decode.head"),
    ("fusion.8", {"long_name": "fusion.8 = f32[8] fusion(), op_name=decode.sample/argmax"},
     "decode.sample"),
    ("fusion.9", {"tf_op": "jit(_prefill_masked_fn)/attention/dot_general"}, None),
])
def test_scope_of_reads_stats_or_the_name(name, stats, scope):
    assert spans.scope_of(name, stats, {}) == scope


def test_name_stacks_come_from_the_device_event_metadata(tmp_path):
    """On the TPU the name stack is the ``tf_op`` stat of an operation's
    event metadata, kept as a string or as a reference to a stat name."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    dev = space.planes.add(name="/device:TPU:0")
    dev.stat_metadata[1].name = "tf_op"
    dev.stat_metadata[2].name = f"{SEG}/vmap(decode.mlp)/pallas_call"
    a, b = dev.event_metadata[1], dev.event_metadata[2]
    a.name = "%fusion.3 = bf16[8] fusion()"
    a.stats.add(metadata_id=1, str_value=f"{SEG}/decode.attention/exp")
    b.name = "vusa_fused_mlp_matmul.6"
    b.stats.add(metadata_id=1, ref_value=2)
    host = space.planes.add(name="/host:CPU")
    host.stat_metadata[1].name = "tf_op"
    host.event_metadata[1].name = "serve.round"
    host.event_metadata[1].stats.add(metadata_id=1, str_value="decode.head")
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space.SerializeToString())
    stacks = spans.name_stacks(str(path))
    assert set(stacks) == {a.name, b.name}
    assert spans.scope_of(a.name, {}, stacks) == "decode.attention"
    assert spans.scope_of(b.name, {}, stacks) == "decode.mlp"


@pytest.mark.parametrize("name, waits", [
    ("%while.39 = (s32[]) while(...)", True), ("copy-start.6", True), ("copy-done", True),
    ("slice-start.1", True), ("conditional.2", True), ("fusion.1", False),
    ("vusa_fused_mlp_matmul.6", False), ("copy.3", False),
])
def test_waiting_operations(name, waits):
    assert spans.waits(name) is waits


def _metric(name):
    path = Path(spans.__file__).parent / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


EXISTING = ("decode_step_ms", "packed_kernel_share", "vusa_packed_matmul_roofline",
            "vusa_fused_mlp_matmul_roofline", "idle_share", "step_mfu", "slot_occupancy")


def test_existing_metrics_read_the_same_with_the_spans_added(traces):
    call = SimpleNamespace(least_s=lambda pk: 1e-4)
    model = {"num_hidden_layers": 2, "hidden_size": 256, "num_attention_heads": 4,
             "num_key_value_heads": 2, "head_dim": 64, "intermediate_size": 512,
             "vocab_size": 1000}
    dense = harness.family({"reference": "dense_lm"})

    def ctx(trace):
        return SimpleNamespace(
            trace=xplane.summarize(trace, KERNELS), stats={"slot_occupancy": 0.75},
            serve={"segment": 8}, peaks={"bf16_flop_per_s": 197e12, "hbm_byte_per_s": 819e9},
            calls={"vusa_packed_matmul": [call], "vusa_fused_mlp_matmul": [call]},
            decode_flops=partial(dense.decode_flops, model),
            prefill_flops=partial(dense.prefill_flops, model),
            window_s=10 * MS, prefills=[(0.0, 100)], model=model,
            records=[{"prompt": 100, "n_in": 3}])

    old, new = ctx(traces[0]), ctx(traces[1])
    for name in EXISTING:
        m = _metric(name)
        assert m.read(old) is not None and m.read(new) == m.read(old), name
    assert new.trace.gaps == old.trace.gaps


STATS = {"syncs": 4, "admit_s": 0.012, "dispatch_s": 0.004, "consume_s": 0.002,
         "fetch_s": 35.0, "hook_s": 0.01, "prefill_tokens": 9983, "prefill_positions": 14336}


def test_sync_host_ms_reads_admit_dispatch_and_consume_per_sync():
    m = _metric("sync_host_ms")
    assert m.read(SimpleNamespace(stats=STATS)) == pytest.approx(1000 * 0.018 / 4)
    parent = {"slot_occupancy": 1.0, "admit_s": 0.012, "decode_s": 35.0}
    assert m.read(SimpleNamespace(stats=parent)) is None
    assert m.read(SimpleNamespace(stats={**STATS, "syncs": 0})) is None


def test_prefill_pad_share_reads_the_prefill_counters():
    m = _metric("prefill_pad_share")
    assert m.read(SimpleNamespace(stats=STATS)) == pytest.approx(100 * (1 - 9983 / 14336))
    assert m.read(SimpleNamespace(stats={"slot_occupancy": 1.0})) is None
    assert m.read(SimpleNamespace(stats={**STATS, "prefill_positions": 0})) is None
