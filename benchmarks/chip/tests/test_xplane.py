"""The trace reduction on a small trace of known shape: one device with a
decode segment and a prefill program, the two packed kernels, and the
benchmark's host spans.  Times in ms, from the trace's start:

    host   window [0, 10), on_sync [6, 7)
    device segment [1, 5.5): fusion [1, 2), vusa_packed_matmul [2, 3),
                             vusa_fused_mlp_matmul [3, 5.5)
           prefill [8, 9.5): fusion [8, 9.5)
"""

import pytest
from jax.profiler import ProfileData

import xplane

MS = 10**9  # picoseconds
KERNELS = ("vusa_packed_matmul", "vusa_fused_mlp_matmul")  # the dense family's


def event(mid, start_ms, dur_ms):
    return f"events {{ metadata_id: {mid} offset_ps: {int(start_ms * MS)} " \
           f"duration_ps: {int(dur_ms * MS)} }}"


def meta(names):
    return " ".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
                    for i, n in names.items())


HOST = {1: xplane.WINDOW_SPAN, 2: "bench.on_sync"}
DEV = {1: "jit__segment_paged_fn(3)", 2: "jit__prefill_masked_fn(4)", 3: "fusion.1",
       4: "%vusa_packed_matmul.24 = f32[8,1,2048] custom-call(bf16[8,1,2048] %copy.1)",
       5: "%vusa_fused_mlp_matmul.6 = f32[8,1,2048] custom-call(bf16[8,1,2048] %copy.2)",
       6: "fusion.2"}
TEXT = f"""
planes {{ name: "/host:CPU" lines {{ name: "python3" timestamp_ns: 0
  {event(1, 0, 10)} {event(2, 6, 1)} }} {meta(HOST)} }}
planes {{ name: "/device:TPU:0"
  lines {{ name: "XLA Modules" timestamp_ns: 0 {event(1, 1, 4.5)} {event(2, 8, 1.5)} }}
  lines {{ name: "XLA Ops" timestamp_ns: 0 {event(3, 1, 1)} {event(4, 2, 1)} {event(5, 3, 2.5)}
    {event(6, 8, 1.5)} }}
  {meta(DEV)} }}
"""


@pytest.fixture(scope="module")
def summary():
    return xplane.summarize(ProfileData.from_text_proto(TEXT), KERNELS)


def test_busy_and_window(summary):
    assert summary.devices == 1
    assert summary.window_s == pytest.approx(10e-3)
    assert summary.busy_s == pytest.approx(6e-3)


def test_programs_and_kernels(summary):
    assert summary.module_time("segment") == (1, pytest.approx(4.5e-3))
    assert summary.module_time("prefill") == (1, pytest.approx(1.5e-3))
    assert summary.kernels["vusa_packed_matmul"] == [1, pytest.approx(1e-3)]
    assert summary.kernels["vusa_fused_mlp_matmul"] == [1, pytest.approx(2.5e-3)]


def test_idle_gaps_are_named_after_the_host_span_over_them(summary):
    gaps = dict(summary.gaps)
    assert gaps["on_sync"] == pytest.approx(2.5e-3)
    assert gaps["host (unannotated)"] == pytest.approx(1.5e-3)
    b = xplane.breakdown(summary)
    assert b["device_ops"][0] == ["vusa_fused_mlp_matmul.6", pytest.approx(2.5e-3)]
    assert b["idle_gaps"][0][0] == "on_sync"


def test_op_names_drop_their_hlo_text():
    name = "%vusa_fused_mlp_matmul.6 = f32[8,1,2048]{2,1,0} custom-call(bf16[8,1,2048] %copy.152)"
    assert xplane.op_name(name) == "vusa_fused_mlp_matmul.6"
    assert xplane.op_name("fusion.2") == "fusion.2"


@pytest.mark.parametrize("name, kernels, kernel", [
    ("%vusa_fused_mlp_matmul.6 = f32[8,1,2048] custom-call(...)", KERNELS,
     "vusa_fused_mlp_matmul"),
    ("%vusa_packed_matmul.24 = f32[8,1,2048] custom-call(...)", KERNELS, "vusa_packed_matmul"),
    ("%vusa_packed_matmul_head.6 = f32[8,1,92672] custom-call(...)", KERNELS,
     "vusa_packed_matmul"),
    ("%vmap_jit_vusa_packed_matmul__.6 = f32[8,1,92672] custom-call(...)", KERNELS,
     "vusa_packed_matmul"),
    ("%convert_convert_fusion.5 = bf16[8,1,2048] fusion(...)", KERNELS, None),
    ("%vusa_packed_matmul_experts.3 = f32[2,1408] custom-call(...)",
     ("vusa_packed_matmul", "vusa_packed_matmul_experts"), "vusa_packed_matmul_experts"),
    ("%vusa_packed_matmul_head.6 = f32[8,1,92672] custom-call(...)",
     ("vusa_packed_matmul_experts", "vusa_packed_matmul"), "vusa_packed_matmul"),
    ("%vusa_packed_matmul.24 = f32[8,1,2048] custom-call(...)", (), None),
])
def test_only_the_pallas_calls_are_kernels(name, kernels, kernel):
    assert xplane.kernel_of(name, kernels) == kernel
