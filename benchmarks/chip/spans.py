"""The program's own spans and scopes in a kept trace (``run.py
--keep-trace``): idle gaps named after the innermost host span over them,
host time per span, and device time per ``decode.*`` scope.

``xplane.summarize`` keeps only the benchmark's ``bench.*`` host spans and
an operation's name; this reads the same trace further.  Host spans are
those named ``serve.*`` (``Scheduler.run``, ``Engine`` prefill, the
journal) or ``bench.*``.  The busy union is ``xplane``'s own, so the idle
time is the same; each instant of a gap is named after the shortest span
that covers it, which is the innermost one, since spans nest.  A device
operation's scope is the ``decode.<part>`` segment of its name stack.
On the TPU that stack is the ``tf_op`` stat of the operation's event
metadata, which ``ProfileData`` does not expose, so it is read from the
raw ``XSpace`` with the XPlane protobuf module that the installed
TensorFlow carries; failing that, from any string stat of the event or
its name.  Operations that only wait on others (``while``,
``conditional``, ``call``, asynchronous ``*-start``/``*-done``) span whole
loops and are left out of the scope sums.

    python benchmarks/chip/spans.py <file.xplane.pb> --kernels <kernel> ... [--segment 8]

``--kernels`` names the packed kernels whose device time each scope
splits off: the keys of the family module's ``packed_calls``.
"""

from __future__ import annotations

import argparse
import json
import re
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path

import xplane

SPAN_PREFIXES = ("serve.", "bench.")
UNNAMED = "host (unannotated)"
SCOPE = re.compile(r"(?:^|[/(\"=])(decode\.[A-Za-z_]+)(?=[/)\"]|$)")
WAITS = re.compile(r"^(while|conditional|call)(\.\d+)?$|-(start|done)(\.\d+)?$")


@dataclass
class SpanSummary:
    window_s: float
    idle_s: float  # averaged over device planes, as xplane's busy time
    devices: int
    gaps: dict = field(default_factory=dict)  # innermost span -> idle seconds
    host: dict = field(default_factory=dict)  # span name -> [count, seconds]
    scopes: dict = field(default_factory=dict)  # decode.* -> [count, seconds]
    scope_kernels: dict = field(default_factory=dict)  # decode.* -> packed-kernel seconds
    segments: int = 0  # decode segment programs started in the window

    def per_sync_ms(self) -> dict:
        """Host milliseconds of each span per segment dispatch."""
        n = self.host.get("serve.dispatch", [0, 0.0])[0]
        return {k: 1000.0 * s / n for k, (_, s) in sorted(self.host.items())} if n else {}

    def scope_ms_per_step(self, steps_per_segment: int) -> dict:
        """Device milliseconds per decode step of each scope, split into
        the packed-kernel calls in it and everything else."""
        steps = self.segments * steps_per_segment
        if not steps:
            return {}
        out = {}
        for k, (_, s) in sorted(self.scopes.items()):
            kern = self.scope_kernels.get(k, 0.0)
            out[k] = {"all": 1000.0 * s / steps, "kernels": 1000.0 * kern / steps,
                      "other": 1000.0 * (s - kern) / steps}
        return out


def span_name(name: str) -> str:
    """A host event's span name without the metadata a profiler annotation
    may encode in it (``serve.prefill#rows=4,bucket=2048#``)."""
    return name.split("#", 1)[0]


def scope_of(name: str, stats: dict, stacks: dict):
    """The ``decode.*`` scope an operation ran under, or None.  ``stacks``:
    operation event name -> name stack (:func:`name_stacks`)."""
    for text in [stacks.get(name, ""), *(v for v in stats.values() if isinstance(v, str)), name]:
        m = SCOPE.search(text)
        if m:
            return m.group(1)
    return None


def name_stacks(path: str) -> dict:
    """{operation event name: its ``tf_op`` name stack} from the device
    planes' event metadata of the ``.xplane.pb`` at ``path``; empty where
    the XPlane protobuf module is not installed."""
    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
    except ImportError:
        return {}
    space = xplane_pb2.XSpace()
    space.ParseFromString(Path(path).read_bytes())
    out = {}
    for plane in space.planes:
        if not xplane._is_device(plane.name):
            continue
        names = {k: m.name for k, m in plane.stat_metadata.items()}
        for em in plane.event_metadata.values():
            for st in em.stats:
                if names.get(st.metadata_id) == "tf_op":
                    out[em.name] = st.str_value or names.get(st.ref_value, "")
    return out


def waits(name: str) -> bool:
    """True for operations that span others rather than occupy the device."""
    return bool(WAITS.search(xplane.op_name(name)))


def _innermost(a: int, b: int, spans: list) -> dict:
    """Seconds of [a, b) under each innermost span (UNNAMED where none)."""
    inside = [s for s in spans if s[1] < b and s[2] > a]
    cuts = sorted({a, b, *(x for _, s, e in inside for x in (s, e) if a < x < b)})
    out = defaultdict(float)
    for lo, hi in zip(cuts, cuts[1:]):
        cover = [(e - s, n) for n, s, e in inside if s <= lo and e >= hi]
        out[min(cover)[1] if cover else UNNAMED] += (hi - lo) / 1e9
    return out


def summarize(trace, kernels) -> SpanSummary:
    """``trace``: a path to an ``.xplane.pb`` or a ``ProfileData``;
    ``kernels``: the packed kernels' names, as ``xplane.summarize`` takes
    them."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(trace) if isinstance(trace, str) else trace
    stacks = name_stacks(trace) if isinstance(trace, str) else {}
    spans, window, planes = [], None, []
    for plane in pd.planes:
        if xplane._is_device(plane.name):
            planes.append(list(plane.lines))
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    name = span_name(ev.name)
                    if name == xplane.WINDOW_SPAN:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    elif name.startswith(SPAN_PREFIXES):
                        spans.append((name, ev.start_ns, ev.start_ns + ev.duration_ns))
    ops, modules = [], []
    for lines in planes:
        mine = []
        for line in lines:
            if "Module" in line.name:
                modules += [(ev.name, ev.start_ns) for ev in line.events]
            elif "Op" in line.name:
                mine += [(ev.name, ev.start_ns, ev.duration_ns, ev) for ev in line.events]
        ops.append(mine)
    if window is None:
        evs = [(s, s + d) for mine in ops for _, s, d, _ in mine]
        if not evs:
            return SpanSummary(0.0, 0.0, 0)
        window = (min(a for a, _ in evs), max(b for _, b in evs))
    t0, t1 = window
    out = SpanSummary(window_s=max(t1 - t0, 1) / 1e9, idle_s=0.0, devices=0)
    host = defaultdict(lambda: [0, 0.0])
    for name, s, e in spans:
        if s >= t0 and e <= t1:
            host[name][0] += 1
            host[name][1] += (e - s) / 1e9
    scopes = defaultdict(lambda: [0, 0.0])
    in_kernels = defaultdict(float)
    gaps = defaultdict(float)
    for mine in ops:
        if not mine:
            continue
        out.devices += 1
        ivs = []
        for name, s, d, ev in mine:
            if s + d <= t0 or s >= t1:
                continue
            ivs.append((max(s, t0), min(s + d, t1)))
            if waits(name):
                continue
            scope = scope_of(name, xplane._stats(ev), stacks)
            if scope:
                scopes[scope][0] += 1
                scopes[scope][1] += d / 1e9
                if xplane.kernel_of(name, kernels):
                    in_kernels[scope] += d / 1e9
        u = xplane._union(ivs)
        edges = [t0] + [x for iv in u for x in iv] + [t1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                for k, v in _innermost(a, b, spans).items():
                    gaps[k] += v
    n = max(out.devices, 1)
    out.gaps = dict(sorted(((k, v / n) for k, v in gaps.items()), key=lambda kv: -kv[1]))
    out.idle_s = sum(out.gaps.values())
    out.host = dict(host)
    out.scopes = dict(scopes)
    out.scope_kernels = dict(in_kernels)
    out.segments = sum(1 for name, s in modules if "segment" in name and t0 <= s < t1)
    return out


def report(s: SpanSummary, steps_per_segment: int) -> dict:
    steps = s.scope_ms_per_step(steps_per_segment)
    return {
        "window_s": s.window_s, "idle_s": s.idle_s, "devices": s.devices,
        "segments": s.segments,
        "idle_gaps_ms": {k: 1000.0 * v for k, v in s.gaps.items()},
        "idle_gap_shares": {k: v / s.idle_s for k, v in s.gaps.items()} if s.idle_s else {},
        "host_ms_per_sync": s.per_sync_ms(),
        "scope_ms_per_step": steps,
        "decode_attention_ms": steps.get("decode.attention", {}).get("other"),
    }


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", nargs="+")
    ap.add_argument("--kernels", nargs="+", required=True, help="the packed kernels' names")
    ap.add_argument("--segment", type=int, default=8, help="decode steps per segment program")
    args = ap.parse_args()
    for path in args.trace:
        print(json.dumps({"trace": path, **report(summarize(path, args.kernels), args.segment)}))
