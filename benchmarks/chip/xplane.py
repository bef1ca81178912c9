"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

``summarize(path, kernels)`` reads the file with ``jax.profiler.ProfileData``
and keeps, for the device planes, every operation and every whole program
("XLA Modules") that ran, with the time of each kernel named in ``kernels``
(the keys of the family module's ``packed_calls``), and for the host, the
spans the benchmark annotates (``jax.profiler.TraceAnnotation``).  The
window is the benchmark's own host span ``WINDOW_SPAN`` when the trace holds
it, else the extent of the device events.

Busy time is the union of the device's operation intervals inside the
window, averaged over the device planes; idle gaps are the holes in that
union, each named after the host span that overlaps it most.

    python benchmarks/chip/xplane.py <file.xplane.pb> [kernel ...]   # print a summary
"""

from __future__ import annotations

import json
import sys
from collections import defaultdict
from dataclasses import dataclass, field

WINDOW_SPAN = "bench.traced_window"
HOST_SPAN_PREFIX = "bench."


@dataclass
class Summary:
    window_s: float
    busy_s: float  # averaged over device planes
    devices: int
    modules: dict = field(default_factory=dict)  # name -> [count, seconds]
    ops: dict = field(default_factory=dict)  # name -> [count, seconds]
    kernels: dict = field(default_factory=dict)  # kernel name -> [count, seconds]
    gaps: list = field(default_factory=list)  # [(name, seconds)], longest first

    def module_time(self, needle: str) -> tuple:
        """(count, seconds) of programs whose name holds ``needle``."""
        n = s = 0.0
        for name, (c, t) in self.modules.items():
            if needle in name:
                n, s = n + c, s + t
        return int(n), s


def _union(intervals):
    """Sorted disjoint union of (start, end) pairs."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _stats(ev) -> dict:
    try:
        return {k: v for k, v in ev.stats}
    except Exception:  # stats of an unknown type: the name alone still classifies
        return {}


def kernel_of(name: str, kernels) -> str | None:
    """The kernel of ``kernels`` an operation event is, or None.  A Pallas
    call is an operation named after its kernel function with a suffix the
    compiler adds (``<kernel>.6``, ``<kernel>_head.6`` for a call site
    named apart, ``vmap_jit_<kernel>__.6`` under a vmap that keeps the
    kernel's grid); the small XLA operations around it carry the kernel's
    name only in their metadata and are not it.  The longest name that
    the operation's name holds wins, so a kernel whose name extends
    another's is told apart from it."""
    short = op_name(name)
    return next((k for k in sorted(kernels, key=len, reverse=True) if k in short), None)


def op_name(name: str) -> str:
    """An operation event's name without its HLO text: the TPU names an
    event ``%while.39 = (s32[], ...) while(...)``, thousands of letters."""
    return name.split(" = ", 1)[0].lstrip("%")


def _is_device(plane_name: str) -> bool:
    return plane_name.startswith("/device:") and "CPU" not in plane_name


def summarize(trace, kernels) -> Summary:
    """``trace``: a path to an ``.xplane.pb`` or a ``ProfileData``;
    ``kernels``: the kernel names whose calls ``Summary.kernels`` sums."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(trace) if isinstance(trace, str) else trace
    host_spans = []
    device_lines = []
    for plane in pd.planes:
        if _is_device(plane.name):
            device_lines.append((plane.name, list(plane.lines)))
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_SPAN_PREFIX):
                        host_spans.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    win = [s for s in host_spans if s[0] == WINDOW_SPAN]
    planes = defaultdict(lambda: {"ops": [], "modules": []})
    for pname, lines in device_lines:
        for line in lines:
            kind = ("modules" if "Module" in line.name
                    else "ops" if "Op" in line.name else None)
            if kind is None:
                continue
            for ev in line.events:
                planes[pname][kind].append((ev.name, ev.start_ns, ev.duration_ns, ev))
    if win:
        t0, t1 = win[0][1], win[0][2]
    else:
        evs = [(s, s + d) for p in planes.values() for _, s, d, _ in p["ops"]]
        if not evs:
            return Summary(0.0, 0.0, 0)
        t0, t1 = min(a for a, _ in evs), max(b for _, b in evs)
    span = max(t1 - t0, 1)
    modules = defaultdict(lambda: [0, 0.0])
    ops = defaultdict(lambda: [0, 0.0])
    per_kernel = defaultdict(lambda: [0, 0.0])
    busy = 0.0
    gaps = defaultdict(float)
    n_dev = 0
    for p in planes.values():
        if not p["ops"]:
            continue
        n_dev += 1
        ivs = []
        for name, s, d, ev in p["ops"]:
            if s + d <= t0 or s >= t1:
                continue
            ivs.append((max(s, t0), min(s + d, t1)))
            ops[op_name(name)][0] += 1
            ops[op_name(name)][1] += d / 1e9
            k = kernel_of(name, kernels)
            if k:
                per_kernel[k][0] += 1
                per_kernel[k][1] += d / 1e9
        for name, s, d, _ in p["modules"]:
            if t0 <= s < t1:
                modules[name][0] += 1
                modules[name][1] += d / 1e9
        u = _union(ivs)
        busy += sum(b - a for a, b in u) / 1e9
        edges = [t0] + [x for iv in u for x in iv] + [t1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b - a <= 0:
                continue
            best, over = "host (unannotated)", 0
            for hn, hs, he in host_spans:
                if hn == WINDOW_SPAN:
                    continue
                o = min(b, he) - max(a, hs)
                if o > over:
                    best, over = hn[len(HOST_SPAN_PREFIX):], o
            gaps[best] += (b - a) / 1e9
    return Summary(
        window_s=span / 1e9,
        busy_s=busy / max(n_dev, 1),
        devices=n_dev,
        modules=dict(modules),
        ops=dict(ops),
        kernels=dict(per_kernel),
        gaps=sorted(((k, v / max(n_dev, 1)) for k, v in gaps.items()), key=lambda kv: -kv[1]),
    )


def breakdown(s: Summary, top: int = 10) -> dict:
    ops = sorted(s.ops.items(), key=lambda kv: -kv[1][1])[:top]
    return {
        "device_ops": [[name, secs] for name, (_, secs) in ops],
        "idle_gaps": [[name, secs] for name, secs in s.gaps[:top]],
    }


def describe_file(path: str) -> dict:
    """Planes, lines and a few events with their stats: what to look at by
    hand before trusting the reduction on a new program or JAX version."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            evs = list(line.events)
            lines.append({
                "line": line.name, "events": len(evs),
                "sample": [{"name": e.name, "dur_ns": e.duration_ns,
                            "stats": {k: str(v)[:200] for k, v in _stats(e).items()}}
                           for e in evs[:4]],
                "names": sorted({e.name for e in evs})[:60],
            })
        out.append({"plane": plane.name, "lines": lines})
    return {"planes": out}


if __name__ == "__main__":
    s = summarize(sys.argv[1], sys.argv[2:])
    print(json.dumps({"window_s": s.window_s, "busy_s": s.busy_s, "devices": s.devices,
                      "modules": s.modules, "kernels": s.kernels,
                      **breakdown(s)}, indent=1))
