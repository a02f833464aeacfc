"""From a profiler trace to busy time, kernel time and labelled gaps.

The harness wraps its own loop in `jax.profiler.TraceAnnotation` spans
named "bench.*" ("bench.trace" around the whole traced window, and
"bench.step", "bench.submit", "bench.wait" inside it).  The device's
operations are the events of the "XLA Ops" line of each "/device:TPU:n"
plane, named by their HLO text ("%int8_matmul.20 = f32[64,3072] ...");
an op is known by the instruction name at its head ("int8_matmul.20").
A `while` op spans the ops of its body, which are listed too.  XLA may
stage a custom call's int8 operand (a layer's weight sliced from the
stacked weights, or re-laid out) into fast memory by ops of its own just
before the call: that HBM traffic belongs to the kernel, so those
feeding ops count toward the kernel's time.  (On the CPU backend, which
only the tests use, operations run on host threads and carry an
"hlo_op" stat.)  Everything here works on
plain (name, start_ns, end_ns) tuples once extracted, so the reduction
is checked on a hand-made trace too.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

WINDOW_SPAN = "bench.trace"
# ops that only contain other ops: counted in busy time, not ranked
CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass
class Events:
    device_ops: dict      # device name -> [(op name, start_ns, end_ns)]
    host_spans: list      # [(span name, start_ns, end_ns)]
    feeders: dict = dataclasses.field(default_factory=dict)
                          # custom-call op -> ops making its int8 operands


def latest_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def extract(path: str) -> Events:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, spans, feeders = {}, [], {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                dev = ops.setdefault(plane.name, [])
                for e in line.events:
                    name = op_name(e.name)
                    dev.append((name, e.start_ns,
                                e.start_ns + e.duration_ns))
                    if name not in feeders and " custom-call(" in e.name:
                        feeders[name] = int8_operands(e.name)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
                    elif any(k == "hlo_op" for k, _ in e.stats):
                        ops.setdefault("cpu", []).append(
                            (e.name, e.start_ns,
                             e.start_ns + e.duration_ns))
    return Events(ops, spans, feeders)


def int8_operands(text: str) -> list[str]:
    """The ops that make a custom call's int8 (s8) operands, from its
    HLO text: "custom-call(bf16[..] %a, s8[..]{..} %b, ..)" -> ["b"]."""
    args = text.split(" custom-call(", 1)[1].split("), custom_call_target",
                                                   1)[0]
    return re.findall(r"(?:^|, )s8\[[^%]*%([\w.\-]+)", args)


def op_name(text: str) -> str:
    """"%fusion.62 = (f32[...]) fusion(...)" -> "fusion.62"."""
    if text.startswith("%"):
        return text[1:].split(" ", 1)[0]
    return text


def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged intervals, clipped to [lo, hi]."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def window(ev: Events) -> tuple[float, float]:
    spans = [(s, e) for n, s, e in ev.host_spans if n == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    return min(s for s, _ in spans), max(e for _, e in spans)


def _host_label(spans, t: float) -> str:
    """The innermost bench span that covers time t, or "host-other"."""
    inside = [(e - s, n) for n, s, e in spans
              if s <= t <= e and n != WINDOW_SPAN]
    return min(inside)[1] if inside else "host-other"


def _op_family(name: str) -> str:
    """An op name without its numeric suffixes ("fusion.123" -> "fusion")."""
    return re.sub(r"(\.\d+)+$", "", name)


def reduce(ev: Events, kernel: str, top: int = 10) -> dict:
    """Busy and idle time in the traced window, averaged over the devices
    that ran anything; the device time of ops whose name matches the
    regex `kernel`, with the ops that feed them their int8 operands; the
    ops that took most time and the longest idle gaps, each gap named by
    what the host was doing at its middle."""
    lo, hi = window(ev)
    win = (hi - lo) * 1e-9
    devices = {d: ops for d, ops in ev.device_ops.items()
               if any(e > lo and s < hi for _, s, e in ops)}
    if not devices:
        raise ValueError("no device operation in the traced window")
    busy, kern, by_op, gaps = 0.0, 0.0, {}, []
    pat = re.compile(kernel)
    fed = {f for k, fs in ev.feeders.items() if pat.search(k) for f in fs}
    for ops in devices.values():
        merged = union(((s, e) for _, s, e in ops), lo, hi)
        busy += sum(e - s for s, e in merged)
        for name, s, e in ops:
            s, e = max(s, lo), min(e, hi)
            if e <= s:
                continue
            fam = _op_family(name)
            if fam not in CONTAINERS:
                by_op[fam] = by_op.get(fam, 0.0) + (e - s)
            if pat.search(name) or name in fed:
                kern += e - s
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1])
                 for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    n = len(devices)
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "window_s": win,
        "busy_s": busy * 1e-9 / n,
        "idle_share": 1.0 - busy * 1e-9 / n / win,
        "kernel_s": kern * 1e-9 / n,
        "devices": n,
        "device_ops": [[k, v * 1e-9 / n] for k, v in
                       sorted(by_op.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[_host_label(ev.host_spans, (s + e) / 2),
                       (e - s) * 1e-9] for s, e in gaps[:top]],
    }
