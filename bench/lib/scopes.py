"""From a profiler trace of the serving engine to device time by sublayer
scope and by program, and idle time by engine phase.

`trace.py` reduces a trace by what the harness alone can see: the XLA
op names and its own "bench.*" spans.  This module reads what the
program marks itself, on the same trace:

* host spans: the serving engine marks its phases inside `engine.step()`
  with `jax.profiler` spans named "engine.*" (step, admit, reset_slot,
  plan, dispatch, retire, telemetry).  Each idle gap of the device is
  named by the innermost host span at its middle, and the idle time
  inside every span name is kept.  A program without them leaves the
  gaps named by the bench spans.
* scopes: the decode step wraps each sublayer in a `jax.named_scope`
  (`SCOPES`, and "proj/<route>/<label>" around every projection), which
  XLA keeps as each instruction's op_name metadata
  ("jit(serve_batch_step)/layer_scan/while/body/attn_core/...").  A
  TPU's "XLA Ops" events carry only their HLO text and times, so an
  op's path comes from the HLO of the compiled program (`hlo_paths`,
  `step_op_paths`), and its program from the "XLA Modules" event it
  runs inside, since instruction names repeat across programs.  An op's
  bucket is the innermost scope in its path, "(unscoped)" if none.

The events here are `trace.Events` whose device ops carry their program
as a fourth element, (op name, start_ns, end_ns, program), and whose
host spans include the engine's; `plain` gives what `trace.extract`
gives, so `trace.reduce` reads the same trace unchanged.

The harness does not call this module yet: the traced run would pass
its trace through `extract` and add `idle` and `scopes` of it to its
breakdown (PERF.md, open questions).
"""
from __future__ import annotations

import bisect
import re

import numpy as np

from . import trace as tr

# host spans kept from the trace: the harness's and the engine's
HOST_SPANS = ("bench.", "engine.")
# the decode step's sublayer scopes and the projection scope's head, as
# repro.models.layers names them
SCOPES = ("embed", "norm", "ffn_act", "attn_core", "ssd", "cache_mask",
          "lm_head", "layer_scan")
PROJ_SCOPE = "proj"
UNSCOPED = "(unscoped)"


def extract(path: str) -> tr.Events:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, spans, feeders = {}, [], {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            # an op's events carry its HLO text and times only: its
            # program is the "XLA Modules" event it runs inside
            modules = sorted(
                (e.start_ns, e.start_ns + e.duration_ns,
                 re.sub(r"\(\d+\)$", "", e.name))
                for line in plane.lines if line.name == "XLA Modules"
                for e in line.events)
            starts = [m[0] for m in modules]
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                dev = ops.setdefault(plane.name, [])
                for e in line.events:
                    name = tr.op_name(e.name)
                    i = bisect.bisect_right(starts, e.start_ns) - 1
                    program = (modules[i][2] if i >= 0
                               and modules[i][1] >= e.start_ns else None)
                    dev.append((name, e.start_ns,
                                e.start_ns + e.duration_ns, program))
                    if name not in feeders and " custom-call(" in e.name:
                        feeders[name] = tr.int8_operands(e.name)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_SPANS):
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
                        continue
                    stats = dict(e.stats)
                    if "hlo_op" in stats:
                        ops.setdefault("cpu", []).append(
                            (e.name, e.start_ns,
                             e.start_ns + e.duration_ns,
                             stats.get("hlo_module")))
    return tr.Events(ops, spans, feeders)


def plain(ev: tr.Events) -> tr.Events:
    """The events as `trace.extract` gives them: ops without their
    program, the bench spans alone."""
    return tr.Events(
        {d: [op[:3] for op in ops] for d, ops in ev.device_ops.items()},
        [s for s in ev.host_spans if s[0].startswith("bench.")],
        ev.feeders)


def _ran(ev: tr.Events, lo: float, hi: float) -> list:
    """The op lists of the devices that ran anything in [lo, hi]."""
    return [ops for ops in ev.device_ops.values()
            if any(e > lo and s < hi for _, s, e, *_ in ops)]


def _overlap(a, b) -> float:
    """Total overlap of two sorted lists of disjoint intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        total += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle(ev: tr.Events, top: int = 10) -> dict:
    """The longest idle gaps of the traced window, each named by the
    innermost host span at its middle, and for every host span name the
    idle time inside spans of that name, averaged over the devices that
    ran anything."""
    lo, hi = tr.window(ev)
    names = {n for n, _, _ in ev.host_spans if n != tr.WINDOW_SPAN}
    within = {n: tr.union(((s, e) for m, s, e in ev.host_spans if m == n),
                          lo, hi) for n in names}
    idle_in = dict.fromkeys(names, 0.0)
    devices = _ran(ev, lo, hi)
    gaps = []
    for ops in devices:
        merged = tr.union(((s, e) for _, s, e, *_ in ops), lo, hi)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        free = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps += free
        for name, spans in within.items():
            idle_in[name] += _overlap(free, spans)
    n = max(len(devices), 1)
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "idle_gaps": [[tr._host_label(ev.host_spans, (s + e) / 2),
                       (e - s) * 1e-9] for s, e in gaps[:top]],
        "idle_in_spans_s": {k: v * 1e-9 / n for k, v in idle_in.items()},
    }


def scope_of(path: str | None) -> str:
    """The innermost scope in an op_name path: "proj/<route>/<label>",
    one of SCOPES, or UNSCOPED.  A projection's label may repeat a scope
    name ("lm_head/proj/int8-dequant-xla/lm_head"), so the two components
    after "proj" belong to it."""
    parts = (path or "").split("/")
    scope, i = UNSCOPED, 0
    while i < len(parts):
        if parts[i] == PROJ_SCOPE and i + 2 < len(parts):
            scope, i = "/".join(parts[i:i + 3]), i + 3
            continue
        if parts[i] in SCOPES:
            scope = parts[i]
        i += 1
    return scope


_COMPUTATION = re.compile(r"(?:ENTRY )?%([\w.\-]+) .*\{$")
_INSTR = re.compile(r"\s*(?:ROOT )?%([\w.\-]+) = (.*)")
_OPCODE = re.compile(r" ([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")
# scopes an op falls back to when it holds no sublayer's work
_PLUMBING = (UNSCOPED, "layer_scan")


def hlo_paths(text: str) -> tuple[str, dict]:
    """(module name, {instruction: op_name path}) from a compiled
    program's HLO text.

    A fusion carries the metadata of its root, the last op XLA fused into
    it.  Where that root is the layer scan's own plumbing (the slice or
    broadcast that stacks a sublayer's result) or unscoped, the fusion
    takes the path of the sublayer most of its fused instructions come
    from.  An instruction without metadata (a copy, an async copy, a
    convert that XLA inserted) takes the path of its first operand that
    has one, followed through get-tuple-element, bitcast and the like."""
    module = text.split(None, 2)[1].rstrip(",") if text.startswith(
        "HloModule") else ""
    own, operands, calls, members = {}, {}, {}, {}
    body = members.setdefault("", [])
    for line in text.splitlines():
        c = _COMPUTATION.match(line)
        if c:
            body = members.setdefault(c.group(1), [])
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name, rest = m.groups()
        body.append(name)
        op = _OP_NAME.search(rest)
        if op:
            own[name] = op.group(1)
        called = _CALLS.search(rest)
        if called and " fusion(" in rest:
            calls[name] = called.group(1)
        elif not op:
            code = _OPCODE.search(rest)
            args = rest[code.end():] if code else ""
            operands[name] = _OPERAND.findall(
                re.split(r"\), [a-z_]+=", args, 1)[0])

    def fused(comp, seen):
        for n in members.get(comp, ()):
            if n in calls and calls[n] not in seen:
                seen.add(calls[n])
                yield from fused(calls[n], seen)
            elif n in own:
                yield own[n]

    paths = dict(own)
    for name, comp in calls.items():
        if scope_of(own.get(name)) not in _PLUMBING:
            continue
        inner = [p for p in fused(comp, {comp})
                 if scope_of(p) not in _PLUMBING]
        if inner:
            counts = {}
            for p in inner:
                counts[scope_of(p)] = counts.get(scope_of(p), 0) + 1
            most = max(counts.values())
            paths[name] = next(p for p in inner
                               if counts[scope_of(p)] == most)

    def resolve(name, seen):
        if name in paths:
            return paths[name]
        if name in seen or name not in operands:
            return None
        seen.add(name)
        for o in operands[name]:
            got = resolve(o, seen)
            if got is not None:
                return got
        return None

    for name in operands:
        got = resolve(name, set())
        if got is not None:
            paths[name] = got
    return module, paths


def step_op_paths(engine, phases) -> dict:
    """{(program, op): op_name path} of the engine's batch-step programs
    for the phase plans in `phases` ("decode", "prefill"), from their
    compiled HLO.  Once the engine has run those programs, lowering its
    step again with the engine's own arguments finds them compiled."""
    core, n = engine.core, engine.n_slots
    tables = {"decode": core.plan_table, "prefill": core.prefill_plan_table}
    tokens = engine._mix_tokens(engine._token_batch(), np.zeros(n, bool))
    out = {}
    for phase in phases:
        text = core.batch_step_for(tables[phase]).lower(
            core.params, engine.cache, tokens, np.zeros(n, np.int32),
            np.zeros(n, bool), engine.block_tables).compile().as_text()
        module, paths = hlo_paths(text)
        for op, path in paths.items():
            out.setdefault((module, op), path)
    return out


def _pieces(spans, lo: float, hi: float):
    """Cut [lo, hi] wherever one of `spans` [(start, end, rank, item)]
    starts or ends, and yield (t0, t1, item) for each piece in which a
    span runs: the highest-ranked one, the latest to start among equals
    (the innermost)."""
    spans = sorted((max(s, lo), min(e, hi), r, x) for s, e, r, x in spans
                   if min(e, hi) > max(s, lo))
    cuts = sorted({t for s, e, _, _ in spans for t in (s, e)})
    active, j = [], 0
    for t0, t1 in zip(cuts, cuts[1:]):
        while j < len(spans) and spans[j][0] <= t0:
            active.append(spans[j])
            j += 1
        active = [a for a in active if a[1] > t0]
        if active:
            yield t0, t1, max(active, key=lambda a: (a[2], a[0]))[3]


def scopes(ev: tr.Events, paths: dict | None = None, top: int = 10
           ) -> dict:
    """Busy device time of the traced window by sublayer scope and by
    program, averaged over the devices that ran anything.  Every instant
    in which an op runs goes to the innermost op running then (one that
    holds no other ops before a `while`), and that op's time to the
    scope of its op_name (`scope_of`) in `paths` {(program, op): path}
    (`step_op_paths`).  So the buckets are disjoint and sum to the busy
    time.  Also the ops left unscoped, by op family, the longest first."""
    lo, hi = tr.window(ev)
    paths = paths or {}
    by_scope, by_prog, unscoped = {}, {}, {}
    devices = _ran(ev, lo, hi)
    for ops in devices:
        spans = [(s, e, tr._op_family(name) not in tr.CONTAINERS,
                  (name, prog[0] if prog else None))
                 for name, s, e, *prog in ops]
        for t0, t1, (name, prog) in _pieces(spans, lo, hi):
            scope = scope_of(paths.get((prog, name)))
            by_scope[scope] = by_scope.get(scope, 0.0) + (t1 - t0)
            by_prog[prog] = by_prog.get(prog, 0.0) + (t1 - t0)
            if scope == UNSCOPED:
                fam = tr._op_family(name)
                unscoped[fam] = unscoped.get(fam, 0.0) + (t1 - t0)
    n = max(len(devices), 1)

    def ranked(d, k=None):
        return [[name, v * 1e-9 / n] for name, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:k]]
    return {"device_scopes": ranked(by_scope),
            "device_programs": ranked(by_prog),
            "unscoped_ops": ranked(unscoped, top)}
