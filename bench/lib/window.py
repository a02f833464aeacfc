"""The measured window: the harness's own clock around the engine.

The harness stamps when each request was due, submits it on schedule
(open loop) or keeps a backlog of `slots` queued requests (offline),
brings the engine to a loaded state before the window opens (a pre-roll
of the open-loop schedule, or slots primed part-way through requests),
calls `engine.step()`, and stamps every output token when the host sees
it retired.  Nothing here computes a metric: it records, and the readers
under metrics/ reduce the record.
"""
from __future__ import annotations

import dataclasses
import gc
import time

import jax
import numpy as np

# a request due in the window may finish after it closes: the harness
# keeps serving (and keeps offering load) this long for its first token
DRAIN_S = 60.0


@dataclasses.dataclass
class Rec:
    """What the harness saw of one request (times on its own clock)."""
    req: object
    due: float
    submitted: float
    times: list = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Counters:
    steps: int
    dispatch_s: float
    host_fetch_s: float
    telemetry_s: float
    phase_steps: dict

    @classmethod
    def of(cls, engine) -> "Counters":
        return cls(engine.steps, engine.dispatch_s, engine.host_fetch_s,
                   engine.telemetry_s, dict(engine.phase_steps))

    def minus(self, other: "Counters") -> "Counters":
        return Counters(
            self.steps - other.steps, self.dispatch_s - other.dispatch_s,
            self.host_fetch_s - other.host_fetch_s,
            self.telemetry_s - other.telemetry_s,
            {k: v - other.phase_steps.get(k, 0)
             for k, v in self.phase_steps.items()})


@dataclasses.dataclass
class Window:
    """The record of one window."""
    t_open: float
    t_close: float
    t_end: float                  # after the drain
    recs: list
    counters: Counters            # engine counters over the window
    compiles: int                 # programs traced or compiled inside it
    longest_steps: list           # [(seconds, process CPU seconds in
                                  #   it, at seconds after opening)]
    gc_s: list                    # the window's garbage collections, s


# JAX's events for a program traced or compiled
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")
# how many of the window's longest engine steps the record keeps
LONGEST_STEPS = 5


def run(engine, schedule, seconds: float, slots: int, backlog: bool,
        tracer=None, drain_s: float = DRAIN_S, preroll_s: float = 0.0,
        primed=(), clock=time.perf_counter) -> Window:
    """Serve `schedule` for `seconds`, then drain: keep serving (and
    offering the open-loop load) until every request due in the window
    has its first token, for at most `drain_s`.  Backlog traffic stops
    at the close.  Before the window opens (set-up): the `primed`
    requests are submitted and one step admits them; an open-loop
    schedule, whose due times count from its start, is served for
    `preroll_s`.  `tracer`, if given, is called with (now, t_open) after
    every step and may start or stop a profiler trace."""
    from repro.serving import Request
    ann = jax.profiler.TraceAnnotation
    compiles = []

    def heard(name, _secs, **_kw):
        if name in COMPILE_EVENTS and before is not None and after is None:
            compiles.append(name)

    collections, gc_start = [], []

    def collecting(phase, _info):
        if phase == "start":
            gc_start[:] = [clock()]
        elif gc_start and before is not None and after is None:
            collections.append(clock() - gc_start[0])

    recs, live = [], []

    def submit(planned, due):
        req = Request(rid=planned.index, prompt=planned.prompt,
                      max_new_tokens=planned.max_new)
        submitted = clock()
        engine.submit(req)
        rec = Rec(req, due, submitted)
        recs.append(rec)
        live.append(rec)

    def stamp(now):
        kept = []
        for rec in live:
            n = len(rec.req.tokens)
            if n > len(rec.times):
                rec.times.extend([now] * (n - len(rec.times)))
            if rec.req.state != "done":
                kept.append(rec)
        live[:] = kept

    for planned in primed:
        submit(planned, clock())
    if primed:
        engine.step()
        stamp(clock())
    pending = None
    t_sched = clock()
    t_open = t_sched + preroll_s
    t_close = t_open + seconds
    before = after = None
    steps = []
    jax.monitoring.register_event_duration_secs_listener(heard)
    gc.callbacks.append(collecting)
    try:
        while True:
            now = clock()
            if before is None and now >= t_open:
                before = Counters.of(engine)
            if after is None and now >= t_close:
                after = Counters.of(engine)
                if backlog:
                    break
            if after is not None and (
                    now >= t_close + drain_s
                    or all(r.times for r in recs if r.due < t_close)):
                break
            with ann("bench.submit"):
                while True:
                    if pending is None:
                        pending = next(schedule)
                    if backlog:
                        if len(engine.queue) >= slots:
                            break
                        due = now
                    else:
                        due = t_sched + pending.due
                        if due > now:
                            break
                    submit(pending, due)
                    pending = None
            t_step, cpu = clock(), time.process_time()
            with ann("bench.step"):
                stepped = engine.step()
            now = clock()
            if t_open <= t_step and now < t_close:
                steps.append((now - t_step, time.process_time() - cpu,
                              t_step - t_open))
            stamp(now)
            if tracer is not None:
                tracer(now, t_open)
            if not stepped and not backlog and pending is not None:
                wait = t_sched + pending.due - clock()
                if wait > 0:
                    with ann("bench.wait"):
                        time.sleep(min(wait, 1e-3))
    finally:
        gc.callbacks.remove(collecting)
        jax.monitoring.unregister_event_duration_listener(heard)
    return Window(t_open, t_close, clock(), recs, after.minus(before),
                  len(compiles), sorted(steps, reverse=True)[:LONGEST_STEPS],
                  collections)


def drain(engine, limit_s: float = 600.0) -> None:
    """Serve until the engine is idle (set-up and between windows)."""
    t0 = time.perf_counter()
    while engine.step():
        if time.perf_counter() - t0 > limit_s:
            raise RuntimeError(f"engine not idle after {limit_s} s")


def warm(engine, slots: int, vocab: int) -> None:
    """Compile every program the cell's traffic runs: one 2-token
    request per slot (so every slot index is admitted and reset), which
    runs a pure-prefill step and then decode steps, i.e. both phase
    programs where the phase plans differ."""
    from repro.serving import Request
    rng = np.random.default_rng(0)
    for i in range(slots):
        engine.submit(Request(rid=f"warm{i}",
                              prompt=rng.integers(0, vocab, 2, np.int32),
                              max_new_tokens=2))
    drain(engine)
