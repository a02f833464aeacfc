"""Slot-scheduled continuous batching over one compiled decode core.

Production traffic is a stream of ragged requests, not one fixed-shape
batch.  This module turns the plan-gated decode step into a request
server:

  * an **admission queue** (FIFO) of `Request`s;
  * **slots**: the jitted step always runs at a fixed batch of
    `n_slots` lanes; a request joins a free slot, decodes in place, and
    is evicted on EOS / max-tokens — mid-decode, without retracing —
    via the step's jit-dynamic active-slot mask;
  * **paged KV**: attention caches live in a shared block pool
    (models.model.init_paged_cache); a host-side `BlockAllocator` hands
    fixed-size blocks to slots and reclaims them on eviction, so ragged
    lengths share one executable and one pool;
  * **piggy-backed prefill**: a joining request's prompt tokens stream
    through the *same* decode step, one per engine iteration, while the
    other slots keep generating — prefill and decode share the plan
    gate, the executable, and the batch;
  * a **sync-free token loop**: greedy traffic runs one step ahead of
    the host — step t's sampled tokens stay on device and feed step t+1
    directly (a jitted where-select mixes device tokens with host
    prompt tokens per lane), and the host blocks on step t's tokens
    only after step t+1 is dispatched.  When the core donates its cache
    argument (`DecodeCore.donate` — accelerator default), the paged-KV
    pools update in place (no per-token copy;
    `telemetry()["aggregate"]["kv_donation_ok"]` probes it on the first
    step, and stays None when donation is off).  Temperature requests
    need host logits between steps, so they flip the engine to
    synchronous retire;
  * **per-request telemetry**: TTFT, queue wait, decode tokens/s, plus
    engine-level queue depth / slot occupancy / block usage samples and
    a `decode_step_breakdown` (admission, plan selection, dispatch,
    host-fetch and telemetry time per step);
  * **spans on the profiler's clock**: every `step()` is a
    `jax.profiler.StepTraceAnnotation` "engine.step" holding one
    "engine.<phase>" `TraceAnnotation` per phase (admit, plan,
    dispatch, retire, telemetry), opened and closed where the phase's
    counter reads the clock.  With no profiler running, a span costs
    about a microsecond of host time;
  * **admission is host-only**: a joining slot starts at position 0,
    and the decode step itself zeroes the SSM state and conv carry of
    every lane at position 0 (`fresh_lanes` counts them), so admission
    dispatches no device work and copies no state;
  * **phase-split gating**: the core fixes one plan table per serving
    phase when it is built; a step whose live slots are all still
    feeding their prompt runs the prefill table's program, any other
    step the decode table's.

The scheduler is pure host-side Python around the core's batch steps
(`DecodeCore.batch_step_for`); everything it varies per step (tokens,
positions, active mask, block tables) is a jit-*dynamic* input, so any
traffic pattern hits exactly one compiled executable per distinct phase
table (`decode_executables` is 1 when the phases share a table, 2 when
they differ).
"""
from __future__ import annotations

import collections
import dataclasses
import math
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from ..models import period_slots
from ..models.model import init_paged_cache
from .core import DecodeCore, sample_token


@dataclasses.dataclass
class Request:
    """One serving request: a prompt plus generation settings.

    Telemetry fields (t_*, tokens, ...) are engine-written; times are
    seconds on the engine clock.  `tokens` holds generated token ids
    (ints; audio: (n_codebooks,) int arrays)."""
    rid: Any
    prompt: Any                       # (P,) int32 (audio: (P, nb))
    max_new_tokens: int
    temperature: float = 0.0
    eos_id: int | None = None
    # --- engine-written telemetry ---
    state: str = "new"                # new | queued | running | done
    done_reason: str | None = None    # eos | max_tokens
    t_submit: float | None = None
    t_admit: float | None = None
    t_first: float | None = None      # first generated token (TTFT ref)
    t_done: float | None = None
    tokens: list = dataclasses.field(default_factory=list)
    first_logits: Any = None          # recorded iff record_logits=True

    @property
    def prompt_len(self) -> int:
        return int(self.prompt.shape[0])


class BlockAllocator:
    """Host-side free list over the paged KV pool's physical blocks.

    Allocation is all-or-nothing per request (the engine reserves the
    request's full horizon at admission, so a running request can never
    hit pool exhaustion mid-decode — admission control is the only
    back-pressure point)."""

    def __init__(self, n_blocks: int):
        self.n_blocks = n_blocks
        self._free = list(range(n_blocks - 1, -1, -1))
        self._free_set = set(self._free)
        self.peak_in_use = 0

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def in_use(self) -> int:
        return self.n_blocks - len(self._free)

    def alloc(self, n: int) -> list[int] | None:
        if n < 0:
            raise ValueError(f"cannot allocate {n} blocks")
        if n > len(self._free):
            return None
        blocks = [self._free.pop() for _ in range(n)]
        self._free_set.difference_update(blocks)
        self.peak_in_use = max(self.peak_in_use, self.in_use)
        return blocks

    def free(self, blocks: list[int]) -> None:
        """Return blocks to the pool.  A double-free or an id the pool
        never issued would silently corrupt the free list (free_blocks
        could exceed n_blocks and a block could be handed to two slots),
        so both raise — and validation happens before any mutation, so a
        bad call leaves the allocator state untouched."""
        bad = [b for b in blocks
               if not (0 <= b < self.n_blocks) or b in self._free_set]
        if len(set(blocks)) != len(blocks):
            bad.extend(b for b in set(blocks)
                       if blocks.count(b) > 1 and b not in bad)
        if bad:
            raise ValueError(
                f"invalid free of block ids {sorted(set(bad))}: "
                f"double-free or id outside pool [0, {self.n_blocks})")
        self._free.extend(reversed(blocks))
        self._free_set.update(blocks)


class _Slot:
    """Mutable per-slot decode state (host-side only)."""

    def __init__(self, req: Request, blocks: list[int]):
        self.req = req
        self.blocks = blocks
        self.pos = 0          # tokens written into this slot's KV/state
        self.n_fed = 0        # prompt tokens consumed so far
        self.n_gen = 0        # tokens generated so far (counted at
                              # dispatch; retire attributes them)
        self.last_tok = None  # last retired token (host copy)
        self.dev_feed = False  # next feed comes from the previous
                               # step's on-device greedy tokens
        self.draining = False  # hit max_new_tokens at dispatch: excluded
                               # from further steps, evicted at retire

    @property
    def prefilling(self) -> bool:
        return self.n_fed < self.req.prompt_len

    def next_token(self):
        return (self.req.prompt[self.n_fed] if self.prefilling
                else self.last_tok)


class _InFlight:
    """One dispatched-but-not-retired decode step (the one-step-deep
    async queue of the sync-free token loop): the device-resident logits
    and greedy tokens plus the attribution records deciding which lanes'
    tokens belong to which requests once the host looks."""

    __slots__ = ("logits", "greedy", "recs")

    def __init__(self, logits, greedy, recs):
        self.logits = logits
        self.greedy = greedy
        self.recs = recs      # [(lane, slot, is_first, is_final), ...]


class ContinuousBatchingEngine:
    """Request server: admission queue + slot-scheduled continuous
    batching + paged KV, over one immutable `DecodeCore`.

    Every engine iteration (`step()`) advances all active slots by one
    token through the single jitted masked decode step: joining requests
    stream prompt tokens (piggy-backed prefill), running requests feed
    their last sampled token, and finished requests leave their slot the
    moment EOS / max-tokens hits — the next queued request takes it on
    the following step.
    """

    def __init__(self, core: DecodeCore, n_slots: int, max_len: int,
                 block_size: int = 8, n_kv_blocks: int | None = None,
                 seed: int = 0, record_logits: bool = False,
                 pipeline: bool = True,
                 clock: Callable[[], float] = time.perf_counter):
        if core.cfg.family == "vlm":
            raise NotImplementedError(
                "continuous batching does not yet thread per-request "
                "image embeddings through cross-attention slots")
        self.core = core
        self.cfg = core.cfg
        self.n_slots = n_slots
        self.max_len = max_len
        self.block_size = block_size
        self.record_logits = record_logits
        self.clock = clock
        self.needs_kv = any(s.mixer == "attn"
                            for s in period_slots(core.cfg))
        self.max_blocks = max(1, math.ceil(max_len / block_size))
        if n_kv_blocks is None:
            n_kv_blocks = self.max_blocks * n_slots   # full provisioning
        self.allocator = BlockAllocator(n_kv_blocks if self.needs_kv
                                        else 0)
        self.cache = init_paged_cache(core.cfg, core.rc, n_slots,
                                      max(1, n_kv_blocks), block_size)
        self.block_tables = np.zeros((n_slots, self.max_blocks), np.int32)
        self.queue: collections.deque[Request] = collections.deque()
        self.slots: list[_Slot | None] = [None] * n_slots
        self._key = jax.random.PRNGKey(seed)
        self._t0: float | None = None
        # sync-free token loop: step t's host fetch overlaps step t+1's
        # dispatch.  Temperature sampling needs host logits before the
        # next feed, so any temperature>0 submit flips the engine to
        # synchronous retire (pipeline=False forces it outright).
        self.pipeline = pipeline
        self._sync = False
        self._inflight: _InFlight | None = None
        self._device_toks = None      # prev step's greedy (device)
        self._select_fn = None        # jitted host/device token mix
        self._greedy_fn = None        # jitted greedy sampler
        self.donation_ok: bool | None = None  # cache-donation probe
        # counters + per-step samples (the telemetry block)
        self.completed: list[Request] = []
        self.evictions = 0
        self.steps = 0
        self.queue_depth_samples: list[int] = []
        self.occupancy_samples: list[float] = []
        # decode_step_breakdown accumulators (seconds): telemetry_s
        # holds admission (admit_s) and the samples
        self.admissions = 0
        self.fresh_lanes = 0          # lane-steps dispatched at pos 0
        self.admit_s = 0.0
        self.plan_s = 0.0             # phase-table selection + step fetch
        self.dispatch_s = 0.0
        self.host_fetch_s = 0.0
        self.telemetry_s = 0.0
        # phase-split gating: a step whose live slots are ALL still
        # prefilling runs under the prefill-phase table, any decoding
        # slot makes it a decode-phase step.  Each phase's program comes
        # from the core's per-table memo, so steady mixed traffic serves
        # from at most two compiled programs.  An unquantized core has
        # no prefill table: every step runs the decode program.
        self._step_fn = None          # resolved on the first step
        self._phase_tables = {"decode": core.plan_table,
                              "prefill": core.prefill_plan_table}
        self._phase = "decode"
        self.phase_switches = 0
        self.phase_steps = {"prefill": 0, "decode": 0}

    # --- admission ------------------------------------------------------

    def _now(self) -> float:
        if self._t0 is None:
            self._t0 = self.clock()
        return self.clock() - self._t0

    def _blocks_needed(self, req: Request) -> int:
        if not self.needs_kv:
            return 0
        return math.ceil((req.prompt_len + req.max_new_tokens)
                         / self.block_size)

    def submit(self, req: Request) -> None:
        """Queue a request (validates it can ever be admitted)."""
        horizon = req.prompt_len + req.max_new_tokens
        if horizon > self.max_len:
            raise ValueError(
                f"request {req.rid}: prompt_len + max_new_tokens = "
                f"{horizon} exceeds engine max_len {self.max_len}")
        if self._blocks_needed(req) > self.allocator.n_blocks:
            raise ValueError(
                f"request {req.rid} needs {self._blocks_needed(req)} KV "
                f"blocks; the pool only has {self.allocator.n_blocks}")
        req.prompt = np.asarray(req.prompt, np.int32)
        if req.temperature > 0.0:
            # the pipelined loop feeds on-device greedy tokens; a
            # categorical draw needs host logits before the next feed,
            # so temperature traffic degrades to synchronous retire
            self._sync = True
        req.state = "queued"
        req.t_submit = self._now()
        self.queue.append(req)

    def _admit(self) -> None:
        """FIFO admission: the queue head takes the first free slot if
        its full KV horizon fits in the pool (no skipping — head-of-line
        order keeps TTFT fairness).  Host bookkeeping only: the slot's
        stale SSM state is zeroed by the step that feeds it at position
        0, and its stale KV blocks are dead by construction (per-slot
        lens mask + freed block ids)."""
        for i in range(self.n_slots):
            if not self.queue:
                return
            if self.slots[i] is not None:
                continue
            req = self.queue[0]
            blocks = self.allocator.alloc(self._blocks_needed(req))
            if blocks is None:
                return                      # pool pressure: wait
            self.queue.popleft()
            self.block_tables[i, :] = 0
            if blocks:
                self.block_tables[i, :len(blocks)] = blocks
            self.slots[i] = _Slot(req, blocks)
            self.admissions += 1
            req.state = "running"
            req.t_admit = self._now()

    # --- the engine iteration -------------------------------------------

    @property
    def active_slots(self) -> int:
        return sum(s is not None for s in self.slots)

    def _token_batch(self) -> np.ndarray:
        shape = ((self.n_slots, 1, self.cfg.audio.n_codebooks)
                 if self.cfg.family == "audio" else (self.n_slots, 1))
        toks = np.zeros(shape, np.int32)
        for i, st in enumerate(self.slots):
            if st is not None:
                tok = st.next_token()
                # a pipelined slot's last token may still be on device
                # (retired next step); its lane is overridden by the
                # device-token select in _dispatch, so 0 is a dead value
                toks[i, 0] = 0 if tok is None else tok
        return toks

    def _select_phase_table(self) -> None:
        """Per-step phase gating: serve a pure-prefill step (every live
        slot still feeding its prompt) under the prefill-phase plan
        table, anything else under the decode table.  A phase flip
        points the step at the other phase's program from the core's
        per-table memo — each compiles at most once, steady traffic
        never retraces."""
        live = [s for s in self.slots if s is not None and not s.draining]
        phase = ("prefill" if live and all(s.prefilling for s in live)
                 else "decode")
        if phase != self._phase:
            self._phase = phase
            self.phase_switches += 1
            self._step_fn = self.core.batch_step_for(
                self._phase_tables[phase])
        self.phase_steps[phase] += 1

    @property
    def _pipelined(self) -> bool:
        return self.pipeline and not self._sync

    def _mix_tokens(self, host_toks: np.ndarray, use_dev: np.ndarray):
        """Per-lane token feed: the previous step's on-device greedy
        token where `use_dev`, the host token (prompt / synchronous
        last_tok) elsewhere.  Tokens ALWAYS flow through the jitted
        select — even all-host batches — because the decode step's jit
        cache keys on input sharding/commitment, and mixing raw numpy
        steps with select-output steps would compile the program
        twice."""
        if self._select_fn is None:
            self._select_fn = jax.jit(jnp.where)
        mask = use_dev.reshape((self.n_slots, 1)
                               + (1,) * (host_toks.ndim - 2))
        dev = (self._device_toks if self._device_toks is not None
               else host_toks)
        return self._select_fn(mask, dev, host_toks)

    def step(self) -> bool:
        """One engine iteration.  Returns False when idle (nothing
        active, nothing admissible, nothing in flight).

        Pipelined (the default, greedy traffic): dispatch step *t* to
        the device first, *then* block on step *t-1*'s tokens — the host
        fetch of one step overlaps the device compute of the next.
        Synchronous (temperature traffic / pipeline=False): dispatch and
        retire the same step, the pre-pipeline behavior."""
        with jax.profiler.StepTraceAnnotation("engine.step",
                                              step_num=self.steps):
            return self._step()

    def _step(self) -> bool:
        if not self._pipelined and self._inflight is not None:
            self._retire(self._inflight)    # mode flipped: flush first
        t0 = self.clock()
        with _Span(self, "admit", "admit_s"):
            self._admit()
        with _Span(self, "telemetry"):
            self.queue_depth_samples.append(len(self.queue))
            self.occupancy_samples.append(self.active_slots / self.n_slots)
        self.telemetry_s += self.clock() - t0
        if not any(s is not None and not s.draining for s in self.slots):
            if self._inflight is not None:
                self._retire(self._inflight)
                return True
            return False
        with _Span(self, "plan", "plan_s"):
            if self._phase_tables["prefill"] is not None:
                self._select_phase_table()
            if self._step_fn is None:
                self._step_fn = self.core.batch_step_for(
                    self._phase_tables[self._phase])
        prev = self._inflight
        with _Span(self, "dispatch", "dispatch_s"):
            self._inflight = self._dispatch()
        if prev is not None:
            self._retire(prev, keep_inflight=True)
        if not self._pipelined:
            self._retire(self._inflight)
        return True

    def _dispatch(self) -> _InFlight:
        """Enqueue one decode step on the device and account for it.

        Token feed is device-resident: a lane whose previous token is
        still in flight takes it from the prior step's on-device greedy
        array (no host round-trip); prompt lanes and synchronous-mode
        lanes take host tokens.  All per-slot bookkeeping (pos / fed /
        generated counts, max-token draining) happens here, at dispatch;
        `_retire` only attributes the finished tokens to requests."""
        host_toks = self._token_batch()
        pos = np.array([0 if s is None else s.pos for s in self.slots],
                       np.int32)
        active = np.array([s is not None and not s.draining
                           for s in self.slots], bool)
        use_dev = np.array([s is not None and s.dev_feed
                            and not s.prefilling for s in self.slots],
                           bool)
        tokens = self._mix_tokens(host_toks, use_dev)
        # the step zeroes these lanes' SSM state before their first token
        self.fresh_lanes += int(np.count_nonzero(active & (pos == 0)))
        probe = None
        if self.donation_ok is None and self.core.donate:
            probe = next((leaf for leaf in jax.tree.leaves(self.cache)
                          if hasattr(leaf, "is_deleted")), None)
        logits, self.cache = self._step_fn(
            self.core.params, self.cache, tokens, pos, active,
            self.block_tables)
        if probe is not None:
            # the jitted step donates its cache argument; if XLA
            # accepted the donation the input buffer is dead the moment
            # the call is dispatched (pools update in place, no copy)
            self.donation_ok = bool(probe.is_deleted())
        if self._greedy_fn is None:
            cfg = self.cfg

            def greedy_tokens(logits):
                return sample_token(cfg, logits, 0.0, None)

            self._greedy_fn = jax.jit(greedy_tokens)
        greedy = self._greedy_fn(logits)
        self._device_toks = greedy
        self.steps += 1
        recs = []
        for i, st in enumerate(self.slots):
            if st is None or st.draining:
                continue
            fed_prompt = st.prefilling
            st.pos += 1
            if fed_prompt:
                st.n_fed += 1
                if st.prefilling:
                    st.dev_feed = False
                    continue        # mid-prompt: sampled token discarded
            st.n_gen += 1
            st.dev_feed = True
            final = st.n_gen >= st.req.max_new_tokens
            if final:
                # final token: stop dispatching this lane now (the KV
                # horizon is exactly spent); the slot is evicted when
                # this step retires
                st.draining = True
            recs.append((i, st, st.n_gen == 1, final))
        return _InFlight(logits, greedy, recs)

    def _retire(self, inf: _InFlight, keep_inflight: bool = False) -> None:
        """Block on one dispatched step's tokens and attribute them:
        append to requests, stamp TTFT, record first-logits (one batched
        transfer for exactly the lanes that produced their first token),
        and evict EOS / max-token slots."""
        with _Span(self, "retire"):
            self._retire_step(inf, keep_inflight)

    def _retire_step(self, inf: _InFlight, keep_inflight: bool) -> None:
        if not keep_inflight:
            self._inflight = None
        elif self._inflight is inf:
            self._inflight = None
        t0 = self.clock()
        greedy = np.asarray(inf.greedy)     # blocks until the step ran
        first_rows = {}
        if self.record_logits:
            idxs = [i for i, st, first, _ in inf.recs
                    if first and st.req.state != "done"]
            if idxs:
                rows = np.asarray(
                    jax.device_get(inf.logits[np.array(idxs), -1]),
                    np.float32)
                first_rows = dict(zip(idxs, rows))
        self.host_fetch_s += self.clock() - t0
        now = self._now()
        for i, st, first, final in inf.recs:
            req = st.req
            if req.state == "done":
                continue    # evicted at an earlier retire (EOS lag):
                            # this lane's speculative token is discarded
            tok = self._sample_slot(i, st, inf.logits, greedy)
            st.last_tok = tok
            req.tokens.append(tok)
            if first:
                req.t_first = now
                if i in first_rows:
                    req.first_logits = first_rows[i]
            hit_eos = (req.eos_id is not None
                       and self.cfg.family != "audio"
                       and int(tok) == req.eos_id)
            if hit_eos or final:
                self._evict(i, "eos" if hit_eos else "max_tokens", now)
        if not self._pipelined:
            self._device_toks = None    # sync mode: host tokens only

    def _sample_slot(self, i: int, st: _Slot, logits, greedy):
        """Next token for slot i: batchwide greedy argmax unless the
        request asked for temperature sampling (then a per-slot
        categorical draw from the engine's PRNG stream — synchronous
        mode only, see `submit`)."""
        if st.req.temperature <= 0.0:
            return greedy[i, 0]
        self._key, sub = jax.random.split(self._key)
        row = np.asarray(jax.device_get(logits[i, -1]),
                         np.float32) / st.req.temperature
        tok = jax.random.categorical(sub, row, axis=-1)
        return np.asarray(jax.device_get(tok), np.int32)

    def _evict(self, i: int, reason: str, now: float) -> None:
        st = self.slots[i]
        self.allocator.free(st.blocks)
        self.slots[i] = None
        self.evictions += 1
        st.req.state = "done"
        st.req.done_reason = reason
        st.req.t_done = now
        self.completed.append(st.req)

    # --- driving loops ----------------------------------------------------

    def run(self, requests: list[Request],
            arrival_times: list[float] | None = None,
            timeout_s: float = 300.0) -> dict:
        """Drive an open-loop arrival process to completion.

        `arrival_times[i]` is request i's arrival offset (seconds from
        run start) on the engine clock; None submits everything up
        front.  Returns `telemetry()`."""
        self._t0 = None
        t_start = self._now()           # pins the epoch
        target = len(self.completed) + len(requests)
        pending = sorted(zip(arrival_times or [0.0] * len(requests),
                             requests), key=lambda p: p[0])
        while len(self.completed) < target:
            now = self._now()
            if now - t_start > timeout_s:
                raise RuntimeError(
                    f"engine run exceeded {timeout_s}s with "
                    f"{len(pending)} arrivals pending")
            while pending and pending[0][0] <= now:
                self.submit(pending.pop(0)[1])
            if not self.step() and pending:
                # idle until the next arrival is due (open-loop clock)
                time.sleep(min(0.001, max(0.0, pending[0][0]
                                          - self._now())))
        return self.telemetry()

    def drain(self, timeout_s: float = 300.0) -> None:
        """Step until queue + slots are empty."""
        t0 = self._now()
        while self.step():
            if self._now() - t0 > timeout_s:
                raise RuntimeError(f"drain exceeded {timeout_s}s")

    # --- telemetry --------------------------------------------------------

    @property
    def decode_executables(self) -> int | None:
        """Compiled program count of the masked batch step — the
        continuous-batching no-retrace gate (expects exactly 1)."""
        return self.core.batch_decode_executables

    def telemetry(self) -> dict:
        """Per-request + engine-aggregate serving telemetry."""
        reqs = []
        for r in self.completed:
            # a request can complete without ever generating a token
            # (t_first is None — e.g. evicted before its first decode);
            # its latency fields are None and it is excluded from the
            # TTFT percentiles below rather than crashing them
            decode_s = ((r.t_done - r.t_first)
                        if r.t_first is not None and len(r.tokens) > 1
                        else None)
            reqs.append({
                "rid": r.rid,
                "prompt_len": r.prompt_len,
                "new_tokens": len(r.tokens),
                "done_reason": r.done_reason,
                "queue_wait_s": (r.t_admit - r.t_submit
                                 if r.t_admit is not None else None),
                "ttft_s": (r.t_first - r.t_submit
                           if r.t_first is not None else None),
                "decode_tokens_per_s": (
                    (len(r.tokens) - 1) / decode_s
                    if decode_s and decode_s > 0 else None),
            })
        ttfts = [r["ttft_s"] for r in reqs if r["ttft_s"] is not None]
        total_tokens = sum(r["new_tokens"] for r in reqs)
        t_done = [r.t_done for r in self.completed]
        makespan = max(t_done) if t_done else 0.0
        dts = [r["decode_tokens_per_s"] for r in reqs
               if r["decode_tokens_per_s"]]
        agg = {
            "completed": len(self.completed),
            "evictions": self.evictions,
            "eos_evictions": sum(r["done_reason"] == "eos" for r in reqs),
            "steps": self.steps,
            "total_new_tokens": total_tokens,
            "engine_tokens_per_s": (total_tokens / makespan
                                    if makespan > 0 else None),
            "request_tokens_per_s_mean": (float(np.mean(dts))
                                          if dts else None),
            "ttft_mean_s": float(np.mean(ttfts)) if ttfts else None,
            "ttft_p50_s": float(np.percentile(ttfts, 50)) if ttfts
            else None,
            "ttft_p95_s": float(np.percentile(ttfts, 95)) if ttfts
            else None,
            "queue_depth_mean": (float(np.mean(self.queue_depth_samples))
                                 if self.queue_depth_samples else 0.0),
            "queue_depth_max": (int(max(self.queue_depth_samples))
                                if self.queue_depth_samples else 0),
            "slot_occupancy_mean": (float(np.mean(self.occupancy_samples))
                                    if self.occupancy_samples else 0.0),
            "n_slots": self.n_slots,
            "kv_blocks": {"total": self.allocator.n_blocks,
                          "block_size": self.block_size,
                          "peak_in_use": self.allocator.peak_in_use},
            "decode_executables": self.decode_executables,
            "kv_donation_ok": self.donation_ok,
            "phase_gating": {
                "enabled": self._phase_tables["prefill"] is not None,
                "phase_switches": self.phase_switches,
                "phase_steps": dict(self.phase_steps),
                # programs compiled per phase plan: 1 each when nothing
                # retraced (the phases share one when their plans agree)
                "executables": {
                    ph: self.core.plan_executables(t)
                    for ph, t in self._phase_tables.items()
                    if t is not None},
            },
            "decode_step_breakdown": self._step_breakdown(),
        }
        return {"requests": reqs, "aggregate": agg}

    def _step_breakdown(self) -> dict:
        """Where the per-step host budget goes: admission, plan
        selection (the phase-table choice and the fetch of its step from
        the core), device dispatch (token select + step call +
        bookkeeping), blocking host fetches (tokens / first-logits at
        retire), and telemetry, which counts admission and the samples
        (telemetry_s >= admit_s).  Pipelined engines overlap the fetch
        of step t with the compute of step t+1, so fetch time here is
        host *blocked* time, not device time.  `fresh_lanes` counts the
        lane-steps the step started from a zero SSM state: one per
        admission."""
        n = max(1, self.steps)
        out = {"steps": self.steps, "pipelined": self._pipelined,
               "admissions": self.admissions,
               "fresh_lanes": self.fresh_lanes}
        for name in ("admit", "plan", "dispatch", "host_fetch",
                     "telemetry"):
            secs = getattr(self, f"{name}_s")
            out[f"{name}_s"] = round(secs, 6)
            out[f"{name}_ms_per_step"] = round(1e3 * secs / n, 4)
        return out


class _Span:
    """One engine phase: a `jax.profiler.TraceAnnotation`
    "engine.<phase>" on the profiler's clock, and, when `counter` names
    one, the phase's seconds on the engine clock added to that engine
    counter.  The clock is read just inside the span's edges, so the
    counter and the span cover the same work."""

    __slots__ = ("engine", "counter", "ann", "t0")

    def __init__(self, engine, phase: str, counter: str | None = None):
        self.engine, self.counter = engine, counter
        self.ann = jax.profiler.TraceAnnotation(f"engine.{phase}")

    def __enter__(self):
        self.ann.__enter__()
        if self.counter is not None:
            self.t0 = self.engine.clock()

    def __exit__(self, *exc):
        e = self.engine
        if self.counter is not None:
            setattr(e, self.counter,
                    getattr(e, self.counter) + e.clock() - self.t0)
        self.ann.__exit__(*exc)


# --- synthetic open-loop traffic ------------------------------------------


def synthetic_requests(cfg, n: int, seed: int = 0,
                       prompt_len: tuple[int, int] = (4, 12),
                       new_tokens: tuple[int, int] = (4, 16),
                       temperature: float = 0.0) -> list[Request]:
    """Seeded ragged request set (uniform prompt/output length ranges,
    inclusive) — the reproducible workload behind `launch.serve
    --requests` and the serving tests."""
    rng = np.random.RandomState(seed)
    reqs = []
    for i in range(n):
        p = int(rng.randint(prompt_len[0], prompt_len[1] + 1))
        m = int(rng.randint(new_tokens[0], new_tokens[1] + 1))
        shape = ((p, cfg.audio.n_codebooks) if cfg.family == "audio"
                 else (p,))
        prompt = rng.randint(0, cfg.vocab, size=shape).astype(np.int32)
        reqs.append(Request(rid=i, prompt=prompt, max_new_tokens=m,
                            temperature=temperature))
    return reqs


def poisson_arrivals(n: int, rate: float, seed: int = 0) -> list[float]:
    """Open-loop Poisson arrival offsets (seconds): exponential
    inter-arrivals at `rate` req/s.  rate <= 0 means all-at-once."""
    if rate <= 0:
        return [0.0] * n
    rng = np.random.RandomState(seed)
    return list(np.cumsum(rng.exponential(1.0 / rate, size=n)))
