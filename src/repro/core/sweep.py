"""Batched What/When/Where sweep engine (the planner's fast path).

`planner.decide` answers the paper's three questions one scalar cost-model
call at a time: every GEMM x 12 system configs x ~3 candidate mappings x 6
loop orders, plus a ~1300-point tensor-core baseline search, all in
Python.  This module flattens the whole workload — every GEMM, every
config, every candidate mapping — into two device batches (CiM rows and
baseline tile rows) and scores each under ONE `jax.jit` call through
`vectorized.evaluate_flat` / `evaluate_baseline_flat`.  CiMLoop-style
batched analytical evaluation is what makes full design-space sweeps
tractable; here it makes full-workload planning 10x+ faster than the
scalar path (benchmarks/sweep_bench.py tracks the ratio).

Results are memoized in an LRU cache keyed by (GEMM shape, system config,
order_mode), so repeated decode-shape queries — the serving engine asks
about the same handful of GEMMs for every session — are answered without
touching the device at all.  `cache_info()` exposes hit/miss telemetry.
The cache (and the compiled-kernel registry) is lock-protected: concurrent
`ServeSession.kernel_plan` builds may hammer one shared engine from many
threads.

Both order modes run fully batched: "exact" keeps the in-kernel min over
all 6 DRAM orders, "greedy" keeps each row's smallest-factor-outermost
order, also selected in-kernel (vectorized.evaluate_flat) — there is no
scalar fallback on any planner path.

Two CiM row kernels score those batches: the default XLA-fused path
(vectorized.evaluate_flat) and backend="pallas", a fused hand-written
kernel (repro.kernels.sweep_eval) consuming the same backend-shared cost
spec.  Pallas results live in their own result-cache keyspace, so parity
suites exercise the kernel rather than the LRU (`cache_info()` carries
a per-backend hit/miss breakdown).  An accelerator that cannot compile
the Pallas kernel raises (kernels.sweep_eval.pallas_status); it is
never silently replaced by the XLA kernel.

Multi-device and multi-host scaling: an engine given a 1-D row mesh
(launch.mesh.row_mesh) shards every flattened row batch across the mesh
devices with `shard_map` — each row is independent, so
`exhaustive_best`-scale grids (tens of thousands of rows per workload)
split evenly over the row axis.  A mesh spanning several
`jax.distributed` processes (launch.distributed.global_row_mesh) runs the
same kernels pod-scale: every host enumerates the same grid SPMD,
materializes on device only the row shard its local devices own
(launch.distributed.host_local_to_global), and all-gathers only the
per-row output columns (_OUT_KEYS) for the replicated argmin/verdict
reduction — intermediate cost fields never cross hosts.  The default
engine auto-shards over all devices of an accelerator platform (the
global list: on a pod that is already every host's devices) and keeps the
plain single-device path when only one device exists (or on CPU, where
forced host-device counts are a debugging fiction, not parallel
hardware).

Streaming chunk enumerator: `SweepEngine(chunk_rows=N)` bounds device
memory per evaluation — the flattened grid is generated group by group
(a group = one query's candidate rows) and folded through the jitted
kernel in mesh-aligned tiles of at most N rows, with a cross-chunk
running reduction per group, so workload grids larger than one host's
memory stream through the engine.  Per-chunk accounting lands in
`cache_info()["chunks"]` (and, on a multi-host mesh,
`cache_info()["distributed"]` carries the process topology + row shard
balance).

Verdict parity with the scalar path is enforced by tests/test_sweep.py;
multi-process parity against the golden verdict fingerprint by
tests/test_distributed_sweep.py.
"""
from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Iterable, Sequence

import jax
import numpy as np

from .baseline import evaluate_baseline
from .cost_model import Metrics, evaluate, metrics_from_row
from .gemm import GEMM
from .loopnest import check_order_mode
from .mapping import candidate_mappings
from .memory import CiMSystemConfig
from .vectorized import (BASE_TILE_FIELDS, MAP_FIELDS, config_row,
                         enumerate_baseline_space, evaluate_baseline_flat,
                         evaluate_flat, precision_row)

_OUT_KEYS = ("energy_pj", "time_ns", "compute_ns", "dram_ns", "smem_ns",
             "utilization", "dram_bytes", "smem_bytes", "valid")

# The result-cache/counter buckets a CiM query can resolve to, plus the
# baseline keyspace — cache_info()'s per-backend breakdown reports these.
CIM_BACKENDS = ("vectorized", "pallas")

# --- compiled-kernel registry ------------------------------------------------
# Every jitted sweep entry point — (kind, order_mode, mesh, kernel) —
# lives here, so jit_cache_clear() can drop *all* compiled executables: a
# "cold-jit" benchmark stays honest no matter which greedy/sharded/pallas
# variants earlier code in the process already traced.
_KERNEL_LOCK = threading.Lock()
_KERNELS: dict = {}


def _jit_kernel(kind: str, order_mode: str = "exact", mesh=None,
                kernel: str = "xla"):
    """Jitted evaluator for `kind` ("cim" | "base"), memoized per
    (order_mode, mesh, kernel).  kernel="xla" scores CiM rows through
    vectorized.evaluate_flat (XLA fusion of the 6-order unroll);
    kernel="pallas" through the fused hand-written kernel
    (repro.kernels.sweep_eval — same backend-shared cost spec, one
    pallas_call).  mesh=None is the single-device fast path; a 1-D row
    mesh wraps either kernel in shard_map over its row axis (rows are
    independent, so sharding is a pure data split — results are bitwise
    identical to the unsharded kernel)."""
    key = (kind, order_mode, mesh, kernel)
    with _KERNEL_LOCK:
        fn = _KERNELS.get(key)
        if fn is None:
            if kind == "cim" and kernel == "pallas":
                from ..kernels.sweep_eval import sweep_eval

                def base(batch, _om=order_mode):
                    return sweep_eval(batch, order_mode=_om)
            elif kind == "cim":
                def base(batch, _om=order_mode):
                    return evaluate_flat(batch, order_mode=_om)
            else:
                base = evaluate_baseline_flat
            if mesh is not None:
                from jax.sharding import PartitionSpec
                axis = mesh.axis_names[0]
                # pallas_call has no varying-manual-axes rule; rows are
                # a pure data split (no cross-shard collectives), so
                # skipping the check is sound
                base = jax.shard_map(base, mesh=mesh,
                                     in_specs=(PartitionSpec(axis),),
                                     out_specs=PartitionSpec(axis),
                                     check_vma=(kernel != "pallas"))
            fn = jax.jit(base)
            _KERNELS[key] = fn
    return fn


def _auto_mesh():
    """Row mesh over all devices when they are real parallel hardware;
    None (single-device path) for one device or CPU hosts
    (XLA_FLAGS-forced CPU device counts emulate topology, they don't add
    FLOPs — sharding tiny analytical batches over them only adds
    dispatch overhead).  jax.devices() is the GLOBAL list: in a
    jax.distributed multi-process job on accelerators the auto mesh
    already spans every host, and evaluation takes the multi-host path
    (global sharded inputs + output all-gather)."""
    devices = jax.devices()
    if len(devices) > 1 and devices[0].platform != "cpu":
        from ..launch.mesh import row_mesh
        return row_mesh(devices)
    return None


def _gemm_key(g: GEMM):
    return (g.M, g.N, g.K, g.bits, g.fp)


def _cfg_key(cfg: CiMSystemConfig):
    p = cfg.prim
    return (p.name, p.Rp, p.Cp, p.Rh, p.Ch, p.capacity_bytes, p.latency_ns,
            p.mac_energy_pj, cfg.cim_level, cfg.resolved_n_prims(),
            cfg.serialize_primitives, cfg.kn_balance_threshold)


def _pad_len(n: int, shards: int = 1) -> int:
    """Next power of two (bounds jit retraces to O(log B)), rounded up to
    a multiple of the shard count so the row axis splits evenly."""
    p = 1
    while p < n:
        p *= 2
    if shards > 1:
        p = -(-p // shards) * shards
    return p


def _mesh_is_multihost(mesh) -> bool:
    """Does `mesh` contain devices of other jax.distributed processes?
    (Local duplicate of launch.distributed.is_multihost so the hot path
    needs no launch import on the common single-host mesh.)"""
    if mesh is None:
        return False
    pi = jax.process_index()
    return any(d.process_index != pi for d in mesh.devices.flat)


def _run_padded(fn, batch: dict, n: int, shards: int = 1,
                mesh=None) -> dict:
    """jit-run a flat batch padded (by repeating row 0) to a pow2 length
    (multiple of `shards` when the kernel is row-sharded).

    On a multi-host mesh each process feeds the kernel global arrays of
    which it materializes only its addressable row shard, and the per-row
    output columns — only those — are all-gathered back so every host
    can run the identical reduction (launch.distributed)."""
    m = _pad_len(max(1, n), shards)
    if m != n:
        batch = {k: np.concatenate(
            [v, np.broadcast_to(v[:1], (m - n,) + v.shape[1:])])
            for k, v in batch.items()}
    arrs = {k: np.asarray(v, np.float32) for k, v in batch.items()}
    if _mesh_is_multihost(mesh):
        from ..launch import distributed as dist
        out = fn(dist.host_local_to_global(arrs, mesh))
        out = dist.gather_rows({k: out[k] for k in _OUT_KEYS})
    else:
        out = fn(arrs)
    return {k: np.asarray(out[k])[:n] for k in _OUT_KEYS}


def _cat_cols(parts: list[dict]) -> dict:
    """Concatenate columnar row-group slices into one flat batch."""
    if len(parts) == 1:
        return dict(parts[0])
    return {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}


def _iter_chunks(groups, chunk_rows: int | None):
    """The streaming enumerator: walk `groups` — an iterable of
    (gid, cols) where cols is a dict of equal-length (n,) numpy columns —
    and yield evaluation tiles of at most `chunk_rows` rows.

    Yields (batch, segments): `batch` is the concatenated columns,
    `segments` is [(gid, group_offset, lo, hi)] mapping each slice of the
    tile back to its group (a group larger than a tile spans several
    tiles; the caller folds segments through a running per-group
    reduction).  chunk_rows=None degenerates to one tile holding
    everything — the classic whole-batch path.  Groups are consumed
    lazily, so grids larger than host memory stream through as long as
    each *group* fits.
    """
    parts: list[dict] = []
    segs: list[tuple] = []
    filled = 0
    for gid, cols in groups:
        n = len(next(iter(cols.values())))
        off = 0
        while off < n:
            take = (n - off if chunk_rows is None
                    else min(n - off, chunk_rows - filled))
            parts.append({k: v[off:off + take] for k, v in cols.items()})
            segs.append((gid, off, filled, filled + take))
            filled += take
            off += take
            if chunk_rows is not None and filled >= chunk_rows:
                yield _cat_cols(parts), segs
                parts, segs, filled = [], [], 0
    if filled:
        yield _cat_cols(parts), segs


class SweepEngine:
    """Whole-workload batched planner evaluation with an LRU result cache.

    cim_metrics / baseline_metrics return the same Metrics the scalar
    cost model produces (within float32 tolerance), but evaluate every
    uncached (GEMM, config) pair of a query in one fused device call.

    mesh: "auto" (default) shards row batches over all accelerator
    devices when more than one exists (single-device fast path
    otherwise); None forces the unsharded path; an explicit 1-D mesh
    (launch.mesh.row_mesh) is always honored — including a 1-device mesh,
    which exercises the shard_map path for parity testing, and a
    multi-host mesh (launch.distributed.global_row_mesh), which takes the
    global-array + output-all-gather path.

    chunk_rows: None (default) evaluates each query batch in one device
    call; an integer bounds every call to at most that many rows — the
    flattened grid streams through the kernel in mesh-aligned tiles with
    a cross-chunk running reduction per query, so grids larger than one
    host's device memory still evaluate (and every chunk lands in the
    LRU/telemetry accounting as it completes).  Results are bitwise
    identical either way: rows are evaluated elementwise, and the
    reductions preserve first-index tie-breaks across tiles.

    All cache mutations (and the hit/miss counters) are serialized by a
    per-engine lock: the process-wide default engine is shared by every
    ServeSession.kernel_plan build, which may run on concurrent threads.
    """

    def __init__(self, cache_size: int = 16384, mesh="auto",
                 chunk_rows: int | None = None):
        if chunk_rows is not None and chunk_rows < 1:
            raise ValueError(f"chunk_rows must be >= 1 or None, "
                             f"got {chunk_rows}")
        self.cache_size = cache_size
        self.chunk_rows = chunk_rows
        self._mesh = mesh
        self._cache: OrderedDict = OrderedDict()
        self._lock = threading.RLock()
        self._local = threading.local()   # per-thread hit/miss counters
        self.hits = 0
        self.misses = 0
        # per-backend keyspace breakdown ("vectorized" / "pallas" /
        # "baseline")
        self._backend_counts: dict = {}
        # streaming-enumerator accounting (cache_info()["chunks"])
        self._chunks_evaluated = 0
        self._rows_evaluated = 0
        self._rows_padded = 0

    @property
    def mesh(self):
        """The resolved row mesh (lazy: "auto" queries jax.devices() on
        first evaluation, not at construction/import time)."""
        if self._mesh == "auto":
            self._mesh = _auto_mesh()
        return self._mesh

    @property
    def n_shards(self) -> int:
        return self.mesh.size if self.mesh is not None else 1

    # --- cache plumbing ---------------------------------------------------
    def _get(self, key, bucket: str):
        with self._lock:
            counts = self._backend_counts.setdefault(
                bucket, {"hits": 0, "misses": 0})
            if key in self._cache:
                self._cache.move_to_end(key)
                self.hits += 1
                counts["hits"] += 1
                self._local.hits = getattr(self._local, "hits", 0) + 1
                return self._cache[key]
            self.misses += 1
            counts["misses"] += 1
            self._local.misses = getattr(self._local, "misses", 0) + 1
            return None

    def thread_cache_counts(self) -> tuple[int, int]:
        """(hits, misses) accrued by the CALLING thread only — monotonic,
        unaffected by cache_clear.  Lets telemetry attribute a plan
        build's lookups to that build without locking out concurrent
        builds or counting their traffic (measured_cache_delta)."""
        tl = self._local
        return getattr(tl, "hits", 0), getattr(tl, "misses", 0)

    def _put(self, key, value):
        with self._lock:
            self._cache[key] = value
            self._cache.move_to_end(key)
            while len(self._cache) > self.cache_size:
                self._cache.popitem(last=False)

    def cache_info(self) -> dict:
        """Size + hit/miss totals, the per-backend breakdown (which
        keyspace — vectorized / pallas / baseline — each lookup resolved
        to), the streaming-enumerator accounting under "chunks"
        (tiles evaluated / real vs padding rows), and — on a multi-host
        mesh — a "distributed" block with the process topology and the
        cumulative per-process row shard balance.  Serve/dryrun telemetry
        embed this dict verbatim (launch.report renders it)."""
        with self._lock:
            info = {"size": len(self._cache), "max_size": self.cache_size,
                    "hits": self.hits, "misses": self.misses,
                    "backends": {b: dict(c) for b, c in
                                 self._backend_counts.items()},
                    "chunks": {"chunk_rows": self.chunk_rows,
                               "evaluated": self._chunks_evaluated,
                               "rows": self._rows_evaluated,
                               "padded_rows": self._rows_padded},
                    "distributed": None}
        if _mesh_is_multihost(self.mesh):
            from ..launch import distributed as dist
            total = info["chunks"]["rows"] + info["chunks"]["padded_rows"]
            info["distributed"] = {
                **dist.distributed_info(),
                "mesh_devices": self.mesh.size,
                "shard_balance": dist.shard_balance(total, self.mesh),
            }
        return info

    def cache_clear(self) -> None:
        with self._lock:
            self._cache.clear()
            self.hits = self.misses = 0
            self._backend_counts = {}
            self._chunks_evaluated = 0
            self._rows_evaluated = 0
            self._rows_padded = 0

    # --- streaming evaluation --------------------------------------------
    def _stream_batches(self, fn, groups, update) -> None:
        """Fold a lazily-enumerated grid through the jitted kernel.

        groups: iterable of (gid, cols) — see `_iter_chunks`.  Every tile
        is padded/mesh-aligned and evaluated in ONE device call
        (`_run_padded`, which takes the global-array path on a multi-host
        mesh); `update(gid, group_offset, out, lo, hi)` folds each tile
        segment into the caller's running per-group reduction.  Per-tile
        accounting lands in the "chunks" telemetry.
        """
        shards = self.n_shards
        mesh = self.mesh
        for cols, segs in _iter_chunks(groups, self.chunk_rows):
            n = len(next(iter(cols.values())))
            out = _run_padded(fn, cols, n, shards, mesh)
            with self._lock:
                self._chunks_evaluated += 1
                self._rows_evaluated += n
                self._rows_padded += _pad_len(max(1, n), shards) - n
            for gid, off, lo, hi in segs:
                update(gid, off, out, lo, hi)

    # --- CiM options ------------------------------------------------------
    def _resolve_cim_backend(self, backend: str) -> tuple[str, str]:
        """(kernel, bucket) for a CiM query: `kernel` in {"xla","pallas"}
        picks the jitted entry point, `bucket` names the result-cache
        keyspace (and per-backend counters).  A "pallas" request first
        runs the platform probe, which raises where an accelerator
        cannot compile the kernel."""
        if backend not in CIM_BACKENDS:
            raise ValueError(f"unknown sweep backend {backend!r}; "
                             f"expected one of {CIM_BACKENDS}")
        if backend == "pallas":
            from ..kernels.sweep_eval import pallas_status
            pallas_status()
            return "pallas", "pallas"
        return "xla", "vectorized"

    def cim_metrics(self, pairs: Sequence[tuple[GEMM, CiMSystemConfig]],
                    order_mode: str = "exact",
                    backend: str = "vectorized") -> list[Metrics]:
        """Metrics for each (GEMM, config) pair: the min-energy candidate
        mapping, scored on-device (== cost_model.evaluate).  Both order
        modes run in-kernel — "exact" takes the min over all 6 DRAM
        orders, "greedy" selects each row's smallest-factor-outermost
        order (no scalar fallback).  backend="pallas" routes the batch
        through the fused Pallas kernel (distinct result-cache keyspace,
        so backend parity tests measure the kernel, not the LRU)."""
        check_order_mode(order_mode)
        kernel, bucket = self._resolve_cim_backend(backend)
        keys = [("cim", bucket, _gemm_key(g), _cfg_key(c), order_mode)
                for g, c in pairs]
        results: dict = {}
        todo: OrderedDict = OrderedDict()      # key -> (gemm, cfg)
        for key, (g, c) in zip(keys, pairs):
            hit = self._get(key, bucket)
            if hit is not None:
                results[key] = hit
            else:
                todo.setdefault(key, (g, c))

        if todo:
            fn = _jit_kernel("cim", order_mode, self.mesh, kernel)
            best: dict = {}          # key -> [energy, out_row, mapping]
            # candidate lists of groups still in flight (some rows not
            # yet reduced) — dropped as soon as a group completes, so
            # host memory holds O(chunk) mappings, not the whole grid
            live: dict = {}          # key -> [maps, rows_remaining]

            def groups():
                # the streaming enumerator: candidate mappings are
                # generated per query as tiles fill, never all at once
                for key, (g, c) in todo.items():
                    maps = candidate_mappings(g, c, order_mode)
                    live[key] = [maps, len(maps)]
                    crow = {"M": g.M, "N": g.N, "K": g.K,
                            **precision_row(g), **config_row(c)}
                    cols = {f: np.full(len(maps), float(v), np.float32)
                            for f, v in crow.items()}
                    for f in MAP_FIELDS:
                        cols[f] = np.asarray(
                            [getattr(mp, f) for mp in maps], np.float32)
                    yield key, cols

            def update(key, off, out, lo, hi):
                # min-energy valid row; strict < keeps the first index on
                # ties, within a tile (np.argmin) and across tiles alike
                entry = live[key]
                e = np.where(out["valid"][lo:hi],
                             out["energy_pj"][lo:hi], np.inf)
                i = int(np.argmin(e))
                st = best.get(key)
                if np.isfinite(e[i]) and (st is None or e[i] < st[0]):
                    best[key] = [e[i], {k: out[k][lo + i]
                                        for k in _OUT_KEYS},
                                 entry[0][off + i]]
                entry[1] -= hi - lo
                if entry[1] == 0:              # group fully reduced
                    del live[key]

            self._stream_batches(fn, groups(), update)
            for key, (g, c) in todo.items():
                st = best.get(key)
                if st is None:                 # should not happen: mappings
                    met = evaluate(g, c, order_mode)   # are pre-validated
                else:
                    met = metrics_from_row(g.ops, st[1], mapping=st[2])
                self._put(key, met)
                results[key] = met
        return [results[k] for k in keys]

    # --- tensor-core baseline --------------------------------------------
    def baseline_metrics(self, gemms: Sequence[GEMM]) -> list[Metrics]:
        """Baseline Metrics per GEMM: the full tile grid scored on-device,
        lexicographic (time, energy) winner (== evaluate_baseline)."""
        keys = [("base", _gemm_key(g)) for g in gemms]
        results: dict = {}
        todo: OrderedDict = OrderedDict()
        for key, g in zip(keys, gemms):
            hit = self._get(key, "baseline")
            if hit is not None:
                results[key] = hit
            else:
                todo.setdefault(key, g)

        if todo:
            fn = _jit_kernel("base", mesh=self.mesh)
            names = BASE_TILE_FIELDS + ("M", "N", "K")
            best: dict = {}          # key -> [time, energy, out_row]

            def groups():
                # one group per GEMM's full tile grid (the ~1300-point
                # search space), enumerated lazily as tiles fill
                for key, g in todo.items():
                    space = enumerate_baseline_space(g)
                    yield key, {f: np.asarray(space[f], np.float32)
                                for f in names}

            def update(key, off, out, lo, hi):
                # lexicographic (time, energy) among valid rows, first
                # index on ties — the scalar search's iteration-order
                # tie-break.  Strict-improvement replacement preserves it
                # across tiles (earlier tiles hold earlier rows).
                ok = out["valid"][lo:hi]
                t = np.where(ok, out["time_ns"][lo:hi], np.inf)
                tmin = t.min()
                if not np.isfinite(tmin):
                    return                       # no valid row in segment
                cand = np.where(t == tmin,
                                np.where(ok, out["energy_pj"][lo:hi],
                                         np.inf), np.inf)
                i = int(np.argmin(cand))
                st = best.get(key)
                if (st is None or tmin < st[0]
                        or (tmin == st[0] and cand[i] < st[1])):
                    best[key] = [tmin, cand[i],
                                 {k: out[k][lo + i] for k in _OUT_KEYS}]

            self._stream_batches(fn, groups(), update)
            for key, g in todo.items():
                st = best.get(key)
                if st is None:
                    met = evaluate_baseline(g)
                else:
                    met = metrics_from_row(g.ops, st[2])
                self._put(key, met)
                results[key] = met
        return [results[k] for k in keys]


# Shared default engine: one process-wide cache, so the serving engine,
# benchmarks, and examples all reuse each other's results.
_ENGINE = SweepEngine()


def default_engine() -> SweepEngine:
    return _ENGINE


def cache_info() -> dict:
    return _ENGINE.cache_info()


def cache_clear() -> None:
    _ENGINE.cache_clear()


def jit_cache_clear() -> None:
    """Drop the compiled executables of EVERY jitted sweep kernel — all
    (kind, order_mode, mesh, kernel) entry points in the registry, so
    greedy, sharded and pallas variants go cold too (the LRU *result*
    cache is untouched — use `cache_clear` for that).

    Benchmarks call this before a cold-jit measurement so the number is
    honest even when earlier code in the same process already traced the
    kernels (e.g. `benchmarks/run.py` runs other planner benches first).
    """
    with _KERNEL_LOCK:
        for fn in _KERNELS.values():
            fn.clear_cache()


def jit_kernel_count() -> int:
    """Number of live compiled executables across every registered sweep
    kernel (0 right after jit_cache_clear) — benchmark/test telemetry.

    `_cache_size` is a private jax attribute; if a future jax drops it,
    unknown kernels count as 0 rather than crashing telemetry callers."""
    with _KERNEL_LOCK:
        total = 0
        for fn in _KERNELS.values():
            size = getattr(fn, "_cache_size", None)
            if size is not None:
                total += size()
        return total


def measured_cache_delta(fn):
    """Run `fn()` (a plan build against the default engine) and return
    (result, telemetry): the default engine's hit/miss delta attributed
    to this call, plus the engine-wide totals.

    Shared by ServeSession.kernel_plan and the dry-run decode cells so
    the telemetry schema can't drift between reports.  Attribution uses
    the engine's per-thread counters, so concurrent measured builds
    neither serialize behind each other nor contaminate each other's
    deltas (`fn` must do its engine queries on the calling thread, which
    plan_workload does).
    """
    h0, m0 = _ENGINE.thread_cache_counts()
    result = fn()
    h1, m1 = _ENGINE.thread_cache_counts()
    return result, {
        "plan_hits": h1 - h0,
        "plan_misses": m1 - m0,
        "engine": _ENGINE.cache_info(),
    }


def sweep_evaluate(gemm: GEMM, cfg: CiMSystemConfig,
                   order_mode: str = "exact") -> Metrics:
    """Cached batched equivalent of cost_model.evaluate."""
    return _ENGINE.cim_metrics([(gemm, cfg)], order_mode)[0]


def sweep_evaluate_baseline(gemm: GEMM) -> Metrics:
    """Cached batched equivalent of baseline.evaluate_baseline."""
    return _ENGINE.baseline_metrics([gemm])[0]


def plan_workload_batched(gemms: Iterable[GEMM],
                          configs: dict[str, CiMSystemConfig] | None = None,
                          order_mode: str = "exact",
                          throughput_floor: float = 0.5,
                          engine: SweepEngine | None = None,
                          backend: str = "vectorized"):
    """Batched planner.plan_workload: one device sweep, scalar verdicts.

    Evaluates all GEMMs x all configs x all candidate mappings in one
    fused call per kind (CiM / baseline), then applies exactly the same
    eligibility + "when" rules as planner.decide.  backend selects the
    CiM row kernel ("vectorized" = XLA-fused evaluate_flat, "pallas" =
    the fused hand-written kernel); the tensor-core baseline sweep always
    runs on the XLA kernel — its 36-permutation search is outside the
    Pallas tentpole and shared by both backends, so verdicts can only
    differ through the CiM rows.
    """
    from .planner import make_decision, standard_configs
    engine = engine or _ENGINE
    gemms = list(gemms)
    configs = configs or standard_configs()
    names = list(configs)
    bases = engine.baseline_metrics(gemms)
    pairs = [(g, configs[name]) for g in gemms for name in names]
    mets = engine.cim_metrics(pairs, order_mode, backend)
    decisions = []
    for i, g in enumerate(gemms):
        opts = {name: mets[i * len(names) + j]
                for j, name in enumerate(names)}
        decisions.append(make_decision(g, bases[i], opts, throughput_floor))
    return decisions


def decide_batched(gemm: GEMM,
                   configs: dict[str, CiMSystemConfig] | None = None,
                   order_mode: str = "exact",
                   throughput_floor: float = 0.5,
                   engine: SweepEngine | None = None,
                   backend: str = "vectorized"):
    return plan_workload_batched([gemm], configs, order_mode,
                                 throughput_floor, engine, backend)[0]
