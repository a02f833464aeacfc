"""Property-based differential harness for the planner core.

Pins the three sweep backends to each other — scalar Python cost model,
XLA-vectorized `evaluate_flat`, and the fused Pallas kernel
(`kernels.sweep_eval`) — over hypothesis-generated inputs: GEMM shapes
including degenerate M/N/K = 1 and non-power-of-two dims, every
standard config, and both DRAM order modes.  The batched backends share
one cost spec (vectorized.cim_*) but lower through entirely different
compilation pipelines, so agreement here is evidence about the kernels,
not about shared code paths; the scalar model is the independent
reference implementation.
"""
import functools

import jax
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import GEMM, decide, evaluate, standard_configs
from repro.core.sweep import SweepEngine
from repro.core.vectorized import FLAT_FIELDS, MAP_FIELDS, config_row, \
    evaluate_flat
from repro.kernels.sweep_eval import sweep_eval

CONFIGS = standard_configs()
CONFIG_NAMES = sorted(CONFIGS)

# One engine for the whole module: vectorized and pallas results live in
# separate result-cache keyspaces, so every pallas query really runs the
# Pallas kernel (module-level instead of the conftest fixture: hypothesis
# warns about function-scoped fixtures under @given).
ENGINE = SweepEngine(mesh=None)

# Shape pool: the degenerate GEMV corner (1), awkward primes/non-pow2
# sizes (3, 17, 31, 100, 257, 300), and pow2 paper-scale dims.  The low
# boundary corner is the all-ones GEMM (hypothesis's shrink target).
DIMS = (1, 3, 17, 31, 64, 100, 257, 300, 1024, 4096)
dim = st.sampled_from(DIMS)
gemm_shape = st.tuples(dim, dim, dim)

# the widened What axis: every precision the cost model supports, as
# (bits, fp) pairs.  INT8 first: it is the Table-IV calibration identity
# and the boundary case hypothesis shrinks toward.
PRECISIONS = ((8, False), (4, False), (8, True))
precision = st.sampled_from(PRECISIONS)


@st.composite
def cim_cases(draw):
    """(GEMM, config name, order_mode): one planner cost-model query.

    Draws span the full widened grid: GEMM shape x precision
    (INT8/INT4/FP8) x config (all four Table-IV prototypes — both
    analog and digital kinds — at RF/SMEM-A/SMEM-B) x order mode."""
    m, n, k = draw(gemm_shape)
    bits, fp = draw(precision)
    name = draw(st.sampled_from(CONFIG_NAMES))
    greedy = draw(st.booleans())
    return (GEMM(m, n, k, bits=bits, fp=fp), name,
            "greedy" if greedy else "exact")


@given(case=cim_cases())
@settings(max_examples=16, deadline=None)
def test_metric_parity_scalar_vs_vectorized_vs_pallas(case):
    """Per-(GEMM, config) metrics agree across all three backends: the
    two batched kernels within float32 round-off of each other, both
    within tolerance of the float64 scalar reference."""
    g, name, om = case
    cfg = CONFIGS[name]
    ms = evaluate(g, cfg, om)
    mv = ENGINE.cim_metrics([(g, cfg)], om, backend="vectorized")[0]
    mp = ENGINE.cim_metrics([(g, cfg)], om, backend="pallas")[0]
    assert mp.energy_pj == pytest.approx(mv.energy_pj, rel=1e-5), (g, name)
    assert mp.time_ns == pytest.approx(mv.time_ns, rel=1e-5), (g, name)
    assert mp.dram_bytes == pytest.approx(mv.dram_bytes, rel=1e-5)
    assert mv.energy_pj == pytest.approx(ms.energy_pj, rel=0.02), (g, name)
    assert mv.time_ns == pytest.approx(ms.time_ns, rel=0.02), (g, name)
    assert mp.energy_pj == pytest.approx(ms.energy_pj, rel=0.02), (g, name)


def _tie_ok(name_a, name_b, decision, tol=0.02):
    """Verdicts may differ only on float32 near-ties of the objective."""
    def topsw(name):
        return (decision.baseline.tops_per_w if name == "baseline"
                else decision.options[name].tops_per_w)
    ta, tb = topsw(name_a), topsw(name_b)
    return abs(ta - tb) <= tol * max(ta, tb)


@given(shape=st.tuples(st.sampled_from(DIMS[:8]), st.sampled_from(DIMS[:8]),
                       st.sampled_from(DIMS[:8])),
       prec=precision, greedy=st.booleans())
@settings(max_examples=4, deadline=None)
def test_verdict_parity_three_backends(shape, prec, greedy):
    """Full decide() verdicts (what/when/where over all 12 standard
    configs + baseline) agree across scalar, vectorized and pallas —
    at every precision of the widened What axis."""
    g = GEMM(*shape, bits=prec[0], fp=prec[1])
    om = "greedy" if greedy else "exact"
    ds = decide(g, CONFIGS, order_mode=om, backend="scalar")
    dv = decide(g, CONFIGS, order_mode=om, backend="vectorized")
    dp = decide(g, CONFIGS, order_mode=om, backend="pallas")
    assert dp.use_cim == dv.use_cim == ds.use_cim, (g, om)
    assert (dp.best_energy == dv.best_energy
            or _tie_ok(dp.best_energy, dv.best_energy, ds)), (g, om)
    assert (dv.best_energy == ds.best_energy
            or _tie_ok(dv.best_energy, ds.best_energy, ds)), (g, om)


# --- raw-row differential: XLA kernel vs Pallas kernel ----------------------
# candidate_mappings only emits pre-validated rows, so the engine-level
# tests above never exercise the kernels' invalid-row handling.  Here the
# mapping fields are drawn wide (beyond array bounds, over-capacity,
# over-provisioned primitives), rows mix configs freely, and the two
# kernels must agree bitwise on the full output dict — valid mask, inf
# fills and all.

_N_RAW_ROWS = 16          # fixed row count -> one trace per (mode, kernel)
# jitted once at module scope: a fresh jax.jit per example would recompile
# the kernels 2 x max_examples times
_RAW_FNS = {om: (jax.jit(functools.partial(evaluate_flat, order_mode=om)),
                 jax.jit(functools.partial(sweep_eval, order_mode=om)))
            for om in ("exact", "greedy")}
map_field = st.sampled_from((1, 2, 5, 7, 16, 64, 253, 1024, 4096))
raw_row = st.tuples(dim, dim, dim,                      # M, N, K
                    map_field, map_field,               # k_arr, n_arr
                    map_field, map_field,               # pk, pn
                    map_field, map_field, map_field,    # m1, fk, fn
                    st.sampled_from(CONFIG_NAMES),
                    precision)                          # (bits, fp)


def _raw_batch(rows):
    batch = {f: [] for f in FLAT_FIELDS}
    for row in rows:
        m, n, k = row[0], row[1], row[2]
        bits, fp = row[11]
        vals = dict(zip(MAP_FIELDS, row[3:10]))
        vals.update({"M": m, "N": n, "K": k, "bits": bits, "is_fp": int(fp)},
                    **config_row(CONFIGS[row[10]]))
        for f in FLAT_FIELDS:
            batch[f].append(float(vals[f]))
    return {f: np.asarray(v, np.float32) for f, v in batch.items()}


@given(rows=st.lists(raw_row, min_size=_N_RAW_ROWS, max_size=_N_RAW_ROWS),
       greedy=st.booleans())
@settings(max_examples=10, deadline=None)
def test_raw_rows_xla_vs_pallas_bitwise(rows, greedy):
    om = "greedy" if greedy else "exact"
    batch = _raw_batch(rows)
    fn_x, fn_p = _RAW_FNS[om]
    out_x = fn_x(batch)
    out_p = fn_p(batch)
    assert set(out_p) == set(out_x)
    for key in out_x:
        a, b = np.asarray(out_x[key]), np.asarray(out_p[key])
        assert np.array_equal(a, b, equal_nan=True), (
            key, om, a[:4], b[:4])
    # degenerate/invalid rows must be flagged, not scored: any row whose
    # mapping exceeds the array bounds is invalid in BOTH kernels
    k_over = batch["k_arr"] > batch["k_rows"]
    assert not np.asarray(out_p["valid"])[k_over].any()


@pytest.mark.slow
def test_full_grid_three_backend_parity_exhaustive():
    """The @slow full-grid gate: EVERY (shape, precision, order-mode)
    combination of a representative shape set — degenerate GEMV,
    awkward primes, paper-scale pow2 — decided by all three backends
    over all 12 standard configs + baseline, no sampling.  The fast
    tier draws from this grid; this job walks it exhaustively."""
    shapes = ((1, 1, 1), (1, 4096, 4096), (17, 100, 300),
              (64, 1024, 4096), (300, 257, 31), (1024, 1024, 1024))
    for shape in shapes:
        for bits, fp in PRECISIONS:
            g = GEMM(*shape, bits=bits, fp=fp)
            for om in ("exact", "greedy"):
                ds = decide(g, CONFIGS, order_mode=om, backend="scalar")
                dv = decide(g, CONFIGS, order_mode=om,
                            backend="vectorized")
                dp = decide(g, CONFIGS, order_mode=om, backend="pallas")
                assert dp.use_cim == dv.use_cim == ds.use_cim, (g, om)
                assert (dp.best_energy == dv.best_energy
                        or _tie_ok(dp.best_energy, dv.best_energy, ds)), (
                    g, om)
                assert (dv.best_energy == ds.best_energy
                        or _tie_ok(dv.best_energy, ds.best_energy, ds)), (
                    g, om)


def test_degenerate_all_ones_gemm_all_backends():
    """M=N=K=1 (the boundary corner the strategies shrink to) is valid,
    finite, and identically scored by every backend on every config and
    both order modes."""
    g = GEMM(1, 1, 1)
    for om in ("exact", "greedy"):
        for name in CONFIG_NAMES:
            cfg = CONFIGS[name]
            ms = evaluate(g, cfg, om)
            mv = ENGINE.cim_metrics([(g, cfg)], om, "vectorized")[0]
            mp = ENGINE.cim_metrics([(g, cfg)], om, "pallas")[0]
            assert np.isfinite(ms.energy_pj)
            assert mp.energy_pj == pytest.approx(mv.energy_pj, rel=1e-5)
            assert mv.energy_pj == pytest.approx(ms.energy_pj, rel=0.02), (
                name, om)
