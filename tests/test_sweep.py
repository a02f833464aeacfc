"""Batched sweep engine vs the scalar planner: verdict parity (exact AND
greedy order modes, both fully in-kernel), metric parity (including the
CiM@SMEM and baseline scoring the vectorized model gained), sharded-vs-
unsharded bitwise parity (forced 1-device row mesh), LRU-cache behavior +
thread safety, the one-registry jit cache clear, and the summarize()
eligibility fix."""
import threading

import jax
import numpy as np
import pytest

from repro.core import (DIGITAL_6T, GEMM, CiMSystemConfig, Decision,
                        decide, evaluate, evaluate_baseline, make_decision,
                        plan_workload, standard_configs, summarize)
from repro.core.cost_model import Metrics, metrics_from_row
from repro.core.sweep import SweepEngine

# paper-flavored shape grid: BERT layer, GPT-J decode GEMV, ResNet stem,
# batched decode FFN, squares, and awkward non-pow2 dims
PAPER_GEMMS = [
    GEMM(512, 1024, 1024),      # BERT-Large projection
    GEMM(1, 4096, 4096),        # GPT-J M=1 decode (the "when NOT to CiM")
    GEMM(12544, 64, 147),       # ResNet50 stem conv-as-GEMM
    GEMM(128, 5632, 2048),      # batched decode FFN
    GEMM(4096, 1408, 2048),     # train-shape expert GEMM
    GEMM(256, 256, 256),
    GEMM(17, 100, 300),         # non-pow2 everything
    GEMM(1, 32, 64),            # tiny GEMV
]

CONFIGS = standard_configs()


def _llm_gemms():
    """One assigned arch's full llm_workloads GEMM set (train + decode) —
    the greedy parity suite sweeps these on top of PAPER_GEMMS."""
    from repro.configs import ARCHS, SHAPES
    from repro.core.llm_workloads import gemms_of_model
    out = []
    for sname in ("train_4k", "decode_32k"):
        out += gemms_of_model(ARCHS["qwen2-7b"], SHAPES[sname])
    return out


@pytest.fixture(scope="session")
def plans_exact():
    """Both backends over PAPER_GEMMS, order_mode="exact" — computed once
    per session (the scalar path is the expensive reference)."""
    dv = plan_workload(PAPER_GEMMS, CONFIGS, backend="vectorized")
    ds = plan_workload(PAPER_GEMMS, CONFIGS, backend="scalar")
    return dv, ds


@pytest.fixture(scope="session")
def plans_greedy():
    """Both backends under order_mode="greedy" over llm_workloads GEMMs +
    the paper grid — the path that used to silently fall back to scalar."""
    gemms = _llm_gemms() + PAPER_GEMMS
    dv = plan_workload(gemms, CONFIGS, order_mode="greedy",
                       backend="vectorized")
    ds = plan_workload(gemms, CONFIGS, order_mode="greedy",
                       backend="scalar")
    return gemms, dv, ds


def _tie_ok(name_a, name_b, opts_a, base_a, tol=0.02):
    """Verdicts may differ only on float32 near-ties: the two chosen
    options' efficiencies must then be within `tol`."""
    def topsw(name):
        return (base_a.tops_per_w if name == "baseline"
                else opts_a[name].tops_per_w)
    ta, tb = topsw(name_a), topsw(name_b)
    return abs(ta - tb) <= tol * max(ta, tb)


@pytest.mark.parametrize("i", range(len(PAPER_GEMMS)),
                         ids=[f"{g.M}x{g.N}x{g.K}" for g in PAPER_GEMMS])
def test_verdict_parity_all_standard_configs(i, plans_exact):
    dv, ds = (p[i] for p in plans_exact)
    gemm = PAPER_GEMMS[i]
    assert dv.use_cim == ds.use_cim, (gemm, dv.best_energy, ds.best_energy)
    assert (dv.best_energy == ds.best_energy
            or _tie_ok(dv.best_energy, ds.best_energy, ds.options,
                       ds.baseline)), (gemm, dv.best_energy, ds.best_energy)


def test_option_metric_parity_all_standard_configs(plans_exact):
    dvs, dss = plans_exact
    for gemm, dv, ds in list(zip(PAPER_GEMMS, dvs, dss))[:4]:
        assert dv.baseline.energy_pj == pytest.approx(
            ds.baseline.energy_pj, rel=0.02)
        assert dv.baseline.time_ns == pytest.approx(
            ds.baseline.time_ns, rel=0.02)
        for name in CONFIGS:
            assert dv.options[name].energy_pj == pytest.approx(
                ds.options[name].energy_pj, rel=0.02), (gemm, name)
            assert dv.options[name].time_ns == pytest.approx(
                ds.options[name].time_ns, rel=0.02), (gemm, name)


def test_plan_workload_backends_agree(plans_exact):
    for a, b in zip(*plans_exact):
        assert a.use_cim == b.use_cim
        assert (a.best_energy == b.best_energy
                or _tie_ok(a.best_energy, b.best_energy, b.options,
                           b.baseline))


# --- greedy order mode: in-kernel per-row order selection ------------------


def test_greedy_verdict_parity_llm_workloads(plans_greedy):
    """vectorized greedy verdicts == scalar greedy verdicts across
    llm_workloads x standard_configs (PR-2 tentpole: no scalar
    fallback)."""
    gemms, dvs, dss = plans_greedy
    for g, a, b in zip(gemms, dvs, dss):
        assert a.use_cim == b.use_cim, (g, a.best_energy, b.best_energy)
        assert (a.best_energy == b.best_energy
                or _tie_ok(a.best_energy, b.best_energy, b.options,
                           b.baseline)), (g, a.best_energy, b.best_energy)


def test_greedy_option_metric_parity(plans_greedy):
    gemms, dvs, dss = plans_greedy
    for g, dv, ds in list(zip(gemms, dvs, dss))[:6]:
        for name in CONFIGS:
            assert dv.options[name].energy_pj == pytest.approx(
                ds.options[name].energy_pj, rel=0.02), (g, name)
            assert dv.options[name].time_ns == pytest.approx(
                ds.options[name].time_ns, rel=0.02), (g, name)


def test_greedy_mask_matches_loopnest_reference():
    """The in-kernel one-hot order selection == loopnest.greedy_order for
    every trip-count pattern, ties included."""
    import itertools
    import jax.numpy as jnp
    from repro.core.loopnest import greedy_perm
    from repro.core.vectorized import _ORDERS, _greedy_mask
    patterns = list(itertools.product([1, 2, 3, 7], repeat=3))
    trips = {d: jnp.asarray([float(p[i]) for p in patterns])
             for i, d in enumerate(("M", "K", "N"))}
    masks = np.stack([np.asarray(_greedy_mask(trips, o)) for o in _ORDERS])
    assert (masks.sum(axis=0) == 1).all()      # exactly one order per row
    for r, p in enumerate(patterns):
        picked = _ORDERS[int(np.argmax(masks[:, r]))]
        want = greedy_perm({"M": p[0], "K": p[1], "N": p[2]})
        assert tuple(picked) == want, (p, picked, want)


def test_greedy_runs_with_zero_scalar_fallback(monkeypatch):
    """The batched greedy path must never touch the scalar cost model —
    poison it and score a full config sweep through a fresh engine (fresh
    LRU, so every pair really hits the device kernel)."""
    import repro.core.sweep as sweep_mod

    def boom(*a, **k):
        raise AssertionError("scalar fallback invoked on the batched path")
    monkeypatch.setattr(sweep_mod, "evaluate", boom)
    eng = SweepEngine(mesh=None)
    pairs = [(PAPER_GEMMS[0], cfg) for cfg in CONFIGS.values()]
    mets = eng.cim_metrics(pairs, order_mode="greedy")
    assert len(mets) == len(pairs)
    assert all(np.isfinite(m.energy_pj) for m in mets)


# --- sharded evaluation ----------------------------------------------------


def test_sharded_engine_bitwise_parity_1device_mesh():
    """An explicit 1-device row mesh exercises the shard_map path on a
    single host device; sharding is a pure data split, so metrics must be
    bitwise identical to the unsharded engine.  (The multi-device split
    is covered by the @slow subprocess test and the benchmark gate.)"""
    from repro.launch.mesh import row_mesh
    mesh = row_mesh(jax.devices()[:1])
    es = SweepEngine(mesh=mesh)
    eu = SweepEngine(mesh=None)
    assert es.n_shards == 1
    gemms = [PAPER_GEMMS[0], PAPER_GEMMS[1]]
    pairs = [(g, CONFIGS[n]) for g in gemms
             for n in ("Digital-6T@RF", "Digital-6T@SMEM-B",
                       "Analog-8T@SMEM-A")]
    for om in ("exact", "greedy"):
        for a, b in zip(es.cim_metrics(pairs, om),
                        eu.cim_metrics(pairs, om)):
            assert a.energy_pj == b.energy_pj     # bitwise, not approx
            assert a.time_ns == b.time_ns
            assert a.dram_bytes == b.dram_bytes
    # (sharded baseline parity: @slow subprocess test + the benchmark's
    # sharded plan_workload gate — its 36-order kernel compile is too
    # heavy for the fast tier)


@pytest.mark.slow
def test_sharded_engine_parity_multidevice_subprocess():
    """Real row-axis split: 4 forced host devices in a subprocess, bitwise
    parity of the sharded vs unsharded engine over the paper grid."""
    import os
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(repo, "src")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    code = """
import jax
assert len(jax.devices()) == 4
from repro.core import GEMM
from repro.core.planner import standard_configs
from repro.core.sweep import SweepEngine
from repro.launch.mesh import row_mesh
CONFIGS = standard_configs()
es = SweepEngine(mesh=row_mesh())
eu = SweepEngine(mesh=None)
assert es.n_shards == 4
gemms = [GEMM(512,1024,1024), GEMM(1,4096,4096), GEMM(17,100,300),
         GEMM(4096,1408,2048)]
pairs = [(g, c) for g in gemms for c in CONFIGS.values()]
for om in ("exact", "greedy"):
    for a, b in zip(es.cim_metrics(pairs, om), eu.cim_metrics(pairs, om)):
        assert a.energy_pj == b.energy_pj and a.time_ns == b.time_ns
for a, b in zip(es.baseline_metrics(gemms), eu.baseline_metrics(gemms)):
    assert a.energy_pj == b.energy_pj and a.time_ns == b.time_ns
print("OK")
"""
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=repo,
                       capture_output=True, text=True, timeout=540)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout


# --- other vectorized-model parity -----------------------------------------


def test_smem_config_batch_matches_scalar(engine):
    """The vectorized model's CiM@SMEM scoring (configA/B) matches
    cost_model.evaluate."""
    for g in (GEMM(512, 1024, 1024), GEMM(1, 4096, 4096),
              GEMM(128, 5632, 2048)):
        for name in ("Digital-6T@SMEM-A", "Digital-6T@SMEM-B",
                     "Analog-8T@SMEM-B"):
            cfg = CONFIGS[name]
            m_s = evaluate(g, cfg)
            m_v = engine.cim_metrics([(g, cfg)])[0]
            assert m_v.energy_pj == pytest.approx(m_s.energy_pj, rel=0.02)
            assert m_v.time_ns == pytest.approx(m_s.time_ns, rel=0.02)


def test_baseline_batch_matches_scalar(engine):
    """The vectorized model's tensor-core baseline scoring matches
    baseline.evaluate_baseline."""
    for g in PAPER_GEMMS:
        m_s = evaluate_baseline(g)
        m_v = engine.baseline_metrics([g])[0]
        assert m_v.energy_pj == pytest.approx(m_s.energy_pj, rel=0.02), g
        assert m_v.time_ns == pytest.approx(m_s.time_ns, rel=0.02), g


# --- cache behavior --------------------------------------------------------


def test_sweep_cache_hits_and_identity():
    eng = SweepEngine(mesh=None)
    g = GEMM(256, 512, 512)
    cfg = CONFIGS["Digital-6T@RF"]
    m1 = eng.cim_metrics([(g, cfg)])[0]
    assert eng.cache_info()["misses"] == 1
    m2 = eng.cim_metrics([(g, cfg)])[0]
    assert m2 is m1                       # cached object, no re-evaluation
    assert eng.cache_info()["hits"] == 1
    # greedy results cache under a distinct key
    mg = eng.cim_metrics([(g, cfg)], order_mode="greedy")[0]
    assert mg is not m1
    assert eng.cim_metrics([(g, cfg)], order_mode="greedy")[0] is mg
    # label/count do not affect metrics: same cache entry
    m3 = eng.cim_metrics([(g.scaled(label="x", count=7), cfg)])[0]
    assert m3 is m1
    # eviction respects the LRU bound
    small = SweepEngine(cache_size=2, mesh=None)
    for m in (16, 32, 64, 128):
        small.baseline_metrics([GEMM(m, 64, 64)])
    assert small.cache_info()["size"] == 2


def test_engine_cache_thread_safety():
    """Concurrent kernel_plan-style queries against ONE shared engine:
    the locked LRU must neither corrupt (OrderedDict invariants) nor lose
    hit/miss counts, even with eviction churn (tiny cache_size)."""
    eng = SweepEngine(cache_size=16, mesh=None)
    gemms = [GEMM(16 * (1 + i % 8), 32 * (1 + i % 3), 64 + 32 * (i % 4))
             for i in range(24)]
    cfgs = [CONFIGS[n] for n in ("Digital-6T@RF", "Analog-6T@RF",
                                 "Digital-6T@SMEM-B")]
    # prewarm the jitted kernels so threads only race the cache, not the
    # first-compile path
    eng.cim_metrics([(gemms[0], cfgs[0])])
    n_threads, n_iter = 8, 40
    errors: list = []
    local_counts: list = []

    def work(t):
        try:
            for i in range(n_iter):
                g = gemms[(t * 7 + i) % len(gemms)]
                c = cfgs[(t + i) % len(cfgs)]
                m = eng.cim_metrics([(g, c)])[0]
                assert np.isfinite(m.energy_pj)
            local_counts.append(eng.thread_cache_counts())
        except Exception as e:            # pragma: no cover - failure path
            errors.append(e)

    threads = [threading.Thread(target=work, args=(t,))
               for t in range(n_threads)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert not errors, errors
    info = eng.cache_info()
    assert info["size"] <= 16
    # every locked _get incremented exactly one counter
    assert info["hits"] + info["misses"] == 1 + n_threads * n_iter
    # per-thread attribution (measured_cache_delta's basis): each thread
    # saw exactly its own n_iter lookups, and the locals sum to the
    # global counters (main thread did the 1 prewarm lookup)
    assert all(h + m == n_iter for h, m in local_counts)
    assert sum(h + m for h, m in local_counts) == n_threads * n_iter


# --- pallas backend --------------------------------------------------------


def test_pallas_backend_parity_and_cache_keyspace():
    """backend="pallas" runs the fused kernel into its OWN result-cache
    keyspace (a shared keyspace would let parity tests pass on LRU hits
    instead of kernel output), and cache_info() breaks hits/misses down
    per backend."""
    eng = SweepEngine(mesh=None)
    g = GEMM(512, 1024, 1024)
    cfg = CONFIGS["Digital-6T@RF"]
    mv = eng.cim_metrics([(g, cfg)], backend="vectorized")[0]
    mp = eng.cim_metrics([(g, cfg)], backend="pallas")[0]
    assert mp is not mv                    # distinct keyspaces, both cold
    assert mp.energy_pj == pytest.approx(mv.energy_pj, rel=1e-5)
    assert mp.time_ns == pytest.approx(mv.time_ns, rel=1e-5)
    assert eng.cim_metrics([(g, cfg)], backend="pallas")[0] is mp
    info = eng.cache_info()
    assert info["backends"]["vectorized"] == {"hits": 0, "misses": 1}
    assert info["backends"]["pallas"] == {"hits": 1, "misses": 1}
    assert "pallas_fallback" not in info
    # scalar-reference agreement (the property suite covers this wide;
    # here it pins the engine-level path end to end)
    ms = evaluate(g, cfg)
    assert mp.energy_pj == pytest.approx(ms.energy_pj, rel=0.02)


def test_pallas_fallback_records_reason(monkeypatch):
    """A platform that cannot compile the Pallas kernel gets no silent
    XLA fallback: the pallas request raises with the lowering error, and
    no result lands in either keyspace.  Forced here by reporting an
    accelerator backend to the probe, which then compiles with
    interpret=False on a host that has only the CPU."""
    import repro.kernels.sweep_eval as se
    monkeypatch.setattr(se, "_STATUS", None)
    monkeypatch.setattr(se.jax, "default_backend", lambda: "tpu")
    eng = SweepEngine(mesh=None)
    g = GEMM(256, 512, 512)
    cfg = CONFIGS["Analog-8T@SMEM-A"]
    with pytest.raises(RuntimeError, match="does not compile on tpu"):
        eng.cim_metrics([(g, cfg)], backend="pallas")
    info = eng.cache_info()
    assert "pallas_fallback" not in info
    assert info["size"] == 0 and info["backends"] == {}
    assert se._STATUS is None            # a failed probe is not memoized


def test_measured_cache_delta_carries_backend_breakdown():
    """Serving/dryrun telemetry consumers read measured_cache_delta's
    engine block — the per-backend breakdown must be in it (launch.serve
    prints it; dryrun decode cells embed it)."""
    from repro.core.sweep import measured_cache_delta, sweep_evaluate
    g = GEMM(96, 160, 224)
    _, tel = measured_cache_delta(
        lambda: sweep_evaluate(g, CONFIGS["Digital-8T@RF"]))
    assert tel["plan_hits"] + tel["plan_misses"] >= 1
    eng = tel["engine"]
    assert "backends" in eng
    assert eng["backends"]["vectorized"]["misses"] >= 1


def test_report_renders_backend_breakdown():
    """launch.report's planner-cache table shows the per-backend counts;
    cells predating the fields still render."""
    from repro.launch.report import planner_cache_table
    base = {"status": "ok", "arch": "a", "shape": "s", "mesh": "single"}
    planner = {"summary": {"cim_fraction": 0.5, "energy_gain_x": 2.0},
               "plan_hits": 3, "plan_misses": 4,
               "cim_routed_fraction": 0.25,
               "cache": {"hits": 7, "misses": 9, "size": 16,
                         "backends": {"vectorized": {"hits": 5,
                                                     "misses": 6},
                                      "pallas": {"hits": 2, "misses": 3}}}}
    table = planner_cache_table([{**base, "planner": planner}])
    assert "vectorized:5h/6m" in table
    assert "pallas:2h/3m" in table
    legacy = {**planner, "cache": {"hits": 1, "misses": 2, "size": 3}}
    assert "size=3" in planner_cache_table([{**base, "planner": legacy}])


# --- argument validation ---------------------------------------------------


def test_unknown_backend_rejected():
    g = GEMM(64, 64, 64)
    with pytest.raises(ValueError, match="unknown planner backend"):
        decide(g, backend="vectorised")
    with pytest.raises(ValueError, match="unknown planner backend"):
        plan_workload([g], backend="batched")
    with pytest.raises(ValueError, match="unknown planner backend"):
        plan_workload([g], backend="palas")
    with pytest.raises(ValueError, match="unknown sweep backend"):
        SweepEngine(mesh=None).cim_metrics(
            [(g, CONFIGS["Digital-6T@RF"])], backend="xla")


def test_unknown_order_mode_rejected_by_both_backends():
    """Satellite fix: no silent reroute, no asymmetric errors — both
    backends accept exactly {exact, greedy} and reject the rest."""
    g = GEMM(64, 64, 64)
    for backend in ("vectorized", "scalar"):
        with pytest.raises(ValueError, match="unknown order_mode"):
            decide(g, order_mode="greddy", backend=backend)
        with pytest.raises(ValueError, match="unknown order_mode"):
            plan_workload([g], order_mode="fastest", backend=backend)
    with pytest.raises(ValueError, match="unknown order_mode"):
        SweepEngine(mesh=None).cim_metrics(
            [(g, CONFIGS["Digital-6T@RF"])], order_mode="greddy")


def test_order_mode_greedy_stays_batched():
    """decide(order_mode="greedy", backend="vectorized") now scores
    in-kernel (and agrees with scalar) instead of silently falling back."""
    g = GEMM(256, 512, 512)
    d = decide(g, CONFIGS, order_mode="greedy", backend="vectorized")
    ds = decide(g, CONFIGS, order_mode="greedy", backend="scalar")
    assert d.best_energy == ds.best_energy
    # and the engine accepts greedy directly (no ValueError)
    m = SweepEngine(mesh=None).cim_metrics(
        [(g, CONFIGS["Digital-6T@RF"])], order_mode="greedy")[0]
    assert isinstance(m, Metrics)


# --- decision layer --------------------------------------------------------


def _fake_metrics(energy, time):
    return metrics_from_row(1000.0, {"energy_pj": energy, "time_ns": time})


def test_summarize_uses_eligible_winner():
    """energy_gain_x must come from the option decide() deploys, not from
    an unconstrained min-energy config the throughput floor rules out."""
    g = GEMM(64, 64, 64)
    base = _fake_metrics(energy=100.0, time=10.0)          # 100 gflops eq.
    options = {
        # eligible winner: keeps throughput, halves energy
        "good": _fake_metrics(energy=50.0, time=12.0),
        # ineligible tempter: 10x energy win but 100x throughput collapse
        "slow": _fake_metrics(energy=10.0, time=1000.0),
    }
    d = make_decision(g, base, options, throughput_floor=0.5)
    assert d.best_energy == "good"
    s = summarize([d])
    assert s["energy_gain_x"] == pytest.approx(100.0 / 50.0)


def test_make_decision_shared_by_both_backends():
    g = GEMM(512, 1024, 1024)
    ds = decide(g, CONFIGS, backend="scalar")
    rebuilt = make_decision(g, ds.baseline, ds.options)
    assert rebuilt.best_energy == ds.best_energy
    assert rebuilt.use_cim == ds.use_cim


# NOTE: defined last on purpose — it drops every compiled sweep kernel,
# so any test running after it would pay a recompile.
def test_jit_cache_clear_covers_every_kernel():
    # benchmarks drop the compiled kernels to take an honest cold-jit
    # sample; ALL registered entry points (exact, greedy, sharded) must
    # go cold, and recompiling must reproduce identical metrics
    from repro.core.sweep import jit_cache_clear, jit_kernel_count
    from repro.launch.mesh import row_mesh
    eng = SweepEngine(mesh=None)
    sharded = SweepEngine(mesh=row_mesh(jax.devices()[:1]))
    g = GEMM(64, 128, 128)
    cfg = CONFIGS["Digital-6T@RF"]
    before = eng.cim_metrics([(g, cfg)])[0]
    eng.cim_metrics([(g, cfg)], order_mode="greedy")
    eng.cim_metrics([(g, cfg)], backend="pallas")
    sharded.cim_metrics([(g, cfg)])
    assert jit_kernel_count() > 0
    jit_cache_clear()
    assert jit_kernel_count() == 0        # no stale executable survives
    eng.cache_clear()
    after = eng.cim_metrics([(g, cfg)])[0]
    assert after.energy_pj == before.energy_pj
    assert after.time_ns == before.time_ns


@pytest.mark.slow
def test_serving_kernel_plan_gates_decode_gemvs():
    """ServeSession consults the batched planner: per-token decode GEMMs
    of a tiny model are "don't CiM" (the paper's M=1 pathology), and the
    build records sweep-cache telemetry for LRU sizing."""
    from repro.configs import ARCHS, RunConfig, reduced
    from repro.models import init
    from repro.serving import ServeSession
    import jax

    cfg = reduced(ARCHS["qwen2-7b"])
    rc = RunConfig(remat=False, attn_impl="naive")
    params = init(jax.random.PRNGKey(0), cfg)
    s = ServeSession(cfg, rc, params, max_len=32, batch=2)
    plan = s.kernel_plan
    assert plan and all(isinstance(d, Decision) for d in plan.values())
    assert s.kernel_plan is plan          # lazily computed once
    # batch-2 decode: every GEMM is tiny/low-reuse -> nothing offloads
    gemvs = [lab for lab in plan if "decode" in lab or "Wq" in lab]
    assert gemvs
    for lab in gemvs:
        assert s.use_cim_for(lab) == plan[lab].use_cim
    # unknown labels raise (label drift must not silently disable gating)
    with pytest.raises(KeyError):
        s.use_cim_for("no-such-gemm")
    # cache telemetry: one plan build = one hit-or-miss per (gemm, config)
    # option plus one per baseline, recorded for traffic-driven sizing
    tel = s.plan_cache_telemetry
    assert tel["plan_hits"] + tel["plan_misses"] >= len(plan)
    assert tel["engine"]["hits"] >= tel["plan_hits"]
