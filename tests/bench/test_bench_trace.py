"""The reduction from a profiler trace to busy time, idle share, kernel
time and labelled gaps: on a hand-made trace whose answers are known,
and on a trace recorded here on the CPU."""
import jax
import jax.numpy as jnp
import pytest

from bench.lib import trace as tr

MS = 1_000_000      # ns


def hand_made():
    """A 100 ms window on one device: ops at 10-30 (two overlapping, one
    of them the kernel), 50-60 (kernel) and 95-110 (clipped at 100); the
    host stepped over 0-40 and waited over 40-100."""
    ops = [("while.4", 10 * MS, 30 * MS),       # holds the next two
           ("fusion.1", 10 * MS, 25 * MS), ("int8_gemm.3", 20 * MS, 30 * MS),
           ("int8_gemm.3", 50 * MS, 60 * MS), ("copy.7", 95 * MS, 110 * MS),
           ("fusion.2", 120 * MS, 130 * MS)]     # outside the window
    spans = [(tr.WINDOW_SPAN, 0, 100 * MS), ("bench.step", 0, 40 * MS),
             ("bench.wait", 40 * MS, 100 * MS), ("bench.submit", 45 * MS,
                                                 46 * MS)]
    return tr.Events({"/device:TPU:0": ops}, spans)


def test_busy_union_idle_share_and_kernel_time():
    r = tr.reduce(hand_made(), kernel=r"int8_gemm")
    assert r["window_s"] == pytest.approx(0.100)
    # union: 10-30, 50-60, 95-100 -> 35 ms, not the 45 ms the ops sum to
    assert r["busy_s"] == pytest.approx(0.035)
    assert r["idle_share"] == pytest.approx(0.65)
    assert r["kernel_s"] == pytest.approx(0.020)
    assert dict(r["device_ops"]) == pytest.approx(
        {"fusion": 0.015, "int8_gemm": 0.020, "copy": 0.005})


def test_ops_staging_the_kernels_int8_operand_count_as_the_kernel():
    ev = hand_made()
    ev.feeders = {"int8_gemm.3": ["copy.7"], "custom.1": ["fusion.1"]}
    # copy.7 (clipped to 95-100) feeds the kernel; fusion.1 feeds another
    assert tr.reduce(ev, kernel=r"int8_gemm")["kernel_s"] == pytest.approx(
        0.025)


def test_int8_operands_of_a_custom_call():
    text = ("%int8_matmul.20 = f32[64,3072]{1,0:T(8,128)S(1)} custom-call("
            "bf16[64,1536]{1,0:T(8,128)(2,1)S(1)} %copy-done.6, "
            "s8[1536,3072]{1,0:T(8,128)(4,1)S(1)} %dynamic-slice_fusion.14,"
            " f32[1,3072]{1,0:T(1,128)S(1)} %constant_fusion.24), "
            "custom_call_target=\"tpu_custom_call\"")
    assert tr.int8_operands(text) == ["dynamic-slice_fusion.14"]


def test_gaps_are_named_by_what_the_host_did():
    r = tr.reduce(hand_made(), kernel=r"int8_gemm")
    # gaps 60-95 (wait), 30-50 (mid 40: step and wait both end/start
    # there; the shorter span wins), 0-10 (step)
    names = [g[0] for g in r["idle_gaps"]]
    secs = [g[1] for g in r["idle_gaps"]]
    assert secs == pytest.approx([0.035, 0.020, 0.010])
    assert names[0] == "bench.wait" and names[2] == "bench.step"


def test_devices_are_averaged_and_idle_ones_ignored():
    ev = hand_made()
    ev.device_ops["/device:TPU:1"] = [("fusion.9", 0, 100 * MS)]
    ev.device_ops["/device:TPU:2"] = [("fusion.9", 200 * MS, 300 * MS)]
    r = tr.reduce(ev, kernel=r"int8_gemm")
    assert r["devices"] == 2
    assert r["busy_s"] == pytest.approx((0.035 + 0.100) / 2)


def test_a_trace_without_the_window_span_or_device_ops_is_refused():
    ev = hand_made()
    with pytest.raises(ValueError, match="span"):
        tr.reduce(tr.Events(ev.device_ops, ev.host_spans[1:]), "x")
    with pytest.raises(ValueError, match="device operation"):
        tr.reduce(tr.Events({}, ev.host_spans), "x")


def test_op_names_come_from_the_hlo_text():
    assert tr.op_name("%int8_matmul.20 = f32[64,3072]{1,0} custom-call("
                      "bf16[64,1536] %copy-done.6)") == "int8_matmul.20"
    assert tr.op_name("dot_general.1") == "dot_general.1"


def test_union_merges_and_clips():
    assert tr.union([(5, 8), (0, 3), (2, 4), (9, 20)], 1, 10) == [
        (1, 4), (5, 8), (9, 10)]


def test_recorded_trace(tmp_path):
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.wait"):
                jax.numpy.zeros(()).block_until_ready()
    jax.profiler.stop_trace()
    r = tr.reduce(tr.extract(tr.latest_xplane(str(tmp_path))),
                  kernel=r"dot")
    assert 0 < r["busy_s"] < r["window_s"]
    assert 0 < r["kernel_s"] <= r["busy_s"]
    assert 0 < r["idle_share"] < 1
    assert all(name.startswith("bench.") or name == "host-other"
               for name, _ in r["idle_gaps"])
