"""INT8 GEMM kernel share of its roofline, %: the least time of every
kernel call in the traced window (per phase plan, from the shapes the
step lowers: bench/lib/flops.py) over the kernel's device time there."""


def read(run):
    t = run.trace
    if t is None or not t["kernel_s"]:
        return None
    least = sum(n * run.kernel_least_s[ph]
                for ph, n in t["phase_steps"].items())
    return 100.0 * least / t["kernel_s"]
