"""Model FLOPs of the steps in the traced window over the window times
the bf16 peak, % (counts in bench/lib/flops.py: active lanes only)."""


def read(run):
    t = run.trace
    if t is None or not t["model_flops"]:
        return None
    return 100.0 * t["model_flops"] / (
        t["window_s"] * run.peaks["bf16_flops_per_s"])
