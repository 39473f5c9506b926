"""model step (fifth token family): the useful operations of the traced
steps (counted from the configuration's shapes,
benchmark/harness/flops_falcon.py: the scan's four products per chunk and
attention at their causal pairs, the dense feed-forward, the projections
and the head, recompute not counted) over the device's busy seconds in the
trace x the chip's bf16 peak: the whole step's share of the peak, whatever
later implements it — a masked-dense tile or a recomputed block reads low,
never high."""

from benchmark.harness import flops, flops_falcon
from benchmark.harness.peaks import peaks_for


def read(ctx):
    win, cfg, tr = ctx["window"], ctx["config"], ctx.get("trace")
    if not tr or not tr.get("busy_s") or win.get("kind") != "tune_falcon":
        return None  # nothing traced: never 0
    work = ((win.get("traced_steps") or 0) * win["batch"]
            * flops.tune_step_flops(flops_falcon.falcon_ops(cfg, win["tokens"])))
    if not work:
        return None
    peak = peaks_for(ctx["device"]["kind"])["bf16_flops"] * ctx["device"]["count"]
    return 100.0 * work / (tr["busy_s"] * peak)
