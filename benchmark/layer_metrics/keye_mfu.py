"""model step (fourth token family): the useful operations of the traced
steps (counted from the configuration's shapes, benchmark/harness/flops_keye.py:
attention at the keys SELECTED by the program's own counter, the scorer
forward only, the experts at the pairs that landed on held experts by the
program's own counter, the projections and the head, recompute not counted)
over the device's busy seconds in the trace x the chip's bf16 peak: the
whole step's share of the peak, whatever later implements it — a pair that
walks every causal tile or a padded expert block reads low, never high."""

from benchmark.harness import flops, flops_keye
from benchmark.harness.peaks import peaks_for


def read(ctx):
    win, cfg, tr = ctx["window"], ctx["config"], ctx.get("trace")
    if not tr or not tr.get("busy_s") or win.get("kind") != "tune_keye":
        return None  # nothing traced: never 0
    counters = win.get("counters") or {}
    ops = flops_keye.keye_ops(cfg, win["tokens"],
                              counters.get("held_pair_share"),
                              counters.get("keys_selected_mean"))
    work = ((win.get("traced_steps") or 0) * win["batch"]
            * flops.tune_step_flops(ops))
    if not work:
        return None
    peak = peaks_for(ctx["device"]["kind"])["bf16_flops"] * ctx["device"]["count"]
    return 100.0 * work / (tr["busy_s"] * peak)
