"""model step (third token family): the useful operations of the traced steps
(counted from the configuration's shapes, benchmark/harness/flops_command.py:
the sliding layers' attention at the BANDED pairs, the full layer's at the
causal pairs, routed experts at the pairs that landed on held experts by the
program's own counter, the shared experts at the columns held, recompute not
counted) over the device's busy seconds in the trace x the chip's bf16 peak:
the whole step's share of the peak, whatever later implements it — a
masked-dense window, a walked tile with nothing in it or a padded expert
block reads low, never high."""

from benchmark.harness import flops, flops_command
from benchmark.harness.peaks import peaks_for


def read(ctx):
    win, cfg, tr = ctx["window"], ctx["config"], ctx.get("trace")
    if not tr or not tr.get("busy_s") or win.get("kind") != "tune_command":
        return None  # nothing traced: never 0
    ops = flops_command.command_ops(
        cfg, win["tokens"], (win.get("counters") or {}).get("held_pair_share"))
    work = ((win.get("traced_steps") or 0) * win["batch"]
            * flops.tune_step_flops(ops))
    if not work:
        return None
    peak = peaks_for(ctx["device"]["kind"])["bf16_flops"] * ctx["device"]["count"]
    return 100.0 * work / (tr["busy_s"] * peak)
