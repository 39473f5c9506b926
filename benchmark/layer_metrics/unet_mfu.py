"""model step: the UNet's useful operations of the traced calls (counted
from the configuration's shapes, benchmark/harness/flops.py) over the
device's busy seconds in the trace (the union of its operations' intervals)
x the chip's bf16 peak: the whole step's share of the peak, whatever kernels
it is made of. ``.edit``: forwards of every stream-step of the traced edits;
``.tune``: forward + gradient products of every traced step (recompute not
counted). Host time between calls is not in it: that is ``device_idle``."""

from benchmark.harness import flops
from benchmark.harness.peaks import peaks_for


def read(ctx):
    win, cfg, tr = ctx["window"], ctx["config"], ctx.get("trace")
    if not tr or not tr.get("busy_s"):
        return None  # nothing traced: never 0
    geo = cfg["geometry"]
    ops = flops.unet_ops(cfg, win["frames"], geo["latent"], geo["text_len"])
    if win["kind"] == "serve":
        work = (win.get("traced_forwards") or 0) * flops.forward_flops(ops)
    elif win["kind"] == "tune":
        work = ((win.get("traced_steps") or 0) * win["batch"]
                * flops.tune_step_flops(ops))
    else:
        return None
    if not work:
        return None
    peak = peaks_for(ctx["device"]["kind"])["bf16_flops"] * ctx["device"]["count"]
    return 100.0 * work / (tr["busy_s"] * peak)
