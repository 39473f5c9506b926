"""kernels: least time by the roofline for the frame attention of every
forward the traced window held (shapes from the configuration, per call
site) over the summed device time of ``fused_frame_attention`` events."""

from benchmark.harness import roofline
from benchmark.harness.peaks import peaks_for


def read(ctx):
    tr, win, cfg = ctx.get("trace"), ctx["window"], ctx["config"]
    if not tr or not tr.get("kernel_s", {}).get("fused_frame_attention"):
        return None  # nothing to read: never 0
    forwards = win.get("traced_forwards")
    if not forwards:
        return None
    geo = cfg["geometry"]
    per = roofline.frame_attention_forward(
        cfg, win["frames"], geo["latent"], peaks_for(ctx["device"]["kind"]))
    return 100.0 * forwards * per["seconds"] / tr["kernel_s"][
        "fused_frame_attention"]
