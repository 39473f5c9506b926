"""kernels (selection pair over grouped-query heads): least seconds the chip
could take for the attention products at the SELECTED pairs of the traced
steps — 2 forward + 4 backward, every query head, once a layer and step
whatever the layer's recompute runs (benchmark/harness/flops_keye.py
``selected_attention_pair_flops``, the pairs from the program's
``keys_selected_mean``) over the chip's bf16 peak — as a share of the summed
device time of the kernel pair's own events (``lm_selected_attention`` /
``_bwd``) under the scope ``lm.sparse_attention`` in the traced call, %. The
pair walks every causal tile pair, selected or not, so it reads well under
the share of the tiles it walks. None where the trace holds no such event (a
program without the scope, or one that attends as XLA)."""

from benchmark.harness import flops_keye
from benchmark.harness.peaks import peaks_for


def read(ctx):
    win, tr = ctx["window"], ctx.get("trace") or {}
    seconds = (tr.get("attention_kernel_s") or {}).get("lm.sparse_attention")
    steps = win.get("traced_steps")
    if not seconds or not steps or win.get("kind") != "tune_keye":
        return None
    work = steps * win["batch"] * flops_keye.selected_attention_pair_flops(
        ctx["config"], win["tokens"],
        (win.get("counters") or {}).get("keys_selected_mean"))
    peak = peaks_for(ctx["device"]["kind"])["bf16_flops"] * ctx["device"]["count"]
    return 100.0 * (work / peak) / seconds
