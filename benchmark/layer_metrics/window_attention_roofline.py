"""kernels (windowed attention pair): least seconds the chip could take for
the sliding layers' banded attention products of the traced steps — 2
forward + 4 backward at the pairs 0 <= t - s < window, the query heads held,
once a layer and step whatever the layer's recompute runs
(benchmark/harness/flops_command.py ``window_attention_pair_flops``) over the
chip's bf16 peak — as a share of the summed device time of the kernel pair's
own events (``lm_selected_attention`` / ``_bwd``) under the scope
``lm.window_attention`` in the traced call, %. The pair is compute-bound at
this shape (its operands are read once a tile pair from VMEM-sized tiles), so
the peak is the MXU's. None where the trace holds no such event (a program
without the scope, or one whose sliding layers attend as XLA)."""

from benchmark.harness import flops_command
from benchmark.harness.peaks import peaks_for


def read(ctx):
    win, tr = ctx["window"], ctx.get("trace") or {}
    seconds = (tr.get("attention_kernel_s") or {}).get("lm.window_attention")
    steps = win.get("traced_steps")
    if not seconds or not steps or win.get("kind") != "tune_command":
        return None
    work = steps * win["batch"] * flops_command.window_attention_pair_flops(
        ctx["config"], win["tokens"])
    peak = peaks_for(ctx["device"]["kind"])["bf16_flops"] * ctx["device"]["count"]
    return 100.0 * (work / peak) / seconds
