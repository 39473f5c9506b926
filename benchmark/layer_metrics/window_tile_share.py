"""sliding attention layers: what their dispatch walks over what the same
dispatch walks for a full layer — the kernel pair's (query tile, key tile)
steps inside the band over the causal ones (540 / 2080 at 32768 tokens, a
window of 4096 and 512 x 512 tiles), 1.0 where a masked-dense path ran — the
program's own counter, a scalar a step out of ``train_steps``, averaged over
the first call's steps. None where the program has no such counter."""


def read(ctx):
    return (ctx["window"].get("counters") or {}).get("window_tile_share")
