"""expert layer: tokens to the busiest held expert over the mean over held
experts — the program's own counter, a scalar a step out of ``train_steps``
(mean over the expert layers), averaged over the first call's steps."""


def read(ctx):
    return (ctx["window"].get("counters") or {}).get("expert_load_max_over_mean")
