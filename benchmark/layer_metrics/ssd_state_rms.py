"""state-space scan: root mean square, over the last chunk's outputs, of the
term the state handed to that chunk adds to them, mean over the Mamba
layers — the program's own counter, a scalar a step out of ``train_steps``,
averaged over the first call's steps. It is read off the very term the scan
adds to its outputs, so it reads 0 if chunks stop handing their state on
(the scan is then faster, and wrong: that is what ``better: higher`` and
``moves: tune_step_ms`` mean for it). None where the program has no such
counter."""


def read(ctx):
    return (ctx["window"].get("counters") or {}).get("ssd_state_rms")
