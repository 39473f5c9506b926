"""fifth token family: device SELF time a step under the program's named
scope ``lm.dense_ffn`` — the dense SwiGLU feed-forward, its three
projections, activation and multipliers — in the traced call (each device
event's ``tf_op``, reduced by the driver with the harness's nesting), ms.
None where no event carries the scope (a program without it)."""


def read(ctx):
    tr, steps = ctx.get("trace") or {}, ctx["window"].get("traced_steps")
    seconds = (tr.get("scope_s") or {}).get("lm.dense_ffn")
    if seconds is None or not steps:
        return None
    return 1e3 * seconds / steps
