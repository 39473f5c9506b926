"""fourth token family: device SELF time a step under the program's named
scope ``lm.attn_proj`` — the q / k / v / o projections, QK-norm and rotary —
in the traced call (each device event's ``tf_op``, reduced by the cell's
driver with the harness's nesting), ms. None where no event carries the scope (a
program without it)."""


def read(ctx):
    tr, steps = ctx.get("trace") or {}, ctx["window"].get("traced_steps")
    seconds = (tr.get("scope_s") or {}).get("lm.attn_proj")
    if seconds is None or not steps:
        return None
    return 1e3 * seconds / steps
