"""programs layer: mean ``dispatch_s`` (dispatch to ready of the
``serve_edit`` program, as the engine's request record has it)."""


def read(ctx):
    rows = [r["dispatch_s"] for r in ctx["window"].get("requests", [])
            if r.get("status") == "done" and r.get("dispatch_s") is not None]
    return sum(rows) / len(rows) if rows else None
