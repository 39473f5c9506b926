"""serving layer: mean over the window's requests of client-seen seconds
minus that request's ``dispatch_s`` (what the engine, HTTP and client add
around the device program: resolve, decode to host, GIF writing, polling)."""


def read(ctx):
    rows = [r for r in ctx["window"].get("requests", [])
            if r.get("status") == "done" and r.get("dispatch_s") is not None]
    if not rows:
        return None
    return 1e3 * sum(r["client_s"] - r["dispatch_s"] for r in rows) / len(rows)
