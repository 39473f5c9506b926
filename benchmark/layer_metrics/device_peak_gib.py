"""device: ``memory_stats()["peak_bytes_in_use"]`` after the window, GiB."""


def read(ctx):
    peak = ctx["window"].get("memory_peak_bytes")
    return peak / 2 ** 30 if peak else None
