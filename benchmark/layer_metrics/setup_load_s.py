"""set-up: ``program.backend_compile`` under the first ``program.call`` of
``train_steps`` — the XLA compile, or on a warm cache the retrieval and load
of the executable — not the analysis pass's own, seconds."""

from benchmark.harness import spans


def read(ctx):
    return spans.setup_part(ctx, "load")
