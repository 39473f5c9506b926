"""set-up: jax's own trace + lower durations of ``train_steps``
(``program.trace`` + ``program.lower``) under the first ``program.call``, not
what the analysis pass traced again, seconds."""

from benchmark.harness import spans


def read(ctx):
    return spans.setup_part(ctx, "trace_lower")
