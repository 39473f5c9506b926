"""set-up: ``metrics.tensorboard_writer`` under ``tune.metrics_logger`` under
``tune.setup`` — the TensorBoard writer's import and construction, seconds.
None — never 0 — where the run has no such span."""

from benchmark.harness import process_spans


def read(ctx):
    return process_spans.tensorboard_s(ctx)
