"""set-up: ``process.import`` under ``process`` — the package's first line
to the end of the tune CLI's module-level imports, seconds. None — never 0 —
where the run has no such span."""

from benchmark.harness import process_spans


def read(ctx):
    return process_spans.import_s(ctx)
