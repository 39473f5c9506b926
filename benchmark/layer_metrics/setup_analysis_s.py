"""set-up: ``program.analysis`` under the first ``program.call`` of
``train_steps`` — the introspection pass (AOT lower + compile again, cost and
memory analysis, HLO fingerprint and histogram), seconds."""

from benchmark.harness import spans


def read(ctx):
    return spans.setup_part(ctx, "analysis")
