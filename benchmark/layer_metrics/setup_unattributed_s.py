"""set-up: ``tune.setup`` less the five set-up parts and the first call's
``program.execute``: what no span explains, seconds."""

from benchmark.harness import spans


def read(ctx):
    return spans.setup_part(ctx, "unattributed")
