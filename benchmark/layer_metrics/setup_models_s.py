"""set-up: ``tune.build_models`` + ``tune.state_create`` under ``tune.setup``
(the three models from the seed or the checkpoint, and the train state with
its optimizer), seconds."""

from benchmark.harness import spans


def read(ctx):
    return spans.setup_part(ctx, "models")
