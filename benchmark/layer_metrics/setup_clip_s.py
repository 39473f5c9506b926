"""set-up: ``tune.load_clip`` + ``tune.vae_encode`` + ``tune.text_encode`` under
``tune.setup`` (the clip from disk, its latents, the prompt's text states),
seconds."""

from benchmark.harness import spans


def read(ctx):
    return spans.setup_part(ctx, "clip")
