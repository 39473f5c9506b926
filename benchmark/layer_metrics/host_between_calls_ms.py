"""programs: mean host time between two ``program.call`` spans of
``train_steps`` in the window (start of call n+1 less end of call n), ms."""

from benchmark.harness import spans


def read(ctx):
    return spans.host_between_calls_ms(ctx)
