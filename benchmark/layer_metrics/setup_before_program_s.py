"""set-up: from the process's start (the kernel's record) to the package's
first line — the interpreter, ``import jax`` and the backend's start-up
(``run.py prepare``'s ``jax.devices()``), seconds. None — never 0 — where
the run has no ``process`` span with a ``process.import`` child, or its start
is not the kernel's (``anchor`` other than ``"proc"``)."""

from benchmark.harness import process_spans


def read(ctx):
    return process_spans.before_program_s(ctx)
