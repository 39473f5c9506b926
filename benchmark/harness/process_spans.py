"""The set-up's spans before and beside the tune path's root, read from the
run's ledger.

The program writes one ``process`` span a ledger, at the moment the ledger's
first live span (``tune.setup``) opens: it starts at the process's own start
(``anchor`` ``"proc"``: the kernel's record; ``"import"`` where that could not
be read, and the span then starts at the package's first line) and ends where
``tune.setup`` starts. Its children are ``process.import`` (the package's
first line to the end of the entry CLI's imports) and
``process.ledger_open`` (the ledger's construction); what lies between them
is the caller's — in the benchmark, the harness's own work. Beside the root,
``metrics.tensorboard_writer`` under ``tune.metrics_logger`` times the
TensorBoard writer's import and construction.

Like :mod:`benchmark.harness.spans` (whose parser this reuses) it imports
nothing of the program, and every function returns ``None`` — never 0 —
where the ledger or a span it needs is missing: a program from before these
spans (the parent of the PR that added them) leaves the metrics out.
"""

from __future__ import annotations

from benchmark.harness.spans import Tree, ledger_path, read_spans

# the span names read here; videop2p_tpu/obs/spans.py keeps the same tuple
# (BENCHMARK_PROCESS_SPAN_NAMES) and a test of the program holds the two equal
READ_NAMES = (
    "process",
    "process.import",
    "metrics.tensorboard_writer",
)


def _tree(ctx: dict):
    spans = read_spans(ledger_path(ctx))
    return None if spans is None else Tree(spans)


def _process_import(tree: Tree):
    """``(process, process.import)`` of the run's one ``process`` span, or
    None."""
    roots = tree.named("process")
    if len(roots) != 1:
        return None
    imports = tree.children(roots[0], "process.import")
    if len(imports) != 1:
        return None
    return roots[0], imports[0]


def before_program_s(ctx: dict):
    """Seconds from the process's start to the package's first line: the
    interpreter, ``import jax`` and, in the benchmark, the backend's start-up.
    None unless the start is the kernel's (``anchor`` ``"proc"``)."""
    tree = _tree(ctx)
    found = None if tree is None else _process_import(tree)
    if found is None or found[0].get("anchor") != "proc":
        return None
    process, imported = found
    return (int(imported["wall_ns"]) - int(process["wall_ns"])) * 1e-9


def import_s(ctx: dict):
    """Seconds of ``process.import``."""
    tree = _tree(ctx)
    found = None if tree is None else _process_import(tree)
    return None if found is None else float(found[1]["duration_s"])


def tensorboard_s(ctx: dict):
    """Seconds of ``metrics.tensorboard_writer`` under ``tune.metrics_logger``
    under the one ``tune.setup``."""
    tree = _tree(ctx)
    if tree is None:
        return None
    roots = tree.named("tune.setup")
    if len(roots) != 1:
        return None
    writers = [w for logger in tree.children(roots[0], "tune.metrics_logger")
               for w in tree.children(logger, "metrics.tensorboard_writer")]
    if not writers:
        return None
    return sum(float(w["duration_s"]) for w in writers)
