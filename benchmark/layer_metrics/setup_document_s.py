"""set-up: ``tune.load_document`` under ``tune.setup`` (the token model's
document of token ids, from disk or from its seed, onto the device),
seconds. None — never 0 — where the run has no such span: a clip's tune, or
a program from before the token model."""

from benchmark.harness import spans


def read(ctx):
    found = spans.read_spans(spans.ledger_path(ctx))
    if found is None:
        return None
    tree = spans.Tree(found)
    roots = tree.named("tune.setup")
    if len(roots) != 1:
        return None
    documents = tree.children(roots[0], "tune.load_document")
    if not documents:
        return None
    return sum(float(s["duration_s"]) for s in documents)
