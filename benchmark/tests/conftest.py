import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")


import json  # noqa: E402

import pytest  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))


def with_serve_entries(manifest: dict) -> dict:
    """``BENCHMARK.json`` with the served-edit cell's entries put back: the
    cell is built and measured but not listed (PERF.md, Open questions,
    row 0); its entries are kept in ``data/serve_manifest_entries.json``."""
    with open(os.path.join(HERE, "data", "serve_manifest_entries.json")) as f:
        kept = json.load(f)
    return {k: (v + kept[k] if k in kept else v) for k, v in manifest.items()}


@pytest.fixture
def serve_listed(monkeypatch):
    """``run.py`` reads a manifest that lists the served-edit cell too."""
    sys.path.insert(0, os.path.dirname(HERE))
    import run as bench_run

    real = bench_run.load_json

    def load(path):
        d = real(path)
        return with_serve_entries(d) if path.endswith("BENCHMARK.json") else d

    monkeypatch.setattr(bench_run, "load_json", load)
    return bench_run
