"""The seam between the program and its yardstick: every name of
``videop2p_tpu`` that a file under ``benchmark/`` imports, and every
attribute it reaches through such a name or its alias
(``run_tuning.save_pipeline``, ``ds.forward_loss``, ``UNet3DConfig.sd15``),
resolves on the imported program.

``benchmark/tests`` runs outside tier-1, so without this a PR that deletes or
renames one of these names learns it on the chip. The cases are computed at
collection time by an ``ast`` walk; ``benchmark/`` is read, never imported
and never edited, and nothing here touches a device.
"""

from __future__ import annotations

import ast
import importlib
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
PROGRAM = "videop2p_tpu"

_SCOPES = (ast.Module, ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda,
           ast.ClassDef)


def _direct_imports(scope):
    """Import statements that belong to ``scope`` itself, not to a scope
    nested in it."""
    stack = list(ast.iter_child_nodes(scope))
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.Import, ast.ImportFrom)):
            yield node
        if not isinstance(node, _SCOPES):
            stack.extend(ast.iter_child_nodes(node))


def _chain(node):
    """``a.b.c`` as ``("a", ["b", "c"])``; None when the base is no Name."""
    attrs = []
    while isinstance(node, ast.Attribute):
        attrs.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        return node.id, attrs[::-1]
    return None


def _seam_of(tree):
    """``(module, dotted)`` pairs: ``dotted`` resolves from ``module``."""
    seam = set()
    for scope in ast.walk(tree):
        if not isinstance(scope, _SCOPES):
            continue
        alias = {}  # local name -> (module, dotted prefix)
        for node in _direct_imports(scope):
            if isinstance(node, ast.ImportFrom):
                if node.level or not (node.module or "").startswith(PROGRAM):
                    continue
                for a in node.names:
                    alias[a.asname or a.name] = (node.module, a.name)
                    seam.add((node.module, a.name))
            else:
                for a in node.names:
                    if not a.name.startswith(PROGRAM):
                        continue
                    seam.add((a.name, ""))
                    if a.asname:
                        alias[a.asname] = (a.name, "")
                    else:  # ``import videop2p_tpu.x`` binds ``videop2p_tpu``
                        alias[PROGRAM] = (PROGRAM, "")
        if not alias:
            continue
        # ``ast.walk(scope)`` covers the scopes nested in it too: a nested
        # function sees its enclosing function's imports. Only the outermost
        # Attribute of a chain is a case; the ones inside it are its prefixes.
        inner = {id(n.value) for n in ast.walk(scope)
                 if isinstance(n, ast.Attribute)}
        for node in ast.walk(scope):
            if not isinstance(node, ast.Attribute) or id(node) in inner:
                continue
            chain = _chain(node)
            if chain is None or chain[0] not in alias:
                continue
            module, prefix = alias[chain[0]]
            seam.add((module, ".".join(filter(None, [prefix, *chain[1]]))))
    return seam


def _cases():
    cases = {}
    for path in sorted((ROOT / "benchmark").rglob("*.py")):
        tree = ast.parse(path.read_text(), filename=str(path))
        for module, dotted in _seam_of(tree):
            cases.setdefault((module, dotted), path.relative_to(ROOT))
    return [pytest.param(module, dotted, str(path),
                         id=f"{module}:{dotted or '<module>'}")
            for (module, dotted), path in sorted(cases.items())]


def _resolve(module: str, dotted: str):
    """Walk ``dotted`` from ``module``; a part that is no attribute may be a
    submodule nobody has imported yet (``import videop2p_tpu.obs.trace``)."""
    obj = importlib.import_module(module)
    name = module
    for part in filter(None, dotted.split(".")):
        name = f"{name}.{part}"
        try:
            obj = getattr(obj, part)
        except AttributeError:
            obj = importlib.import_module(name)
    return obj


_CASES = _cases()


@pytest.mark.parametrize("module, dotted, first_seen_in", _CASES)
def test_benchmark_reaches_a_name_the_program_has(module, dotted,
                                                  first_seen_in):
    try:
        _resolve(module, dotted)
    except (ImportError, AttributeError) as e:
        pytest.fail(f"{first_seen_in} reaches {module}:{dotted}, which the "
                    f"program no longer has ({type(e).__name__}: {e})")


def test_the_walk_finds_the_seam():
    """The walker itself: an empty or shrunken case list would pass in
    silence, so the names the drivers patch must be among the cases."""
    found = {(p.values[0], p.values[1]) for p in _CASES}
    for want in [("videop2p_tpu.cli", "run_tuning.main"),
                 ("videop2p_tpu.cli", "run_tuning.save_pipeline"),
                 ("videop2p_tpu.cli", "run_tuning.train_steps"),
                 ("videop2p_tpu.cli", "run_tuning.instrumented_jit"),
                 ("videop2p_tpu.cli", "run_tuning.build_models"),
                 ("videop2p_tpu.cli", "run_tuning.build_token_model"),
                 ("videop2p_tpu.models", "UNet3DConfig.sd15"),
                 ("videop2p_tpu.models", "deepseek.init_params"),
                 ("videop2p_tpu.models", "deepseek.forward_loss"),
                 ("videop2p_tpu.obs.trace", "_iter_fields")]:
        assert want in found, want
    assert len(found) >= 40
