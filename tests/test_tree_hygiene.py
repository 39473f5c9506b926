"""What a deletion leaves behind: an import that no longer resolves, and a
document that still names a file that is gone.

``test_tool_help_contract`` (tests/test_cost.py) imports every tool, but an
``import`` inside a function runs only when the function does: three tools
hid their import of a root-level script that way. Here every import statement of every script
outside the package is walked with ``ast`` and resolved without running the
script. Nothing here touches a device or starts a process.
"""

from __future__ import annotations

import ast
import importlib
import importlib.util
import os
import functools
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
PROGRAM = "videop2p_tpu"

_SCRIPTS = sorted(p.relative_to(ROOT).as_posix()
                  for p in (ROOT / "tools").glob("*.py")) + [
    "__graft_entry__.py", "chip_smoke.py"]


def _unresolved(path: Path):
    tree = ast.parse(path.read_text(), filename=str(path))
    missing = []

    def spec_of(module, lineno):
        try:
            found = importlib.util.find_spec(module) is not None
        except (ImportError, ValueError):
            found = False
        if not found:
            missing.append(f"{path.name}:{lineno}: no module {module!r}")
        return found

    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                spec_of(a.name, node.lineno)
        elif isinstance(node, ast.ImportFrom) and not node.level:
            if not spec_of(node.module, node.lineno):
                continue
            if node.module.split(".")[0] != PROGRAM:
                continue
            # of the program, the names too: an attribute or a submodule
            mod = importlib.import_module(node.module)
            for a in node.names:
                if a.name == "*" or hasattr(mod, a.name):
                    continue
                spec_of(f"{node.module}.{a.name}", node.lineno)
    return missing


@pytest.mark.parametrize("script", _SCRIPTS)
def test_every_import_resolves(script, monkeypatch):
    # what the scripts put on ``sys.path`` themselves when they run
    monkeypatch.syspath_prepend(str(ROOT / "tools"))
    monkeypatch.syspath_prepend(str(ROOT))
    missing = _unresolved(ROOT / script)
    assert not missing, "\n".join(missing)


# ------------------------------------------------------------ documents --

_DOCUMENTS = ["README.md", "PERF.md", "docs/OBSERVABILITY.md",
              "docs/SERVING.md", "docs/STREAMING.md", "docs/PARITY.md"]

_NAME = re.compile(r"^[\w.\-/]+\.(?:py|md|json|yaml)$")

# names of files that are not in the tree and should not be, with the reason
_NOT_IN_THE_TREE = {
    "config.json": "a published checkpoint's configuration (PERF.md §4)",
    "unet/config.json": "a published checkpoint's configuration (PERF.md §4)",
    "manifest.json": "written by a streaming job under its own directory "
                     "(stream/manifest.py)",
}

# docs/PARITY.md sets each module beside the file of the reference
# implementation it stands for (/root/reference, not part of this tree);
# these are the ones no file of this tree shares a name with
_REFERENCE_FILES = {
    "docs/PARITY.md": {
        "app_gradio.py", "dependent_ddim.py", "dependent_noise.py",
        "pipeline_tuneavideo.py", "ptp_utils.py", "resnet.py", "run_car.py",
        "run_rabbit.py", "util.py", "tuneavideo/data/dataset.py",
        "tuneavideo/models/unet.py",
    },
}


@functools.cache
def _tree_files():
    skip = {"__pycache__", "outputs", "chiprun_out"}
    files = set()
    for here, dirs, names in os.walk(ROOT):
        dirs[:] = [d for d in dirs if d not in skip
                   and (d == ".claude" or not d.startswith("."))]
        rel = Path(here).relative_to(ROOT)
        files |= {(rel / n).as_posix() for n in names}
    return frozenset(files)


def _names_in(text: str):
    """Back-ticked words that are file names: ``tools/obs_diff.py``, the
    ``chip_smoke.py`` of ``python chip_smoke.py --rehearse``, the file of
    ``tests/test_ops.py:216``."""
    for token in re.findall(r"`([^`\n]+)`", text):
        if any(c in token for c in "*<{"):
            continue
        for word in token.split():
            word = re.sub(r":\d+(?:-\d+)?$", "", word.strip("()[],;"))
            if _NAME.match(word):
                yield word


@pytest.mark.parametrize("document", _DOCUMENTS)
def test_documents_name_files_that_exist(document):
    files = _tree_files()
    root_dirs = {f.split("/")[0] for f in files if "/" in f}
    allowed = set(_NOT_IN_THE_TREE) | _REFERENCE_FILES.get(document, set())
    gone = []
    for name in sorted(set(_names_in((ROOT / document).read_text()))):
        if name in allowed:
            continue
        if "/" in name and name.split("/")[0] in root_dirs:
            ok = name in files
        else:  # a bare name, or a path from inside the package
            ok = any(f == name or f.endswith("/" + name) for f in files)
        if not ok:
            gone.append(name)
    assert not gone, f"{document} names files that are not in the tree: {gone}"


def test_the_exemptions_are_still_needed():
    """An exemption for a name no document gives any more hides the next
    file of that name that goes missing."""
    named = {d: set(_names_in((ROOT / d).read_text())) for d in _DOCUMENTS}
    everywhere = set().union(*named.values())
    assert set(_NOT_IN_THE_TREE) <= everywhere, \
        sorted(set(_NOT_IN_THE_TREE) - everywhere)
    for document, names in _REFERENCE_FILES.items():
        assert names <= named[document], sorted(names - named[document])
