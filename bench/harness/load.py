"""Modules the benchmark finds by name: ``bench/<kind>/<name>.py`` of a
checkout, where kind is ``architectures``, ``references``, ``end_to_end`` or
``layer_metrics``. Adding an architecture, a reference or a metric adds a
file; nothing here changes.

Each file is loaded once per process, so an architecture's ``Arch`` is one
class wherever the harness, a reference or a reader meets it.
"""

from __future__ import annotations

import hashlib
import importlib.util
import sys
from pathlib import Path


def module(root: Path, kind: str, name: str):
    """The module ``<root>/bench/<kind>/<name>.py``."""
    path = (Path(root) / "bench" / kind / f"{name}.py").resolve()
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} module {name!r}: {path} does not exist")
    digest = hashlib.sha1(str(path).encode()).hexdigest()[:12]
    mod_name = f"bench_{kind}_{name}_{digest}".replace(".", "_").replace("-", "_")
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod  # before it runs: dataclasses look their module up
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[mod_name]
        raise
    return mod


def sibling(file: str, kind: str, name: str):
    """The module of kind ``kind`` named ``name`` in the checkout that holds
    ``file`` (a module under ``bench/<some kind>/``)."""
    return module(Path(file).resolve().parents[2], kind, name)
