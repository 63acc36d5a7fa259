"""The benchmark's parts, found by name.

Each part is one Python file in the directory of its kind:

``generators/<name>.py``
    a graph generator, named by a configuration's ``graph.generator``;
``pairs/<name>.py``
    how a query's endpoints are drawn, named by a mix's ``pairs.kind``;
``arrivals/<name>.py``
    when queries are sent, named by a mix's ``arrivals.kind``;
``metrics/<name>.py``
    a per-layer metric reader, named by the metric in ``BENCHMARK.json``.

A new part is a new file; nothing here changes.  An unknown name is an
error, never a fallback to another part.
"""
from __future__ import annotations

import importlib.util
import re
from pathlib import Path

HERE = Path(__file__).resolve().parent
KINDS = ("generators", "pairs", "arrivals", "metrics")
_NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}\Z")
_loaded: dict[tuple[str, str], object] = {}


def load(kind: str, name: str):
    """The module of part ``name`` of ``kind``."""
    if kind not in KINDS:
        raise ValueError(f"unknown kind of part {kind!r}; have {KINDS}")
    if not isinstance(name, str) or not _NAME.match(name):
        raise ValueError(f"bad {kind} name {name!r}")
    key = (kind, name)
    if key not in _loaded:
        path = HERE / kind / f"{name}.py"
        if not path.is_file():
            have = sorted(p.stem for p in (HERE / kind).glob("*.py"))
            raise ValueError(f"unknown {kind} part {name!r}; have {have}")
        spec = importlib.util.spec_from_file_location(
            f"perfbench_{kind}_{name}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _loaded[key] = mod
    return _loaded[key]
