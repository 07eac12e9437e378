"""Find a piece of the benchmark by its name: one file each.

Every piece that belongs to one configuration, traffic kind, distance or
metric is a module ``bench/<folder>/<name>.py`` of its own under the
checkout's root, loaded by path, so a later PR adds a piece as a new file
and edits none:

* ``generators/<generator>.py``: a corpus generator (``data.py``);
* ``reference/<distance>.py``: a distance of the plain reference
  (``reference/scan.py``);
* ``kinds/<kind>.py``: how a traffic mix's kind is planned and driven
  (``traffic.py``);
* ``e2e/<metric>.py`` and ``metrics/<metric>.py``: the readers of an
  end-to-end and of a per-layer metric.
"""

from __future__ import annotations

import importlib.util
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")

_loaded: dict = {}


def module(folder: str, name: str, root: str = ROOT):
    """The module ``bench/<folder>/<name>.py`` under ``root``, loaded once
    (the same object on every call, so it can key a ``jax.jit`` cache)."""
    if not NAME.match(str(name)):
        raise ValueError(f"not a name: {name!r}")
    path = os.path.join(root, "bench", folder, name + ".py")
    if path not in _loaded:
        if not os.path.isfile(path):
            d = os.path.dirname(path)
            known = sorted(f[:-3] for f in (os.listdir(d) if os.path.isdir(d)
                                            else [])
                           if f.endswith(".py") and f != "__init__.py")
            raise KeyError(f"no {folder}/{name}.py; known: {known}")
        spec = importlib.util.spec_from_file_location(
            "bench_" + folder + "_" + re.sub(r"\W", "_", name), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _loaded[path] = mod
    return _loaded[path]
