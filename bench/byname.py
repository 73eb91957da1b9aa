"""Find a part of the benchmark by its name: ``bench/<folder>/<name>.py``.

Every part that belongs to one request kind, distance, arrival pattern,
popularity or per-layer metric sits in a file of its own, so a later
change adds a part by adding a file and edits none:

* ``kinds/<kind>.py``: a request kind (``bench/kinds/window_range.py``
  says what one provides);
* ``distances/<name>.py``: a distance's reference DP, its optional
  control and its per-row operation and byte counts;
* ``arrivals/<name>.py``: ``arrivals(traffic, seconds)``, the due times
  of an open loop;
* ``popularity/<name>.py``: ``stream(traffic, seed)``, the pool index of
  each request;
* ``metrics/<name>.py``: ``read(run)``, one per-layer or end-to-end
  metric.
"""

from __future__ import annotations

import importlib.util
import pathlib
import re

#: where the folders are
ROOT = pathlib.Path(__file__).resolve().parent
_LOADED: dict = {}


def module(folder: str, name: str):
    """The module of ``<folder>/<name>.py``, loaded once."""
    path = ROOT / folder / f"{name}.py"
    if path not in _LOADED:
        if not path.exists():
            raise FileNotFoundError(f"no {folder} file for {name!r}: {path}")
        spec = importlib.util.spec_from_file_location(
            f"bench_{folder}_{re.sub(r'[^A-Za-z0-9_]', '_', name)}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _LOADED[path] = mod
    return _LOADED[path]
