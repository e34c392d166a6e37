"""The parts of a cell that are found by name, each one Python file.

``bench/<kind>/<name>.py``, where ``kind`` is

* ``graphs``: a graph generator, named by a configuration's
  ``graph.generator``; it defines ``make(spec, seed, n_dcs)`` and
  ``tiny(spec)``;
* ``events``: an event source acting on the store inside the window, named
  by a traffic file's ``events.source``; it defines the class ``Source``
  and ``tiny(events)`` (``bench/harness.py`` says what ``Source`` provides);
* ``metrics``: a per-layer metric's reader, named by the metric in
  ``BENCHMARK.json``; it defines ``read(ctx)``.

A deployment, a traffic mix or a metric then joins the benchmark as new
files, with no edit to the harness.
"""
from __future__ import annotations

import importlib.util
import pathlib
import re

BENCH = pathlib.Path(__file__).resolve().parent
DIRS = {kind: BENCH / kind for kind in ("graphs", "events", "metrics")}
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")


def load(kind: str, name: str):
    """The module ``bench/<kind>/<name>.py``, executed afresh."""
    if not NAME.fullmatch(name):
        raise ValueError(f"{name!r} is not a {kind} name: letters, digits, '_', '.', '-'")
    path = DIRS[kind] / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file named {name!r}: looked for {path}")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
