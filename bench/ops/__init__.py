"""One module per analysis op a cell's traffic runs, found by the op's name:
``bench/ops/<op>.py`` holds

* ``reference(sel, **kwargs)`` — the plain reference of the op over a
  :class:`bench.selection.Selection`;
* ``compare(tally, got, ref)`` — the served result against it, into a
  :class:`bench.compare.Tally`.
"""

from __future__ import annotations

import importlib
from typing import Dict


def load(op: str):
    return importlib.import_module(f"{__package__}.{op}")


def reference(op: str, sel, kwargs: Dict):
    kw = dict(kwargs)
    if "metrics" in kw:
        kw["metrics"] = tuple(kw["metrics"])
    return load(op).reference(sel, **kw)
