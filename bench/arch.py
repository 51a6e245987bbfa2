"""The modules a configuration file names for its architecture, and the
metric readers, loaded by path relative to the checkout, once per
process.

``"reference"``: the plain float32 forward pass and the benchmark's
weights (``layout``, ``make_weights``, ``Reference``).
``"work"``: the sizes the program's model must have
(``program_sizes``), the work counts the readers divide by
(``decode_step``, ``prefill``, ``kernel_bound_s``) and ``roofline_s``,
which turns a count into seconds at the chip's peaks.

A new architecture brings both as files of its own; no file that the
harness already has changes.
"""
from __future__ import annotations

import functools
import importlib
import importlib.util
import os

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def load(path: str):
    """The module at ``path`` (relative to the checkout, or absolute)."""
    return _load(os.path.normpath(os.path.join(ROOT, path)))


@functools.cache
def _load(path: str):
    base = os.path.basename(path)[:-3]
    if os.path.dirname(path) == BENCH and base.isidentifier():
        # a module beside this one is imported by its name elsewhere too
        # (``import work``): the same module either way
        return importlib.import_module(base)
    name = "bench_" + base.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
