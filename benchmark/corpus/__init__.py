"""Corpus generators, found by the name a configuration file gives.

The generator named ``x`` is the function ``x`` of the module
``benchmark/corpus/x.py``; it maps ``(seed, document index, tail
length)`` to one document's op tail as plain data
``[(seq, client, min_seq, contents)]``.  A new generator is a new module
here, and nothing else changes.
"""

from __future__ import annotations

import importlib


def generator(name: str):
    try:
        module = importlib.import_module(f"{__name__}.{name}")
    except ModuleNotFoundError:
        raise KeyError(f"no corpus generator module benchmark/corpus/"
                       f"{name}.py") from None
    return getattr(module, name)
