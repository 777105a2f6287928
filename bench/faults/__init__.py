"""Breaks planted under the timed path, to show that the check catches them.

Each fault is a file of its own, ``bench/faults/<name>.py``, found by its
name as a metric's reader is: ``apply(name)`` runs the file's ``apply()``.
That runs in the run's own process, after the store is opened and before
any put, patches the program through ``patch`` and returns a function
that takes it out again.  ``pack16`` is the control; the others are the
faults a cell can have.  A traffic kind names in its ``FAULTS`` those it
must be able to show beyond the ones every kind is held to.
"""

from __future__ import annotations

import importlib
from typing import Callable, Tuple

import spec


def target(module: str, attr: str) -> Tuple[object, str]:
    """The object that holds ``attr`` (dotted, under ``module``), and the
    attribute's own name."""
    owner = importlib.import_module(module)
    *path, name = attr.split(".")
    for part in path:
        owner = getattr(owner, part)
    return owner, name


def patch(module: str, attr: str, make) -> Callable[[], None]:
    """Replaces ``attr`` by ``make(original)``; returns what puts the
    original back."""
    owner, name = target(module, attr)
    orig = getattr(owner, name)
    setattr(owner, name, make(orig))
    return lambda: setattr(owner, name, orig)


def apply(name: str) -> Callable[[], None]:
    return spec.fault_module(name).apply()
