"""Temporary replacement of program functions, found by
"module:Qualified.name" targets.

Used for the traced run's host spans, for the controls (the program with a
shortcut a later change might take) and for the faults the tests plant.
Everything is restored on exit.
"""
from __future__ import annotations

import contextlib
import functools
import importlib


def _resolve(target: str):
    mod_name, _, qual = target.partition(":")
    owner = importlib.import_module(mod_name)
    *path, attr = qual.split(".")
    for p in path:
        owner = getattr(owner, p)
    return owner, attr


@contextlib.contextmanager
def replaced(replacements: dict):
    """{target: make(original) -> replacement} for the duration."""
    saved = []
    try:
        for target, make in replacements.items():
            owner, attr = _resolve(target)
            orig = owner.__dict__[attr] if isinstance(owner, type) \
                else getattr(owner, attr)
            saved.append((owner, attr, orig))
            setattr(owner, attr, make(orig))
        yield
    finally:
        for owner, attr, orig in reversed(saved):
            setattr(owner, attr, orig)


def spans(span_list: list, prefix: str):
    """Wrap each target in a profiler span named prefix + label."""
    import jax

    def wrap(label):
        def make(fn):
            @functools.wraps(fn)
            def inner(*a, **kw):
                with jax.profiler.TraceAnnotation(prefix + label):
                    return fn(*a, **kw)
            return inner
        return make

    return replaced({target: wrap(label) for label, target in span_list})
