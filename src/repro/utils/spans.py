"""Named spans on the profiler's clock, host and device.

* ``span(name, **args)``: a host span ``fedback/<name>``
  (``jax.profiler.TraceAnnotation``) around dispatch and host work;
  ``args`` are values the host already holds.
* ``scope(name)``: a device scope ``fedback/<name>``
  (``jax.named_scope``) inside a traced program; it changes op
  metadata only, never the program.
* ``gc_spans()``: while it is open, each pass of Python's collector is
  a ``fedback/gc`` span carrying its ``generation``.

All of them land in the trace that ``jax.profiler.trace`` records
(host spans on the host plane, scopes in each device op's ``tf_op``
path), on the one clock of the device ops.  With no trace running a
span costs one check of an atomic.
"""
from __future__ import annotations

import contextlib
import gc
import threading

import jax

PREFIX = "fedback/"


def span(name: str, **args):
    """Host span ``fedback/<name>`` with ``args`` as its stats."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **args)


def scope(name: str):
    """Device scope ``fedback/<name>`` for the ops traced inside it."""
    return jax.named_scope(PREFIX + name)


_lock = threading.Lock()
_users = 0
_open: list = []  # the collector's open span, at most one


def _on_gc(phase: str, info: dict) -> None:
    if phase == "start":
        if not _open:
            s = span("gc", generation=info["generation"])
            s.__enter__()
            _open.append(s)
    elif _open:
        _open.pop().__exit__(None, None, None)


@contextlib.contextmanager
def gc_spans():
    """Record each collector pass as a ``fedback/gc`` span while open.

    Nested and concurrent uses share one ``gc.callbacks`` hook, which
    the last one to leave removes."""
    global _users
    with _lock:
        if _users == 0 and _on_gc not in gc.callbacks:
            gc.callbacks.append(_on_gc)
        _users += 1
    try:
        yield
    finally:
        with _lock:
            _users -= 1
            if _users == 0:
                if _on_gc in gc.callbacks:
                    gc.callbacks.remove(_on_gc)
                if _open:
                    _open.pop().__exit__(None, None, None)
