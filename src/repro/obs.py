"""Named host spans on the profiler's clock.

``span(name, **meta)`` marks one stretch of host work, as a context manager
or as a decorator::

    with obs.span("server.read_slice", tensor=name, start=a, stop=b):
        ...

    @obs.span("dataset.manifest")
    def _write_manifest(self): ...

Spans record only while a JAX profiler collects: ``jax.profiler.trace``,
``start_trace``/``stop_trace``, or a client of ``jax.profiler.start_server``.
There is no other switch.  While none collects a span costs one check of the
profiler's state.  While one collects, a span

* opens a ``jax.profiler.TraceAnnotation`` under its name (``meta`` appears
  as the event's arguments), so it lands in the trace's host plane on the
  same clock as the device's programs, and an idle stretch of the device
  can be read as the innermost span the host was in;
* adds, under its name, to an in-memory table read by :func:`totals`: the
  count, the wall time, and the self time (the wall time less that of the
  spans opened inside it on the same thread).

A span whose name ends in ``.device_wait`` holds one place where the host
blocks on the device (``jax.device_get``, ``np.asarray`` of a device array);
dispatch stays in its parent's self time.  Span names are listed in
``docs/architecture.md`` (Spans).
"""
from __future__ import annotations

import functools
import threading
import time

import jax

try:  # the profiler's own switch; absent in another jaxlib, spans are off
    from jax._src.lib import _profiler as _xla_profiler

    _collecting = _xla_profiler.TraceMe.is_enabled
except (ImportError, AttributeError):
    def _collecting() -> bool:
        return False

_lock = threading.Lock()
_table: dict[str, list] = {}          # name -> [count, total_s, self_s]
_local = threading.local()


def _decorate(name: str, fn):
    @functools.wraps(fn)
    def spanned(*args, **kwargs):
        if not _collecting():
            return fn(*args, **kwargs)
        with _Span(name, {}):
            return fn(*args, **kwargs)

    return spanned


class _Span:
    __slots__ = ("name", "meta", "_ann", "_t0", "_child")

    def __init__(self, name: str, meta: dict):
        self.name = name
        self.meta = meta

    def __call__(self, fn):
        return _decorate(self.name, fn)

    def __enter__(self):
        self._ann = jax.profiler.TraceAnnotation(self.name, **self.meta)
        self._ann.__enter__()
        stack = getattr(_local, "stack", None)
        if stack is None:
            stack = _local.stack = []
        stack.append(self)
        self._child = 0.0
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, et, ev, tb):
        dt = time.perf_counter() - self._t0
        stack = _local.stack
        stack.pop()
        if stack:
            stack[-1]._child += dt
        with _lock:
            row = _table.get(self.name)
            if row is None:
                row = _table[self.name] = [0, 0.0, 0.0]
            row[0] += 1
            row[1] += dt
            row[2] += dt - self._child
        self._ann.__exit__(et, ev, tb)


class _Off:
    """A span made while no profiler collects: entered, it does nothing;
    as a decorator it checks again at each call."""
    __slots__ = ("name",)

    def __init__(self, name: str):
        self.name = name

    def __call__(self, fn):
        return _decorate(self.name, fn)

    def __enter__(self):
        return self

    def __exit__(self, et, ev, tb):
        return None


_OFF: dict = {}                       # name -> its _Off


def span(name: str, **meta):
    """A span named ``name``; see the module's docstring.  As a decorator
    it records the name alone: ``meta`` is for the context manager."""
    if _collecting():
        return _Span(name, meta)
    try:
        return _OFF[name]
    except KeyError:
        off = _OFF[name] = _Off(name)
        return off


def totals() -> dict:
    """``{name: (count, total_s, self_s)}`` of every span recorded since
    the last :func:`reset`."""
    with _lock:
        return {k: tuple(v) for k, v in _table.items()}


def reset() -> None:
    with _lock:
        _table.clear()
