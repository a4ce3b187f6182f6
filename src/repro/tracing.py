"""Host spans and counters, recorded while a JAX profiler session collects.

The recorder is on exactly while ``jax.profiler.TraceAnnotation
.is_enabled()`` is true: inside ``jax.profiler.trace(...)``, between
``start_trace`` and ``stop_trace``, or while an operator's profiler is
attached to ``jax.profiler.start_server(port)``.  There is no other
switch.  While on, :func:`span` does two things:

* it opens a ``jax.profiler.TraceAnnotation`` of the same name, so the
  span lands in the profiler trace on the same clock as the device's
  ``XLA Ops``;
* it appends ``(id, name, start_ns, end_ns, parent, call_id, attrs)`` to
  a bounded in-memory buffer, stamped with ``time.time_ns()`` (the wall
  clock the profiler's host events use, so buffer and trace differ by
  one constant).  Past :data:`CAPACITY` records it counts ``dropped``
  instead.

``parent`` is the id of the span open around it on the same thread, and
``call_id`` the id of the outermost (root) span of that thread's entry
call, shared by every span of the call.  Work handed to another thread
takes its caller's context along with :func:`current` and :func:`carry`.

Two counters go into the same buffer, as spans, under the same rule:
``jax.compile`` (a jaxpr trace or a backend compile, from
``jax.monitoring``; ``attrs["event"]`` names which) and ``python.gc`` (a
garbage-collector pass, from ``gc.callbacks``).  Both hooks are
installed once, on import, and are a flag check while off.

While off, :func:`span` costs one ``is_enabled()`` call and returns a
shared no-op.  :func:`snapshot` reads the buffer and :func:`reset`
clears it; the profiler trace is the export.
"""

from __future__ import annotations

import gc
import itertools
import threading
import time

import jax
from jax import monitoring

CAPACITY = 1 << 18

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")

_annotation = jax.profiler.TraceAnnotation
_enabled = _annotation.is_enabled
_ids = itertools.count(1)
_lock = threading.RLock()
_local = threading.local()
_buf = []
_dropped = 0


class _Noop:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOOP = _Noop()


def _stack():
    st = getattr(_local, "stack", None)
    if st is None:
        st = _local.stack = []
    return st


def _record(rec):
    global _dropped
    with _lock:
        if len(_buf) < CAPACITY:
            _buf.append(rec)
        else:
            _dropped += 1


class _Span:
    __slots__ = ("name", "attrs", "id", "parent", "call_id", "start",
                 "_close")

    def __init__(self, name, attrs):
        self.name = name
        self.attrs = attrs

    def __enter__(self):
        st = _stack()
        self.id = next(_ids)
        self.parent, self.call_id = st[-1] if st else (None, self.id)
        st.append((self.id, self.call_id))
        ann = _annotation(self.name)
        # bound ahead: nothing between the two clocks' readings may
        # allocate, or a garbage collection could fall between them
        self._close = ann.__exit__
        ann.__enter__()
        self.start = time.time_ns()
        return self

    def __exit__(self, *exc):
        end = time.time_ns()
        self._close(None, None, None)
        _stack().pop()
        _record((self.id, self.name, self.start, end, self.parent,
                 self.call_id, self.attrs))
        return False


def span(name, **attrs):
    """Context manager timing ``name`` while a profiler session collects.

    A span opened with no span around it on its thread is a root: it
    allocates the ``call_id`` its descendants share."""
    if not _enabled():
        return _NOOP
    return _Span(name, attrs)


def current():
    """The calling thread's innermost open span as ``(id, call_id)``, or
    ``None`` (nothing open, or the recorder off)."""
    st = getattr(_local, "stack", None)
    return st[-1] if st else None


class _Carry:
    __slots__ = ("ctx",)

    def __init__(self, ctx):
        self.ctx = ctx

    def __enter__(self):
        _stack().append(self.ctx)
        return self

    def __exit__(self, *exc):
        _stack().pop()
        return False


def carry(ctx):
    """Run the ``with`` body under ``ctx`` (from :func:`current` on the
    handing-off thread): its spans take that span as parent and share
    its ``call_id``."""
    return _NOOP if ctx is None else _Carry(ctx)


def _add_span(name, start_ns, end_ns, attrs):
    parent, call_id = current() or (None, None)
    _record((next(_ids), name, start_ns, end_ns, parent, call_id, attrs))


def _on_time_span(event, start, end, **kwargs):
    if event in COMPILE_EVENTS and _enabled():
        _add_span("jax.compile", int(start * 1e9), int(end * 1e9),
                  {"event": event, **kwargs})


def _on_gc(phase, info):
    if phase == "start":
        if _enabled():
            ann = _annotation("python.gc")
            ann.__enter__()
            _local.gc = (ann.__exit__, time.time_ns())
        return
    pending = getattr(_local, "gc", None)
    if pending is not None:
        _local.gc = None
        close, start = pending
        end = time.time_ns()
        close(None, None, None)
        _add_span("python.gc", start, end,
                  {"generation": info["generation"],
                   "collected": info["collected"]})


monitoring.register_event_time_span_listener(_on_time_span)
gc.callbacks.append(_on_gc)


def snapshot():
    """``{"spans": [...], "dropped": n}``: every record in the buffer, in
    the order each ended, as dicts with keys ``id``, ``name``,
    ``start_ns``, ``end_ns``, ``parent``, ``call_id`` and ``attrs``."""
    with _lock:
        recs, dropped = list(_buf), _dropped
    keys = ("id", "name", "start_ns", "end_ns", "parent", "call_id",
            "attrs")
    return {"spans": [dict(zip(keys, r)) for r in recs], "dropped": dropped}


def reset():
    """Empty the buffer and zero ``dropped``."""
    global _dropped
    with _lock:
        _buf.clear()
        _dropped = 0
