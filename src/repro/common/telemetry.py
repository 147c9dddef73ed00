"""In-program spans and counters of the AL server.

One process-global recorder, as ``kernels.pairwise.ops`` keeps its op
counts and JAX its monitoring listeners:

* ``span(name)`` times one operation. It opens
  ``jax.profiler.TraceAnnotation(f"alaas.{name}")``, so that a profiler
  trace shows the span on its host plane on the device ops' clock, and
  appends an :class:`Event` to a bounded ring on ``time.perf_counter``'s
  clock. The parent span and the request id come from a thread-local
  stack; work handed to another thread carries them with ``context()``
  and ``attached(ctx)``.
* ``count(name, n)`` adds to a counter and appends the increment to the
  same ring, so that a reader can take the counts of a stretch of time.
* a ``jax.monitoring`` listener, registered at import, adds every backend
  compile to ``compile.count`` and ``compile.s`` and charges it to the
  innermost span open on the compiling thread (one ``compile.s`` event
  per compile in the ring).
* ``snapshot()`` aggregates: per span its count, total and self seconds
  (self = the span less its children on the same thread), the counters,
  and the compiles per span. ``events()`` is the ring.

The ring holds ``RING_SIZE`` events; a full ring drops its oldest event and
counts it in ``trace.dropped``. It never blocks and never grows. Spans are
per operation (a query, a push, a batch), never per row or per pick.
``set_enabled(False)`` turns ``span`` and ``count`` into one branch each.
"""
from __future__ import annotations

import collections
import contextlib
import functools
import threading
import time
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

PREFIX = "alaas."
RING_SIZE = 131072
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
NO_SPAN = "(none)"


class Event(NamedTuple):
    """One ring entry. A span has ``value`` None and ``t0 <= t1``; a
    counter increment has ``t0 == t1`` and its increment in ``value``.
    ``parent`` is the enclosing span's name, ``thread`` the recording
    thread's name."""
    name: str
    t0: float
    t1: float
    parent: Optional[str]
    request_id: Optional[str]
    thread: str
    value: Optional[float] = None


# a stack frame: [name, t0 (None for a frame carried from another
# thread), children's seconds on this thread, request id]
_NAME, _T0, _CHILD, _RID = range(4)

_enabled = True
_lock = threading.Lock()
_local = threading.local()
_ring: collections.deque = collections.deque(maxlen=RING_SIZE)
_spans: Dict[str, list] = {}           # name -> [count, total_s, self_s]
_counters: Dict[str, float] = {}
_compiles: Dict[str, list] = {}        # innermost span -> [count, seconds]


def _stack() -> list:
    st = getattr(_local, "stack", None)
    if st is None:
        st = _local.stack = []
    return st


def _append(ev: Event) -> None:
    """Caller holds ``_lock``."""
    if len(_ring) == _ring.maxlen:
        _counters["trace.dropped"] = _counters.get("trace.dropped", 0) + 1
    _ring.append(ev)


class _Span:
    __slots__ = ("name", "rid", "ann")

    def __init__(self, name: str, rid: Optional[str], attrs: dict):
        self.name = PREFIX + name
        self.rid = rid
        self.ann = jax.profiler.TraceAnnotation(self.name, **attrs)

    def __enter__(self):
        st = _stack()
        rid = self.rid
        if rid is None and st:
            rid = st[-1][_RID]
        self.ann.__enter__()
        st.append([self.name, time.perf_counter(), 0.0, rid])
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        st = _stack()
        frame = st.pop()
        self.ann.__exit__(*exc)
        dur = t1 - frame[_T0]
        parent = None
        if st:
            st[-1][_CHILD] += dur
            parent = st[-1][_NAME]
        with _lock:
            agg = _spans.get(self.name)
            if agg is None:
                agg = _spans[self.name] = [0, 0.0, 0.0]
            agg[0] += 1
            agg[1] += dur
            agg[2] += dur - frame[_CHILD]
            _append(Event(self.name, frame[_T0], t1, parent, frame[_RID],
                          threading.current_thread().name))
        return False


_OFF = contextlib.nullcontext()


def span(name: str, request_id: Optional[str] = None, **attrs):
    """Time one operation as the span ``alaas.<name>``. ``request_id``
    names the request it serves (spans inside inherit it); ``attrs`` go to
    the profiler annotation only."""
    if not _enabled:
        return _OFF
    return _Span(name, request_id, attrs)


def traced(name: str):
    """Decorator: run the function inside ``span(name)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)
        return inner
    return wrap


def count(name: str, n: float = 1) -> None:
    """Add ``n`` to the counter ``name``."""
    if not _enabled:
        return
    t = time.perf_counter()
    st = _stack()
    top = st[-1] if st else None
    with _lock:
        _counters[name] = _counters.get(name, 0) + n
        _append(Event(name, t, t, top[_NAME] if top else None,
                      top[_RID] if top else None,
                      threading.current_thread().name, n))


def h2d(x, dtype=None):
    """``jnp.asarray(x, dtype)``, counting the bytes of a host array it
    uploads in ``h2d_bytes``; a device array passes uncounted."""
    out = jnp.asarray(x, dtype)
    if not isinstance(x, jax.Array):
        count("h2d_bytes", out.nbytes)
    return out


def context() -> Optional[Tuple[str, Optional[str]]]:
    """The calling thread's innermost span and request id, for work it
    hands to another thread (``attached``)."""
    st = _stack()
    return (st[-1][_NAME], st[-1][_RID]) if st else None


@contextlib.contextmanager
def attached(ctx: Optional[Tuple[str, Optional[str]]]):
    """Open spans on this thread as children of ``ctx`` (a ``context()``
    taken on another thread). Their time is not taken from the parent's
    self time, which counts the parent's own thread only."""
    if ctx is None or not _enabled:
        yield
        return
    st = _stack()
    st.append([ctx[0], None, 0.0, ctx[1]])
    try:
        yield
    finally:
        st.pop()


def _on_compile(event: str, duration_s: float, **_kw) -> None:
    if event != COMPILE_EVENT or not _enabled:
        return
    t = time.perf_counter()
    st = _stack()
    top = st[-1] if st else None
    where = top[_NAME] if top else NO_SPAN
    rid = top[_RID] if top else None
    thread = threading.current_thread().name
    with _lock:
        _counters["compile.count"] = _counters.get("compile.count", 0) + 1
        _counters["compile.s"] = _counters.get("compile.s", 0) + duration_s
        agg = _compiles.get(where)
        if agg is None:
            agg = _compiles[where] = [0, 0.0]
        agg[0] += 1
        agg[1] += duration_s
        _append(Event("compile.s", t, t, where, rid, thread, duration_s))


jax.monitoring.register_event_duration_secs_listener(_on_compile)


def set_enabled(on: bool) -> None:
    """Turn recording on or off (on at import)."""
    global _enabled
    _enabled = bool(on)


def enabled() -> bool:
    return _enabled


def snapshot() -> dict:
    """Aggregates since the process started: ``spans`` {name: count,
    total_s, self_s}, ``counters``, ``compiles`` {innermost span: count,
    s}, and the ring's fill."""
    with _lock:
        return {
            "enabled": _enabled,
            "spans": {k: {"count": c, "total_s": t, "self_s": s}
                      for k, (c, t, s) in _spans.items()},
            "counters": dict(_counters),
            "compiles": {k: {"count": c, "s": s}
                         for k, (c, s) in _compiles.items()},
            "ring": {"events": len(_ring), "size": RING_SIZE},
        }


def events() -> list:
    """The ring's events, oldest first."""
    with _lock:
        return list(_ring)

