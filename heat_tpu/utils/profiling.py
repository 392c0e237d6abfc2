"""Profiling: the ONE module through which the program names its work.

The reference has **no tracing/profiling support** (SURVEY.md §5 —
benchmarks use bare ``time.perf_counter``). Here three things carry names,
and every one of them goes through this module (``grep named_scope
TraceAnnotation heat_tpu/`` finds only this file):

* **Device side** — :func:`scope` is ``jax.named_scope``: used inside traced
  functions, it lands in each HLO operation's ``op_name`` and so in the
  device trace's event metadata (``tf_op``), where ``perfbench/trace_scopes``
  groups device time by it. :func:`named` gives a callable the ``__name__``
  its jitted module carries (``jit_train_step``). Both are metadata only:
  nothing a program computes changes.
* **Host side** — :func:`span` is a host span on two clocks at once: a
  ``jax.profiler.TraceAnnotation("ht.<name>")`` on the profiler's clock (the
  host plane of the device trace) and a record ``(name, t0, t1, id,
  parent_id, thread, attrs, events)`` in a bounded in-memory ring, on
  ``time.perf_counter()``. :func:`begin` opens a span that may end on
  another thread (a request: submitted by a client, finished by the engine's
  worker); those live in the ring only, because a ``TraceAnnotation`` belongs
  to one thread.
* **Operator's tools** — :func:`trace` / :func:`start_trace` /
  :func:`stop_trace` (TensorBoard/XProf device traces) and :class:`Timer`
  (a wall timer that syncs, so users do not time the dispatch).

Recording is ON while a ``jax.profiler`` session is active (however it was
started: ``trace`` here, a bare ``jax.profiler.trace``, a benchmark's
``--trace 1``) or after :func:`enable`. OFF, :func:`span` and :func:`begin`
return one shared do-nothing object: no allocation, no ``TraceAnnotation``.
Counters are not here: ``utils/metrics.py`` is the one registry.
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import threading
import time
from typing import NamedTuple, Optional, Tuple

import jax

__all__ = ["trace", "start_trace", "stop_trace", "Timer",
           "scope", "named", "span", "begin", "enable", "disable",
           "recording", "session_active", "spans", "clear", "dropped",
           "SpanRecord", "RING_SIZE", "SPAN_PREFIX"]

SPAN_PREFIX = "ht."     # host-plane name of a span: "ht." + name
RING_SIZE = 65536       # records kept; older ones drop (counted)


def start_trace(logdir: str) -> None:
    """Begin a device trace viewable in TensorBoard/XProf."""
    jax.profiler.start_trace(logdir)


def stop_trace() -> None:
    jax.profiler.stop_trace()


@contextlib.contextmanager
def trace(logdir: str):
    """Context manager around a device trace."""
    start_trace(logdir)
    try:
        yield
    finally:
        stop_trace()


# ---------------------------------------------------------------------- #
# device side: names inside the programs                                 #
# ---------------------------------------------------------------------- #
def scope(name: str):
    """``jax.named_scope(name)``: for use inside traced functions, as
    ``with scope("loss"):`` around a passage or ``@scope("mlp")`` on a whole
    function. The name joins the ``op_name`` of every operation traced
    under it."""
    return jax.named_scope(name)


def named(fn, name: str):
    """``fn`` with ``__name__`` set: ``jax.jit`` calls the module
    ``jit_<name>``, which the trace's ``XLA Modules`` line then carries.
    A family is a name; no shape or hash belongs in it."""
    fn.__name__ = fn.__qualname__ = name
    return fn


# ---------------------------------------------------------------------- #
# host side: spans                                                       #
# ---------------------------------------------------------------------- #
class SpanRecord(NamedTuple):
    name: str
    t0: float               # time.perf_counter()
    t1: float
    id: int
    parent_id: int          # 0: no enclosing span
    thread: int             # threading.get_ident() of the thread that ended it
    attrs: dict
    events: Tuple[Tuple[str, float], ...]   # (name, perf_counter) stamps


class _NullSpan:
    """What :func:`span` returns while recording is off. ONE instance."""

    __slots__ = ()
    id = 0

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def event(self, name: str) -> None:
        pass

    def set(self, **attrs) -> None:
        pass

    def end(self) -> None:
        pass


_NULL = _NullSpan()
_enabled = False
_ring: "collections.deque[SpanRecord]" = collections.deque(maxlen=RING_SIZE)
_lock = threading.Lock()
_dropped = 0
_ids = itertools.count(1)
_tls = threading.local()


def _find_profile_state():
    """jax keeps the active profiler session in a private place; this is
    the one spot that knows where (tests/test_tracing.py fails if a JAX
    moves it, and the program then records only after ``enable()``)."""
    try:
        from jax._src.profiler import _profile_state
        _profile_state.profile_session
        return _profile_state
    except Exception:
        class _NoState:
            profile_session = None
        return _NoState()


_state = _find_profile_state()


def session_active() -> bool:
    """Whether a ``jax.profiler`` session is recording in this process."""
    return _state.profile_session is not None


def recording() -> bool:
    return _enabled or _state.profile_session is not None


def enable() -> None:
    """Record spans into the ring from now on, profiler session or not."""
    global _enabled
    _enabled = True


def disable() -> None:
    global _enabled
    _enabled = False


def spans() -> list:
    """A snapshot of the ring, oldest first."""
    with _lock:
        return list(_ring)


def dropped() -> int:
    """Records pushed out of the full ring since the last :func:`clear`."""
    return _dropped


def clear() -> None:
    global _dropped
    with _lock:
        _ring.clear()
        _dropped = 0


def _stack() -> list:
    try:
        return _tls.stack
    except AttributeError:
        _tls.stack = []
        return _tls.stack


class _Span:
    __slots__ = ("name", "attrs", "id", "parent_id", "t0", "events", "_ann")

    def __init__(self, name: str, attrs: dict, parent_id: int = 0):
        self.name = name
        self.attrs = attrs
        self.id = next(_ids)
        self.parent_id = parent_id
        self.events = []
        self._ann = None

    # `with span(...)`: nests under this thread's current span, and is on
    # the profiler's host plane as well
    def __enter__(self):
        stack = _stack()
        if stack:
            self.parent_id = stack[-1].id
        stack.append(self)
        self._ann = jax.profiler.TraceAnnotation(SPAN_PREFIX + self.name,
                                                 **self.attrs)
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        stack = _stack()
        if stack and stack[-1] is self:
            stack.pop()
        self._record(t1)
        return False

    def event(self, name: str) -> None:
        """Stamp a time inside this span (a token's arrival)."""
        self.events.append((name, time.perf_counter()))

    def set(self, **attrs) -> None:
        """Attributes known only once the work is under way (`hit`). They
        reach the ring; the host plane has what `span()` was given."""
        self.attrs.update(attrs)

    def end(self) -> None:
        """Close a span opened with :func:`begin`, from any thread. A
        second ``end()`` does nothing."""
        if self._ann is None:
            self._ann = False
            self._record(time.perf_counter())

    def _record(self, t1: float) -> None:
        global _dropped
        rec = SpanRecord(self.name, self.t0, t1, self.id, self.parent_id,
                         threading.get_ident(), self.attrs,
                         tuple(self.events))
        with _lock:
            if len(_ring) == _ring.maxlen:
                _dropped += 1
            _ring.append(rec)


def span(name: str, **attrs):
    """A host span around the work it encloses: ``with span("flush",
    n_nodes=7) as sp: ...``. Off: the shared null object."""
    if not (_enabled or _state.profile_session is not None):
        return _NULL
    return _Span(name, attrs)


def begin(name: str, parent=None, **attrs):
    """Open a span that is closed by ``.end()``, on whichever thread gets
    there: a request's life from ``submit`` to its last token. ``parent`` is
    the span it belongs to (spans of one request also share
    ``attrs["rid"]``). Ring only. Off: the shared null object."""
    if not recording():
        return _NULL
    sp = _Span(name, attrs, parent.id if parent is not None else 0)
    sp.t0 = time.perf_counter()
    return sp


class Timer:
    """Device-synchronized wall timer.

    >>> with Timer("kmeans-epoch") as t:
    ...     result = step(x, c)
    ...     t.sync(result)
    >>> t.seconds
    """

    def __init__(self, name: str = ""):
        self.name = name
        self.seconds: Optional[float] = None
        self._sync_target = None

    def sync(self, value) -> None:
        self._sync_target = value

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if self._sync_target is not None:
            jax.block_until_ready(self._sync_target)
        self.seconds = time.perf_counter() - self._t0
        return False
