"""Profiling: the ONE module through which the program names its work.

The reference has **no tracing/profiling support** (SURVEY.md §5 —
benchmarks use bare ``time.perf_counter``). Here four things carry names,
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
* **Compiler boundary** — every trace, lowering and backend compile JAX
  makes becomes a record: ``jit.trace``, ``jit.lower`` and ``jit.compile``,
  each with ``fun`` (JAX's name of the function: ``g``, ``jit(g)``), under
  the recording thread's current span. A ``jit.compile`` also says what
  JAX's persistent cache did: ``cache`` is ``hit`` (loaded), ``miss``
  (looked up, not found, compiled; ``writable``, whether JAX then wrote it,
  which it does when the compile reached ``jax_persistent_cache_min_compile_time_secs`` as it stood then:
  only such a program should load in the next process) or ``off`` (no cache
  directory, or no lookup). They come from ``jax.monitoring`` listeners, and
  JAX reports a compile only AFTER it is over, with its start and end on
  ``time.time()``: no ``TraceAnnotation`` can be opened around it, so these
  are records alone, put on the ring's clock by the offset between the two
  clocks read as JAX reports each (an offset read once drifts as the wall
  clock is slewed). They are kept apart from the ring, one a compile event
  and at most ``RING_SIZE``, so that a long run's spans never push set-up's
  out, and :func:`spans` returns both; each carries ``n``, its place among
  them since :func:`clear`, so that a reader can tell whether it has all.
* **Operator's tools** — :func:`trace` / :func:`start_trace` /
  :func:`stop_trace` (TensorBoard/XProf device traces) and :class:`Timer`
  (a wall timer that syncs, so users do not time the dispatch).

Recording is ON while a ``jax.profiler`` session is active (however it was
started: ``trace`` here, a bare ``jax.profiler.trace``, a benchmark's
``--trace 1``) or after :func:`enable`. OFF, :func:`span` and :func:`begin`
return one shared do-nothing object: no allocation, no ``TraceAnnotation``;
the compile listeners return at their first test, and JAX calls them only
when something compiles, never on a step.
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
# JAX's compile events (jax/_src/dispatch.py, jax/_src/compiler.py) and the
# ring's names for them
_COMPILE_SPANS = {"/jax/core/compile/jaxpr_trace_duration": "jit.trace",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration": "jit.lower",
                  "/jax/core/compile/backend_compile_duration": "jit.compile"}
_CACHE_EVENTS = {"/jax/compilation_cache/compile_requests_use_cache": "lookup",
                 "/jax/compilation_cache/cache_hits": "hit",
                 # raised when JAX WRITES an entry, not on every miss
                 "/jax/compilation_cache/cache_misses": "written"}


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
# the jit.* records: out of the ring's reach, one a trace, lowering or compile
_compiles: "collections.deque[SpanRecord]" = collections.deque(maxlen=RING_SIZE)
_n_compiles = 0


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
    """A snapshot of the ring and the ``jit.*`` records, by end time."""
    with _lock:
        return sorted(itertools.chain(_ring, _compiles), key=lambda r: r.t1)


def dropped() -> int:
    """Records pushed out of the full ring since the last :func:`clear`."""
    return _dropped


def clear() -> None:
    global _dropped, _n_compiles
    with _lock:
        _ring.clear()
        _compiles.clear()
        _dropped = 0
        _n_compiles = 0


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


# ---------------------------------------------------------------------- #
# compiler boundary: JAX's compile events, after the fact                #
# ---------------------------------------------------------------------- #
def _cache_seen() -> dict:
    """This thread's persistent-cache events since its last ``jit.compile``:
    what -> ``time.time()``."""
    try:
        return _tls.cache
    except AttributeError:
        _tls.cache = {}
        return _tls.cache


def _on_cache_event(event: str, **_kw) -> None:
    if not (_enabled or _state.profile_session is not None):
        return
    what = _CACHE_EVENTS.get(event)
    if what is not None:
        _cache_seen()[what] = time.time()


def _on_compile_span(event: str, start: float, end: float,
                     fun_name: str = "", **_kw) -> None:
    global _n_compiles
    if not (_enabled or _state.profile_session is not None):
        return
    name = _COMPILE_SPANS.get(event)
    if name is None:
        return
    attrs = {"fun": fun_name}
    if name == "jit.compile":
        # the cache events this thread raised inside this compile's interval
        seen, _tls.cache = _cache_seen(), {}
        inside = {k for k, t in seen.items() if start <= t <= end}
        if "hit" in inside:
            attrs["cache"] = "hit"
        elif "lookup" in inside and jax.config.jax_compilation_cache_dir:
            # (JAX computes a key and "looks up" with no directory too)
            attrs["cache"] = "miss"
            attrs["writable"] = "written" in inside
        else:
            attrs["cache"] = "off"
    to_perf = time.perf_counter() - time.time()
    stack = _stack()
    parent = stack[-1].id if stack else 0
    with _lock:
        _n_compiles += 1
        attrs["n"] = _n_compiles
        _compiles.append(SpanRecord(
            name, start + to_perf, end + to_perf, next(_ids), parent,
            threading.get_ident(), attrs, ()))


jax.monitoring.register_event_time_span_listener(_on_compile_span)
jax.monitoring.register_event_listener(_on_cache_event)


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
