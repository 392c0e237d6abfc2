"""Dispatch core of the tape-compiled distributed data engine.

Every relational/ordering primitive in :mod:`heat_tpu.data` compiles to a
cached ``shard_map`` program — shard-local compute plus a statically
planned exchange (one packed all-reduce for groupby, a k-sized psum
exchange for top-k, bisection-count psum rounds for order statistics, the
static-shape all-to-all for the join partition) — and dispatches through
:func:`engine_call`, the data-engine sibling of
``fusion.fit_step_call``:

* programs live in a dedicated :class:`ProgramCache` (``data_engine.*``
  counter mirror), keyed by the caller's structural signature PLUS the
  captured ``fusion.quant_key()/chunk_key()/hier_key()`` tuples, so a
  wire-codec toggle compiles a sibling program instead of reusing one
  traced under the other wire format (the PR 9 deferred-trace
  discipline);
* the ``data.exchange.dispatch`` / ``data.stream.carry`` fault sites fire
  BEFORE the program runs (donated buffers still intact), and any
  build/dispatch failure degrades to the caller's eager reference path
  with identical results, counted in ``data_engine.exchange_fallbacks``
  (or ``data_engine.stream_fallbacks`` for the streaming carry);
* a failure after a donated input buffer was already invalidated
  re-raises — replaying from dead buffers is the PR 8 flush-fallback
  hazard.

Escape hatch: ``HEAT_TPU_DATA_ENGINE=0`` (or :func:`override`) disables
the compiled paths; every caller runs its eager reference instead and
``ht.percentile``/``ht.median`` stay on the merge-split sort path.
"""

from __future__ import annotations

import os
from contextlib import contextmanager

from ..utils import metrics
from ..utils import faults as _faults
from ..utils.program_cache import ProgramCache

__all__ = ["enabled", "override", "engine_call", "program_cache",
           "stats", "reset", "DATA_ENGINE_COUNTERS"]


def _env_on(name: str, default: str = "1") -> bool:
    return os.environ.get(name, default) not in ("", "0", "false", "False")


_ENABLED = _env_on("HEAT_TPU_DATA_ENGINE")

# every counter the engine may tick — the serve/metrics aggregation and
# the stats() snapshot init from this tuple so a missing counter reads 0
# instead of KeyError'ing a dashboard (the PR 7 stats-key drift lesson)
DATA_ENGINE_COUNTERS = (
    "data_engine.dispatches",
    "data_engine.exchange_fallbacks",
    "data_engine.stream_chunks",
    "data_engine.stream_fallbacks",
    "data_engine.groupby_calls",
    "data_engine.topk_calls",
    "data_engine.quantile_calls",
    "data_engine.join_calls",
)

_CACHE = ProgramCache("data_engine", counter_prefix="data_engine")


def enabled() -> bool:
    """True when the compiled data-engine paths are active."""
    return _ENABLED


@contextmanager
def override(flag: bool):
    """Temporarily force the engine on/off (tests; mirrors the
    ``HEAT_TPU_DATA_ENGINE`` env gate)."""
    global _ENABLED
    prev = _ENABLED
    _ENABLED = bool(flag)
    try:
        yield
    finally:
        _ENABLED = prev


def program_cache() -> ProgramCache:
    return _CACHE


def engine_call(key, build, args, eager, *, site="data.exchange.dispatch",
                fallback_counter="data_engine.exchange_fallbacks"):
    """Dispatch ONE compiled data-engine program through the cache.

    ``key`` is the caller's structural signature (physical shapes, dtypes,
    logical sizes, the communicator cache key); the full program key
    appends the captured wire-codec tuples. ``build(qk, ck, hk)`` returns
    the compiled callable and must PIN the captured tuples into any
    ``packed_psum`` it traces. ``eager(*args)`` replays the same
    mathematics without the compiled program — the degrade path of the
    ``site`` fault and of real compile/dispatch failures.
    """
    from ..core import fusion

    qk, ck, hk = fusion.quant_key(), fusion.chunk_key(), fusion.hier_key()
    full_key = ("data",) + tuple(key) + (qk, ck, hk)
    try:
        prog = _CACHE.get_custom(full_key, lambda: build(qk, ck, hk))
        _faults.check(site)
        fusion.refuse_deleted(args, "data.engine_call")
        out = prog(*args)
    except Exception:
        for a in args:
            if getattr(a, "is_deleted", lambda: False)():
                raise  # donated buffer already invalidated — no replay
        metrics.inc(fallback_counter)
        return eager(*args)
    metrics.inc("data_engine.dispatches")
    return out


def stats() -> dict:
    """Data-engine snapshot (folded into ``ht.runtime_stats()`` under the
    ``"data_engine"`` key — shape pinned by ``tests/test_stats_contract``)."""
    c = metrics.counters()
    short = {k.split(".", 1)[1]: int(c.get(k, 0))
             for k in DATA_ENGINE_COUNTERS}
    return {"enabled": _ENABLED, **short, "program_cache": _CACHE.stats()}


def reset() -> None:
    """Drop every cached program (tests: the drop-caches-at-teardown
    executable-budget discipline)."""
    _CACHE.reset()
