"""Node-wide flight recorder: lightweight span/event tracing into a
bounded in-memory ring buffer.

Prometheus metrics (``libs/metrics``) answer "how much / how fast on
average"; this module answers "where did THIS block's latency go".  Every
subsystem on the commit path emits spans (an interval with a duration:
a consensus step, an ABCI call, a scheduler dispatch) and events (a
point: a WAL fsync, a micro-batch flush, a kernel first-dispatch) into
one process-wide ring, and the RPC server dumps it as JSON via
``GET /dump_trace?limit=N`` — so a single trace of height H shows the
verify micro-batches the vote scheduler ran inside the prevote span.

Design constraints, in order:

- **Disabled means free.**  Tracing is off unless
  ``[instrumentation] tracing = true`` **or a JAX profiler session is
  live in the process** (:func:`is_enabled`): whoever captures a device
  profile finds the same interval in ``/dump_trace``, on the clock the
  profile's host spans share.  Off, ``event()`` returns after one check;
  ``span()`` returns one shared no-op context manager (no per-call
  allocation); ``begin()`` returns None and ``finish(None)`` is a
  no-op.  Hot paths may additionally guard with :func:`is_enabled` to
  skip building attrs at all.
- **Thread/asyncio-safe without locks on the emit path.**  Records are
  single ``deque.append`` calls (atomic under the GIL) of fully-built
  tuples, and ids come from ``itertools.count`` (also atomic) — writers
  on the event loop, scheduler worker threads, and the device-owner
  thread never contend or tear.
- **Bounded memory.**  The ring is a ``deque(maxlen=N)``; old records
  fall off the back.  N is ``[instrumentation] tracing_ring_size``.

Span classification (see ``docs/explanation/observability.md``): records carry
a ``sub`` (subsystem: ``consensus``, ``abci``, ``crypto.sched``,
``crypto.kernel``, ``wal``, ``mempool``), a ``name`` (one word: ``step``,
``call``, ``dispatch``, ``fsync``...), and free-form ``attrs``.  Spans
opened with the :func:`span` context manager propagate their id through
a ``ContextVar`` so lexically nested spans record a ``parent`` id;
long-lived spans that cross handler boundaries (consensus steps) use
:func:`begin`/:func:`finish` directly and correlate by time + attrs.
"""

from __future__ import annotations

import itertools
import sys
import threading
import time
from collections import deque
from contextvars import ContextVar

from . import clock as _clock

_ENABLED = False
_MAXLEN = 8192
_RING: deque = deque(maxlen=_MAXLEN)
_SEQ = itertools.count(1)
_CUR: ContextVar[int] = ContextVar("tracing_cur_span", default=0)
_CONF_LOCK = threading.Lock()

# record tuples: (kind, id, parent, sub, name, wall_ns, start_ns, end_ns,
# attrs) — built whole, appended once (no partially-visible records)


_TRACEME = None     # jax's TraceMe class, once jax is imported (False: none)


def _profiling() -> bool:
    """Is a JAX profiler session live in this process?  Resolved lazily
    and only once ``jax`` is already imported, so this module imports
    nothing heavy and a process without JAX reads False."""
    global _TRACEME
    tm = _TRACEME
    if tm is None:
        if "jax" not in sys.modules:
            return False
        try:
            from jax._src.lib import _profiler
            tm = _profiler.TraceMe
        except (ImportError, AttributeError):   # a JAX that moved it
            tm = False
        _TRACEME = tm
    return bool(tm) and tm.is_enabled()


def is_enabled() -> bool:
    """Recording: configured on, or a JAX profiler session is live.
    Also the gate for call sites that would otherwise build attrs dicts
    or format values just to have ``event()`` drop them."""
    return _ENABLED or _profiling()


def configure(enabled: bool | None = None,
              ring_size: int | None = None) -> None:
    """Install the node config: flip tracing on/off and/or resize the
    ring (existing records are kept up to the new bound).  Process-wide —
    in-proc ensembles share one flight recorder, records carry a
    ``node`` attr where it matters."""
    global _ENABLED, _RING, _MAXLEN
    with _CONF_LOCK:
        if ring_size is not None:
            size = max(16, int(ring_size))
            if size != _MAXLEN:
                _MAXLEN = size
                _RING = deque(_RING, maxlen=size)
        if enabled is not None:
            _ENABLED = bool(enabled)


def clear() -> None:
    _RING.clear()


# ------------------------------------------------------------------ emit


class _Open:
    """An in-flight span: handed out by :func:`begin`, turned into a ring
    record by :func:`finish`.  Nothing is visible in the ring until the
    span closes (a mid-span ``/dump_trace`` shows completed work only)."""

    __slots__ = ("id", "parent", "sub", "name", "attrs", "t0", "wall0")


def begin(sub: str, name: str, **attrs) -> "_Open | None":
    """Open a span that outlives the current stack frame (consensus
    steps span many handler invocations) or changes thread (its parent
    is the opener's current span).  Returns None when disabled —
    :func:`finish` accepts it."""
    if not (_ENABLED or _profiling()):
        return None
    o = _Open.__new__(_Open)
    o.id = next(_SEQ)
    o.parent = _CUR.get()
    o.sub = sub
    o.name = name
    o.attrs = attrs
    # stamps ride the clock seam: under the sim's virtual clock the
    # ring orders by VIRTUAL time, which is what makes the scenario
    # lab's timeline verdicts a pure function of the seed.  With no
    # clock installed these are the exact raw calls they replace.
    if _clock._CLOCK is None:
        o.wall0 = time.time_ns()
        o.t0 = time.monotonic_ns()
    else:
        o.wall0 = _clock.walltime_ns()
        o.t0 = _clock.monotonic_ns()
    return o


def finish(open_: "_Open | None", **extra) -> None:
    """Close a span from :func:`begin`; ``extra`` attrs merge in (e.g.
    the verdict that was only known at the end)."""
    if open_ is None:
        return
    end = time.monotonic_ns() if _clock._CLOCK is None \
        else _clock.monotonic_ns()
    if extra:
        open_.attrs.update(extra)
    _RING.append(("span", open_.id, open_.parent, open_.sub, open_.name,
                  open_.wall0, open_.t0, end, open_.attrs))


def event(sub: str, name: str, **attrs) -> None:
    """Fire-and-forget point event."""
    if not (_ENABLED or _profiling()):
        return
    if _clock._CLOCK is None:
        wall, t = time.time_ns(), time.monotonic_ns()
    else:
        wall, t = _clock.walltime_ns(), _clock.monotonic_ns()
    _RING.append(("event", next(_SEQ), _CUR.get(), sub, name,
                  wall, t, t, attrs))


class _SpanCM:
    """Context-manager span: sets itself as the current parent for the
    duration so nested ``span()``/``event()`` calls record ``parent``.
    While a profiler session is live it also writes itself into the
    profile as a ``TraceMe`` named ``<sub>:<name>``, beside the device
    ops on the profiler's own clock.  ``with span(...) as sp`` hands
    the open span out (None when off) for attrs only known at the end;
    a span opened with ``ok=True`` closes with ``ok=False`` when its
    scope raises."""

    __slots__ = ("_sub", "_name", "_attrs", "_open", "_tok", "_traceme")

    def __init__(self, sub, name, attrs):
        self._sub = sub
        self._name = name
        self._attrs = attrs
        self._open = None
        self._tok = None
        self._traceme = None

    def __enter__(self):
        self._open = begin(self._sub, self._name, **self._attrs)
        if self._open is not None:
            self._tok = _CUR.set(self._open.id)
            if _profiling():
                self._traceme = _TRACEME(f"{self._sub}:{self._name}")
                self._traceme.__enter__()
        return self._open

    def __exit__(self, *exc):
        if self._open is not None:
            if self._traceme is not None:
                self._traceme.__exit__(*exc)
            _CUR.reset(self._tok)
            if exc[0] is not None and "ok" in self._open.attrs:
                self._open.attrs["ok"] = False
            finish(self._open)
        return False


class _NoopSpan:
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NOOP = _NoopSpan()


def span(sub: str, name: str, **attrs):
    """Context manager measuring one lexical scope.  Disabled tracing
    returns a shared no-op instance — zero per-call allocation."""
    if not (_ENABLED or _profiling()):
        return _NOOP
    return _SpanCM(sub, name, attrs)


# ------------------------------------------------------------------ dump


def _jsonable(v):
    if isinstance(v, (bytes, bytearray)):
        return v.hex()
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    return repr(v)


def _to_dict(rec) -> dict:
    kind, rid, parent, sub, name, wall0, t0, t1, attrs = rec
    return {
        "kind": kind, "id": rid, "parent": parent,
        "sub": sub, "name": name,
        "wall_ns": wall0,            # wall clock at start (cross-node)
        "start_ns": t0,              # monotonic: orders records
        "end_ns": t1,
        "dur_us": (t1 - t0) // 1000,
        "attrs": {k: _jsonable(v) for k, v in attrs.items()},
    }


def _rec_matches_height(attrs: dict, height: int) -> bool:
    """A record belongs to ``height`` when it stamps ``height`` exactly
    or its ``h_lo``..``h_hi`` window (batched emitters: a scheduler
    dispatch mixing heights) covers it."""
    h = attrs.get("height")
    if h is not None:
        return h == height
    lo, hi = attrs.get("h_lo"), attrs.get("h_hi")
    if lo is not None and hi is not None:
        return lo <= height <= hi
    return False


def snapshot() -> list[tuple]:
    """The raw ring as a list (newest last) — the zero-copy input for
    ``libs/timeline``; each element is the record tuple documented at
    the top of this module."""
    return list(_RING)


def dump(limit: int = 1000, sub: str | None = None,
         height: int | None = None) -> list[dict]:
    """The newest ``limit`` COMPLETED records (``limit <= 0``: the whole
    ring) as JSON-able dicts, in completion order — sort by ``start_ns``
    to reconstruct the timeline, since spans append at finish.  ``sub``
    keeps one subsystem's records; ``height`` keeps records stamped with
    that height (exactly, or inside their ``h_lo``..``h_hi`` window).
    Filters apply BEFORE the limit, so ``limit=100&height=H`` is the
    newest 100 records OF that height."""
    recs = list(_RING)               # snapshot: writers keep appending
    if sub is not None:
        recs = [r for r in recs if r[3] == sub]
    if height is not None:
        h = int(height)
        recs = [r for r in recs if _rec_matches_height(r[8], h)]
    if limit and int(limit) > 0:
        recs = recs[-int(limit):]
    return [_to_dict(r) for r in recs]


def stats() -> dict:
    """``enabled`` is the configuration; ``recording`` also follows a
    live profiler session."""
    return {"enabled": _ENABLED, "recording": is_enabled(),
            "ring_size": _MAXLEN, "buffered": len(_RING)}
