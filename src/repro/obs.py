"""Spans and counters of Heta's training step, on the profiler's clock.

One recorder for the whole program.  A span times one piece of host work::

    with obs.span("heta.stage.gather"):
        ...

It reads ``time.perf_counter`` once on entry and once on exit, and enters
``jax.profiler.TraceAnnotation(name)`` around that interval, so a profiler
trace shows the span on the host plane, on the same clock as the device's
operations (the root span of a training step, ``heta.step``, enters
``StepTraceAnnotation`` instead).  Spans nest per thread: each closed span
holds its parent and its own (self) time, the duration less what its child
spans cover.  A span takes its training step and its session serial from
its parent unless the caller gives them, so every span of one step carries
the step's global index.  ``scope(step, session)`` binds both for the spans
opened under it on a thread that has no step of its own, such as the sample
stream's producer; it records nothing.

``count(name, n)`` adds ``n`` to a counter, stored against the innermost
open span (and so its step).  ``jax.monitoring``'s backend-compile events
are counted as ``heta.compiles`` the same way, once a session has started.

Everything stays in memory: running totals per session and name, and a
ring of the ``RING`` most recent closed spans.  ``Heta.results()["spans"]``
gives a session's totals; ``window`` gives the closed spans of a run of
training steps, for the benchmark's per-layer readers.

The module imports no jax of its own accord: sampler worker processes run
spanned code and stay jax-free, so a span enters a trace annotation only in
a process that has imported jax.
"""

from __future__ import annotations

import collections
import sys
import threading
import time
from typing import Dict, List, Optional

__all__ = ["span", "scope", "count", "new_session", "records", "totals",
           "counters", "window", "RING", "STEP", "DEVICE"]

RING = 1 << 15  # closed spans kept: a few thousand training steps
STEP = "heta.step"  # the root span of one training step
DEVICE = "heta.device"  # the device step as the executor runs it
COMPILES = "heta.compiles"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_lock = threading.Lock()
_local = threading.local()
_ring: "collections.deque[span]" = collections.deque()
_evicted: Dict[Optional[int], int] = {}  # session -> newest step evicted
_totals: Dict[tuple, list] = {}  # (session, name) -> [count, s, self s]
_counters: Dict[tuple, float] = {}  # (session, name) -> total
_sessions = 0
_listening = False
_profiler = None


def _stack() -> list:
    try:
        return _local.stack
    except AttributeError:
        _local.stack = []
        return _local.stack


def _annotation(name: str, step: Optional[int]):
    """The entered profiler annotation of a span, or None before jax is
    imported in this process."""
    global _profiler
    if _profiler is None:
        if "jax" not in sys.modules:
            return None
        import jax.profiler

        _profiler = jax.profiler
    if name == STEP and step is not None:
        ann = _profiler.StepTraceAnnotation(name, step_num=step)
    else:
        ann = _profiler.TraceAnnotation(name)
    ann.__enter__()
    return ann


class span:
    """A timed, annotated piece of host work; after it closes, ``t0``,
    ``t1``, ``seconds``, ``self_seconds``, ``parent``, ``step``,
    ``session`` and ``counts`` describe it."""

    __slots__ = ("name", "step", "session", "parent", "t0", "t1", "child_s",
                 "last", "counts", "_ann")

    def __init__(self, name: str, step: Optional[int] = None,
                 session: Optional[int] = None):
        self.name = name
        self.step = step
        self.session = session
        self.parent = None
        self.child_s = 0.0  # summed durations of the closed child spans
        self.last = None  # the child span that closed last, while open
        self.counts = None
        self._ann = None

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    @property
    def self_seconds(self) -> float:
        return self.t1 - self.t0 - self.child_s

    def _push(self) -> None:
        stack = _stack()
        if stack:
            top = stack[-1]
            if self.step is None:
                self.step = top.step
            if self.session is None:
                self.session = top.session
            self.parent = top if top.name is not None else top.parent
        stack.append(self)

    def __enter__(self) -> "span":
        self._push()
        self._ann = _annotation(self.name, self.step)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.t1 = t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        self.last = None
        stack = _stack()
        stack.pop()
        dur = t1 - self.t0
        if stack:
            stack[-1].child_s += dur
            stack[-1].last = self
        with _lock:
            key = (self.session, self.name)
            tot = _totals.get(key)
            if tot is None:
                tot = _totals[key] = [0, 0.0, 0.0]
            tot[0] += 1
            tot[1] += dur
            tot[2] += dur - self.child_s
            if len(_ring) == RING:
                old = _ring.popleft()
                if old.step is not None and old.step > _evicted.get(old.session, -1):
                    _evicted[old.session] = old.step
            _ring.append(self)
        return False


class scope(span):
    """Binds ``step`` and ``session`` for the spans opened under it on this
    thread.  Records nothing and reads no clock; ``child_s`` sums the spans
    directly under it."""

    __slots__ = ()

    def __init__(self, step: Optional[int], session: Optional[int] = None):
        super().__init__(None, step, session)

    def __enter__(self) -> "scope":
        self._push()
        return self

    def __exit__(self, *exc) -> bool:
        self.last = None
        stack = _stack()
        stack.pop()
        if stack:
            stack[-1].child_s += self.child_s
        return False


def count(name: str, n: float = 1) -> None:
    """Add ``n`` to counter ``name``, against the innermost open span."""
    stack = _stack()
    top = stack[-1] if stack else None
    with _lock:
        key = (top.session if top is not None else None, name)
        _counters[key] = _counters.get(key, 0) + n
    if top is not None:
        if top.counts is None:
            top.counts = {}
        top.counts[name] = top.counts.get(name, 0) + n


def _on_event(event: str, duration: float, **kw) -> None:
    if event == _COMPILE_EVENT:
        count(COMPILES)


def new_session() -> int:
    """A fresh session serial; the first call also starts counting
    compiles."""
    global _sessions, _listening
    with _lock:
        _sessions += 1
        serial = _sessions
        listen, _listening = not _listening, True
    if listen:
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(_on_event)
    return serial


def records() -> List[span]:
    """The ring: the most recent closed spans, oldest first."""
    with _lock:
        return list(_ring)


def totals(session: Optional[int]) -> Dict[str, Dict[str, float]]:
    """Per span name of ``session``: count, seconds and self seconds."""
    with _lock:
        return {name: {"count": c, "seconds": s, "self_seconds": own}
                for (sess, name), (c, s, own) in sorted(
                    _totals.items(), key=lambda kv: kv[0][1])
                if sess == session}


def counters(session: Optional[int]) -> Dict[str, float]:
    """Per counter name of ``session``: the total counted."""
    with _lock:
        return {name: n for (sess, name), n in sorted(
            _counters.items(), key=lambda kv: kv[0][1]) if sess == session}


def window(first: int, steps: int,
           session: Optional[int] = None) -> Optional[List[span]]:
    """The closed spans of training steps ``[first, first + steps)`` of
    ``session`` (by default the newest session in the ring), or None when
    any of those steps is not wholly in the ring: its root ``heta.step`` is
    missing, or a span of it or of a later step was dropped."""
    with _lock:
        recs = list(_ring)
        evicted = dict(_evicted)
    if session is None:
        known = [r.session for r in recs if r.session is not None]
        if not known:
            return None
        session = max(known)
    if evicted.get(session, -1) >= first:
        return None
    end = first + steps
    out = [r for r in recs if r.session == session and r.step is not None
           and first <= r.step < end]
    if len({r.step for r in out if r.name == STEP}) != steps:
        return None
    return out
