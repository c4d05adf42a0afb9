"""Host spans and counters of the JAX tier, on the profiler's clock.

The switch is the profiler session (``jax.profiler.trace`` or
``start_trace``/``stop_trace``): there is no flag of its own.

* Off, :func:`span` returns one shared no-op context manager and
  :func:`count` returns at once: no span object, no annotation, no
  counter entry is made.
* On, :func:`span` enters a ``jax.profiler.TraceAnnotation``, so the span
  lies in the profiler's own trace, on the clock of the device planes, and
  adds to an aggregate per name: calls, total time and self time (total
  less the time of child spans on the same thread; a span's parent is the
  innermost span open on its thread when it starts).  :func:`count` adds
  into one :class:`~repro.obs.registry.CounterGroup`.

Counters such as ``sched.compactions`` repeat what the serving objects
count over their whole life, but only for the session: the benchmark's
traced window, not the set-up replay before it.

The recorder starts afresh with each session: the first call that finds
the profiler on after finding it off clears what the last session left.
:func:`snapshot` then reads what this session recorded, without clearing.

Usage::

    with jax.profiler.trace(logdir):
        loop.run(decode_fn)
    print(spans.snapshot())   # {"spans": {...}, "counters": {...}}
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Dict, List, Optional

from jax.profiler import TraceAnnotation

from .registry import CounterGroup

OFF = contextlib.nullcontext()      # the one span handed out while off

_is_enabled = TraceAnnotation.is_enabled
_was_on = False
_lock = threading.Lock()
_local = threading.local()
# name -> [calls, total ns, self ns, set of parent names]
_spans: Dict[str, list] = {}
_counters = CounterGroup()


def on() -> bool:
    """True while a profiler session is on; clears the recorder when a
    session has started since the last call."""
    global _was_on
    enabled = _is_enabled()
    if enabled != _was_on:
        with _lock:
            if enabled and not _was_on:
                _spans.clear()
                _counters.clear()
            _was_on = enabled
    return enabled


def _stack() -> List["Span"]:
    try:
        return _local.stack
    except AttributeError:
        _local.stack = []
        return _local.stack


class Span:
    """One timed span; build it only while :func:`on` is true (what
    :func:`span` does for callers that check nothing themselves)."""

    __slots__ = ("name", "annotation", "parent", "t0", "child_ns")

    def __init__(self, name: str, **attrs) -> None:
        self.name = name
        self.annotation = TraceAnnotation(name, **attrs)

    def __enter__(self) -> "Span":
        stack = _stack()
        self.parent: Optional[str] = stack[-1].name if stack else None
        stack.append(self)
        self.child_ns = 0
        self.annotation.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        dur = time.perf_counter_ns() - self.t0
        self.annotation.__exit__(*exc)
        stack = _stack()
        stack.pop()
        if stack:
            stack[-1].child_ns += dur
        with _lock:
            agg = _spans.get(self.name)
            if agg is None:
                agg = _spans[self.name] = [0, 0, 0, set()]
            agg[0] += 1
            agg[1] += dur
            agg[2] += dur - self.child_ns
            agg[3].add(self.parent)


def span(name: str, **attrs):
    """A context manager timing ``name`` while a profiler session is on,
    else the shared no-op :data:`OFF`.  ``attrs`` become the trace event's
    stats; a caller whose attributes cost anything to build checks
    :func:`on` once and builds a :class:`Span` itself when it is true."""
    if not on():
        return OFF
    return Span(name, **attrs)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to counter ``name`` while a profiler session is on."""
    if not on():
        return
    with _lock:
        _counters[name] = _counters.get(name, 0) + n


def snapshot() -> Dict[str, Dict]:
    """What the current (or last) session recorded, without clearing:
    ``{"spans": {name: {count, total_ms, self_ms, parents}},
    "counters": {name: n}}``; ``parents`` lists the names the span was
    opened under (``None`` at the top)."""
    on()
    with _lock:
        return {
            "spans": {name: {"count": c, "total_ms": total * 1e-6,
                             "self_ms": own * 1e-6,
                             "parents": sorted(parents, key=str)}
                      for name, (c, total, own, parents)
                      in sorted(_spans.items())},
            "counters": dict(sorted(_counters.items())),
        }


def reset() -> None:
    """Forget every span and counter (a new session does this itself)."""
    with _lock:
        _spans.clear()
        _counters.clear()
