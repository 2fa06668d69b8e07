"""``jax.profiler`` integration, and the program's own record of its
driver calls.

The telemetry counters (obs/telemetry.py) answer *what the emulation
did*; the XLA profiler answers *where the chip time went*. This module
wraps the latter so callers can always write ``with
profile_session(logdir):`` — ``logdir=None`` is a no-op session; a
directory that was asked for and a session that cannot start is an
error (a profile run that profiled nothing must not exit 0).

:func:`span` is the one host-side span primitive: the drivers open
``tw.<driver>`` / ``tw.dispatch`` / ``tw.wait`` / ``tw.guard`` with it
(``RunStatsMixin._driver_call``), the sweep service ``tw.sweep.bucket``,
``MetricsRegistry.span`` every span of the metrics stream. On the
device the superstep's stages carry the ``jax.named_scope`` names of
``common.STAGES``. Both are the profiler's own: with no session open a
span is TraceMe's inactive path, and a scope is metadata of the
compiled program.

Every span is also noted in memory, on ``time.perf_counter_ns()``:
:func:`call` opens **one record a driver call** (its spans, and the
counts the call read back from the device), :func:`calls` returns the
finished records. The record is always kept; it costs a few clock
reads a call and is bounded (``MAX_CALLS``). ``jax.profiler`` times a
session's events from the session's own start, so no host clock puts
a record on a trace's time base: ``benchmark/record_reduce.py`` ties
the two by the causal order of a call's ``tw.dispatch`` and
``tw.wait`` with the program it launched.
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import List, Optional

__all__ = ["profile_session", "span", "call", "calls", "MAX_CALLS"]

#: finished records kept, the newest: 4096 driver calls are 143 s of
#: the ring's 35 ms jobs (a benchmark run makes some 290 of them in its
#: 10 s window and eight before it), and over an hour of praos slots
MAX_CALLS = 4096

_open = threading.local()       # this thread's open spans and open call
_runs = itertools.count(1)      # `run`: one number a driver call, process-wide
_calls: deque = deque(maxlen=MAX_CALLS)


@contextmanager
def profile_session(logdir: Optional[str]):
    """A ``jax.profiler`` trace session writing to ``logdir`` (view
    with TensorBoard or xprof). ``logdir=None`` yields a plain no-op
    session. A session that was asked for and cannot start raises:
    ``timewarp-tpu profile`` fails instead of exiting 0 with no
    trace."""
    if not logdir:
        yield None
        return
    import jax
    import jax.profiler as _jp
    # the stage names a profile shows are the executable's, and the
    # persistent compile cache keys on the program less its metadata:
    # an executable cached before a scope was named would be served
    # without it. What compiles under a session keys on metadata too.
    key = "jax_compilation_cache_include_metadata_in_key"
    was = getattr(jax.config, key)
    jax.config.update(key, True)
    _jp.start_trace(logdir)
    try:
        yield logdir
    finally:
        _jp.stop_trace()
        jax.config.update(key, was)


def _record(run) -> dict:
    return {"run": run, "spans": [], "counts": {}}


@contextmanager
def span(name: str, **attrs):
    """A host span: a ``jax.profiler.TraceAnnotation`` named ``name``
    whose start and end in a profile are the profiler's, with
    ``attrs`` and ``cause`` (the name of the span it was opened in;
    none at the top) as stats on the event; and a tuple ``(name,
    start_ns, end_ns, cause, attrs)`` on ``time.perf_counter_ns()`` in
    the open driver call's record (:func:`call`). A span opened under
    no driver call is a record of its own. Yields the record: once the
    span has closed its tuple is the record's last
    (``MetricsRegistry.span`` takes its times from there)."""
    from jax.profiler import TraceAnnotation
    state = _open.__dict__
    names = state.setdefault("names", [])
    rec = state.get("call")
    own = rec is None
    if own:
        rec = _record(None)
    cause = names[-1] if names else None
    stats = attrs if cause is None else {**attrs, "cause": cause}
    with TraceAnnotation(name, **stats):
        names.append(name)
        t0 = time.perf_counter_ns()
        try:
            yield rec
        finally:
            rec["spans"].append(
                (name, t0, time.perf_counter_ns(), cause, attrs))
            names.pop()
            if own:
                _calls.append(rec)


@contextmanager
def call(name: str):
    """One driver call's record, open for the ``with``: the span
    ``name`` around the call, carrying the call's ``run`` (its number
    in the process, shared by every span of the call), and every span
    opened inside it. Yields the record, a dict ``{"run", "spans",
    "counts"}``: the caller puts what the call counted into
    ``counts``. Finished, the record joins :func:`calls`."""
    rec = _record(next(_runs))
    state = _open.__dict__
    outer = state.get("call")
    state["call"] = rec
    try:
        with span(name, run=rec["run"]):
            yield rec
    finally:
        state["call"] = outer
        _calls.append(rec)


def calls() -> List[dict]:
    """The finished records, oldest first, at most ``MAX_CALLS``: for
    each driver call ``{"run": int, "engine": str, "n_nodes": int,
    "max_out": int, "spans": ((name, start_ns, end_ns, cause, attrs),
    ...), "counts": {...}}``. Spans are in the order they closed (the call's own span
    last), times are ``time.perf_counter_ns()``, ``counts`` is the
    call's ``last_run_stats`` (docs/observability.md). A span opened
    under no driver call is a record with that one span, ``run`` None
    and no counts. Plain tuples and dicts, copied: the caller may keep
    them."""
    return [{**r, "spans": tuple(r["spans"]), "counts": dict(r["counts"])}
            for r in list(_calls)]
