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

The record also reaches back from the first driver call to the
process's start (ISSUE 51): :func:`process_start_ns` and
:func:`package_start_ns` are the two ends of what ran before the
program did; :func:`phase` is the live span of what builds a run (a
scenario's tables ``tw.scenario``, an engine's constructor
``tw.engine.init``, ``tw.init_state``); and one listener a process
(:func:`listen`) turns JAX's own monitoring events into the spans of
the compile path, ``tw.trace``, ``tw.lower``, ``tw.compile`` and, inside
it, ``tw.cache_fetch``, each with the name of the program (``fun``) and
what the persistent cache answered (``cache``). These are rare, so they
are kept beside the calls and not in them, the newest ``MAX_PHASES``
(:func:`phases`), and a driver call's ``counts`` sum its own
(:func:`compile_account`: ``compile_seconds``, ``cache_misses``).
"""

from __future__ import annotations

import functools
import itertools
import os
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import List, Optional, Tuple

from .. import _PACKAGE_START_NS

__all__ = ["profile_session", "span", "call", "calls", "MAX_CALLS",
           "phase", "phased", "phases", "MAX_PHASES", "listen",
           "compile_account", "process_start_ns", "package_start_ns"]

#: finished records kept, the newest: 4096 driver calls are 143 s of
#: the ring's 35 ms jobs (a benchmark run makes some 290 of them in its
#: 10 s window and eight before it), and over an hour of praos slots
MAX_CALLS = 4096

#: phases kept, the newest: the four-chip fleet's set-up, the longest,
#: notes some hundreds (three a program it builds or fetches)
MAX_PHASES = 8192

_open = threading.local()       # this thread's open spans and open call
_runs = itertools.count(1)      # `run`: one number a driver call, process-wide
_calls: deque = deque(maxlen=MAX_CALLS)
_phases: deque = deque(maxlen=MAX_PHASES)

#: JAX's duration events of the compile path, and the span each becomes
_COMPILE_PATH = {
    "/jax/core/compile/jaxpr_trace_duration": "tw.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "tw.lower",
    "/jax/core/compile/backend_compile_duration": "tw.compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "tw.cache_fetch",
}
#: JAX's plain events of the persistent cache, and what each says of
#: the ``tw.compile`` that closes next: answered from the cache; or
#: compiled and written to it. A compile that fires neither ("none")
#: did not ask, or asked a cache with no directory, or built a program
#: under the cache's thresholds for keeping one.
_CACHE_SAID = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}
_listening = False


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
    outer = state.get("call"), state.get("compiled")
    # what the call compiles, the listener notes here too
    # (``compile_account``)
    state["call"], state["compiled"] = rec, []
    try:
        with span(name, run=rec["run"]):
            yield rec
    finally:
        state["call"], state["compiled"] = outer
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


# -- from the process's start to the first driver call ------------------------

def package_start_ns() -> int:
    """The clock read in the first line of ``timewarp_tpu/__init__.py``:
    where the program's own imports begin."""
    return _PACKAGE_START_NS


@functools.cache
def process_start_ns() -> int:
    """The process's start on ``time.perf_counter_ns()``'s clock: the
    kernel's start time of the process (``/proc/self/stat`` field 22,
    in ticks of ``SC_CLK_TCK`` since boot, so to a hundredth of a
    second) less what the boot clock reads now, on the span clock. With
    no ``/proc``, or a start time that lies after the package's first
    line, the earliest clock read the package has
    (:func:`package_start_ns`)."""
    try:
        with open("/proc/self/stat") as f:
            # the fields after the command, which may hold spaces
            ticks = int(f.read().rpartition(")")[2].split()[19])
        since_boot = ticks * 1_000_000_000 // os.sysconf("SC_CLK_TCK")
        boot_now = time.clock_gettime_ns(time.CLOCK_BOOTTIME)
    except (OSError, ValueError, IndexError, AttributeError):
        return _PACKAGE_START_NS
    start = since_boot - (boot_now - time.perf_counter_ns())
    return min(start, _PACKAGE_START_NS)


def _note(name: str, t0: int, t1: int, attrs: dict) -> tuple:
    """One more of :func:`phases`: ``cause`` the span open on this
    thread, ``run`` the open driver call's."""
    state = _open.__dict__
    names = state.get("names")
    rec = state.get("call")
    if rec is not None:
        attrs["run"] = rec["run"]
    noted = (name, t0, t1, names[-1] if names else None, attrs)
    _phases.append(noted)
    return noted


@contextmanager
def phase(name: str, **attrs):
    """A live span of what builds a run, noted in :func:`phases` and
    in no call's record: ``tw.scenario``, ``tw.engine.init``,
    ``tw.init_state``. In a profile it is a ``TraceAnnotation`` like
    any :func:`span`. The outermost only: a phase opened inside one of
    its own name (a sharded engine's constructor calling its local
    base class's, a fused ring's ``init_state`` its edge engine's) is
    that one."""
    from jax.profiler import TraceAnnotation
    names = _open.__dict__.setdefault("names", [])
    if name in names:
        yield
        return
    listen()
    with TraceAnnotation(name, **attrs):
        t0 = time.perf_counter_ns()
        names.append(name)
        try:
            yield
        finally:
            names.pop()
            _note(name, t0, time.perf_counter_ns(), attrs)


def phased(name: str, **attrs):
    """Decorator: the function runs under :func:`phase` ``name``."""
    def wrap(fn):
        @functools.wraps(fn)
        def under(*args, **kwargs):
            with phase(name, **attrs):
                return fn(*args, **kwargs)
        return under
    return wrap


def _on_start(event: str, value, **kwargs) -> None:
    # JAX says when a trace, a lowering or a compile begins (a scalar,
    # its wall time) as well as how long it took: a thread's depth in
    # each tells the outermost from what it encloses
    if event in _COMPILE_PATH:
        depth = _open.__dict__.setdefault("depth", {})
        depth[event] = depth.get(event, 0) + 1


def _on_duration(event: str, secs: float, **kwargs) -> None:
    name = _COMPILE_PATH.get(event)
    if name is None:
        return
    end = time.perf_counter_ns()
    state = _open.__dict__
    depth = state.setdefault("depth", {})
    depth[event] = inside = max(depth.get(event, 0) - 1, 0)
    if inside:
        # the outermost only: tracing a driver traces a `jit` for every
        # `jnp` call in it, thousands, each inside the driver's own
        return
    attrs = {}
    if "fun_name" in kwargs:
        attrs["fun"] = kwargs["fun_name"]
    if name == "tw.compile":
        attrs["cache"] = state.pop("cache", "none")
    noted = _note(name, end - int(secs * 1e9), end, attrs)
    compiled = state.get("compiled")
    if compiled is not None:
        compiled.append(noted)


def _on_event(event: str, **kwargs) -> None:
    said = _CACHE_SAID.get(event)
    if said is not None:
        _open.cache = said


def listen() -> None:
    """Register the process's one listener of JAX's monitoring events
    (``jax.monitoring``): every trace, lowering and backend compile (or
    the persistent cache's answer in its place) from here on is a span
    of :func:`phases`, named, on the thread that compiled, under the
    span open there. Its end is the clock when the event fires, its
    start the end less the event's seconds. A trace inside a trace (a
    ``jit`` called by the function being traced) is part of it and is
    not noted. Idempotent; the engines' module calls it when imported,
    :func:`phase` when opened."""
    global _listening
    if _listening:
        return
    _listening = True
    from jax import monitoring
    monitoring.register_scalar_listener(_on_start)
    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)


def _covered_ns(intervals) -> int:
    """Nanoseconds of the union of ``(start_ns, end_ns)`` intervals."""
    total, edge = 0, None
    for t0, t1 in sorted(intervals):
        if edge is None or t0 > edge:
            total, edge = total + t1 - t0, t1
        elif t1 > edge:
            total, edge = total + t1 - edge, t1
    return total


def compile_account() -> Tuple[float, int]:
    """``(compile_seconds, cache_misses)`` of the driver call open on
    this thread: the seconds of the union of its compile-path spans
    (traces nest, and a fetch lies inside its compile) and the backend
    compiles among them that the persistent cache did not have and
    now keeps.
    ``(0.0, 0)`` from a call that compiled nothing, and outside any."""
    compiled = _open.__dict__.get("compiled")
    if not compiled:
        return 0.0, 0
    return (_covered_ns((p[1], p[2]) for p in compiled) / 1e9,
            sum(p[0] == "tw.compile" and p[4]["cache"] == "miss"
                for p in compiled))


def phases() -> List[tuple]:
    """The spans from the process's start to whatever compiled last,
    oldest first (in the order they closed), at most ``MAX_PHASES``:
    tuples ``(name, start_ns, end_ns, cause, attrs)`` on
    ``time.perf_counter_ns()``, as a call's ``spans`` are. The live
    ones (:func:`phase`) carry what their opener gave them (an
    engine's class and ``n_nodes``, a scenario's ``model``); the
    compile path's (:func:`listen`) ``fun``, JAX's name of the program
    (``jit(_run_while)``), ``tw.compile`` also ``cache``: ``"hit"``,
    ``"miss"`` (compiled, and written to the cache) or ``"none"`` (the
    cache was not asked, has no directory, or keeps no program so
    small). ``cause`` is the name of the span the thread was in
    (``tw.dispatch``, ``tw.init_state``, ...; None: the caller's own
    ``jit``), and ``attrs["run"]`` the driver call's number where one
    was open: ``[p for p in phases() if p[4].get("run") == run]`` is
    what call ``run`` compiled. Copies: the caller may keep them."""
    return [(name, t0, t1, cause, dict(attrs))
            for name, t0, t1, cause, attrs in list(_phases)]
