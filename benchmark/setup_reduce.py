"""From the program's own record to where set-up went: the seconds from
the process's start to the window's first driver call, by phase, by
cause and by program.

Since PR 51 the program's record (``timewarp_tpu/obs/profiler.py``)
reaches back from its driver calls to the process's start:
``process_start_ns()``, ``package_start_ns()`` (the first line of
``timewarp_tpu/__init__.py``) and ``phases()``, tuples ``(name,
start_ns, end_ns, cause, attrs)`` on ``time.perf_counter_ns()`` like a
call's spans: the live spans ``tw.scenario``, ``tw.engine.init``,
``tw.init_state``, and JAX's own compile events as ``tw.trace``,
``tw.lower``, ``tw.compile`` (``attrs["fun"]`` the program's name,
``attrs["cache"]`` ``hit``, ``miss`` or ``none``) and ``tw.cache_fetch``
inside its ``tw.compile``. README_setup.md is the page on this.

**Set-up's end** is the start of the window's first driver call: the
call that ``record_reduce``'s pairing ties to the trace's first main
program (``record_reduce.of_trace``, used and not copied). No pairing,
no number, as for the ``idle_in_*`` metrics. Every moment of set-up
then has one owner (:func:`partition`), in this order: the compile
path (a moment under two of its spans goes to the one that began last,
:func:`newest_owner`), the live spans, the driver calls before the
window, the package's and the builder's imports, and the time before
the package's first line; the rest is ``setup_unowned_s``.

Everything but :func:`record` is a pure function over tuples
(``benchmark/tests/test_setup_reduce.py``). A program without
``phases()`` (the parent of PR 51) gives :func:`record` nothing, and
the eleven readers return ``None``.
"""

from __future__ import annotations

from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import record_reduce

Interval = Tuple[int, int]        # (start_ns, end_ns)
Phase = tuple                     # (name, start_ns, end_ns, cause, attrs)

TRACE, LOWER, COMPILE, FETCH = ("tw.trace", "tw.lower", "tw.compile",
                                "tw.cache_fetch")
COMPILE_PATH = (TRACE, LOWER, COMPILE, FETCH)
LIVE = ("tw.scenario", "tw.engine.init", "tw.init_state")
#: the seven durations that partition set-up, with ``unowned`` the
#: eighth: the metric ``setup_<key>_s`` of each
PARTS = ("before_program", "import", "engine", "trace", "lower",
         "backend", "run")
_PART_OF = {TRACE: "trace", LOWER: "lower", COMPILE: "backend",
            FETCH: "backend"}


# -- pure functions over tuples ----------------------------------------------

def union(intervals: Iterable[Interval]) -> List[Interval]:
    """The intervals merged where they touch or overlap, in order;
    empty ones dropped."""
    out: List[Interval] = []
    for t0, t1 in sorted(i for i in intervals if i[1] > i[0]):
        if out and t0 <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], t1))
        else:
            out.append((t0, t1))
    return out


def length(intervals: Iterable[Interval]) -> int:
    """Nanoseconds the intervals cover, a moment under two counted
    once."""
    return sum(t1 - t0 for t0, t1 in union(intervals))


def clip(intervals: Iterable[Interval], lo: int, hi: int) -> List[Interval]:
    """The parts of the intervals inside ``[lo, hi)``."""
    return [(max(t0, lo), min(t1, hi)) for t0, t1 in intervals
            if min(t1, hi) > max(t0, lo)]


def subtract(intervals: Iterable[Interval], cover: Iterable[Interval]
             ) -> List[Interval]:
    """The union of ``intervals`` less what ``cover`` covers."""
    out, cover = [], union(cover)
    for t0, t1 in union(intervals):
        for c0, c1 in cover:
            if c1 <= t0 or c0 >= t1:
                continue
            if c0 > t0:
                out.append((t0, c0))
            t0 = max(t0, c1)
        if t1 > t0:
            out.append((t0, t1))
    return out


def self_ns(span: Interval, children: Iterable[Interval]) -> int:
    """A span's self time: its duration less the part of it that its
    children cover."""
    return length(subtract([span], children))


def partition(layers: Sequence[Iterable[Interval]], lo: int, hi: int
              ) -> Tuple[List[int], int]:
    """``[lo, hi)`` cut among ``layers``, the first in the order given
    that covers a moment owning it: ``(ns of each layer, ns no layer
    covers)``. The parts sum to ``hi - lo`` exactly."""
    owned: List[Interval] = []
    parts = []
    for layer in layers:
        mine = subtract(clip(layer, lo, hi), owned)
        parts.append(length(mine))
        owned = union(owned + mine)
    return parts, (hi - lo) - sum(parts)


def newest_owner(spans: Sequence[Phase]) -> List[Tuple[int, int, Phase]]:
    """``(start_ns, end_ns, span)`` pieces that partition the union of
    ``spans``: a moment under several goes to the one that began last
    (of two that began together, the one that ends first: the inner).
    So a lowering inside a trace is the lowering's, a cache fetch
    inside its compile the fetch's, and the lengths by name sum to the
    union's."""
    edges = sorted({t for s in spans for t in (s[1], s[2])})
    by_start = sorted(spans, key=lambda s: (s[1], -s[2]))
    pieces, active, nxt = [], [], 0
    for t0, t1 in zip(edges, edges[1:]):
        while nxt < len(by_start) and by_start[nxt][1] <= t0:
            active.append(by_start[nxt])
            nxt += 1
        active = [s for s in active if s[2] > t0]
        if active:
            # `active` is in the order the spans began
            pieces.append((t0, t1, active[-1]))
    return pieces


def program_of(span: Phase, spans: Sequence[Phase]) -> str:
    """The program a compile-path span belongs to, by JAX's name less
    its ``jit(...)`` wrapper (a trace's event has the bare name, the
    lowering's and the compile's the wrapped one). A cache fetch has no
    name of its own: its compile's, the one that encloses it."""
    fun = span[4].get("fun")
    if fun is None:
        around = [s for s in spans if s[0] == COMPILE
                  and s[1] <= span[1] and span[2] <= s[2]]
        fun = min(around, key=lambda s: s[2] - s[1])[4].get("fun") \
            if around else None
    if fun is None:
        return "?"
    return fun[4:-1] if fun.startswith("jit(") and fun.endswith(")") else fun


def account(record: dict, end_ns: int) -> dict:
    """Where set-up went. ``record`` is :func:`record`'s dict
    (``process_start_ns``, ``package_start_ns``, ``phases``, and
    ``calls``, the records of the driver calls: those that began
    before ``end_ns`` count); ``end_ns`` is set-up's end on the
    record's clock. Returns ``{"length_ns",
    "parts_ns": {one of PARTS: ns}, "unowned_ns", "cache_fetch_ns",
    "programs", "cache_misses", "by_cause": {cause: {"trace", "lower",
    "backend": ns, "programs", "misses"}}, "by_program": {name: ns}}``.
    The seven parts and ``unowned_ns`` sum to ``length_ns``."""
    t0, t1 = record["process_start_ns"], record["package_start_ns"]
    phases = [p for p in record["phases"] if p[1] < end_ns]
    path = [p for p in phases if p[0] in COMPILE_PATH]
    live = [p for p in phases if p[0] in LIVE]
    # the package's first line to the first scenario or engine: the
    # program's own imports and the builder's
    first = min((p[1] for p in live), default=end_ns)
    # a call's own span is the last it noted
    calls = [c["spans"][-1][1:3] for c in record["calls"]
             if c["run"] is not None and c["spans"][-1][1] < end_ns]
    spans_of = lambda ps: [(p[1], p[2]) for p in ps]      # noqa: E731
    (_, engine, run, imports, before), unowned = partition(
        [spans_of(path), spans_of(live), calls, [(t1, first)], [(t0, t1)]],
        t0, end_ns)

    parts = {"before_program": before, "import": imports, "engine": engine,
             "trace": 0, "lower": 0, "backend": 0, "run": run}
    fetch_ns = 0
    by_cause: Dict[Optional[str], Dict[str, int]] = defaultdict(
        lambda: dict.fromkeys(("trace", "lower", "backend", "programs",
                               "misses"), 0))
    by_program: Dict[str, int] = defaultdict(int)
    for a, b, span in newest_owner(path):
        ns = max(0, min(b, end_ns) - max(a, t0))
        parts[_PART_OF[span[0]]] += ns
        by_cause[span[3]][_PART_OF[span[0]]] += ns
        by_program[program_of(span, path)] += ns
        if span[0] == FETCH:
            fetch_ns += ns
    for p in path:
        if p[0] == COMPILE and p[2] <= end_ns:
            by_cause[p[3]]["programs"] += 1
            by_cause[p[3]]["misses"] += p[4].get("cache") == "miss"
    return {"length_ns": end_ns - t0, "parts_ns": parts,
            "unowned_ns": unowned, "cache_fetch_ns": fetch_ns,
            "programs": sum(c["programs"] for c in by_cause.values()),
            "cache_misses": sum(c["misses"] for c in by_cause.values()),
            "by_cause": {k: dict(v) for k, v in by_cause.items()},
            "by_program": dict(by_program)}


def lines(acc: dict, longest: int = 5) -> List[str]:
    """The account for a reader of the run's output, in the manner of
    ``run.py``'s own ``jobs:`` line: the parts, then one line a
    ``cause`` (seconds by phase, programs, misses), then the
    ``longest`` programs by name."""
    s = lambda ns: f"{ns / 1e9:.3f}"                      # noqa: E731
    out = [f"set-up by phase: {s(acc['length_ns'])} s to the window's "
           "first driver call; "
           + ", ".join(f"{k} {s(v)}" for k, v in acc["parts_ns"].items())
           + f", unowned {s(acc['unowned_ns'])}; of backend, cache fetch "
           f"{s(acc['cache_fetch_ns'])}; programs {acc['programs']}, "
           f"cache misses {acc['cache_misses']}"]
    for cause, c in sorted(acc["by_cause"].items(),
                           key=lambda kv: -sum(kv[1][k] for k in
                                               ("trace", "lower", "backend"))):
        out.append(f"set-up compile path under {cause or 'no span'}: "
                   f"trace {s(c['trace'])} s, lower {s(c['lower'])} s, "
                   f"backend {s(c['backend'])} s; programs "
                   f"{c['programs']}, cache misses {c['misses']}")
    top = sorted(acc["by_program"].items(), key=lambda kv: -kv[1])[:longest]
    out.append("set-up longest programs: "
               + ", ".join(f"{name} {s(ns)} s" for name, ns in top))
    return out


# -- what the readers ask -----------------------------------------------------

def record() -> Optional[dict]:
    """The program's record of its set-up, or ``None`` from a program
    that keeps none: the two starts, ``phases()``, and the driver
    calls' records (``record_reduce.records()``)."""
    try:
        from timewarp_tpu.obs import profiler
    except ImportError:
        return None
    if not hasattr(profiler, "phases"):
        return None
    return {"process_start_ns": profiler.process_start_ns(),
            "package_start_ns": profiler.package_start_ns(),
            "phases": profiler.phases(),
            "calls": record_reduce.records()}


_last: Tuple[object, Optional[dict]] = (None, None)


def of_trace(trace) -> Optional[dict]:
    """The :func:`account` of the run whose ``trace_reduce.Trace`` this
    is, printed once (:func:`lines`) and shared by the eleven readers;
    ``None`` where the program keeps no such record or the trace's main
    programs cannot be paired with the record's calls."""
    global _last
    if _last[0] is not trace:
        _last = (trace, None)
        red, rec = record_reduce.of_trace(trace), record()
        if red is not None and rec is not None:
            # the call that launched the trace's first main program
            first = record_reduce.driver_calls(rec["calls"])[red["shift"]][0]
            acc = account(rec, min(s[1] for s in
                                   rec["calls"][first]["spans"]))
            print("\n".join(lines(acc)))
            _last = (trace, acc)
    return _last[1]


def seconds(trace, part: str) -> Optional[float]:
    """Seconds of set-up's part ``part`` (one of ``PARTS``,
    ``unowned`` or ``cache_fetch``)."""
    acc = of_trace(trace)
    if acc is None:
        return None
    ns = acc["parts_ns"][part] if part in PARTS else acc[part + "_ns"]
    return ns / 1e9


def count(trace, what: str) -> Optional[int]:
    """``programs`` or ``cache_misses`` of set-up."""
    acc = of_trace(trace)
    return None if acc is None else acc[what]
