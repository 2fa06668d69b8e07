"""From the program's own record to numbers: who owned each idle gap
between main programs, and how wide the routing ladder ran.

Since PR 35 the program keeps, in memory, one record a driver call
(``timewarp_tpu/obs/profiler.py`` ``calls()``): the call's host spans
``(name, start_ns, end_ns, cause, attrs)`` on ``time.perf_counter_ns()``
(``tw.run_quiet`` or ``tw.run``, inside it ``tw.dispatch``, ``tw.wait``,
``tw.guard``) and its counts (``last_run_stats``: what it launched and
read back, and the routing stage's ``rung_lanes``, ``sender_lanes``,
``rung_steps``). A reader under ``layer_metrics/`` is handed ``(trace,
run)`` and nothing of the program; it imports the record through
:func:`records`, which finds nothing in a program without one (the
parent of PR 35), and the readers then return ``None``.

Like ``trace_reduce`` and ``span_reduce`` (whose ``clock_bracket``,
``gaps_between_programs`` and ``owner_of_gaps`` it uses and does not
copy) everything but :func:`records` is a pure function over tuples
(``tests/test_zzzzzzzzzzzzzzzrecord.py``, ``benchmark/tests/
test_record_reduce.py``).

**The clock.** ``jax.profiler`` times a session's events from the
session's own start, so no host clock read in the program is the
trace's; the record is tied to the device's clock through the
program's own causal order instead. The k-th execution of the trace's
main program was launched by one driver call of the record: its
``tw.dispatch`` began before the program started, and its ``tw.wait``
ended after the program ended. Each of the two bounds the nanoseconds
to add to the record's clock from one side (:func:`bracket`); the
offset used is the middle, the width is ``span_clock_slack_ms``.

Which call launched which execution is found, not assumed
(:func:`find_shift`): both sequences are ordered, so a pairing is one
index shift, and the right one is the only shift at which no pair
contradicts causality. A shift that is off by one pairs the traced
window's first program with a call from before the profile started (or
its last with one from after it ended), which no offset fitting the
other pairs allows. A host stall inside one ``tw.dispatch`` (the
ledger's 0.130 s gap, PR 34) widens one pair and breaks none, which is
why the criterion is causality and the constancy of the
launch-to-start delays is only reported (``delay_spread_ns``). No
shift or more than one: no pairing, no number.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import span_reduce
import trace_reduce
from span_reduce import CLIENT, clock_bracket, gaps_between_programs, \
    owner_of_gaps

Call = Tuple[int, int]        # (tw.dispatch began, tw.wait ended), host ns
Program = Tuple[int, int]     # (started, ended), device ns

DISPATCH, WAIT = "tw.dispatch", "tw.wait"
#: the four owners of the idle time between main programs; every other
#: ``tw.`` span (the driver's own code, ``tw.guard``) is the driver's
OWNERS = ("dispatch", "wait", "driver", "client")
_OWNER_OF = {DISPATCH: "dispatch", WAIT: "wait", CLIENT: "client"}
_LANES = ("rung_lanes", "sender_lanes")


# -- pure functions over tuples ----------------------------------------------

def driver_calls(records: Sequence[dict]) -> List[Tuple[int, Call]]:
    """``(index, (dispatch began, wait ended))`` of the records that
    crossed to the chip: those with a ``tw.dispatch`` and a ``tw.wait``
    (the first launch, the last wait), in the record's order."""
    out = []
    for i, rec in enumerate(records):
        began = [s[1] for s in rec["spans"] if s[0] == DISPATCH]
        ended = [s[2] for s in rec["spans"] if s[0] == WAIT]
        if began and ended:
            out.append((i, (min(began), max(ended))))
    return out


def _pairs(programs: Sequence[Program], calls: Sequence[Call], shift: int):
    """``(before, after)`` for ``clock_bracket``: program ``k`` against
    call ``shift + k``."""
    paired = list(zip(programs, calls[shift:shift + len(programs)]))
    return ([(c[0], p[0]) for p, c in paired],
            [(c[1], p[1]) for p, c in paired])


def find_shift(programs: Sequence[Program], calls: Sequence[Call]
               ) -> Optional[int]:
    """The one ``shift`` at which pairing program ``k`` with call
    ``shift + k`` contradicts causality nowhere; ``None`` where there
    are fewer calls than programs, fewer than two programs, or more
    than one such shift (calls so regular that the pairing cannot be
    told). Raises where every shift contradicts: the record and the
    trace are not of the same run."""
    n = len(programs)
    if n < 2 or len(calls) < n:
        return None
    fits = []
    for shift in range(len(calls) - n + 1):
        try:
            clock_bracket(*_pairs(programs, calls, shift))
        except ValueError:
            continue
        fits.append(shift)
    if not fits:
        raise ValueError(
            f"no pairing of the trace's {n} main programs with the "
            f"record's {len(calls)} driver calls is free of contradiction")
    return fits[0] if len(fits) == 1 else None


def bracket(programs: Sequence[Program], calls: Sequence[Call], shift: int
            ) -> Tuple[int, int]:
    """``(lo, hi)`` bounding the nanoseconds to add to the record's
    clock to get the device's, from the pairing ``shift``; raises where
    the pairs contradict (``span_reduce.clock_bracket``)."""
    return clock_bracket(*_pairs(programs, calls, shift))


def delay_spread_ns(programs: Sequence[Program], calls: Sequence[Call],
                    shift: int) -> int:
    """Largest less smallest launch-to-start delay of the pairing: what
    a host stall inside a ``tw.dispatch`` shows in."""
    before, _ = _pairs(programs, calls, shift)
    delays = [d - h for h, d in before]
    return max(delays) - min(delays)


def host_spans(records: Sequence[dict]) -> List[span_reduce.Span]:
    """The records' spans as ``span_reduce`` has a host span:
    ``(start_ns, duration_ns, name, stats)``."""
    return [(t0, t1 - t0, name, attrs) for rec in records
            for name, t0, t1, _, attrs in rec["spans"]]


def owners_ns(gaps, records: Sequence[dict], offset: int) -> Dict[str, int]:
    """Nanoseconds of the device-idle intervals ``gaps`` by the four
    ``OWNERS``, the records' spans put on the device's clock by
    ``offset``. Every owner is there, at 0 where it held nothing, and
    the four sum to the gaps."""
    acc = dict.fromkeys(OWNERS, 0)
    for name, ns in owner_of_gaps(gaps, host_spans(records), offset).items():
        acc[_OWNER_OF.get(name, "driver")] += ns
    return acc


def lane_sums(records: Sequence[dict]) -> Optional[Dict[str, int]]:
    """Over the records that counted their routing: ``rung_lanes``,
    ``sender_lanes``, and ``full_lanes``, the ladder's whole width over
    the same iterations (``n_nodes`` times the loop's iterations: a
    fleet's ``fleet_iterations``, a solo call's ``supersteps``).
    ``None`` where none did."""
    acc = dict.fromkeys(_LANES + ("full_lanes",), 0)
    counted = False
    for rec in records:
        counts = rec["counts"]
        if "rung_lanes" not in counts or not rec.get("n_nodes"):
            continue
        counted = True
        for key in _LANES:
            acc[key] += counts[key]
        acc["full_lanes"] += rec["n_nodes"] * counts.get(
            "fleet_iterations", counts["supersteps"])
    return acc if counted else None


def reduction(modules, events, records: Sequence[dict]) -> Optional[dict]:
    """Everything the seven readers ask, from a trace's executed
    programs (``modules``), its leaf operations and copies (``events``)
    and the program's ``records``; ``None`` where the main program's
    executions cannot be paired with the record's calls. ``owners_ms``
    is a mean over the gaps between jobs, as ``sync_gap_ms`` is."""
    main = trace_reduce.main_program(modules)
    programs = sorted((s, s + d) for s, d, name in modules if name == main)
    indexed = driver_calls(records)
    calls = [c for _, c in indexed]
    shift = find_shift(programs, calls)
    if shift is None:
        return None
    lo, hi = bracket(programs, calls, shift)
    # the window's records: from the first paired call to the last, and
    # whatever the program noted between them
    first, last = indexed[shift][0], indexed[shift + len(programs) - 1][0]
    window = records[first:last + 1]
    gaps = gaps_between_programs(modules, events)
    # one gap between jobs for each pair of consecutive main programs,
    # as trace_reduce.gaps_between_jobs counts them
    jobs = sum(b[0] > a[1] for a, b in zip(programs, programs[1:]))
    owners = owners_ns(gaps, window, (lo + hi) // 2)
    return {"owners_ms": {k: v / jobs / 1e6 for k, v in owners.items()}
            if jobs else None,
            "slack_ms": (hi - lo) / 1e6,
            "delay_spread_ms": delay_spread_ns(programs, calls, shift) / 1e6,
            "lanes": lane_sums([records[i] for i, _ in
                                indexed[shift:shift + len(programs)]]),
            "paired": len(programs), "shift": shift}


# -- what the readers ask ------------------------------------------------------

def records() -> Optional[List[dict]]:
    """The program's record of its driver calls, or ``None`` from a
    program that keeps none."""
    try:
        from timewarp_tpu.obs import profiler
    except ImportError:
        return None
    calls = getattr(profiler, "calls", None)
    return calls() if calls is not None else None


_last: Tuple[object, Optional[dict]] = (None, None)


def of_trace(trace) -> Optional[dict]:
    """The :func:`reduction` of a ``trace_reduce.Trace`` and the program's
    record; the seven readers of one run share one reduction."""
    global _last
    if _last[0] is not trace:
        recs = records()
        _last = (trace, None if not recs else reduction(
            trace.modules, trace.ops[0] + trace.asyncs[0], recs))
    return _last[1]


def owner_ms(trace, owner: str) -> Optional[float]:
    """Milliseconds a job of the idle time between main programs that
    ``owner`` (one of ``OWNERS``) held."""
    red = of_trace(trace)
    if red is None or red["owners_ms"] is None:
        return None
    return red["owners_ms"][owner]


def lane_share(trace, over: str, under: str) -> Optional[float]:
    """``100 * lanes[over] / lanes[under]`` of the traced jobs' calls."""
    red = of_trace(trace)
    if red is None or not red["lanes"] or not red["lanes"][under]:
        return None
    return 100.0 * red["lanes"][over] / red["lanes"][under]
