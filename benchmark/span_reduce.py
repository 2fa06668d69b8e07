"""From the program's own names to numbers: device time by superstep
stage, and the owner of every idle gap between programs.

``trace_reduce.py`` reads what any JAX program leaves in a profiler
trace. This file reads what time-warp-tpu puts there since PR 24:

- on the device, every operation's ``op_name`` holds the
  ``jax.named_scope`` path it was traced under, and the superstep's
  stages are scopes named ``tw.<stage>`` (``timewarp_tpu/interp/
  jax_engine/common.py`` ``STAGES``; the ring's kernel ``tw.ring_kernel``);
- on the host, every driver call is a span ``tw.<driver>`` with
  ``tw.dispatch``, ``tw.wait`` and ``tw.guard`` inside it
  (``RunStatsMixin._driver_call``), each with the stats ``run`` and
  ``cause``.

Two halves, like ``trace_reduce``. :func:`load` reads the ``.xplane.pb``
into a :class:`Spans` of tuples; everything else is a pure function
over tuples (``tests/test_span_reduce.py``). A trace of a program that
has no such scope or span (the parent of PR 24) loads too: the readers
then find nothing and return ``None``.

What a v5e trace holds beyond ``trace_reduce``'s notes (looked at by
hand, PERF.md PR 24):

- The ``op_name`` of a device operation is the stat ``tf_op`` of the
  event's *metadata* (``jit(_run_while)/while/body/tw.route/insert/
  sort:``), which ``jax.profiler.ProfileData`` does not expose: it
  gives an event's own stats only. So :func:`op_names` walks the
  file's protobuf wire format for that one stat. A fusion carries the
  ``op_name`` XLA gave it (its root instruction's); an operation the
  compiler put in itself (the copy that stages the ring kernel's
  operand) carries its parent's (``jit(_run_while)/while``) and so
  falls under no ``tw.`` scope.
- The runtime's own host events carry the ``run_id`` of the program
  they launch (``DoEnqueueProgram``) or finish (``CompleteCallbacks``),
  the same ``run_id`` the ``XLA Modules`` event of that execution has.
  A program cannot start before the host began to enqueue it, and the
  host cannot run its completion callbacks before it ended: the two
  bound the offset between the clocks from both sides
  (:func:`clock_bracket`).
"""

from __future__ import annotations

import bisect
import re
from typing import (Dict, Iterable, List, NamedTuple, Optional, Sequence,
                    Tuple)

import trace_reduce
from trace_reduce import Event

Span = Tuple[int, int, str, dict]     # (start_ns, duration_ns, name, stats)
Run = Tuple[int, int, str, int]       # (start_ns, duration_ns, name, run_id)

SPAN_PREFIX = "tw."
UNSCOPED = "unscoped"
CLIENT = "client"
LAUNCH_EVENT = "DoEnqueueProgram"     # starts before the program does
DONE_EVENT = "CompleteCallbacks"      # starts after the program ended
OP_NAME_STAT = "tf_op"                # on a device operation's metadata


class Spans(NamedTuple):
    """What the program's names add to a :class:`trace_reduce.Trace`:
    the host's ``tw.`` spans and the harness's ``bench_job`` (``host``);
    per chip the ``op_name`` of each leaf operation, parallel to
    ``Trace.ops`` (``scopes``); the runtime's host events that carry a
    ``run_id`` (``launches``) and the first chip's executed programs
    with theirs (``programs``), for the clock."""
    host: List[Span]
    scopes: List[List[str]]
    launches: List[Run]
    programs: List[Run]


# -- pure functions over tuples ----------------------------------------------

def clock_bracket(before: Iterable[Tuple[int, int]],
                  after: Iterable[Tuple[int, int]]) -> Tuple[int, int]:
    """``(lo, hi)`` bounding the nanoseconds to add to a host time to
    get the device's. ``before`` holds pairs ``(host_ns, device_ns)``
    of a host instant that precedes a device instant (the enqueue of a
    program, its start): each bounds the offset from above. ``after``
    holds pairs of a host instant that follows a device instant (the
    completion callbacks, the program's end): each bounds it from
    below. The tightest of each; raises where they contradict."""
    hi = min((d - h for h, d in before), default=None)
    lo = max((d - h for h, d in after), default=None)
    if lo is None or hi is None:
        raise ValueError("no causal pair on one side: the clocks are "
                         "not bracketed")
    if lo > hi:
        raise ValueError(f"the spans contradict causality: the offset "
                         f"would be at least {lo} ns and at most {hi} ns")
    return lo, hi


def causal_pairs(launches: Iterable[Run], programs: Iterable[Run]):
    """``(before, after)`` for :func:`clock_bracket`, from the runtime's
    host events and the executions they name by ``run_id``: the start
    of each ``LAUNCH_EVENT`` against its program's start, the start of
    each ``DONE_EVENT`` against its program's end."""
    prog = {rid: (s, s + d) for s, d, _, rid in programs}
    before, after = [], []
    for s, _, name, rid in launches:
        if rid not in prog:
            continue
        if name == LAUNCH_EVENT:
            before.append((s, prog[rid][0]))
        elif name == DONE_EVENT:
            after.append((s, prog[rid][1]))
    return before, after


def gaps_between_programs(modules: Sequence[Event], events: Iterable[Event]
                          ) -> List[Tuple[int, int]]:
    """The device-idle intervals ``(start, duration)`` between
    consecutive executions of the main program, on the device's clock:
    what ``trace_reduce.gaps_between_jobs`` sums, kept as intervals."""
    main = trace_reduce.main_program(modules)
    runs = sorted(e for e in modules if e[2] == main)
    events = sorted(events)
    starts = [e[0] for e in events]
    out = []
    for a, b in zip(runs, runs[1:]):
        lo, hi = a[0] + a[1], b[0]
        if hi > lo:
            # as gaps_between_jobs has it: what starts in between
            out.extend(trace_reduce.idle_gaps(
                events[bisect.bisect_left(starts, lo):
                       bisect.bisect_left(starts, hi)], lo, hi))
    return sorted(out)


def owner_of_gaps(gaps: Iterable[Tuple[int, int]], host: Iterable[Span],
                  offset: int) -> Dict[str, int]:
    """Nanoseconds of the device-idle intervals ``gaps`` (device clock)
    by what the host was in: the innermost ``tw.`` span of ``host``
    (host clock, shifted by ``offset``) that covers the instant, or
    ``CLIENT`` where none does (the caller's code: in the benchmark the
    builder's state program, its readback, its gates)."""
    spans = sorted((s + offset, s + offset + d, name)
                   for s, d, name, _ in host if name.startswith(SPAN_PREFIX))
    acc: Dict[str, int] = {}
    for g0, dur in gaps:
        g1 = g0 + dur
        over = [sp for sp in spans if sp[0] < g1 and sp[1] > g0]
        cuts = sorted({g0, g1} | {t for sp in over for t in sp[:2]
                                  if g0 < t < g1})
        for a, b in zip(cuts, cuts[1:]):
            # spans of one thread nest: the innermost started last
            inner = max((sp for sp in over if sp[0] <= a and sp[1] >= b),
                        key=lambda sp: (sp[0], -sp[1]), default=None)
            owner = inner[2] if inner else CLIENT
            acc[owner] = acc.get(owner, 0) + b - a
    return acc


#: components of an ``op_name`` path that JAX's own transformations
#: put there (``cond/branch_3_fun``, ``while/body``, ``jit(_where)``)
_STRUCTURAL = re.compile(r"(cond|while|body|scan|branch_\d+_fun|\w+\(.*\))$")


def stage_of(op_name: str, depth: int = 1) -> str:
    """The top-level ``tw.`` scope of an ``op_name`` path
    (``jit(f)/while/body/tw.route/cond/branch_3_fun/insert/sort:`` is
    ``tw.route``, and ``tw.route/insert`` at ``depth=2``), or
    ``UNSCOPED``. The path's last component is the operation itself,
    and those of JAX's own transformations are no scopes of the
    program's."""
    parts = op_name.split("/")[:-1]
    for i, part in enumerate(parts):
        if part.startswith(SPAN_PREFIX):
            nested = [p for p in parts[i + 1:] if not _STRUCTURAL.match(p)]
            return "/".join([part] + nested[:depth - 1])
    return UNSCOPED


def stage_ns(ops: Sequence[Event], scopes: Sequence[str], depth: int = 1
             ) -> Dict[str, int]:
    """Device nanoseconds of the leaf operations ``ops`` by the
    top-level ``tw.`` scope of each one's ``op_name`` (``scopes``,
    parallel to ``ops``); an operation under none counts as
    ``UNSCOPED``. ``depth=2`` keeps the scope nested in it too
    (``tw.route/insert``; ``tw.route`` alone is then the stage's own
    operations)."""
    acc: Dict[str, int] = {}
    for (_, d, _), scope in zip(ops, scopes):
        stage = stage_of(scope, depth)
        acc[stage] = acc.get(stage, 0) + d
    return acc


def loop_idle_ns(ops: Iterable[Event], asyncs: Iterable[Event],
                 modules: Sequence[Event]) -> int:
    """Device-idle nanoseconds inside the executions of the main
    program: each execution's length less the time an operation ran or
    a copy was in flight inside it. What ``device_idle_share`` holds
    less what ``sync_gap_ms`` holds."""
    main = trace_reduce.main_program(modules)
    events = sorted(list(ops) + list(asyncs))
    starts = [e[0] for e in events]
    idle = 0
    for s, d, name in modules:
        if name != main:
            continue
        # an operation of this execution starts inside it
        inside = events[bisect.bisect_left(starts, s):
                        bisect.bisect_left(starts, s + d)]
        idle += d - trace_reduce.union_ns(inside, s, s + d)
    return idle


# -- what the readers ask ------------------------------------------------------

def supersteps(run: dict) -> int:
    return sum(j["supersteps"] for j in run["jobs"])


def stages(trace, run) -> Optional[Dict[str, float]]:
    """Nanoseconds by stage, averaged over the chips read; ``None``
    where the run brought no :class:`Spans` or no operation of the
    trace lies under a ``tw.`` scope (a program without them)."""
    spans = run.get("spans")
    if spans is None or not spans.scopes:
        return None
    acc: Dict[str, float] = {}
    for ops, scopes in zip(trace.ops, spans.scopes):
        for stage, ns in stage_ns(ops, scopes).items():
            acc[stage] = acc.get(stage, 0.0) + ns / len(trace.ops)
    if set(acc) <= {UNSCOPED}:
        return None
    return acc


def stage_us(trace, run, stage: str) -> Optional[float]:
    """Device microseconds a superstep under the scope ``stage``."""
    acc, steps = stages(trace, run), supersteps(run)
    if acc is None or not steps or stage not in acc:
        return None
    return acc[stage] / steps / 1e3


def clock(spans: Spans) -> Tuple[int, int]:
    """:func:`clock_bracket` of a loaded trace."""
    return clock_bracket(*causal_pairs(spans.launches, spans.programs))


def gap_owners_ms(trace, run) -> Optional[Dict[str, float]]:
    """Milliseconds a job of the idle time between main programs by
    owner, at the middle of the clock's bracket; ``None`` where the
    run brought no :class:`Spans` or the trace holds no ``tw.`` span."""
    spans = run.get("spans")
    if spans is None or not any(
            name.startswith(SPAN_PREFIX) for _, _, name, _ in spans.host):
        return None
    events = trace.ops[0] + trace.asyncs[0]
    gaps = gaps_between_programs(trace.modules, events)
    # the mean is over what sync_gap_ms takes it over
    n = len(trace_reduce.gaps_between_jobs(trace.modules, events))
    if not n:
        return None
    lo, hi = clock(spans)
    owners = owner_of_gaps(gaps, spans.host, (lo + hi) // 2)
    return {k: v / n / 1e6 for k, v in owners.items()}


# -- reading the profiler's file ---------------------------------------------

def _varint(buf, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(number, value)`` of each field of one protobuf message:
    varints as ints, length-delimited fields as slices of ``buf``
    (nothing is copied, so a field not asked for costs its key)."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, value


def _text(buf) -> str:
    return bytes(buf).decode("utf-8", "replace")


def _map_entries(plane, number: int):
    """The values of the plane's map field ``number``."""
    for num, entry in _fields(plane):
        if num == number:
            for k, v in _fields(entry):
                if k == 2:
                    yield v


def op_names(path: str) -> Dict[str, Dict[str, str]]:
    """For each device plane of the ``.xplane.pb`` at ``path``, by plane
    name: the ``op_name`` of each operation (the metadata stat
    ``OP_NAME_STAT``), keyed by the operation's event name, which is its whole
    HLO text (XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4,
    .stat_metadata = 5; XEventMetadata.name = 2, .stats = 5;
    XStat.metadata_id = 1, .str_value = 5, .ref_value = 7;
    XStatMetadata.id = 1, .name = 2)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: Dict[str, Dict[str, str]] = {}
    for num, plane in _fields(space):
        if num != 1:
            continue
        name = next((_text(v) for k, v in _fields(plane) if k == 2), "")
        if not name.startswith(trace_reduce.DEVICE_PLANE):
            continue
        stat_names = {}
        for meta in _map_entries(plane, 5):
            f = dict(_fields(meta))
            stat_names[f.get(1, 0)] = _text(f.get(2, b""))
        wanted = {i for i, n in stat_names.items() if n == OP_NAME_STAT}
        names = out[name] = {}
        for meta in _map_entries(plane, 4):
            event, value = "", None
            for k, v in _fields(meta):
                if k == 2:
                    event = _text(v)
                elif k == 5:
                    st = dict(_fields(v))
                    if st.get(1) in wanted:
                        value = (_text(st[5]) if 5 in st
                                 else stat_names.get(st.get(7), ""))
            if value is not None:
                names[event] = value
    return out


def load(path: str, trace: Optional[trace_reduce.Trace] = None) -> Spans:
    """Read ``path``, an ``.xplane.pb``. ``trace`` is what
    ``trace_reduce.load`` made of the same file: ``scopes`` is parallel
    to its ``ops`` (and empty without it)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    host, launches, programs = [], [], []
    device_planes = []
    for plane in data.planes:
        if plane.name.startswith(trace_reduce.DEVICE_PLANE):
            by_line = {line.name: line for line in plane.lines}
            if trace_reduce.OPS_LINE not in by_line:
                continue
            device_planes.append(plane.name)
            if not programs and trace_reduce.MODULES_LINE in by_line:
                for e in by_line[trace_reduce.MODULES_LINE].events:
                    rid = dict(e.stats).get("run_id")
                    if rid is not None:
                        programs.append((int(e.start_ns), int(e.duration_ns),
                                         e.name, int(rid)))
        elif plane.name.startswith(trace_reduce.HOST_PLANE):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX) \
                            or e.name == trace_reduce.JOB_SPAN:
                        host.append((int(e.start_ns), int(e.duration_ns),
                                     e.name, dict(e.stats)))
                    elif e.name in (LAUNCH_EVENT, DONE_EVENT):
                        rid = dict(e.stats).get("run_id")
                        if rid is not None:
                            launches.append((int(e.start_ns),
                                             int(e.duration_ns), e.name,
                                             int(rid)))
    scopes = []
    if trace is not None:
        by_plane = op_names(path)
        for plane, ops in zip(device_planes, trace.ops):
            names = by_plane.get(plane, {})
            scopes.append([names.get(name, "") for _, _, name in ops])
    return Spans(host=sorted(host, key=lambda s: s[:3]), scopes=scopes,
                 launches=sorted(launches), programs=sorted(programs))
