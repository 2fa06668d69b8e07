"""From a profiler trace to numbers: the one reduction every per-layer
metric of the benchmark reads.

Two halves. ``load`` reads the ``.xplane.pb`` that ``jax.profiler``
wrote (``jax.profiler.ProfileData``, nothing but JAX) and returns a
:class:`Trace` of ``(start_ns, duration_ns, name)`` tuples. Everything
else is a pure function over such tuples (``tests/test_trace_reduce.py``
holds them to hand-made cases), so a later PR that wants a new metric
writes a reader over a :class:`Trace` and touches nothing here.

What a v5e trace looks like (looked at by hand, PERF.md PR 23):

- One plane ``/device:TPU:<i>`` per chip. Its line ``XLA Modules`` has
  one event per executed program (``jit__run_while(<hash>)``), ``XLA
  Ops`` one per HLO operation executed, named by the operation's whole
  HLO text, and ``Async XLA Ops`` the copies in flight beside them.
- A ``while`` or ``conditional`` operation is one event of ``XLA Ops``
  that spans its whole body, with the body's operations nested inside
  it on the same line. Busy time is therefore taken over the *leaf*
  events (those that contain no other), or every loop would read as
  fully busy.
- The host's ``TraceAnnotation`` spans are on the line ``python`` of
  ``/host:CPU``, on a clock that stands 0.5 to 1 ms off the device's
  (device programs appear to start before the host span that
  dispatches them). So nothing here places a device event inside a
  host span: the host spans give the traced window's length, jobs are
  told apart on the device's own clock by the executions of the main
  program, and every device event of the trace belongs to the window
  (the harness runs nothing else while it traces).
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Iterable, List, NamedTuple, Optional, Sequence, Tuple

Event = Tuple[int, int, str]          # (start_ns, duration_ns, name)

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
JOB_SPAN = "bench_job"


class Trace(NamedTuple):
    """One traced slice, reduced to tuples: per chip the leaf
    operations (``ops``) and the copies in flight (``asyncs``); the
    programs the first chip executed (``modules``); the host's span of
    each traced job (``jobs``)."""
    ops: List[List[Event]]
    asyncs: List[List[Event]]
    modules: List[Event]
    jobs: List[Event]


# -- pure functions over (start, duration, name) tuples ---------------------

def leaves(events: Iterable[Event]) -> List[Event]:
    """The events that contain no other event of the list: what is
    left of a line once every enclosing ``while``/``conditional``/
    ``call`` event is dropped. An event contains another when it
    starts no later and ends no earlier (an event equal to its
    neighbour in both is kept once as the leaf)."""
    evs = sorted(events, key=lambda e: (e[0], -e[1]))
    out: List[Event] = []
    for i, (s, d, name) in enumerate(evs):
        nxt = evs[i + 1] if i + 1 < len(evs) else None
        # sorted by start, longest first: an event is a parent exactly
        # when the next one starts inside it and ends inside it
        if nxt is not None and nxt[0] >= s and nxt[0] + nxt[1] <= s + d \
                and (nxt[0], nxt[1]) != (s, d):
            continue
        if out and (out[-1][0], out[-1][1]) == (s, d):
            continue
        out.append((s, d, name))
    return out


def union_ns(events: Iterable[Event], lo: Optional[int] = None,
             hi: Optional[int] = None) -> int:
    """Nanoseconds covered by at least one event, inside ``[lo, hi)``
    where given. Overlapping and nested events count once."""
    total = 0
    end = None
    for s, d, _ in sorted(events, key=lambda e: e[0]):
        e = s + d
        if lo is not None:
            s = max(s, lo)
        if hi is not None:
            e = min(e, hi)
        if e <= s:
            continue
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def sums_by_name(events: Iterable[Event]) -> List[Tuple[str, int, int]]:
    """``(name, total_ns, count)`` per event name, longest first."""
    acc = {}
    for _, d, name in events:
        t = acc.setdefault(name, [0, 0])
        t[0] += d
        t[1] += 1
    return sorted(((k, v[0], v[1]) for k, v in acc.items()),
                  key=lambda r: (-r[1], r[0]))


def named(events: Iterable[Event], part: str) -> List[Event]:
    """The events whose name holds ``part``."""
    return [e for e in events if part in e[2]]


def idle_gaps(ops: Iterable[Event], lo: int, hi: int
              ) -> List[Tuple[int, int]]:
    """The intervals ``(start, duration)`` of ``[lo, hi)`` in which no
    event runs, longest first."""
    out = []
    cur = lo
    for s, d, _ in sorted(ops, key=lambda e: e[0]):
        if s >= hi:
            break
        if s > cur:
            out.append((cur, s - cur))
        cur = max(cur, s + d)
    if hi > cur:
        out.append((cur, hi - cur))
    return sorted(out, key=lambda g: -g[1])


def main_program(modules: Iterable[Event]) -> str:
    """Name of the program that took most of the device's time: the
    job's driver, beside which the small programs of a job (making its
    state, reducing its counters) are seen."""
    sums = sums_by_name(modules)
    if not sums:
        raise ValueError("the trace holds no executed program")
    return sums[0][0]


def gaps_between_jobs(modules: Sequence[Event], ops: Iterable[Event]
                      ) -> List[int]:
    """Device-idle nanoseconds between consecutive executions of the
    main program: from the end of one to the start of the next, less
    whatever ran in between. One job runs the main program once, so
    this is what the device waits for the host from job to job
    (readback, gates, the next dispatch)."""
    main = main_program(modules)
    runs = sorted(e for e in modules if e[2] == main)
    ops = sorted(ops)
    starts = [s for s, _, _ in ops]
    gaps = []
    for a, b in zip(runs, runs[1:]):
        lo, hi = a[0] + a[1], b[0]
        if hi > lo:
            between = ops[bisect.bisect_left(starts, lo):
                          bisect.bisect_left(starts, hi)]
            gaps.append(hi - lo - union_ns(between, lo, hi))
    return gaps


_LAYOUT = re.compile(r"\{[^{}]*\}")


def short_name(hlo: str) -> str:
    """``%copy.29 copy`` from an operation's whole HLO text
    (``%copy.29 = s32[10,1024,1024]{...} copy(s32[...] %x)``): its name
    and its opcode. Text that does not parse so is cut to 60
    characters."""
    name, sep, rest = hlo.partition(" = ")
    if not sep:
        return hlo[:60]
    prev = None
    while prev != rest:                    # layouts nest one level
        prev, rest = rest, _LAYOUT.sub("", rest)
    if rest.startswith("("):               # a tuple shape: skip it whole
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.partition(" ")[2]
    op = rest.strip().partition("(")[0]
    if not re.fullmatch(r"[a-z][a-z0-9\-]*", op):
        return hlo[:60]
    return f"{name} {op}"


# -- what the harness asks of a Trace ---------------------------------------

def busy_and_window(tr: Trace) -> Tuple[float, int]:
    """Nanoseconds in which an operation ran, or a copy was in flight,
    on the device, averaged over the chips read; and the length of the
    traced window, from the start of the first traced job to the end
    of the last on the host's clock."""
    if not tr.jobs:
        raise ValueError(f"no '{JOB_SPAN}' span in the trace")
    window = max(s + d for s, d, _ in tr.jobs) - min(s for s, _, _ in tr.jobs)
    busy = sum(union_ns(o + a) for o, a in zip(tr.ops, tr.asyncs))
    return busy / len(tr.ops), window


def breakdown(tr: Trace, top: int = 10) -> dict:
    """For the first chip, in seconds: the operations that took most
    device time, by name and opcode; and where the device sat idle, by
    its own clock: between executions of programs (named by the
    program that came next: the host was reading back, gating or
    dispatching) or inside a program (named by the operation that came
    next)."""
    ops = tr.ops[0]
    device_ops = [[short_name(name), ns / 1e9]
                  for name, ns, _ in sums_by_name(ops)[:top]]
    everything = ops + tr.asyncs[0]
    lo = min(s for s, _, _ in everything)
    hi = max(s + d for s, d, _ in everything)
    next_op = {s: "before " + short_name(name) for s, _, name in everything}
    programs = sorted((s, name.partition("(")[0]) for s, _, name in tr.modules)
    program_starts = [s for s, _ in programs]
    acc = {}
    for s, d in idle_gaps(everything, lo, hi):
        # a gap in which a program starts lies between programs
        j = bisect.bisect_right(program_starts, s + d) - 1
        if j >= 0 and program_starts[j] >= s:
            label = "between programs, before " + programs[j][1]
        else:
            label = next_op.get(s + d, "(unnamed)")
        acc[label] = acc.get(label, 0) + d
    idle = sorted(acc.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": device_ops,
            "idle_gaps": [[name, ns / 1e9] for name, ns in idle]}


# -- reading the profiler's file --------------------------------------------

def find_xplane(logdir: str) -> str:
    """The one ``.xplane.pb`` under a ``jax.profiler`` log directory."""
    found = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return found[-1]


def _line_events(line) -> List[Event]:
    return [(int(e.start_ns), int(e.duration_ns), e.name)
            for e in line.events]


def load(path: str) -> Trace:
    """Read ``path``, an ``.xplane.pb``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops, asyncs, modules, jobs = [], [], [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            by_line = {line.name: line for line in plane.lines}
            if OPS_LINE not in by_line:
                continue
            ops.append(leaves(_line_events(by_line[OPS_LINE])))
            asyncs.append(_line_events(by_line[ASYNC_LINE])
                          if ASYNC_LINE in by_line else [])
            if not modules and MODULES_LINE in by_line:
                modules = _line_events(by_line[MODULES_LINE])
        elif plane.name.startswith(HOST_PLANE):
            for line in plane.lines:
                jobs.extend(e for e in _line_events(line)
                            if e[2] == JOB_SPAN)
    if not ops:
        raise ValueError(
            f"{path}: no '{OPS_LINE}' line on any '{DEVICE_PLANE}*' "
            f"plane (planes: {[p.name for p in data.planes]})")
    return Trace(ops=ops, asyncs=asyncs, modules=modules, jobs=sorted(jobs))
