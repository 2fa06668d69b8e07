"""What a trace of the node-sharded general engine needs beyond
``x4_reduce.py``: its collectives. ``x4_reduce.is_collective`` knows the
three opcodes ``MeshComm``'s ring lowers to (``ring_x4_costs
.COLLECTIVES``) and so does its ``in_flight``, which would read an
``all-to-all`` as one more operation running beside the collectives;
here a collective is one of ``steady_x4_costs.COLLECTIVES``, with
``all-to-all`` among them, synchronous (one operation) or async (a
``-start`` and a ``-done``). Times by scope are ``x4_reduce.scope_us``'s,
opcodes ``x4_reduce.opcode``'s, unions ``trace_reduce.union_ns``'s.

Everything here is a pure function over a ``trace_reduce.Trace`` and
the run's facts (``tests/test_steady_x4_rehearsal.py`` holds them to
hand-made four-plane traces). Nothing to read is ``None``, never 0.
"""

from typing import Iterable, List, Optional

import span_reduce
import steady_x4_costs
import trace_reduce
import x4_reduce
from trace_reduce import Event


def is_collective(hlo: str) -> bool:
    return x4_reduce.opcode(hlo).startswith(steady_x4_costs.COLLECTIVES)


def collectives(events: Iterable[Event]) -> List[Event]:
    return [e for e in events if is_collective(e[2])]


def in_flight(ops: Iterable[Event]) -> List[Event]:
    """``x4_reduce.in_flight`` over this engine's collectives: one
    event a collective that one chip started and finished, from the
    start of its ``-start`` half to the end of its ``-done`` half,
    named by the start. A synchronous collective has no halves and
    adds nothing here: it is its own leaf operation."""
    pending, out = {}, []
    for s, d, hlo in sorted(collectives(ops)):
        name, _, op = trace_reduce.short_name(hlo).partition(" ")
        if op.endswith("-start"):
            pending[name.replace("-start", "-done")] = (s, hlo)
        elif op.endswith("-done") and name in pending:
            s0, start = pending.pop(name)
            out.append((s0, s + d - s0, start))
    return out


def _collective_events(ops, asyncs) -> List[Event]:
    ops = list(ops)
    return collectives(ops) + in_flight(ops) + collectives(asyncs)


def collective_ns(ops: Iterable[Event], asyncs: Iterable[Event]) -> int:
    """Nanoseconds of one chip in which a collective ran or was in
    flight (``x4_reduce.collective_ns``, with ``all-to-all``)."""
    return trace_reduce.union_ns(_collective_events(ops, asyncs))


def exposed_ns(ops: Iterable[Event], asyncs: Iterable[Event]) -> int:
    """The part of :func:`collective_ns` in which no other leaf
    operation ran on the same chip."""
    ops = list(ops)
    rest = [e for e in ops if not is_collective(e[2])]
    return trace_reduce.union_ns(rest + _collective_events(ops, asyncs)) \
        - trace_reduce.union_ns(rest)


def us_a_superstep(trace, run, fn) -> Optional[float]:
    """``fn(ops, asyncs)`` nanoseconds averaged over the chips read,
    as microseconds a superstep of the traced jobs; ``None`` where no
    plane holds a collective or no superstep ran."""
    steps = span_reduce.supersteps(run)
    planes = list(zip(trace.ops, trace.asyncs))
    if not steps or not any(collectives(o + a) for o, a in planes):
        return None
    return sum(fn(o, a) for o, a in planes) / len(planes) / steps / 1e3


def executed(ops: Iterable[Event]) -> int:
    """Collective operations one chip executed, an async one counted
    once (by its ``-start`` half)."""
    return sum(not x4_reduce.opcode(hlo).endswith("-done")
               for _, _, hlo in collectives(ops))


def counted(run, key: str) -> Optional[List[int]]:
    """The traced jobs' ``key`` (a count of ``last_run_stats`` the
    builder put on each job); ``None`` where a job has none (a program
    that does not count it) or no job ran."""
    values = [j.get(key) for j in run["jobs"]]
    return None if not values or None in values else values
