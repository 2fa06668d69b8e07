"""What a trace of several chips needs beyond ``trace_reduce.py``: which
of a plane's events are collectives, how much of their time no other
operation covers, and the ``op_name``s of every plane.

``trace_reduce.load`` gives a :class:`trace_reduce.Trace` whose ``ops``
and ``asyncs`` hold one list a chip (``modules`` is the first chip's).
Everything here but :func:`traced_op_names` is a pure function over
such tuples (``tests/test_x4_rehearsal.py`` holds them to hand-made
four-plane traces). A trace with no collective (a one-chip program)
reads as ``None``, never as 0: nothing was there to time.
"""

import os
from typing import Dict, Iterable, List, Optional

import ring_x4_costs
import span_reduce
import trace_reduce
from trace_reduce import Event

HERE = os.path.dirname(os.path.abspath(__file__))


def opcode(hlo: str) -> str:
    """``collective-permute-start`` from an event's whole HLO text."""
    return trace_reduce.short_name(hlo).rpartition(" ")[2]


def is_collective(hlo: str) -> bool:
    return opcode(hlo).startswith(ring_x4_costs.COLLECTIVES)


def collectives(events: Iterable[Event]) -> List[Event]:
    return [e for e in events if is_collective(e[2])]


def in_flight(ops: Iterable[Event]) -> List[Event]:
    """One event a collective that one chip started and finished, from
    the start of its ``-start`` half to the end of its ``-done`` half
    (``%collective-permute-start.1`` pairs with
    ``%collective-permute-done.1``), named by the start: the time the
    transfer was in flight, whatever ran beside it. It is what the
    ``Async XLA Ops`` line holds, which a v5e's profile has for its
    first chip alone (looked at by hand, PERF.md PR 37: 18 828 events
    on ``/device:TPU:0``, none on the other three), so every chip's is
    rebuilt from its own operations."""
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
    flight: the union of its leaf operations that are collectives
    (both halves of an async one), of the time from each start to its
    done (:func:`in_flight`) and of the collectives of the async
    line."""
    return trace_reduce.union_ns(_collective_events(ops, asyncs))


def exposed_ns(ops: Iterable[Event], asyncs: Iterable[Event]) -> int:
    """The part of :func:`collective_ns` in which no other leaf
    operation ran on the same chip: the union of the collectives and
    the rest, less the union of the rest."""
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
    """Collective operations one chip executed: its leaf collectives,
    an async one counted once (by its ``-start`` half)."""
    return sum(not opcode(hlo).endswith("-done")
               for _, _, hlo in collectives(ops))


def traced_op_names(workload: str, seed: int) -> Optional[Dict[str, str]]:
    """``{HLO text: op_name}`` over every chip's operations, from the
    profile ``run.py --trace 1`` wrote for this cell and seed and has
    not deleted yet (``fleet_reduce.traced_op_names`` reads the first
    chip's alone); ``None`` where there is none (an untraced run)."""
    logdir = os.path.join(HERE, "out", f"trace_{workload}_{seed}")
    try:
        by_plane = span_reduce.op_names(trace_reduce.find_xplane(logdir))
    except FileNotFoundError:
        return None
    names: Dict[str, str] = {}
    for plane in sorted(by_plane):
        names.update(by_plane[plane])
    return names or None


def scope_us(trace, run, scope: str) -> Optional[float]:
    """Device microseconds a superstep of the leaf operations under
    ``scope``, averaged over the chips read (``steady_reduce.scope_us``
    reads the first chip's); ``None`` where the builder brought no
    names or the program names no such scope (the parent of the PR
    that named it)."""
    names = run["facts"].get("op_names")
    steps = span_reduce.supersteps(run)
    if not names or not steps:
        return None
    depth = scope.count("/") + 1
    total, found = 0, False
    for ops in trace.ops:
        acc = span_reduce.stage_ns(
            ops, [names.get(hlo, "") for _, _, hlo in ops], depth)
        found = found or scope in acc
        total += acc.get(scope, 0)
    if not found:
        return None
    return total / len(trace.ops) / steps / 1e3
