"""What a trace of the world-sharded fleet needs beyond ``x4_reduce.py``
and ``fleet_reduce.py``: both at once. ``x4_reduce`` reads four device
planes and knows a collective by its opcode; ``fleet_reduce`` takes the
``vmap(...)`` wrapper off a fleet's scope names and reads the first
plane. The fleet over a mesh traces its superstep inside ``vmap``
inside ``shard_map``, and its one collective sits in the loop's
condition under the scope ``tw.liveness``
(``fleet_x4_costs.LIVENESS_SCOPE``), not in a stage.

Everything here is a pure function over a ``trace_reduce.Trace`` and
the run's facts (``tests/test_fleet_x4_rehearsal.py`` holds them to
hand-made four-plane traces). Nothing to read is ``None``, never 0.
"""

from typing import List, Optional

import fleet_reduce
import fleet_x4_costs
import span_reduce
import trace_reduce
import x4_reduce


def scope_of(op_name: str) -> str:
    """The top-level ``tw.`` scope of an ``op_name``, the wrappers of
    ``vmap`` and ``shard_map`` off (``span_reduce.stage_of`` of
    ``fleet_reduce.unwrap``)."""
    return span_reduce.stage_of(fleet_reduce.unwrap(op_name))


def stage_us(trace, run, stage: str) -> Optional[float]:
    """Device microseconds an iteration of the leaf operations under
    the scope ``stage``, averaged over the chips read:
    ``x4_reduce.scope_us`` on the names with a fleet's wrappers off.
    ``None`` where the builder brought no ``op_name``s or no plane
    names the scope."""
    names = run["facts"].get("op_names")
    if not names:
        return None
    plain = {hlo: fleet_reduce.unwrap(name) for hlo, name in names.items()}
    return x4_reduce.scope_us(
        trace, {**run, "facts": {"op_names": plain}}, stage)


def is_liveness(hlo: str, names: dict) -> bool:
    """Whether the operation ``hlo`` is the liveness reduction: a
    collective by its opcode (``x4_reduce.is_collective``) whose
    ``op_name`` lies under ``tw.liveness``. A program that does not
    name the scope (the parent of the PR that did) has none."""
    return x4_reduce.is_collective(hlo) and scope_of(
        names.get(hlo, "")) == fleet_x4_costs.LIVENESS_SCOPE


def liveness_us(trace, run, fn) -> Optional[float]:
    """``fn(ops, asyncs)`` (``x4_reduce.collective_ns`` or
    ``exposed_ns``) with the liveness reduction as a plane's only
    collective, as microseconds an iteration, averaged over the chips
    read. The other leaf operations stay, so that ``exposed_ns`` still
    knows what ran beside it. ``None`` where no plane holds one."""
    names = run["facts"].get("op_names")
    steps = span_reduce.supersteps(run)
    if not names or not steps:
        return None
    total, found = 0, False
    for ops, asyncs in zip(trace.ops, trace.asyncs):
        live = [e for e in ops if is_liveness(e[2], names)]
        rest = [e for e in ops if not x4_reduce.is_collective(e[2])]
        found = found or bool(live)
        total += fn(rest + live,
                    [e for e in asyncs if is_liveness(e[2], names)])
    return total / len(trace.ops) / steps / 1e3 if found else None


def plane_busy_ns(trace) -> List[int]:
    """Device-busy nanoseconds of each plane inside the executions of
    the main program (by the first chip's ``XLA Modules``: every chip
    runs the one SPMD program over the same intervals, to the
    collective's wait)."""
    main = trace_reduce.main_program(trace.modules)
    runs = [(s, s + d) for s, d, name in trace.modules if name == main]
    return [sum(trace_reduce.union_ns(o + a, lo, hi) for lo, hi in runs)
            for o, a in zip(trace.ops, trace.asyncs)]
