"""Sharding: how unevenly the mesh's chips were busy, in percent: the
busiest plane's device-busy time less the idlest's, over the busiest's,
inside the traced jobs' main programs
(``fleet_x4_reduce.plane_busy_ns``). The chips run in lockstep at the
loop's liveness reduction, so what one is busy less than another it
spends waiting there. ``None`` from a trace of fewer than two planes."""

import fleet_x4_reduce


def read(trace, run):
    if len(trace.ops) < 2 or not trace.modules:
        return None
    busy = fleet_x4_reduce.plane_busy_ns(trace)
    top = max(busy)
    return 100.0 * (top - min(busy)) / top if top else None
