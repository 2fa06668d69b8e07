"""Device time by scope for a solo engine whose builder brought the
profile's ``op_name``s (``facts()["op_names"]``, read in ``compare``
while ``run.py`` still has the file: README_fleet.md): what
``fleet_reduce.stage_us`` reads, one level further down. The scopes
nested in a stage (``tw.route/sort``, ``tw.route/insert``) take their
time out of the stage's own at depth 2, so the stage whole is read at
depth 1 and a nested scope at depth 2.
"""

import fleet_reduce
import span_reduce


def scope_us(trace, run, scope: str):
    """Device microseconds a superstep of the leaf operations under
    ``scope`` (``tw.route``, or ``tw.route/sort``); ``None`` where the
    builder brought no names, or the program names no such scope (a
    parent commit from before the scope)."""
    acc = fleet_reduce.stage_ns(trace, run, scope.count("/") + 1)
    steps = span_reduce.supersteps(run)
    if acc is None or not steps or scope not in acc:
        return None
    return acc[scope] / steps / 1e3
