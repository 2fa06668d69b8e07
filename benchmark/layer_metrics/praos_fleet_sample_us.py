"""Superstep, XLA: device microseconds an iteration of the praos
fleet's loop under the nested scope ``tw.route/sample``: each world's
link draw on the rung's lanes (the per-message entropy, the lognormal
with that world's median as a traced operand, the clamp and the
quantum), where the ``rebind_link`` tracers enter. The compiler fuses a
draw into its consumer where it can, and a fusion carries one
operation's name: what is read is the time of the operations that kept
the scope's name, a floor of the draw's cost. Nothing to read where the
builder brought no ``op_name``s or no operation kept the name."""

import fleet_reduce
import span_reduce

SCOPE = "tw.route/sample"


def read(trace, run):
    acc = fleet_reduce.stage_ns(trace, run, depth=2)
    steps = span_reduce.supersteps(run)
    if acc is None or not steps or SCOPE not in acc:
        return None
    return acc[SCOPE] / steps / 1e3
