"""Superstep, XLA: device microseconds a superstep under the scope
``tw.route/insert``, averaged over the chips: ``_insert_sorted`` on the
lanes a device received (``steady_insert_us``'s reading on four
planes)."""

import steady_x4_costs
import x4_reduce


def read(trace, run):
    return x4_reduce.scope_us(trace, run, steady_x4_costs.INSERT_SCOPE)
