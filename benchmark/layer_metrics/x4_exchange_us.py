"""Sharding: device microseconds a superstep under the scope
``tw.route/exchange`` (``edge_engine.py``: the delivery's ``comm.roll``
calls, on a mesh the boundary ``ppermute``s and the local shift beside
them), averaged over the chips. Nothing to read where the builder
brought no ``op_name``s or the program names no such scope."""

import ring_x4_costs
import x4_reduce


def read(trace, run):
    return x4_reduce.scope_us(trace, run, ring_x4_costs.EXCHANGE_SCOPE)
