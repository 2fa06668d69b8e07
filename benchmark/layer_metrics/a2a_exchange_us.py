"""Sharding: device microseconds a superstep under the scope
``tw.route/exchange`` (``sharded.py`` ``ShardedEngine._exchange``: the
bucketing, the ``all_to_all``s and the ``psum`` of the bucket
overflow), averaged over the chips. Nothing to read where the builder
brought no ``op_name``s or the program names no such scope."""

import steady_x4_costs
import x4_reduce


def read(trace, run):
    return x4_reduce.scope_us(trace, run, steady_x4_costs.EXCHANGE_SCOPE)
