"""Superstep, XLA: device microseconds an iteration of the
world-sharded fleet's loop under the scope ``tw.route`` (sampling, the
rung a chip took, exchange, insertion), the ``vmap(...)`` and
``shard_map`` wrappers JAX puts around the scope's name taken off
(``fleet_x4_reduce.scope_of``), averaged over the chips. Nothing to
read where the builder brought no ``op_name``s or the program names no
stage."""

import fleet_x4_reduce


def read(trace, run):
    return fleet_x4_reduce.stage_us(trace, run, "tw.route")
