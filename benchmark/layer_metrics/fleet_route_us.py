"""Superstep, XLA: device microseconds an iteration of the fleet's loop
under the scope ``tw.route`` (stage 6: sampling, the routing ladder's
top rung, exchange, insertion), with the ``vmap(...)`` JAX wraps a
fleet's scope names in taken off (``fleet_reduce.unwrap``). Nothing to
read where the builder brought no ``op_name``s or the program names no
stage."""

import fleet_reduce


def read(trace, run):
    return fleet_reduce.stage_us(trace, run, "tw.route")
