"""Superstep, XLA: device microseconds an iteration of the praos
fleet's loop under the scope ``tw.route`` (stage 6: the sender
compaction, the ladder's one rung for all the worlds with each world's
link draw before its sort, insertion), with the ``vmap(...)`` JAX wraps
a fleet's scope names in taken off (``fleet_reduce.unwrap``). Nothing
to read where the builder brought no ``op_name``s or the program names
no stage."""

import fleet_reduce


def read(trace, run):
    return fleet_reduce.stage_us(trace, run, "tw.route")
