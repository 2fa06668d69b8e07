"""Superstep, XLA: device microseconds an iteration of the chaos
fleet's loop under the scope ``tw.route`` (stage 6: the partition's
cut, the sender compaction, the ladder's rung with the link's draw
before its sort, the down-window drop, insertion), with the
``vmap(...)`` JAX wraps a fleet's scope names in taken off
(``fleet_reduce.unwrap``). Nothing to read where the builder brought no
``op_name``s or the program names no stage."""

import fleet_reduce


def read(trace, run):
    return fleet_reduce.stage_us(trace, run, "tw.route")
