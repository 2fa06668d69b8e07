"""Superstep, XLA: device microseconds an iteration of the praos
fleet's loop under the scope ``tw.fire``, whole: every node's step in
every world (the longest tip of the inbox, the threshold compare, eight
chained generator draws and their distinctness) and the firing entropy
nested in it, with the ``vmap(...)`` wrappers taken off
(``fleet_reduce.unwrap``). Nothing to read where the builder brought no
``op_name``s or the program names no stage."""

import fleet_reduce


def read(trace, run):
    return fleet_reduce.stage_us(trace, run, "tw.fire")
