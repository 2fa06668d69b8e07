"""Superstep, XLA: how full the routing ladder's rungs ran, in percent:
``sender_lanes`` (the active senders each rung was chosen for; a fleet:
its busiest world's) over ``rung_lanes`` (the rung taken), both summed
over the iterations of the traced jobs' calls
(``record_reduce.lane_sums``). What is missing from 100 % is what the
ladder's geometric steps leave empty. ``None`` from a program that does
not count its rungs."""

import record_reduce


def read(trace, run):
    return record_reduce.lane_share(trace, "sender_lanes", "rung_lanes")
