"""Superstep, XLA: share of the routing ladder's full width that the
traced jobs' supersteps paid, in percent: ``rung_lanes`` (the rung
taken, in senders, summed over the iterations of the driver's loop)
over iterations x ``n_nodes``, from the counts of the program's record
of the calls that launched the traced main programs
(``record_reduce.lane_sums``). A fleet takes one rung for all its
worlds. ``None`` from a program that does not count its rungs."""

import record_reduce


def read(trace, run):
    return record_reduce.lane_share(trace, "rung_lanes", "full_lanes")
