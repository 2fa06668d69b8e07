"""Sharding: the part of ``fleet_x4_liveness_us`` in which no other
leaf operation ran on the same chip (``x4_reduce.exposed_ns``): what an
iteration waits for the liveness reduction and the other chips and
hides behind nothing. Averaged over the chips. ``None`` from a program
that does not name the scope."""

import fleet_x4_reduce
import x4_reduce


def read(trace, run):
    return fleet_x4_reduce.liveness_us(trace, run, x4_reduce.exposed_ns)
