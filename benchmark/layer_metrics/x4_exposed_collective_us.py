"""Sharding: the part of ``x4_collective_us`` in which no other leaf
operation ran on the same chip: what the superstep waits for a
collective and hides behind nothing. Averaged over the chips. ``None``
from a trace that holds no collective."""

import x4_reduce


def read(trace, run):
    return x4_reduce.us_a_superstep(trace, run, x4_reduce.exposed_ns)
