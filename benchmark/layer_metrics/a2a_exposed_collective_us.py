"""Sharding: the part of ``a2a_collective_us`` in which no other leaf
operation ran on the same chip: what the superstep waits for a
collective, and for the slowest chip to reach it, and hides behind
nothing. Averaged over the chips. ``None`` from a trace that holds no
collective."""

import steady_x4_reduce


def read(trace, run):
    return steady_x4_reduce.us_a_superstep(
        trace, run, steady_x4_reduce.exposed_ns)
