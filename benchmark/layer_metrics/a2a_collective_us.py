"""Sharding: microseconds a superstep in which a collective ran or was
in flight on a chip (``all-to-all``, ``all-gather``, ``all-reduce``:
leaf operations, both halves of an async one, and the async line's
events, counted once where they overlap), averaged over the chips.
``None`` from a trace that holds no collective."""

import steady_x4_reduce


def read(trace, run):
    return steady_x4_reduce.us_a_superstep(
        trace, run, steady_x4_reduce.collective_ns)
