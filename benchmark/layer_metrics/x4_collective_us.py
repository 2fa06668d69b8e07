"""Sharding: microseconds a superstep in which a collective ran or was
in flight on a chip (``collective-permute``, ``all-gather``,
``all-reduce``: leaf operations, both halves of an async one, and the
async line's events, counted once where they overlap), averaged over
the chips. ``None`` from a trace that holds no collective."""

import x4_reduce


def read(trace, run):
    return x4_reduce.us_a_superstep(trace, run, x4_reduce.collective_ns)
