"""Superstep, XLA: device microseconds a superstep under the scope
``tw.next_event``: finding the global next event time (the min-reductions of the loop's condition and of the superstep's head).
Leaf operations by the ``op_name`` the program's ``jax.named_scope``
gave them (``span_reduce.stage_ns``). Nothing to read where the run
brings no spans or the program names no stage."""

import span_reduce


def read(trace, run):
    return span_reduce.stage_us(trace, run, "tw.next_event")
