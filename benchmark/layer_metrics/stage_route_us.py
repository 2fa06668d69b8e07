"""Superstep, XLA: device microseconds a superstep under the scope
``tw.route``: routing the outboxes (stage 6: sampling, the ladder, exchange, insertion).
Leaf operations by the ``op_name`` the program's ``jax.named_scope``
gave them (``span_reduce.stage_ns``). Nothing to read where the run
brings no spans or the program names no stage."""

import span_reduce


def read(trace, run):
    return span_reduce.stage_us(trace, run, "tw.route")
