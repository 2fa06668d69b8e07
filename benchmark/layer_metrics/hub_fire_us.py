"""Superstep, XLA: device microseconds a superstep under the scope
``tw.fire``, whole: every node's step under ``vmap``, the observer's
check among it (a ``lax.scan`` over the inbox's width, in inbox
order)."""

import steady_reduce


def read(trace, run):
    return steady_reduce.scope_us(trace, run, "tw.fire")
