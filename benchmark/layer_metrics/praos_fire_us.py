"""Superstep, XLA: device microseconds a superstep under the scope
``tw.fire``, whole: every node's step under ``vmap`` (the longest tip
of the inbox, the threshold compare, eight chained generator draws and
their distinctness) and the firing entropy nested in it."""

import steady_reduce


def read(trace, run):
    return steady_reduce.scope_us(trace, run, "tw.fire")
