"""Superstep, XLA: device microseconds a superstep under the scope
``tw.route/sort``: the eager path's one variadic sort of every outbox
slot by (destination, sender-major rank). Nothing to read from a
program that has no such scope (before PR 31 the sort's time was the
stage's own)."""

import steady_reduce


def read(trace, run):
    return steady_reduce.scope_us(trace, run, "tw.route/sort")
