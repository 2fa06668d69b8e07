"""Superstep, XLA: device microseconds a superstep under the scopes
``tw.deliver/sort`` and ``tw.rebase/compact`` together: the two
variadic sorts along the mailbox's slots that an ordered inbox costs
(the inbox in due-time and arrival order; the compaction that keeps
arrival order in slot order, with the kept messages a node). Nothing to
read from a program that has neither scope (before PR 39 their time was
the stages' own)."""

import steady_reduce


def read(trace, run):
    parts = [steady_reduce.scope_us(trace, run, scope)
             for scope in ("tw.deliver/sort", "tw.rebase/compact")]
    return None if None in parts else sum(parts)
