"""Faults: device microseconds an iteration of the chaos fleet's loop
of every operation under a ``fault`` scope, at whatever depth
(``chaos_reduce.fault_us``: ``tw.next_event/fault``,
``tw.route/fault``, ``tw.route/sample/fault``, ``tw.fire/fault``). What
the masks cost where the compiler left them a name of their own; the
control on which a per-node form of the tables (ROADMAP M4) must cost
nothing. Nothing to read from a program without the scope."""

import chaos_reduce


def read(trace, run):
    return chaos_reduce.fault_us(trace, run)
