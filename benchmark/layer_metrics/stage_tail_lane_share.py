"""Superstep, XLA: share of the densely staged message lanes that the
staging's tail scattered, in percent: the engine's ``last_run_stats``
``tail_lanes`` (the width the scatters of the arrivals that no row of
the network took ran at, summed over the iterations of the driver's
loop) over ``dense_lanes`` (the lanes of every iteration staged in the
dense form), from the program's record of the calls that launched the
traced main programs (``record_reduce``'s pairing). PR 36's form reads
50 or 100 (half the lanes or all of them); since PR 44 the width is
the smallest of a ladder that holds the tail. ``None`` from a program
that does not count it, and where no traced call staged densely
(README_stage_tail.md)."""

import record_reduce


def share(records):
    """``100 * tail_lanes / dense_lanes`` over ``records``; ``None``
    where any of them lacks one of the two, or no lane was staged."""
    tail = lanes = 0
    for rec in records:
        counts = rec["counts"]
        if "tail_lanes" not in counts or "dense_lanes" not in counts:
            return None
        tail += counts["tail_lanes"]
        lanes += counts["dense_lanes"]
    return 100.0 * tail / lanes if lanes else None


def read(trace, run):
    red = record_reduce.of_trace(trace)
    records = record_reduce.records()
    if red is None or not records:
        return None
    paired = record_reduce.driver_calls(records)[
        red["shift"]:red["shift"] + red["paired"]]
    return share([records[i] for i, _ in paired])
