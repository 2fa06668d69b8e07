"""Superstep, XLA: share of the routing rungs' lanes that the ranked
insertion handed to each of its scatters, in percent: the engine's
``last_run_stats`` ``scatter_lanes`` (the width its ladder of static
widths took, summed over the iterations of the driver's loop; the
call's lanes where one scatter ran) over ``rung_lanes`` x ``max_out``
(the rungs taken, in senders, times the outbox slots a sender has: the
lanes a rung sorts and, before PR 43, scattered), from the program's
record of the calls that launched the traced main programs
(``record_reduce``'s pairing). The ring with its hub reads 31.8: a
cycle's three supersteps take an eighth of the wide rung (the hub keeps
8 notes of 65 536), a half (every sender uses one slot of two) and the
hub's own small rung whole. ``None`` from a program that does not count
it (README_scatter.md)."""

import record_reduce


def share(records):
    """``100 * scatter_lanes / (rung_lanes * max_out)`` over
    ``records``; ``None`` where any of them lacks one of the three."""
    scattered = lanes = 0
    for rec in records:
        counts = rec["counts"]
        if "scatter_lanes" not in counts or "rung_lanes" not in counts \
                or not rec.get("max_out"):
            return None
        scattered += counts["scatter_lanes"]
        lanes += counts["rung_lanes"] * rec["max_out"]
    return 100.0 * scattered / lanes if lanes else None


def read(trace, run):
    red = record_reduce.of_trace(trace)
    records = record_reduce.records()
    if red is None or not records:
        return None
    paired = record_reduce.driver_calls(records)[
        red["shift"]:red["shift"] + red["paired"]]
    return share([records[i] for i, _ in paired])
