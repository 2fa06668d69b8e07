"""Superstep, XLA: device-busy microseconds (leaf operations, copies in
flight beside them counted once) over the supersteps the traced jobs
ran: ``superstep_us``'s reading, of a Praos world's two slots (one
full-width firing a slot under the ladder's smallest rung, then a flood
that climbs the ladder and comes down again)."""

from layer_metrics import superstep_us


def read(trace, run):
    return superstep_us.read(trace, run)
