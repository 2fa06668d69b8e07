"""Superstep, XLA: device-busy microseconds (leaf operations, copies in
flight beside them counted once) over the supersteps the traced jobs
ran: ``superstep_us``'s reading, of the ring with its observer hub
(a cycle of three supersteps: two at full width on the ladder's top
rung, one that fires the hub alone)."""

from layer_metrics import superstep_us


def read(trace, run):
    return superstep_us.read(trace, run)
