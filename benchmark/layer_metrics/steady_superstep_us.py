"""Superstep, XLA: device-busy microseconds (leaf operations, copies in
flight beside them counted once) over the supersteps the traced jobs
ran: ``superstep_us``'s reading, of a stream in which every superstep
is at full width (every node receives, every node sends)."""

from layer_metrics import superstep_us


def read(trace, run):
    return superstep_us.read(trace, run)
