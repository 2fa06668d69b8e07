"""Superstep, XLA: device-busy microseconds (leaf operations, copies in
flight beside them counted once) over the iterations of the praos
fleet's loop the traced jobs ran: ``superstep_us``'s reading, of a job
whose ``supersteps`` is the largest of its worlds' counts
(``last_run_stats`` ``fleet_iterations``). One iteration steps every
world of the fleet that is still running, each on its own link's
median, at the ladder's rung for the busiest."""

from layer_metrics import superstep_us


def read(trace, run):
    return superstep_us.read(trace, run)
