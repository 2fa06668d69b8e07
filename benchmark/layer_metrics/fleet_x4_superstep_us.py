"""Superstep, XLA: device-busy microseconds (leaf operations, copies and
the liveness reduction in flight beside them counted once) over the
iterations of the world-sharded fleet's loop the traced jobs ran,
averaged over the chips of the mesh: ``fleet_superstep_us``'s reading
(a fleet job's ``supersteps`` is ``fleet_iterations``, the largest of
its 32 worlds' counts). One iteration steps every world of every chip,
quiet or not, each chip at the rung its own busiest world asks for."""

from layer_metrics import superstep_us


def read(trace, run):
    return superstep_us.read(trace, run)
