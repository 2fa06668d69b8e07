"""Superstep, XLA: device-busy microseconds (leaf operations, copies and
collectives in flight beside them counted once) over the supersteps the
traced jobs ran, averaged over the chips of the mesh: ``superstep_us``'s
reading of the node-sharded general engine, every superstep at full
width on a quarter of the nodes behind an ``all_to_all``."""

from layer_metrics import superstep_us


def read(trace, run):
    return superstep_us.read(trace, run)
