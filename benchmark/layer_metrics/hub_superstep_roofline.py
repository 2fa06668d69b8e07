"""Superstep, XLA: the least time the chip could take for one superstep
of the ring with its observer hub as a share of the device time one
took, in percent. The least time is the bytes ``hub_costs.
hub_superstep_bytes`` says it cannot avoid (every per-node leaf and the
four mailbox planes read once and written once, plus the words of the
messages that take a slot) over the published HBM bandwidth; the time is
the device-busy time over the supersteps the traced jobs ran
(``hub_superstep_us``). HBM-bound: a few integer operations a byte.
There is no kernel here: the share prices what a fused superstep could
gain."""

from layer_metrics import steady_superstep_roofline


def read(trace, run):
    # the same quotient over the builder's own ``superstep_bytes``
    return steady_superstep_roofline.read(trace, run)
