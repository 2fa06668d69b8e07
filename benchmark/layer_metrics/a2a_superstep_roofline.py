"""Superstep, XLA: the least time a chip could take for one full-width
superstep of its shard as a share of the device time one took, in
percent. The least time is the bytes ``steady_x4_costs.superstep_bytes``
says it cannot avoid (the one-chip steady superstep's at the chip's
nodes, plus the exchange buffers written and read once each way; the
builder's ``facts()["superstep_bytes"]``) over the published HBM
bandwidth; the time is ``a2a_superstep_us``: ``steady_superstep_
roofline``'s reading of this cell's bytes. HBM-bound: a few integer
operations a byte, and no rate of the interconnect is in ``peaks.json``
to hold the transfer to. There is no kernel here: the share prices what
a superstep that touched its shard and its buckets once, and waited for
no other chip, would take."""

from layer_metrics import steady_superstep_roofline


def read(trace, run):
    return steady_superstep_roofline.read(trace, run)
