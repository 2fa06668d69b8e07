"""Superstep, XLA: the least time a chip could take for one superstep of
its shard as a share of the device time one took, in percent. The least
time is the bytes ``ring_x4_costs.x4_superstep_bytes`` says it cannot
avoid (its nodes of every per-node leaf of ``EdgeState`` read once and
written once) over the published HBM bandwidth; the time is
``x4_superstep_us``. HBM-bound: a few integer operations a byte. There
is no kernel here: the share prices what a superstep that touched its
shard once, and waited for no collective, would take."""

from layer_metrics import superstep_us


def read(trace, run):
    nbytes = run["facts"].get("superstep_bytes")
    busy_us = superstep_us.read(trace, run)
    if not nbytes or not run["peaks"] or not busy_us:
        return None
    return 100.0 * nbytes / (run["peaks"]["hbm_gbps"] * 1e3) / busy_us
