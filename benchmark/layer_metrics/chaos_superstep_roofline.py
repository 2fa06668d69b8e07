"""Superstep, XLA: the least time the chip could take for one
full-width iteration of the chaos fleet as a share of the device time
one took, in percent. The least time is the bytes ``chaos_costs.
chaos_superstep_bytes`` says it cannot avoid (in each of the eight
worlds every per-node plane and both mailbox planes read once and
written once, the words of one message a node, a partition row's
groups) over the published HBM bandwidth; the time is the device-busy
time over the iterations the traced jobs ran (``chaos_superstep_us``).
HBM-bound: a few integer operations a byte. There is no kernel here:
the share prices what a fused full-width iteration could gain."""

from layer_metrics import superstep_us


def read(trace, run):
    nbytes = run["facts"].get("superstep_bytes")
    busy_us = superstep_us.read(trace, run)
    if not nbytes or not run["peaks"] or not busy_us:
        return None
    return 100.0 * nbytes / (run["peaks"]["hbm_gbps"] * 1e3) / busy_us
