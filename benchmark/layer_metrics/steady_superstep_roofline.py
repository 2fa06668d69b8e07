"""Superstep, XLA: the least time the chip could take for one full-width
superstep of the general engine as a share of the device time one took,
in percent. The least time is the bytes ``steady_costs.steady_superstep_
bytes`` says it cannot avoid (every per-node plane and both mailbox
planes read once and written once, plus the words of one message a
node) over the published HBM bandwidth; the time is the device-busy
time over the supersteps the traced jobs ran (``steady_superstep_us``).
HBM-bound: a few integer operations a byte. There is no kernel here:
the share prices what a fused full-width superstep could gain."""

from layer_metrics import superstep_us


def read(trace, run):
    nbytes = run["facts"].get("superstep_bytes")
    busy_us = superstep_us.read(trace, run)
    if not nbytes or not run["peaks"] or not busy_us:
        return None
    return 100.0 * nbytes / (run["peaks"]["hbm_gbps"] * 1e3) / busy_us
