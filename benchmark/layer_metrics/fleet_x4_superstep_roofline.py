"""Superstep, XLA: the least time a chip could take for one iteration
over its worlds as a share of the device time one took, in percent. The
least time is the bytes ``fleet_x4_costs.fleet_x4_superstep_bytes``
says it cannot avoid (every per-node plane and both written mailbox
planes of the chip's eight worlds read once and written once, plus the
words of the traced jobs' mean messages an iteration on one chip) over
the published HBM bandwidth; the time is ``fleet_x4_superstep_us``.
HBM-bound: a few integer operations a byte. There is no kernel here:
the share prices an iteration that touched a chip's worlds once and
waited for no other chip."""

import fleet_x4_costs
from layer_metrics import superstep_us


def read(trace, run):
    facts, busy_us = run["facts"], superstep_us.read(trace, run)
    steps = sum(j["supersteps"] for j in run["jobs"])
    if (not facts.get("worlds_local") or not run["peaks"] or not busy_us
            or not steps):
        return None
    chips = facts["worlds"] // facts["worlds_local"]
    nbytes = fleet_x4_costs.fleet_x4_superstep_bytes(
        facts["n_nodes"], facts["mailbox_cap"], facts["payload_width"],
        facts["worlds_local"],
        sum(j["msgs"] for j in run["jobs"]) / steps / chips)
    return 100.0 * nbytes / (run["peaks"]["hbm_gbps"] * 1e3) / busy_us
