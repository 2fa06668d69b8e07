"""Superstep, XLA: the time one pass over a Praos world's state would
take as a share of the device time a superstep took, in percent. The
pass is the bytes of ``praos_costs.praos_superstep_bytes`` (every
per-node plane and the three written mailbox planes read once and
written once, plus the words of the traced jobs' mean messages a
superstep) over the published HBM bandwidth; the time is the
device-busy time over the supersteps the traced jobs ran
(``praos_superstep_us``). There is no kernel here, and most supersteps
touch few nodes: the share prices what a superstep that touched the
state once would take."""

import praos_costs
from layer_metrics import superstep_us


def read(trace, run):
    facts, busy_us = run["facts"], superstep_us.read(trace, run)
    steps = sum(j["supersteps"] for j in run["jobs"])
    if (not facts.get("mailbox_cap") or not run["peaks"] or not busy_us
            or not steps):
        return None
    nbytes = praos_costs.praos_superstep_bytes(
        facts["n_nodes"], facts["mailbox_cap"], facts["payload_width"],
        sum(j["msgs"] for j in run["jobs"]) / steps)
    return 100.0 * nbytes / (run["peaks"]["hbm_gbps"] * 1e3) / busy_us
