"""Superstep, XLA: the time one pass over the praos fleet's state would
take as a share of the device time an iteration took, in percent. The
pass is the bytes of ``praos_fleet_costs.praos_fleet_superstep_bytes``
(in each world every per-node plane and the three written mailbox
planes read once and written once, plus the words of the traced jobs'
mean messages a world an iteration) over the published HBM bandwidth;
the time is the device-busy time over the iterations the traced jobs
ran (``praos_fleet_superstep_us``). There is no kernel here, and most
iterations touch few nodes: the share prices an iteration that touched
each world's state once, and cannot pass 100."""

import praos_fleet_costs
from layer_metrics import superstep_us


def read(trace, run):
    facts, busy_us = run["facts"], superstep_us.read(trace, run)
    steps = sum(j["supersteps"] for j in run["jobs"])
    worlds = facts.get("worlds")
    if (not facts.get("mailbox_cap") or not worlds or not run["peaks"]
            or not busy_us or not steps):
        return None
    nbytes = praos_fleet_costs.praos_fleet_superstep_bytes(
        facts["n_nodes"], worlds, facts["mailbox_cap"],
        facts["payload_width"],
        sum(j["msgs"] for j in run["jobs"]) / steps / worlds)
    return 100.0 * nbytes / (run["peaks"]["hbm_gbps"] * 1e3) / busy_us
