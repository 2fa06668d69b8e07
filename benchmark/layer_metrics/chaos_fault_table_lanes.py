"""Faults: the lanes a world's fault masks compare an iteration, from
the engine's ``last_run_stats`` ``fault_table_lanes`` of the traced
jobs' calls over their iterations: crash rows x nodes in the horizon
and at a reboot, partition rows x 2 x outbox lanes, crash rows and link
rows x the message lanes of the rung taken (``JaxEngine.
_fault_table_lanes``: shapes and the rungs' sum, no device work). The
number per-node tables (ROADMAP M4) would bring down. Nothing to read
from a program that does not count it."""


def read(trace, run):
    lanes = [j.get("fault_table_lanes") for j in run["jobs"]]
    steps = sum(j["supersteps"] for j in run["jobs"])
    if not lanes or None in lanes or not steps:
        return None
    return sum(lanes) / steps
