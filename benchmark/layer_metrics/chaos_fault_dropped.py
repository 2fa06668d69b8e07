"""Faults: the messages a job's schedules killed, summed over its
worlds and over the three causes (cut by a partition, due inside a down
window, purged at a reboot: the growth of ``EngineState.fault_dropped``
over the job), the mean of the traced jobs. The configuration fixes it
(every job runs the same eight worlds under the same eight schedules),
so it is ``better: lower`` only in ``hub_fan_in_peak``'s sense: under
the cell's gates it cannot move, and a reading that differs from the
ledger's says that the schedules no longer bite as they did. Nothing to
read from a program that does not count it."""


def read(trace, run):
    dropped = [j.get("fault_dropped") for j in run["jobs"]]
    if not dropped or None in dropped:
        return None
    return sum(dropped) / len(dropped)
