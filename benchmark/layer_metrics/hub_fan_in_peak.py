"""Superstep, XLA: the most arrivals to one destination in one
superstep, the largest over the traced jobs' calls, from the engine's
``last_run_stats`` ``fan_in_peak`` (the ranked insertion's largest rank
+ 1 over its valid lanes, carried beside the state and read in the
call's one readback). The ring with its hub reads the ring's size: every
note of a cycle reaches the hub at one instant. Nothing to read from a
program that does not count it."""


def read(trace, run):
    peaks = [j.get("fan_in_peak") for j in run["jobs"]]
    if not peaks or None in peaks:
        return None
    return max(peaks)
