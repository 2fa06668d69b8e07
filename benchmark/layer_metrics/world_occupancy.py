"""Drivers: share of the fleet's world-supersteps that a world still
running took, in percent: the sum of the worlds' supersteps over the
worlds times the iterations of the fleet's loop, from the engine's
``last_run_stats`` (``world_supersteps``, ``fleet_iterations``) of the
traced jobs. Every iteration costs all worlds; what is missing from
100 % was spent stepping worlds already quiet. Nothing to read from a
program that does not count per world."""


def read(trace, run):
    done = total = 0
    for job in run["jobs"]:
        worlds = job.get("world_supersteps")
        if not worlds:
            return None
        done += sum(worlds)
        total += len(worlds) * job["supersteps"]
    return 100.0 * done / total if total else None
