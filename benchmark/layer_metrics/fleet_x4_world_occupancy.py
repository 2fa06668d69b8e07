"""Drivers: share of the world-sharded fleet's world-supersteps that a
world still running took, in percent: ``world_occupancy``'s reading
over the 32 worlds of the mesh (``last_run_stats`` ``world_supersteps``
over worlds x ``fleet_iterations`` of the traced jobs). What is missing
from 100 % stepped worlds already quiet, on whichever chip."""

from layer_metrics import world_occupancy


def read(trace, run):
    return world_occupancy.read(trace, run)
