"""Drivers: share of the praos fleet's world-supersteps that a world
still running took, in percent: ``world_occupancy``'s reading
(``last_run_stats`` ``world_supersteps`` over worlds x
``fleet_iterations`` of the traced jobs). The four worlds' floods end
supersteps apart, as their medians do: what is missing from 100 %
stepped worlds already quiet."""

from layer_metrics import world_occupancy


def read(trace, run):
    return world_occupancy.read(trace, run)
