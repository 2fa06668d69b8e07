"""Sharding: bytes one device hands the ``all_to_all``s a superstep
(``steady_x4_costs.exchange_bytes`` of the engine's own ``shards`` and
``bucket_cap``, as ``last_run_stats`` of the traced jobs' calls has
them: its buckets whole, full or not), of which ``(shards - 1) /
shards`` leave the chip. Nothing to read from a program that does not
count its exchange."""

import steady_x4_costs
import steady_x4_reduce


def read(trace, run):
    lanes = steady_x4_reduce.counted(run, "exchange_lanes")
    width = run["facts"].get("payload_width")
    if lanes is None or width is None:
        return None
    return max(lanes) * steady_x4_costs.lane_bytes(width)
