"""Sharding: device microseconds a superstep under the scope
``tw.route/exchange/bucket``: the variadic sort of a device's outbox
lanes by destination shard, the ranks, and the scatters into a
``[shards, bucket_cap]`` buffer a plane; averaged over the chips.
Nothing to read from a program that does not name the scope (the
parent of PR 49: its bucketing is ``tw.route/exchange``'s own)."""

import steady_x4_costs
import x4_reduce


def read(trace, run):
    return x4_reduce.scope_us(trace, run, steady_x4_costs.BUCKET_SCOPE)
