"""A toy copy of the chaos fleet's cell for the CPU tests, as ``toy.py``
makes them of the cells it knows: eight worlds of 512 nodes, each
under the source's schedule written at that size."""

import toy
from builders import gossip_chaos


def fleet(base, name="toy_chaos.fleet8", n=512, **cuts):
    return toy.make(base, "gossip_100k_chaos.fleet8", name, **{
        "n_nodes": n, "faults": gossip_chaos.schedules(n), **cuts})
