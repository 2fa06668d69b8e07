"""A toy copy of the world-sharded fleet's cell for the CPU tests, as
``toy.py`` makes them of the cells it knows: eight worlds of 1024 nodes
over four virtual devices, two a device."""

import toy


def fleet(base, name="toy_gossip_x4.fleet8", **cuts):
    return toy.make(base, "gossip_100k_x4.fleet32", name, **{
        "n_nodes": 1024, "worlds": 8, "world_seeds": list(range(8)),
        **cuts})
