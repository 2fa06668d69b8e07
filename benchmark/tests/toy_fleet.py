"""A toy copy of the fleet's cell for the CPU tests, as ``toy.py`` makes
them of the cells it knows: four worlds of 2048 nodes."""

import toy


def fleet(base, name="toy_gossip.fleet4", **cuts):
    return toy.make(base, "gossip_100k.fleet8", name, **{
        "n_nodes": 2048, "worlds": 4, "world_seeds": [0, 1, 2, 3], **cuts})
