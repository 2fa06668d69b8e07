"""A toy copy of the steady-mongering cell for the CPU tests, as
``toy.py`` makes them of the cells it knows: 4096 nodes, which hold the
rumor after some 36 supersteps, so a ramp of 64."""

import toy


def rounds(base, name="toy_steady.rounds", **cuts):
    return toy.make(base, "gossip_steady_1m.rounds", name, **{
        "n_nodes": 4096, "ramp_supersteps": 64, **cuts})
