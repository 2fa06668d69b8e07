"""A toy copy of the four-chip ring's cell for the CPU tests, as
``toy.py`` makes them of the cells it knows: 4096 nodes over four
virtual devices, twelve supersteps a job."""

import toy


def dense(base, name="toy_ring_x4.dense", **cuts):
    return toy.make(base, "ring_1m_x4.dense", name, **{
        "n_nodes": 4096, "supersteps_per_job": 12, **cuts})
