"""A toy copy of the observer-ring cell for the CPU tests, as ``toy.py``
makes them of the cells it knows: 256 ring nodes and their hub."""

import toy


def observer(base, name="toy_ring.observer", **cuts):
    cuts = {"n_ring": 256, **cuts}
    return toy.make(base, "ring_64k.observer", name, **{
        "n_nodes": cuts["n_ring"] + 1, "n_tokens": cuts["n_ring"], **cuts})
