"""A toy copy of the praos fleet's cell for the CPU tests, as ``toy.py``
makes them of the cells it knows: four worlds of 2048 nodes on the
source's four medians, where the four seeds mint 2, 3, 4 and 4 blocks in
the first slot and the worlds take 44, 47, 50 and 50 supersteps: the
state is stopped mid-flood after 22."""

import toy


def fleet(base, name="toy_praos_fleet.fleet4", n=2048, mid=22, **cuts):
    return toy.make(base, "praos_1m.fleet4", name, **{
        "n_nodes": n, "mid_supersteps": mid, **cuts})
