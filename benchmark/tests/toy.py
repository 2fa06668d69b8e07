"""Toy copies of the committed cells for the CPU tests: the committed
data files with the sizes cut, written into a directory of the test's
own. ``run.py`` finds them there by name, as it would a later PR's."""

import json
import os

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(kind, name):
    with open(os.path.join(HERE, kind, name + ".json")) as f:
        return json.load(f)


def _write(base, kind, name, data):
    os.makedirs(os.path.join(base, kind), exist_ok=True)
    with open(os.path.join(base, kind, name + ".json"), "w") as f:
        json.dump(data, f)


def make(base, cell, new_cell, **cuts):
    """Write ``new_cell`` under ``base``: the committed ``cell`` with
    its configuration's ``params`` and its traffic
    updated from ``cuts``. Returns the new cell's name."""
    traffic = _load("workloads", cell)
    config = _load("configs", traffic["config"])
    config["name"] = traffic["config"] = new_cell.partition(".")[0]
    traffic["name"] = new_cell
    for key, value in cuts.items():
        if key in config["params"]:
            config["params"][key] = value
        else:
            traffic[key] = value
    _write(str(base), "configs", config["name"], config)
    _write(str(base), "workloads", new_cell, traffic)
    return new_cell


def ring(base, name="toy_ring.dense", **cuts):
    return make(base, "ring_1m.dense", name, **{
        "n_nodes": 8192, "supersteps_per_job": 12,
        **cuts})


def wave(base, name="toy_gossip.wave", **cuts):
    return make(base, "gossip_100k.wave", name, **{
        "n_nodes": 2048, **cuts})
