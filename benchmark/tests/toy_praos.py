"""A toy copy of the Praos cell for the CPU tests, as ``toy.py`` makes
them of the cells it knows: 2048 nodes, where the configuration's
engine seed mints in the first slot and in the second (two blocks, then one). The most tips
in flight to one node are 15 there, so the control's 16 mailbox slots
would hold them all: the toy's control has 8."""

import json
import os

import toy


def slots(base, name="toy_praos.slots", control_cap=8, **cuts):
    toy.make(base, "praos_1m.slots", name, **{"n_nodes": 2048, **cuts})
    path = os.path.join(str(base), "configs",
                        name.partition(".")[0] + ".json")
    with open(path) as f:
        config = json.load(f)
    config["control"]["mailbox_cap"] = control_cap
    with open(path, "w") as f:
        json.dump(config, f)
    return name
