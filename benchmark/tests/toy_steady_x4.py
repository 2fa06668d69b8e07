"""A toy copy of the node-sharded steady cell for the CPU tests, as
``toy.py`` makes them of the cells it knows: 4096 nodes over four
virtual devices, 1024 a device, so a bucket's mean is 256; a ramp of
64; the capacity 320 (the rule's room over the largest bucket the
reference sees there, 296-308 over the tests' seeds) and the control's
256, the mean."""

import json
import os

import toy


def rounds(base, name="toy_steady_x4.rounds", control_bucket_cap=256,
           **cuts):
    name = toy.make(base, "gossip_steady_1m_x4.rounds", name, **{
        "n_nodes": 4096, "ramp_supersteps": 64, "bucket_cap": 320, **cuts})
    # the control's capacity is no parameter of the deployment
    path = os.path.join(str(base), "configs",
                        name.partition(".")[0] + ".json")
    with open(path) as f:
        config = json.load(f)
    config["control"]["bucket_cap"] = control_bucket_cap
    with open(path, "w") as f:
        json.dump(config, f)
    return name
