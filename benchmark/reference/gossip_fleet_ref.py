"""Plain reference of the seed-sweep fleet: world by world the
event-by-event broadcast wave of ``gossip_ref.Graph``, each with its
own engine seed and all from the configuration's origin. Nothing of the
program is imported and nothing the program made is taken.

The worlds of a fleet share the scenario (the push graph, the think
time, the link's parameters) and differ in the seed that keys every
link latency, so they reach the same nodes by other paths: other hop
counts, other superstep counts, another last time; the deliveries are
the push graph's and the same in all. A world owes nothing to its
neighbours along the batch axis, so the reference of the fleet is the
solo reference of each world, in no order: ``Fleet.waves`` returns
them by seed, and the comparison looks each slot's seed up.
"""

from concurrent.futures import ThreadPoolExecutor

from reference import gossip_ref

_THREADS = 4        # numpy and the device's latencies let go of the lock


class Fleet:
    """One configuration's worlds, built once: a ``gossip_ref.Graph``
    per engine seed in ``params["world_seeds"]``."""

    def __init__(self, params: dict, precision="float32"):
        self.origin = int(params["origin"])
        self.graphs = {
            seed: gossip_ref.Graph({**params, "engine_seed": seed}, precision)
            for seed in map(int, params["world_seeds"])}

    def waves(self) -> dict:
        """``{engine seed: Graph.wave(origin)}``: every world's wave
        run to quiescence (hop counts, deliveries, supersteps, the last
        superstep's time)."""
        with ThreadPoolExecutor(_THREADS) as pool:
            done = pool.map(lambda g: g.wave(self.origin),
                            self.graphs.values())
            return dict(zip(self.graphs, done))
