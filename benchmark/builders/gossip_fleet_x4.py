"""Builder of the seed sweep laid over a mesh by worlds: one
world-sharded batched general engine (``ShardedBatchedEngine(sc, link,
make_mesh(4, "worlds"), batch=BatchSpec(seeds=...), window="auto")``:
what ``python -m timewarp_tpu gossip --engine sharded-batched --batch 32
--devices 4`` builds) steps the configuration's worlds together, eight
a chip, one ``run_quiet`` a job, from the fresh sharded state to the
quiescence of the last world, ended by one readback of every world's
counters and hop counts gathered from the four chips.

The worlds, the scenario, the link, the plain reference, the
comparison (``compare``, ``control``) and the per-world gates of a job
are ``builders/gossip_fleet.py``'s: this ``Cell`` is that one with
another engine, another layout of the state and further gates, as
``sharded_ring.Cell`` is ``fused_ring.Cell``'s. ``--seed`` draws the
order of the worlds along the batch axis, and so which chip holds
which world, and nothing else (``rebind_identity``: traced operands, no
compile); ``slot.worlds_misplaced`` then also says that no chip
returned another chip's world. What is new is where the state lives,
and the gates hold every job to it.
"""

import jax
import jax.numpy as jnp

import x4_reduce
from builders import gossip_fleet
from builders.gossip_wave import scenario_and_link
from timewarp_tpu.core.scenario import NEVER
from timewarp_tpu.interp.jax_engine.batched import BatchSpec
from timewarp_tpu.interp.jax_engine.sharded import ShardedBatchedEngine
from timewarp_tpu.parallel.mesh import make_mesh


class Cell(gossip_fleet.Cell):
    def __init__(self, config, traffic, *, interpret=False):
        del interpret                    # no kernel on this path
        # the scope ``fleet_x4_liveness_us`` reads is newer than the
        # engine: a cache keyed without the names would hand a program
        # compiled from a checkout that lacks it to one that has it
        # (PERF.md, Findings PR 24)
        jax.config.update(
            "jax_compilation_cache_include_metadata_in_key", True)
        p = self.p = config["params"]
        self.workload = traffic["name"]
        self.n = int(p["n_nodes"])
        self.chips = int(traffic["chips"])
        self.seeds = tuple(int(s) for s in p["world_seeds"])
        if len(self.seeds) != int(p["worlds"]) or int(p["origin"]):
            raise SystemExit("benchmark: world_seeds names one seed a world, "
                             "and the scenario's origin is node 0")
        if list(p["mesh"]["shape"]) != [self.chips]:
            raise SystemExit(f"benchmark: the configuration's mesh "
                             f"{p['mesh']['shape']} is not the cell's "
                             f"{self.chips} chips")
        if len(jax.devices()) < self.chips:
            raise SystemExit(f"benchmark: the cell's mesh needs "
                             f"{self.chips} devices, JAX found "
                             f"{len(jax.devices())}")
        self.budget = int(traffic["max_supersteps_per_job"])
        sc, link = scenario_and_link(p)
        axis = p["mesh"]["axes"][0]
        self.engine = ShardedBatchedEngine(
            sc, link, make_mesh(self.chips, axis), axis=axis,
            batch=BatchSpec(seeds=self.seeds), window=p["window"])
        eng = self.engine

        @jax.jit
        def counters(fin):
            return (fin.delivered, fin.steps, fin.time,
                    jax.vmap(eng._next_event)(fin) >= NEVER,
                    (fin.states["hop"] >= 0).sum(axis=1),
                    jnp.stack([getattr(fin, f) for f in gossip_fleet._PARITY],
                              axis=1))

        def read(fin):
            # ``gossip_fleet.Cell.job`` hands the job's result to the
            # counters and to nothing else: kept for the placement gates
            self._fin = fin
            return counters(fin)

        self._counters = read
        self._op_names = None

    # -- one job ----------------------------------------------------------

    def _placement(self, fin):
        """What is wrong with where the job's result lives: its
        ``steps`` and hop counts have to be one slice a chip, each of
        ``worlds_local`` worlds, on distinct devices at distinct
        offsets (``sharded_ring.Cell._placement``, on the world axis);
        and every leaf has to come back laid out as the same leaf of
        the fresh state went in. (Laid out, not named: PR 46 found the
        mailbox planes coming back as ``P("worlds")`` where
        ``init_state`` had placed them as ``P("worlds", None, None)``,
        one layout under two names, which costs a run streamed on the
        returned state a second compile and a job from the fresh state
        nothing; ``renamed`` lists such leaves, the tier-1 tests and
        this PR's chip run hold it empty, and the parent of PR 46 runs
        this cell's jobs soundly.)"""
        local, why = self.engine.worlds_local, []
        for name, leaf in (("steps", fin.steps), ("hop", fin.states["hop"])):
            shards = leaf.addressable_shards
            shapes = {s.data.shape for s in shards}
            offsets = {s.index[0].start or 0 for s in shards}
            devices = {s.device for s in shards}
            if (len(shards), shapes) != (self.chips,
                                         {(local,) + leaf.shape[1:]}) \
                    or len(offsets) != self.chips \
                    or len(devices) != self.chips:
                why.append(f"{name} lives as {len(shards)} shards of "
                           f"{sorted(shapes)} at {len(offsets)} offsets on "
                           f"{len(devices)} devices")
        moved = self._leaves_where(fin, lambda a, b: not (
            a.sharding.is_equivalent_to(b.sharding, a.ndim)))
        if moved:
            why.append(f"leaves laid out otherwise than they went in: "
                       f"{moved}")
        return why

    def _leaves_where(self, fin, differ):
        return [jax.tree_util.keystr(path) for (path, a), b in zip(
            jax.tree_util.tree_leaves_with_path(self.state0),
            jax.tree.leaves(fin)) if differ(a, b)]

    def renamed(self, fin):
        """The leaves of a result whose sharding is not, as an object,
        the fresh state's: what ``jit``'s cache would compile a
        streamed call anew for."""
        return self._leaves_where(fin, lambda a, b: a.sharding != b.sharding)

    def job(self, i):
        res = super().job(i)
        stats = self.engine.last_run_stats      # still this job's call
        why = self._placement(self._fin)
        del self._fin
        if (stats["dispatches"], stats["readbacks"]) != (1, 1):
            why.append(f"{stats['dispatches']} dispatches, "
                       f"{stats['readbacks']} readbacks in the call")
        res["failed"] = "; ".join(filter(None, [res["failed"]] + why))
        # None from a program that does not count a device
        res["device_rung_lanes"] = stats.get("device_rung_lanes")
        return res

    # -- what decides `correct` -------------------------------------------

    def compare(self, reference, produced=None):
        """``gossip_fleet.Cell.compare``'s seven rows; with them, the
        one chance to read every plane's ``op_name``s (the fleet's
        reads the first chip's)."""
        rows = super().compare(reference, produced)
        if produced is None:
            # run.py deletes a traced run's profile before the readers
            # run: this is the one call it makes while the file is there
            self._op_names = x4_reduce.traced_op_names(
                self.workload, self.seed)
        return rows

    # -- counts for the per-layer readers ---------------------------------

    def facts(self):
        sc = self.engine.scenario
        return {"op_names": self._op_names, "n_nodes": self.n,
                "mailbox_cap": sc.mailbox_cap,
                "payload_width": sc.payload_width,
                "worlds": len(self.seeds),
                "worlds_local": self.engine.worlds_local}
