"""Builder of the seed-sweep fleet's cells: one batched general engine
(``JaxEngine(batch=BatchSpec(seeds=...))``, XLA insertion,
``window="auto"``: what ``python -m timewarp_tpu ... --batch 8`` builds)
steps the configuration's worlds together, one ``run_quiet`` a job,
from a fresh state (every world's rumor at the configuration's origin)
to the quiescence of the last of them, ended by one readback of every
world's counters and hop counts.

The worlds are the configuration's (``world_seeds``, ``origin``), as
``bench.py`` ``gossip_100k_b8``'s are, and so is the work of a job: the
fleet's loop runs as long as its slowest world, and how long that is
depends on the worlds' seeds and origin. ``--seed`` draws the order of
the worlds along the batch axis and nothing else, and puts it into the
engine by ``rebind_identity`` (traced operands: no compile). A world's
result must not depend on its slot or its neighbours, which is what the
comparison then holds it to. (PERF.md, Findings PR 27: a fleet drawn
from ``--seed`` did 93 to 97 iterations a job and was refused as noise.)
"""

import numpy as np

import jax
import jax.numpy as jnp

import fleet_reduce
from builders.gossip_wave import scenario_and_link
from timewarp_tpu.core.scenario import NEVER
from timewarp_tpu.interp.jax_engine.batched import BatchSpec
from timewarp_tpu.interp.jax_engine.engine import JaxEngine

_PARITY = ("overflow", "short_delay", "route_drop", "bad_dst")


class Cell:
    def __init__(self, config, traffic, *, interpret=False):
        del interpret                    # no kernel on this path
        p = self.p = config["params"]
        self.workload = traffic["name"]
        self.n = int(p["n_nodes"])
        self.seeds = tuple(int(s) for s in p["world_seeds"])
        if len(self.seeds) != int(p["worlds"]) or int(p["origin"]):
            raise SystemExit("benchmark: world_seeds names one seed a world, "
                             "and the scenario's origin is node 0")
        self.budget = int(traffic["max_supersteps_per_job"])
        sc, link = scenario_and_link(p)
        self.engine = JaxEngine(sc, link, window=p["window"], insert="xla",
                                batch=BatchSpec(seeds=self.seeds))
        eng = self.engine

        @jax.jit
        def counters(fin):
            return (fin.delivered, fin.steps, fin.time,
                    jax.vmap(eng._next_event)(fin) >= NEVER,
                    (fin.states["hop"] >= 0).sum(axis=1),
                    jnp.stack([getattr(fin, f) for f in _PARITY], axis=1))

        self._counters = counters
        self._op_names = None

    # -- set-up ---------------------------------------------------------

    def set_up(self, seed):
        self.seed = seed
        # the seed's draw: which world sits in which slot
        rng = np.random.default_rng(seed)
        self.order = tuple(self.seeds[i] for i in rng.permutation(
            len(self.seeds)))
        if not self.engine.rebind_identity(BatchSpec(seeds=self.order)):
            raise SystemExit("benchmark: the engine would recompile for "
                             "a permutation of its own worlds")
        # the scenario's initial state is the same in every world: the
        # rumor at node 0. Made once; a job starts from it untouched
        self.state0 = jax.block_until_ready(self.engine.init_state())
        # what each fleet of the window left behind: every world's hop
        # counts [B, n] and its counts
        self.fleets = []
        return self.job(0)               # compiles every program of a job

    # -- one job ----------------------------------------------------------

    def job(self, i):
        fin = self.engine.run_quiet(self.budget, self.state0)
        stats = self.engine.last_run_stats
        delivered, steps, time, quiet, infected, parity, hop = \
            jax.device_get(self._counters(fin) + (fin.states["hop"],))
        why = []
        for b, seed in enumerate(self.order):
            if not quiet[b]:
                why.append(f"world {seed} not quiescent inside the "
                           "step budget")
            for name, v in zip(_PARITY, parity[b]):
                if v:
                    why.append(f"world {seed} {name}={int(v)}")
            # the push-only miss floor, as the wave's gate has it
            missed = self.n - int(infected[b])
            if missed > max(self.n // 500, 8):
                why.append(f"world {seed}: {missed} nodes never infected")
        if stats["compiles"] and i:
            why.append(f"{stats['compiles']} driver compiles inside the "
                       "window")
        if i:                            # a job of the window
            self.fleets.append((hop, [
                {"delivered": int(d), "supersteps": int(s), "time": int(t)}
                for d, s, t in zip(delivered, steps, time)]))
        # the loop's iterations: the largest world's count (the state
        # is fresh, so its `steps` is this job's), by the engine's own
        # account where it keeps one; its "supersteps" is their sum
        return {"msgs": int(delivered.sum()),
                "supersteps": stats.get("fleet_iterations", int(steps.max())),
                "world_supersteps": stats.get("world_supersteps"),
                "failed": "; ".join(why)}

    # -- what decides `correct` -------------------------------------------

    def compare(self, reference, produced=None):
        """Rows ``(name, value, limit)``, all exact (limit 0), over
        every fleet the timed path ran and every world of it, as the
        final state has it at full width, against the plain reference's
        event-by-event run of the world whose seed the permutation put
        in that slot. Per node the hop count it ended with (and so who
        was reached at all); per world the messages delivered, the
        supersteps, the time of the last, whether any hop count
        differs, and whether the slot holds another world's result in
        place of its own. ``produced`` stands in the program's place
        where it is given (the control)."""
        if produced is None:
            # run.py deletes a traced run's profile before the readers
            # run: this is the one call it makes while the file is there
            self._op_names = fleet_reduce.traced_op_names(
                self.workload, self.seed)
            produced = self.fleets
            # the equal-work law: the same in every job of every seed
            print(f"worlds in slot order {list(self.order)}; supersteps of "
                  "each and messages of all, by job: " + "; ".join(sorted(
                      {f"{[w['supersteps'] for w in got]} "
                       f"{sum(w['delivered'] for w in got)}"
                       for _, got in produced})))
        wants = reference.Fleet(self.p).waves()
        hop = infected = worlds = delivered = steps = time = misplaced = 0
        for got_hop, got in produced:
            for b, seed in enumerate(self.order):
                want = wants[seed]
                differ = got_hop[b] != want["hop"]
                hop += int(differ.sum())
                worlds += (moved := bool(differ.any()))
                infected += int(((got_hop[b] >= 0)
                                 != (want["hop"] >= 0)).sum())
                delivered += got[b]["delivered"] != want["delivered"]
                steps += got[b]["supersteps"] != want["supersteps"]
                time += got[b]["time"] != want["time"]
                misplaced += moved and any(
                    np.array_equal(got_hop[b], w["hop"])
                    for s, w in wants.items() if s != seed)
        name = f"fleets_{len(produced)}x{len(self.order)}"
        return [(f"{name}.hop.nodes_that_differ", hop, 0),
                (f"{name}.hop.worlds_that_differ", worlds, 0),
                (f"{name}.infected.nodes_that_differ", infected, 0),
                (f"{name}.delivered.worlds_that_differ", delivered, 0),
                (f"{name}.supersteps.worlds_that_differ", steps, 0),
                (f"{name}.last_superstep_time.worlds_that_differ", time, 0),
                (f"{name}.slot.worlds_misplaced", misplaced, 0)]

    def control(self, reference):
        """The comparison with the control in the program's place:
        every world's reference wave with the lognormal of the link's
        latency computed in bfloat16, the precision below the float32
        the configuration's link states, in the slots the permutation
        names."""
        low = reference.Fleet(self.p, "bfloat16").waves()
        worlds = [low[seed] for seed in self.order]
        return self.compare(reference, [(
            np.stack([w["hop"] for w in worlds]), worlds)])

    # -- counts for the per-layer readers ---------------------------------

    def facts(self):
        return {"op_names": self._op_names}
