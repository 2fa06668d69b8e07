"""Builder of the chaos fleet's cells: one batched general engine with
``faults=`` on (``JaxEngine(batch=BatchSpec(seeds=...), faults=
FaultFleet(...), window="auto")``: what ``python -m timewarp_tpu gossip
--steady --batch 8 --window auto`` with ``--faults`` given once a world
builds) steps the configuration's worlds together, each in steady push
mongering under its own schedule of crashes, a partition and a degraded
link, one ``run_quiet`` a job, from a fresh state (every world's rumor
at node 0) through every fault to the quiescence of all of them, ended
by one readback of every world's counters and per-node state.

The worlds and their schedules are the configuration's
(``world_seeds``, ``faults``: one string of the ``--faults`` grammar a
world, built by ``parse_faults``), as ``bench.py``
``gossip_100k_chaos``'s are, and so is the work of a job. ``--seed``
draws the order of the worlds along the batch axis and nothing else,
as the seed-sweep fleet's does (``gossip_fleet.py``), and a world's
schedule moves with its seed: ``rebind_identity(BatchSpec(seeds=order),
faults=FaultFleet(those schedules))``, traced operands, no compile. A
world's result must depend neither on its slot nor on its neighbours'
faults, which is what the comparison then holds it to.
README_chaos.md has the page.
"""

import numpy as np

import jax
import jax.numpy as jnp

import chaos_costs
import fleet_reduce
from timewarp_tpu.core.scenario import NEVER
from timewarp_tpu.faults import FaultFleet, parse_faults
from timewarp_tpu.interp.jax_engine.batched import BatchSpec
from timewarp_tpu.interp.jax_engine import engine as engine_module
from timewarp_tpu.interp.jax_engine.engine import JaxEngine
from timewarp_tpu.models.gossip import gossip
from timewarp_tpu.net.delays import Quantize, UniformDelay

_NEVER_SILENT = ("overflow", "short_delay", "route_drop", "bad_dst",
                 "bad_delay")
#: the losses a schedule causes, by cause, as the record counts them a
#: world (``last_run_stats`` ``world_fault_<cause>``) and as the plain
#: reference names them
CAUSES = ("cut", "down", "purged")
_NODE_FACTS = ("hop", "lcg", "next")
_WORLD_FACTS = ("delivered",) + CAUSES + ("steps", "time")


def schedules(n: int, worlds: int = 8) -> list:
    """The source's schedule of each world at ``n`` nodes, letter for
    letter ``bench.py`` ``bench_gossip_100k_chaos``'s, in the
    ``--faults`` grammar: a crash with state loss, a second without, a
    partition into halves, and a window in which every link is 2.0 to
    3.75 times slower. What the configuration's ``faults`` holds at
    its own size, and what the tests write at theirs."""
    half = n // 2
    return [
        f"crash:{(7 * b + 3) % n}:20ms:{60 + 5 * b}ms:reset; "
        f"crash:{(11 * b + half + 5) % n}:30ms:{70 + 5 * b}ms; "
        f"partition:0-{half - 1}|{half}-{n - 1}:25ms:{70 + 2 * b}ms; "
        f"degrade:all:all:80ms:120ms:{2.0 + 0.25 * b}"
        for b in range(worlds)]


def scenario_and_link(p):
    lk = p["link"]
    if lk["model"] != "uniform" or not p["steady"]:
        raise SystemExit("benchmark: this builder runs steady mongering "
                         "on a uniform link")
    sc = gossip(int(p["n_nodes"]), fanout=int(p["fanout"]),
                think_us=int(p["think_us"]),
                gossip_interval=int(p["gossip_interval_us"]),
                bootstrap_us=int(p["bootstrap_us"]), end_us=int(p["end_us"]),
                steady=True, mailbox_cap=int(p["mailbox_cap"]))
    return sc, Quantize(UniformDelay(int(lk["lo_us"]), int(lk["hi_us"])),
                        int(lk["quantum_us"]))


class Cell:
    def __init__(self, config, traffic, *, interpret=False):
        del interpret                    # no kernel on this path
        if not hasattr(engine_module, "FaultCounts"):
            # before anything is built or compiled: the gates and the
            # comparison read a schedule's losses by cause
            raise SystemExit(
                "benchmark: this program's engine does not count a fault "
                "schedule's losses by cause (the parent of PR 53): the "
                "cell cannot be held to its guarantees there")
        # the scopes this cell's readers look for are newer than the
        # engine's older cache entries: key the cache on the names
        jax.config.update(
            "jax_compilation_cache_include_metadata_in_key", True)
        p = self.p = config["params"]
        self.control_of = config["control"]
        self.workload = traffic["name"]
        self.n = int(p["n_nodes"])
        self.seeds = tuple(int(s) for s in p["world_seeds"])
        if len(self.seeds) != int(p["worlds"]) or int(p["origin"]) \
                or len(p["faults"]) != len(self.seeds):
            raise SystemExit("benchmark: world_seeds and faults name one "
                             "seed and one schedule a world, and the "
                             "scenario's origin is node 0")
        #: each world's schedule, by its seed
        self.schedules = {seed: parse_faults(text)
                          for seed, text in zip(self.seeds, p["faults"])}
        self.budget = int(traffic["max_supersteps_per_job"])
        self.sc, self.link = scenario_and_link(p)
        self.engine = JaxEngine(
            self.sc, self.link, window=p["window"], insert="xla",
            batch=BatchSpec(seeds=self.seeds), faults=self._fleet(self.seeds))
        self._counters = self._counters_of(self.engine)
        self._op_names = None

    def _fleet(self, order, swapped=()):
        """The schedules of the worlds in slot order ``order``; with
        ``swapped`` (two seeds), those two worlds' schedules exchanged
        and their seeds left (the control)."""
        other = dict(zip(swapped, reversed(swapped)))
        return FaultFleet(tuple(
            self.schedules[other.get(seed, seed)] for seed in order))

    @staticmethod
    def _counters_of(eng):
        @jax.jit
        def counters(fin):
            nxt = fin.states["next"]
            return (fin.delivered, fin.steps, fin.time,
                    jax.vmap(eng._next_event)(fin) >= NEVER,
                    fin.fault_dropped,
                    jnp.stack([getattr(fin, f) for f in _NEVER_SILENT],
                              axis=1),
                    fin.states["hop"], fin.states["lcg"],
                    jnp.where(nxt >= NEVER, -1, nxt))
        return counters

    # -- set-up ---------------------------------------------------------

    def set_up(self, seed):
        self.seed = seed
        # the seed's draw: which world sits in which slot
        rng = np.random.default_rng(seed)
        self.order = tuple(self.seeds[i] for i in rng.permutation(
            len(self.seeds)))
        self._rebind(self.engine, self._fleet(self.order))
        # the scenario's initial state is the same in every world: the
        # rumor at node 0. Made once; a job starts from it untouched
        self.state0 = jax.block_until_ready(self.engine.init_state())
        #: what each fleet of the window left behind, by world in slot
        #: order: ``_produce``'s rows
        self.fleets = []
        return self.job(0)               # compiles every program of a job

    def _rebind(self, engine, fleet):
        if not engine.rebind_identity(BatchSpec(seeds=self.order),
                                      faults=fleet):
            raise SystemExit("benchmark: the engine would recompile for "
                             "a permutation of its own worlds")

    # -- one job ----------------------------------------------------------

    def _produce(self, engine, counters):
        """One fleet from the fresh state to quiescence on ``engine``:
        what it left behind (per-node facts ``[B, n]`` and each
        world's counts, in slot order), the call's record, and what
        the gates read."""
        fin = engine.run_quiet(self.budget, self.state0)
        stats = engine.last_run_stats
        (delivered, steps, time, quiet, dropped, silent, *nodes) = \
            jax.device_get(counters(fin))
        lost = {c: stats.get(f"world_fault_{c}") or [0] * len(self.order)
                for c in CAUSES}
        worlds = [
            {"delivered": int(delivered[b]), "steps": int(steps[b]),
             "time": int(time[b]), **{c: int(lost[c][b]) for c in CAUSES}}
            for b in range(len(self.order))]
        return (dict(zip(_NODE_FACTS, nodes)), worlds), stats, \
            (quiet, dropped, silent)

    def job(self, i):
        produced, stats, (quiet, dropped, silent) = self._produce(
            self.engine, self._counters)
        nodes, worlds = produced
        why = []
        for b, seed in enumerate(self.order):
            if not quiet[b]:
                why.append(f"world {seed} not quiescent inside the "
                           "step budget")
            for name, v in zip(_NEVER_SILENT, silent[b]):
                if v:
                    why.append(f"world {seed} {name}={int(v)}")
            # a fault's loss is counted, by cause, and the causes sum
            # to the state's own counter
            by_cause = [worlds[b][c] for c in CAUSES]
            if sum(by_cause) != int(dropped[b]):
                why.append(f"world {seed}: fault_dropped {int(dropped[b])} "
                           f"is not its causes' sum {by_cause}")
            # the schedule bit: its partition cut, and a down node's
            # messages were dropped (a reboot purges nothing here:
            # README_chaos.md)
            for c in self.p["causes_that_bite"]:
                if not worlds[b][c]:
                    why.append(f"world {seed}: no message {c}")
            missed = int((nodes["hop"][b] < 0).sum())
            if missed > max(self.n // 500, 8):
                why.append(f"world {seed}: {missed} nodes without the "
                           "rumor at the end")
        if stats["compiles"] and i:
            why.append(f"{stats['compiles']} driver compiles inside the "
                       "window")
        if i:                            # a job of the window
            self.fleets.append(produced)
        return {"msgs": sum(w["delivered"] for w in worlds),
                "supersteps": stats["fleet_iterations"],
                "world_supersteps": stats["world_supersteps"],
                "fault_table_lanes": stats.get("fault_table_lanes"),
                "fault_dropped": int(dropped.sum()),
                "failed": "; ".join(why)}

    # -- what decides `correct` -------------------------------------------

    def compare(self, reference, produced=None, wants=None):
        """Rows ``(name, value, limit)``, all exact (limit 0), over
        every fleet the timed path ran and every world of it, against
        the plain reference's run of the world whose seed the
        permutation put in that slot, under that world's schedule. Per
        node the hop count, the generator and the next push time the
        final state holds; per world the messages delivered, the
        losses by cause, the supersteps and the time of the last; and
        whether the slot holds another world's result in place of its
        own. One more row holds the reference's largest count of
        messages pending to one node against the mailbox's slots.
        ``produced`` stands in the program's place where it is given,
        ``wants`` in the reference's (the controls)."""
        if produced is None:
            # run.py deletes a traced run's profile before the readers
            # run: this is the one call it makes while the file is there
            self._op_names = fleet_reduce.traced_op_names(
                self.workload, self.seed)
            produced = self.fleets
            # the equal-work law: the same in every job of every seed
            print(f"worlds in slot order {list(self.order)}; supersteps of "
                  "each and messages of all, by job: " + "; ".join(sorted(
                      {f"{[w['steps'] for w in got]} "
                       f"{sum(w['delivered'] for w in got)}"
                       for _, got in produced})))
        if wants is None:
            wants = reference.Fleet(self.p).runs()
        differ = dict.fromkeys(_NODE_FACTS + _WORLD_FACTS, 0)
        misplaced = 0
        for nodes, got in produced:
            for b, seed in enumerate(self.order):
                want = wants[seed]
                moved = False
                for f in _NODE_FACTS:
                    d = int((nodes[f][b] != want[f]).sum())
                    differ[f] += d
                    moved |= bool(d)
                for f in _WORLD_FACTS:
                    differ[f] += got[b][f] != want[f]
                misplaced += moved and any(
                    all(np.array_equal(nodes[f][b], w[f])
                        for f in _NODE_FACTS)
                    for s, w in wants.items() if s != seed)
        name = f"fleets_{len(produced)}x{len(self.order)}"
        held = max(w["largest_in_flight"] for w in wants.values())
        return [(f"{name}.{f}.nodes_that_differ", differ[f], 0)
                for f in _NODE_FACTS] + [
            (f"{name}.{f}.worlds_that_differ", differ[f], 0)
            for f in _WORLD_FACTS] + [
            (f"{name}.slot.worlds_misplaced", misplaced, 0),
            ("reference.largest_in_flight_to_one_node", held,
             self.sc.mailbox_cap)]

    def control(self, reference, wants=None):
        """Three controls in the program's place, each of which has to
        fail: the reference with the link's word cut to its low bits
        (``link_word_bits``, the precision below its 32), in the slots
        the permutation names; the program with two worlds' schedules
        exchanged and their seeds left (the isolation of schedules);
        the program built with ``faults=None``. The rows of all three;
        of one alone if it passes, so that a control that has stopped
        failing does not hide behind the others."""
        if wants is None:
            wants = reference.Fleet(self.p).runs()
        low = reference.Fleet(
            self.p, int(self.control_of["link_word_bits"])).runs()
        worlds = [low[seed] for seed in self.order]
        parts = {"low_word": self.compare(reference, [(
            {f: np.stack([w[f] for w in worlds]) for f in _NODE_FACTS},
            worlds)], wants)}
        self._rebind(self.engine, self._fleet(
            self.order, tuple(self.control_of["swapped_worlds"])))
        parts["swapped_schedules"] = self.compare(reference, [
            self._produce(self.engine, self._counters)[0]], wants)
        self._rebind(self.engine, self._fleet(self.order))
        plain = JaxEngine(self.sc, self.link, window=self.p["window"],
                          insert="xla", batch=BatchSpec(seeds=self.order))
        parts["no_faults"] = self.compare(reference, [
            self._produce(plain, self._counters_of(plain))[0]], wants)
        for name, rows in parts.items():
            if all(v <= limit for _, v, limit in rows):
                print(f"the control {name} passed the comparison")
                return rows
        return [(f"{name}.{row}", v, limit)
                for name, rows in parts.items() for row, v, limit in rows]

    # -- counts for the per-layer readers ---------------------------------

    def facts(self):
        return {"op_names": self._op_names,
                "superstep_bytes": chaos_costs.chaos_superstep_bytes(
                    self.n, len(self.seeds), self.sc.mailbox_cap,
                    self.sc.payload_width)}
