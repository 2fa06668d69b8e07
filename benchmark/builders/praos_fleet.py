"""Builder of the praos fleet's cells: one batched general engine
(``JaxEngine(sc, link, window="auto", batch=BatchSpec(seeds=...,
link_params={"inner.median_us": [...]}))``: what ``python -m
timewarp_tpu praos --burst --batch 4 --window auto`` with ``--link``
given once a world builds) steps the configuration's worlds together,
world b on engine seed b and on a lognormal link of its own median,
from a fresh state (every node of every world on the job's genesis
length) through ``slots_per_job`` slots to the quiescence of the last,
one ``run_quiet`` a job, ended by one readback of every world's
counters and chain lengths: what a researcher who reads chain growth
and the length of a slot's flood against link latency reads, four
latencies at once.

The worlds are the configuration's pairs (``world_seeds[b]``,
``link_params["inner.median_us"][b]``), as ``bench.py``
``bench_praos_1m_b4``'s are, and so is the work of a job. ``--seed``
draws (a) once a run the order of the pairs along the batch axis,
seed and median moving together (``rebind_identity(BatchSpec(seeds=
order, link_params=those medians))``: traced operands, no compile), and
(b) for every job one genesis length a world, written into every node's
``best``: every payload word and every node's result move with it, no
count does. A world's result must depend neither on its slot nor on its
neighbours' links, which is what the comparison then holds it to, at
the end of every job and, once a run, mid-flood (``mid_supersteps``
iterations from the last job's fresh state by the executable every job
ran: the budget is an operand), where who holds the tip and what is in
flight to whom depend on every latency drawn so far.
README_praos_fleet.md has the page.
"""

import numpy as np

import jax
import jax.numpy as jnp

import fleet_reduce
from timewarp_tpu.core.scenario import NEVER
from timewarp_tpu.interp.jax_engine.batched import BatchSpec
from timewarp_tpu.interp.jax_engine.engine import JaxEngine
from timewarp_tpu.models.praos import praos
from timewarp_tpu.net.delays import LogNormalDelay, Quantize

_NEVER_SILENT = ("overflow", "short_delay", "route_drop", "bad_dst")
_NODE_FACTS = ("best", "slot", "lcg")
_WORLD_FACTS = ("delivered", "supersteps", "time")
#: what a state stopped mid-flood is held to: a node's chain length and
#: generator, and the mailbox as two tables a node
_MID_NODE_FACTS = ("best", "lcg", "in_flight_count", "in_flight_earliest")
_MID_WORLD_FACTS = ("delivered", "time")
_GENESIS_BELOW = 1 << 30
_EMPTY = np.iinfo(np.int32).max      # an empty mailbox slot's mb_rel
_PATH = "inner.median_us"


def scenario_and_link(p, n_slots):
    """The configuration's scenario and the engine's own link (the
    solo cell's: every world's but for the median)."""
    lk = p["link"]
    if lk["model"] != "lognormal" or not p["burst"]:
        raise SystemExit("benchmark: this builder runs burst diffusion "
                         "on a lognormal link")
    n = int(p["n_nodes"])
    sc = praos(n, slot_us=int(p["slot_us"]), n_slots=int(n_slots),
               leader_prob=float(p["leaders_per_slot"]) / n,
               fanout=int(p["fanout"]), burst=True,
               mailbox_cap=int(p["mailbox_cap"]))
    link = Quantize(LogNormalDelay(
        int(lk["median_us"]), float(lk["sigma"]), cap_us=int(lk["cap_us"]),
        floor_us=int(lk["floor_us"])), int(lk["quantum_us"]))
    return sc, link


@jax.jit
def _with_genesis(st, h0):
    """``st`` with every node of world b on a chain of length
    ``h0[b]``."""
    best = jnp.broadcast_to(h0[:, None], st.states["best"].shape)
    return st._replace(states={**st.states, "best": best})


def _counters_of(eng):
    """One jitted function of a fleet's final state: what a job reads
    back beside the chain lengths, a world a row."""
    @jax.jit
    def counters(fin):
        return (fin.delivered, fin.steps, fin.time,
                jax.vmap(eng._next_event)(fin) >= NEVER,
                fin.states["slot"].min(axis=1),
                jnp.stack([getattr(fin, f) for f in _NEVER_SILENT], axis=1))
    return counters


@jax.jit
def _mid_tables(st):
    """A fleet's state stopped mid-flood, reduced on the device: every
    node's chain length and generator, and its mailbox as the count of
    messages in flight to it and the due time of the first (-1 where
    none), ``[B, n]`` each; each world's messages delivered so far and
    the time of its last superstep."""
    held = st.mb_rel < _EMPTY                               # [B, K, n]
    count = held.sum(axis=1, dtype=jnp.int32)
    first = st.time[:, None] + st.mb_rel.min(axis=1).astype(jnp.int64)
    return ({"best": st.states["best"], "lcg": st.states["lcg"],
             "in_flight_count": count,
             "in_flight_earliest": jnp.where(count > 0, first, -1)},
            st.delivered, st.time, st.steps)


class Cell:
    def __init__(self, config, traffic, *, interpret=False):
        del interpret                    # no kernel on this path
        p = self.p = config["params"]
        self.control_of = config["control"]
        self.workload = traffic["name"]
        self.n = int(p["n_nodes"])
        self.n_slots = int(traffic["slots_per_job"])
        self.budget = int(traffic["max_supersteps_per_job"])
        self.mid = int(traffic["mid_supersteps"])
        self.seeds = tuple(int(s) for s in p["world_seeds"])
        if list(p["link_params"]) != [_PATH] \
                or len(p["link_params"][_PATH]) != len(self.seeds) \
                or len(self.seeds) != int(p["worlds"]):
            raise SystemExit("benchmark: world_seeds and link_params name "
                             "one seed and one median a world, and the "
                             "median is all the links differ in")
        #: each world's median, by its seed: the pair moves together
        self.medians = dict(zip(self.seeds, map(int, p["link_params"][_PATH])))
        self.sc, self.link = scenario_and_link(p, self.n_slots)
        self.engine = JaxEngine(self.sc, self.link, window=p["window"],
                                batch=self._batch(self.seeds))
        if not self.engine._adaptive_regime():
            raise SystemExit("benchmark: the cell measures the windowed "
                             "ladder, and this engine routes eagerly")
        self._counters = _counters_of(self.engine)
        self._op_names = None
        self._memory_peak = None
        #: the plain reference's runs, which no seed moves: kept over
        #: the seeds of one process (control.py)
        self._wants = {}
        self._plain_engine = None

    def _batch(self, order, swapped=()):
        """The fleet's identity in slot order ``order``: each world's
        seed and, beside it, its median; with ``swapped`` (two seeds),
        those two worlds' medians exchanged and their seeds left (the
        control)."""
        other = dict(zip(swapped, reversed(swapped)))
        return BatchSpec(seeds=tuple(order), link_params={_PATH: [
            self.medians[other.get(seed, seed)] for seed in order]})

    # -- set-up ---------------------------------------------------------

    def set_up(self, seed):
        self.seed = seed
        self.rng = np.random.default_rng(seed)
        # the seed's first draw: which world sits in which slot
        self.order = tuple(self.seeds[i] for i in self.rng.permutation(
            len(self.seeds)))
        self._rebind(self.engine, self._batch(self.order))
        # the scenario's initial state is the same in every world; made
        # once, and a job starts from it with its genesis lengths
        self.state0 = jax.block_until_ready(self.engine.init_state())
        #: what each job of the window left behind: its genesis lengths,
        #: the per-node facts ``[B, n]`` (the chain lengths read in the
        #: job, the rest on the device until the comparison) and each
        #: world's counts, in slot order
        self.runs = []
        return self.job(0)               # compiles every program of a job

    @staticmethod
    def _rebind(engine, batch):
        if not engine.rebind_identity(batch):
            raise SystemExit("benchmark: the engine would recompile for "
                             "a permutation of its own worlds")

    # -- one job ----------------------------------------------------------

    def _produce(self, engine, counters, h0):
        """One fleet from the fresh state with genesis lengths ``h0``
        (one a slot) to quiescence on ``engine``: what it left behind,
        the call's record, and what the gates read."""
        fin = engine.run_quiet(self.budget, _with_genesis(
            self.state0, jnp.asarray(h0, jnp.int32)))
        stats = engine.last_run_stats
        delivered, steps, time, quiet, slots_seen, silent, best = \
            jax.device_get(counters(fin) + (fin.states["best"],))
        nodes = {"best": best, "slot": fin.states["slot"],
                 "lcg": fin.states["lcg"]}
        # each world's own senders, where the program counts them (the
        # parent of PR 55 does not: the row is then left out)
        own = stats.get("world_sender_lanes") or [None] * len(self.order)
        worlds = [{"delivered": int(delivered[b]), "supersteps": int(steps[b]),
                   "time": int(time[b]), "senders": own[b]}
                  for b in range(len(self.order))]
        return (h0, nodes, worlds), stats, (quiet, slots_seen, silent)

    def job(self, i):
        h0 = self.rng.integers(0, _GENESIS_BELOW, len(self.order))
        produced, stats, (quiet, slots_seen, silent) = self._produce(
            self.engine, self._counters, h0)
        _, nodes, worlds = produced
        why = []
        for b, seed in enumerate(self.order):
            if not quiet[b]:
                why.append(f"world {seed} not quiescent inside the step "
                           "budget")
            why += [f"world {seed} {name}={int(v)}"
                    for name, v in zip(_NEVER_SILENT, silent[b]) if v]
            if slots_seen[b] != self.n_slots:
                why.append(f"world {seed}: a node saw {int(slots_seen[b])} "
                           f"slots of {self.n_slots}")
            # how far the chain grew is the seed's (a slot with no
            # leader grows nothing: the comparison holds it to the
            # reference's count); here, that all but a few nodes ended
            # on the longest chain of their world. The push-only miss
            # floor: no push of the last flood reaches a node with
            # probability about e^-fanout
            tip = int(nodes["best"][b].max())
            short = int((nodes["best"][b] < tip).sum())
            if short > max(self.n // 500, 8):
                why.append(f"world {seed}: {short} nodes short of the "
                           "final chain length")
            if tip < h0[b] or tip - h0[b] > self.n_slots:
                why.append(f"world {seed}: the chain grew by "
                           f"{tip - int(h0[b])} in {self.n_slots} slots")
        if stats["compiles"] and i:
            why.append(f"{stats['compiles']} driver compiles after the "
                       "run's first job")
        if i:                            # a job of the window
            self.runs.append(produced)
        return {"msgs": sum(w["delivered"] for w in worlds),
                "supersteps": stats["fleet_iterations"],
                "world_supersteps": stats["world_supersteps"],
                "rung_lanes": stats.get("rung_lanes"),
                "sender_lanes": stats.get("sender_lanes"),
                # None from a program that does not count it
                "world_sender_lanes": stats.get("world_sender_lanes"),
                "failed": "; ".join(why)}

    # -- what decides `correct` -------------------------------------------

    def _reference(self, reference, key="sound", **how):
        """``{seed: the plain reference's run of that world}`` from
        genesis 0 to quiescence, and through ``mid_supersteps``
        supersteps: once a run for each ``how`` (the controls' own
        precision or medians), kept."""
        if key not in self._wants:
            fleet = reference.Fleet(self.p, self.n_slots, **how)
            self._wants[key] = (fleet.runs(), fleet.runs(self.mid))
        return self._wants[key]

    def _rows(self, tag, produced, ends):
        """The final state's rows: ``produced`` is ``(genesis lengths,
        node facts [B, n], world facts)`` a job, each slot held to the
        run in ``ends`` of the seed the permutation put there; the
        chain lengths are compared less the genesis length."""
        # a world's own senders where the program counted them
        counted = all(w["senders"] is not None
                      for _, _, worlds in produced for w in worlds)
        world_facts = _WORLD_FACTS + ("senders",) * counted
        differ = dict.fromkeys(_NODE_FACTS + world_facts, 0)
        moved_worlds = set()
        misplaced = 0
        for h0, nodes, worlds in produced:
            got = {f: np.asarray(nodes[f]) for f in _NODE_FACTS}
            got["best"] = got["best"] - np.asarray(h0, np.int32)[:, None]
            for b, seed in enumerate(self.order):
                want = ends[seed]
                before = sum(differ.values())
                for f in _NODE_FACTS:
                    differ[f] += int((got[f][b] != want[f]).sum())
                for f in world_facts:
                    differ[f] += worlds[b][f] != want[f]
                if sum(differ.values()) > before:
                    moved_worlds.add(seed)
                    # the slot holds another world's result, or ran on
                    # another world's median: its counts are that
                    # world's to the last
                    misplaced += any(
                        all(worlds[b][f] == w[f] for f in _WORLD_FACTS)
                        and all(np.array_equal(got[f][b], w[f])
                                for f in _NODE_FACTS)
                        for s, w in ends.items() if s != seed)
        return [(f"{tag}.{f}.nodes_that_differ", differ[f], 0)
                for f in _NODE_FACTS] + [
            (f"{tag}.{f}.worlds_that_differ", differ[f], 0)
            for f in world_facts] + [
            (f"{tag}.worlds_that_differ", len(moved_worlds), 0),
            (f"{tag}.slot.worlds_misplaced", misplaced, 0)]

    def _mid_state(self, engine):
        """The fleet stopped after ``mid_supersteps`` iterations from
        the last job's fresh state, by the executable every job ran
        (the budget is an operand): its tables on the host, and the
        driver compiles the call made."""
        h0 = self.runs[-1][0]
        st = engine.run_quiet(self.mid, _with_genesis(
            self.state0, jnp.asarray(h0, jnp.int32)))
        compiles = engine.last_run_stats["compiles"]
        nodes, delivered, time, steps = jax.device_get(_mid_tables(st))
        nodes["best"] = nodes["best"] - np.asarray(h0, np.int32)[:, None]
        return (nodes, [{"delivered": int(delivered[b]), "time": int(time[b]),
                         "supersteps": int(steps[b])}
                        for b in range(len(self.order))]), compiles

    def _mid_rows(self, tag, got, mids):
        nodes, worlds = got
        differ = dict.fromkeys(_MID_NODE_FACTS + _MID_WORLD_FACTS, 0)
        moved_worlds = set()
        for b, seed in enumerate(self.order):
            want = mids[seed]
            before = sum(differ.values())
            for f in _MID_NODE_FACTS:
                differ[f] += int((nodes[f][b] != want[f]).sum())
            for f in _MID_WORLD_FACTS:
                differ[f] += worlds[b][f] != want[f]
            if sum(differ.values()) > before:
                moved_worlds.add(seed)
        return [(f"{tag}.{f}.nodes_that_differ", differ[f], 0)
                for f in _MID_NODE_FACTS] + [
            (f"{tag}.{f}.worlds_that_differ", differ[f], 0)
            for f in _MID_WORLD_FACTS] + [
            (f"{tag}.worlds_that_differ", len(moved_worlds), 0)]

    def compare(self, reference, produced=None, mid=None, wants=None):
        """Rows ``(name, value, limit)``, all exact (limit 0), at full
        width, each slot against the plain reference's run of the pair
        (seed, median) the permutation put there. **The final state**,
        over every job of the window and each of its worlds: nodes
        whose chain length (less the job's genesis length), slots seen
        or generator differ; worlds whose delivered messages,
        supersteps or last superstep's time differ, or whose own
        senders as the call's record counts them
        (``world_sender_lanes``; left out on a program that does not
        count them) differ from the nodes the reference had pushing;
        worlds in which anything does; slots that hold another
        world's result.
        **The mid-flood state**, once a run: after ``mid_supersteps``
        iterations from the last job's fresh state, nodes whose chain
        length, generator, count of messages in flight or earliest due
        time differ, worlds whose delivered messages or time differ,
        worlds in which anything does; whether the call compiled; and
        worlds the reference had already brought to rest by then (the
        state would not be mid-flood). Last, each world's largest
        count in flight against the mailbox's slots. ``produced`` and
        ``mid`` stand in the program's place where they are given,
        ``wants`` in the reference's (the controls)."""
        sound = produced is None
        if sound:
            # run.py deletes a traced run's profile before the readers
            # run: this is the one call it makes while the file is there
            self._op_names = fleet_reduce.traced_op_names(
                self.workload, self.seed)
            # and the window's peak, before the mid-flood call's
            self._memory_peak = max(
                ((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in jax.local_devices()), default=0) or None
            produced = self.runs
            print(f"worlds in slot order {list(self.order)} on medians "
                  f"{[self.medians[s] for s in self.order]}; supersteps of "
                  "each and messages of all, by job: " + "; ".join(sorted(
                      {f"{[w['supersteps'] for w in worlds]} "
                       f"{sum(w['delivered'] for w in worlds)}"
                       for _, _, worlds in produced})))
        ends, mids = wants or self._reference(reference)
        if sound:
            for seed in self.seeds:
                w = ends[seed]
                short = int((w["best"] < w["best"].max()).sum())
                print(f"reference, world {seed} on median "
                      f"{self.medians[seed]}: blocks minted a slot "
                      f"{w['minted']}; {short} nodes short of the final "
                      f"chain length; {w['supersteps']} supersteps, "
                      f"{w['delivered']} messages, largest in flight "
                      f"{w['largest_in_flight']}")
        compiles = 0
        if mid is None:
            mid, compiles = self._mid_state(self.engine)
        still = sum(w["supersteps"] < self.mid or
                    ends[s]["supersteps"] <= self.mid
                    for s, w in mids.items())
        name = f"jobs_{len(produced)}x{len(self.order)}"
        return self._rows(name, produced, ends) \
            + self._mid_rows(f"mid_{self.mid}", mid, mids) + [
            (f"mid_{self.mid}.driver_compiles", compiles, 0),
            (f"mid_{self.mid}.worlds_at_rest_by_then", still, 0)] + [
            (f"reference.world_{seed}.largest_in_flight_to_one_node",
             ends[seed]["largest_in_flight"], self.sc.mailbox_cap)
            for seed in self.seeds]

    def control(self, reference):
        """Three controls in the program's place, each of which has to
        fail: the reference with the link's lognormal in the precision
        below its float32 (``link_precision``), in the slots the
        permutation names; the program with the medians of two worlds
        exchanged and their seeds left (``swapped_worlds``: exactly
        those two move); the program built with ``link_params=None``,
        every world on the engine's own median (every world moves but
        the one whose median that is). The rows of all three; of one
        alone if it passes, so that a control that has stopped failing
        does not hide behind the others."""
        wants = self._reference(reference)
        h0 = self.runs[-1][0]
        ends, mids = self._reference(
            reference, "low", precision=self.control_of["link_precision"])
        zeros = np.zeros(len(self.order), np.int64)

        def stack(runs, facts):
            return {f: np.stack([runs[s][f] for s in self.order])
                    for f in facts}
        parts = {"low_precision": self.compare(
            reference,
            [(zeros, stack(ends, _NODE_FACTS),
              [ends[s] for s in self.order])],
            (stack(mids, _MID_NODE_FACTS), [mids[s] for s in self.order]),
            wants)}

        def on(engine, counters):
            return self.compare(
                reference, [self._produce(engine, counters, h0)[0]],
                self._mid_state(engine)[0], wants)
        self._rebind(self.engine, self._batch(
            self.order, tuple(self.control_of["swapped_worlds"])))
        parts["swapped_medians"] = on(self.engine, self._counters)
        self._rebind(self.engine, self._batch(self.order))
        parts["no_link_params"] = on(*self._plain())
        for name, rows in parts.items():
            if all(v <= limit for _, v, limit in rows):
                print(f"the control {name} passed the comparison")
                return rows
        return [(f"{name}.{row}", v, limit)
                for name, rows in parts.items() for row, v, limit in rows]

    def _plain(self):
        """The fleet without ``link_params`` (every world on the
        engine's own median) in the run's slot order, and its
        counters: built and compiled once a process, whatever the
        seeds (control.py)."""
        if self._plain_engine is None:
            eng = JaxEngine(self.sc, self.link, window=self.p["window"],
                            batch=BatchSpec(seeds=self.order))
            self._plain_engine = eng, _counters_of(eng)
        self._rebind(self._plain_engine[0], BatchSpec(seeds=self.order))
        return self._plain_engine

    # -- counts for the per-layer readers ---------------------------------

    def facts(self):
        return {"op_names": self._op_names, "n_nodes": self.n,
                "worlds": len(self.seeds),
                "mailbox_cap": self.sc.mailbox_cap,
                "payload_width": self.sc.payload_width,
                "memory_peak_bytes": self._memory_peak}
