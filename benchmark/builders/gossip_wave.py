"""Builder of the broadcast-wave cells: the general engine
(``JaxEngine``, XLA insertion, ``window="auto"``) runs one push-rumor
wave per job from a fresh state to quiescence, ended by the readback
of its counters and of every node's hop count: what a user who sweeps
origins reads, and what the comparison holds to the plain reference.

``--seed`` draws each job's origin node. The engine's own seed is a
compile-time constant of a solo engine (PERF.md, Open questions), so it
is fixed in the configuration and the origin moves instead: rows 0 and
``k`` of ``hop``, ``left``, ``next`` and ``wake`` of ``init_state()``
swap, in one small jitted function of ``k`` that is warmed in set-up.
Making the state is part of the job, as it is for a user who sweeps
origins.
"""

from concurrent.futures import ThreadPoolExecutor

import numpy as np

import jax
import jax.numpy as jnp

from timewarp_tpu.core.scenario import NEVER
from timewarp_tpu.interp.jax_engine.engine import JaxEngine
from timewarp_tpu.models.gossip import gossip, gossip_links
from timewarp_tpu.net.delays import Quantize

_ORIGIN_FIELDS = ("hop", "left", "next")
_REFERENCE_THREADS = 4      # the plain reference's, after the window


def scenario_and_link(p):
    sc = gossip(int(p["n_nodes"]), fanout=int(p["fanout"]),
                think_us=int(p["think_us"]), burst=True,
                bootstrap_us=int(p["bootstrap_us"]),
                end_us=int(p["end_us"]), mailbox_cap=int(p["mailbox_cap"]))
    lk = p["link"]
    link = Quantize(gossip_links(
        median_us=int(lk["median_us"]), sigma=float(lk["sigma"]),
        cap_us=int(lk["cap_us"]), floor_us=int(lk["floor_us"])),
        int(lk["quantum_us"]))
    return sc, link


class Cell:
    def __init__(self, config, traffic, *, interpret=False):
        del interpret                    # no kernel on this path
        p = self.p = config["params"]
        self.n = int(p["n_nodes"])
        self.budget = int(traffic["max_supersteps_per_job"])
        sc, link = scenario_and_link(p)
        self.engine = JaxEngine(sc, link, window=p["window"],
                                seed=int(p["engine_seed"]), insert="xla")
        eng = self.engine

        @jax.jit
        def with_origin(st, k):
            idx = jnp.stack([jnp.zeros_like(k), k])

            def swap(x):
                return x.at[idx].set(x[idx[::-1]])
            states = {f: swap(v) if f in _ORIGIN_FIELDS else v
                      for f, v in st.states.items()}
            return st._replace(states=states, wake=swap(st.wake))

        @jax.jit
        def counters(fin):
            return (fin.delivered, fin.steps, fin.time,
                    eng._next_event(fin) >= NEVER,
                    (fin.states["hop"] >= 0).sum(),
                    jnp.stack([fin.overflow, fin.short_delay,
                               fin.route_drop, fin.bad_dst]))

        self._with_origin, self._counters = with_origin, counters

    # -- set-up ---------------------------------------------------------

    def set_up(self, seed):
        self.rng = np.random.default_rng(seed)
        self.state0 = jax.block_until_ready(self.engine.init_state())
        # what each wave of the window left behind: (origin, every
        # node's hop count, the wave's counts)
        self.waves = []
        return self.job(0)               # compiles every program of a job

    # -- one job ----------------------------------------------------------

    def job(self, i):
        k = int(self.rng.integers(0, self.n))
        st = self._with_origin(self.state0, np.int32(k))
        fin = self.engine.run_quiet(self.budget, st)
        delivered, steps, time, quiet, infected, parity, hop = \
            jax.device_get(self._counters(fin) + (fin.states["hop"],))
        why = []
        if not quiet:
            why.append("not quiescent inside the step budget")
        for name, v in zip(("overflow", "short_delay", "route_drop",
                            "bad_dst"), parity):
            if v:
                why.append(f"{name}={int(v)}")
        # the push-only miss floor: a node is missed with probability
        # about e^-fanout, so literal full coverage is not owed
        missed = self.n - int(infected)
        if missed > max(self.n // 500, 8):
            why.append(f"{missed} nodes never infected")
        compiles = self.engine.last_run_stats["compiles"]
        if compiles and i:
            why.append(f"{compiles} driver compiles inside the window")
        if i:                            # a job of the window
            self.waves.append((k, hop, {
                "delivered": int(delivered), "supersteps": int(steps),
                "time": int(time)}))
        return {"msgs": int(delivered), "supersteps": int(steps),
                "failed": "; ".join(why)}

    # -- what decides `correct` -------------------------------------------

    def _reference_waves(self, graph):
        """``graph.wave`` of every origin the window ran, on a few
        threads (numpy lets go of the interpreter lock)."""
        origins = [k for k, _, _ in self.waves]
        with ThreadPoolExecutor(_REFERENCE_THREADS) as pool:
            return list(pool.map(graph.wave, origins))

    def compare(self, reference, produced=None):
        """Rows ``(name, value, limit)``, all exact (limit 0): every
        wave the timed path ran, as its final state has it at full
        width, against the plain reference's event-by-event run of the
        same wave. Per node the hop count it ended with (and so who was
        reached at all); per wave the messages delivered, the number of
        supersteps and the time of the last. ``produced`` stands in the
        program's place where it is given (the control)."""
        wants = self._reference_waves(reference.Graph(self.p))
        if produced is None:
            produced = [(hop, facts) for _, hop, facts in self.waves]
        hop = infected = delivered = steps = time = 0
        for (got_hop, got), want in zip(produced, wants):
            hop += int((got_hop != want["hop"]).sum())
            infected += int(((got_hop >= 0) != (want["hop"] >= 0)).sum())
            delivered += got["delivered"] != want["delivered"]
            steps += got["supersteps"] != want["supersteps"]
            time += got["time"] != want["time"]
        name = f"waves_{len(wants)}"
        return [(f"{name}.hop.nodes_that_differ", hop, 0),
                (f"{name}.infected.nodes_that_differ", infected, 0),
                (f"{name}.delivered.waves_that_differ", delivered, 0),
                (f"{name}.supersteps.waves_that_differ", steps, 0),
                (f"{name}.last_superstep_time.waves_that_differ", time, 0)]

    def control(self, reference):
        """The comparison with the control in the program's place: the
        reference with the lognormal of the link's latency computed in
        bfloat16, the precision below the float32 the configuration's
        link states."""
        low = self._reference_waves(reference.Graph(self.p, "bfloat16"))
        return self.compare(reference, [(w["hop"], w) for w in low])

    # -- counts for the per-layer readers ---------------------------------

    def facts(self):
        return {}
