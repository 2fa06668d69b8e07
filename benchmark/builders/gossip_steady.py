"""Builder of the steady-mongering cells: the general engine as
``JaxEngine(sc, link)`` builds it for a scenario with one outbox slot
(``window`` 1: the eager routing path, no ladder) runs push rumor
mongering for ever, streamed in jobs of a fixed number of supersteps:
``run_quiet`` on the state the last job returned, ended by one readback
of its counters. Every node pushes on every round, so every superstep
is at full width.

``--seed`` draws the origin node. The engine's own seed is a
compile-time constant of a solo engine, so it is fixed in the
configuration; rows 0 and ``k`` of ``hop``, ``left``, ``next`` and
``wake`` of ``init_state()`` swap, as the wave's builder swaps them.
Set-up runs the ramp (the epidemic from one node to all of them), holds
it to its gate, and then the warm-up jobs; the window streams on from
there. README_steady.md has the page.
"""

import math

import numpy as np

import jax
import jax.numpy as jnp

import fleet_reduce
import steady_costs
from timewarp_tpu.core.scenario import NEVER
from timewarp_tpu.interp.jax_engine.engine import JaxEngine
from timewarp_tpu.models.gossip import gossip
from timewarp_tpu.net.delays import Quantize, UniformDelay

_ORIGIN_FIELDS = ("hop", "left", "next")
_NEVER_SILENT = ("overflow", "short_delay", "route_drop", "bad_dst",
                 "bad_delay")
_EMPTY = np.int32(2**31 - 1)     # the mailbox's "no message" deliver time
_NODE_FACTS = ("hop", "lcg", "next_round", "in_flight_count",
               "in_flight_least_hop")
_RUN_FACTS = ("delivered", "steps", "time")
_OFF_THE_GRID = ("messages_off_the_grid", "timers_off_the_grid")


def engine_of(p, mailbox_cap=None):
    """The configuration's engine; ``mailbox_cap`` stands in for the
    configuration's own (the control)."""
    lk = p["link"]
    if lk["model"] != "uniform" or not p["steady"]:
        raise SystemExit("benchmark: this builder runs steady mongering "
                         "on a uniform link")
    sc = gossip(int(p["n_nodes"]), fanout=int(p["fanout"]),
                think_us=int(p["think_us"]),
                gossip_interval=int(p["gossip_interval_us"]),
                bootstrap_us=int(p["bootstrap_us"]), end_us=int(p["end_us"]),
                steady=True, mailbox_cap=int(
                    p["mailbox_cap"] if mailbox_cap is None else mailbox_cap))
    link = Quantize(UniformDelay(int(lk["lo_us"]), int(lk["hi_us"])),
                    int(lk["quantum_us"]))
    return JaxEngine(sc, link, window=p["window"],
                     seed=int(p["engine_seed"]))


def _near(count, due):
    """Whether ``count`` deliveries are within 1 % of the ``due`` ones
    that full width means. A superstep's count is a sum of independent
    arrivals with a deviation of about ``sqrt(due)``, 0.1 % at 2^20
    nodes and over 1 % at the tests' sizes: six of those where that is
    the wider."""
    return abs(count - due) <= max(due // 100, 6 * math.isqrt(due))


@jax.jit
def _with_origin(st, k):
    idx = jnp.stack([jnp.zeros_like(k), k])

    def swap(x):
        return x.at[idx].set(x[idx[::-1]])
    states = {f: swap(v) if f in _ORIGIN_FIELDS else v
              for f, v in st.states.items()}
    return st._replace(states=states, wake=swap(st.wake))


class Cell:
    def __init__(self, config, traffic, *, interpret=False):
        del interpret                    # no kernel on this path
        # the nested scope this cell's readers look for is newer than
        # the cell: a cache keyed without the names would hand a
        # program compiled from a checkout that lacks it to one that
        # has it (PERF.md, Findings PR 24)
        jax.config.update(
            "jax_compilation_cache_include_metadata_in_key", True)
        p = self.p = config["params"]
        self.control_of = config["control"]
        self.workload = traffic["name"]
        self.n = int(p["n_nodes"])
        self.per_job = int(traffic["supersteps_per_job"])
        self.ramp = int(traffic["ramp_supersteps"])
        self.engine = engine_of(p)
        if self.engine._adaptive_regime():
            raise SystemExit("benchmark: the cell measures the eager "
                             "routing path, and this engine routes by "
                             "the ladder")
        q = int(p["link"]["quantum_us"])
        ring = -(-int(p["link"]["hi_us"]) // q)

        @jax.jit
        def reduce(st):
            due = [st.mb_rel == (j + 1) * q for j in range(ring)]
            hops = st.mb_payload[:, 0, :]
            count = jnp.stack([m.sum(axis=0, dtype=jnp.int32) for m in due])
            return {
                "hop": st.states["hop"], "lcg": st.states["lcg"],
                "next_round": jnp.where(st.wake >= NEVER, -1,
                                        st.wake // q).astype(jnp.int32),
                "in_flight_count": count,
                "in_flight_least_hop": jnp.stack([
                    jnp.where(m, hops, _EMPTY).min(axis=0) for m in due]),
                "messages_off_the_grid":
                    (st.mb_rel < _EMPTY).sum() - count.sum(),
                "timers_off_the_grid": (
                    (st.wake != st.states["next"])
                    | ((st.wake < NEVER) & (st.wake % q != 0))).sum(),
            }

        self._reduce = reduce
        self._op_names = None

    # -- set-up ---------------------------------------------------------

    def _ramp(self, engine):
        """A stream from the seed's origin through the ramp: the state,
        and what the ramp's gate has against it."""
        st = _with_origin(engine.init_state(), np.int32(self.origin))
        # the ramp's last superstep apart, for what it alone delivered
        st = engine.run_quiet(self.ramp - 1, st)
        before = int(st.delivered)
        st = engine.run_quiet(1, st)
        last = int(st.delivered) - before
        infected = int((st.states["hop"] >= 0).sum())
        why = []
        if infected != self.n:
            why.append(f"{self.n - infected} nodes without the rumor "
                       f"after the ramp's {self.ramp} supersteps")
        if not _near(last, self.n):
            why.append(f"the ramp's last superstep delivered {last}, not "
                       f"within 1 % of {self.n}")
        return st, why

    def set_up(self, seed):
        self.seed = seed
        self.origin = int(np.random.default_rng(seed).integers(0, self.n))
        self.state, why = self._ramp(self.engine)
        self.first = None
        self._wants = {}                 # the reference's facts, by steps
        return self.job(0, why)

    # -- one job ----------------------------------------------------------

    def _advance(self, engine, st):
        """One job's supersteps on ``st``: the state they leave, the
        messages they delivered, how many ran, and what the job's
        gates have against them."""
        new = engine.run_quiet(self.per_job, st)
        delivered0, steps0, delivered, steps, *silent = (
            int(x) for x in jax.device_get(
                (st.delivered, st.steps, new.delivered, new.steps)
                + tuple(getattr(new, f) for f in _NEVER_SILENT)))
        msgs, ran = delivered - delivered0, steps - steps0
        why = [f"{name}={v}" for name, v in zip(_NEVER_SILENT, silent) if v]
        if ran != self.per_job:
            why.append(f"{ran} supersteps of {self.per_job}")
        due = self.per_job * self.n
        if not _near(msgs, due):
            why.append(f"delivered {msgs}, not within 1 % of {due}")
        return new, msgs, ran, why

    def job(self, i, why=()):
        self.state, msgs, ran, gates = self._advance(self.engine, self.state)
        why = list(why) + gates
        compiles = self.engine.last_run_stats["compiles"]
        if compiles and i:
            why.append(f"{compiles} driver compiles inside the window")
        if i == 1:                       # the first job of the window
            self.first = self.state
        return {"msgs": msgs, "supersteps": ran, "failed": "; ".join(why)}

    # -- what decides `correct` -------------------------------------------

    def _facts(self, st):
        """The engine's state as the plain facts the reference states:
        per node the hop count, the generator and the round of the next
        push (``wake``: when the engine will fire the node), and the
        mailbox reduced to how many messages are in flight to each node
        for each due round and the least hop among them. A message due
        on no round of the ring, or a timer off the rounds' grid, is
        counted apart."""
        facts = self._reduce(st)
        facts.update(delivered=int(st.delivered), steps=int(st.steps),
                     time=int(st.time))
        return facts

    @staticmethod
    def _rows(tag, got, want):
        rows = [(f"{tag}.{f}.mismatches", int((got[f] != want[f]).sum()), 0)
                for f in _NODE_FACTS]
        rows += [(f"{tag}.{f}.mismatches", int(got[f] != want[f]), 0)
                 for f in _RUN_FACTS]
        return rows + [(f"{tag}.{f}", int(got[f]), 0)
                       for f in _OFF_THE_GRID if f in got]

    def compare(self, reference, stand_in=None):
        """Rows ``(name, value, limit)``, all exact (limit 0), at full
        width, after the first job of the window and for the state the
        window ended on: how many nodes' hop count, generator or next
        push differ from the plain reference's, how many ``(due round,
        node)`` pairs' in-flight count or least in-flight hop do, and
        whether ``delivered``, ``steps`` and ``time`` do; last, the most
        messages the reference ever had in flight to one node, against
        the mailbox's slots. ``stand_in(steps)`` puts other facts in the
        program's place (the control)."""
        if stand_in is None:
            # run.py deletes a traced run's profile before the readers
            # run: this is the one call it makes while the file is there
            self._op_names = fleet_reduce.traced_op_names(
                self.workload, self.seed)
        rows = []
        for tag, st in (("first_job", self.first),
                        ("window_end", self.state)):
            want = self._want(reference, int(st.steps))
            rows += self._rows(tag, stand_in(want["steps"]) if stand_in
                               else self._facts(st), want)
        return rows + [("reference.largest_in_flight_to_one_node",
                        want["largest_in_flight"], int(self.p["mailbox_cap"]))]

    def _want(self, reference, steps):
        """The plain reference's facts after ``steps`` supersteps of this
        set-up's stream: one run forwards, kept for the controls."""
        if steps not in self._wants:
            first = not self._wants
            if first:
                self._mongering = reference.Mongering(self.p, self.origin)
            self._wants[steps] = self._mongering.run_to(steps)
            if first:
                print(f"origin {self.origin}: every node held the rumor "
                      f"after superstep {self._mongering.saturation_step()}")
        return self._wants[steps]

    def control(self, reference):
        """Two controls in the program's place, each of which has to
        fail: the reference with the link's word cut to its low bits
        (``link_word_bits``, the precision below its 32), and the
        program built with the source's own ``mailbox_cap``, which
        drops what does not fit (the reference loses nothing, so a
        dropped message shows as an in-flight count that differs and
        as fewer delivered). The rows of both; of one alone if it
        passes, so that a control that has stopped failing does not
        hide behind the other."""
        low = reference.Mongering(self.p, self.origin,
                                  int(self.control_of["link_word_bits"]))
        parts = {"low_word": self.compare(reference, low.run_to),
                 "small_mailbox": self._small_mailbox(reference)}
        for name, rows in parts.items():
            if all(v <= limit for _, v, limit in rows):
                print(f"the control {name} passed the comparison")
                return rows
        return [(f"{name}.{row}", v, limit)
                for name, rows in parts.items() for row, v, limit in rows]

    def _small_mailbox(self, reference):
        """The comparison's rows after the first job of the window, of
        a stream from the same origin on an engine with the control's
        ``mailbox_cap``; and what that engine counted as overflow."""
        small = engine_of(self.p, self.control_of["mailbox_cap"])
        st, _ = self._ramp(small)
        for _ in range((int(self.first.steps) - self.ramp) // self.per_job):
            st = self._advance(small, st)[0]
        return self._rows("first_job", self._facts(st),
                          self._want(reference, int(st.steps))) + [
            ("first_job.overflow", int(st.overflow), 0)]

    # -- counts for the per-layer readers ---------------------------------

    def facts(self):
        sc = self.engine.scenario
        return {"op_names": self._op_names,
                "superstep_bytes": steady_costs.steady_superstep_bytes(
                    self.n, sc.mailbox_cap, sc.payload_width)}
