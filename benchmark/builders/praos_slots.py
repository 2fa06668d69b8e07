"""Builder of the Praos cells: the general engine as the CLI's ``praos
--burst`` builds it (``JaxEngine(sc, link, window="auto")``: the link's
8 ms floor is the window, the adaptive ladder routes) takes one fresh
world from genesis through ``slots_per_job`` slots to quiescence, one
``run_quiet`` a job, ended by the readback of its counters and of every
node's chain length: what a researcher who reads chain growth against
link latency reads, and what the comparison holds to the plain
reference.

``--seed`` draws each job's genesis chain length ``h0``, written into
every node's ``best`` before the job: every payload word and every
node's result move with it, and no superstep or message count does (the
engine's own seed, a compile-time constant of a solo engine, decides
who leads and when a push lands, and is the configuration's). So the
reference runs once, from genesis 0, and every job is held to it less
its ``h0``. README_praos.md has the page.
"""

import numpy as np

import jax
import jax.numpy as jnp

import fleet_reduce
from timewarp_tpu.core.scenario import NEVER
from timewarp_tpu.interp.jax_engine.engine import JaxEngine
from timewarp_tpu.models.praos import praos
from timewarp_tpu.net.delays import LogNormalDelay, Quantize

_NEVER_SILENT = ("overflow", "short_delay", "route_drop", "bad_dst")
_NODE_FACTS = ("best", "slot", "lcg")
_RUN_FACTS = ("delivered", "supersteps", "time")
_GENESIS_BELOW = 1 << 30


def engine_of(p, n_slots, mailbox_cap=None):
    """The configuration's engine; ``mailbox_cap`` stands in for the
    configuration's own (the control)."""
    lk = p["link"]
    if lk["model"] != "lognormal" or not p["burst"]:
        raise SystemExit("benchmark: this builder runs burst diffusion "
                         "on a lognormal link")
    n = int(p["n_nodes"])
    sc = praos(n, slot_us=int(p["slot_us"]), n_slots=int(n_slots),
               leader_prob=float(p["leaders_per_slot"]) / n,
               fanout=int(p["fanout"]), burst=True, mailbox_cap=int(
                   p["mailbox_cap"] if mailbox_cap is None else mailbox_cap))
    link = Quantize(LogNormalDelay(
        int(lk["median_us"]), float(lk["sigma"]), cap_us=int(lk["cap_us"]),
        floor_us=int(lk["floor_us"])), int(lk["quantum_us"]))
    return JaxEngine(sc, link, window=p["window"],
                     seed=int(p["engine_seed"]))


@jax.jit
def _with_genesis(st, h0):
    best = jnp.full_like(st.states["best"], h0)
    return st._replace(states={**st.states, "best": best})


def _counters_of(eng):
    """One jitted function of a final state: what a job reads back
    beside the chain lengths."""
    @jax.jit
    def counters(fin):
        return (fin.delivered, fin.steps, fin.time,
                eng._next_event(fin) >= NEVER, fin.states["slot"].min(),
                jnp.stack([getattr(fin, f) for f in _NEVER_SILENT]))
    return counters


class Cell:
    def __init__(self, config, traffic, *, interpret=False):
        del interpret                    # no kernel on this path
        p = self.p = config["params"]
        self.control_of = config["control"]
        self.workload = traffic["name"]
        self.n = int(p["n_nodes"])
        self.n_slots = int(traffic["slots_per_job"])
        self.budget = int(traffic["max_supersteps_per_job"])
        self.engine = engine_of(p, self.n_slots)
        if not self.engine._adaptive_regime():
            raise SystemExit("benchmark: the cell measures the windowed "
                             "ladder, and this engine routes eagerly")
        self._counters = _counters_of(self.engine)
        self._op_names = None

    def _reference_params(self):
        return {**self.p, "n_slots": self.n_slots}

    # -- set-up ---------------------------------------------------------

    def set_up(self, seed):
        self.seed = seed
        self.rng = np.random.default_rng(seed)
        self.state0 = jax.block_until_ready(self.engine.init_state())
        # what each job of the window left behind: its genesis length,
        # every node's chain length (read in the job), the slots seen
        # and the generators (on the device until the comparison), and
        # the job's counts
        self.runs = []
        self._want = None
        return self.job(0)               # compiles every program of a job

    # -- one job ----------------------------------------------------------

    def _run(self, engine, counters, state0, h0):
        """One world from ``state0`` with genesis length ``h0`` to
        quiescence on ``engine``: what its nodes ended with (the chain
        lengths read back, the rest on the device), the job's counts
        and what its gates have against it."""
        fin = engine.run_quiet(self.budget,
                               _with_genesis(state0, np.int32(h0)))
        delivered, steps, time, quiet, slots_seen, silent, best = \
            jax.device_get(counters(fin) + (fin.states["best"],))
        why = []
        if not quiet:
            why.append("not quiescent inside the step budget")
        why += [f"{name}={int(v)}" for name, v in zip(_NEVER_SILENT, silent)
                if v]
        if slots_seen != self.n_slots:
            why.append(f"a node saw {int(slots_seen)} slots of "
                       f"{self.n_slots}")
        tip = int(best.max())
        if tip - h0 != self.n_slots:
            # a slot with no leader, or a leader that never heard the
            # slot before: the chain is then shorter than the slots
            why.append(f"the chain grew by {tip - h0} in {self.n_slots} "
                       "slots")
        short = int((best < tip).sum())
        # the push-only miss floor: no push of the last flood reaches a
        # node with probability about e^-fanout
        if short > max(self.n // 500, 8):
            why.append(f"{short} nodes short of the final chain length")
        nodes = {"best": best, "slot": fin.states["slot"],
                 "lcg": fin.states["lcg"]}
        facts = {"delivered": int(delivered), "supersteps": int(steps),
                 "time": int(time), "overflow": int(silent[0])}
        return nodes, facts, why

    def job(self, i):
        h0 = int(self.rng.integers(0, _GENESIS_BELOW))
        nodes, facts, why = self._run(self.engine, self._counters,
                                      self.state0, h0)
        compiles = self.engine.last_run_stats["compiles"]
        if compiles and i:
            why.append(f"{compiles} driver compiles inside the window")
        if i:                            # a job of the window
            self.runs.append((h0, nodes, facts))
        return {"msgs": facts["delivered"],
                "supersteps": facts["supersteps"], "failed": "; ".join(why)}

    # -- what decides `correct` -------------------------------------------

    def _reference(self, reference):
        """The plain reference's run from genesis 0: once, kept for the
        controls."""
        if self._want is None:
            self._want = reference.Chain(self._reference_params()).run(0)
            w = self._want
            short = int((w["best"] < w["best"].max()).sum())
            print(f"reference: blocks minted a slot {w['minted']}; "
                  f"{short} nodes short of the final chain length; "
                  f"{w['supersteps']} supersteps, {w['delivered']} messages")
        return self._want

    @staticmethod
    def _rows(tag, produced, want):
        """``produced``: ``(genesis length, node facts, run facts)`` a
        job; the chain lengths are compared less the genesis length."""
        differ = dict.fromkeys(_NODE_FACTS + _RUN_FACTS, 0)
        for h0, nodes, run in produced:
            got = {**nodes, "best": nodes["best"] - np.int32(h0)}
            for f in _NODE_FACTS:
                differ[f] += int((np.asarray(got[f]) != want[f]).sum())
            for f in _RUN_FACTS:
                differ[f] += run[f] != want[f]
        return [(f"{tag}.{f}.nodes_that_differ", differ[f], 0)
                for f in _NODE_FACTS] + [
            (f"{tag}.{f}.jobs_that_differ", differ[f], 0)
            for f in _RUN_FACTS]

    def compare(self, reference, produced=None):
        """Rows ``(name, value, limit)``, all exact (limit 0), at full
        width: for every job of the window, nodes whose chain length
        (less the job's genesis length), slots seen or generator differ
        from the plain reference's run, and jobs whose delivered
        messages, supersteps or last superstep's time differ; last,
        the most messages the reference ever had in flight to one
        node, against the mailbox's slots. ``produced`` stands in the
        program's place where it is given (the control)."""
        if produced is None:
            # run.py deletes a traced run's profile before the readers
            # run: this is the one call it makes while the file is there
            self._op_names = fleet_reduce.traced_op_names(
                self.workload, self.seed)
            produced = self.runs
        want = self._reference(reference)
        return self._rows(f"jobs_{len(produced)}", produced, want) + [
            ("reference.largest_in_flight_to_one_node",
             want["largest_in_flight"], int(self.p["mailbox_cap"]))]

    def control(self, reference):
        """Two controls in the program's place, each of which has to
        fail: the reference with the link's lognormal in the precision
        below its float32 (``link_precision``), and the program built
        with the source's own ``mailbox_cap``, which drops the tips
        that do not fit. The rows of both; of one alone if it passes,
        so that a control that has stopped failing does not hide
        behind the other."""
        want = self._reference(reference)
        low = reference.Chain(self._reference_params(),
                              self.control_of["link_precision"]).run(0)
        parts = {"low_precision": self._rows("job", [(0, low, low)], want),
                 "small_mailbox": self._small_mailbox(want)}
        for name, rows in parts.items():
            if all(v <= limit for _, v, limit in rows):
                print(f"the control {name} passed the comparison")
                return rows
        return [(f"{name}.{row}", v, limit)
                for name, rows in parts.items() for row, v, limit in rows]

    def _small_mailbox(self, want):
        """The comparison's rows of one job, from the first genesis
        length of the window, on an engine with the control's
        ``mailbox_cap``; and what that engine counted as overflow."""
        h0 = self.runs[0][0]
        small = engine_of(self.p, self.n_slots, self.control_of["mailbox_cap"])
        nodes, facts, _ = self._run(small, _counters_of(small),
                                    small.init_state(), h0)
        return self._rows("job", [(h0, nodes, facts)], want) + [
            ("job.overflow", facts["overflow"], 0)]

    # -- counts for the per-layer readers ---------------------------------

    def facts(self):
        sc = self.engine.scenario
        return {"op_names": self._op_names, "n_nodes": self.n,
                "mailbox_cap": sc.mailbox_cap,
                "payload_width": sc.payload_width}
