"""Builder of the observer-ring cells: the token ring with the upstream
example's observer hub on the general engine as ``JaxEngine(sc,
FixedDelay(d))`` builds it (``window`` 1; two outbox slots put routing
on the adaptive ladder; the scenario keeps the engine's default
contract, the ordered inbox with sender ids), streamed in jobs of a
fixed number of supersteps: ``run_quiet`` on the state the last job
returned, ended by one readback of its counters and the hub's two
words.

Every ring node holds a token, so a ring cycle is three supersteps (the
timers fire, the tokens arrive and every node notes it to the hub, the
hub fires) and a job is a whole number of cycles. All ``n`` notes of a
cycle reach the hub at one instant and its inbox has ``mailbox_cap``
slots: it keeps the first ``mailbox_cap`` in arrival order and the
engine counts the rest in ``overflow``. That count is no fault here: it
is the deployment (a hub of bounded inbox under overload), and every
job is gated on its exact value, not on zero.

``--seed`` draws every ring node's initial ``val`` (int32 below
``value_below``), so every token, every note and the hub's ``prev`` and
``errs`` move with the seed and no count does. The engine is built from
the configuration alone, so every seed runs the one compiled program.
README_observer.md has the page.
"""

import numpy as np

import jax
import jax.numpy as jnp

import fleet_reduce
import hub_costs
from timewarp_tpu.core.scenario import NEVER
from timewarp_tpu.interp.jax_engine.engine import JaxEngine
from timewarp_tpu.models.token_ring import token_ring
from timewarp_tpu.net.delays import FixedDelay

_CYCLE = 3                       # supersteps: timers, tokens, the hub
_NEVER_SILENT = ("bad_dst", "bad_delay", "short_delay", "route_drop")
_EMPTY = np.int32(2**31 - 1)     # the mailbox's "no message" deliver time
_NODE_FACTS = ("cnt", "val", "send_at", "wake")
_MAILBOX_FACTS = ("mailbox_due", "mailbox_src", "mailbox_word",
                  "mailbox_kind")
_RUN_FACTS = ("hub_prev", "hub_errs", "delivered", "overflow", "steps",
              "time")


def engine_of(p):
    """The configuration's engine, as ``python -m timewarp_tpu
    token-ring --observer --engine general`` builds it."""
    lk = p["link"]
    if lk["model"] != "fixed" or not p["with_observer"]:
        raise SystemExit("benchmark: this builder runs the ring with its "
                         "observer hub on a link of fixed latency")
    sc = token_ring(int(p["n_ring"]), n_tokens=int(p["n_tokens"]),
                    think_us=int(p["think_us"]),
                    bootstrap_us=int(p["bootstrap_us"]),
                    end_us=int(p["end_us"]), with_observer=True,
                    mailbox_cap=int(p["mailbox_cap"]))
    return JaxEngine(sc, FixedDelay(int(lk["delay_us"])),
                     window=p["window"])


def _timer(x):
    """The engine's ``NEVER`` as the reference's "no timer" (-1)."""
    x = np.asarray(x)
    return np.where(x >= NEVER, -1, x)


class Cell:
    def __init__(self, config, traffic, *, interpret=False):
        del interpret                    # no kernel on this path
        # the scopes this cell's readers look for are newer than the
        # engine: a cache keyed without the names would hand a program
        # compiled from a checkout that lacks them to one that has them
        # (PERF.md, Findings PR 24)
        jax.config.update(
            "jax_compilation_cache_include_metadata_in_key", True)
        p = self.p = config["params"]
        self.control_of = config["control"]
        self.workload = traffic["name"]
        self.n = int(p["n_ring"])
        cap = int(p["mailbox_cap"])
        self.per_job = int(traffic["supersteps_per_job"])
        self.value_below = int(traffic["value_below"])
        if self.per_job % _CYCLE or int(p["n_tokens"]) != self.n:
            raise SystemExit("benchmark: a job is a whole number of ring "
                             "cycles of three supersteps, every ring node "
                             "holding a token")
        cycles = self.per_job // _CYCLE
        #: what a job delivers, and what it drops at the hub and counts
        self.due = cycles * (self.n + cap)
        self.dropped = cycles * (self.n - cap)
        self.engine = engine_of(p)
        sc = self.engine.scenario
        if sc.commutative_inbox or not sc.inbox_src \
                or not self.engine._adaptive_regime():
            raise SystemExit("benchmark: the cell measures the ordered "
                             "inbox with sender ids on the routing ladder")
        hub = self.n
        # the hub's two words, cut out on the device: one small
        # program a job beside the driver's
        self._hub_words = jax.jit(lambda prev, errs: (prev[hub], errs[hub]))
        self._op_names = None

    # -- set-up ---------------------------------------------------------

    def set_up(self, seed):
        """State from the seed, then the first job: it compiles the
        driver (the step budget is an operand, so this is the program
        every later job runs)."""
        self.seed = seed
        self.val0 = np.random.default_rng(seed).integers(
            0, self.value_below, self.n, dtype=np.int32)
        st = self.engine.init_state()
        val = st.states["val"].at[:self.n].set(jnp.asarray(self.val0))
        self.state = st._replace(states={**st.states, "val": val})
        self.counted = {"delivered": 0, "overflow": 0, "steps": 0}
        self.first = None
        self._stream = None              # the reference, one run forwards
        self._wants = {}                 # its facts, by supersteps
        self._beyond = {}                # states past the window's end
        return self.job(0)

    # -- one job ----------------------------------------------------------

    def job(self, i):
        st = self.engine.run_quiet(self.per_job, self.state)
        stats = self.engine.last_run_stats
        (delivered, overflow, steps, _, prev, errs, *silent) = (
            int(x) for x in jax.device_get(
                (st.delivered, st.overflow, st.steps, st.time,
                 *self._hub_words(st.states["prev"], st.states["errs"]))
                + tuple(getattr(st, f) for f in _NEVER_SILENT)))
        was = self.counted
        msgs, dropped = delivered - was["delivered"], overflow - was["overflow"]
        ran = steps - was["steps"]
        why = [f"{name}={v}" for name, v in zip(_NEVER_SILENT, silent) if v]
        if ran != self.per_job:
            why.append(f"{ran} supersteps of {self.per_job}")
        if msgs != self.due:
            why.append(f"delivered {msgs}, due {self.due}")
        if dropped != self.dropped:
            why.append(f"the hub dropped and counted {dropped} notes, "
                       f"due {self.dropped}")
        # a program from before the counter has nothing to hold to it
        peak = stats.get("fan_in_peak")
        if peak is not None and peak != self.n:
            why.append(f"fan_in_peak {peak}, due {self.n}")
        if (stats["dispatches"], stats["readbacks"]) != (1, 1):
            why.append(f"{stats['dispatches']} dispatches and "
                       f"{stats['readbacks']} readbacks a job")
        if stats["compiles"] and i:
            why.append(f"{stats['compiles']} driver compiles inside the "
                       "window")
        self.state = st
        self.counted = {"delivered": delivered, "overflow": overflow,
                        "steps": steps}
        if i == 1:                       # the first job of the window
            self.first = st
        return {"msgs": msgs, "supersteps": ran, "fan_in_peak": peak,
                "hub_prev": prev, "hub_errs": errs,
                "failed": "; ".join(why)}

    # -- what decides `correct` -------------------------------------------

    def _facts(self, st):
        """The engine's state as the plain facts the reference states,
        on the host: every node's ``cnt``, ``val``, ``send_at`` and
        ``wake``; the hub's ``prev`` and ``errs``; every mailbox slot
        in slot order (its due time, its sender and both payload
        words; what an empty slot holds is no part of the result and
        reads as the reference's empty slot); the counters."""
        st = jax.device_get(st)
        live = st.mb_rel < _EMPTY
        return {
            "cnt": st.states["cnt"], "val": st.states["val"],
            "send_at": _timer(st.states["send_at"]), "wake": _timer(st.wake),
            "hub_prev": int(st.states["prev"][self.n]),
            "hub_errs": int(st.states["errs"][self.n]),
            "mailbox_due": np.where(
                live, int(st.time) + st.mb_rel.astype(np.int64), -1),
            "mailbox_src": np.where(live, st.mb_src, 0),
            "mailbox_word": np.where(live, st.mb_payload[:, 0, :], 0),
            "mailbox_kind": np.where(live, st.mb_payload[:, 1, :], 0),
            "delivered": int(st.delivered), "overflow": int(st.overflow),
            "steps": int(st.steps), "time": int(st.time),
        }

    @staticmethod
    def _rows(tag, got, want):
        return [(f"{tag}.{f}.mismatches",
                 int(np.sum(np.asarray(got[f]) != np.asarray(want[f]))), 0)
                for f in _NODE_FACTS + _MAILBOX_FACTS + _RUN_FACTS]

    def compare(self, reference, stand_in=None):
        """Rows ``(name, value, limit)``, all exact (limit 0), at full
        width: entries that differ from the plain reference's after the
        first job of the window and for the state the window ended on.
        A job ends with the hub's firing, so every mailbox is empty at
        both; the state the window ended on is therefore followed one
        superstep further (``tokens_in_flight``: a token in every ring
        node's mailbox) and one more (``hub_inbox``: the tokens
        delivered, the hub's slots holding the notes it kept, in
        arrival order), outside the window, by the program every job
        ran. ``stand_in(steps)`` puts other facts in the program's
        place (the control)."""
        if stand_in is None:
            # run.py deletes a traced run's profile before the readers
            # run: this is the one call it makes while the file is there
            self._op_names = fleet_reduce.traced_op_names(
                self.workload, self.seed)
        rows = []
        for tag, st in (("first_job", self.first),
                        ("window_end", self.state)):
            rows += self._compared(reference, tag, st, stand_in)
        st = self.state
        for tag in ("tokens_in_flight", "hub_inbox"):
            st = self._further(st)
            rows += self._compared(reference, tag, st, stand_in)
        return rows

    def _further(self, st):
        """``st`` one superstep on; kept, so that the controls compare
        with the states the comparison did."""
        key = int(st.steps)
        if key not in self._beyond:
            self._beyond[key] = self.engine.run_quiet(1, st)
        return self._beyond[key]

    def _compared(self, reference, tag, st, stand_in):
        steps = int(st.steps)
        want = self._want(reference, steps)
        return self._rows(tag, stand_in(steps) if stand_in
                          else self._facts(st), want)

    def _want(self, reference, steps):
        """The plain reference's facts after ``steps`` supersteps of
        this set-up's stream: one run forwards, kept for the controls."""
        if steps not in self._wants:
            if self._stream is None:
                self._stream = reference.ObserverRing(self.p, self.val0)
            self._wants[steps] = self._stream.run_to(steps)
        return self._wants[steps]

    def control(self, reference):
        """Two controls in the program's place, each of which has to
        fail: the reference with the token values in the next narrower
        integer type (``value_dtype``: the ring is integer throughout,
        so "a precision below" is int16 for int32; seeded values reach
        ``value_below`` and wrap at once), and the reference with the
        hub's arrivals of an instant taken in descending sender order
        (``hub_order``: the ordered inbox is the contract the cell
        exists for; ``hub_prev``, ``hub_errs`` and the hub's slots
        differ). The rows of both; of one alone if it passes, so that a
        control that has stopped failing does not hide behind the
        other."""
        dtype, order = (self.control_of[k]
                        for k in ("value_dtype", "hub_order"))
        parts = {}
        for name, kw in (
                (f"{dtype}_values", {"dtype": np.dtype(dtype)}),
                (f"hub_{order}", {"hub_descending": order == "descending"})):
            parts[name] = self.compare(
                reference, reference.ObserverRing(self.p, self.val0,
                                                  **kw).run_to)
        for name, rows in parts.items():
            if all(v <= limit for _, v, limit in rows):
                print(f"the control {name} passed the comparison")
                return rows
        return [(f"{name}.{row}", v, limit)
                for name, rows in parts.items() for row, v, limit in rows]

    # -- counts for the per-layer readers ---------------------------------

    def facts(self):
        sc = self.engine.scenario
        return {"op_names": self._op_names,
                "superstep_bytes": hub_costs.hub_superstep_bytes(
                    sc.n_nodes, sc.mailbox_cap, sc.payload_width,
                    self.due / self.per_job)}
