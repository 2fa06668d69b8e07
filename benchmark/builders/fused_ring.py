"""Builder of the dense-ring cells: ``FusedRingEngine`` (the compiled
Mosaic superstep kernel) streamed in jobs of a fixed number of
supersteps, each on the state the last one returned and ended by the
readback of its counters.

``--seed`` draws the tokens' initial values (``states["val"]``, int32
below ``value_below``), set on ``EdgeEngine.init_state()`` and carried
into the fused layout by ``from_edge_state``. The engine itself is
built from the configuration and the traffic alone, so every seed runs
the one compiled program.
"""

import numpy as np

import jax
import jax.numpy as jnp

from timewarp_tpu.interp.jax_engine import fused_ring
from timewarp_tpu.interp.jax_engine.edge_engine import EdgeEngine
from timewarp_tpu.models.token_ring import token_ring
from timewarp_tpu.net.delays import FixedDelay

import kernel_costs

_EMPTY = np.int32(2**31 - 1)     # the fused layout's "no entry" time


class Cell:
    def __init__(self, config, traffic, *, interpret=False):
        p = config["params"]
        self.n = int(p["n_nodes"])
        self.bootstrap_us = int(p["bootstrap_us"])
        self.link_delay_us = int(p["link_delay_us"])
        self.per_job = int(traffic["supersteps_per_job"])
        self.value_below = int(traffic["value_below"])
        n_tokens = traffic["n_tokens"]
        sc = token_ring(
            self.n, n_tokens=self.n if n_tokens == "all" else int(n_tokens),
            think_us=int(traffic["think_us"]),
            bootstrap_us=self.bootstrap_us, end_us=int(p["end_us"]),
            with_observer=False, mailbox_cap=int(p["mailbox_cap"]))
        link = FixedDelay(self.link_delay_us)
        self._edge = EdgeEngine(sc, link, cap=int(p["edge_cap"]))
        self.engine = fused_ring.FusedRingEngine(
            sc, link, cap=int(p["edge_cap"]), interpret=interpret)
        self.kernel_event = config.get("kernel_event")

    # -- set-up ---------------------------------------------------------

    def set_up(self, seed):
        """State from the seed, then the first job: it compiles the
        driver (the step budget is an operand, so this is the program
        every later job runs) and is the first answer ``compare``
        holds to the reference."""
        self.val0 = np.random.default_rng(seed).integers(
            0, self.value_below, self.n, dtype=np.int32)
        es = self._edge.init_state()
        es = es._replace(states={**es.states, "val": jnp.asarray(self.val0)})
        self.state = self.engine.from_edge_state(es)
        self.delivered, self.steps = 0, 0
        first = self.job(0)
        self.first_job = self._facts(self.state)
        return first

    # -- one job ----------------------------------------------------------

    def job(self, i):
        st = self.engine.run_quiet(self.per_job, self.state)
        delivered, overflow, steps = (int(x) for x in jax.device_get(
            (st.delivered, st.overflow, st.steps)))
        ran = steps - self.steps
        # the run's first superstep sends and delivers nothing
        due = self.n * (ran - (1 if self.steps == 0 else 0))
        why = []
        if overflow:
            why.append(f"overflow={overflow}")
        if ran != self.per_job:
            why.append(f"{ran} supersteps of {self.per_job}")
        if delivered - self.delivered != due:
            why.append(f"delivered {delivered - self.delivered}, due {due}")
        compiles = self.engine.last_run_stats["compiles"]
        if compiles and i:
            why.append(f"{compiles} driver compiles inside the window")
        msgs = delivered - self.delivered
        self.state, self.delivered, self.steps = st, delivered, steps
        return {"msgs": msgs, "supersteps": ran, "failed": "; ".join(why)}

    # -- what decides `correct` -------------------------------------------

    def _facts(self, st):
        """The fused state as the plain facts the reference states
        (read to the host, so that nothing of it stays on the device)."""
        p = np.asarray(jax.device_get(st.planes))
        p = p.reshape(p.shape[0], -1)
        base = int(st.base)
        r0, r1 = p[fused_ring._QR0], p[fused_ring._QR1]
        in0, in1 = r0 < _EMPTY, r1 < _EMPTY
        one = in0 ^ in1          # exactly one token in flight to the node
        return {
            "val": p[fused_ring._VAL],
            "in_flight": np.where(in0, p[fused_ring._QV0],
                                  p[fused_ring._QV1]),
            "in_flight_due_us": np.where(
                one, base + np.where(in0, r0, r1).astype(np.int64), -1),
            "tokens_held": p[fused_ring._CNT],
            "timers_armed": int((p[fused_ring._SEND] < _EMPTY).sum()
                                + (p[fused_ring._WAKE] < _EMPTY).sum()),
            "delivered": int(st.delivered), "overflow": int(st.overflow),
            "steps": int(st.steps), "time": base,
        }

    def compare(self, reference, stand_in=None):
        """Rows ``(name, value, limit)``: for the run's first job, by
        the recursion itself, and for the state the window ended on,
        after all of its supersteps (by the recursion's closed form),
        how many entries of each field differ from the plain
        reference. Exact, so every limit is 0. ``stand_in(steps,
        many)`` puts other facts in the program's place (the
        control)."""
        rows = []
        for tag, facts, many in (
                ("first_job", self.first_job, False),
                ("window_end", self._facts(self.state), True)):
            want = self._expect(reference, facts["steps"], many)
            if stand_in is not None:
                facts = stand_in(facts["steps"], many)
            for field, w in want.items():
                bad = int(np.sum(np.asarray(facts[field]) != np.asarray(w)))
                rows.append((f"{tag}.{field}.mismatches", bad, 0))
        return rows

    def _expect(self, reference, steps, many, **kw):
        return reference.expect(
            self.val0, steps, bootstrap_us=self.bootstrap_us,
            link_delay_us=self.link_delay_us, many=many, **kw)

    def control(self, reference):
        """The comparison with the control in the program's place: the
        reference itself with the token values in the next narrower
        integer type (the ring is integer throughout, so "a precision
        below" is int16 for int32). Seeded values reach ``value_below``,
        so they wrap at once."""
        return self.compare(
            reference, lambda steps, many: self._expect(
                reference, steps, many, dtype=np.int16))

    # -- counts for the per-layer readers ---------------------------------

    def facts(self):
        return {"kernel_event": self.kernel_event,
                "kernel_bytes": kernel_costs.ring_superstep_bytes(self.n)}
