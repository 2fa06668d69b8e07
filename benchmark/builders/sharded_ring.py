"""Builder of the dense ring over a mesh: ``ShardedEdgeEngine`` (the XLA
edge engine under ``shard_map``, the node axis divided over the cell's
chips, ring delivery on a boundary ``ppermute``) streamed in jobs of a
fixed number of supersteps, each on the sharded state the last one
returned and ended by the readback of its replicated counters.

``--seed`` draws the tokens' initial values (``states["val"]``, int32
below ``value_below``), placed on ``init_state()``'s own sharding. The
engine is built from the configuration and the traffic alone, so every
seed runs the one compiled program. The scenario, the draw and the
plain reference are ``builders/fused_ring.py``'s, and so is the
comparison (``compare``, ``control``: this ``Cell`` is that one with
another engine, another layout of the state and further gates); what
is new is where the state lives, and the gates hold every job to it.
"""

import numpy as np

import jax

from timewarp_tpu.core.scenario import NEVER
from timewarp_tpu.interp.jax_engine.common import I32MAX
from timewarp_tpu.interp.jax_engine.sharded import ShardedEdgeEngine
from timewarp_tpu.models.token_ring import token_ring
from timewarp_tpu.net.delays import FixedDelay
from timewarp_tpu.parallel.mesh import make_mesh

import ring_x4_costs
import x4_reduce
from builders import fused_ring


class Cell(fused_ring.Cell):
    def __init__(self, config, traffic, *, interpret=False):
        del interpret                    # no kernel on this path
        # the scope ``x4_exchange_us`` reads is newer than the engine:
        # a cache keyed without the names would hand a program compiled
        # from a checkout that lacks it to one that has it (PERF.md,
        # Findings PR 24)
        jax.config.update(
            "jax_compilation_cache_include_metadata_in_key", True)
        p = config["params"]
        self.workload = traffic["name"]
        self.n = int(p["n_nodes"])
        self.chips = int(traffic["chips"])
        if list(p["mesh"]["shape"]) != [self.chips]:
            raise SystemExit(f"benchmark: the configuration's mesh "
                             f"{p['mesh']['shape']} is not the cell's "
                             f"{self.chips} chips")
        if len(jax.devices()) < self.chips:
            raise SystemExit(f"benchmark: the cell's mesh needs "
                             f"{self.chips} devices, JAX found "
                             f"{len(jax.devices())}")
        self.bootstrap_us = int(p["bootstrap_us"])
        self.link_delay_us = int(p["link_delay_us"])
        self.per_job = int(traffic["supersteps_per_job"])
        self.value_below = int(traffic["value_below"])
        n_tokens = traffic["n_tokens"]
        sc = token_ring(
            self.n, n_tokens=self.n if n_tokens == "all" else int(n_tokens),
            think_us=int(traffic["think_us"]),
            bootstrap_us=self.bootstrap_us, end_us=int(p["end_us"]),
            with_observer=bool(p["with_observer"]),
            mailbox_cap=int(p["mailbox_cap"]))
        self.edge_cap = int(p["edge_cap"])
        self.engine = ShardedEdgeEngine(
            sc, FixedDelay(self.link_delay_us),
            make_mesh(self.chips, p["mesh"]["axes"][0]),
            axis=p["mesh"]["axes"][0], cap=self.edge_cap)
        self._op_names = None

    # -- set-up ---------------------------------------------------------

    def set_up(self, seed):
        """State from the seed, placed as ``init_state()`` places it,
        then the first job: it compiles the driver (the step budget is
        an operand, so this is the program every later job runs) and is
        the first answer ``compare`` holds to the reference."""
        self.seed = seed
        self.val0 = np.random.default_rng(seed).integers(
            0, self.value_below, self.n, dtype=np.int32)
        st = self.engine.init_state()
        val = jax.device_put(self.val0, st.states["val"].sharding)
        self.state = st._replace(states={**st.states, "val": val})
        self.delivered, self.steps, self.jobs_run = 0, 0, 0
        first = self.job(0)
        self.first_job = self._facts(self.state)
        return first

    # -- one job ----------------------------------------------------------

    def _placement(self, st):
        """What is wrong with where the job's result lives: its
        ``wake`` has to be one slice a chip, each ``n / chips`` wide,
        on distinct devices at distinct offsets (``chip_smoke.py``
        ``_node_shard_devices``, on the result itself)."""
        shards = st.wake.addressable_shards
        shapes = {s.data.shape for s in shards}
        offsets = {s.index[0].start or 0 for s in shards}
        devices = {s.device for s in shards}
        if (len(shards), shapes) != (self.chips,
                                     {(self.n // self.chips,)}) \
                or len(offsets) != self.chips or len(devices) != self.chips:
            return [f"wake lives as {len(shards)} shards of "
                    f"{sorted(shapes)} at {len(offsets)} offsets on "
                    f"{len(devices)} devices"]
        return []

    def job(self, i):
        st = self.engine.run_quiet(self.per_job, self.state)
        stats = self.engine.last_run_stats
        # one readback of the replicated counters, and no program
        # launched to combine them
        delivered, overflow, steps, *lost = (int(x) for x in jax.device_get(
            (st.delivered, st.overflow, st.steps,
             st.unrouted, st.misrouted, st.bad_delay)))
        lost = sum(lost)
        ran = steps - self.steps
        # the run's first superstep sends and delivers nothing
        due = self.n * (ran - (1 if self.steps == 0 else 0))
        why = self._placement(st)
        if overflow or lost:
            why.append(f"overflow={overflow} unrouted+misrouted+"
                       f"bad_delay={lost}")
        if ran != self.per_job:
            why.append(f"{ran} supersteps of {self.per_job}")
        if delivered - self.delivered != due:
            why.append(f"delivered {delivered - self.delivered}, due {due}")
        if (stats["dispatches"], stats["readbacks"]) != (1, 1):
            why.append(f"{stats['dispatches']} dispatches, "
                       f"{stats['readbacks']} readbacks in the call")
        # the first call compiles the driver; a state that came back
        # placed otherwise than it went in would compile the second
        if stats["compiles"] and self.jobs_run:
            why.append(f"{stats['compiles']} driver compiles in job "
                       f"{self.jobs_run} of the run")
        msgs = delivered - self.delivered
        self.state, self.delivered, self.steps = st, delivered, steps
        self.jobs_run += 1
        return {"msgs": msgs, "supersteps": ran, "failed": "; ".join(why),
                # None from a program that does not count them
                "boundary_msgs": stats.get("boundary_msgs")}

    # -- what decides `correct` -------------------------------------------

    def _facts(self, st):
        """The sharded edge state as the plain facts the reference
        states (every shard read to the host; nothing on the device
        moves)."""
        states, wake, q_rel, q_pay = jax.device_get(
            (st.states, st.wake, st.q_rel[0], st.q_pay[0]))
        time = int(st.time)
        # ``I32MAX`` is the ``q_rel`` of a slot that holds nothing
        in0, in1 = q_rel[0] < I32MAX, q_rel[1] < I32MAX
        one = in0 ^ in1          # exactly one token in flight to the node
        return {
            "val": states["val"],
            "in_flight": np.where(in0, q_pay[0, 0], q_pay[1, 0]),
            "in_flight_due_us": np.where(
                one, time + np.where(in0, q_rel[0],
                                     q_rel[1]).astype(np.int64), -1),
            "tokens_held": states["cnt"],
            "timers_armed": int((states["send_at"] < NEVER).sum()
                                + (wake < NEVER).sum()),
            "delivered": int(st.delivered), "overflow": int(st.overflow),
            "steps": int(st.steps), "time": time,
        }

    def compare(self, reference, stand_in=None):
        """``fused_ring.Cell.compare``'s rows, on the sharded state's
        facts; before them, the one chance to read the profile."""
        if stand_in is None:
            # run.py deletes a traced run's profile before the readers
            # run: this is the one call it makes while the file is there
            self._op_names = x4_reduce.traced_op_names(
                self.workload, self.seed)
        return super().compare(reference, stand_in)

    # -- counts for the per-layer readers ---------------------------------

    def facts(self):
        sc = self.engine.scenario
        return {"op_names": self._op_names,
                "superstep_bytes": ring_x4_costs.x4_superstep_bytes(
                    self.n // self.chips, self.edge_cap, sc.payload_width,
                    self.engine.topo.n_edges)}
