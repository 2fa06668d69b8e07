"""Edge engine: sort/scatter-free batched execution for static topologies.

The general engine (engine.py) routes messages with one variadic sort
plus 2+P mailbox scatters per superstep; on TPU scatters are the
dominant cost (docs/engines.md "Measured on a v5e": random scatter
≈ 1 ms/131k updates, int64 scatter ≈ 15 ms, while elementwise/sort
work is ~free). When the communication graph is
*static* — every outbox slot always targets the same destination
(``Scenario.static_dst``) — routing needs none of that:

- the graph is inverted **on the host** into per-node in-edge tables;
- per-edge bounded queues hold in-flight messages in ``[E, C, N]``
  layout (minor dim = node axis: no lane padding, perfect VPU tiling);
- delivery moves each sender's outbox slot to its receiver's edge
  queue by a *static* index map — a gather, and for pure-shift
  topologies (the ring: ``dst = (i+1) mod N``) ``jnp.roll``, which XLA
  fuses into the surrounding elementwise work;
- queue insert/remove are one-hot elementwise updates over the static
  capacity axis ``C`` — no scatter anywhere.

This is the reference's event loop (TimedT.hs:234-286) specialized the
TPU way: the priority queue becomes per-edge arrival buffers whose
minimum is a masked reduction.

Semantics match core/scenario.py's superstep contract with one scoped
difference: capacity is **per edge** (``cap`` messages in flight per
(src,slot)→dst edge) rather than per-node ``mailbox_cap``. Overflow is
still counted and dropped, never silent; trace parity with the oracle
is bit-for-bit in all no-overflow regimes (the parity tests assert
overflow == 0), which is the regime the capacity declarations are for.

Inbox ordering: for ``commutative_inbox`` scenarios the inbox is
presented unsorted (the step result and the order-independent digests
are invariant to slot order, so parity holds bit-for-bit); otherwise
one variadic ``lax.sort`` along the slot axis — cheap in this layout —
restores contract #2's ``(deliver_time, insert_step, sender-major)``
order.

Delays must fit int32 µs (< ~35 min): queue times are stored relative
to the engine's rebased epoch so no int64 ever needs scattering (or
storing per-slot).
"""

from __future__ import annotations

from functools import partial
from typing import Any, List, NamedTuple, Optional, Tuple

from ...utils import jaxconfig  # noqa: F401

import jax
import jax.numpy as jnp
import numpy as np

from ...core.rng import fire_bits, msg_bits, seed_words
from ...core.scenario import NEVER, Inbox, Outbox, Scenario
from ...net.delays import LinkModel
from ...trace.events import SuperstepTrace
from ...trace.hashing import FIRED, RECV, SENT, mix32_jnp
from .common import I32MAX as _I32MAX
from .common import LocalComm, RunStatsMixin, Stages, StepOut as _StepOut
from .common import padded_scan, scan_pad
from .controlled import ControlledRunMixin
from .common import thi as _thi, tlo as _tlo, u32sum as _u32sum
from .engine import Horizon
from ...integrity.runner import VerifiedRunMixin
from ...obs.flight import FlightRecorderMixin

__all__ = ["EdgeEngine", "EdgeState", "EdgeTopology"]


class EdgeTopology(NamedTuple):
    """Host-side inversion of ``Scenario.static_dst`` (int32 [N, M],
    -1 = unused slot) into receiver-centric in-edge tables.

    Edge order per node is *arbitrary* (the slot-major fast path orders
    edges by outbox column, the inversion path by (src, slot) rank) —
    contract #2/#3 ordering is enforced downstream by the explicit
    ``(deliver_time, insert_step, src, slot)`` inbox sort keys, never
    by edge index.
    """
    n_edges: int               # E = max in-degree
    in_valid: np.ndarray       # bool [E, N] — edge exists
    in_src: np.ndarray         # int32 [E, N] — sender (0 where invalid)
    in_slot: np.ndarray        # int32 [E, N] — sender's outbox slot
    in_flat: np.ndarray        # int32 [E, N] — slot*N + src, for 1D gather
    shift: List[Optional[Tuple[int, int]]]  # per edge: (roll, slot) or None

    @staticmethod
    def build(static_dst: np.ndarray, n: int) -> "EdgeTopology":
        sd = np.asarray(static_dst, np.int32)
        if sd.shape[0] != n:
            raise ValueError(f"static_dst rows {sd.shape[0]} != n_nodes {n}")
        if n * sd.shape[1] >= 2**31:
            # in_flat = slot*N + src must fit int32 (mirrors the
            # JaxEngine smrank guard)
            raise ValueError(
                "n_nodes * max_out must fit int32 (in_flat gather index)")
        used = sd >= 0
        if np.any(sd[used] >= n):
            raise ValueError("static_dst contains out-of-range destination")
        M = sd.shape[1]
        # slot-major fast path: when every declared outbox column is a
        # uniform ring shift, make each column one shift edge directly.
        # The receiver-centric lexsort below ranks in-edges by (src,
        # slot) per receiver, and that ranking is NOT a uniform shift at
        # the ring wrap (receiver 1's smallest src may come from a
        # different column than receiver 20's) — which would wrongly
        # disqualify multi-shift topologies from the ppermute engine.
        # Inbox-order semantics don't depend on edge rank: the contract
        # #2 sort keys on actual (src, slot).
        ids64 = np.arange(n, dtype=np.int64)
        col_shift: List[Optional[int]] = []
        for k in range(M):
            col = sd[:, k]
            if (col < 0).all():
                col_shift.append(-1)        # unused column: skip
            elif (col >= 0).all():
                d = (col.astype(np.int64) - ids64) % n
                col_shift.append(int(d[0]) if (d == d[0]).all() else None)
            else:
                col_shift.append(None)      # partially declared
        if all(s is not None for s in col_shift) \
                and any(s != -1 for s in col_shift):
            cols = [k for k in range(M) if col_shift[k] != -1]
            E = len(cols)
            in_valid = np.ones((E, n), bool)
            in_src = np.stack([
                ((ids64 - col_shift[k]) % n).astype(np.int32)
                for k in cols])
            in_slot = np.stack([np.full(n, k, np.int32) for k in cols])
            in_flat = in_slot * np.int32(n) + in_src
            shift = [(int(col_shift[k]), k) for k in cols]
            return EdgeTopology(E, in_valid, in_src, in_slot, in_flat,
                                shift)
        # vectorized graph inversion: flatten (src, slot) pairs, order by
        # (dst, src, slot) — sender-major within each receiver
        flat = sd.ravel()
        srcs = np.repeat(np.arange(n, dtype=np.int32), M)
        slots = np.tile(np.arange(M, dtype=np.int32), n)
        mask = flat >= 0
        d, s, sl = flat[mask], srcs[mask], slots[mask]
        if d.size == 0:
            raise ValueError("static_dst declares no edges")
        o = np.lexsort((sl, s, d))
        d, s, sl = d[o], s[o], sl[o]
        starts = np.searchsorted(d, np.arange(n, dtype=np.int32))
        e_idx = np.arange(d.size, dtype=np.int64) - starts[d]
        E = int(e_idx.max()) + 1
        in_valid = np.zeros((E, n), bool)
        in_src = np.zeros((E, n), np.int32)
        in_slot = np.zeros((E, n), np.int32)
        in_valid[e_idx, d] = True
        in_src[e_idx, d] = s
        in_slot[e_idx, d] = sl
        in_flat = in_slot * np.int32(n) + in_src
        # pure-shift detection: edge e is src = (i - s) mod N for all i
        shift: List[Optional[Tuple[int, int]]] = []
        ids = np.arange(n, dtype=np.int64)
        for e in range(E):
            if in_valid[e].all() and (in_slot[e] == in_slot[e, 0]).all():
                d = (ids - in_src[e]) % n
                if (d == d[0]).all():
                    shift.append((int(d[0]), int(in_slot[e, 0])))
                    continue
            shift.append(None)
        return EdgeTopology(E, in_valid, in_src, in_slot, in_flat, shift)


class EdgeState(NamedTuple):
    """Complete simulation state — one pytree, checkpointable and
    shardable. Queue axes: [E edges, C capacity, N nodes]."""
    states: Any            # scenario pytree, leading dim N
    wake: jax.Array        # int64[N]
    #: int32[E, C, N] deliver time minus `time`; I32MAX = empty slot
    #: (real delays clamp to I32MAX-1), so validity is derived
    q_rel: jax.Array
    q_step: jax.Array      # int32[E, C, N] — insertion superstep
    #                        (C is 0 for commutative_inbox scenarios:
    #                        the table only feeds the contract-#2 sort)
    q_pay: jax.Array       # int32[E, C, P, N]
    overflow: jax.Array    # int32[]
    unrouted: jax.Array    # int32[] — valid sends on undeclared slots
    misrouted: jax.Array   # int32[] — out.dst disagreeing with static_dst
    bad_delay: jax.Array   # int32[] — delays >= 2^31 µs, clamped
    delivered: jax.Array   # int64[]
    steps: jax.Array       # int64[]
    time: jax.Array        # int64[] — current virtual time == queue epoch
    #: int32[] — messages the fault schedule killed (cuts, down-window
    #: deliveries, reset purges) — mirrors EngineState.fault_dropped
    fault_dropped: jax.Array
    #: bool[C] — consumed restart injections (engine.py EngineState)
    restart_done: jax.Array


class EdgeEngine(RunStatsMixin, ControlledRunMixin, VerifiedRunMixin,
                 FlightRecorderMixin):
    """Batched engine for static-topology scenarios. Same driver API as
    :class:`~timewarp_tpu.interp.jax_engine.engine.JaxEngine`: ``run``
    (traced, per-superstep rows) and ``run_quiet`` (while_loop, no
    trace work compiled in), including the ``telemetry`` knob and its
    zero-overhead/bit-exactness contract (obs/; the edge engine has no
    routing ladder, so the rung field is pinned -1 and ``route_drop``
    0 — per-edge capacity losses are the ``overflow`` counter)."""

    def __init__(self, scenario: Scenario, link: LinkModel, *,
                 seed: int = 0, cap: int = 2,
                 lint: str = "warn", faults=None,
                 telemetry: str = "off", controller=None,
                 verify: str = "off", record: str = "off",
                 record_cap: Optional[int] = None) -> None:
        # static scenario sanitizer — same knob contract as JaxEngine
        from ...analysis import check_scenario
        from ...obs.telemetry import validate_mode
        self.telemetry = validate_mode(telemetry, type(self).__name__)
        # state-integrity checking — same knob contract as JaxEngine
        # (integrity/, docs/integrity.md)
        self._bind_verify(verify)
        # causal flight recorder — same knob contract as JaxEngine
        # (obs/flight.py, docs/observability.md)
        self._bind_record(record, record_cap)
        self.metrics = None
        self.metrics_label = type(self).__name__
        self.last_run_telemetry = None
        self.lint = lint
        self.lint_report = check_scenario(scenario, lint,
                                          who=type(self).__name__)
        if scenario.static_dst is None:
            raise ValueError(
                f"scenario {scenario.name!r} declares no static_dst; "
                "use the general JaxEngine")
        self.scenario = scenario
        self.link = link
        self.s0, self.s1 = seed_words(seed)
        self.cap = cap
        self.topo = EdgeTopology.build(scenario.static_dst,
                                       scenario.n_nodes)
        self.comm = LocalComm(scenario.n_nodes)
        self._setup_faults(faults, scenario, lint)
        # online dispatch (dispatch/): the edge engine runs classic
        # W=1 supersteps and has no routing ladder, so the controller
        # adapts CHUNK LENGTH only — window/rung ride the decision
        # trace pinned (1 / -1). `window` exists for the controller's
        # bound query; `_dyn_ok` stays False (ControlledRunMixin).
        self.window = 1
        self._bind_controller(controller)

    # -- faults (same semantics/masks as JaxEngine, classic W=1) ---------

    def _setup_faults(self, faults, scenario, lint) -> None:
        self.faults = faults
        self._faulted = faults is not None
        self._ft = None
        self.fault_lint_report = None
        self._has_skew = self._has_reset = False
        self._n_restarts = 0
        if faults is None:
            return
        from ...faults.schedule import FaultSchedule
        if not isinstance(faults, FaultSchedule):
            raise ValueError(
                f"the edge engine runs one world; faults must be a "
                f"FaultSchedule, got {faults!r}")
        from ...analysis import check_faults
        self.fault_lint_report = check_faults(
            faults, scenario, lint, who=type(self).__name__)
        self._has_skew = faults.has_skew
        self._has_reset = faults.has_reset
        self._n_restarts = faults.n_restarts
        tables = faults.tables(scenario.n_nodes)
        self._ft = type(tables)(*(jnp.asarray(x) for x in tables))
        if self._has_reset:
            self._reset_states, _ = self._init_states_wake()

    # -- initial state ---------------------------------------------------

    def _init_states_wake(self):
        from .common import init_states_wake
        return init_states_wake(self.scenario)

    def init_state(self) -> EdgeState:
        sc = self.scenario
        n, E, C, P = sc.n_nodes, self.topo.n_edges, self.cap, \
            sc.payload_width
        states, wake = self._init_states_wake()
        # q_step orders same-deliver-time messages for the contract-#2
        # sort; a commutative inbox never sorts, so carrying the table
        # through the loop would be pure dead HBM traffic (~2 reads +
        # writes of [E,C,N] int32 per superstep) — elide it to width 0
        C_step = 0 if sc.commutative_inbox else C
        return EdgeState(
            states=states,
            wake=wake,
            q_rel=jnp.full((E, C, n), _I32MAX, jnp.int32),
            q_step=jnp.zeros((E, C_step, n), jnp.int32),
            q_pay=jnp.zeros((E, C, P, n), jnp.int32),
            overflow=jnp.int32(0),
            unrouted=jnp.int32(0),
            misrouted=jnp.int32(0),
            bad_delay=jnp.int32(0),
            delivered=jnp.int64(0),
            steps=jnp.int64(0),
            time=jnp.int64(0),
            fault_dropped=jnp.int32(0),
            restart_done=jnp.zeros((self._n_restarts,), bool),
        )

    # -- one superstep ---------------------------------------------------

    def _node_next(self, st: EdgeState) -> jax.Array:
        """Each node's next event time as the state alone has it
        (int64[n]; the batched "pop min" before the minimum): its
        wake, or its earliest queued message."""
        nnr = st.q_rel.min(axis=(0, 1))                          # int32[N]
        return jnp.minimum(
            st.wake,
            jnp.where(nnr == _I32MAX, jnp.int64(NEVER),
                      st.time + nnr.astype(jnp.int64)))

    def _horizon(self, st: EdgeState) -> Horizon:
        """The state's :class:`~.engine.Horizon` (``JaxEngine._horizon``'s
        twin): when its next superstep fires, agreed over the mesh,
        and each node's next event. Under a fault schedule events
        inside a down window slide to its ``t_up`` and unconsumed
        reset rows inject the restart firing (faults/apply.py
        ``defer_next``), so the deferral is part of the horizon
        wherever it is computed. Integers and ``min``: the same bits
        from a state whoever asks, so a superstep that takes the
        horizon it was handed is the superstep that finds it again."""
        node_next = self._node_next(st)
        if self._faulted:
            # crash suppression + injected restarts (faults/apply.py;
            # same masks as JaxEngine)
            from ...faults.apply import defer_next
            node_next = defer_next(self._ft, self.comm.node_ids(),
                                   node_next, st.restart_done)
        return Horizon(self.comm.all_min(node_next.min()), node_next)

    def _superstep(self, st: EdgeState, with_trace: bool
                   ) -> Tuple[EdgeState, Optional[_StepOut]]:
        """One superstep of a state on its own: finds the state's
        horizon, and returns the state unchanged once nothing is
        pending. What the scan driver, the chunked and controlled
        drivers and every caller outside the quiet loop step with."""
        with Stages() as stage:
            return self._staged_superstep(st, None, with_trace, stage)

    def _superstep_carried(self, st: EdgeState, hz: Horizon
                           ) -> Tuple[EdgeState, Horizon]:
        """The quiet loop's superstep: ``(state, horizon) -> (state',
        horizon')``. ``hz`` is ``st``'s horizon, found by the
        superstep before (or by ``_quiet_loop``'s one scan before the
        loop), and the loop's condition has decided on ``hz.t`` that
        this superstep runs: nothing is selected by liveness.
        ``horizon'`` is one pass over the ``q_rel`` and ``wake`` the
        superstep has just written, and the one agreement over the
        mesh an iteration makes on its next event."""
        with Stages() as stage:
            new, _ = self._staged_superstep(st, hz, False, stage)
            stage("tw.next_event")
            return new, self._horizon(new)

    def _staged_superstep(self, st, hz, with_trace, stage):
        """One superstep of ``st``, each numbered part under the scope
        ``stage`` names for it (common.py ``STAGES``, as
        ``JaxEngine._staged_superstep`` has them); the delivery's
        ``comm.roll`` calls sit under ``tw.route/exchange``, so that a
        profile of a sharded run tells the boundary hop from the rest
        of the stage. ``hz`` is ``st``'s horizon where the caller's
        loop carries it and has decided on it that the superstep
        applies (``_superstep_carried``); None where nobody has: the
        superstep then finds the horizon itself and the result is
        ``st`` wherever nothing is pending."""
        stage("tw.next_event")
        sc, topo, comm = self.scenario, self.topo, self.comm
        E, C, P = topo.n_edges, self.cap, sc.payload_width
        n = comm.n_local            # array width on this device
        n_glob = comm.n_global
        W = E * C
        node_ids = comm.node_ids()  # global identities, int32[n]
        base = st.time
        #: flight-recorder side channels (obs/flight.py; the JaxEngine
        #: twin): per-trace compacted event buffers, merged into the
        #: StepOut event plane below
        self._rec_extra = []
        rec_full = with_trace and self.record == "full"

        # validity is the rel sentinel (I32MAX = empty slot)
        q_live = st.q_rel < _I32MAX                          # [E,C,N]

        # 1. global next event time (the batched "pop min"): the
        # state's horizon, found here or by whoever made the state
        live = None
        if hz is None:
            hz = self._horizon(st)
            live = hz.t < NEVER
        t, node_next = hz
        if self._faulted and rec_full:
            # fault action: crash window slid a pending event
            # later (engine.py's defer capture, identically)
            from ...obs import flight as _flight
            node_next_pre = self._node_next(st)
            dm = (node_next > node_next_pre) \
                & (node_next_pre < NEVER)
            self._rec_extra.append(_flight.compact(
                self.record_cap, _flight.EV_FAULT, dm, node_ids,
                node_ids, node_next_pre, node_next,
                _flight.TAG_DEFER))
        fire = node_next == t
        if live is not None:
            fire = fire & live

        # 1.5. restart bookkeeping (engine.py twin): consume restart
        # rows firing now; reset their nodes' state; purge pre-crash
        # queue entries (counted — memory the reboot lost)
        restart_done = st.restart_done
        fault_step = jnp.int32(0)
        purge = None
        states_in = st.states
        if self._faulted and self._has_reset:
            from ...faults.apply import consume_restarts, restart_fire
            now_vec = jnp.broadcast_to(t, (n,))  # classic W=1: now == t
            reset_now, purge_before = restart_fire(
                self._ft, fire, now_vec, node_ids, st.restart_done)
            restart_done = consume_restarts(
                self._ft, fire, now_vec, node_ids, st.restart_done)
            purge = q_live & (
                (base + st.q_rel.astype(jnp.int64))
                < purge_before[None, None, :])
            fault_step = fault_step + comm.all_sum(
                jnp.sum(purge, dtype=jnp.int32))
            states_in = jax.tree.map(
                lambda cur, init: jnp.where(
                    reset_now.reshape((n,) + (1,) * (cur.ndim - 1)),
                    init, cur),
                st.states, self._reset_states)
            if rec_full:
                # the injected reboot firing (purged entries are
                # captured below, once per-edge sender ids exist)
                from ...obs import flight as _flight
                self._rec_extra.append(_flight.compact(
                    self.record_cap, _flight.EV_FAULT, reset_now,
                    node_ids, node_ids, jnp.int64(-1), now_vec,
                    _flight.TAG_RESTART))

        stage("tw.deliver")
        # 2. deliverable messages (all per-edge slots due at fired nodes)
        shift32 = jnp.minimum(t - base,
                              jnp.int64(_I32MAX - 1)).astype(jnp.int32)
        deliver = q_live & (st.q_rel <= shift32) & fire[None, None, :]
        if purge is not None:
            deliver = deliver & ~purge

        # 3. inbox [W, N] — slot-axis views of the queues (leading-axis
        #    reshape: no relayout)
        iv = deliver.reshape(W, n)
        rel = jnp.where(iv, st.q_rel.reshape(W, n), _I32MAX)
        istep = None if sc.commutative_inbox \
            else st.q_step.reshape(W, n)
        # per-edge sender ids: computable elementwise for shift edges
        # (works sharded); table lookup otherwise (local only)
        src_rows = jnp.stack([
            (node_ids - jnp.int32(topo.shift[e][0])) % jnp.int32(n_glob)
            if topo.shift[e] is not None
            else comm.local_rows(topo.in_src[e])
            for e in range(E)], axis=0)                      # int32[E, n]
        isrc = jnp.broadcast_to(
            src_rows[:, None, :], (E, C, n)).reshape(W, n)
        ipay = st.q_pay.reshape(W, P, n)
        # what a sharded driver counts beside the state (None here)
        self._crossed = self._remote_deliveries(deliver, src_rows)
        if rec_full and purge is not None:
            # purged queue entries (reboot memory loss), now that the
            # per-edge sender ids exist — src/deliver-time identify
            # the lost message
            from ...obs import flight as _flight
            self._rec_extra.append(_flight.compact(
                self.record_cap, _flight.EV_FAULT,
                purge.transpose(2, 0, 1),
                jnp.broadcast_to(src_rows[:, None, :],
                                 (E, C, n)).transpose(2, 0, 1)
                if sc.inbox_src else jnp.int32(0),
                jnp.broadcast_to(node_ids[None, None, :],
                                 (E, C, n)).transpose(2, 0, 1),
                jnp.int64(-1),
                st.q_rel.transpose(2, 0, 1),
                _flight.TAG_PURGE, t_off=base))
        if not sc.commutative_inbox:
            # contract #2 order: (deliver_time, insert_step, src, slot)
            # — the oracle's arrival order is chronological routing
            # order, i.e. step-major then sender-major then slot; one
            # variadic sort along the inbox-slot axis restores it
            slot_rows = jnp.stack([
                jnp.full((n,), topo.shift[e][1], jnp.int32)
                if topo.shift[e] is not None
                else comm.local_rows(topo.in_slot[e])
                for e in range(E)], axis=0)                  # int32[E, n]
            islot = jnp.broadcast_to(
                slot_rows[:, None, :], (E, C, n)).reshape(W, n)
            ops = jax.lax.sort(
                (~iv, rel, istep, isrc, islot) + tuple(
                    ipay[:, p, :] for p in range(P)),
                dimension=0, num_keys=5)
            iv, rel, isrc = ~ops[0], ops[1], ops[3]
            ipay = jnp.stack(ops[5:5 + P], axis=1)
        itime = jnp.where(iv, base + rel.astype(jnp.int64),
                          jnp.int64(NEVER))
        inbox = Inbox(
            valid=iv,
            # inbox_src=False scenarios never read src: all
            # interpreters present 0 (core/scenario.py)
            src=jnp.where(iv, isrc, 0) if sc.inbox_src
            else jnp.zeros_like(isrc),
            time=itime,
            payload=jnp.where(iv[:, None, :], ipay, 0),
        )

        stage("tw.fire")
        # 4. fire every node; batch axis is the *minor* dim for inbox and
        #    outbox leaves (no [N, small] padding anywhere)
        bits = fire_bits(self.s0, self.s1, node_ids, t) \
            if sc.needs_key else None
        stepf = sc.step
        if self._faulted and self._has_skew:
            from ...faults.apply import skewed_step
            stepf = skewed_step(sc.step, self._ft.skew)
        new_states, out, new_wake = jax.vmap(
            stepf,
            in_axes=(0, Inbox(valid=-1, src=-1, time=-1, payload=-1),
                     None, 0, None if bits is None else 0),
            out_axes=(0, Outbox(valid=-1, dst=-1, payload=-1), 0))(
                states_in, inbox, t, node_ids, bits)
        states = jax.tree.map(
            lambda a, b: jnp.where(
                fire.reshape((n,) + (1,) * (b.ndim - 1)), b, a),
            st.states, new_states)
        new_wake = jnp.where(new_wake >= NEVER, NEVER,
                             jnp.maximum(new_wake, t + 1))  # contract #5
        wake = jnp.where(fire, new_wake, st.wake)
        out_valid = out.valid & fire[None, :]               # [M, N]
        out_pay = out.payload                                # [M, P, N]
        # never-silent contract: a valid send on a slot whose static_dst
        # is -1 has nowhere to go — counted (≙ JaxEngine's bad_dst);
        # and routing goes by the *declared* table, so a step emitting a
        # dst that disagrees with its declaration is counted too rather
        # than silently diverging from the oracle (which routes by dst)
        sd_local = comm.local_rows(
            np.asarray(sc.static_dst, np.int32).T)           # [M, N]
        declared = sd_local >= 0
        unrouted_step = jnp.sum(out_valid & ~declared, dtype=jnp.int32)
        misrouted_step = jnp.sum(
            out_valid & declared & (out.dst != sd_local), dtype=jnp.int32)

        stage("tw.rebase")
        # 5. rebase surviving queue entries to the new epoch t
        keep = q_live & ~deliver
        if purge is not None:
            keep = keep & ~purge
        q_rel = jnp.where(keep, st.q_rel - shift32, _I32MAX)
        q_step = st.q_step
        q_pay = st.q_pay

        stage("tw.route")
        # 6-7. route + enqueue, one static in-edge at a time — gathers
        # only on non-shift edges, never a scatter
        step32 = st.steps.astype(jnp.int32)
        overflow_step = jnp.int32(0)
        bad_delay_total = jnp.int32(0)
        sent_count = jnp.int32(0)
        sent_hash = jnp.uint32(0)
        for e in range(E):
            sh = topo.shift[e]
            if sh is not None:
                s, slot = sh
                with jax.named_scope("exchange"):
                    arr_v = comm.roll(out_valid[slot], s)
                    arr_p = comm.roll(out_pay[slot], s)      # [P, N]
                slot_e = jnp.int32(slot)
            else:
                flat_idx = jnp.asarray(topo.in_flat[e])
                arr_v = out_valid.reshape(-1)[flat_idx] \
                    & jnp.asarray(topo.in_valid[e])
                arr_p = out_pay.transpose(1, 0, 2).reshape(P, -1)[
                    :, flat_idx]
                slot_e = comm.local_rows(topo.in_slot[e])
            src_e = src_rows[e]
            mb = msg_bits(self.s0, self.s1, src_e, node_ids, t, slot_e) \
                if self.link.needs_key else None
            delay, drop = self.link.sample(src_e, node_ids, t, mb)
            ok = arr_v & ~drop
            if self._faulted:
                # same drop order as JaxEngine/oracle: partition cut
                # at the send instant, degradation on the sampled
                # delay, down-window check on the deliver time
                from ...faults.apply import (cut_mask, degrade,
                                             down_mask)
                cutm = ok & cut_mask(self._ft, src_e, node_ids, t)
                delay = degrade(self._ft, delay, src_e, node_ids, t)
                downm = (ok & ~cutm) & down_mask(
                    self._ft, node_ids,
                    t + jnp.maximum(delay, jnp.int64(1)))
                fault_step = fault_step + comm.all_sum(
                    jnp.sum(cutm | downm, dtype=jnp.int32))
                if rec_full:
                    # per-edge flight capture (obs/flight.py): the
                    # cut, then the sends with down-dropped ones
                    # re-tagged — the shared mixin helpers, at edge
                    # width
                    self._rec_cut(rec_full, cutm, src_e, node_ids, t)
                    self._rec_extra.append(self._rec_sends(
                        ok & ~cutm, downm, src_e, node_ids, t,
                        t + jnp.maximum(delay, jnp.int64(1))))
                ok = ok & ~cutm & ~downm
            elif rec_full:
                self._rec_extra.append(self._rec_sends(
                    ok, None, src_e, node_ids, t,
                    t + jnp.maximum(delay, jnp.int64(1))))
            drel64 = jnp.maximum(delay, jnp.int64(1))       # contract #4
            # queue times are int32-relative; a >= 2^31 µs delay cannot
            # be represented — clamp and count, never wrap silently
            bad_delay_step = jnp.sum(
                ok & (drel64 > jnp.int64(_I32MAX - 1)), dtype=jnp.int32)
            bad_delay_total = bad_delay_total + bad_delay_step
            drel = jnp.minimum(
                drel64, jnp.int64(_I32MAX - 1)).astype(jnp.int32)
            if with_trace:
                dt_abs = t + jnp.maximum(delay, jnp.int64(1))
                smix = mix32_jnp(SENT, src_e, node_ids, _tlo(dt_abs),
                                 _thi(dt_abs), arr_p[0])
                sent_hash = sent_hash + _u32sum(jnp.where(ok, smix, 0))
                sent_count = sent_count + jnp.sum(ok, dtype=jnp.int32)
            # first-free-slot one-hot insert over the static C axis
            free = q_rel[e] == _I32MAX                       # [C, N]
            cids = jnp.arange(C, dtype=jnp.int32)[:, None]
            ff = jnp.where(free, cids, C).min(axis=0)        # int32[N]
            ins = ok[None, :] & (cids == ff)                 # [C, N]
            q_rel = q_rel.at[e].set(
                jnp.where(ins, drel, q_rel[e]))
            if not sc.commutative_inbox:
                q_step = q_step.at[e].set(
                    jnp.where(ins, step32, q_step[e]))
            q_pay = q_pay.at[e].set(
                jnp.where(ins[:, None, :], arr_p[None, :, :], q_pay[e]))
            overflow_step = overflow_step + jnp.sum(
                ok & (ff == C), dtype=jnp.int32)

        stage("tw.finish")
        recv_count = comm.all_sum(jnp.sum(deliver, dtype=jnp.int32))
        overflow_step = comm.all_sum(overflow_step)
        new_st = EdgeState(
            states=states, wake=wake,
            q_rel=q_rel, q_step=q_step, q_pay=q_pay,
            overflow=st.overflow + overflow_step,
            unrouted=st.unrouted + comm.all_sum(unrouted_step),
            misrouted=st.misrouted + comm.all_sum(misrouted_step),
            bad_delay=st.bad_delay + comm.all_sum(bad_delay_total),
            delivered=st.delivered + recv_count.astype(jnp.int64),
            steps=st.steps + 1,
            time=t,
            fault_dropped=st.fault_dropped + fault_step,
            restart_done=restart_done,
        )
        if live is None:
            # the caller's loop has decided that this superstep runs
            return new_st, None
        final = jax.tree.map(lambda a, b: jnp.where(live, b, a), st, new_st)
        if not with_trace:
            return final, None

        # 8. trace digests (order-independent; computed pre-sort from the
        # deliver mask — identical to the sorted-inbox digest by
        # commutativity of the (wrapping) uint32 sum, which also makes
        # the cross-device psum exact)
        fired_hash = comm.all_sum(
            _u32sum(jnp.where(fire, mix32_jnp(FIRED, node_ids), 0)))
        d_abs = base + jnp.where(deliver, st.q_rel, 0).astype(jnp.int64)
        rsrc = (jnp.broadcast_to(src_rows[:, None, :], (E, C, n))
                if sc.inbox_src else jnp.zeros((E, C, n), jnp.int32))
        rmix = mix32_jnp(
            RECV, jnp.broadcast_to(node_ids, (E, C, n)),
            rsrc, _tlo(d_abs), _thi(d_abs), st.q_pay[:, :, 0, :])
        recv_hash = comm.all_sum(_u32sum(jnp.where(deliver, rmix, 0)))
        rec = None
        if self.record != "off" and with_trace:
            # the flight-recorder event plane (engine.py's twin):
            # deliveries node-major over the [E, C] queue axes, then
            # the capture buffers in superstep order
            from ...obs import flight as _flight
            d_src = (jnp.broadcast_to(src_rows[:, None, :],
                                      (E, C, n)).transpose(2, 0, 1)
                     if sc.inbox_src else jnp.int32(0))
            d_dst = jnp.broadcast_to(node_ids[None, None, :],
                                     (E, C, n)).transpose(2, 0, 1)
            if self.record == "deliveries":
                # slim fast path (engine.py's twin): one compaction,
                # constant planes elided
                rec = _flight.record_deliveries(
                    self.record_cap, deliver.transpose(2, 0, 1),
                    d_src, d_dst, st.q_rel.transpose(2, 0, 1),
                    t_off=base)
            else:
                row = _flight.record_masked(
                    _flight.empty_row(self.record_cap),
                    _flight.EV_DELIVER, deliver.transpose(2, 0, 1),
                    d_src, d_dst, jnp.int64(-1),
                    st.q_rel.transpose(2, 0, 1), 0, t_off=base)
                for comp in self._rec_extra:
                    row = _flight.record_compacted(row, comp)
                rec = row
        telem = None
        if self.telemetry != "off":
            telem = self._telemetry_row(wake, q_rel, t, out_valid,
                                        fault_step)
        integ = None
        if self.verify != "off":
            # the guard invariant plane — the JaxEngine twin
            # (integrity/checks.py; one shared implementation)
            from ...integrity.checks import make_guard_row
            integ = make_guard_row(
                comm, t, st.time,
                (new_st.overflow, new_st.unrouted, new_st.misrouted,
                 new_st.bad_delay, new_st.fault_dropped,
                 new_st.delivered, new_st.steps, new_st.time),
                wake, jnp.int64(NEVER), (q_rel,),
                st.restart_done, restart_done, self._faulted)
        yrow = _StepOut(
            valid=live, t=t,
            fired_count=comm.all_sum(jnp.sum(fire, dtype=jnp.int32)),
            fired_hash=fired_hash,
            recv_count=recv_count, recv_hash=recv_hash,
            sent_count=comm.all_sum(sent_count),
            sent_hash=comm.all_sum(sent_hash),
            overflow=overflow_step,
            telem=telem,
            integ=integ,
            rec=rec,
        )
        yrow = jax.tree.map(
            lambda x: jnp.where(live, x, jnp.zeros_like(x)), yrow)
        return final, yrow

    def _telemetry_row(self, wake, q_rel, t, out_valid, fault_step):
        """The edge engine's telemetry plane (obs/telemetry.py) —
        derived from the post-step wake, post-insert queues, and the
        step's outbox/fault values, so digests are bit-identical with
        telemetry on or off. No routing ladder here: rung is -1 and
        route_drop 0 by construction (per-edge losses are
        ``overflow``)."""
        from ...obs.telemetry import TelemetryRow
        comm = self.comm
        qmin = q_rel.min()
        nxt = comm.all_min(jnp.minimum(
            wake.min(),
            jnp.where(qmin < _I32MAX, t + qmin.astype(jnp.int64),
                      jnp.int64(NEVER))))
        row = TelemetryRow(
            active_senders=comm.all_sum(jnp.sum(
                jnp.any(out_valid, axis=0), dtype=jnp.int32)),
            rung=jnp.int32(-1),
            route_drop=jnp.int32(0),
            fault_dropped=fault_step,
            qslack_us=jnp.where(nxt >= NEVER, jnp.int64(-1), nxt - t),
        )
        if self.telemetry == "full":
            # queue occupancy: per-node fill over the [E, C] axes
            fill_node = jnp.sum(q_rel < _I32MAX, axis=(0, 1),
                                dtype=jnp.int32)                # [N]
            row = row._replace(
                mb_fill=comm.all_sum(jnp.sum(fill_node,
                                             dtype=jnp.int32)),
                mb_peak=comm.all_max(fill_node.max()))
        return row

    # -- drivers ---------------------------------------------------------

    def _next_event(self, carry: EdgeState) -> jax.Array:
        """This device's next event time (NEVER = quiesced): the probe
        of a state at rest (``world_active``, the controlled drivers
        between chunks). No driver's loop asks it: the quiet loop
        carries each state's horizon. Under a fault schedule it is
        the horizon's ``t``, so that a state quiet but for a restart
        still to come reads as active, as the supersteps see it."""
        if self._faulted:
            return self._horizon(carry).t
        return self._node_next(carry).min()

    #: the edge engine carries no world axis (batch=BatchSpec is the
    #: general engine's lever); the shared drivers key off this
    batch = None

    def world_active(self, state) -> jax.Array:
        """Liveness probe (JaxEngine.world_active's solo twin): True
        while an event is pending — the controller drivers
        (controlled.py) test it between chunks."""
        return self._next_event(state) < NEVER

    def _remote_deliveries(self, deliver, src_rows):
        """Messages delivered this superstep whose sender lives on
        another shard (``deliver`` bool [E, C, n]; ``src_rows`` int32
        [E, n], the sender on each in-edge). One device has no other
        shard and counts nothing: ``ShardedEdgeEngine`` overrides."""
        return None

    def _counted(self, st):
        """What the quiet loop carries of ``st``: here the state
        alone. A sharded driver carries each shard's count of
        boundary messages beside it."""
        return st

    def _settled(self, carry):
        """``(state, crossed)`` of what a driver carried or returned:
        here the state alone (``_counted``'s inverse)."""
        return carry, None

    def _step_all(self, st, with_trace: bool):
        """One driver step (the ShardedDriver/scan hook — the edge
        engine has no world axis, so this is always the solo step)."""
        return self._superstep(st, with_trace)

    def _step_carried(self, carry, hz):
        """The quiet loop's body on ``(_counted(state), horizon)``."""
        return self._superstep_carried(carry, hz)

    def _quiet_loop(self, st, max_steps):
        """The quiet driver's ``while``, the local ``_run_while``'s and
        (on one device's shard) ``ShardedDriver._run_while``'s, in the
        shape of ``JaxEngine._quiet_loop``: one scan for the state's
        horizon (under ``tw.next_event``, inside the same program),
        then the loop on ``(_counted(state), horizon)``. The
        condition is the carried next event time against NEVER and
        the step budget: two compares of scalars every device holds
        alike (``hz.t`` comes out of ``all_min``), no reduction and
        no collective at the loop's edge; the body runs only where
        the condition has just found ``hz.t`` pending, and selects
        nothing by it. The last horizon is dropped (a function of the
        state: ``EdgeState`` has no field for it, and the next call
        scans once again)."""
        start_steps = st.steps  # max_steps is per-call, same as run()
        with jax.named_scope("tw.next_event"):
            hz = self._horizon(st)

        def cond(carry):
            counted, hz = carry
            steps = self._settled(counted)[0].steps
            return (hz.t < NEVER) & (steps - start_steps < max_steps)

        return jax.lax.while_loop(
            cond, lambda carry: self._step_carried(*carry),
            (self._counted(st), hz))[0]

    @partial(jax.jit, static_argnums=(0, 2))
    def _run_scan(self, st: EdgeState, n_pad: int, max_steps):
        # pow2-padded scan length + masked tail, the shared
        # compile-reuse contract (common.py scan_pad/padded_scan)
        return padded_scan(self._step_all, st, n_pad, max_steps)

    def _warn_on_overflow(self, final: EdgeState) -> None:
        """Per-edge capacity (``cap``) is NOT the oracle's per-node
        ``mailbox_cap``: once anything overflows, which message is
        dropped legitimately differs, so a run with overflow > 0 is not
        trace-comparable to the oracle — said out loud, not silently. Use :class:`JaxEngine` when
        overflow-exact parity matters."""
        import warnings
        if int(final.overflow) > 0:
            warnings.warn(
                f"edge engine counted {int(final.overflow)} overflowed "
                "messages; per-edge capacity semantics diverge from the "
                "per-node-capacity oracle under overflow — raise cap=, "
                "or use the general JaxEngine for overflow-exact parity",
                RuntimeWarning, stacklevel=3)

    def run(self, max_steps: int,
            state: Optional[EdgeState] = None
            ) -> Tuple[EdgeState, SuperstepTrace]:
        # _pad_mult = 2 is the shadow verify mode's pow2-cache twin
        # (integrity/runner.py) — a distinct executable, same results
        with self._driver_call("run") as call:
            st = state if state is not None else self.init_state()
            carry, ys = call.dispatch(
                self._run_scan, st, scan_pad(max_steps) * self._pad_mult,
                jnp.asarray(max_steps, jnp.int64))
            final, crossed = self._settled(carry)
            ys, = call.wait(st.steps, final.steps, ys, crossed=crossed)
        self._capture_flight(ys, st)
        self._capture_integrity(ys)
        self.last_run_telemetry = None
        if self.telemetry != "off" and ys.telem is not None:
            from ...obs.telemetry import decode_frames
            self.last_run_telemetry = decode_frames(
                ys.telem, np.asarray(ys.valid), np.asarray(ys.t))
            if self.metrics is not None:
                self.metrics.superstep_chunk(self.metrics_label,
                                             self.last_run_telemetry)
        self._warn_on_overflow(final)
        m = np.asarray(ys.valid)
        rows = list(zip(
            np.asarray(ys.t)[m], np.asarray(ys.fired_count)[m],
            np.asarray(ys.fired_hash)[m], np.asarray(ys.recv_count)[m],
            np.asarray(ys.recv_hash)[m], np.asarray(ys.sent_count)[m],
            np.asarray(ys.sent_hash)[m], np.asarray(ys.overflow)[m]))
        return final, SuperstepTrace.from_rows(rows)

    @partial(jax.jit, static_argnums=(0,))
    def _run_while(self, st: EdgeState, max_steps) -> EdgeState:
        return self._quiet_loop(st, jnp.asarray(max_steps, jnp.int64))

    def run_quiet(self, max_steps: int,
                  state: Optional[EdgeState] = None) -> EdgeState:
        """Traceless driver: one ``while_loop``, digests, counts, and
        telemetry planes not even compiled in."""
        with self._driver_call("run_quiet") as call:
            st = state if state is not None else self.init_state()
            final, crossed = self._settled(
                call.dispatch(self._run_while, st, max_steps))
            call.wait(st.steps, final.steps, crossed=crossed)
            if self.verify != "off":
                # never silently unverified (JaxEngine.run_quiet twin)
                from ...integrity.checks import final_state_guard
                with call.guard():
                    final_state_guard(final, type(self).__name__)
        return final
