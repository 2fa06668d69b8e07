"""Sharded engines: the same superstep semantics over a device mesh.

The mesh/collective layer itself (MeshComm, ShardedDriver, make_mesh)
lives in :mod:`timewarp_tpu.parallel`; this module binds it to the two
engines:

- :class:`ShardedEdgeEngine` — the edge engine (edge_engine.py) under
  ``shard_map`` with the node axis sharded; ring delivery is a
  boundary-slice ``ppermute`` (one ICI neighbor hop per superstep),
  requiring a pure-shift topology.
- :class:`ShardedEngine` — the general engine (engine.py) with its
  exchange stage replaced by destination-shard bucketing + one
  ``lax.all_to_all`` per superstep.

The acceptance law is unchanged: an 8-device run must reproduce the
1-device trace **bit-for-bit** (tests/test_sharded.py runs both
engines on a virtual 8-device CPU mesh against the 1-device engines
and the host oracle).
"""

from __future__ import annotations

from typing import Optional

from ...utils import jaxconfig  # noqa: F401

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ...core.scenario import Scenario
from ...net.delays import LinkModel
from ...parallel.mesh import (AxisName, Mesh, MeshComm,
                              ShardedDriver, axis_size, make_mesh)
from .batched import BatchSpec
from .edge_engine import EdgeEngine, EdgeState
from .engine import EngineState, JaxEngine

__all__ = ["MeshComm", "ShardedBatchedEngine", "ShardedEdgeEngine",
           "ShardedEngine", "make_mesh"]


def _refuse_record(record: str, who: str) -> str:
    """The node-sharded engines distribute each superstep's events
    across the mesh; the flight recorder's per-superstep event plane
    is a single-host debug artifact (like the device event ring).
    Refused loudly — a 1-device run of the same config records the
    identical events by the sharding exactness law (docs/engines.md).
    The WORLD-sharded engine records fine (each world's nodes are
    device-local) and does not route through this guard."""
    if record != "off":
        raise ValueError(
            f"{who}: record={record!r} is unsupported on the "
            "node-sharded engines (events would be scattered across "
            "shards); run the config on 1 device — bit-identical by "
            "the sharding exactness law — or use ShardedBatchedEngine "
            "for recorded fleets (docs/observability.md)")
    return record


class ShardedEdgeEngine(ShardedDriver, EdgeEngine):
    """Edge engine over a mesh: node axis sharded, ring delivery on
    ``ppermute``. Same ``run`` / ``run_quiet`` API as the local engine."""

    def __init__(self, scenario: Scenario, link: LinkModel,
                 mesh: Mesh, *, axis: AxisName = "nodes", seed: int = 0,
                 cap: int = 2, lint: str = "warn",
                 telemetry: str = "off", verify: str = "off",
                 record: str = "off") -> None:
        _refuse_record(record, type(self).__name__)
        super().__init__(scenario, link, seed=seed, cap=cap, lint=lint,
                         telemetry=telemetry, verify=verify)
        bad = [e for e, s in enumerate(self.topo.shift) if s is None]
        if bad:
            raise ValueError(
                f"edges {bad} are not pure shifts; the sharded edge "
                "engine delivers by ppermute only — irregular "
                "topologies need the all_to_all general sharded engine")
        self.mesh = mesh
        self.axis = axis
        D = axis_size(mesh, axis)
        self.comm = MeshComm(axis, scenario.n_nodes, D)

    # -- the boundary count, beside the state ----------------------------

    def _remote_deliveries(self, deliver, src_rows):
        # a sum on this shard alone: no collective joins the superstep
        here = jax.lax.axis_index(self.axis).astype(jnp.int32)
        remote = src_rows // jnp.int32(self.comm.n_local) != here
        return jnp.sum(deliver & remote[:, None, :], dtype=jnp.int32)

    def _counted(self, st):
        """What a driver's loop carries: this shard's state and,
        beside it, its count of boundary messages from zero, one row
        of the ``[shards]`` the call reads back and sums
        (``last_run_stats`` ``boundary_msgs``)."""
        return st, jnp.zeros((1,), jnp.int64)

    def _step_counted(self, carry, with_trace: bool):
        st, crossed = carry
        new, y = self._step_all(st, with_trace)
        return (new, crossed + self._crossed.astype(jnp.int64)), y

    def _step_carried(self, carry, hz):
        """``EdgeEngine._quiet_loop``'s body on this device's shard:
        the carried superstep on the counted carry. The horizon it
        returns holds the next event time all devices agree on
        (``all_min`` where it is produced), which is all the loop's
        condition reads."""
        st, crossed = carry
        new, hz = self._superstep_carried(st, hz)
        return (new, crossed + self._crossed.astype(jnp.int64)), hz

    def _settled(self, carry):
        return carry

    # -- sharding specs --------------------------------------------------

    def _carry_specs(self, st, specs):
        return specs, P(self.axis)

    def _state_specs(self, st: EdgeState) -> EdgeState:
        leaf = self._leaf_spec
        return EdgeState(
            states=jax.tree.map(lambda x: leaf(x, False), st.states),
            wake=P(self.axis),
            q_rel=leaf(st.q_rel, True),
            q_step=leaf(st.q_step, True),
            q_pay=leaf(st.q_pay, True),
            overflow=P(), unrouted=P(), misrouted=P(), bad_delay=P(),
            delivered=P(), steps=P(), time=P(),
            fault_dropped=P(), restart_done=P(),
        )


class ShardedEngine(ShardedDriver, JaxEngine):
    """General (dynamic-destination) engine over a mesh: node axis
    sharded, message exchange via destination-shard bucketing + one
    ``lax.all_to_all`` per superstep (SURVEY.md §5.8's general-topology
    delivery — the TPU-native replacement for the reference's per-peer
    TCP sockets, `Transfer.hs:473,577`).

    Each device buckets its outgoing messages by destination shard
    (keyed on shard only, so in-bucket order is slot-major and
    *irrelevant*), the buckets swap in one collective, and contract
    #3's arrival order is restored downstream by the insertion sort on
    the global sender-major rank (``smrank``) that rides along with
    every message — exchange order never matters. Bucket
    capacity ``bucket_cap`` defaults to this device's total outbox
    width (``n_local * max_out``), which cannot overflow — bit-for-bit
    parity by construction; tune it down to shrink the exchange volume
    (≤ the true per-shard fan-in) and any overflow is counted in
    ``EngineState.overflow``, never silent. At the default every
    device receives ``D * n_local * max_out`` lanes a superstep, the
    whole world's outbox width, and sorts and inserts them all.

    After the sort by shard a bucket is a contiguous run of the sorted
    lanes, so each plane's ``[D, bucket_cap]`` buffer is ``D`` slices
    of the sorted plane, blank from each run's end: no scatter, no
    gather, and the first ``bucket_cap`` of a run are the ones that
    fit. Under ``tw.route/exchange`` the sort, the runs' counts and
    the slices carry the scope ``bucket``, the ``all_to_all``s
    ``swap``. ``last_run_stats`` of every call holds ``shards``,
    ``remote_msgs``, ``bucket_fill_peak`` (counted before the cut, so
    a value over ``bucket_cap`` says by how much it was short),
    ``bucket_cap`` and ``exchange_lanes`` (``D * bucket_cap``): a
    device counts its own beside the state and the call's one readback
    brings them (common.py ``RunStatsMixin``).
    """

    def __init__(self, scenario: Scenario, link: LinkModel,
                 mesh: Mesh, *, axis: AxisName = "nodes", seed: int = 0,
                 bucket_cap: Optional[int] = None,
                 window: int = 1,
                 lint: str = "warn", telemetry: str = "off",
                 verify: str = "off", record: str = "off") -> None:
        _refuse_record(record, type(self).__name__)
        super().__init__(scenario, link, seed=seed, window=window,
                         lint=lint, telemetry=telemetry, verify=verify)
        self.mesh = mesh
        self.axis = axis
        D = axis_size(mesh, axis)
        self.comm = MeshComm(axis, scenario.n_nodes, D)
        full = self.comm.n_local * scenario.max_out
        self.bucket_cap = full if bucket_cap is None else min(
            bucket_cap, full)

    # -- the all_to_all exchange -----------------------------------------

    @jax.named_scope("exchange")
    def _exchange(self, ok, drel, src_f, dst_f, smrank, woff, pay_cols):
        comm = self.comm
        D, nl, B = comm.n_shards, comm.n_local, self.bucket_cap
        here = jax.lax.axis_index(self.axis).astype(jnp.int32)
        with jax.named_scope("bucket"):
            # destination shard of each message; invalid -> sentinel D.
            # One variadic sort groups messages by shard with all
            # values riding along (no argsort + gather chain);
            # in-bucket order is irrelevant — insertion downstream
            # sorts on (woff, smrank).
            dshard = jnp.where(ok, dst_f // jnp.int32(nl), jnp.int32(D))
            ops = jax.lax.sort(
                (dshard, drel, src_f, dst_f, smrank, woff) + pay_cols,
                dimension=0, num_keys=1)
            # after the sort a shard's lanes are one contiguous run
            # (``start`` lanes sort below it) and a lane's offset in
            # the run is its column in the bucket, so row ``d`` of a
            # plane's buffer is a slice of the sorted plane from
            # ``start[d]``, blank from the run's end: the first ``B``
            # of a run fit (the sort is stable), the rest overflow
            count = jnp.sum(
                dshard == jnp.arange(D, dtype=jnp.int32)[:, None],
                axis=1, dtype=jnp.int32)
            start = jnp.cumsum(count, dtype=jnp.int32) - count
            live = (jnp.arange(B, dtype=jnp.int32)
                    < jnp.minimum(count, B)[:, None])

            def rows(x):
                # ``B`` blank lanes behind the plane: a slice from any
                # ``start <= len(x)`` lies inside, so dynamic_slice
                # never clamps its start and shifts a run. One gather
                # of ``D`` slices, so the program does not grow with
                # the mesh (on four v5e ``D`` unrolled slices are 65 us
                # a superstep cheaper: PERF.md, Findings PR 50)
                x = jnp.pad(x, (0, B))
                return jnp.where(live, jax.vmap(
                    lambda s: jax.lax.dynamic_slice(x, (s,), (B,)))(
                        start), 0)

            b_ok = live.astype(jnp.int8)
            bufs = [b_ok] + [rows(x) for x in ops[1:]]
            # what this shard hands the exchange, for the drivers'
            # counts (``_count_route``): the messages that leave it
            # and its fullest bucket before the cut at ``B``. Its own
            # lanes only: no collective joins the superstep for them
            self._exchanged = (
                jnp.sum(count, dtype=jnp.int32) - count[here],
                jnp.max(count))
        bucket_ovf = comm.all_sum(
            jnp.sum(jnp.maximum(count - B, 0), dtype=jnp.int32))

        with jax.named_scope("swap"):
            def a2a(x):
                return jax.lax.all_to_all(
                    x, self.axis, split_axis=0,
                    concat_axis=0).reshape(D * B)

            r_ok = a2a(b_ok).astype(bool)
            r_drel, r_src, r_dst, r_smrank, r_woff = (
                a2a(b) for b in bufs[1:6])
            r_pay = tuple(a2a(b) for b in bufs[6:])
        # received rows are local: subtract this shard's node offset
        return (r_ok, r_drel, r_src, r_dst - here * jnp.int32(nl),
                r_smrank, r_woff, r_pay, bucket_ovf)

    # -- what crossed, beside the state ------------------------------------

    def _counted(self, st):
        """``JaxEngine._counted`` and, beside the routing counts, this
        shard's own two of the exchange from zero: one row each of the
        ``[shards]`` the call reads back (``last_run_stats``
        ``remote_msgs``, ``bucket_fill_peak``)."""
        st, counts = super()._counted(st)
        return st, counts._replace(
            remote_msgs=jnp.zeros((1,), jnp.int64),
            bucket_fill_peak=jnp.zeros((1,), jnp.int32))

    def _count_route(self, counts, stepped=True, world_stepped=True):
        remote, fill = self._exchanged
        return super()._count_route(counts, stepped)._replace(
            remote_msgs=counts.remote_msgs + jnp.where(
                stepped, remote, 0).astype(jnp.int64),
            bucket_fill_peak=jnp.maximum(
                counts.bucket_fill_peak, jnp.where(stepped, fill, 0)))

    def _carry_specs(self, st, specs):
        specs, counts = super()._carry_specs(st, specs)
        return specs, counts._replace(remote_msgs=P(self.axis),
                                      bucket_fill_peak=P(self.axis))

    # -- sharding specs --------------------------------------------------

    def _state_specs(self, st: EngineState) -> EngineState:
        leaf = self._leaf_spec
        return EngineState(
            states=jax.tree.map(lambda x: leaf(x, False), st.states),
            wake=P(self.axis),
            mb_rel=leaf(st.mb_rel, True),
            mb_src=leaf(st.mb_src, True),
            mb_payload=leaf(st.mb_payload, True),
            overflow=P(), bad_dst=P(), bad_delay=P(), short_delay=P(),
            route_drop=P(),
            delivered=P(), steps=P(), time=P(),
            # the event ring is a single-chip debug artifact
            # (record_events=0 sharded: zero-size, replicated)
            ev_time=P(), ev_meta=P(), ev_count=P(),
            # faults are the local/world-sharded engines' lever; the
            # node-sharded engine carries the (empty) leaves replicated
            fault_dropped=P(), restart_done=P(),
        )


class ShardedBatchedEngine(ShardedDriver, JaxEngine):
    """The fleet over a mesh: the **world axis** sharded, nodes
    device-local. Each device runs ``B / D`` complete worlds — the
    embarrassingly-parallel layout the replica-sweep workload wants
    (worlds are independent, so NO driver holds a collective: the
    quiet loop runs while a world of THIS device is active, each
    device its own trip count, and the devices meet once a call, at
    its readback). Contrast :class:`ShardedEngine`, which shards the
    *node* axis of one world and pays an ``all_to_all`` a superstep.
    A world axis needs no agreement whatever mesh axes it is
    composed with.

    The batch exactness law is unchanged: world b sliced out of the
    gathered state is bit-identical to the solo run with that world's
    seed/link (tests/test_world_batch.py runs this on the virtual
    8-device CPU mesh)."""

    def __init__(self, scenario: Scenario, link: LinkModel,
                 mesh: Mesh, *, batch: BatchSpec,
                 axis: AxisName = "worlds", seed: int = 0,
                 window=1, lint: str = "warn", faults=None,
                 telemetry: str = "off", controller=None,
                 verify: str = "off", record: str = "off",
                 record_cap=None, speculate: str = "off") -> None:
        # the flight recorder works here: worlds are whole per device
        # (comm stays LocalComm), and the per-world [T, B_local, R]
        # event planes gather over the world axis like any trace leaf
        # — and so does the speculation plane (speculate/): worlds
        # are device-local, so the violation decode sees the gathered
        # [T, B] columns exactly like the single-chip fleet's
        super().__init__(scenario, link, seed=seed, window=window,
                         lint=lint, batch=batch, faults=faults,
                         telemetry=telemetry,
                         controller=controller, verify=verify,
                         record=record, record_cap=record_cap,
                         speculate=speculate)
        if batch is None:
            raise ValueError(
                "ShardedBatchedEngine shards the world axis; it needs "
                "a BatchSpec (for a single sharded world use "
                "ShardedEngine)")
        self.mesh = mesh
        self.axis = axis
        D = axis_size(mesh, axis)
        if batch.B % D:
            raise ValueError(
                f"batch of {batch.B} worlds not divisible over "
                f"{D} devices (worlds are whole — pad the seed list "
                "or shrink the mesh)")
        #: worlds resident per device
        self.worlds_local = batch.B // D
        # comm stays LocalComm: every world's nodes live on one device

    # -- world-axis sharding ---------------------------------------------

    def _state_specs(self, st: EngineState) -> EngineState:
        # uniform rule: every leaf's LEADING axis is the world axis.
        # Written without the trailing ``None``s, the form in which
        # JAX names a sharding it reads back from a compiled program:
        # a driver returned the mailbox planes as ``P(ax)`` where
        # ``init_state`` had placed them as ``P(ax, None, None)``, the
        # same layout under another name, and a fleet streamed in
        # calls compiled its second call anew (PERF.md, PR 46)
        return jax.tree.map(lambda x: P(self.axis), st)

    def _trace_spec(self) -> P:
        # scan-trace leaves are [T, B_local] per device: gather the
        # world axis, not the (nonexistent) replication
        return P(None, self.axis)

    def _world_context(self):
        # this device's slice of the world context (seed words + link
        # parameter vectors + fault tables): the identity arrives as
        # the driver-bound replicated operand (engine.py WorldIdentity
        # — traced, never a closure constant, so an identity swap is
        # zero-recompile here too), sliced by mesh position — the
        # same pattern as MeshComm.local_rows
        s0v, s1v, lpv, ftv = super()._world_context()
        Bl = self.worlds_local
        off = jax.lax.axis_index(self.axis).astype(jnp.int32) \
            * jnp.int32(Bl)
        def sl(v):
            return jax.lax.dynamic_slice_in_dim(v, off, Bl, axis=0)
        return (sl(s0v), sl(s1v), {k: sl(v) for k, v in lpv.items()},
                None if ftv is None else jax.tree.map(sl, ftv))
